"""Device time of the ops that are not Pallas kernels (FC dots, pads,
copies, glue) per image served in the window, in ms."""


def read(m):
    if m.trace is None or not m.served_in_window:
        return None
    return m.trace.xla_s / m.served_in_window * 1e3
