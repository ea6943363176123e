"""Share of the traced window in which no op ran on the device, in %."""


def read(m):
    if m.trace is None or m.trace.window_s <= 0:
        return None
    return 100.0 * m.trace.idle_share
