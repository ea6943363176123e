"""Device-idle time inside ``engine.step`` per batch the window retired,
in ms: the host work of staging, launching and retiring that the device
waits for."""


def read(m):
    batches = sum(m.batches.values())
    if m.trace is None or not batches:
        return None
    return m.trace.idle_in_step_s / batches * 1e3
