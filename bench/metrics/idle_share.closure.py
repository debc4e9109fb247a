"""Share of the traced closure window in which no op ran on the device,
in percent (device layer): 1 - busy / window, from the trace."""


def read(r):
    if r.trace is None or not r.trace.ops or r.trace.window_s() <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s() / r.trace.window_s())
