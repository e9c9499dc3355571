"""Share of the traced round in which the device ran no operation: 1 minus
the union of the op intervals over the traced window."""


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    busy = run.trace.busy_s()
    return None if not busy else 100.0 * (1.0 - busy / run.trace.window_s)
