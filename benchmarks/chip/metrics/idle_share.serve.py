"""idle_share.serve: 1 - device busy / traced window, from the profiler's
trace, averaged over the cell's chips (serving cells)."""


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * rec.trace.idle_share
