"""Device: 1 - (union of device op intervals) / traced window."""


def read(run):
    return None if run.trace is None else run.trace.idle_share
