"""Scheduler: 95th percentile of due arrival -> batch launch, in ms, over
the requests completed in the window (host clock)."""


def read(run):
    return run.window.wait_ms(95)
