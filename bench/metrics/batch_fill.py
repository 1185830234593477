"""Scheduler: request images / padded tier images over the window's
batches (the ladder's padding waste)."""


def read(run):
    return run.window.batch_fill
