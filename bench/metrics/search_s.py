"""Set-up: seconds of the TetrisG-SDK mapping search (``core.map_net``,
through the mapping disk cache)."""


def read(run):
    return run.setup["search_s"]
