"""Set-up: process start to the window (weights, compile or cache load,
warm-up request), by the host clock."""


def read(run):
    return run.setup_s
