"""Set-up: process start to the window (build, compile or cache load,
warm-up traffic)."""


def read(run):
    return run["setup_s"]
