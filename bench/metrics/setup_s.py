"""Set-up time: process start to the window's start, in seconds (host clock)."""


def read(run):
    return run.setup_s
