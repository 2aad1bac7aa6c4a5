"""Seconds from the process start until the window could open."""


def read(run):
    return run.setup_s
