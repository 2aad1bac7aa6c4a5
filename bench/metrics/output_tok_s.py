"""Output tokens the host received in the window, per second of window."""


def read(run):
    return run.win.tokens / run.win.seconds
