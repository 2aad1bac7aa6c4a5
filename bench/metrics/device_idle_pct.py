"""Share of the traced window in which no operation ran on the device."""


def read(run):
    r = run.reduction
    return r.idle_share * 100.0 if r and r.window_s > 0 else None
