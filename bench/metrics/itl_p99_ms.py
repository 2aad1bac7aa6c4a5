"""99th percentile of every gap between consecutive output tokens of
every request in the window, as the host received them, in ms."""
import numpy as np


def read(run):
    g = run.win.gaps
    return float(np.percentile(g, 99)) * 1e3 if g else None
