"""Share of the engine's slots that held a request, averaged over the
window's steps (scheduler state after each step)."""
import numpy as np


def read(run):
    o = run.win.occupancy
    return float(np.mean(o)) * 100.0 if o else None
