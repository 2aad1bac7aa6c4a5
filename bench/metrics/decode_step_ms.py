"""Device time per execution of the decode program in the trace, in ms."""
import numpy as np


def read(run):
    d = run.reduction.programs.get("serve_step") if run.reduction else None
    return float(np.mean(d)) * 1e3 if d else None
