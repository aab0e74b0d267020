"""Median time to first token of the requests due in the window, from the
time each was due."""
import numpy as np


def read(run):
    if not run.ttft:
        return None
    return float(np.percentile(np.asarray(run.ttft), 50)) * 1e3
