"""99th percentile of the gaps between consecutive output tokens of one
request, at the router's journal commit, over every gap that closed in
the window: the stall a running stream sees when a newcomer is admitted."""
import numpy as np


def read(run):
    return float(np.percentile(run.itl, 99)) * 1e3 if run.itl else None
