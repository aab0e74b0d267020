"""Median gap between consecutive output tokens of one request, at the
router's journal commit, over every gap that closed in the window."""
import numpy as np


def read(run):
    return float(np.percentile(run.itl, 50)) * 1e3 if run.itl else None
