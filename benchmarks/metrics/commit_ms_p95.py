"""95th percentile of the same commit times (linear interpolation)."""
import numpy as np


def read(run):
    return float(np.percentile(run["commit_ms"], 95)) if run.get("commit_ms") else None
