"""Median time a membership change was pending, from the journal's change
rows (``t_close - t_open``): a commit seen from inside the program; on a
stream, a wave's latency from ``submit`` to its retirement."""
import numpy as np

from benchmarks import journal


def read(run):
    found = journal.window(run)
    if found is None or not len(found["changes"]):
        return None
    return float(np.median(journal.change_ms(found)))
