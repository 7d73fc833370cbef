"""Median, over the window's membership changes, of the host time inside a
change that no dispatch phase covers: ``t_close - t_open`` less the union of
its dispatches' intervals (the caller's glue between the driver's calls, and
any eager work outside a block)."""
import numpy as np

from benchmarks import journal


def read(run):
    found = journal.window(run)
    if found is None or not len(found["changes"]):
        return None
    return float(np.median(journal.unphased_ms(found)))
