"""Host milliseconds a step between the opening of a fetching phase and the
moment its wait began (``t_wait - t_start`` of the window's journal rows that
waited): the jitted call's argument handling and the eager packing of the
observation, work the device may sit idle through."""
from benchmarks import journal


def read(run):
    found = journal.window(run)
    if found is None or not journal.steps(run):
        return None
    rows = journal.waiting(found["dispatches"])
    return 1e3 * float((rows["t_wait"] - rows["t_start"]).sum()) / journal.steps(run)
