"""Share of the window the host spent WAITING for the device: the sum of
``t_end - t_wait`` over the window's journal rows that waited, over the window.
The wait alone; ``host_blocked_share`` beside it also holds the fetching
phases' own host work before the wait."""
from benchmarks import journal


def read(run):
    found = journal.window(run)
    if found is None:
        return None
    rows = journal.waiting(found["dispatches"])
    return 100.0 * float((rows["t_end"] - rows["t_wait"]).sum()) / run["window_s"]
