"""The window's slowest membership change (``t_close - t_open`` of the
journal's change rows): what a median does not show."""
from benchmarks import journal


def read(run):
    found = journal.window(run)
    if found is None or not len(found["changes"]):
        return None
    return float(journal.change_ms(found).max())
