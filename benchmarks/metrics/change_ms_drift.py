"""How far the time a membership change is pending settles inside a window:
the median ``t_close - t_open`` of the first quarter of the window's changes
less that of its last quarter (0 where the window holds fewer than 40). Above
the result line it prints the four quarters' medians and, beside each, the
medians of the changes' summed enqueue part and summed wait
(``journal.split_ms``): whether a slow start is the host's or the device's."""
from benchmarks import journal

#: Changes a window must hold for its quarters' medians to mean something.
ENOUGH = 40


def read(run):
    found = journal.window(run)
    if found is None or not len(found["changes"]):
        return None
    if len(found["changes"]) < ENOUGH:
        return 0.0
    pending = journal.quarters(journal.change_ms(found))
    enqueue, wait = (journal.quarters(part) for part in journal.split_ms(found))
    print("change_ms by quarter of the window, median ms (enqueue, wait): " + " | ".join(
        f"{p:.3f} ({e:.3f}, {w:.3f})" for p, e, w in zip(pending, enqueue, wait)))
    return pending[0] - pending[-1]
