"""How many of the window's dispatches took more than twice their phase's
median milliseconds a round, and more than a millisecond over it. The worst
are printed above the result line, in the order they ran, with the journal's
evidence: programs loaded and seconds of garbage collection while each was
open, rounds, bytes fetched."""
from benchmarks import journal

#: Lines printed at most: the end of a run's output is what a caller keeps.
PRINTED = 12


def read(run):
    found = journal.window(run)
    if found is None:
        return None
    slow = journal.slow(found)
    worst = sorted(slow, key=lambda item: item[2] - item[1])[:PRINTED]
    for row, a_round, median in sorted(worst, key=lambda item: item[0]["t_start"]):
        print(journal.describe(found, row, a_round, median))
    if len(slow) > PRINTED:
        print(f"slow dispatches: {len(slow) - PRINTED} more, each less over its median than these")
    return len(slow)
