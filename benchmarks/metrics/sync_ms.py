"""Host time of ``sync`` per commit: the checksum barrier that waits for the
injection's device work (the ``sync`` span; it blocks)."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("sync",), needs="sync")
