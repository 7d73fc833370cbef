"""Host time of setting the partition per commit: bounds checks, one upload of
the deaf cohorts and the unheard senders, one placement program and the
re-stamp of the fired edges, enqueued without a fetch (the program's
``inject_partition`` span; the ``sync`` after it waits for the scatter)."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("inject_partition",), needs="inject_partition")
