"""Host time of setting the link-fault lane per commit: bounds check, index
upload and one placement program, enqueued without a fetch (the program's
``inject_link_faults`` span; the ``sync`` after it waits for the scatter)."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("inject_link_faults",), needs="inject_link_faults")
