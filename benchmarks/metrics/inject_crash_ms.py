"""Host time of crash injection per commit: bounds check, index upload and
scatter enqueue (the program's ``inject_crash`` span; it does not block)."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("inject_crash",), needs="inject_crash")
