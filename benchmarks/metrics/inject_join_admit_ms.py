"""Host time of the join wave's admissibility check per commit: the gather
and the blocking fetch of its [j] bools (the ``inject_join_admit`` span)."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("inject_join_admit",), needs="inject_join_admit")
