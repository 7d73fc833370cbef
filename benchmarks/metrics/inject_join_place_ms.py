"""Host time of placing a join wave per commit: ``predecessor_of_keys``, the
three scatters and the fired-edge stamps, all enqueued without a fetch (the
``inject_join_place`` span)."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("inject_join_place",), needs="inject_join_place")
