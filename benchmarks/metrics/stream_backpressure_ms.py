"""Host time a wave's submit waited for the queue: the ``stream_fetch`` span
that blocks on the oldest wave in flight (and the drain's fetches), a wave."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("stream_fetch",), needs="stream_fetch")
