"""Host time a wave spent enqueueing: its rounds (``stream_enqueue``) and the
injection before them (``inject_crash``, ``inject_join_place``). None of it
waits for the device."""
from benchmarks.phase_ms import per_step


def read(run):
    return per_step(run, ("stream_enqueue", "inject_crash", "inject_join_place"), needs="inject_crash")
