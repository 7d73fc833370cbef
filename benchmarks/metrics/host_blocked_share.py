"""Share of the window the host spent waiting for the device inside the
program's dispatch phases that fetch (``utils/dispatch.py`` histograms)."""
from benchmarks.targets import BLOCKING_PHASES


def read(run):
    before, after = run["counters_before"]["dispatch_ms"], run["counters_after"]["dispatch_ms"]
    blocked_ms = sum(after.get(p, 0.0) - before.get(p, 0.0) for p in BLOCKING_PHASES)
    return 100.0 * blocked_ms / (run["window_s"] * 1e3)
