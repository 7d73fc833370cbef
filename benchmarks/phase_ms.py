"""Host milliseconds a step spent inside dispatch phases of the program.

``run["counters_before"/"counters_after"]["dispatch_ms"]`` snapshot the exact
time sum of every phase of the ``engine_dispatch`` histogram family
(``rapid_tpu/utils/dispatch.py``), whatever its name. A reader names the
phases it adds up; the step count is the window's commits or, for a stream,
its waves. A phase that took no sample in a window reads 0. A program that
has no such phase at all (``needs`` names one the cell always runs) reads
nothing, and the result line leaves the metric out.
"""


def per_step(run, phases, needs):
    before, after = run["counters_before"]["dispatch_ms"], run["counters_after"]["dispatch_ms"]
    steps = len(run.get("commit_ms") or ()) or run["attempted"]
    if needs not in after or not steps:
        return None
    return sum(after.get(phase, 0.0) - before.get(phase, 0.0) for phase in phases) / steps
