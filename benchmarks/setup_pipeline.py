"""Set-up as the program itself accounts for it: the seconds its constructors
spent in their set-up stages, and the seconds jax spent tracing, lowering and
loading (compiling, or reading from the persistent cache) its programs.

``rapid_tpu/utils/engine_telemetry.py`` keeps both as exact sums for the whole
process: ``compile_snapshot()["pipeline_s"]`` by stage of the pipeline and
``setup_snapshot()["outermost"]`` for the constructors' outermost stage blocks
(a stage inside another is inside its parent's seconds and not added again).
The readers call them after the window, as ``harness.py`` does for
``warmup_programs``, and report the process totals. Those are the set-up's
figures because the run's check holds ``compiles_in_window`` to 0 and no
generator builds a target inside its window: every pipeline event and every
stage block of a ``correct`` run happened before the window opened.

A program that keeps no such sums (the parent of the PR that brought them)
reads nothing, and the result line leaves the metric out.
"""


def pipeline_seconds(stage):
    from rapid_tpu.utils import engine_telemetry

    return engine_telemetry.compile_snapshot().get("pipeline_s", {}).get(stage)


def create_seconds():
    from rapid_tpu.utils import engine_telemetry

    if not hasattr(engine_telemetry, "setup_snapshot"):
        return None
    return engine_telemetry.setup_snapshot().get("outermost", {}).get("wall_s")
