"""Programs made before the window opened, compiled or loaded from the
persistent cache (``engine_telemetry.compile_snapshot``)."""


def read(run):
    return run["warmup_programs"]
