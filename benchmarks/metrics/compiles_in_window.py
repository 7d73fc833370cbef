"""Programs made inside the window (``engine_telemetry.compile_snapshot`` before and after): has
to be 0, and the run's check holds it to that."""


def read(run):
    return run["compiles_in_window"]
