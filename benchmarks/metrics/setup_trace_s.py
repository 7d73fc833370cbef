"""Seconds of set-up spent tracing the programs to jaxprs, a jit traced inside
another counted once, with its caller (``pipeline_s["trace"]``)."""
from benchmarks.setup_pipeline import pipeline_seconds


def read(run):
    return pipeline_seconds("trace")
