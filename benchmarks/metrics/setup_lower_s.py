"""Seconds of set-up spent lowering the traced programs to MLIR modules
(``pipeline_s["lower"]``)."""
from benchmarks.setup_pipeline import pipeline_seconds


def read(run):
    return pipeline_seconds("lower")
