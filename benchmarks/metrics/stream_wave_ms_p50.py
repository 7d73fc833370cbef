"""Median time between the returns of consecutive ``submit`` calls: under
the stream driver's backpressure, the period of one wave. Raw samples kept
by the benchmark (the program's log-bucket histograms quantise)."""
import statistics


def read(run):
    return statistics.median(run["wave_ms"]) if run.get("wave_ms") else None
