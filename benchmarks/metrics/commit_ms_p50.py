"""Median commit time of the window's closed-loop steps (host clock)."""
import statistics


def read(run):
    return statistics.median(run["commit_ms"]) if run.get("commit_ms") else None
