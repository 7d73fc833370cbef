"""Median time from injection to the fleet's fetched decisions, without the
restore and the check that the throughput of the cell includes."""
import statistics


def read(run):
    if run["config"]["deployment"] != "fleet" or not run.get("commit_ms"):
        return None
    return statistics.median(run["commit_ms"])
