"""Committed view changes, as the fetched counters show them, over the
window's seconds: all the work and all the time of the window."""


def read(run):
    return run["view_changes"] / run["window_s"]
