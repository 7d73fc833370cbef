"""Bytes the driver fetched from the device per commit (``_account_d2h``).
The benchmark's own check reads the state past that counter."""


def read(run):
    if not run.get("commit_ms"):
        return None
    moved = run["counters_after"]["d2h_bytes"] - run["counters_before"]["d2h_bytes"]
    return moved / len(run["commit_ms"])
