"""Device-busy time per engine round of the traced window: everything the
device did (rounds, view changes, injection scatters, restores) over the
rounds the drivers reported."""


def read(run):
    if "trace" not in run or not run["rounds"]:
        return None
    return run["trace"]["busy_s"] * 1e6 / run["rounds"]
