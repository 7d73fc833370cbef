"""Engine rounds a commit took, as the driver's fetch reports them."""


def read(run):
    if not run.get("commit_ms"):
        return None
    return run["rounds"] / len(run["commit_ms"])
