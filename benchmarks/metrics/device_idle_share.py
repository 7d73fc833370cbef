"""1 - (union of the device's operation intervals) / window, in percent."""


def read(run):
    if "trace" not in run:
        return None
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["window_s"])
