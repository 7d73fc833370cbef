"""View changes the window committed per wave it submitted."""


def read(run):
    return run["view_changes"] / run["attempted"] if run.get("wave_ms") else None
