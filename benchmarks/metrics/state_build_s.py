"""Seconds of set-up spent building the state through the program's
constructors, before any warm-up step."""


def read(run):
    return run["state_build_s"]
