"""Process start to the first timed step: state build, warm-up, cache loads."""


def read(run):
    return run["setup_s"]
