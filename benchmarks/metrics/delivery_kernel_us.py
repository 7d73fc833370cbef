"""Mean device time of one call of the Mosaic delivery kernel."""

KERNEL = "delivery_new_bits_pallas"


def calls_and_seconds(run):
    trace = run.get("trace")
    if trace is None:
        return 0, 0.0
    names = [name for name in trace["op_s"] if name.startswith(KERNEL)]
    return sum(trace["op_calls"][n] for n in names), sum(trace["op_s"][n] for n in names)


def read(run):
    calls, seconds = calls_and_seconds(run)
    return seconds * 1e6 / calls if calls else None
