"""Share of the first device's busy time spent in collective operations, in
percent: the operations the compiler put in for the mesh, found by the names
the trace gives them. A trace with no such operation (one device, or a
program that was not sharded) reads nothing."""

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute", "reduce-scatter")


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    seconds = sum(s for name, s in trace["op_s"].items() if name.startswith(COLLECTIVES))
    total = sum(trace["op_s"].values())
    if not seconds or not total:
        return None
    return 100.0 * seconds / total
