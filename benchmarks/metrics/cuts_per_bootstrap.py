"""View changes a tenant committed per bootstrap (the program's
``engine_tenant_cuts`` over tenants and commits): Table 1's instrument, as
many as the waves when every wave lands as one cut."""
from benchmarks.targets_fleet_join import window_counts


def read(run):
    counts, steps = window_counts(run, "engine_tenant_cuts"), len(run.get("commit_ms") or ())
    if counts is None or not steps:
        return None
    return counts[0] / (run["config"]["tenants"] * steps)
