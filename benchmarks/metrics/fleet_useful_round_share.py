"""Share of the fleet's tenant-rounds spent on a tenant that had churn in
flight: the rest is what lockstep batching makes idle tenants pay."""


def read(run):
    if run["config"]["deployment"] != "fleet" or not run["tenant_rounds_total"]:
        return None
    return 100.0 * run["tenant_rounds_useful"] / run["tenant_rounds_total"]
