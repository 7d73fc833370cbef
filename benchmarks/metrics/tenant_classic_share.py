"""Share of the window's tenant-steps (one tenant resolving one step's
crashes) that the classic round decided: the differences of the tenants'
``decisions_classic`` telemetry lanes, summed by the generator, over tenants
times steps. A program that carries no such lanes reads nothing."""


def read(run):
    if not run.get("tenant_steps"):
        return None
    return 100.0 * run["tenant_steps_classic"] / run["tenant_steps"]
