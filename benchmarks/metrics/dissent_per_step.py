"""Cohorts a step that had announced another cut than the one their cluster
decided, summed over the fleet: the differences of the tenants' ``dissent``
telemetry lanes over the window's steps. What the paper's Fig. 11 counts
(receivers whose announced cut missed a victim), seen from the decision. A
program that carries no such lane reads nothing."""


def read(run):
    if "dissent" not in run or not run["attempted"]:
        return None
    return run["dissent"] / run["attempted"]
