"""Wall seconds of set-up inside the constructors' outermost stage blocks
(``VirtualCluster.create``, ``TenantFleet.create``): the program's own
reading of what ``state_build_s`` times from outside."""
from benchmarks.setup_pipeline import create_seconds


def read(run):
    return create_seconds()
