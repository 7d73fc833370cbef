"""The closed loop of ``closed_loop.py`` over a cluster sharded on a mesh.

Nothing of the loop is here: importing ``targets_mesh`` makes the
``cluster_mesh`` deployment known to ``targets.build``, and the steps, the
clocks and the check are ``closed_loop.run``'s.
"""

from benchmarks import targets_mesh  # noqa: F401  (registers the deployment)
from benchmarks.generators.closed_loop import run  # noqa: F401
