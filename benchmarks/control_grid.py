"""The grid controls: a run of the grid cell with one of its own guarantees
broken underneath, as ``control.py`` breaks the crash cells' (whose two faults
patch the fleet's crash injection, which this traffic calls too, so they apply
here as they stand).

``python3 benchmarks/control_grid.py --fault <name> --workload ... --seed ...
--seconds ... --trace 0`` drives the same harness over the same system, altered
where the benchmark cannot see it: the plain reference goes on from the
configuration as it is written. The run has to end with ``correct: false``.

- ``never_fall_back``: the fleet is built with a recovery delay beyond a
  step's round budget, so a tenant without a fast quorum never decides ("every
  tenant resolves within max_cuts cuts and max_steps rounds whichever path
  decides": ``unresolved``). A step then runs its whole budget of 192 rounds:
  give it a short window.
- ``one_triple_for_all``: every tenant is built with (H, L) = (9, 3) whatever
  its combination says, so the tenants the reference expects on the classic
  path wait for every report, agree, and decide fast, and the others decide in
  other rounds than theirs ("a tenant in which the reference finds no fast
  quorum decides by the classic round": ``view_changes_out_of_range``).
- ``evict_healthy``, ``lose_crash``: ``control.py``'s.

The benchmark's own runs never come through here.
"""

from __future__ import annotations

import os
import sys
import time


#: fault -> what it does to the configuration the TARGET is built from (the
#: reference keeps the file's); ``control.py``'s two patch the built target.
RECONFIGURE = {
    "never_fall_back": lambda config: dict(config, fallback_rounds=1 << 20),
    "one_triple_for_all": lambda config: dict(
        config, h_values=[9] * len(config["h_values"]), l_values=[3] * len(config["l_values"])),
}


def main(argv, t_process_start) -> int:
    from benchmarks import control, harness
    from benchmarks.generators import grid

    known = sorted({*RECONFIGURE, *control.FAULTS})
    if "--fault" not in argv or argv[argv.index("--fault") + 1] not in known:
        raise SystemExit(f"benchmarks/control_grid.py needs --fault, one of {known}")
    at = argv.index("--fault")
    name, rest = argv[at + 1], argv[:at] + argv[at + 2:]
    reconfigure = RECONFIGURE.get(name, lambda config: config)
    fault = control.FAULTS.get(name, lambda target: None)
    build = grid.GridFleetTarget

    def broken_build(config, seed, platform):
        target = build(reconfigure(config), seed, platform)
        fault(target)
        return target

    grid.GridFleetTarget = broken_build
    print(f"control: fault {name} installed under the driver", flush=True)
    return harness.main(rest, t_process_start)


if __name__ == "__main__":
    T_PROCESS_START = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import control_grid

    sys.exit(control_grid.main(sys.argv[1:], T_PROCESS_START))
