"""The link-fault controls: a run of a link-fault cell with one stated
guarantee broken underneath, as ``control.py`` breaks the crash cells' and
``control_join.py`` the join cell's (whose faults patch crash and join
injection, which this traffic never calls).

``python3 benchmarks/control_link.py --fault <name> --workload ... --seed ...
--seconds ... --trace 0`` drives the same harness over the same system, with
the driver's link-fault injection altered where the benchmark cannot see it.
The run has to end with ``correct: false``.

- ``lose_fault``: every injection drops its last faulty member, so a member
  whose ingress the schedule made faulty stays in the view ("exactly the
  faulty set is removed").
- ``deafen_healthy``: every injection also names a member the schedule never
  did, so the system evicts a healthy member ("a member the schedule never
  named keeps its place").

Either way the membership never reaches the step's target, so a control's
steps run their whole round budget and are long. The benchmark's own runs
never come through here.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np


def _lose_fault(target):
    inject = target.driver.set_link_faults
    target.driver.set_link_faults = lambda slots, *args, **kw: inject(
        np.asarray(slots, dtype=np.int32)[:-1], *args, **kw)


def _deafen_healthy(target):
    inject, calls = target.driver.set_link_faults, [0]

    def broken(slots, *args, **kw):
        slots = np.asarray(slots, dtype=np.int32)
        calls[0] += 1
        extra, taken = (calls[0] * 9973) % target.members, set(slots.tolist())
        while extra in taken:
            extra = (extra + 1) % target.members
        return inject(np.append(slots, np.int32(extra)), *args, **kw)

    target.driver.set_link_faults = broken


FAULTS = {"lose_fault": _lose_fault, "deafen_healthy": _deafen_healthy}


def main(argv, t_process_start) -> int:
    from benchmarks import harness, targets

    if "--fault" not in argv:
        raise SystemExit(f"benchmarks/control_link.py needs --fault, one of {sorted(FAULTS)}")
    at = argv.index("--fault")
    fault, rest = FAULTS[argv[at + 1]], argv[:at] + argv[at + 2:]
    build = targets.build

    def broken_build(config, seed, platform):
        target = build(config, seed, platform)
        fault(target)
        return target

    targets.build = broken_build
    print(f"control: fault {argv[at + 1]} installed under the driver", flush=True)
    return harness.main(rest, t_process_start)


if __name__ == "__main__":
    T_PROCESS_START = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import control_link

    sys.exit(control_link.main(sys.argv[1:], T_PROCESS_START))
