"""The partition controls: a run of the partition cell with one of its own
guarantees broken underneath, as ``control.py`` breaks the crash cells'
(whose two faults patch crash injection, which this traffic calls too, so they
apply here as they stand).

``python3 benchmarks/control_partition.py --fault <name> --workload ...
--seed ... --seconds ... --trace 0`` drives the same harness over the same
system, altered where the benchmark cannot see it. The run has to end with
``correct: false``.

- ``lose_partition``: every injection drops the partition, so every cohort
  hears every report, the fast round reaches its quorum and decides ("exactly
  one cut a step, decided by the classic round": ``view_changes_out_of_range``).
- ``never_fall_back``: the cluster is built with a recovery delay beyond a
  step's round budget, so the classic round never starts and the cut never
  commits ("the cut commits though no fast quorum exists": ``unresolved``). A
  step then runs its whole budget of 192 rounds: give it a short window.

The benchmark's own runs never come through here.
"""

from __future__ import annotations

import os
import sys
import time


def _lose_partition(target):
    set_partition = target.driver.set_partition
    target.driver.set_partition = lambda cohorts, senders: set_partition([], [])


#: fault -> (what it does to the configuration before the build, what it does
#: to the target after it)
FAULTS = {
    "lose_partition": (lambda config: config, _lose_partition),
    "never_fall_back": (lambda config: dict(config, fallback_rounds=1 << 20), lambda target: None),
}


def main(argv, t_process_start) -> int:
    from benchmarks import harness, targets

    if "--fault" not in argv:
        raise SystemExit(f"benchmarks/control_partition.py needs --fault, one of {sorted(FAULTS)}")
    at = argv.index("--fault")
    (reconfigure, fault), rest = FAULTS[argv[at + 1]], argv[:at] + argv[at + 2:]
    build = targets.build

    def broken_build(config, seed, platform):
        target = build(reconfigure(config), seed, platform)
        fault(target)
        return target

    targets.build = broken_build
    print(f"control: fault {argv[at + 1]} installed under the driver", flush=True)
    return harness.main(rest, t_process_start)


if __name__ == "__main__":
    T_PROCESS_START = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import control_partition

    sys.exit(control_partition.main(sys.argv[1:], T_PROCESS_START))
