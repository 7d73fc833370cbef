"""The controls: a run of a cell with one stated guarantee broken underneath.

``python3 benchmarks/control.py --fault <name> --workload ... --seed ...
--seconds ... --trace 0`` drives the same harness over the same system, with
the driver's crash injection altered where the benchmark cannot see it. The
run has to end with ``correct: false``; a control that comes out correct
means the check is blind to that guarantee.

- ``evict_healthy``: every injection also crashes one member the schedule
  never named, so the system evicts a healthy member ("no healthy member is
  evicted").
- ``lose_crash``: every injection drops its last crash, so a crashed member
  stays in the view ("every crashed member is out of the view").

The benchmark's own runs never come through here.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np


def _evict_healthy(target):
    driver, calls = target.driver, [0]

    def extra_slot(taken) -> int:
        calls[0] += 1
        slot = (calls[0] * 9973) % target.members
        while slot in taken:
            slot = (slot + 1) % target.members
        return slot

    if target.kind == "cluster":
        crash = driver.crash
        driver.crash = lambda slots: crash(
            np.append(np.asarray(slots, dtype=np.int32), extra_slot(set(map(int, slots)))))
    else:
        stream_crash = driver.stream_crash

        def broken(pairs):
            pairs = np.asarray(pairs, dtype=np.int32).reshape(-1, 2)
            taken = {int(s) for t, s in pairs if t == 0}
            return stream_crash(np.vstack([pairs, [[0, extra_slot(taken)]]]))

        driver.stream_crash = broken


def _lose_crash(target):
    driver = target.driver
    if target.kind == "cluster":
        crash = driver.crash
        driver.crash = lambda slots: crash(np.asarray(slots, dtype=np.int32)[:-1])
    else:
        stream_crash = driver.stream_crash
        driver.stream_crash = lambda pairs: stream_crash(
            np.asarray(pairs, dtype=np.int32).reshape(-1, 2)[:-1])


FAULTS = {"evict_healthy": _evict_healthy, "lose_crash": _lose_crash}


def main(argv, t_process_start) -> int:
    from benchmarks import harness, targets

    if "--fault" not in argv:
        raise SystemExit(f"benchmarks/control.py needs --fault, one of {sorted(FAULTS)}")
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
    from benchmarks import control

    sys.exit(control.main(sys.argv[1:], T_PROCESS_START))
