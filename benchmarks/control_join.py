"""The join controls: a run of a join cell with one stated guarantee broken
underneath, as ``control.py`` breaks the crash cells' (whose two faults patch
crash injection only, so on joins-only traffic they would break nothing).

``python3 benchmarks/control_join.py --fault <name> --workload ... --seed ...
--seconds ... --trace 0`` drives the same harness over the same system, with
the fleet's join injection altered where the benchmark cannot see it. The run
has to end with ``correct: false``.

- ``lose_join``: every injection drops its last joiner, so a joiner whose
  admission the schedule injected never enters the view ("every joiner is in
  the view when its wave has resolved").
- ``admit_stranger``: every injection also admits, into tenant 0, a spare
  slot that this wave of the schedule never named, so the wave lets in
  somebody else ("nobody else enters"; the cuts no longer account for the
  injected joins). A bootstrap names every spare slot in the end, so the
  fault remembers whom it let in and drops the pair when the schedule gets
  to it: the system never sees an inadmissible joiner, only a wrong one.

A tenant that cannot reach its wave's target runs its wave's whole step
budget, so a control's steps are long. The benchmark's own runs never come
through here.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np


def _lose_join(target):
    inject = target.driver.inject_join_wave
    target.driver.inject_join_wave = lambda pairs, **kw: inject(
        np.asarray(pairs, dtype=np.int32).reshape(-1, 2)[:-1], **kw)


def _admit_stranger(target):
    inject, restore = target.driver.inject_join_wave, target.restore
    let_in = set()  # tenant 0's slots that went in since the last restore

    def restore_and_forget(pristine):
        let_in.clear()
        restore(pristine)

    def broken(pairs, **kw):
        pairs = np.asarray(pairs, dtype=np.int32).reshape(-1, 2)
        mine = pairs[:, 0] == 0
        known = mine & np.isin(pairs[:, 1], list(let_in))
        pairs = pairs[~known]  # the schedule names an earlier stranger: already in
        named = set(pairs[pairs[:, 0] == 0, 1].tolist())
        spare = [s for s in range(target.members, target.slots) if s not in let_in | named]
        let_in.update(named)
        if spare:
            let_in.add(spare[0])
            pairs = np.vstack([pairs, [[0, spare[0]]]]).astype(np.int32)
        return inject(pairs, **kw)

    target.restore = restore_and_forget
    target.driver.inject_join_wave = broken


FAULTS = {"lose_join": _lose_join, "admit_stranger": _admit_stranger}


def main(argv, t_process_start) -> int:
    from benchmarks import harness
    from benchmarks.generators import bootstrap

    if "--fault" not in argv:
        raise SystemExit(f"benchmarks/control_join.py needs --fault, one of {sorted(FAULTS)}")
    at = argv.index("--fault")
    fault, rest = FAULTS[argv[at + 1]], argv[:at] + argv[at + 2:]
    build = bootstrap.JoinFleetTarget

    def broken_build(config, seed, platform):
        target = build(config, seed, platform)
        fault(target)
        return target

    bootstrap.JoinFleetTarget = broken_build
    print(f"control: fault {argv[at + 1]} installed under the driver", flush=True)
    return harness.main(rest, t_process_start)


if __name__ == "__main__":
    T_PROCESS_START = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import control_join

    sys.exit(control_join.main(sys.argv[1:], T_PROCESS_START))
