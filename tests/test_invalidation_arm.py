"""The compacted implicit-invalidation arm at engine level: twins of the
benchmark's cells' traffic driven through the drivers' own programs and through
the same programs with the dense loop alone (``dense_arms=True``, what
``parallel/mesh.sharded_program`` builds and what every program was before the
arm was compacted): same rounds, same cuts, same view, same state leaf for
leaf, and the telemetry plane's two lanes say which form ran. The pass itself,
bit for bit at the corners of its bucket, is ``tests/test_ops_cut.py``'s.

The drives run in ONE process of their own (``python tests/test_invalidation_arm.py``
prints one JSON record a scenario): the ~300 executables they compile stay
out of this session, which ends within 1 % of vm.max_map_count and whose
compile tables other modules read."""

import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def crash1(create, seed):
    """`cluster-1m.crash1` / `cluster-10m.crash1`: 1 % crash at once, 8 cohorts."""
    vc = create(
        4000, k=10, h=9, l=4, cohorts=8, fd_threshold=3, delivery_spread=2,
        telemetry=True, seed=seed)
    vc.assign_cohorts_roundrobin()
    vc.crash(np.random.default_rng(seed).choice(4000, size=40, replace=False))
    vc.sync()
    return vc, lambda: vc.run_to_decision(max_steps=64)


def churn5(create, seed):
    """`cluster-100k.churn5`: 2.44 % crash and as many joins in one wave."""
    vc = create(
        4000, n_slots=4100, k=10, h=9, l=4, cohorts=16, fd_threshold=3,
        delivery_spread=2, concurrent_coordinators=2, telemetry=True, seed=seed)
    vc.assign_cohorts_roundrobin()
    vc.stagger_fd_counts(np.random.default_rng(seed), 3)
    vc.crash(np.random.default_rng(seed).choice(4000, size=100, replace=False))
    vc.inject_join_wave(list(range(4000, 4100)))
    vc.sync()
    return vc, lambda: vc.run_until_membership(4000, max_steps=192, max_cuts=4, min_cuts=1)


def partition(create, seed):
    """`cluster-100k-zoned.partition` at tests/test_partition.py's 5,000-member
    twin: 24 of 64 cohorts deaf to a rack of 5 %, 2.44 % crash."""
    vc = create(
        5000, n_slots=5125, k=10, h=9, l=4, cohorts=64, fd_threshold=3,
        delivery_spread=2, concurrent_coordinators=2, fallback_rounds=8,
        telemetry=True, seed=3)
    vc.assign_cohorts_roundrobin()
    vc.stagger_fd_counts(np.random.default_rng(5), 3)
    order = np.random.default_rng(seed).permutation(5000)
    vc.set_partition(np.arange(24), np.sort(order[125:375]))
    vc.crash(np.sort(order[:125]))
    vc.sync()
    return vc, lambda: vc.run_until_membership(4875, max_steps=192, max_cuts=4, min_cuts=1)


def loss80(create, seed):
    """`cluster-50k.loss80`: 1 % of the members lose 80 % of their ingress,
    under the windowed detector with its windows warm."""
    vc = create(
        2000, k=10, h=9, l=4, cohorts=4, fd_threshold=4, fd_window=10,
        delivery_spread=2, concurrent_coordinators=2, telemetry=True, seed=seed)
    vc.assign_cohorts_roundrobin()
    for _ in range(10):
        vc.step()
    vc.set_link_faults(
        np.random.default_rng(seed).choice(2000, size=20, replace=False),
        loss_permille=800, seed=seed)
    vc.sync()
    return vc, lambda: vc.run_until_membership(1980, max_steps=192, max_cuts=4, min_cuts=1)


def overflow(create, seed):
    """No cell's: 20 % of 1,000 members crash at once, and while their reports
    trickle in more subjects are in flux than the bucket of 128 holds."""
    vc = create(
        1000, k=10, h=9, l=4, cohorts=4, fd_threshold=2, delivery_spread=3,
        telemetry=True, seed=9)
    vc.assign_cohorts_roundrobin()
    vc.stagger_fd_counts(np.random.default_rng(9), 3)
    vc.crash(np.random.default_rng(seed).choice(1000, size=200, replace=False))
    vc.sync()
    return vc, lambda: vc.run_to_decision(max_steps=64)


SCENARIOS = {
    "crash1": crash1, "churn5": churn5, "partition": partition, "loss80": loss80,
    "overflow": overflow,
}


def drive() -> None:
    """Every scenario through the drivers' programs and through the dense-only
    twins of them; one JSON record a scenario on stdout."""
    import jax

    from rapid_tpu.models import virtual_cluster as vcm

    dense_programs = {
        "decision": vcm.jit_per_observer_count(
            functools.partial(vcm.run_to_decision_impl, dense_arms=True)),
        "wave": vcm.jit_per_observer_count(
            functools.partial(vcm.run_until_membership_impl, dense_arms=True),
            static=(5,)),
    }
    own_programs = dict(vcm._ROUND_PROGRAMS)

    def resolved(name):
        vc, resolve = SCENARIOS[name](vcm.VirtualCluster.create, 45)
        outcome = [np.asarray(part).tolist() for part in resolve()]
        vc.sync()
        return vc, outcome

    for name in SCENARIOS:
        vcm._ROUND_PROGRAMS.update(own_programs)
        vc, outcome = resolved(name)
        vcm._ROUND_PROGRAMS.update(dense_programs)
        twin, outcome_twin = resolved(name)
        lanes = ("rounds", "invalidations", "invalidation_rounds", "invalidation_dense_rounds")
        scrape = vc.prometheus_text()
        print(json.dumps({
            "scenario": name,
            "same_outcome": outcome == outcome_twin,
            "rounds": outcome[0],
            "same_view": (vc.config_id, vc.config_epoch) == (twin.config_id, twin.config_epoch),
            "leaves_that_differ": [
                field for field, left, right in zip(vc.state._fields, vc.state, twin.state)
                if not np.array_equal(np.asarray(left), np.asarray(right))
            ],
            "ours": {lane: vc.activity[lane] for lane in lanes},
            "theirs": {lane: twin.activity[lane] for lane in lanes},
            "scraped": all(
                f'rapid_engine_activity_{lane}_total{{node="virtual-cluster/{vc.cfg.n}"}} '
                f'{vc.activity[lane]}' in scrape for lane in lanes[2:]
            ),
        }), flush=True)
        jax.clear_caches()


@pytest.fixture(scope="module")
def drives():
    done = subprocess.run(
        [sys.executable, str(Path(__file__).resolve())], capture_output=True, text=True,
        cwd=str(REPO), env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)},
        timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    records = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    return {record["scenario"]: record for record in records}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_a_twin_of_the_cells_traffic_resolves_as_under_the_dense_loop(drives, scenario):
    record = drives[scenario]
    # same rounds, same cuts, same sizes (and the same winner's mask), the
    # same view and every leaf of the state
    assert record["same_outcome"] and record["same_view"]
    assert record["leaves_that_differ"] == []
    # the arm ran, in the same rounds, and the dense-only program says of
    # every one of them that it ran dense
    ours, theirs = record["ours"], record["theirs"]
    assert ours["rounds"] == theirs["rounds"] >= record["rounds"]  # loss80 warms its windows
    assert 0 < ours["invalidation_rounds"] == theirs["invalidation_rounds"] <= ours["rounds"]
    assert ours["invalidations"] == theirs["invalidations"]
    assert theirs["invalidation_dense_rounds"] == theirs["invalidation_rounds"]
    assert record["scraped"]
    if scenario == "overflow":  # counted, and still the dense loop's result
        assert 0 < ours["invalidation_dense_rounds"] < ours["invalidation_rounds"]
    else:  # the bucket held every round of the cells' traffic
        assert ours["invalidation_dense_rounds"] == 0


if __name__ == "__main__":
    drive()
