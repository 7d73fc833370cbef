"""The ``cluster-1m`` configuration's shape at a small size, through the driver.

``benchmarks/configs/cluster-1m.json`` is one cluster with as many slots as
members, {K,H,L} = {10,9,4}, eight round-robin cohorts (one cohort word), one
coordinator, ``delivery_spread`` 2, ``fd_threshold`` 3; its traffic
(``benchmarks/traffic/crash1.json``) crashes 1 % of the members at once and
resolves them with one ``run_to_decision``. Here that file, cut to a few
thousand members and nothing else, is built and driven as the benchmark does
it (``benchmarks/targets.py``, the jnp core on the CPU): the view, the epoch
and the configuration id against ``benchmarks/membership_model.py`` (numpy
set arithmetic, no engine code), and the whole decision under both forms of
``ring_topology_from_perm`` (all K rings at once below
``RING_AT_A_TIME_SLOTS``, one at a time from it on), which must agree in every
observation: the cell's 1,000,000 slots take the rings one at a time since
PR 32 (874 -> 705 ms a commit on the chip), these 3,100 all at once, and a
threshold that moves must move nothing but the time.

The slot count is one no other test traces, ragged against the kernel's
128-lane tile as 1,000,000 is (7,812.5 tiles there, 24.2 here).
"""

import json
import os

import pytest

import jax
import jax.numpy as jnp

from benchmarks import membership_model, targets
from benchmarks.generators.closed_loop import StepSchedule
from rapid_tpu.models import virtual_cluster
from rapid_tpu.ops import rings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MEMBERS = 3100


def held(*parts: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", *parts), encoding="utf-8") as handle:
        return json.load(handle)


CONFIG = dict(held("configs", "cluster-1m.json"), members=MEMBERS, slots=MEMBERS)
TRAFFIC = held("traffic", "crash1.json")


def crash_one_percent_and_decide(seed: int) -> dict:
    """One step of the cell: the seed's cluster, the seed's victims, injected
    and resolved through the benchmark's target, with the views around it."""
    target = targets.build(CONFIG, seed, "cpu")
    schedule = StepSchedule(TRAFFIC, target, seed)
    _, crash, join = next(schedule.cycle())
    before = target.view()
    target.inject(crash, join)
    outcome = target.resolve(TRAFFIC["resolve"], MEMBERS - schedule.n_crash)
    return {
        "target": target, "crash": crash, "join": join, "before": before,
        "after": target.view(), "outcome": outcome, "config_id": target.driver.config_id,
    }


@pytest.fixture(scope="module", autouse=True)
def release_compiled_programs():
    # Tier-1 runs near the process's limit of memory maps (the verify
    # notes): give back what this module compiled.
    yield
    jax.clear_caches()


def test_the_shape_is_the_configuration_files():
    cfg = targets.build(CONFIG, 1, "cpu").driver.cfg
    assert (cfg.n, cfg.k, cfg.h, cfg.l, cfg.c) == (MEMBERS, 10, 9, 4, 8)
    assert (cfg.fd_threshold, cfg.delivery_spread, cfg.concurrent_coordinators) == (3, 2, 1)
    assert not cfg.use_pallas  # the Mosaic kernel exists on the chip only


@pytest.mark.parametrize("seed", [11, 4294967301, 3000000019])
def test_one_percent_crash_decides_to_the_plain_references_view(seed):
    seen = crash_one_percent_and_decide(seed)
    assert len(seen["crash"]) == MEMBERS // 100 and len(seen["join"]) == 0
    model = membership_model.MembershipModel(seen["target"].initial_alive())
    model.apply(seen["crash"], seen["join"])
    numbers = model.compare_view(seen["after"]["alive"])
    numbers.update(model.compare_epochs(seen["before"], seen["after"]))
    assert numbers == dict.fromkeys(numbers, 0)
    outcome = seen["outcome"]
    assert outcome["resolved"] and outcome["cuts"] == 1 and 1 <= outcome["rounds"] < 64
    assert outcome["final_sizes"] == model.sizes().tolist() == [MEMBERS - MEMBERS // 100]
    # one cut for the whole rack: the epoch advances exactly once
    assert int(seen["after"]["epoch"][0] - seen["before"]["epoch"][0]) == 1


def test_a_whole_decision_is_the_same_under_both_ring_forms(monkeypatch):
    def decision_text(driver) -> str:
        program = virtual_cluster._ROUND_PROGRAMS["decision"][0]
        return program.lower(driver.cfg, driver.state, driver.faults, jnp.int32(64)).as_text()

    assert MEMBERS < rings.RING_AT_A_TIME_SLOTS
    jax.clear_caches()
    batched = crash_one_percent_and_decide(7)
    batched_text = decision_text(batched["target"].driver)
    # The jitted programs remember their traces by shape: without clearing
    # them the patched constant would never be read.
    monkeypatch.setattr(rings, "RING_AT_A_TIME_SLOTS", MEMBERS)
    jax.clear_caches()
    try:
        one_at_a_time = crash_one_percent_and_decide(7)
        one_at_a_time_text = decision_text(one_at_a_time["target"].driver)
    finally:
        jax.clear_caches()  # nobody after this test gets the patched programs
    assert one_at_a_time_text != batched_text  # the other form was really traced
    assert (one_at_a_time["crash"] == batched["crash"]).all()
    assert batched["outcome"]["resolved"] and one_at_a_time["outcome"] == batched["outcome"]
    assert one_at_a_time["config_id"] == batched["config_id"]
    for key in ("alive", "epoch", "config_hi", "config_lo"):
        assert (one_at_a_time["after"][key] == batched["after"][key]).all(), key
