"""Device ring-topology kernels vs the host MembershipView oracle."""

import numpy as np
import pytest

import jax

from rapid_tpu.ops import rings
from rapid_tpu.ops.rings import (
    endpoint_ring_keys,
    predecessor_of_keys,
    ring_perms,
    ring_topology,
    ring_topology_from_perm,
)
from rapid_tpu.protocol.view import MembershipView
from rapid_tpu.types import Endpoint, NodeId


def make_endpoints(n, seed=0):
    rng = np.random.default_rng(seed)
    ports = rng.choice(50000, size=n, replace=False) + 1
    return [Endpoint(f"10.0.{i % 256}.{i // 256}", int(p)) for i, p in enumerate(ports)]


@pytest.mark.parametrize("n,k", [(4, 3), (10, 10), (100, 10), (257, 7)])
def test_topology_matches_view(n, k):
    endpoints = make_endpoints(n, seed=n)
    view = MembershipView(k)
    for i, ep in enumerate(endpoints):
        view.ring_add(ep, NodeId(0, i))

    key_hi, key_lo = endpoint_ring_keys(endpoints, k)
    alive = np.ones(n, dtype=bool)
    topo = ring_topology(key_hi, key_lo, alive)
    obs = np.asarray(topo.obs_idx)
    subj = np.asarray(topo.subj_idx)

    slot_of = {ep: i for i, ep in enumerate(endpoints)}
    for i, ep in enumerate(endpoints):
        expected_obs = [slot_of[o] for o in view.observers_of(ep)]
        expected_subj = [slot_of[s] for s in view.subjects_of(ep)]
        assert obs[:, i].tolist() == expected_obs
        assert subj[:, i].tolist() == expected_subj


def test_topology_with_dead_slots():
    n, k = 60, 10
    endpoints = make_endpoints(n, seed=3)
    rng = np.random.default_rng(7)
    alive = rng.random(n) > 0.3

    view = MembershipView(k)
    for i, ep in enumerate(endpoints):
        if alive[i]:
            view.ring_add(ep, NodeId(0, i))

    key_hi, key_lo = endpoint_ring_keys(endpoints, k)
    topo = ring_topology(key_hi, key_lo, alive)
    obs = np.asarray(topo.obs_idx)
    subj = np.asarray(topo.subj_idx)

    slot_of = {ep: i for i, ep in enumerate(endpoints)}
    for i, ep in enumerate(endpoints):
        if not alive[i]:
            assert (obs[:, i] == -1).all()
            assert (subj[:, i] == -1).all()
            continue
        assert obs[:, i].tolist() == [slot_of[o] for o in view.observers_of(ep)]
        assert subj[:, i].tolist() == [slot_of[s] for s in view.subjects_of(ep)]


def test_topology_single_and_two_nodes():
    endpoints = make_endpoints(5, seed=9)
    k = 10
    key_hi, key_lo = endpoint_ring_keys(endpoints, k)

    alive = np.zeros(5, dtype=bool)
    alive[2] = True
    topo = ring_topology(key_hi, key_lo, alive)
    # A lone node has no observers (MembershipView.java:240-242).
    assert (np.asarray(topo.obs_idx)[:, 2] == -1).all()

    alive[4] = True
    topo = ring_topology(key_hi, key_lo, alive)
    assert (np.asarray(topo.obs_idx)[:, 2] == 4).all()
    assert (np.asarray(topo.obs_idx)[:, 4] == 2).all()


@pytest.mark.parametrize("n,k,alive_frac", [
    (4, 3, 1.0),      # minimum viable ring
    (64, 10, 0.9),    # sparse deaths
    (257, 7, 0.5),    # half dead, odd N
    (100, 10, 0.02),  # near-empty: 2 alive
    (50, 5, 0.0),     # nobody alive
    (33, 4, None),    # exactly ONE alive (below the 2-node floor)
])
def test_from_perm_matches_sorting_topology(n, k, alive_frac):
    # The sort-free scan path (used by every view change) must be
    # bit-identical to the argsort definition across the aliveness range,
    # including the <2-alive floor where every entry is -1.
    rng = np.random.default_rng(n * 31 + k)
    key_hi = rng.integers(0, 2**32, size=(k, n), dtype=np.uint32)
    key_lo = rng.integers(0, 2**32, size=(k, n), dtype=np.uint32)
    if alive_frac is None:
        alive = np.zeros(n, dtype=bool)
        alive[n // 2] = True
    else:
        alive = rng.random(n) < alive_frac
    perm = ring_perms(key_hi, key_lo)
    want = ring_topology(key_hi, key_lo, alive)
    got = ring_topology_from_perm(perm, alive)
    np.testing.assert_array_equal(np.asarray(got.obs_idx), np.asarray(want.obs_idx))
    np.testing.assert_array_equal(np.asarray(got.subj_idx), np.asarray(want.subj_idx))
    np.testing.assert_array_equal(np.asarray(got.order), np.asarray(want.order))

    # The joiner-gatekeeper query must agree between its sorting and
    # perm-scan paths too (inject_join_wave passes the engine's perm).
    j = min(5, n)
    qhi = rng.integers(0, 2**32, size=(k, j), dtype=np.uint32)
    qlo = rng.integers(0, 2**32, size=(k, j), dtype=np.uint32)
    np.testing.assert_array_equal(
        np.asarray(predecessor_of_keys(key_hi, key_lo, alive, qhi, qlo)),
        np.asarray(
            predecessor_of_keys(key_hi, key_lo, alive, qhi, qlo, perm=perm)
        ),
    )


def test_expected_observers_of_joiners():
    n, k, j = 50, 10, 7
    endpoints = make_endpoints(n + j, seed=11)
    members, joiners = endpoints[:n], endpoints[n:]
    view = MembershipView(k)
    for i, ep in enumerate(members):
        view.ring_add(ep, NodeId(0, i))

    key_hi, key_lo = endpoint_ring_keys(members, k)
    qhi, qlo = endpoint_ring_keys(joiners, k)
    alive = np.ones(n, dtype=bool)
    pred = np.asarray(predecessor_of_keys(key_hi, key_lo, alive, qhi, qlo))

    slot_of = {ep: i for i, ep in enumerate(members)}
    for jx, joiner in enumerate(joiners):
        expected = [slot_of[o] for o in view.expected_observers_of(joiner)]
        assert pred[:, jx].tolist() == expected


def _primitives(jaxpr):
    """Every primitive of a jaxpr, the bodies of its calls and loops included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


#: The form ``ring_topology_from_perm`` takes is a static fact of the ring
#: length: (perm's shape, its dtype, tenants it is ``vmap``ped over, walks the
#: rings one at a time). The sizes are the benchmark's configurations'
#: (PERF.md section 4); int16 is the compact engine's index width.
RING_FORMS = {
    "cluster-1m": ((10, 1_000_000), np.int32, None, True),
    "cluster-10m": ((10, 10_000_000), np.int32, None, True),
    "cluster-100k": ((10, 102_500), np.int32, None, True),
    "paper-fleet-1k": ((10, 1000), np.int32, 256, False),
    "at_the_threshold": ((10, rings.RING_AT_A_TIME_SLOTS), np.int32, None, True),
    "one_under_the_threshold": ((10, rings.RING_AT_A_TIME_SLOTS - 1), np.int32, None, False),
    "compact_index_width": ((10, 1000), np.int16, None, False),
}


@pytest.mark.parametrize("name", list(RING_FORMS))
def test_the_ring_length_picks_the_form_of_the_rebuild(name):
    # Traced over shapes alone: no buffer of ten million slots is ever made.
    shape, dtype, tenants, one_at_a_time = RING_FORMS[name]
    fn, lead = ring_topology_from_perm, ()
    if tenants is not None:
        fn, lead = jax.vmap(ring_topology_from_perm), (tenants,)
    traced = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct(lead + shape, dtype),
        jax.ShapeDtypeStruct(lead + shape[-1:], np.bool_),
    )
    assert [v.aval.shape for v in traced.jaxpr.outvars] == [lead + shape] * 3
    assert [v.aval.dtype for v in traced.jaxpr.outvars] == [np.int32] * 3
    loops = [p for p in _primitives(traced.jaxpr) if p in ("scan", "while")]
    assert loops == (["scan"] if one_at_a_time else []), (name, loops)


def test_the_threshold_lies_between_the_cells_it_separates():
    # The fleet's clusters keep all K rings in one program; cluster-100k,
    # cluster-1m and cluster-10m (whose compile time placed the first bound,
    # 2**22, PR 27) walk them one at a time.
    assert 1000 < rings.RING_AT_A_TIME_SLOTS <= 102_500
