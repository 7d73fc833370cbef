"""Device ring-topology kernels vs the host MembershipView oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rapid_tpu.ops import rings
from rapid_tpu.ops.hashing import lex_argsort
from rapid_tpu.ops.rings import (
    endpoint_ring_keys,
    predecessor_of_keys,
    ring_liveness,
    ring_perms,
    ring_positions,
    ring_tables_after_cut,
    ring_topology,
    ring_topology_from_perm,
    view_change_bucket,
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


def _alive_case(spec, n, perm, rng):
    """The alive mask a case names: a share, or one of the walk's corners.
    ``perm`` is ring 0's key order; the corner cases that speak of a ring
    position are built with keys that put the same slot there in every ring
    (``_keys``)."""
    if isinstance(spec, float):
        return rng.random(n) < spec
    alive = np.zeros(n, dtype=bool)
    if spec == "all":
        alive[:] = True
    elif spec == "one":  # below the 2-node floor
        alive[n // 2] = True
    elif spec == "two":
        alive[[n // 3, n - 2]] = True
    elif spec == "last_position_only":  # one alive, and the scans' wrap holds it
        alive[perm[-1]] = True
    elif spec == "both_ends":  # every neighbour is found by wrapping
        alive[[perm[0], perm[-1]]] = True
    elif spec == "slot0_at_position0_alive":  # the word 0 is a real word
        alive = rng.random(n) < 0.5
        alive[0] = True
    elif spec == "slot0_at_position0_dead":
        alive = rng.random(n) < 0.5
        alive[0] = False
    elif spec != "none":
        raise ValueError(spec)
    return alive


def _keys(n, k, rng):
    """Random ring keys, with slot 0 first and slot n - 1 last in EVERY
    ring's key order, so that a case can speak of a ring position."""
    key_hi = rng.integers(1, 2**32 - 1, size=(k, n), dtype=np.uint32)
    key_lo = rng.integers(0, 2**32, size=(k, n), dtype=np.uint32)
    key_hi[:, 0], key_lo[:, 0] = 0, 0
    key_hi[:, n - 1], key_lo[:, n - 1] = 2**32 - 1, 2**32 - 1
    return key_hi, key_lo


@pytest.mark.parametrize("n,k,alive_frac,idx", [
    (4, 3, 1.0, np.int32),      # minimum viable ring
    (64, 10, 0.9, np.int32),    # sparse deaths
    (257, 7, 0.5, np.int32),    # half dead, odd N
    (100, 10, 0.02, np.int32),  # near-empty: 2 alive
    (50, 5, 0.0, np.int32),     # nobody alive
    (33, 4, "one", np.int32),   # exactly ONE alive (below the 2-node floor)
    # The packed walk (PR 37) at the lengths where its word changes shape:
    # an exact power of two fills the position field, one above it takes a
    # bit more; 65,536 is the last length of one piece, 102,500 takes two.
    (1024, 3, 0.9, np.int32),
    (1025, 3, 0.9, np.int32),
    (65536, 2, 0.99, np.int32),
    (65536, 2, "both_ends", np.int32),
    (2000, 10, 0.97, np.int32),     # paper-fleet-2k's ring: one piece
    (102_500, 2, 0.99, np.int32),   # cluster-100k's ring: two pieces
    (102_500, 2, 0.01, np.int32),
    (102_500, 1, "both_ends", np.int32),
    (1000, 4, "none", np.int32),
    (1000, 4, "one", np.int32),
    (1000, 4, "two", np.int32),
    (1000, 4, "all", np.int32),
    (1000, 4, "last_position_only", np.int32),
    (1000, 4, "both_ends", np.int32),
    (1000, 4, "slot0_at_position0_alive", np.int32),
    (1000, 4, "slot0_at_position0_dead", np.int32),
    (1024, 4, "slot0_at_position0_alive", np.int32),
    (1024, 4, "both_ends", np.int32),
    # The compact engine hands perm over at the policy's index width.
    (100, 10, 0.9, np.int8),
    (127, 3, "both_ends", np.int8),
    (2000, 10, 0.9, np.int16),
    (32767, 2, "slot0_at_position0_alive", np.int16),
])
def test_from_perm_matches_sorting_topology(n, k, alive_frac, idx):
    # The sort-free scan path (used by every view change) must be
    # bit-identical to the argsort definition across the aliveness range,
    # including the <2-alive floor where every entry is -1.
    rng = np.random.default_rng(n * 31 + k)
    key_hi, key_lo = _keys(n, k, rng)
    perm = ring_perms(key_hi, key_lo)
    alive = _alive_case(alive_frac, n, np.asarray(perm[0]), rng)
    want = ring_topology(key_hi, key_lo, alive)
    got = jax.jit(ring_topology_from_perm)(perm.astype(idx), alive)
    assert got.obs_idx.dtype == got.subj_idx.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got.obs_idx), np.asarray(want.obs_idx))
    np.testing.assert_array_equal(np.asarray(got.subj_idx), np.asarray(want.subj_idx))
    np.testing.assert_array_equal(np.asarray(got.order), np.asarray(want.order))

    # The joiner-gatekeeper query, which builds no order, must read what the
    # order it no longer builds would give, sorted or scanned from the perm.
    slots = rng.choice(n, size=min(5, n), replace=False)
    narrow = perm.astype(idx)
    got = np.asarray(predecessor_of_keys(ring_positions(narrow), narrow, alive, slots))
    for order in (None, want.order):
        np.testing.assert_array_equal(
            got, np.asarray(_predecessors_by_rank(key_hi, key_lo, alive, slots, order))
        )


@pytest.mark.parametrize("n,alive_frac", [
    (1000, 0.9),            # one piece (the fleet's ring)
    (70_000, 0.99),         # two pieces
    (70_000, "both_ends"),
])
def test_both_schedules_of_the_rings_give_the_sorting_topology(n, alive_frac, monkeypatch):
    # All K rings at once (``vmap``) and one at a time (``lax.map``) are one
    # walk under two schedules: the same input, the same tables, the oracle's.
    k = 3
    rng = np.random.default_rng(n)
    key_hi, key_lo = _keys(n, k, rng)
    perm = ring_perms(key_hi, key_lo)
    alive = _alive_case(alive_frac, n, np.asarray(perm[0]), rng)
    want = ring_topology(key_hi, key_lo, alive)
    for bound, loop in ((n + 1, []), (n, ["scan"])):
        monkeypatch.setattr(rings, "RING_AT_A_TIME_SLOTS", bound)
        form = jax.jit(lambda p, a: ring_topology_from_perm(p, a))  # traced after the patch
        assert [
            name for name in _primitives(jax.make_jaxpr(form)(perm, alive).jaxpr)
            if name in ("scan", "while")
        ] == loop
        got = form(perm, alive)
        np.testing.assert_array_equal(np.asarray(got.obs_idx), np.asarray(want.obs_idx))
        np.testing.assert_array_equal(np.asarray(got.subj_idx), np.asarray(want.subj_idx))


def test_expected_observers_of_joiners():
    n, k, j = 50, 10, 7
    endpoints = make_endpoints(n + j, seed=11)
    members, joiners = endpoints[:n], endpoints[n:]
    view = MembershipView(k)
    for i, ep in enumerate(members):
        view.ring_add(ep, NodeId(0, i))

    # A joiner holds a slot, and so its keys and ring positions, before it
    # is admitted: the members' slots first, alive, then the joiners'.
    perm = ring_perms(*endpoint_ring_keys(endpoints, k))
    alive = np.arange(n + j) < n
    pred = np.asarray(
        predecessor_of_keys(ring_positions(perm), perm, alive, np.arange(n, n + j))
    )

    slot_of = {ep: i for i, ep in enumerate(members)}
    for jx, joiner in enumerate(joiners):
        expected = [slot_of[o] for o in view.expected_observers_of(joiner)]
        assert pred[:, jx].tolist() == expected


def _predecessors_by_rank(key_hi, key_lo, alive, slots, orders=None):
    """The plain reference of ``predecessor_of_keys``, which was the function
    itself up to PR 49: sort every ring alive-first by key (or take
    ``orders``, ``RingTopology.order``), rank each query by a masked
    comparison sum, and read ``order[rank - 1]``, wrapping below rank 0. A
    query is a slot's own key; where another slot holds the SAME 64-bit key
    the lower slot comes first, as in the static order (the parent counted
    strictly smaller keys alone, so it differs there and only there)."""
    key_hi, key_lo, alive = jnp.asarray(key_hi), jnp.asarray(key_lo), jnp.asarray(alive)
    slots = jnp.asarray(slots)
    n_alive = jnp.sum(alive.astype(jnp.int32))
    if orders is None:
        dead = (~alive).astype(jnp.uint32)
        orders = jax.vmap(lambda h, low: lex_argsort((dead, h, low)))(key_hi, key_lo)
    slot = jnp.arange(key_hi.shape[-1])

    def one_ring(khi, klo, order):
        def one_query(q):
            h, low = khi[q], klo[q]
            less = (khi < h) | ((khi == h) & ((klo < low) | ((klo == low) & (slot < q))))
            rank = jnp.sum((less & alive).astype(jnp.int32))
            pred_pos = jnp.where(rank - 1 < 0, n_alive - 1, rank - 1)
            return jnp.where(n_alive >= 1, order[pred_pos], -1).astype(jnp.int32)

        return jax.vmap(one_query)(slots)

    return jax.vmap(one_ring)(key_hi, key_lo, orders)


#: name -> (key bits, who is alive, which slots ask, the index dtype). One
#: shape for all of them: 2-bit keys tie among the members and between a
#: joiner and members; a "member" asks for its own key, whose gatekeeper is
#: the alive slot BEFORE it, never itself; "padded" is the fleet's layout.
GATEKEEPER_CASES = {
    "random_32_bit_keys": (32, 0.7, "joiners", np.int32),
    "two_bit_keys": (2, 0.7, "joiners", np.int32),
    "two_bit_keys_all_alive": (2, "all", "members", np.int32),
    "nobody_alive": (32, "none", "joiners", np.int32),
    "one_alive": (32, "one", "joiners", np.int32),
    "one_alive_two_bit_keys": (2, "one", "any", np.int32),
    "all_alive": (32, "all", "members", np.int32),
    "queries_are_members_own_keys": (32, 0.7, "members", np.int32),
    "compact_index_dtype": (32, 0.7, "any", np.int8),
    "tenant_vmap_padded_queries": (32, 0.7, "padded", np.int16),
}


@pytest.mark.parametrize("case", list(GATEKEEPER_CASES))
def test_the_gatekeeper_is_the_one_the_alive_first_order_gives(case):
    bits, who, asks, idx = GATEKEEPER_CASES[case]
    tenants, k, n, j = 3, 4, 96, 12
    rng = np.random.default_rng(sorted(GATEKEEPER_CASES).index(case))
    key_hi = rng.integers(0, 2**bits, size=(tenants, k, n), dtype=np.uint32)
    key_lo = rng.integers(0, 2**bits, size=(tenants, k, n), dtype=np.uint32)
    alive = {
        "none": np.zeros((tenants, n), bool),
        "all": np.ones((tenants, n), bool),
        "one": np.arange(n) == rng.integers(0, n, size=(tenants, 1)),
    }.get(who)
    if alive is None:
        alive = rng.random((tenants, n)) < who
    pools = {"joiners": ~alive, "members": alive}
    slots = np.stack([
        rng.choice(np.flatnonzero(pools.get(asks, np.ones((tenants, n), bool))[t]), size=j, replace=False)
        for t in range(tenants)
    ])
    if asks == "padded":
        # As the fleet's placement lays them out: tenant 1 has fewer than the
        # width and tenant 2 none, padded with slot ``n``, which asks for
        # slot ``n - 1`` (and whose answer every scatter drops).
        slots[1, j // 2:] = n
        slots[2, :] = n
        slots = np.minimum(slots, n - 1)
    perm = jax.vmap(ring_perms)(key_hi, key_lo).astype(idx)
    pos = jax.vmap(ring_positions)(perm)
    assert pos.dtype == idx
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(pos), np.asarray(perm).astype(np.int64), axis=2),
        np.broadcast_to(np.arange(n), (tenants, k, n)),
    )

    got = jax.vmap(predecessor_of_keys)(pos, perm, alive, slots)
    assert got.dtype == np.int32 and got.shape == (tenants, k, j)
    want = np.stack([
        np.asarray(_predecessors_by_rank(key_hi[t], key_lo[t], alive[t], slots[t]))
        for t in range(tenants)
    ])
    np.testing.assert_array_equal(np.asarray(got), want)
    # alone as under the tenants' vmap
    np.testing.assert_array_equal(
        np.asarray(predecessor_of_keys(pos[0], perm[0], alive[0], slots[0])), want[0]
    )
    assert (want >= 0).all() == bool(alive.any(axis=1).all())  # -1 only with nobody alive
    # The engine narrows the gatekeepers to its index width on store: every
    # slot and the -1 survive it.
    np.testing.assert_array_equal(np.asarray(got.astype(idx)).astype(np.int32), want)


def _equations(jaxpr):
    """Every equation of a jaxpr, the bodies of its calls and loops included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _primitives(jaxpr):
    """Every primitive of a jaxpr, the bodies of its calls and loops included."""
    return (eqn.primitive.name for eqn in _equations(jaxpr))


@pytest.mark.parametrize("shape,queries,tenants", [
    ((10, 102_500), 2500, None),  # cluster-100k.churn5
    ((10, 102_500), 16, None),    # cluster-100k.trickle's padded small wave
    ((10, 2000), 242, 128),       # paper-fleet-2k.bootstrap
])
def test_the_placement_builds_no_order_of_the_rings(shape, queries, tenants):
    """Traced over shapes alone: the gatekeepers' jaxpr holds no scatter, no
    scan and no sort (``_alive_first_order``'s, which it ran for every ring
    up to PR 49), and no gather whose indices are K·N: the two there are the
    J joiners' own positions and the J answers' slots. What reads the K·N
    positions is ONE single-word maximum a query and one a ring."""
    fn, lead = predecessor_of_keys, ()
    if tenants is not None:
        fn, lead = jax.vmap(predecessor_of_keys), (tenants,)
    spec = lambda *dims, dtype=np.int32: jax.ShapeDtypeStruct(lead + dims, dtype)
    k, n = shape
    traced = jax.make_jaxpr(fn)(spec(k, n), spec(k, n), spec(n, dtype=np.bool_), spec(queries))
    eqns = list(_equations(traced.jaxpr))
    names = [eqn.primitive.name for eqn in eqns]
    assert not [
        name for name in names if name.startswith(("scatter", "cum", "sort", "reduce_sum"))
    ], names
    gathers = [eqn for eqn in eqns if eqn.primitive.name == "gather"]
    assert len(gathers) == 2
    for eqn in gathers:  # indices [..., K, J, 1] or [..., J, 1]: never K·N of them
        assert int(np.prod(eqn.invars[1].aval.shape)) <= int(np.prod(lead + (k, queries, 2)))
    reduces = [eqn for eqn in eqns if eqn.primitive.name == "reduce_max"]
    assert sorted(eqn.outvars[0].aval.shape for eqn in reduces) == sorted(
        [lead + (k,), lead + (k, queries)]
    )
    assert [v.aval.shape for v in traced.jaxpr.outvars] == [lead + (k, queries)]
    assert [v.aval.dtype for v in traced.jaxpr.outvars] == [np.int32]


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


#: Ring length -> the pieces a slot takes in the walk's scan word
#: (``rings.ring_walk_pieces``): the benchmark's configurations and the
#: lengths on either side of each step.
WALK_PIECES = [
    (2, 1), (1000, 1), (2000, 1), (65_536, 1), (65_537, 2), (102_500, 2),
    (1_000_000, 2), (2_097_152, 2), (2_097_153, 3), (10_000_000, 3),
    (16_777_216, 3), (16_777_217, 4),
]


@pytest.mark.parametrize("n,pieces", WALK_PIECES)
def test_the_ring_length_gives_the_walks_piece_count(n, pieces):
    # Traced over shapes alone (nothing of ten million slots is made or run):
    # one prefix-max and one suffix-min a piece, and the only gather is
    # ``alive[perm]`` (the walk's; ``_alive_first_order`` reads the same bits
    # since PR 50).
    piece_bits, got = rings.ring_walk_pieces(n)
    assert got == pieces
    slot_bits = 32 - piece_bits
    assert (n - 1) < 1 << slot_bits and pieces * piece_bits >= slot_bits
    traced = jax.make_jaxpr(rings._from_perm_single)(
        jax.ShapeDtypeStruct((n,), np.int32), jax.ShapeDtypeStruct((n,), np.bool_)
    )
    names = list(_primitives(traced.jaxpr))
    assert names.count("cummax") == names.count("cummin") == pieces
    assert names.count("gather") == 1
    assert [v.aval.dtype for v in traced.jaxpr.outvars] == [np.int32] * 3


def test_the_walk_gathers_nothing_by_a_position_it_computed():
    """Lowered text only (no compile): of a function that returns just
    ``obs_idx`` and ``subj_idx`` of one ring at ``[4096]``, the one gather
    left is ``alive[perm]`` and the two scatters are the ``.at[perm].set``.
    On the parent (before PR 37) the same text holds THREE gathers: the two
    more are ``perm[succ_pos]`` and ``perm[pred_pos]``, by the positions its
    own scans had just produced."""
    lowered = jax.jit(lambda p, a: rings._from_perm_single(p, a)[:2]).lower(
        jax.ShapeDtypeStruct((4096,), np.int32), jax.ShapeDtypeStruct((4096,), np.bool_)
    )
    text = lowered.as_text()
    assert text.count('"stablehlo.gather"(') == 1
    assert text.count('"stablehlo.scatter"(') == 2
    assert text.count('"stablehlo.reduce_window"(') == 2  # 4,096 slots: one piece
    gather = next(l for l in text.splitlines() if '"stablehlo.gather"(' in l)
    assert "tensor<4096xi1>" in gather  # it reads the alive mask



# ---------------------------------------------------------------------------
# Liveness by ring position as a lane a view change keeps up to date (PR 50)
# ---------------------------------------------------------------------------


def test_the_view_change_bucket_is_a_function_of_the_slot_count_alone():
    # An eighth of the slots in whole 128-lane tiles, and it holds the
    # largest cut any cell commits (the bootstrap's 242 joiners of 2,000).
    sizes = (1_000, 2_000, 50_000, 102_500, 1_000_000)
    assert [view_change_bucket(n) for n in sizes] == [128, 256, 6_272, 12_928, 125_056]
    for n, largest_cut in zip(sizes, (16, 242, 500, 5_000, 10_000)):
        assert largest_cut <= view_change_bucket(n) < n


def _cut_case(spec, n, alive, bucket, rng):
    """The cut a case names, ``[n]`` bools (the slots whose bit flips)."""
    cut = np.zeros(n, dtype=bool)
    if spec == "one":
        cut[n // 3] = True
    elif spec == "mixed":  # leaves and joins in one cut
        cut[rng.choice(np.flatnonzero(alive), size=min(bucket, n) // 3, replace=False)] = True
        cut[rng.choice(np.flatnonzero(~alive), size=min(bucket, n) // 5, replace=False)] = True
    elif spec == "last_slot":  # where the compaction's filler entries point
        cut[[0, n - 1]] = True
    elif spec == "exactly_the_bucket":
        cut[rng.choice(n, size=min(bucket, n), replace=False)] = True
    elif spec == "one_over_the_bucket":  # the overflow arm (a ring of under 128 slots has none)
        cut[rng.choice(n, size=min(bucket + 1, n), replace=False)] = True
    elif spec == "everybody":
        cut[:] = True
    elif spec != "empty":
        raise ValueError(spec)
    return cut


@pytest.mark.parametrize("cut_spec", [
    "empty", "one", "mixed", "last_slot", "exactly_the_bucket", "one_over_the_bucket",
    "everybody",
])
@pytest.mark.parametrize("n,k,idx,one_at_a_time", [
    (100, 4, np.int8, False),       # the compact engine's index widths
    (100, 4, np.int8, True),
    (2000, 10, np.int16, False),    # paper-fleet-2k's ring, all rings at once
    (2000, 3, np.int16, True),
    (33_000, 2, np.int32, True),    # over RING_AT_A_TIME_SLOTS as it stands
    (31_000, 2, np.int32, False),   # and under it
])
def test_a_cut_flips_its_own_ring_positions(n, k, idx, one_at_a_time, cut_spec, monkeypatch):
    """The lane after a cut, by update, is ``alive2[perm]`` bit for bit, and
    the observer table, by repair, the walk's (PR 52), for cuts at the
    bucket's corners (the overflow arm among them: one member more than it
    holds) and with the compaction's filler entries repeating a slot; and
    the walk fed the lane gives the tables of the walk that gathers. Both
    ring schedules, the compact index widths included."""
    if one_at_a_time != (n >= rings.RING_AT_A_TIME_SLOTS):
        monkeypatch.setattr(rings, "RING_AT_A_TIME_SLOTS", n if one_at_a_time else n + 1)
    rng = np.random.default_rng(n * 7 + k + len(cut_spec))
    key_hi, key_lo = _keys(n, k, rng)
    perm = ring_perms(key_hi, key_lo).astype(idx)
    pos = ring_positions(perm)
    assert pos.dtype == idx
    alive = rng.random(n) < 0.7
    bucket = view_change_bucket(n)
    cut = _cut_case(cut_spec, n, alive, bucket, rng)
    alive2 = alive ^ cut

    @jax.jit  # traced after the patch
    def commit(table, lane, perm, pos, alive2, cut):
        lane2, table2, took_dense = ring_tables_after_cut(table, lane, perm, pos, alive2, cut)
        fed = ring_topology_from_perm(perm, alive2, lane2)
        return lane2, table2, took_dense, fed, ring_topology_from_perm(perm, alive2)

    table = ring_topology_from_perm(perm, alive).obs_idx.astype(idx)
    loops = [
        name for name in _primitives(jax.make_jaxpr(commit)(
            table, ring_liveness(perm, alive), perm, pos, alive2, cut).jaxpr)
        if name in ("scan", "while")
    ]
    assert bool(loops) == one_at_a_time
    lane = ring_liveness(perm, alive)
    assert lane.dtype == np.bool_ and lane.shape == perm.shape
    np.testing.assert_array_equal(np.asarray(lane), alive[np.asarray(perm)])
    lane2, table2, took_dense, fed, gathering = commit(table, lane, perm, pos, alive2, cut)
    np.testing.assert_array_equal(np.asarray(lane2), alive2[np.asarray(perm)])
    assert bool(took_dense) == (int(cut.sum()) > bucket)
    for got, want in zip(fed, gathering):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    want = ring_topology(key_hi, key_lo, alive2)
    np.testing.assert_array_equal(np.asarray(fed.obs_idx), np.asarray(want.obs_idx))
    assert table2.dtype == idx
    np.testing.assert_array_equal(np.asarray(table2), np.asarray(want.obs_idx))


def test_the_overflow_arm_stays_a_conditional_under_a_named_vmap():
    """Under a ``vmap`` that names its axis the choice between the update and
    the whole rebuild is ONE conditional for the batch, opened by the members
    whose cut is both too large and committed; unnamed, it is a select (no
    conditional left: both forms run for everybody). Per committing member
    the result is the rebuild's either way, lane and observer table."""
    tenants, n, k = 3, 1000, 3
    rng = np.random.default_rng(50)
    bucket = view_change_bucket(n)
    perms, alives, cuts = [], [], []
    for size in (5, bucket + 1, bucket + 7):  # one tenant fits, two overflow
        perms.append(np.asarray(ring_perms(*_keys(n, k, rng))))
        alives.append(rng.random(n) < 0.8)
        cut = np.zeros(n, dtype=bool)
        cut[rng.choice(n, size=size, replace=False)] = True
        cuts.append(cut)
    perm, alive, cut = (jnp.asarray(np.stack(x)) for x in (perms, alives, cuts))
    pos = jax.vmap(ring_positions)(perm)
    lane = jax.vmap(ring_liveness)(perm, alive)
    table = jax.vmap(lambda p, a: ring_topology_from_perm(p, a).obs_idx)(perm, alive)
    alive2 = alive ^ cut
    want = jax.vmap(ring_liveness)(perm, alive2)
    want_table = jax.vmap(lambda p, a: ring_topology_from_perm(p, a).obs_idx)(perm, alive2)

    def commit(axis):
        def one(table, lane, perm, pos, alive2, cut, commits):
            return ring_tables_after_cut(table, lane, perm, pos, alive2, cut, axis, commits)
        return jax.jit(jax.vmap(one, axis_name=axis))

    def conditionals(fn, *args):
        return [p for p in _primitives(jax.make_jaxpr(fn)(*args).jaxpr) if p == "cond"]

    for commits in ([True, True, True], [True, False, True], [True, False, False]):
        args = (table, lane, perm, pos, alive2, cut, jnp.asarray(commits))
        got, got_table, took_dense = commit("tenants")(*args)
        kept = np.asarray(commits)  # a member that does not commit is dropped by its caller
        np.testing.assert_array_equal(np.asarray(got)[kept], np.asarray(want)[kept])
        np.testing.assert_array_equal(np.asarray(got_table)[kept], np.asarray(want_table)[kept])
        assert took_dense.tolist() == [False, commits[1], commits[2]]
        assert len(conditionals(commit("tenants"), *args)) == 1
    got, got_table, _ = commit(None)(*args)
    np.testing.assert_array_equal(np.asarray(got)[kept], np.asarray(want)[kept])
    np.testing.assert_array_equal(np.asarray(got_table)[kept], np.asarray(want_table)[kept])
    assert conditionals(commit(None), *args) == []


# ---------------------------------------------------------------------------
# The observer table as a lane a view change repairs (PR 52)
# ---------------------------------------------------------------------------


def _repair_case(spec, n, perm, rng):
    """``(alive before, cut)`` a case names. ``perm`` is the ``[k, n]`` key
    order; with ``_keys`` slot 0 is first and slot n - 1 last on EVERY ring,
    so the cases that speak of the ring's two ends hold on all of them."""
    alive, cut = rng.random(n) < 0.8, np.zeros(n, dtype=bool)
    if spec == "three_neighbours_removed":  # a run of cut positions, on each ring its own
        for ring, start in enumerate(rng.choice(n - 3, size=perm.shape[0], replace=False)):
            run = perm[ring, start:start + 3]
            alive[run], cut[run] = True, True
    elif spec == "joiner_between_two_removed":
        for ring, start in enumerate(rng.choice(n - 3, size=perm.shape[0], replace=False)):
            run = perm[ring, start:start + 3]
            alive[run], cut[run] = [True, False, True], True
    elif spec == "both_ends_leave":  # the wrap on both sides
        alive[[0, n - 1]], cut[[0, n - 1]] = True, True
    elif spec == "both_ends_join":
        alive[[0, n - 1]], cut[[0, n - 1]] = False, True
    elif spec == "first_leaves_last_joins":
        alive[[0, n - 1]], cut[[0, n - 1]] = [True, False], True
    elif spec in ("none_alive_before", "one_alive_before", "two_alive_before"):
        alive[:] = False
        alive[rng.choice(n, size=("none", "one", "two").index(spec.split("_")[0]), replace=False)] = True
        cut[rng.choice(np.flatnonzero(~alive), size=5, replace=False)] = True
    elif spec in ("none_alive_after", "one_alive_after", "two_alive_after"):
        alive[:] = False
        alive[rng.choice(n, size=6, replace=False)] = True
        left = ("none", "one", "two").index(spec.split("_")[0])
        cut[rng.choice(np.flatnonzero(alive), size=6 - left, replace=False)] = True
    elif spec == "two_replaced_by_two":
        alive[:] = False
        alive[rng.choice(n, size=2, replace=False)] = True
        cut[alive] = True
        cut[rng.choice(np.flatnonzero(~alive), size=2, replace=False)] = True
    else:
        raise ValueError(spec)
    return alive, cut


_REPAIR_SHAPES = [
    (64, 3, np.int8, True),        # n an exact power of two, both schedules,
    (256, 10, np.int16, False),    # the compact engine's index widths
    (4096, 2, np.int16, True),
    (1000, 3, np.int32, False),    # paper-fleet-1k's ring
    (33_000, 2, np.int32, True),   # over RING_AT_A_TIME_SLOTS as it stands
    (66_000, 2, np.int32, True),   # two pieces a slot in the walk's word: a look-up a neighbour
]


@pytest.mark.parametrize("spec", [
    "three_neighbours_removed", "joiner_between_two_removed", "both_ends_leave",
    "both_ends_join", "first_leaves_last_joins", "none_alive_before", "one_alive_before",
    "two_alive_before", "none_alive_after", "one_alive_after", "two_alive_after",
    "two_replaced_by_two",
])
@pytest.mark.parametrize("n,k,idx,one_at_a_time", _REPAIR_SHAPES)
def test_a_cut_repairs_only_the_observers_it_changed(n, k, idx, one_at_a_time, spec, monkeypatch):
    """The observer table by repair is the walk's on the same ``(perm,
    alive2)``, entry for entry, where the cut's positions are neighbours,
    hold both ends of the ring, or leave the ring at the floor of two alive;
    under it, before or after the cut, every entry changes to or from -1 and
    the commit takes the rebuild and says so."""
    if one_at_a_time != (n >= rings.RING_AT_A_TIME_SLOTS):
        monkeypatch.setattr(rings, "RING_AT_A_TIME_SLOTS", n if one_at_a_time else n + 1)
    rng = np.random.default_rng(n * 11 + k + len(spec))
    key_hi, key_lo = _keys(n, k, rng)
    perm = ring_perms(key_hi, key_lo).astype(idx)
    pos = ring_positions(perm)
    alive, cut = _repair_case(spec, n, np.asarray(perm), rng)
    alive2 = alive ^ cut
    table = ring_topology_from_perm(perm, alive).obs_idx.astype(idx)
    commit = jax.jit(ring_tables_after_cut)  # traced after the patch
    loops = {
        name for name in _primitives(
            jax.make_jaxpr(ring_tables_after_cut)(table, ring_liveness(perm, alive), perm, pos, alive2, cut).jaxpr)
        if name in ("scan", "while")
    }
    assert bool(loops) == one_at_a_time
    lane2, table2, took_dense = commit(table, ring_liveness(perm, alive), perm, pos, alive2, cut)
    assert bool(took_dense) == (alive.sum() < 2 or alive2.sum() < 2), spec
    assert table2.dtype == idx
    np.testing.assert_array_equal(np.asarray(lane2), alive2[np.asarray(perm)])
    want = ring_topology(key_hi, key_lo, alive2).obs_idx
    np.testing.assert_array_equal(np.asarray(table2), np.asarray(want))
    if alive2.sum() < 2:
        assert (np.asarray(table2) == -1).all()


@pytest.mark.parametrize("idx", [np.int16, np.int32])
def test_the_repair_is_right_for_any_slot_but_a_joiner_still_pending(idx):
    """The repair's two writes hold for ANY slot of the bucket, changed by the
    cut or not: slots may repeat and may name members and dead slots the cut
    left alone. The one exception is a joiner still pending after the cut (a
    filler entry of the compaction may name one: it names slot n - 1): its
    column holds its gatekeepers, the repair writes -1 there, and the commit's
    select on ``still_pending`` keeps the gatekeepers in both lanes."""
    n, k = 1000, 3
    rng = np.random.default_rng(52)
    key_hi, key_lo = _keys(n, k, rng)
    perm = ring_perms(key_hi, key_lo).astype(idx)
    pos = ring_positions(perm)
    alive = rng.random(n) < 0.7
    joiner = n - 1  # pending before and after the cut: where the filler entries point
    alive[joiner] = False
    gatekeepers = np.asarray(predecessor_of_keys(pos, perm, alive, np.asarray([joiner])))[:, 0]
    assert (gatekeepers >= 0).all()
    table = np.array(ring_topology_from_perm(perm, alive).obs_idx.astype(idx))
    table[:, joiner] = gatekeepers
    cut = np.zeros(n, dtype=bool)
    cut[rng.choice(np.flatnonzero(alive), size=7, replace=False)] = True
    cut[rng.choice(np.flatnonzero(~alive)[:-1], size=2, replace=False)] = True  # two joiners admitted
    alive2 = alive ^ cut
    want = np.asarray(ring_topology_from_perm(perm, alive2).obs_idx)
    still_pending = np.arange(n) == joiner

    bystanders = np.concatenate([
        rng.choice(np.flatnonzero(alive & ~cut), size=6), rng.choice(np.flatnonzero(~alive2)[:-1], size=6)])
    slots = jnp.asarray(np.concatenate(
        [np.flatnonzero(cut), np.flatnonzero(cut)[::-1], bystanders, [joiner] * 3]).astype(np.int32))
    at, stays = pos[:, slots].astype(jnp.int32), jnp.asarray(alive2)[slots]
    repaired = np.asarray(rings._repair_observers(
        jnp.asarray(table), ring_liveness(perm, alive2), perm, at, slots, stays))
    assert repaired.dtype == idx
    np.testing.assert_array_equal(repaired[:, ~still_pending], want[:, ~still_pending])
    assert (repaired[:, joiner] == -1).all()  # write 1 over the gatekeepers: the exception

    # through the commit's compaction, whose filler entries name slot n - 1
    assert int(np.asarray(rings.first_set_slots(jnp.asarray(cut), view_change_bucket(n)))[-1]) == joiner
    _, repaired, took_dense = ring_tables_after_cut(
        jnp.asarray(table), ring_liveness(perm, alive), perm, pos, alive2, cut)
    assert not bool(took_dense)
    assert (np.asarray(repaired)[:, joiner] == -1).all()
    for lane in (table, np.where(np.arange(n) < 5, -7, table).astype(idx)):  # inval_obs; an obs_idx a leave rewrote
        kept = np.where(still_pending[None, :], lane, np.asarray(repaired))
        np.testing.assert_array_equal(kept, np.where(still_pending[None, :], lane, want))
        np.testing.assert_array_equal(kept[:, joiner], lane[:, joiner])
