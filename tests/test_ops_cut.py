"""Device cut-detection kernel vs the sequential MultiNodeCutDetector oracle.

The device kernel uses end-of-batch semantics: a cut is released iff after the
whole batch (plus implicit invalidation) at least one subject is past H and
none is in [L, H). The sequential oracle is order-sensitive mid-batch, so the
harness feeds it alerts with flux-enders first — the order under which its
union-of-proposals coincides with end-of-batch semantics (see
rapid_tpu/ops/cut_detection.py docstring).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rapid_tpu.ops.cut_detection import (
    CutState,
    first_set_slots,
    alerts_to_report_matrix,
    cohort_watermark_pass,
    invalidation_bucket,
    process_alert_batch,
)
from rapid_tpu.ops.pallas_kernels import watermark_merge_classify_impl
from rapid_tpu.ops.rings import (
    endpoint_ring_keys, predecessor_of_keys, ring_perms, ring_positions, ring_topology,
)
from rapid_tpu.protocol.cut_detector import MultiNodeCutDetector
from rapid_tpu.protocol.view import MembershipView
from rapid_tpu.types import AlertMessage, EdgeStatus, Endpoint, NodeId

K, H, L = 10, 8, 3


def make_world(n_members, n_joiners, seed):
    rng = np.random.default_rng(seed)
    total = n_members + n_joiners
    ports = rng.choice(40000, size=total, replace=False) + 1
    endpoints = [Endpoint(f"10.1.{i % 256}.{i // 256}", int(p)) for i, p in enumerate(ports)]
    members, joiners = endpoints[:n_members], endpoints[n_members:]
    view = MembershipView(K)
    for i, ep in enumerate(members):
        view.ring_add(ep, NodeId(0, i))
    return view, members, joiners, rng


def build_inval_obs(view, members, joiners):
    """[K, n_slots] invalidation-observer table: ring successors for members,
    alive-predecessors (expected observers) for joiner slots."""
    n = len(members)
    key_hi, key_lo = endpoint_ring_keys(members, K)
    alive = np.ones(n, dtype=bool)
    topo = ring_topology(key_hi, key_lo, alive)
    obs = np.asarray(topo.obs_idx)  # [K, n]
    if joiners:
        # joiners hold the slots after the members', not alive yet
        perm = ring_perms(*endpoint_ring_keys(members + joiners, K))
        pred = np.asarray(predecessor_of_keys(
            ring_positions(perm), perm, np.arange(n + len(joiners)) < n,
            np.arange(n, n + len(joiners)),
        ))  # [K, j]
        obs = np.concatenate([obs, pred], axis=1)
    return obs


def run_device(view, members, joiners, alerts):
    slots = members + joiners
    slot_of = {ep: i for i, ep in enumerate(slots)}
    n = len(slots)
    dst_idx, rings = [], []
    has_down = False
    for a in alerts:
        for r in a.ring_numbers:
            dst_idx.append(slot_of[a.edge_dst])
            rings.append(r)
        has_down = has_down or a.edge_status == EdgeStatus.DOWN
    new_reports = alerts_to_report_matrix(n, K, np.array(dst_idx), np.array(rings))
    inval_obs = build_inval_obs(view, members, joiners)
    subject_mask = np.ones(n, dtype=bool)
    result = process_alert_batch(
        CutState.create(n, K),
        new_reports,
        np.asarray(has_down),
        inval_obs,
        subject_mask,
        H,
        L,
    )
    mask = np.asarray(result.proposal_mask)
    return bool(result.propose), {slots[i] for i in range(n) if mask[i]}


def run_oracle(view, alerts):
    """Union-of-proposals per batch + invalidation, as the membership service
    consumes it (MembershipService.java:300-354)."""
    detector = MultiNodeCutDetector(K, H, L)
    proposal = set()
    for a in alerts:
        proposal.update(detector.aggregate(a))
    proposal.update(detector.invalidate_failing_edges(view))
    return bool(proposal), proposal


def order_flux_enders_first(alerts):
    """Sort so subjects whose final tally lands in [L, H) come first."""
    by_dst = {}
    for a in alerts:
        by_dst.setdefault(a.edge_dst, []).append(a)
    flux, other = [], []
    for dst, msgs in by_dst.items():
        rings = {r for m in msgs for r in m.ring_numbers}
        (flux if L <= len(rings) < H else other).append((dst, msgs))
    return [m for _, msgs in flux + other for m in msgs]


def make_alerts(view, subjects_with_counts, status=EdgeStatus.DOWN):
    alerts = []
    for subject, count in subjects_with_counts:
        observers = (
            view.observers_of(subject)
            if view.is_host_present(subject)
            else view.expected_observers_of(subject)
        )
        for ring_number in range(count):
            alerts.append(
                AlertMessage(
                    edge_src=observers[ring_number],
                    edge_dst=subject,
                    edge_status=status,
                    configuration_id=0,
                    ring_numbers=(ring_number,),
                )
            )
    return alerts


@pytest.mark.parametrize("seed", range(8))
def test_randomized_equivalence_members_only(seed):
    view, members, joiners, rng = make_world(40, 0, seed)
    n_subjects = rng.integers(1, 8)
    picks = rng.choice(len(members), size=n_subjects, replace=False)
    subjects = [(members[i], int(rng.integers(1, K + 1))) for i in picks]
    alerts = order_flux_enders_first(make_alerts(view, subjects))

    dev_propose, dev_set = run_device(view, members, joiners, alerts)
    ora_propose, ora_set = run_oracle(view, alerts)
    assert dev_propose == ora_propose
    if dev_propose:
        assert dev_set == ora_set


@pytest.mark.parametrize("seed", range(8))
def test_randomized_equivalence_with_joiners(seed):
    view, members, joiners, rng = make_world(30, 5, 100 + seed)
    picks = rng.choice(len(members), size=3, replace=False)
    subjects = [(members[i], int(rng.integers(1, K + 1))) for i in picks]
    join_subjects = [(j, int(rng.integers(1, K + 1))) for j in joiners[:2]]
    alerts = make_alerts(view, subjects, EdgeStatus.DOWN) + make_alerts(
        view, join_subjects, EdgeStatus.UP
    )
    alerts = order_flux_enders_first(alerts)

    dev_propose, dev_set = run_device(view, members, joiners, alerts)
    ora_propose, ora_set = run_oracle(view, alerts)
    assert dev_propose == ora_propose
    if dev_propose:
        assert dev_set == ora_set


def test_link_invalidation_equivalence():
    # The reference's cutDetectionTestLinkInvalidation scenario on device:
    # dst stuck at H-1 with its remaining observers themselves past H.
    view, members, joiners, _ = make_world(30, 0, 42)
    dst = members[0]
    observers = view.observers_of(dst)
    alerts = []
    for i in range(H - 1):
        alerts.append(
            AlertMessage(observers[i], dst, EdgeStatus.DOWN, 0, (i,))
        )
    failed = set()
    for i in range(H - 1, K):
        failed.add(observers[i])
        oo = view.observers_of(observers[i])
        for j in range(K):
            alerts.append(AlertMessage(oo[j], observers[i], EdgeStatus.DOWN, 0, (j,)))

    dev_propose, dev_set = run_device(view, members, joiners, alerts)
    ora_propose, ora_set = run_oracle(view, alerts)
    assert dev_propose and ora_propose
    assert dev_set == ora_set == failed | {dst}


def test_up_alerts_never_trigger_invalidation():
    view, members, joiners, _ = make_world(25, 3, 5)
    # Joiner stuck in flux; no DOWN alerts anywhere: invalidation must not run.
    alerts = make_alerts(view, [(joiners[0], H - 1)], EdgeStatus.UP)
    dev_propose, _ = run_device(view, members, joiners, alerts)
    ora_propose, _ = run_oracle(view, alerts)
    assert not dev_propose and not ora_propose


def test_released_subjects_do_not_repropose():
    # Reference clears its proposal set on release
    # (MultiNodeCutDetector.java:120-121): a cut released in batch 1 must not
    # reappear in batch 2's proposal.
    view, members, joiners, _ = make_world(20, 0, 8)
    n = len(members)
    inval_obs = build_inval_obs(view, members, [])
    subject_mask = np.ones(n, dtype=bool)
    slot_of = {ep: i for i, ep in enumerate(members)}
    a, b = members[2], members[9]

    m1 = alerts_to_report_matrix(n, K, np.array([slot_of[a]] * H), np.arange(H))
    r1 = process_alert_batch(
        CutState.create(n, K), m1, np.asarray(True), inval_obs, subject_mask, H, L
    )
    assert bool(r1.propose)
    assert {i for i in range(n) if np.asarray(r1.proposal_mask)[i]} == {slot_of[a]}

    m2 = alerts_to_report_matrix(n, K, np.array([slot_of[b]] * H), np.arange(H))
    r2 = process_alert_batch(r1.state, m2, np.asarray(True), inval_obs, subject_mask, H, L)
    assert bool(r2.propose)
    assert {i for i in range(n) if np.asarray(r2.proposal_mask)[i]} == {slot_of[b]}


def test_state_accumulates_across_batches():
    view, members, joiners, _ = make_world(20, 0, 6)
    slots = members
    n = len(slots)
    subject = members[3]
    observers = view.observers_of(subject)
    inval_obs = build_inval_obs(view, members, [])
    subject_mask = np.ones(n, dtype=bool)
    state = CutState.create(n, K)
    slot_of = {ep: i for i, ep in enumerate(slots)}

    # H-1 reports in batch one: no proposal.
    m1 = alerts_to_report_matrix(
        n, K, np.array([slot_of[subject]] * (H - 1)), np.arange(H - 1)
    )
    r1 = process_alert_batch(state, m1, np.asarray(True), inval_obs, subject_mask, H, L)
    assert not bool(r1.propose)
    # The H-th report arrives in batch two: proposal fires from accumulated state.
    m2 = alerts_to_report_matrix(n, K, np.array([slot_of[subject]]), np.array([H - 1]))
    r2 = process_alert_batch(r1.state, m2, np.asarray(True), inval_obs, subject_mask, H, L)
    assert bool(r2.propose)
    assert np.asarray(r2.proposal_mask)[slot_of[subject]]


# -- the cohort pass: the compacted invalidation arm against the dense loop -----
#
# ``cohort_watermark_pass`` looks observers up for the subjects in flux alone
# (one bucket of ``invalidation_bucket(n)`` slots) and keeps the dense loop
# over all n slots for a round that overflows the bucket;
# ``dense_invalidation=True`` traces the dense loop alone (the mesh's programs)
# and is the oracle here: every return bit for bit.

#: (cohorts, slots, report dtype, k, h, l): ragged slot counts, the three
#: report-lane dtypes of the compaction policy.
PASS_SHAPES = {
    "c1_n257_u32": (1, 257, np.uint32, 10, 9, 4),
    "c8_n300_u16": (8, 300, np.uint16, 10, 9, 4),
    "c64_n2500_u16": (64, 2500, np.uint16, 10, 9, 4),
    "c8_n4999_u8": (8, 4999, np.uint8, 8, 7, 3),
}
#: what a case puts in flux: none (the arm is skipped), a few, exactly the
#: bucket, one more (the overflow arm), and a few of which only some cohorts
#: have seen a DOWN report.
PASS_KINDS = ("zero", "few", "at_cap", "over_cap", "some_unseen")


def _pass_case(shape, kind, seed=0):
    """Inputs of one pass in which exactly ``in_flux`` slots have ``flux &
    seen_down`` in some cohort. Everybody else is absent, past H (some of
    them released: they legitimize nothing), or unsubscribed with stray
    bits the merge must clear; ``inval_obs`` has -1 rows and names subjects
    in flux, past H, released and absent alike."""
    c, n, dt, k, h, l = PASS_SHAPES[shape]
    rng = np.random.default_rng([seed, c, n, PASS_KINDS.index(kind)])
    cap = invalidation_bucket(n)
    in_flux = {
        "zero": 0, "few": max(3, n // 100), "at_cap": min(cap, n - 40),
        "over_cap": cap + 1, "some_unseen": max(3, n // 100),
    }[kind]
    order = rng.permutation(n)
    fluxed, stable_, stray = order[:in_flux], order[in_flux:in_flux + 20], order[-15:]

    def bits_of(tally):  # `tally` random ring bits
        rings = np.argsort(rng.random(tally.shape + (k,)), axis=-1)
        return ((rings < tally[..., None]) << np.arange(k)).sum(-1)

    tally = np.zeros((c, n), dtype=np.int64)
    # in flux in cohort 0 always; in the others in flux, past H or absent
    tally[:, fluxed] = rng.choice([0, l, h - 1, h, k], size=(c, in_flux))
    tally[0, fluxed] = rng.integers(l, h, size=in_flux)
    tally[:, stable_] = rng.integers(h, k + 1, size=(c, len(stable_)))
    tally[:, stray] = rng.integers(1, k + 1, size=(c, len(stray)))
    merged = bits_of(tally)
    new = np.where(rng.random((c, n)) < 0.4, merged, merged & bits_of(tally // 2))
    old = np.where(rng.random((c, n)) < 0.4, merged, merged & ~new)
    assert ((old | new) == merged).all()
    subject = np.ones(n, dtype=bool)
    subject[stray] = False
    seen = np.ones(c, dtype=bool)
    heard = np.zeros(c, dtype=bool)
    if kind == "some_unseen" and c > 1:
        seen[1::2] = False  # cohort 0 stays armed
        heard[1] = True  # and one hears its first DOWN in this very pass
    released = np.zeros((c, n), dtype=bool)
    released[:, stable_[:8]] = rng.random((c, 8)) < 0.5
    announced = rng.random(c) < 0.2
    named = np.concatenate([fluxed, stable_, stray, order[in_flux + 20:in_flux + 30]])
    obs = rng.choice(named, size=(k, n)).astype(np.int32)
    obs[rng.random((k, n)) < 0.15] = -1
    obs[:, order[::7]] = -1
    args = (old.astype(dt), new.astype(dt), seen, released, announced, subject, obs, heard)
    return tuple(jnp.asarray(a) for a in args), (k, h, l), in_flux, cap


@functools.lru_cache(maxsize=None)
def _pass_program(k, h, l, dense_invalidation, tenants=False):
    def one(*args):
        return cohort_watermark_pass(
            *args, h, l, k, "tenants" if tenants else None, dense_invalidation
        )

    if not tenants:
        return jax.jit(one)
    return jax.jit(jax.vmap(
        one, axis_name="tenants", out_axes=(*(0,) * 6, None, 0)
    ))


@pytest.fixture(scope="module")
def pass_program():
    """The jitted pass by ``(k, h, l, dense_invalidation, tenants)``; the
    module gives back what it compiled (tier-1 runs near the process's limit
    of memory maps)."""
    yield _pass_program
    _pass_program.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("kind", PASS_KINDS)
@pytest.mark.parametrize("shape", sorted(PASS_SHAPES))
def test_the_compacted_invalidation_arm_is_the_dense_loop_bit_for_bit(pass_program, shape, kind):
    args, (k, h, l), in_flux, cap = _pass_case(shape, kind)
    got = pass_program(k, h, l, False)(*args)
    want = pass_program(k, h, l, True)(*args)
    for name, g, w in zip(
        ("report_bits", "released", "announced", "seen_down", "propose",
         "proposal_mask", "invalidation_ran"), got, want,
    ):
        assert g.dtype == w.dtype and np.array_equal(g, w), name
    ran = bool(got[6])
    assert ran == (in_flux > 0)
    # the dense-only program ran dense whenever it ran; the compacted one
    # only past its bucket, and says so
    assert [bool(flag) for flag in want[7]] == [ran, ran]
    assert [bool(flag) for flag in got[7]] == [ran, in_flux > cap]
    if kind == "over_cap":
        assert [bool(flag) for flag in got[7]] == [True, True]
    # what the write-back leans on: nothing is in flux outside the subject
    # mask (the merge is masked and a tally of l >= 1 needs a merged bit),
    # and exactly `in_flux` slots are armed
    old, new, seen, _, _, subject, _, heard = args
    c, n = old.shape
    merged, cls = watermark_merge_classify_impl(
        old, new, jnp.broadcast_to(subject[None, :], (c, n)), h, l
    )
    flux = np.asarray(cls == 1)
    assert not (flux & ~np.asarray(subject)[None, :]).any()
    armed = (flux & np.asarray(seen | heard)[:, None]).any(axis=0)
    assert int(armed.sum()) == in_flux
    if ran:  # the arm did something to look at: some slot gained a bit
        assert (np.asarray(got[0]) != np.asarray(merged)).any()


@pytest.mark.parametrize("kinds,dense", [
    (("zero", "few", "over_cap"), True),
    (("zero", "few", "at_cap"), False),
    (("zero", "zero", "zero"), None),
])
def test_under_a_named_vmap_one_tenants_overflow_takes_the_dense_arm_for_the_round(
    pass_program, kinds, dense
):
    cases = [_pass_case("c8_n300_u16", kind, seed=7 + t) for t, kind in enumerate(kinds)]
    k, h, l = cases[0][1]
    args = tuple(jnp.stack(leaves) for leaves in zip(*(case[0] for case in cases)))
    got = pass_program(k, h, l, False, tenants=True)(*args)
    want = pass_program(k, h, l, True, tenants=True)(*args)
    for g, w in zip(got[:7], want[:7]):
        assert np.array_equal(g, w)
    # and every tenant is the pass it would be alone
    for t, case in enumerate(cases):
        alone = pass_program(k, h, l, True)(*case[0])
        for g, a in zip(got[:6], alone[:6]):
            assert np.array_equal(g[t], a)
    # the arm ran or not for the batch; who needed it and who overflowed is
    # each tenant's own, as it would be alone
    assert got[6].shape == () and bool(got[6]) == (dense is not None)
    needed, dense_loop = (flags.tolist() for flags in got[7])
    assert needed == [kind != "zero" for kind in kinds]
    assert dense_loop == [kind == "over_cap" for kind in kinds]
    assert [flags.tolist() for flags in want[7]] == [needed, needed]


def test_the_invalidation_bucket_is_a_function_of_the_slot_count_alone():
    # a sixteenth of the slots in whole 128-lane tiles: the cells' sizes
    assert [invalidation_bucket(n) for n in (1_000, 2_000, 50_000, 102_500, 1_000_000)] == [
        128, 128, 3_200, 6_528, 62_592,
    ]
    for n in (1, 127, 2_048, 2_049, 10_000_000):
        cap = invalidation_bucket(n)
        assert cap % 128 == 0 and cap * 16 >= n and (cap - 128) * 16 < n


@pytest.mark.parametrize("n", [1, 127, 128, 129, 4999, 131_072, 131_073, 1_000_000])
def test_the_compaction_looks_the_set_slots_up_in_order(n):
    # one level of rows to 131,072 slots (1,024 rows of 128), two beyond;
    # nothing set, a few, exactly the bucket, more than it, everything
    cap = invalidation_bucket(n)
    compact = jax.jit(first_set_slots, static_argnums=1)
    rng = np.random.default_rng(n)
    for share in (0.0, 0.001, 0.05, None, 0.2, 1.0):
        need = rng.random(n) < share if share is not None else np.zeros(n, dtype=bool)
        if share is None:
            need[rng.choice(n, size=min(cap, n), replace=False)] = True
        want = np.nonzero(need)[0][:cap]
        got = np.asarray(compact(jnp.asarray(need), cap))
        assert got.shape == (cap,) and got.dtype == np.int32
        assert np.array_equal(got[:len(want)], want), share
        assert got.min() >= 0 and got.max() < n  # the rest: some slot, never outside
    jax.clear_caches()
