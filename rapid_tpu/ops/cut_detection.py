"""Batched multi-node cut detection on device.

The reference tallies alerts one at a time through hash maps
(``MultiNodeCutDetector.java:84-128``); here the whole detector state is a
dense ``reports[N, K]`` bool matrix and one batch of alerts is processed by a
single fused kernel: OR-in the new reports (per-(subject, ring) dedup is the
OR), row-sum the tallies, apply the H/L watermark, run the implicit
edge-invalidation pass (``MultiNodeCutDetector.java:137-164``), and re-check.

Per-batch semantics match the union-of-proposals the membership service
consumes per BatchedAlertMessage (``MembershipService.java:300-354``): a
proposal is released iff at least one subject is past H and none sits in
[L, H) after implicit invalidation.

Two grains live here:

- :func:`process_alert_batch` — ONE detector over ``[n, k]`` report bools
  (the host-twin / single-receiver grain);
- :func:`cohort_watermark_pass` — C independent detectors batched over a
  leading cohort axis of uint32 ring bitmasks (the engine's round-body
  grain, formerly ``virtual_cluster._cohort_cut_detection``). The cohort
  dimension is a REAL mesh axis on the 2-D ``('cohort', 'nodes')`` engine
  mesh: everything in the pass is either elementwise on ``[c, n]``
  (shard-local) or a per-cohort reduction over the node axis (a psum over
  node-axis subgroups) — on a mesh nothing reduces or gathers over the
  cohort axis, so per-device watermark state is ``[c/dc, n/dn]``, not
  ``[c, n]`` (the one-device programs' compacted invalidation arm does
  reduce over it; the mesh's programs do not trace that form). The
  cross-cohort work (3N/4 quorum count, winner selection, classic
  fallback) lives in the consensus tally, not here.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from rapid_tpu.ops.pallas_kernels import (
    _popcount32,
    watermark_merge_classify_impl,
)
from rapid_tpu.utils.dispatch import cond_across, scope


class CutState(NamedTuple):
    """reports[n, k] — per-(subject, ring) report bits; seen_down — whether any
    DOWN alert was applied since the last clear (gates invalidation, matching
    MultiNodeCutDetector.java:139-142); released[n] — subjects already emitted
    in an earlier batch's proposal (the reference clears its proposal set on
    release, MultiNodeCutDetector.java:120-121, so they must not re-propose)."""

    reports: jnp.ndarray
    seen_down: jnp.ndarray
    released: jnp.ndarray

    @staticmethod
    def create(n: int, k: int) -> "CutState":
        return CutState(
            reports=jnp.zeros((n, k), dtype=bool),
            seen_down=jnp.zeros((), dtype=bool),
            released=jnp.zeros((n,), dtype=bool),
        )


class CutResult(NamedTuple):
    state: CutState
    propose: jnp.ndarray  # scalar bool: a cut is ready
    proposal_mask: jnp.ndarray  # [n] bool: members of the cut (when propose)
    tally: jnp.ndarray  # [n] int32 report counts (diagnostics)


@partial(jax.jit, static_argnames=("h", "l"))
def process_alert_batch(
    state: CutState,
    new_reports: jnp.ndarray,
    batch_has_down: jnp.ndarray,
    inval_obs_idx: jnp.ndarray,
    subject_mask: jnp.ndarray,
    h: int,
    l: int,
) -> CutResult:
    """Apply one batch of alerts.

    new_reports:    [n, k] bool — report bits to OR in (dedup via OR).
    batch_has_down: scalar bool — batch contained any DOWN alert.
    inval_obs_idx:  [k, n] int32 — per (ring, subject): the slot whose own
                    failure implies this edge (observer for present nodes,
                    expected observer for joiners); -1 disables.
    subject_mask:   [n] bool — slots that may legitimately be reported on
                    (present members + pending joiners).
    """
    n, k = state.reports.shape
    reports = (state.reports | new_reports) & subject_mask[:, None]
    seen_down = state.seen_down | batch_has_down

    tally = jnp.sum(reports, axis=1, dtype=jnp.int32)
    stable = tally >= h
    flux = (tally >= l) & (tally < h)
    # Pending-stable only: subjects released in an earlier batch left the
    # reference's proposal set (MultiNodeCutDetector.java:120-121) and no
    # longer legitimize implicit edges.
    in_union = (stable & ~state.released) | flux

    # Implicit edge invalidation: for every subject in flux, edges whose
    # (expected) observer is itself failing/joining are auto-reported. The
    # union (stable | flux) is invariant under the pass, so one masked OR is
    # the fixpoint (see MultiNodeCutDetector.java:146-159).
    obs = inval_obs_idx.T  # [n, k]
    obs_in_union = jnp.where(obs >= 0, in_union[jnp.clip(obs, 0, n - 1)], False)
    implicit = flux[:, None] & obs_in_union
    reports = jnp.where(seen_down, reports | implicit, reports) & subject_mask[:, None]

    tally2 = jnp.sum(reports, axis=1, dtype=jnp.int32)
    stable2 = tally2 >= h
    flux2 = (tally2 >= l) & (tally2 < h)
    fresh_stable = stable2 & ~state.released
    propose = jnp.any(fresh_stable) & ~jnp.any(flux2)
    proposal_mask = fresh_stable & propose

    return CutResult(
        state=CutState(
            reports=reports,
            seen_down=seen_down,
            released=state.released | proposal_mask,
        ),
        propose=propose,
        proposal_mask=proposal_mask,
        tally=tally2,
    )


#: Lanes of a vector tile: the bucket is whole tiles, and the compaction ranks
#: the slots by rows of this many.
_LANES = 128


def invalidation_bucket(n: int) -> int:
    """Slots the compacted form of the implicit-invalidation arm holds: a
    sixteenth of the ``n`` slots, rounded up to whole 128-lane tiles. Only
    crashed, faulty or joining subjects can be in flux, and the densest
    traffic any cell sends has 2.4 % of its slots there at once (10,000 of
    1,000,000, 2,492 of 102,500, 500 of 50,000); a round with more takes
    the dense arm."""
    return -(-n // (16 * _LANES)) * _LANES


def _count_below(table: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """For every ``t[j]``: how many entries of ``table[j]`` (or of the one
    row all share) lie below it. Ascending rows: the insertion point."""
    return jnp.sum(table < t[:, None], axis=1, dtype=jnp.int32)


def first_set_slots(need: jnp.ndarray, cap: int) -> jnp.ndarray:
    """The positions of the first ``cap`` set entries of ``need[n]``, in
    order; entries past the count of set ones are some slot of ``[0, n)``.

    The form is the chip's (PERF.md section 6, PR 45: 0.52 ms at
    ``[1000000]`` where ``jnp.nonzero(size=)``'s N-update scatter-add takes
    9.0, a rank scatter 6.1, a binary search of the ranks 8.5 and a sort
    1.3): one running count over the slots laid out in rows of 128, then
    the j-th set slot is looked up, not scattered to: its row is how many
    rows END below j + 1 (a dense compare against the row ends, through one
    more level of 128 rows where there are many), its lane how many of that
    row's counts lie below j + 1. Two row gathers of ``cap`` rows and
    compares, no update per slot."""
    n = need.shape[0]
    rows = -(-n // _LANES)
    rank = jnp.cumsum(
        jnp.pad(need, (0, rows * _LANES - n)).astype(jnp.int32)
    ).reshape(rows, _LANES)
    t = jnp.arange(1, cap + 1, dtype=jnp.int32)
    ends = rank[:, -1]
    if rows <= 8 * _LANES:
        row = _count_below(ends[None, :], t)
    else:
        groups = -(-rows // _LANES)
        ends = jnp.pad(
            ends, (0, groups * _LANES - rows),
            constant_values=jnp.iinfo(jnp.int32).max,
        ).reshape(groups, _LANES)
        group = jnp.minimum(_count_below(ends[None, :, -1], t), groups - 1)
        row = group * _LANES + _count_below(ends[group], t)
    row = jnp.minimum(row, rows - 1)
    return jnp.minimum(row * _LANES + _count_below(rank[row], t), n - 1)


def cohort_watermark_pass(
    report_bits: jnp.ndarray,
    new_bits: jnp.ndarray,
    seen_down: jnp.ndarray,
    released: jnp.ndarray,
    announced: jnp.ndarray,
    subject_mask: jnp.ndarray,
    inval_obs: jnp.ndarray,
    heard_down: jnp.ndarray,
    h,  # Python int or traced int32 (per-tenant fleet watermarks)
    l,
    k: int,
    batch_axis=None,
    dense_invalidation: bool = False,
):
    """Batched per-cohort watermark pass over uint32 ring-report bitmasks
    (:func:`process_alert_batch` semantics over a leading cohort axis, gated
    by the per-configuration announced-proposal flag,
    MembershipService.java:318-348).

    report_bits/released: ``[c, n]`` per-cohort detector state;
    seen_down/announced/heard_down: ``[c]`` cohort lanes; subject_mask:
    ``[n]``; inval_obs: ``[k, n]``. Returns ``(report_bits, released,
    announced, seen_down, propose, proposal_mask, invalidation_ran,
    invalidation_own)``. ``invalidation_ran``: whether the implicit-invalidation
    arm ran in this pass (under a named ``batch_axis``: for the batch).
    ``invalidation_own``, two bools, is what the telemetry plane adds up, and
    a member's own under any ``vmap``: whether THESE detectors needed the arm,
    and whether they took the dense loop for it (their subjects in flux
    overflowed the bucket; or always, in a program that traces no other form).

    Sharding discipline (the 2-D mesh contract): the merge + popcount + H/L
    classification is plain elementwise jnp on ``[c, n]`` — XLA's own
    fusion measured faster than a hand-written Mosaic version at engine
    shapes (ops/pallas_kernels.py module docstring) and it partitions
    shard-locally on a ``('cohort', 'nodes')`` mesh. The per-cohort
    release/propose decisions are reductions over the NODE axis only
    (per-shard psums). The implicit-invalidation arm only runs when some
    cohort actually has subjects in flux after a DOWN event (lax.cond): in
    pure crash/join rounds every subject jumps straight past H, so the
    expensive gather is skipped — and on the mesh the gathered traffic
    stays cond-gated. The arm has two forms:

    - the DENSE loop: K ``[c, n]`` gathers ``in_union[:, inval_obs[ring]]``
      over all n slots and one ``[c, n]`` merge. It reduces over no axis
      and gathers along the node axis only, so it is the form every program
      of ``parallel/mesh.sharded_program`` takes (``dense_invalidation=True``
      traces it alone: the mesh's programs are the programs they were);
    - the COMPACTED form, every one-device program's: the arm's result is
      zero wherever the subject is not in flux in some cohort that has seen
      a DOWN report, and only crashed, faulty or joining subjects can be.
      It reduces ``flux & seen_down`` over the COHORT axis to ``need[n]``,
      compacts the set slots over the NODE axis into one bucket of
      :func:`invalidation_bucket` slots, looks the K observers up for those
      alone and writes the bits back at them. Both steps are global over an
      axis a mesh shards (a replicated compaction of a 10 M-slot ring would
      cost what it saves), which is why the mesh keeps the dense loop. A
      round with more slots in flux than the bucket holds takes the dense
      loop (a nested ``lax.cond``), so the result is exact at any activity.

    ``batch_axis`` names the batch axis of an enclosing ``vmap`` (the two
    meshless fleet programs of ``tenancy/fleet.py`` hand one). An unnamed
    ``vmap`` makes the conditional a select: the K ``[c, n]`` gathers run
    for every tenant in every round (and a nested conditional would run
    both forms, so those programs pass ``dense_invalidation=True`` too).
    Named, the conditional is taken on "some tenant needs it"
    (:func:`cond_across`) and ``invalidation_ran`` is that scalar: a round
    in which no tenant has a subject in flux skips the gathers for the whole
    fleet; the choice of form goes the same way ("some tenant overflows its
    bucket" takes the dense loop for that round, selected per tenant). The
    fleet's mesh programs name none, because there that any() would be a
    collective across the ``'tenant'`` axis.
    """
    c, n = report_bits.shape
    # The impl, not the jitted wrapper: the tenant fleet vmaps this pass
    # with TRACED per-tenant h/l, which a static-argnames jit would reject;
    # inside the engine's traces the wrapper was inlined anyway, so the
    # compiled program is unchanged.
    report_bits, cls = watermark_merge_classify_impl(
        report_bits,
        new_bits,
        jnp.broadcast_to(subject_mask[None, :], (c, n)),
        h,
        l,
    )
    seen_down = seen_down | heard_down  # [c]
    stable = cls == 2
    flux = cls == 1

    def dense(report_bits):
        # Implicit edge invalidation (MultiNodeCutDetector.java:137-164): the
        # union (pending-stable | flux) is invariant under the pass, so one
        # masked OR is the fixpoint. Already-released subjects left the
        # pending set (MultiNodeCutDetector.java:120-121) and no longer
        # legitimize implicit edges. Per-ring loop: [c, n] gathers, never a
        # [c, n, k] materialization (C can be in the hundreds).
        in_union = (stable & ~released) | flux  # [c, n]
        # Accumulate at the report lane's own dtype (uint8/uint16 under the
        # compact policy, K <= 8*itemsize by construction): a uint32
        # operand would silently re-widen the whole [c, n] lane.
        bdt = report_bits.dtype
        implicit_bits = jnp.zeros((c, n), dtype=bdt)
        for ring in range(k):
            obs_r = inval_obs[ring]  # [n]
            gathered = in_union[:, jnp.clip(obs_r, 0, n - 1)]  # [c, n]
            implicit_r = flux & gathered & (obs_r >= 0)[None, :] & seen_down[:, None]
            implicit_bits = implicit_bits | (
                implicit_r.astype(bdt) << jnp.asarray(ring, bdt)
            )
        merged = report_bits | implicit_bits
        return jnp.where(subject_mask[None, :], merged, 0)

    @scope("invalidation")
    def with_implicit(report_bits):
        if dense_invalidation:
            return dense(report_bits)
        # Only a subject in flux in SOME cohort that has seen a DOWN report
        # can gain a bit: compact those slots into one bucket and look the
        # K observers up for them alone. (Reduced here and not beside the
        # predicate below: a quiet round pays for nothing of it.) ``flux``
        # lies inside ``subject_mask`` already (a tally of l >= 1 needs a
        # merged bit and the merge is masked); the AND makes the write-back
        # need no mask of its own whatever l is.
        need = jnp.any(flux & seen_down[:, None], axis=0) & subject_mask  # [n]
        count = jnp.sum(need, dtype=jnp.int32)
        cap = invalidation_bucket(n)

        def compacted(report_bits):
            # The dense loop's pass for the ``cap`` slots ``idx`` names
            # alone, all K rings in one ``[c, k, cap]`` look-up (ten
            # sixteenths of a ``[c, n]`` lane). A padding row (``live``
            # false) names slot 0 and is disarmed, so it carries zero bits,
            # and the write-back ADDS the bits a slot lacks: the OR for a
            # slot named once, nothing for a padding row, whatever a backend
            # makes of a repeated index.
            live = jnp.arange(cap, dtype=jnp.int32) < count
            idx = jnp.where(live, first_set_slots(need, cap), 0)
            in_union = (stable & ~released) | flux  # [c, n]
            bdt = report_bits.dtype
            armed = flux[:, idx] & seen_down[:, None] & live[None, :]  # [c, cap]
            obs = inval_obs[:, idx]  # [k, cap]
            gathered = in_union[:, jnp.clip(obs, 0, n - 1)]  # [c, k, cap]
            implicit = armed[:, None, :] & gathered & (obs >= 0)[None]
            ring_bit = jnp.arange(k, dtype=bdt)[None, :, None]
            implicit_bits = jnp.bitwise_or.reduce(
                implicit.astype(bdt) << ring_bit, axis=1
            )
            return report_bits.at[:, idx].add(implicit_bits & ~report_bits[:, idx])

        overflows = count > cap
        merged, _ = cond_across(batch_axis, overflows, dense, compacted, report_bits)
        return merged, overflows

    need_invalidation = jnp.any(flux & seen_down[:, None])
    if dense_invalidation:
        # the conditional of before, output for output: no flag rides it
        report_bits, invalidation_ran = cond_across(
            batch_axis, need_invalidation, with_implicit,
            scope("invalidation_skip")(lambda r: r), report_bits,
        )
        ran_dense = need_invalidation
    else:
        (report_bits, ran_dense), invalidation_ran = cond_across(
            batch_axis, need_invalidation, with_implicit,
            scope("invalidation_skip")(lambda r: (r, jnp.bool_(False))), report_bits,
        )

    tally2 = _popcount32(report_bits)
    stable2 = tally2 >= h
    flux2 = (tally2 >= l) & (tally2 < h)
    fresh_stable = stable2 & ~released
    propose = ~announced & jnp.any(fresh_stable, axis=1) & ~jnp.any(flux2, axis=1)
    proposal_mask = fresh_stable & propose[:, None]
    return (
        report_bits,
        released | proposal_mask,
        announced | propose,
        seen_down,
        propose,
        proposal_mask,
        invalidation_ran,
        (need_invalidation, ran_dense),
    )


def telemetry_cut_masks(
    prev_bits: jnp.ndarray,
    new_bits: jnp.ndarray,
    final_bits: jnp.ndarray,
    subject_mask: jnp.ndarray,
    h,
    l,
):
    """Telemetry-plane observation of one :func:`cohort_watermark_pass`:
    ``(active[c, n], invalidated[c, n])`` bool masks, derived purely from
    the pass's inputs and outputs so the pass itself (including its
    cond-gated implicit-invalidation branch) stays byte-identical whether
    or not telemetry observes it.

    ``active``     — slots with nonzero report bits or a watermark tally in
                     the ``[l, h)`` flux band (the ISSUE's active-subject
                     definition; the quantity sparse O(activity) rounds
                     will skip work by).
    ``invalidated``— slots that gained report bits the merge did NOT
                     deliver: any bit in ``final_bits`` absent from
                     ``prev_bits | new_bits`` can only have come from the
                     implicit edge-invalidation pass
                     (MultiNodeCutDetector.java:137-164).

    Everything here is elementwise on ``[c, n]`` (plus the existing-grain
    popcount), so on a ``('cohort', 'nodes')`` mesh it is shard-local —
    zero collectives by construction."""
    bdt = final_bits.dtype
    delivered = (prev_bits.astype(bdt) | new_bits.astype(bdt)) & jnp.where(
        subject_mask[None, :], ~jnp.zeros((), dtype=bdt), 0
    )
    tally = _popcount32(final_bits)
    active = (final_bits != 0) | ((tally >= l) & (tally < h))
    invalidated = (final_bits & ~delivered) != 0
    return active, invalidated


def alerts_to_report_matrix(n: int, k: int, dst_idx, ring_numbers) -> jnp.ndarray:
    """Scatter a list of (subject slot, ring) alerts into an [n, k] bool
    matrix. Inputs are index arrays of equal length; negative dst entries are
    ignored (padding)."""
    dst_idx = jnp.asarray(dst_idx, dtype=jnp.int32)
    ring_numbers = jnp.asarray(ring_numbers, dtype=jnp.int32)
    valid = (dst_idx >= 0) & (ring_numbers >= 0) & (ring_numbers < k)
    flat = jnp.where(valid, dst_idx * k + ring_numbers, n * k)
    out = jnp.zeros((n * k + 1,), dtype=bool).at[flat].set(True)
    return out[: n * k].reshape(n, k)
