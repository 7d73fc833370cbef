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
  node-axis subgroups) — nothing reduces or gathers over the cohort axis,
  so per-device watermark state is ``[c/dc, n/dn]``, not ``[c, n]``. The
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
):
    """Batched per-cohort watermark pass over uint32 ring-report bitmasks
    (:func:`process_alert_batch` semantics over a leading cohort axis, gated
    by the per-configuration announced-proposal flag,
    MembershipService.java:318-348).

    report_bits/released: ``[c, n]`` per-cohort detector state;
    seen_down/announced/heard_down: ``[c]`` cohort lanes; subject_mask:
    ``[n]``; inval_obs: ``[k, n]``. Returns ``(report_bits, released,
    announced, seen_down, propose, proposal_mask, invalidation_ran)``.

    Sharding discipline (the 2-D mesh contract): the merge + popcount + H/L
    classification is plain elementwise jnp on ``[c, n]`` — XLA's own
    fusion measured faster than a hand-written Mosaic version at engine
    shapes (ops/pallas_kernels.py module docstring) and it partitions
    shard-locally on a ``('cohort', 'nodes')`` mesh. The per-cohort
    release/propose decisions are reductions over the NODE axis only
    (per-shard psums); nothing here reduces over the cohort axis. The
    implicit-invalidation gather only runs when some cohort actually has
    subjects in flux after a DOWN event (lax.cond): in pure crash/join
    rounds every subject jumps straight past H, so the expensive gather is
    skipped — and on the mesh the gathered traffic stays cond-gated.

    ``batch_axis`` names the batch axis of an enclosing ``vmap`` (the two
    meshless fleet programs of ``tenancy/fleet.py`` hand one). An unnamed
    ``vmap`` makes the conditional a select: the K ``[c, n]`` gathers run
    for every tenant in every round. Named, the conditional is taken on
    "some tenant needs it" (:func:`cond_across`) and ``invalidation_ran``
    is that scalar: a round in which no tenant has a subject in flux skips
    the gathers for the whole fleet. The fleet's mesh programs name none,
    because there that any() would be a collective across the ``'tenant'``
    axis.
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

    @scope("invalidation")
    def with_implicit(report_bits):
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

    need_invalidation = jnp.any(flux & seen_down[:, None])
    report_bits, invalidation_ran = cond_across(
        batch_axis, need_invalidation, with_implicit,
        scope("invalidation_skip")(lambda r: r), report_bits,
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
