"""The flagship model: a whole Rapid-style cluster of N virtual endpoints
executing the membership protocol as one fused device program.

One ``engine_step`` = one protocol round for every virtual node at once
(the device analog of ``MembershipService``'s per-message pipeline,
MembershipService.java:300-354):

  probe tick -> edge alerts -> cohort delivery -> watermark cut detection ->
  fast-round votes -> quorum tally -> view-change application.

Everything is static-shaped: membership is an ``alive`` mask, faults are
masks, and the view change is a ``lax.cond`` that re-derives ring topology.
The N axis shards over a device mesh (see rapid_tpu.parallel); every global
reduction here is a sum/any over N, which XLA lowers to psum over ICI.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rapid_tpu.models.state import (
    LINK_LOSS_DEAD,
    TELEMETRY_BUCKETS,
    EngineConfig,
    EngineState,
    FaultInputs,
    LinkFaults,
    StepEvents,
    TelemetryLanes,
    TraceRing,
    compaction_policy,
    initial_state,
    initial_telemetry,
    initial_trace,
)
from rapid_tpu.ops.consensus import tally_candidates, undecided_log2_bucket
from rapid_tpu.ops.cut_detection import cohort_watermark_pass, telemetry_cut_masks
from rapid_tpu.ops.hashing import masked_set_hash, mix32
from rapid_tpu.ops.pallas_kernels import (
    _popcount32,
    delivery_new_bits_pallas,
)
from rapid_tpu.ops.rings import (
    endpoint_ring_keys,
    predecessor_of_keys,
    ring_liveness,
    ring_tables_after_cut,
    ring_topology_from_perm,
)
from rapid_tpu.utils import engine_telemetry, exposition
from rapid_tpu.utils.dispatch import DispatchSeam, cond_across, scope, setup_stage
from rapid_tpu.utils.health import NodeHealth
from rapid_tpu.utils.metrics import Metrics


#: The length a small wave's slot indices are uploaded at
#: (``VirtualCluster._slot_index``): a Poisson stream of 8 events a wave
#: passes it in 0.4 % of its waves.
SMALL_WAVE_SLOTS = 16


def cohort_words(c: int) -> int:
    """uint32 words needed to carry one bit per receiver cohort."""
    return (c + 31) // 32


def _validate_delivery_prob(permille: int) -> None:
    """A negative value would wrap through uint32 in the delivery gate and
    silently behave as p=1; every constructor funnels through this."""
    if not 0 <= permille <= 1000:
        raise ValueError(
            f"delivery_prob_permille must be in [0, 1000], got {permille}"
        )


@scope("edge_masks")
def _edge_masks(cfg: EngineConfig, state: EngineState, faults: FaultInputs):
    """Per-edge observer masks: (observer_active[n,k], blocked_rows[w*k,n]).

    ``blocked_rows`` packs "cohort c cannot hear the observer of edge
    (subject, ring)" bitwise over cohorts — row ``wi*k + ring``, bit j of a
    word covers cohort ``32*wi + j`` — so the hoisted delivery mask costs
    O(K·N·C/32) uint32 instead of O(K·N·C) bools, which is what lets C
    scale to hundreds of independently-diverging receiver cohorts. (Slots
    on the last axis: the layout the delivery kernel tiles over lanes.)
    Both outputs depend only on (topology, faults), fixed between view
    changes, so convergence loops hoist this out of the round body
    entirely.

    An edge looks its observer up ONCE. What it needs of that member sits
    in one per-member ``uint32`` table built here and gone at the return:
    rows ``0..w-1`` are the member's packed ``rx_block`` words and row ``w``
    is its ``active`` bit (``alive & ~crashed``), a row of its own for
    every ``c``, so no word of ``blocked_rows`` ever lends a bit. The
    outputs are those of one gather for each of the two, bit for bit
    (``tests/test_edge_masks.py`` keeps that body as the oracle).
    """
    n, k, c = cfg.n, cfg.k, cfg.c
    w = cohort_words(c)
    obs = state.obs_idx.T  # [n, k] — observer of (subject s, ring k)
    obs_clamped = jnp.clip(obs, 0, n - 1)

    # Pack rx_block over the cohort axis, then gather per observer.
    pad = w * 32 - c
    rxb = jnp.pad(faults.rx_block, ((0, pad), (0, 0))).astype(jnp.uint32)  # [32w, n]
    rxb = rxb.reshape(w, 32, n)
    bit_weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(rxb * bit_weights[None, :, None], axis=1, dtype=jnp.uint32)  # [w, n]
    active = state.alive & ~faults.crashed
    table = jnp.concatenate([words, active[None, :].astype(jnp.uint32)])  # [w + 1, n]
    at_observer = table[:, obs_clamped.T]  # [w + 1, k, n] — THE gather
    observer_active = (obs >= 0) & (at_observer[w] != 0).T
    blocked_rows = at_observer[:w].reshape(w * k, n)
    return observer_active, blocked_rows


@scope("fd_tick")
def _observer_loss(cfg: EngineConfig, state: EngineState, links: LinkFaults):
    """``[n, k]`` uint32: the lane's loss at the OBSERVER of every (subject,
    ring) edge, schedule apart. THE gather of the lane, and a function of the
    lane and the topology alone, which no round of a convergence changes:
    the convergence loop computes it once, before its rounds
    (:func:`_converge`), as every loop hoists the per-edge masks."""
    obs = state.obs_idx.T  # [n, k]
    at_observer = links.loss_permille.astype(jnp.uint32)[
        jnp.clip(obs, 0, cfg.n - 1).astype(jnp.int32)
    ]
    return jnp.where(obs >= 0, at_observer, jnp.uint32(0))


@scope("fd_tick")
def link_probe_draws(
    cfg: EngineConfig, state: EngineState, links: LinkFaults, observer_loss=None
):
    """This round's probe outcomes under the link-fault lane: ``(lost[n, k],
    deaf[n])``. The probe of edge (subject s, ring j, observer o) is lost
    when the request is lost at s's ingress or the reply at o's: two
    independent draws from a hash of (edge, ``round_idx``, configuration
    epoch, the lane's seed) against the two members' loss, the hash-stream
    idiom of :func:`_deliver_alerts`. ``x % 1000`` lies in [0, 999], so
    1000 permille loses every probe and 0 none whatever is drawn. In a round
    of the schedule's off-phase every member's loss is 0. ``deaf`` names the
    members whose ingress is wholly dead in this round: they hear no alert
    and no proposal, so the tally leaves them out.

    A pure function of ``obs_idx``, two scalars of the state and the lane:
    the tests fetch it round by round and replay the detector over it
    (``benchmarks/link_model.py``). ``observer_loss`` is
    :func:`_observer_loss` of the same state and lane where a loop has it
    already; the schedule gates after it."""
    n, k = cfg.n, cfg.k
    period = jnp.maximum(links.on_rounds + links.off_rounds, 1)
    on = (links.off_rounds == 0) | (jnp.remainder(links.age, period) < links.on_rounds)
    if observer_loss is None:
        observer_loss = _observer_loss(cfg, state, links)
    loss_obs = jnp.where(on, observer_loss, jnp.uint32(0))  # [n, k]
    loss = jnp.where(on, links.loss_permille.astype(jnp.uint32), jnp.uint32(0))  # [n]
    round_salt = (
        (state.round_idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
        ^ (state.config_epoch.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
        ^ links.seed
    )
    request = mix32(
        (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(0x85EBCA77))[:, None]
        ^ (jnp.arange(k, dtype=jnp.uint32) * jnp.uint32(0xC2B2AE3D))[None, :]
        ^ round_salt
    )
    # An independent stream for the reply, as the delivery gate's is.
    reply = mix32(request ^ jnp.uint32(0xA511E9B3))
    lost = ((request % jnp.uint32(1000)) < loss[:, None]) | (
        (reply % jnp.uint32(1000)) < loss_obs
    )
    return lost, loss >= LINK_LOSS_DEAD


@scope("fd_tick")
def _fd_tick(
    cfg: EngineConfig, state: EngineState, faults: FaultInputs, observer_active,
    link_lost=None,
):
    """Every observer probes its subjects; edges past the failure threshold
    emit one DOWN alert (semantics of PingPongFailureDetector + the
    edge-failure notification path, MembershipService.java:472-495).

    Two policies (cfg.fd_window): the reference code's cumulative counter,
    or the paper's windowed fraction — a uint32 bit-history per edge, fire
    when >= fd_threshold of the last fd_window probe outcomes failed.
    Intermittent blips age out of the window; the counter latches them."""
    subject_down = faults.crashed[:, None] | faults.probe_fail
    if link_lost is not None:  # the link-fault lane's draws of this round
        subject_down = subject_down | link_lost
    probe_failed = observer_active & subject_down & state.alive[:, None]

    if cfg.fd_window:
        # Windowed mode, matching the host twin exactly: the history shifts
        # only when a probe actually happened (an inactive observer
        # contributes no outcome — implicit successes would decay real
        # failure history), and the edge cannot fire until a FULL window of
        # probes has been observed. fd_count counts PROBES here (its only
        # windowed-mode meaning), so stagger_fd_counts' negative offsets
        # still jitter detection by delaying window-full.
        probed = observer_active & state.alive[:, None]
        fd_count = jnp.where(probed, state.fd_count + 1, state.fd_count)
        # Mask and OR-in at the lane's own (policy) dtype: a uint32 operand
        # here would silently re-widen the whole history lane (the
        # dtype-widening lint class) — fd_window <= 8*itemsize by policy.
        hdt = state.fd_hist.dtype
        window_mask = jnp.asarray((1 << cfg.fd_window) - 1, hdt)
        shifted = ((state.fd_hist << 1) | probe_failed.astype(hdt)) & window_mask
        fd_hist = jnp.where(probed, shifted, state.fd_hist)
        past_threshold = (_popcount32(fd_hist) >= cfg.fd_threshold) & (
            fd_count >= cfg.fd_window
        )
    else:
        # Counter mode (the reference code): fd_count counts FAILURES.
        fd_count = jnp.where(probe_failed, state.fd_count + 1, state.fd_count)
        fd_hist = state.fd_hist
        past_threshold = fd_count >= cfg.fd_threshold
    fire = past_threshold & ~state.fd_fired & state.alive[:, None]
    fd_fired = state.fd_fired | fire
    return fd_count, fd_hist, fd_fired, fire


@scope("deliver")
def _deliver_alerts(cfg: EngineConfig, state: EngineState, fire_round, blocked_rows):
    """Per-cohort delivered alert bitmasks, ``new_bits[c, n]`` (bit k = ring
    k's alert for subject n has reached cohort c).

    The device analog of UnicastToAllBroadcaster + per-receiver arrival
    timing: an alert fired at round f reaches cohort c at round
    ``f + delay(c, edge)`` where the delay is drawn deterministically from a
    hash of (cohort, edge, configuration) in ``[0, delivery_spread]``
    (sub-round granularity via cfg.delivery_prob_permille) — different
    cohorts genuinely hear different alert subsets at any instant, which is
    where almost-everywhere-agreement conflicts come from (paper Fig. 11).
    Delivery is recomputed cumulatively each round (cheap bitwise work); the
    OR-merge into ``report_bits`` makes redelivery idempotent. Materializes
    [c, n] per ring — never [c, n, k]. With cfg.use_pallas the whole
    (cohort-word x ring) loop nest runs as one fused VMEM kernel
    (rapid_tpu.ops.pallas_kernels.delivery_new_bits_pallas, hash-stream
    bit-identical to this path).
    """
    n, k, c = cfg.n, cfg.k, cfg.c
    age_kn = state.round_idx - fire_round.T  # [k, n]; hugely negative if unfired
    if cfg.use_pallas:
        out = delivery_new_bits_pallas(
            blocked_rows,
            age_kn,
            state.config_epoch.astype(jnp.uint32).reshape(1),
            cfg.k,
            cfg.delivery_spread,
            cfg.delivery_prob_permille,
            lanes=cfg.pallas_lanes,
        )
        return out[:c, :]

    c_ids = jnp.arange(c, dtype=jnp.uint32)
    word_idx = (c_ids // 32).astype(jnp.int32)  # [c]
    bit_idx = c_ids % 32  # [c]
    slot_salt = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(0x85EBCA77)
    epoch_salt = state.config_epoch.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)

    # Accumulate at the report lane's own (policy) dtype: K <= 8*itemsize
    # by construction, and a uint32 accumulator would re-widen the merge.
    rdt = state.report_bits.dtype
    new_bits = jnp.zeros((c, n), dtype=rdt)
    for ring in range(k):
        blocked = (blocked_rows[word_idx * k + ring, :] >> bit_idx[:, None]) & 1  # [c, n]
        if cfg.delivery_spread > 0:
            rnd = mix32(
                (c_ids[:, None] * jnp.uint32(0x9E3779B1))
                ^ slot_salt[None, :]
                ^ jnp.uint32((ring * 0xC2B2AE3D) & 0xFFFFFFFF)
                ^ epoch_salt
            )
            if cfg.delivery_prob_permille >= 1000:
                delay = (rnd % jnp.uint32(cfg.delivery_spread + 1)).astype(jnp.int32)
            else:
                # Sub-round skew: delay is nonzero (uniform in
                # [1, delivery_spread]) only with probability p; an
                # independent hash stream gates so magnitude and gate are
                # uncorrelated.
                gate = (mix32(rnd ^ jnp.uint32(0xA511E9B3)) % jnp.uint32(1000)) < jnp.uint32(
                    cfg.delivery_prob_permille
                )
                magnitude = 1 + (rnd % jnp.uint32(cfg.delivery_spread)).astype(jnp.int32)
                delay = jnp.where(gate, magnitude, 0)
        else:
            delay = 0
        delivered = (age_kn[ring][None, :] >= delay) & (blocked == 0)  # [c, n]
        new_bits = new_bits | (delivered.astype(rdt) << jnp.asarray(ring, rdt))
    return new_bits


def delivery_delays(cfg: EngineConfig, config_epoch, slots) -> jnp.ndarray:
    """``int32[c, len(slots), k]``: the rounds after its firing at which the
    alert of edge (``slots[i]``, ring) reaches cohort c in configuration
    ``config_epoch``: the schedule :func:`_deliver_alerts` (and the Mosaic
    kernel, hash-stream identical to it) follows, handed out as data.

    Eager and for set-up alone: a plain reference that replays the detector
    (``benchmarks/detector_model.py``) is handed the network's schedule
    through it. No round program calls it; ``tests/test_grid_fleet.py`` holds
    it to ``_deliver_alerts``' delivered bits round by round."""
    slots = jnp.asarray(slots, dtype=jnp.uint32).reshape(-1)
    shape = (cfg.c, slots.shape[0], cfg.k)
    if cfg.delivery_spread <= 0:
        return jnp.zeros(shape, dtype=jnp.int32)
    rings = jnp.arange(cfg.k, dtype=jnp.uint32)
    rnd = mix32(
        (jnp.arange(cfg.c, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1))[:, None, None]
        ^ (slots * jnp.uint32(0x85EBCA77))[None, :, None]
        ^ (rings * jnp.uint32(0xC2B2AE3D))[None, None, :]
        ^ (jnp.asarray(config_epoch).astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    )
    if cfg.delivery_prob_permille >= 1000:
        return (rnd % jnp.uint32(cfg.delivery_spread + 1)).astype(jnp.int32)
    gate = (mix32(rnd ^ jnp.uint32(0xA511E9B3)) % jnp.uint32(1000)) < jnp.uint32(
        cfg.delivery_prob_permille
    )
    magnitude = 1 + (rnd % jnp.uint32(cfg.delivery_spread)).astype(jnp.int32)
    return jnp.where(gate, magnitude, 0)


@scope("cut_detection")
def _cohort_cut_detection(
    cfg: EngineConfig, state: EngineState, new_bits, heard_down, batch_axis=None,
    dense_arms=False,
):
    """The engine's cut-detection seam: C independent watermark detectors
    batched over the (mesh-sharded) cohort axis. The pass itself lives in
    ``rapid_tpu.ops.cut_detection.cohort_watermark_pass`` (the cohort-grain
    twin of ``process_alert_batch``, with the sharding discipline documented
    there); this wrapper only adapts the state pytree."""
    return cohort_watermark_pass(
        state.report_bits,
        new_bits,
        state.seen_down,
        state.released,
        state.announced,
        state.alive | state.join_pending,
        state.inval_obs,
        heard_down,
        cfg.h,
        cfg.l,
        cfg.k,
        batch_axis,
        dense_arms,
    )


def _compute_round(
    cfg: EngineConfig, state: EngineState, faults: FaultInputs, edge_masks=None,
    telem: Optional[TelemetryLanes] = None,
    trace: Optional[TraceRing] = None,
    *,
    batch_axis=None,
    links: Optional[LinkFaults] = None,
    observer_loss=None,
    paths=None,
    dense_arms=False,
):
    """One protocol round WITHOUT view-change application: returns the
    round-advanced state plus (decided, winner_mask, events). Keeping the
    ring re-sort out of the round body lets the convergence loop run
    sort-free and apply the view change exactly once on exit; loops also
    hoist the per-edge gather by passing precomputed ``edge_masks``.

    ``telem`` (the device telemetry plane, ``cfg.telemetry == 1``): when a
    :class:`TelemetryLanes` pytree is passed, the round accumulates into it
    and the return grows a fifth element — the updated lanes. The branch is
    a PYTHON-level ``if``: with no observer passed no observer code is
    traced. THAT is the invariant that keeps an observers-off program the
    same program, and it is why every round body above this function is
    written once with the observers as optional pytrees (``*observers``)
    and jitted once per observer count, not copied per count. Telemetry
    is write-only — nothing below reads a ``tl_`` lane — so engine results
    are bit-identical on vs off by construction, and every accumulation is
    either an already-computed round scalar or elementwise at the lane's
    native [c, n]/[c] grain: zero new collectives in the round body (the
    cross-shard reductions live in ``telemetry_digest_impl``, dispatched
    only at host-sync boundaries).

    ``trace`` (the device round-trace ring, ``cfg.trace == R > 0``): when a
    :class:`TraceRing` is passed the round also writes ONE per-round record
    into slot ``tr_cursor % R`` and the return grows a sixth element — the
    updated ring. Same discipline as the telemetry plane (a Python-level
    ``if``, write-only lanes, zero new collectives: every record field is a
    scalar the round already computed), and the ring's active-subject count
    reuses the telemetry block's cut-mask reduction — which is why
    ``trace`` requires ``telem`` (trace is a refinement of the telemetry
    plane, enforced at driver construction).

    ``batch_axis`` (the name an enclosing ``vmap`` gave its batch axis; the
    two meshless fleet programs of ``tenancy/fleet.py`` hand one): a third
    Python-level branch. With ``None`` not one traced operation changes. With
    a name the round's three conditionals (``deliver``, ``invalidation``,
    ``classic``) stay conditionals under the ``vmap``, each taken when SOME
    member of the batch needs its arm (``utils/dispatch.cond_across``), and
    the return ends with one more element: ``int32[2]``, whether
    ``invalidation`` and ``classic`` ran in this round, not batched.

    ``links`` (the link-fault lane, :class:`LinkFaults`): a fourth
    Python-level branch. With ``None`` not one traced operation changes.
    With a lane the round draws its probes' outcomes (:func:`link_probe_draws`,
    OR-ed into the detector's tick beside ``faults.probe_fail``), leaves a
    member whose ingress is wholly dead out of ``can_vote``, and the return
    ends with the lane, a round older and with the probes it failed added.
    ``observer_loss`` is the lane's gather where the caller's loop made it
    before its rounds (:func:`_observer_loss`).

    ``paths`` (the consensus-path counts, ``int32[3]`` in the order of
    ``exposition.CONSENSUS_PATH_COUNTERS``): a fifth Python-level branch.
    With ``None`` not one traced operation changes. With the counts the
    return ends with them, after the link-fault lane, three scalars the
    round computed anyway added in: whether the classic attempt ran
    (``fallback_due``), whether it decided, whether the fast round did.

    ``dense_arms`` (handed by a program's builder, never by a user): a
    sixth Python-level branch, one flag for "this program takes no compacted
    form". ``False`` is every one-device program: the ``invalidation`` arm
    looks observers up for the subjects in flux alone and falls back to the
    dense loop when they overflow its bucket
    (``ops/cut_detection.cohort_watermark_pass``), and the view change
    flips its cut's own ring positions and falls back to the whole gather
    likewise (:func:`apply_view_change_impl`). ``True`` traces the dense
    forms alone, the program of before: ``parallel/mesh.sharded_program``
    (a compaction is a global operation over the node axis the mesh shards)
    and the fleet's unnamed-``vmap`` programs (a nested conditional would be
    a select that runs both forms)."""
    n, k, c = cfg.n, cfg.k, cfg.c

    # 1. Failure-detector tick -> fresh DOWN alerts per (subject, ring) edge.
    if edge_masks is None:
        edge_masks = _edge_masks(cfg, state, faults)
    observer_active, blocked_rows = edge_masks
    link_lost = deaf = None
    if links is not None:
        link_lost, deaf = link_probe_draws(cfg, state, links, observer_loss)
        with scope("fd_tick"):
            links = links._replace(
                age=links.age + 1,
                probes_lost=links.probes_lost + jnp.sum(
                    link_lost & observer_active & state.alive[:, None],
                    dtype=jnp.int32,
                ),
            )
    fd_count, fd_hist, fd_fired, fire = _fd_tick(
        cfg, state, faults, observer_active, link_lost
    )
    # Stamp at the lane's (policy) dtype: round_idx is int32 and a bare
    # where() would re-widen the whole [n, k] lane. In-envelope round
    # indices (< fire_never) cast losslessly.
    with scope("fd_tick"):
        fire_round = jnp.where(
            fire, state.round_idx.astype(state.fire_round.dtype), state.fire_round
        )
        alerts_emitted = jnp.sum(fire, dtype=jnp.int32)
        # Whether any fired alert has yet to mature (the delivery gate below).
        fired_any = jnp.any(fd_fired)
        last_mature = (
            jnp.max(jnp.where(fd_fired, fire_round, jnp.int32(-1)))
            + cfg.delivery_spread
        )
        need_delivery = fired_any & (state.round_idx <= last_mature)

    # 2. Broadcast delivery: alert for edge (s, ring) originates at the edge's
    #    observer; cohort c hears it unless that observer is rx-blocked, and
    #    only once the per-(cohort, edge) delivery delay has matured
    #    (the device analog of UnicastToAllBroadcaster + drop interceptors +
    #    arrival-timing skew). Delivered alerts pack straight into
    #    per-subject ring bitmasks.
    #    Delivery work is cond-skipped once every fired alert has matured:
    #    delays and rx-blocks are fixed between view changes, so past
    #    max(fire_round) + spread the delivered mask is static and already
    #    OR-merged into report_bits — recomputing it adds nothing.
    new_bits, _ = cond_across(
        batch_axis,
        need_delivery,
        lambda: _deliver_alerts(cfg, state, fire_round, blocked_rows),
        scope("deliver_skip")(
            lambda: jnp.zeros((c, n), dtype=state.report_bits.dtype)
        ),
    )
    # Alerts for ALIVE subjects are DOWN reports; join-pending subjects'
    # reports are UP and must not arm implicit invalidation.
    with scope("cut_detection"):
        heard_down = jnp.any((new_bits != 0) & state.alive[None, :], axis=1)  # [c]

    # 3. Cut detection per cohort.
    (
        report_bits, released, announced, seen_down, proposed_now, prop_masks,
        invalidation_ran, invalidation_own,
    ) = _cohort_cut_detection(
        cfg, state, new_bits, heard_down, batch_axis, dense_arms
    )
    # Proposal identity = commutative set-hash of the cut's member identities
    # (the canonical-sort-free equivalent of the ring-0-sorted endpoint list,
    # MembershipService.java:346-348). Per-cohort hash reductions over N —
    # node-axis psums on the mesh, cohort-local otherwise (deliberately NOT
    # cond-gated: an extra lax.cond in the round body costs more compile
    # time across every engine program than the masked reductions cost to
    # run).
    with scope("cut_detection"):
        prop_hi_new, prop_lo_new = jax.vmap(
            lambda mask: masked_set_hash(state.id_hi, state.id_lo, mask)
        )(prop_masks)
        prop_hi = jnp.where(proposed_now, prop_hi_new, state.prop_hi)
        prop_lo = jnp.where(proposed_now, prop_lo_new, state.prop_lo)
        prop_mask = jnp.where(proposed_now[:, None], prop_masks, state.prop_mask)

    with scope("tally"):
        # 4. Fast-round votes: each live member votes its cohort's proposal, once
        #    per configuration (FastPaxos.java:94-108).
        cohort = state.cohort_of
        cohort_announced = announced[cohort]
        can_vote = state.alive & ~faults.crashed & ~state.vote_valid & cohort_announced
        if deaf is not None:  # hears no proposal this round; may vote in a later one
            can_vote = can_vote & ~deaf
        vote_hi = jnp.where(can_vote, prop_hi[cohort], state.vote_hi)
        vote_lo = jnp.where(can_vote, prop_lo[cohort], state.vote_lo)
        vote_valid = state.vote_valid | can_vote

        # 5. Quorum tally over all N votes (FastPaxos.java:125-156).
        tally = tally_candidates(
            vote_hi, vote_lo, vote_valid, prop_hi, prop_lo, announced, state.n_members
        )
        fast_decided = tally.decided

        # 5a'. Casting a fast-round vote also primes the classic acceptor state:
        #      rnd = vrnd = (1, 1), vval = the vote (Paxos.java:246-260). The
        #      fast round is always round 1; classic rounds start at 2.
        prime = can_vote & (state.cp_rnd_r < 1)
        cp_rnd_r = jnp.where(prime, 1, state.cp_rnd_r)
        cp_rnd_i = jnp.where(prime, 1, state.cp_rnd_i)
        cp_vrnd_r = jnp.where(prime, 1, state.cp_vrnd_r)
        cp_vrnd_i = jnp.where(prime, 1, state.cp_vrnd_i)
        cp_vval_src = jnp.where(prime, cohort, state.cp_vval_src)

        rounds_undecided = jnp.where(
            jnp.any(announced) & ~fast_decided, state.rounds_undecided + 1, state.rounds_undecided
        )
        fallback_due = (rounds_undecided >= cfg.fallback_rounds) & jnp.any(announced) & ~fast_decided
        # The classic arm's coordinator rule counts identical VALUES
        # (Paxos.java:287-308): ``value_of[i]`` is the first cohort whose
        # proposal is cohort i's. Made HERE, outside the conditional: an
        # operand of a conditional lives in HBM, and with ``prop_hi`` /
        # ``prop_lo`` among the arm's operands the votes' two gathers above
        # read their tables from there, 37 % slower on the v5e (PR 43's
        # refusal; PERF section 6). The arm takes this one [c] word instead.
        value_of = jnp.argmax(
            (prop_hi[:, None] == prop_hi[None, :]) & (prop_lo[:, None] == prop_lo[None, :]),
            axis=1,
        ).astype(jnp.int32)

    # 5b. Classic-Paxos fallback, message-level (Paxos.java:98-238): one
    #     attempt per engine round once the recovery delay expires. R =
    #     cfg.concurrent_coordinators rotating coordinators race within the
    #     attempt, rank-ordered as in the reference (Paxos.java:93-97,
    #     333-339): every acceptor promises to each heard phase1a in rank
    #     order, so several coordinators can win phase 1, but an acceptor's
    #     final rnd is the max heard rank and phase2a messages below it are
    #     rejected — a lower-ranked coordinator's phase 2 loses wherever a
    #     higher rank reached. Each coordinator picks a value with the Fast
    #     Paxos coordinator rule (Paxos.java:271-328); decision at a
    #     majority of accepts for one rank (majorities intersect, so at most
    #     one rank can decide per attempt). Delivery respects the same
    #     per-cohort rx-block masks as alerts, so partitioned coordinators
    #     genuinely fail and rotation recovers. Cond-gated: the common fast
    #     path skips the cumsum/gathers entirely.
    @scope("classic")
    def classic_attempt(cp):
        cp_rnd_r, cp_rnd_i, cp_vrnd_r, cp_vrnd_i, cp_vval_src = cp
        # Lane (policy) dtypes the attempt's stores must land at: racer
        # indices/ranks computed in int32 and narrowed on store — a bare
        # int32 operand in a where() would silently re-widen the lane.
        idt = cp_rnd_i.dtype
        cdt = cp_vval_src.dtype
        active = state.alive & ~faults.crashed
        n_active = jnp.sum(active, dtype=jnp.int32)
        majority = state.n_members // 2 + 1
        round_num = 2 + state.classic_epoch  # stays at the counter dtype
        slot_ids = jnp.arange(n, dtype=jnp.int32)
        cohort_ids = jnp.arange(c, dtype=jnp.int32)
        active_rank = jnp.cumsum(active.astype(jnp.int32))

        def rank_gt(ar, ai, br, bi):
            return (ar > br) | ((ar == br) & (ai > bi))

        # Pseudo-random coordinator picks, one hash stream per racer: the
        # real protocol's expovariate jitter makes concurrent recoverers
        # effectively random slots, so a contiguous run of partitioned slots
        # is escaped in O(1) expected attempts.
        coords = []
        for j in range(cfg.concurrent_coordinators):
            pick = mix32(_rotation_seed(state.classic_epoch.astype(jnp.uint32), j))
            target = jnp.where(
                n_active > 0,
                (pick % jnp.maximum(n_active, 1).astype(jnp.uint32)).astype(jnp.int32)
                + 1,
                1,
            )
            coords.append(jnp.argmax(active & (active_rank == target)).astype(idt))

        # Distinct racers only: a duplicate pick would duplicate a rank.
        valid = []
        for j, coord in enumerate(coords):
            v = jnp.bool_(True)
            for prev in coords[:j]:
                v = v & (coord != prev)
            valid.append(v)

        # Phase 1a/1b per racer. Arrival in rank order within the attempt
        # means a lower-ranked phase1a is never blocked by a concurrent
        # higher one — each racer collects promises from every reachable
        # acceptor whose rnd predates this attempt (Paxos.java:118-148).
        per = []
        for coord, v in zip(coords, valid):
            coord_cohort = state.cohort_of[coord]
            hears_coord = active & v & ~faults.rx_block[state.cohort_of, coord]
            coord_hears = active & v & ~faults.rx_block[coord_cohort, slot_ids]
            promise = hears_coord & rank_gt(round_num, coord, cp_rnd_r, cp_rnd_i)
            q1 = promise & coord_hears
            phase1_ok = jnp.sum(q1, dtype=jnp.int32) >= majority

            # Coordinator value-pick rule over the quorum's (vrnd, vval)
            # pairs — the plurality among max-vrnd accepted values (a safe
            # instance of Paxos.java:287-308: a fast-chosen value holds
            # > N/4 of any majority quorum and at most one value can be
            # fast-chosen, so the plurality contains it whenever one
            # exists). An acceptor's vval is kept as the cohort whose
            # proposal it took, so the cohorts that announced the same cut
            # pool their counts (``value_of``). If NO quorum member has
            # accepted anything, safety permits a free choice: propose an
            # announced cut (Paxos.java:310-326's any-proposed-value clause).
            voters = q1 & (cp_vval_src >= 0)
            mv_r = jnp.max(jnp.where(voters, cp_vrnd_r, -1))
            mv_i = jnp.max(jnp.where(voters & (cp_vrnd_r == mv_r), cp_vrnd_i, -1))
            at_max = voters & (cp_vrnd_r == mv_r) & (cp_vrnd_i == mv_i)
            max_counts = jnp.sum(
                at_max[None, :] & (cp_vval_src[None, :] == cohort_ids[:, None]),
                axis=1,
                dtype=jnp.int32,
            )
            value_counts = jnp.where(
                max_counts > 0,
                jnp.sum(
                    jnp.where(
                        value_of[:, None] == value_of[None, :], max_counts[None, :], 0
                    ),
                    axis=1,
                    dtype=jnp.int32,
                ),
                0,
            )
            chosen = jnp.where(
                jnp.any(max_counts > 0),
                jnp.argmax(value_counts).astype(cdt),
                jnp.where(
                    jnp.any(announced), jnp.argmax(announced).astype(cdt), -1
                ),
            )
            per.append((coord, hears_coord, promise, phase1_ok, chosen))

        # After every phase1a has arrived, an acceptor's rnd is the max rank
        # it heard (promises in rank order).
        rnd1_r, rnd1_i = cp_rnd_r, cp_rnd_i
        for coord, hears_coord, promise, _, _ in per:
            bump = promise & rank_gt(round_num, coord, rnd1_r, rnd1_i)
            rnd1_r = jnp.where(bump, round_num, rnd1_r)
            rnd1_i = jnp.where(bump, coord, rnd1_i)

        # Phase 2a/2b: an acceptor accepts only a phase2a matching its final
        # rnd (Paxos.java:195-216) — so where a higher rank's phase1a
        # reached, the lower racer's phase2a is rejected. Ranks are distinct,
        # hence at most one accept per acceptor. Decision at a majority of
        # accepts for one rank (Paxos.java:223-238).
        acc_r, acc_i = cp_vrnd_r, cp_vrnd_i
        acc_src = cp_vval_src
        fb_decided = jnp.bool_(False)
        chosen_winner = jnp.int32(-1)
        any_promise = jnp.zeros((n,), dtype=bool)
        any_accept = jnp.zeros((n,), dtype=bool)
        for coord, hears_coord, promise, phase1_ok, chosen in per:
            # A heard acceptor's final rnd is >= this racer's rank (it
            # promised in rank order), so acceptance means equality: this
            # racer was the highest rank the acceptor heard.
            can_accept = (
                phase1_ok
                & (chosen >= 0)
                & hears_coord
                & (rnd1_r == round_num)
                & (rnd1_i == coord)
            )
            accept_count = jnp.sum(can_accept, dtype=jnp.int32)
            won = phase1_ok & (chosen >= 0) & (accept_count >= majority)
            fb_decided = fb_decided | won
            chosen_winner = jnp.where(won, chosen.astype(jnp.int32), chosen_winner)
            acc_r = jnp.where(can_accept, round_num, acc_r)
            acc_i = jnp.where(can_accept, coord, acc_i)
            acc_src = jnp.where(can_accept, chosen, acc_src)
            any_promise = any_promise | promise
            any_accept = any_accept | can_accept

        return (
            jnp.where(any_promise | any_accept, rnd1_r, cp_rnd_r),
            jnp.where(any_promise | any_accept, rnd1_i, cp_rnd_i),
            acc_r,
            acc_i,
            acc_src,
            fb_decided,
            chosen_winner,
        )

    @scope("classic_skip")
    def no_attempt(cp):
        cp_rnd_r, cp_rnd_i, cp_vrnd_r, cp_vrnd_i, cp_vval_src = cp
        return (
            cp_rnd_r, cp_rnd_i, cp_vrnd_r, cp_vrnd_i, cp_vval_src,
            jnp.bool_(False), jnp.int32(-1),
        )

    (
        cp_rnd_r, cp_rnd_i, cp_vrnd_r, cp_vrnd_i, cp_vval_src, fb_decided, chosen,
    ), classic_ran = cond_across(
        batch_axis,
        fallback_due,
        classic_attempt,
        no_attempt,
        (cp_rnd_r, cp_rnd_i, cp_vrnd_r, cp_vrnd_i, cp_vval_src),
    )
    with scope("tally"):
        classic_epoch = jnp.where(fallback_due, state.classic_epoch + 1, state.classic_epoch)
        if paths is not None:
            paths = paths + jnp.stack(
                [fallback_due, fb_decided, fast_decided]
            ).astype(jnp.int32)

        decided = fast_decided | fb_decided
        winner_cohort = jnp.where(
            fast_decided,
            jnp.argmax(announced & (prop_hi == tally.winner_hi) & (prop_lo == tally.winner_lo)),
            jnp.maximum(chosen, 0),
        )
        # Materialize the decided cut as a one-hot masked reduction over the
        # cohort axis — on the cohort-meshed state this lowers to a reduce-class
        # psum of [n] bools, where the old dynamic row gather
        # (prop_mask[winner_cohort]) would redistribute across the cohort axis
        # as gather/permute traffic in every round of the hot loop.
        winner_mask = decided & jnp.any(
            prop_mask & (jnp.arange(c, dtype=jnp.int32) == winner_cohort)[:, None],
            axis=0,
        )

    round_state = state._replace(
        fd_count=fd_count,
        fd_hist=fd_hist,
        fd_fired=fd_fired,
        fire_round=fire_round,
        round_idx=state.round_idx + 1,
        report_bits=report_bits,
        seen_down=seen_down,
        released=released,
        announced=announced,
        prop_mask=prop_mask,
        prop_hi=prop_hi,
        prop_lo=prop_lo,
        vote_hi=vote_hi,
        vote_lo=vote_lo,
        vote_valid=vote_valid,
        rounds_undecided=rounds_undecided,
        cp_rnd_r=cp_rnd_r,
        cp_rnd_i=cp_rnd_i,
        cp_vrnd_r=cp_vrnd_r,
        cp_vrnd_i=cp_vrnd_i,
        cp_vval_src=cp_vval_src,
        classic_epoch=classic_epoch,
    )
    events = StepEvents(
        decided=decided,
        fast_decided=fast_decided,
        winner_mask=winner_mask,
        proposals_announced=proposed_now,
        alerts_emitted=alerts_emitted,
        total_votes=tally.total_votes,
        max_votes=tally.max_count,
        prop_hi=prop_hi,
        prop_lo=prop_lo,
    )
    # What a named batch axis adds to the end of the return.
    arms_ran = ()
    if batch_axis is not None:
        with scope("tally"):
            arms_ran = (
                jnp.stack([invalidation_ran, classic_ran]).astype(jnp.int32),
            )
    lanes_out = _lane_tail(links, paths)
    if telem is None:
        return (round_state, decided, winner_mask, events, *arms_ran, *lanes_out)

    # Device telemetry plane (write-only; see the docstring contract).
    # Scalars reuse reductions computed above; [c, n]/[c] lanes accumulate
    # elementwise at their native grain.
    with scope("observers"):
        active_cn, invalidated_cn = telemetry_cut_masks(
            state.report_bits, new_bits, report_bits,
            state.alive | state.join_pending, cfg.h, cfg.l,
        )
        decided_i = decided.astype(jnp.int32)
        # Decision-path split, same vocabulary as the host protocol's
        # FastPaxos.decided_path ("classic" iff the classic fallback decided).
        bucket = undecided_log2_bucket(rounds_undecided, TELEMETRY_BUCKETS)
        telem = TelemetryLanes(
            tl_rounds=telem.tl_rounds + 1,
            tl_alerts=telem.tl_alerts + alerts_emitted,
            tl_active=telem.tl_active + active_cn.astype(jnp.int32),
            tl_invalidated=telem.tl_invalidated + invalidated_cn.astype(jnp.int32),
            tl_proposals=telem.tl_proposals + proposed_now.astype(jnp.int32),
            tl_tally_sum=telem.tl_tally_sum + jnp.where(decided, tally.max_count, 0),
            tl_fast_decisions=telem.tl_fast_decisions + fast_decided.astype(jnp.int32),
            tl_classic_decisions=telem.tl_classic_decisions + fb_decided.astype(jnp.int32),
            tl_conflict_rounds=telem.tl_conflict_rounds
            + (jnp.any(announced) & ~fast_decided).astype(jnp.int32),
            tl_dissent=telem.tl_dissent
            + jnp.sum(
                decided & announced & (value_of != value_of[winner_cohort]),
                dtype=jnp.int32,
            ),
            tl_invalidation_rounds=telem.tl_invalidation_rounds
            + invalidation_own[0].astype(jnp.int32),
            tl_invalidation_dense_rounds=telem.tl_invalidation_dense_rounds
            + invalidation_own[1].astype(jnp.int32),
            # the commit's, not the round's: :func:`_count_dense_commit`
            tl_view_change_dense=telem.tl_view_change_dense,
            tl_undecided_hist=telem.tl_undecided_hist.at[bucket].add(decided_i),
        )
    if trace is None:
        return (round_state, decided, winner_mask, events, telem, *arms_ran, *lanes_out)

    # Device round-trace ring (write-only; one record per round into slot
    # cursor % R). Every field is a scalar computed above — the ring adds
    # nine scatter-stores and two int adds, nothing else. The round/epoch
    # stamps are the PRE-round values (round_idx increments in round_state;
    # the epoch bumps only when the caller commits the view change), so the
    # decoded (epoch, round) pairs are lexicographically strictly increasing
    # — the wrap-monotonicity contract tests/test_trace_ring.py pins.
    with scope("observers"):
        slot = jax.lax.rem(trace.tr_cursor, jnp.int32(cfg.trace))
        trace = TraceRing(
            tr_round=trace.tr_round.at[slot].set(state.round_idx),
            tr_epoch=trace.tr_epoch.at[slot].set(state.config_epoch),
            tr_active=trace.tr_active.at[slot].set(
                jnp.sum(active_cn, dtype=jnp.int32)
            ),
            tr_alerts=trace.tr_alerts.at[slot].set(alerts_emitted),
            tr_proposals=trace.tr_proposals.at[slot].set(
                jnp.sum(proposed_now, dtype=jnp.int32)
            ),
            tr_tally=trace.tr_tally.at[slot].set(jnp.where(decided, tally.max_count, 0)),
            tr_path=trace.tr_path.at[slot].set(
                fast_decided.astype(jnp.int32) + 2 * fb_decided.astype(jnp.int32)
            ),
            tr_conflict=trace.tr_conflict.at[slot].set(
                (jnp.any(announced) & ~fast_decided).astype(jnp.int32)
            ),
            tr_undecided=trace.tr_undecided.at[slot].set(
                rounds_undecided.astype(jnp.int32)
            ),
            tr_cursor=trace.tr_cursor + 1,
            tr_wraps=trace.tr_wraps + (slot == cfg.trace - 1).astype(jnp.int32),
        )
    return (round_state, decided, winner_mask, events, telem, trace, *arms_ran, *lanes_out)


def _rotation_seed(epoch_u32, j: int):
    """Per-racer hash-stream seed for coordinator rotation — THE definition,
    shared by the device attempt and the host predictor (uint32 wraparound
    semantics in both)."""
    return epoch_u32 * jnp.uint32(0x9E3779B1) + jnp.uint32(
        (0x5BD1E995 * (j + 1)) & 0xFFFFFFFF
    )


def classic_coordinator_targets(epoch: int, n_active: int, racers: int):
    """Host-side replica of the classic fallback's coordinator rotation:
    the 1-based active-rank target of each racer at ``epoch``. Uses the same
    ``_rotation_seed``/``mix32`` the device attempt uses, so tests and
    diagnostics predict picks from one definition."""
    targets = []
    for j in range(racers):
        pick = int(mix32(_rotation_seed(jnp.uint32(epoch & 0xFFFFFFFF), j)))
        targets.append(pick % max(n_active, 1) + 1)
    return targets


@scope("view_change")
def apply_view_change_impl(
    cfg: EngineConfig, state: EngineState, winner_mask, *, batch_axis=None,
    commits=None, dense_arms=False,
):
    """Commit a decided cut: flip membership, re-derive ring topology, reset
    all per-configuration state (MembershipService.java:385-444). Returns
    ``(state, took_dense)``.

    Joiners NOT in this cut stay pending into the new configuration: their
    UP edges remain armed (gatekeeper observers kept, fired edges re-stamped
    to round 0) so the alerts redeliver and a later cut admits them — unlike
    DOWN alerts, which re-fire from the persistent crash masks, a wiped UP
    edge would never re-fire and the joiner would be stranded forever.

    Both ring tables are UPDATED, not rebuilt
    (``ops/rings.ring_tables_after_cut``, their one writer here): the
    ``ring_alive`` lane has the cut's own positions flipped, and the observer
    table is ``inval_obs`` repaired at the cut's slots and at their
    predecessors, 2·K·B updates where the walk scatters K·N; a cut that
    overflows the bucket, or a ring with fewer than two alive, takes the
    gather and the walk whole. The repair stands on ``inval_obs`` being the
    walk's table at every slot that is not a pending joiner
    (``models/state.EngineState``); ``obs_idx`` is derived from the SAME
    repaired table, so a column a leave had pointed at the leaver itself
    (:meth:`VirtualCluster.initiate_leave`) comes back onto the ring as the
    rebuild puts it back. ``took_dense`` says which form ran, for the
    telemetry plane (:func:`_count_dense_commit`). ``dense_arms``
    (:func:`_compute_round`) traces the rebuild alone and ``took_dense`` is
    True. ``batch_axis`` and ``commits`` go down to the conditional: under a
    ``vmap`` that names its axis it stays a conditional, opened by the
    members whose cut the caller commits."""
    n, k, c = cfg.n, cfg.k, cfg.c
    pol = compaction_policy(cfg)
    idt, cdt = jnp.dtype(pol.idx), jnp.dtype(pol.cohort)
    ndt, rdt = jnp.dtype(pol.counter), jnp.dtype(pol.round)
    alive2 = state.alive ^ winner_mask
    if dense_arms:
        ring_alive2 = ring_liveness(state.ring_perm, alive2)
        took_dense = jnp.bool_(True)
        # Sort-free: O(N) scans over the static key-order perms, not a K-ring
        # argsort. The topology kernels compute at int32; the stores below
        # narrow to the policy's index dtype (lossless: values in [-1, n-1]).
        observers = ring_topology_from_perm(state.ring_perm, alive2, ring_alive2).obs_idx
    else:
        ring_alive2, observers, took_dense = ring_tables_after_cut(
            state.inval_obs, state.ring_alive, state.ring_perm, state.ring_pos,
            alive2, winner_mask, batch_axis, commits,
        )
    config_hi, config_lo = masked_set_hash(state.id_hi, state.id_lo, alive2)
    still_pending = state.join_pending & ~winner_mask  # [n]
    fd_fired2 = state.fd_fired & still_pending[:, None]
    committed = state._replace(
        alive=alive2,
        ring_alive=ring_alive2,
        # Departing members' identity lanes are spent forever.
        retired=state.retired | (winner_mask & state.alive),
        # A joiner still pending keeps its gatekeepers in both lanes.
        obs_idx=jnp.where(still_pending[None, :], state.obs_idx, observers.astype(idt)),
        inval_obs=jnp.where(
            still_pending[None, :], state.inval_obs, observers.astype(idt)
        ),
        config_epoch=state.config_epoch + 1,
        config_hi=config_hi,
        config_lo=config_lo,
        n_members=jnp.sum(alive2, dtype=jnp.int32),
        fd_count=jnp.zeros((n, k), dtype=ndt),
        fd_hist=jnp.zeros((n, k), dtype=jnp.dtype(pol.hist)),
        fd_fired=fd_fired2,
        fire_round=jnp.where(fd_fired2, 0, jnp.asarray(pol.fire_never, rdt)),
        join_pending=still_pending,
        report_bits=jnp.zeros((c, n), dtype=jnp.dtype(pol.report)),
        seen_down=jnp.zeros((c,), dtype=bool),
        released=jnp.zeros((c, n), dtype=bool),
        announced=jnp.zeros((c,), dtype=bool),
        prop_mask=jnp.zeros((c, n), dtype=bool),
        prop_hi=jnp.zeros((c,), dtype=jnp.uint32),
        prop_lo=jnp.zeros((c,), dtype=jnp.uint32),
        vote_hi=jnp.zeros((n,), dtype=jnp.uint32),
        vote_lo=jnp.zeros((n,), dtype=jnp.uint32),
        vote_valid=jnp.zeros((n,), dtype=bool),
        rounds_undecided=jnp.zeros((), dtype=ndt),
        cp_rnd_r=jnp.zeros((n,), dtype=ndt),
        cp_rnd_i=jnp.zeros((n,), dtype=idt),
        cp_vrnd_r=jnp.zeros((n,), dtype=ndt),
        cp_vrnd_i=jnp.zeros((n,), dtype=idt),
        cp_vval_src=jnp.full((n,), -1, dtype=cdt),
        classic_epoch=jnp.zeros((), dtype=ndt),
        round_idx=jnp.int32(0),
    )
    return committed, took_dense


def _count_dense_commit(observers, took_dense):
    """``observers`` with the commits that gathered ``ring_alive`` whole
    (``took_dense``: this round's, False where none committed) added to the
    telemetry plane's ``tl_view_change_dense``. Outside the gate's
    conditional, so that no ``[c, n]`` lane rides through it as an operand;
    with no observer nothing is traced."""
    if not observers:
        return observers
    telem, *rest = observers
    with scope("observers"):
        telem = telem._replace(
            tl_view_change_dense=telem.tl_view_change_dense
            + took_dense.astype(jnp.int32)
        )
    return [telem, *rest]


def _view_change_gate(
    cfg: EngineConfig, state: EngineState, observers, decided, winner_mask,
    batch_axis=None, dense_arms=False,
):
    """THE gate around the commit for a body that carries no masks: the
    view change if the round ``decided``, else the state as it came.
    Returns ``(state, observers)``, the telemetry plane's count of dense
    commits brought up to date (:func:`_count_dense_commit`)."""
    state, took_dense = jax.lax.cond(
        decided,
        lambda s: apply_view_change_impl(
            cfg, s, winner_mask, batch_axis=batch_axis, commits=decided,
            dense_arms=dense_arms,
        ),
        scope("view_keep")(lambda s: (s, jnp.bool_(False))),
        state,
    )
    return state, _count_dense_commit(observers, took_dense)


def _view_change_gate_masks(
    cfg: EngineConfig, state: EngineState, observers, faults: FaultInputs,
    masks, decided, winner_mask,
):
    """The gate for the per-round step, which hands the per-edge masks
    back to its driver beside the state: the view change AND the mask
    rebuild ride one cond. Topology (and with it the observer-active/delivery
    masks) changes ONLY when a cut commits, so the rebuild's pack +
    permutation gathers are per-CUT work, gated exactly like the ring
    rebuild, never unconditional per-round traffic; the rounds that follow
    the cut inside a streamed wave read what the arm built. (The whole-wave
    loop carries no masks across a commit: it builds at the head of each
    convergence, :func:`run_until_membership_impl`.) Returns ``(state,
    observers, masks)``, the masks those of the returned state and
    ``faults``."""

    def commit(s):
        committed, took_dense = apply_view_change_impl(cfg, s, winner_mask)
        return committed, _edge_masks(cfg, committed, faults), took_dense

    state, masks, took_dense = jax.lax.cond(
        decided, commit,
        scope("view_keep")(lambda s: (s, masks, jnp.bool_(False))), state,
    )
    return state, _count_dense_commit(observers, took_dense), masks


# Every round program below follows ONE convention, the one
# ``parallel/mesh.sharded_program`` and ``VirtualCluster._advance`` speak:
#
#     program(cfg, state, *observers, faults, *rest)
#         -> (state, *observers, *observations)
#
# ``observers`` is ``()``, ``(telem,)`` or ``(telem, trace)``, handed to
# ``_compute_round`` as it takes them and carried through a loop directly
# after the state. A driver jits one body once per observer count.
#
# Two lanes ride outside the positions: the one-device programs take the
# link-fault lane as the keyword ``links`` and the consensus-path counts as
# the keyword ``paths`` and, given either, hand it back LAST, after the
# observations (``links`` before ``paths`` where both are set). A call
# without the keywords is the call it always was.


def _lane_tail(*lanes) -> tuple:
    """What a program's return ends with: the lanes that are set."""
    return tuple(lane for lane in lanes if lane is not None)


def _set_lanes(**lanes) -> dict:
    """The keywords a program is called with: the lanes that are set (a call
    that names no lane is the call, and the program, of before)."""
    return {name: lane for name, lane in lanes.items() if lane is not None}


def _lane_off(outputs, *lanes):
    """``(outputs, *lanes)`` of a return that ends with :func:`_lane_tail`
    of ``lanes``: each lane is the element it went back as, or ``None``
    where none went in."""
    riding = len(_lane_tail(*lanes))
    tail = iter(outputs[len(outputs) - riding:])
    return (
        outputs[: len(outputs) - riding],
        *(None if lane is None else next(tail) for lane in lanes),
    )


def engine_step_impl(
    cfg: EngineConfig, state: EngineState, *rest, links=None, paths=None,
    dense_arms=False,
):
    """One full protocol round including conditional view-change application:
    the MESH's per-round step (``sharded_program("step")``) and the
    analyzers' reference, which builds the per-edge masks in every round.
    ``rest`` is ``(*observers, faults)``; returns ``(state, *observers,
    events)``."""
    *observers, faults = rest
    (round_state, decided, winner_mask, events, *observers), links, paths = _lane_off(
        _compute_round(
            cfg, state, faults, None, *observers, links=links, paths=paths,
            dense_arms=dense_arms,
        ),
        links, paths,
    )
    new_state, observers = _view_change_gate(
        cfg, round_state, observers, decided, winner_mask, dense_arms=dense_arms
    )
    return (new_state, *observers, events, *_lane_tail(links, paths))


# Donating step for the long-running driver loop (state buffers reused in
# place) and a non-donating variant for compile checks / sharded dry-runs.
engine_step = jax.jit(engine_step_impl, static_argnums=(0,), donate_argnums=(1,))
engine_step_nodonate = jax.jit(engine_step_impl, static_argnums=(0,))  # donate-ok: compile-check / dry-run variant; callers keep their state buffers


def engine_step_carried_impl(
    cfg: EngineConfig, state: EngineState, *rest, links=None, paths=None
):
    """The MESHLESS per-round step the driver dispatches: the math of
    :func:`engine_step_impl` with the per-edge masks CARRIED from round to
    round beside the state instead of rebuilt in every round. ``rest`` is
    ``(*observers, faults, masks)``.

    ``masks`` must be ``_edge_masks(cfg, state, faults)`` for exactly these
    inputs (the driver's business: :class:`CarriedMasks`). They are a pure
    function of ``alive``, ``obs_idx``, ``crashed`` and ``rx_block``; the
    round leaves those four alone and a committed cut changes the first two,
    so the taken arm of the view-change gate rebuilds them for the committed
    state and the other arm hands them back. The masks returned are those
    of ``(new_state, faults)``, for the driver's next round. Per round the
    state, events and observer lanes are bit-identical to
    :func:`engine_step_impl`'s: the same functions on the same values, only
    the place of the build differs.

    Returns ``(state, *observers, events, masks)``."""
    *observers, faults, masks = rest
    (round_state, decided, winner_mask, events, *observers), links, paths = _lane_off(
        _compute_round(
            cfg, state, faults, masks, *observers, links=links, paths=paths
        ),
        links, paths,
    )
    new_state, observers, masks = _view_change_gate_masks(
        cfg, round_state, observers, faults, masks, decided, winner_mask
    )
    return (new_state, *observers, events, masks, *_lane_tail(links, paths))


#: The build program: dispatched by the driver only when the masks it
#: carries are not those of the inputs it is about to pass.
edge_masks_build = jax.jit(_edge_masks, static_argnums=(0,))  # donate-ok: reads four leaves of a state that stays live


@functools.partial(jax.jit, static_argnums=(0,))
def link_faults_place(n: int, packed) -> LinkFaults:
    """A new link-fault lane in one upload and one dispatch: ``packed`` is
    ``uint32[4 + m]``, the four controls (loss in permille, on rounds, off
    rounds, seed) and then the ``m`` faulty slots of ``n``. The lane's clock
    and its count start at 0."""
    controls, idx = packed[:4].astype(jnp.int32), packed[4:].astype(jnp.int32)
    return LinkFaults(
        loss_permille=jnp.zeros((n,), dtype=jnp.int32).at[idx].set(controls[0]),
        on_rounds=controls[1],
        off_rounds=controls[2],
        seed=packed[3],
        age=jnp.int32(0),
        probes_lost=jnp.int32(0),
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def partition_place(c: int, n: int, deaf: int, packed) -> jnp.ndarray:
    """The ``[c, n]`` receive-block lane of a one-way partition in one upload
    and one dispatch: ``packed`` is ``int32[deaf + m]``, the ``deaf`` cohorts
    that stop hearing and then the ``m`` sender slots they stop hearing
    from. Every (cohort, sender) pair is blocked, nobody else."""
    cohorts, senders = packed[:deaf], packed[deaf:]
    return jnp.zeros((c, n), dtype=bool).at[
        cohorts[:, None], senders[None, :]
    ].set(True)


def telemetry_digest_impl(telem: TelemetryLanes) -> jnp.ndarray:
    """The telemetry lanes reduced to one small int32 vector — THE place the
    plane's cross-shard reductions live, dispatched only at the existing
    host-sync boundaries (``sync`` / ``stream_fetch`` / ``health_scan``;
    each fetch site carries a ``# telemetry-fetch-ok:`` marker the
    ``telemetry`` analyzer family enforces), never inside a convergence
    loop. Layout: ``engine_telemetry.TELEMETRY_DIGEST_FIELDS`` scalars then
    the ``TELEMETRY_BUCKETS`` rounds-undecided histogram buckets."""
    return jnp.concatenate([
        jnp.stack([
            telem.tl_rounds,
            telem.tl_alerts,
            jnp.sum(telem.tl_active, dtype=jnp.int32),
            jnp.max(telem.tl_active),
            jnp.sum(telem.tl_invalidated, dtype=jnp.int32),
            jnp.sum(telem.tl_proposals, dtype=jnp.int32),
            telem.tl_tally_sum,
            telem.tl_fast_decisions,
            telem.tl_classic_decisions,
            telem.tl_conflict_rounds,
            telem.tl_dissent,
            telem.tl_invalidation_rounds,
            telem.tl_invalidation_dense_rounds,
            telem.tl_view_change_dense,
        ]),
        telem.tl_undecided_hist,
    ])


telemetry_digest = jax.jit(telemetry_digest_impl)  # donate-ok: read-only boundary fetch; the lanes stay live


def trace_digest_impl(trace: TraceRing) -> jnp.ndarray:
    """The trace ring packed into one int32 vector for a single boundary
    fetch: ``[tr_cursor, tr_wraps]`` then the nine ``[R]`` lanes in
    ``engine_telemetry.TRACE_RECORD_FIELDS`` order. Dispatched only at the
    host-sync boundaries, under the same ``# telemetry-fetch-ok:`` marker
    discipline as :func:`telemetry_digest_impl` — never inside a
    convergence loop."""
    return jnp.concatenate([
        jnp.stack([trace.tr_cursor, trace.tr_wraps]),
        trace.tr_round,
        trace.tr_epoch,
        trace.tr_active,
        trace.tr_alerts,
        trace.tr_proposals,
        trace.tr_tally,
        trace.tr_path,
        trace.tr_conflict,
        trace.tr_undecided,
    ])


trace_digest = jax.jit(trace_digest_impl)  # donate-ok: read-only boundary fetch; the ring stays live


@scope("sync_checksum")
def sync_checksum_impl(state: EngineState, faults: FaultInputs, links=None):
    """Scalar checksum depending on every state/fault array — the barrier
    ``VirtualCluster.sync`` fetches (a scalar that depends on every array
    cannot arrive before all of them are computed).
    Module-level and jitted so the compiled-program gate audits the sync
    dispatch like every other registered entrypoint."""
    checksum = (
        jnp.sum(state.key_hi, dtype=jnp.uint32)
        + jnp.sum(state.key_lo, dtype=jnp.uint32)
        + jnp.sum(state.id_hi, dtype=jnp.uint32)
        + jnp.sum(state.id_lo, dtype=jnp.uint32)
        + jnp.sum(state.obs_idx).astype(jnp.uint32)
        + jnp.sum(state.fd_count).astype(jnp.uint32)
        + jnp.sum(state.report_bits).astype(jnp.uint32)
        + jnp.sum(state.alive).astype(jnp.uint32)
        + jnp.sum(faults.crashed).astype(jnp.uint32)
        + jnp.sum(faults.probe_fail).astype(jnp.uint32)
    )
    if links is not None:  # a set lane's scatter is behind the barrier too
        checksum = checksum + sum(
            jnp.sum(leaf).astype(jnp.uint32) for leaf in links
        )
    return checksum


sync_checksum = jax.jit(sync_checksum_impl)  # donate-ok: read-only barrier; the state stays live


def _converge(
    cfg: EngineConfig, state: EngineState, observers, faults: FaultInputs,
    masks, steps, max_steps, batch_axis=None, links=None, paths=None,
    dense_arms=False,
):
    """THE inner convergence loop: rounds over fixed per-edge ``masks``
    (topology and faults are fixed until a cut commits, so the per-edge
    gather is hoisted out of the round body by every caller) until one
    decides or ``steps`` reaches ``max_steps``. The round body stays
    sort-free: the caller applies the (at most one) view change after the
    loop, so the ring rebuild runs exactly once per convergence. Returns
    ``(round_state, observers, steps, decided, winner_mask, arm_rounds,
    links, paths)``.

    ``batch_axis`` goes down to the round (:func:`_compute_round`). With a
    name the loop also carries ``arm_rounds``, ``int32[2]``: the rounds in
    which ``invalidation`` and ``classic`` ran. With ``None`` it is ``None``,
    a pytree of no leaves: the carry is the one it always was. So is the
    link-fault lane (``links``) where none is set; a set lane rides the
    carry's end and ages with every round, and its one gather (the loss at
    every edge's observer) is made here, once, beside the masks. The
    consensus-path counts (``paths``) ride behind it the same way.
    ``dense_arms`` goes down to the round like ``batch_axis``."""
    observer_loss = None if links is None else _observer_loss(cfg, state, links)

    def cond(carry):
        *_, steps, decided, _, _, _, _ = carry
        return (~decided) & (steps < max_steps)

    def body(carry):
        state, *observers, steps, _, _, arm_rounds, links, paths = carry
        (round_state, decided, winner_mask, _, *observers), links, paths = _lane_off(
            _compute_round(
                cfg, state, faults, masks, *observers, batch_axis=batch_axis,
                links=links, observer_loss=observer_loss, paths=paths,
                dense_arms=dense_arms,
            ),
            links, paths,
        )
        if batch_axis is not None:
            *observers, arms_ran = observers
            arm_rounds = arm_rounds + arms_ran
        return (
            round_state, *observers, steps + 1, decided, winner_mask, arm_rounds,
            links, paths,
        )

    init = (
        state, *observers, steps, jnp.bool_(False),
        jnp.zeros((cfg.n,), dtype=bool),
        None if batch_axis is None else jnp.zeros((2,), dtype=jnp.int32),
        links, paths,
    )
    (
        state, *observers, steps, decided, winner, arm_rounds, links, paths
    ) = jax.lax.while_loop(cond, body, init)
    return state, observers, steps, decided, winner, arm_rounds, links, paths


def run_to_decision_impl(
    cfg: EngineConfig, state: EngineState, *rest, batch_axis=None, links=None,
    paths=None, dense_arms=False,
):
    """Protocol rounds until a view change commits — entirely on device.

    A ``lax.while_loop`` around the round: the host dispatches ONE
    program per convergence instead of one per round, removing the per-round
    device->host sync that dominates small-round convergences. With
    observers the fused convergence stops being a black box: every round of
    the loop accumulates into the lanes and leaves one record in the ring.
    ``rest`` is ``(*observers, faults, max_steps)``; returns
    ``(state, *observers, steps_taken, decided, winner_mask)``, and under a
    ``vmap`` that hands the name of its ``batch_axis`` (:func:`_converge`)
    one element more, the loop's ``arm_rounds``.
    """
    *observers, faults, max_steps = rest
    masks = _edge_masks(cfg, state, faults)
    state, observers, steps, decided, winner, arm_rounds, links, paths = _converge(
        cfg, state, observers, faults, masks, jnp.int32(0), max_steps, batch_axis,
        links, paths, dense_arms,
    )
    state, observers = _view_change_gate(
        cfg, state, observers, decided, winner, batch_axis, dense_arms
    )
    lanes = _lane_tail(links, paths)
    if batch_axis is None:
        return (state, *observers, steps, decided, winner, *lanes)
    return (state, *observers, steps, decided, winner, arm_rounds, *lanes)


def run_until_membership_impl(
    cfg: EngineConfig, state: EngineState, *rest, links=None, paths=None,
    dense_arms=False,
):
    """Protocol rounds through MULTIPLE view changes until the membership
    reaches ``target`` — one device dispatch for a whole churn/bootstrap
    wave instead of one per cut. ``rest`` is ``(*observers, faults, target,
    max_steps, max_cuts, min_cuts)``.

    Structure: an outer loop of convergences, each of which (a) builds the
    per-edge masks of the topology it starts from, (b) runs the same
    sort-free inner round loop as ``run_to_decision_impl`` over them, and
    (c) applies the view change under :func:`_view_change_gate`. The masks
    are built where they are first read and not where the topology changes:
    one build a convergence, outside the inner round loop, and none after
    the wave's last commit (nor in a wave that is resolved at entry), which
    no round follows. Each dispatch+fetch pair costs a host
    round trip, so resolving a 2-cut churn or a bootstrap admission wave in
    one dispatch removes that many from the measured wall clock. The
    observers accumulate ACROSS the wave's view changes — a commit never
    resets the lanes or the ring, so a multi-cut wave reads as one
    round-indexed story, the epoch stamp marking where each view change
    landed.

    Returns (state, *observers, total_steps, cuts_committed, resolved,
    sizes) where
    ``sizes[i]`` is the membership after the i-th committed cut (-1 beyond
    ``cuts``) — the paper's Table 1 "intermediate views" instrument,
    observed without any per-cut fetch. ``max_cuts`` is static (it sizes
    the sizes buffer). ``min_cuts`` guards the equal-churn trap: a wave of
    J joins + J crashes TARGETS the starting membership, so "membership ==
    target" alone would resolve vacuously before the first cut — requiring
    at least min_cuts committed cuts makes the loop actually run the churn.
    """
    *observers, faults, target, max_steps, max_cuts, min_cuts = rest

    def outer_cond(carry):
        state, *_, steps, cuts, stalled, _, _, _ = carry
        resolved = (state.n_members == target) & (cuts >= min_cuts)
        return (~resolved) & (~stalled) & (steps < max_steps) & (cuts < max_cuts)

    def outer_body(carry):
        state, *observers, steps, cuts, _, sizes, links, paths = carry
        # Built here, where the convergence reads them, and not in the cut's
        # arm: every iteration starts from a topology no build has seen (the
        # wave's first, or the one a commit just left), and the wave's last
        # commit is followed by no round that would read a rebuild.
        masks = _edge_masks(cfg, state, faults)
        state, observers, steps, decided, winner, _, links, paths = _converge(
            cfg, state, observers, faults, masks, steps, max_steps, links=links,
            paths=paths, dense_arms=dense_arms,
        )
        state, observers = _view_change_gate(
            cfg, state, observers, decided, winner, dense_arms=dense_arms
        )
        with scope("loop_result"):
            sizes = jnp.where(
                decided, sizes.at[cuts].set(state.n_members), sizes
            )
        # A convergence that ran out of budget undecided cannot make further
        # progress (the outer loop would spin): latch and exit.
        return (
            state, *observers, steps, cuts + decided.astype(jnp.int32),
            ~decided, sizes, links, paths,
        )

    init = (
        state,
        *observers,
        jnp.int32(0),
        jnp.int32(0),
        jnp.bool_(False),
        jnp.full((max_cuts,), -1, dtype=jnp.int32),
        links, paths,
    )
    state, *observers, steps, cuts, _, sizes, links, paths = jax.lax.while_loop(
        outer_cond, outer_body, init
    )
    with scope("loop_result"):
        resolved = (state.n_members == target) & (cuts >= min_cuts)
    return (
        state, *observers, steps, cuts, resolved, sizes, *_lane_tail(links, paths)
    )


def jit_per_observer_count(impl, static=(), donated=()):
    """Three jits of one round body, by how many observers ride along (0:
    none, 1: the telemetry lanes, 2: lanes and trace ring). ``cfg`` is
    static and the state and the observers are donated; ``static`` and
    ``donated`` name further argument positions as they stand with no
    observer, and shift with the count."""
    return tuple(
        jax.jit(
            impl,
            static_argnums=(0, *(at + k for at in static)),
            donate_argnums=(*range(1, 2 + k), *(at + k for at in donated)),
        )
        for k in range(3)
    )


#: A driver verb's one-device programs, by observer count. The step's carried
#: masks (its last argument) are donated too, the faults alone stay the
#: caller's; the wave's static ``max_cuts`` sits after the faults and two
#: controls. A cluster on a mesh takes the same verb from
#: ``parallel/mesh.sharded_program`` instead (its step is
#: ``engine_step_impl``, which builds the masks in every round).
_ROUND_PROGRAMS = {
    "step": jit_per_observer_count(engine_step_carried_impl, donated=(3,)),
    "decision": jit_per_observer_count(run_to_decision_impl),
    "wave": jit_per_observer_count(run_until_membership_impl, static=(5,)),
}


class CarriedMasks:
    """The per-edge masks a meshless per-round driver carries beside its
    state, and the decision whether they are still those of its inputs.

    Staleness is identity, decided on the host: the masks are remembered
    with the four leaves ``_edge_masks`` reads (after a step: the step's own
    outputs and the faults it was passed) and reused only while the driver's
    ``state.alive``, ``state.obs_idx``, ``faults.crashed`` and
    ``faults.rx_block`` ARE those objects. Every seam that can change one of
    them — an injection, another verb, an assignment to ``state`` or
    ``faults`` from outside the class — leaves a NEW array there, so no seam
    has to report itself; a seam that leaves the four alone
    (``set_flaky_edges``, a fired-edge stamp) costs no build."""

    def __init__(self, build):
        self._build = build  # the driver's build program
        self._masks = None
        self._sources = ()

    @staticmethod
    def _inputs(driver) -> tuple:
        return (
            driver.state.alive, driver.state.obs_idx,
            driver.faults.crashed, driver.faults.rx_block,
        )

    def for_step(self, driver):
        """The masks to pass to the step about to be dispatched: the
        carried ones, or a fresh build (one dispatch, no fetch)."""
        if self._masks is not None and all(
            now is built
            for now, built in zip(self._inputs(driver), self._sources)
        ):
            driver.metrics.inc("engine_edge_mask_reuses")
            return self._masks
        driver.metrics.inc("engine_edge_mask_builds")
        return self._build(driver.cfg, driver.state, driver.faults)

    def keep(self, driver, masks) -> None:
        """After the step: ``masks`` are those of the driver's state and
        faults as they stand now."""
        self._masks = masks
        self._sources = self._inputs(driver)


def _mesh_lib():
    """``rapid_tpu.parallel.mesh``, imported at first use: it imports this
    module's round bodies."""
    from rapid_tpu.parallel import mesh

    return mesh


class VirtualCluster(DispatchSeam):
    """Host driver around the device engine: owns the state, injects faults
    and join waves, and runs rounds until convergence.

    Handed a ``mesh`` (``parallel/mesh.make_mesh``), the same driver runs the
    cluster sharded over it: every leaf lies where ``PARTITION_RULES`` puts
    it, every verb dispatches ``sharded_program``'s form of its program and
    returns the state on the same shardings, and the methods, the dispatch
    phases and the counters are those of a one-device cluster.

    This is the deployment the BASELINE targets: N virtual Rapid endpoints
    co-located on TPU hosts, alerts/votes as device-array writes.

    The telemetry seams (transfer accounting, the phase-validated
    ``_dispatch`` timer) are the shared :class:`DispatchSeam` — one
    vocabulary across this driver, the fleet, and the streaming pipeline.
    """

    def __init__(self, cfg: EngineConfig, state: EngineState, mesh=None):
        DispatchSeam.__init__(self)
        self.cfg = cfg
        self.mesh = mesh
        self.state = state if mesh is None else _mesh_lib().adopt(state, mesh)
        self.faults = self._fresh(FaultInputs.none)
        # The link-fault lane (models/state.LinkFaults): None until
        # ``set_link_faults`` names somebody, and while it is None every verb
        # dispatches the program it always did. ``_link_lost_seen`` is what
        # of the lane's ``probes_lost`` the counter has already been given,
        # and ``_links_kept`` the lane the last dispatch handed back: a lane
        # that is not that object was set or assigned since (the identity
        # idiom of ``CarriedMasks``), and the counter takes all it has lost.
        self.links: Optional[LinkFaults] = None
        self._links_kept: Optional[LinkFaults] = None
        self._link_lost_seen = 0
        # The consensus-path counts (``int32[3]``, in the order of
        # ``exposition.CONSENSUS_PATH_COUNTERS``): None until the first
        # ``set_partition``, and while it is None every verb dispatches the
        # program and fetches the bytes it always did. From then on the
        # counts ride every round program and every ``_fetch``;
        # ``_paths_seen`` is what the counters have been given of them.
        self.paths: Optional[jnp.ndarray] = None
        self._paths_seen = np.zeros(len(exposition.CONSENSUS_PATH_COUNTERS), np.int64)
        self._controls: Dict[int, jnp.ndarray] = {}  # see ``_control``
        self._rng = np.random.default_rng(0)
        # Engine-level telemetry: host-side counters over device dispatches
        # (the per-node flight recorder has no device analog — the engine's
        # observability grain is the dispatch, not the message). Compile
        # events are process-global (one XLA cache per process), captured by
        # the engine_telemetry collector and read at snapshot time.
        self.metrics = Metrics()
        if mesh is not None:
            # How many devices hold the state, and how many leaves a verb
            # has ever left off the rule table (tests hold it at 0).
            self.metrics.inc("engine_state_devices", mesh.devices.size)
            self.metrics.inc("engine_sharding_drift", 0)
        # Attached by rapid_tpu.serving.StreamDriver: the streaming pipeline
        # surfaces its sustained-throughput stats through this cluster's
        # telemetry snapshot (None = batch-only driver, no stream section).
        self.stream = None
        # Attached by rapid_tpu.serving.supervisor.Supervisor: the
        # self-healing tier's checkpoint/retry/wedge stats (None = no
        # supervision, no recovery section).
        self.recovery = None
        self._carried = CarriedMasks(edge_masks_build)
        # Device telemetry plane (cfg.telemetry == 1): the lanes live on
        # device beside the state; the host keeps only a digest cache,
        # zero-minted at attach (the exposition series exist from the first
        # scrape, never mid-run) and refreshed ONLY at host-sync boundaries.
        self.telem = self._fresh(initial_telemetry) if cfg.telemetry else None
        self._activity = (
            engine_telemetry.zero_activity_summary(cfg.n, cfg.c)
            if cfg.telemetry
            else None
        )
        # Device round-trace ring (cfg.trace == R > 0): a refinement of the
        # telemetry plane — its active-subject record reuses the telemetry
        # block's reduction, so a ring without the plane has nothing to
        # record from. Not an assert: python -O must not skip this.
        if cfg.trace and not cfg.telemetry:
            raise ValueError(
                "EngineConfig.trace requires telemetry: the round-trace ring "
                "refines the telemetry plane (pass telemetry=True)"
            )
        if cfg.trace < 0:
            raise ValueError(f"trace capacity must be >= 0, got {cfg.trace}")
        self.trace_ring = self._fresh(initial_trace) if cfg.trace else None
        self._trace = (
            engine_telemetry.zero_trace_summary(cfg.trace)
            if cfg.trace
            else None
        )
        engine_telemetry.install()

    # -- placement ------------------------------------------------------

    def _fresh(self, make):
        """``make(cfg)``: zeroed lanes, made on their shards under a mesh."""
        if self.mesh is None:
            return make(self.cfg)
        return _mesh_lib().fresh_on_mesh(make, self.cfg, self.mesh)

    def _upload(self, field: str, host_array: np.ndarray) -> jnp.ndarray:
        """A whole host-made lane to the device(s) that hold ``field``."""
        self._account_h2d(host_array)
        if self.mesh is None:
            return jnp.asarray(host_array)
        return _mesh_lib().place_leaf(field, host_array, self.mesh)

    def _note_placement(self) -> None:
        """After a verb on a mesh: count the leaves that left the rule
        table (``engine_sharding_drift``). Host work, no device call."""
        if self.mesh is None:
            return
        off = _mesh_lib().off_table
        drifted = sum(
            len(off(tree, self.mesh))
            for tree in (self.state, self.faults, self.telem, self.trace_ring)
            if tree is not None
        )
        if drifted:
            self.metrics.inc("engine_sharding_drift", drifted)

    # -- construction ---------------------------------------------------

    @classmethod
    def create(
        cls,
        n_members: int,
        n_slots: Optional[int] = None,
        k: int = 10,
        h: int = 9,
        l: int = 4,
        cohorts: int = 2,
        fd_threshold: int = 3,
        seed: int = 0,
        use_pallas: bool = False,
        fallback_rounds: int = 8,
        delivery_spread: int = 0,
        concurrent_coordinators: int = 1,
        fd_window: int = 0,
        delivery_prob_permille: int = 1000,
        pallas_lanes: int = 128,
        compact: bool = False,
        telemetry: bool = False,
        trace: int = 0,
        mesh=None,
    ) -> "VirtualCluster":
        """Synthetic cluster: slot identities are random 64-bit lanes (the
        host never materializes 100K endpoint strings; interop deployments
        use from_endpoints). ``compact=True`` stores the engine state at
        the config-derived narrow dtypes (models/state.compaction_policy)
        — bit-identical protocol behavior, a fraction of the bytes/member
        (the wide layout stays the differential oracle). ``telemetry=True``
        carries the device telemetry plane (models/state.TelemetryLanes)
        through every round — engine results stay bit-identical; off, the
        compiled programs are byte-identical to a pre-telemetry engine.
        ``trace=R`` (requires telemetry) additionally records the last R
        rounds into the device round-trace ring (models/state.TraceRing) —
        same bit-identity and byte-identity contracts, pinned by
        tests/test_trace_ring.py. ``mesh`` builds the cluster sharded over a
        ``('nodes',)`` or ``('cohort','nodes')`` device mesh: the identity
        arrays go from the host to their shards and the state is made
        there, never whole on one device. Slots default to the least
        multiple of the ``nodes`` axis at or above ``n_members``; slots or
        cohorts that do not divide their axis raise ``ShardingShapeError``.
        The Mosaic delivery kernel is not partitioned yet: ``use_pallas``
        with a mesh raises."""
        if mesh is not None and use_pallas:
            raise ValueError(
                "use_pallas is off under a mesh: the delivery kernel is not "
                "partitioned (EngineConfig.use_pallas)"
            )
        n = n_slots if n_slots is not None else n_members
        if mesh is not None and n_slots is None:
            pmesh = _mesh_lib()
            n = pmesh.pad_to_multiple(n_members, mesh.shape[pmesh.NODE_AXIS])
        assert n >= n_members
        _validate_delivery_prob(delivery_prob_permille)
        cfg = EngineConfig(
            n=n, k=k, h=h, l=l, c=cohorts, fd_threshold=fd_threshold,
            use_pallas=use_pallas, fallback_rounds=fallback_rounds,
            delivery_spread=delivery_spread,
            concurrent_coordinators=concurrent_coordinators,
            fd_window=fd_window,
            delivery_prob_permille=delivery_prob_permille,
            pallas_lanes=pallas_lanes,
            compact=int(compact),
            telemetry=int(telemetry),
            trace=int(trace),
        )
        with setup_stage("create"):
            with setup_stage("create.keys"):
                rng = np.random.default_rng(seed)
                key_hi = rng.integers(0, 2**32, size=(k, n), dtype=np.uint32)
                key_lo = rng.integers(0, 2**32, size=(k, n), dtype=np.uint32)
                id_hi = rng.integers(0, 2**32, size=(n,), dtype=np.uint32)
                id_lo = rng.integers(0, 2**32, size=(n,), dtype=np.uint32)
                alive = np.zeros(n, dtype=bool)
                alive[:n_members] = True
                identity = (key_hi, key_lo, id_hi, id_lo, alive)
            with setup_stage("create.state"):
                if mesh is None:
                    state = initial_state(cfg, *identity)
                else:
                    state = _mesh_lib().initial_state_on_mesh(cfg, mesh, *identity)
            cluster = cls(cfg, state, mesh=mesh)
        cluster._rng = rng
        cluster._account_h2d(*identity)
        return cluster

    @classmethod
    def from_endpoints(
        cls,
        endpoints: Sequence,
        n_slots: Optional[int] = None,
        k: int = 10,
        h: int = 9,
        l: int = 4,
        cohorts: int = 2,
        fd_threshold: int = 3,
        use_pallas: bool = False,
        fallback_rounds: int = 8,
        delivery_spread: int = 0,
        concurrent_coordinators: int = 1,
        fd_window: int = 0,
        delivery_prob_permille: int = 1000,
        pallas_lanes: int = 128,
        n_members: Optional[int] = None,
        topology: str = "native",
        compact: bool = False,
        telemetry: bool = False,
        trace: int = 0,
    ) -> "VirtualCluster":
        """Build from real endpoints with the host view's exact ring keys, so
        the engine's topology matches a host MembershipView bit-for-bit.

        ``n_members`` (default: all) marks how many of ``endpoints`` start as
        live members; the rest become keyed-but-dead slots reserved for a
        later ``inject_join_wave`` — their ring keys are already the host
        view's keys for those endpoints, so a join admits them at exactly the
        ring positions the host stack would.

        Callers pairing the engine with a host ``MembershipView`` must thread
        ``topology=view.topology``: the engine's u64 keyspace cannot
        represent the java-compat signed ring order, so java mode is
        rejected (``endpoint_ring_keys``). The parameter defaults to native —
        the only mode the engine supports — so a caller that omits it while
        holding a java view still diverges; threading the view's mode is
        what turns that into a loud failure."""
        if n_members is None:
            n_members = len(endpoints)
        if not 0 < n_members <= len(endpoints):
            # Not an assert: python -O must not skip this — slots past the
            # keyed endpoints would go live with all-zero ring keys.
            raise ValueError(
                f"n_members must be in [1, {len(endpoints)}], got {n_members}"
            )
        n = n_slots if n_slots is not None else len(endpoints)
        _validate_delivery_prob(delivery_prob_permille)
        cfg = EngineConfig(
            n=n, k=k, h=h, l=l, c=cohorts, fd_threshold=fd_threshold,
            use_pallas=use_pallas, fallback_rounds=fallback_rounds,
            delivery_spread=delivery_spread,
            concurrent_coordinators=concurrent_coordinators,
            fd_window=fd_window,
            delivery_prob_permille=delivery_prob_permille,
            pallas_lanes=pallas_lanes,
            compact=int(compact),
            telemetry=int(telemetry),
            trace=int(trace),
        )
        with setup_stage("create"):
            with setup_stage("create.keys"):
                key_hi0, key_lo0 = endpoint_ring_keys(endpoints, k, topology=topology)
                key_hi = np.zeros((k, n), dtype=np.uint32)
                key_lo = np.zeros((k, n), dtype=np.uint32)
                key_hi[:, : len(endpoints)] = np.asarray(key_hi0)
                key_lo[:, : len(endpoints)] = np.asarray(key_lo0)
                rng = np.random.default_rng(1234)
                id_hi = rng.integers(0, 2**32, size=(n,), dtype=np.uint32)
                id_lo = rng.integers(0, 2**32, size=(n,), dtype=np.uint32)
                alive = np.zeros(n, dtype=bool)
                alive[:n_members] = True
            with setup_stage("create.state"):
                state = initial_state(cfg, key_hi, key_lo, id_hi, id_lo, alive)
            cluster = cls(cfg, state)
        cluster._account_h2d(key_hi, key_lo, id_hi, id_lo, alive)
        return cluster

    # -- fault & membership injection ----------------------------------

    def _checked_slots(
        self, slots: Sequence[int], size: Optional[int] = None, what: str = "slot"
    ) -> np.ndarray:
        """Host-side bounds check. jnp's gather/scatter CLAMPS out-of-range
        indices instead of raising (a typo'd slot would silently
        inspect/mutate slot n-1), so every lifecycle mutation validates on
        host where it is free — no extra fetch, the indices originate here.
        ``size`` and ``what`` name another axis than the slots (the cohorts)."""
        size = self.cfg.n if size is None else size
        arr = np.asarray(slots, dtype=np.int32)
        if arr.size and (arr.min() < 0 or arr.max() >= size):
            raise IndexError(
                f"{what} indices out of range [0, {size}): "
                f"{arr[(arr < 0) | (arr >= size)].tolist()}"
            )
        return arr

    def _slot_index(self, slots: Sequence[int], wave: bool = False) -> jnp.ndarray:
        """Host-side bounds check (:meth:`_checked_slots`), then upload.

        ``wave``: the slots are one wave's events, of which a stream brings
        a handful at a time and every count sooner or later. Up to
        :data:`SMALL_WAVE_SLOTS` of them go up as a vector of exactly that
        length, filled with the out-of-range slot ``n``: an update at ``n``
        is dropped by every scatter and a look-up at ``n`` reads a row
        nothing keeps, so the padded entries change no bit, and a wave of a
        size this process has not seen dispatches the programs it already
        has where it would compile a set of its own in the serving path. A
        larger wave (a batch seam's) goes up as it is."""
        arr = self._checked_slots(slots)
        if wave and 0 < arr.size < SMALL_WAVE_SLOTS:
            arr = np.concatenate(
                [arr, np.full(SMALL_WAVE_SLOTS - arr.size, self.cfg.n, dtype=arr.dtype)]
            )
        self._account_h2d(arr)
        return jnp.asarray(arr)

    def crash(self, slots: Sequence[int]) -> None:
        """Crash-stop the given slots (unresponsive until revived). Device-side
        scatter: only the slot indices cross the host->device boundary."""
        with self._dispatch("inject_crash"):
            idx = self._slot_index(slots, wave=True)
            self.faults = self.faults._replace(crashed=self.faults.crashed.at[idx].set(True))
            self._note_placement()

    def revive(self, slots: Sequence[int]) -> None:
        with self._dispatch("inject_crash"):
            idx = self._slot_index(slots, wave=True)
            self.faults = self.faults._replace(crashed=self.faults.crashed.at[idx].set(False))
            self._note_placement()

    def _stamp_fired_edges(self, idx: jnp.ndarray, edge_mask) -> None:
        """Mark (slot, ring) edges as fired at the current round (device-side
        scatter — only slot indices and the [j, k] mask cross the boundary);
        the round body's delivery machinery then applies per-cohort rx-block
        masks and delay jitter. Shared by join waves and leaves, which pass
        the ALREADY-UPLOADED bounds-checked index array (an np.asarray here
        would round-trip it back through the host)."""
        state = self.state
        if isinstance(edge_mask, np.ndarray):
            # Host-originated mask: a real upload. A device-resident mask
            # (the join wave's pred-derived bools) uploads nothing — and
            # materializing it here just to count bytes would pay exactly
            # the D2H round trip this path exists to avoid.
            self._account_h2d(edge_mask)
        em = jnp.asarray(edge_mask)  # [j, k] bool
        rdt = state.fire_round.dtype  # policy round dtype + its sentinel
        pol = compaction_policy(self.cfg)
        self.state = state._replace(
            fd_fired=state.fd_fired.at[idx].set(em),
            fire_round=state.fire_round.at[idx].set(
                jnp.where(
                    em,
                    state.round_idx.astype(rdt),
                    jnp.asarray(pol.fire_never, rdt),
                )
            ),
        )

    def initiate_leave(self, slots: Sequence[int]) -> None:
        """Graceful batched leave: the LEAVER broadcasts its own departure as
        a DOWN alert on every ring (LeaveMessage semantics,
        MembershipService.java:296-307) — no fd_threshold detection delay.
        The alert source is the leaver itself, so each slot becomes its own
        column's observer: per-cohort delivery gates on hearing the LEAVER
        (not its ring observers), exactly like the reference's self-broadcast.
        Leavers also stop responding (crashed), so they cannot vote in their
        own eviction. Implicit-invalidation observers (inval_obs) keep the
        real ring topology."""
        slots = np.asarray(slots, dtype=np.int32)
        state = self.state
        idx = self._slot_index(slots)
        self.state = state._replace(
            obs_idx=state.obs_idx.at[:, idx].set(
                jnp.broadcast_to(
                    idx[None, :], (self.cfg.k, len(slots))
                ).astype(state.obs_idx.dtype)
            )
        )
        self._stamp_fired_edges(idx, np.ones((len(slots), self.cfg.k), dtype=bool))
        # Inline crash scatter with the already-validated, already-uploaded
        # index (a self.crash(slots) call would bounds-check and upload again).
        self.faults = self.faults._replace(crashed=self.faults.crashed.at[idx].set(True))
        self._note_placement()

    def set_link_faults(
        self, slots: Sequence[int], loss_permille: int = LINK_LOSS_DEAD,
        on_rounds: int = 0, off_rounds: int = 0, seed: int = 0,
    ) -> None:
        """One-way link faults on the given slots (models/state.LinkFaults):
        each loses ``loss_permille`` of what is sent TO it and keeps sending,
        in the on-phases of a schedule of ``on_rounds`` on / ``off_rounds``
        off that starts with the next round (``off_rounds`` 0: always on).
        The call replaces whatever lane stood before; with no slots it
        clears it, and the cluster is back on the programs of a cluster that
        never had one. Device-side scatter: only the slot indices and four
        scalars cross the host->device boundary, in ONE upload (what a
        transfer costs the host is the call, not the bytes). The per-edge
        masks stay valid (they read ``alive``, ``crashed`` and ``rx_block``
        only)."""
        if self.mesh is not None:
            raise ValueError(
                "link faults are off under a mesh: the lane has no "
                "partition rule (parallel/mesh.PARTITION_RULES)"
            )
        if not 0 <= loss_permille <= LINK_LOSS_DEAD:
            raise ValueError(
                f"loss_permille must be in [0, {LINK_LOSS_DEAD}], got {loss_permille}"
            )
        if on_rounds < 0 or off_rounds < 0 or (off_rounds and not on_rounds):
            raise ValueError(
                f"need on_rounds >= 0 and off_rounds >= 0, and an on-phase "
                f"where there is an off-phase: got {on_rounds} on, {off_rounds} off"
            )
        with self._dispatch("inject_link_faults"):
            # Minted with the first lane, so the series is in every scrape
            # from then on; a cluster that never sets one never grows it.
            self.metrics.inc("engine_link_probes_lost", 0)
            if not len(slots):
                self.links = None
                return
            packed = np.concatenate([
                np.asarray(
                    [loss_permille, on_rounds, off_rounds, seed & 0xFFFFFFFF],
                    dtype=np.uint32,
                ),
                self._checked_slots(slots).astype(np.uint32),
            ])
            self._account_h2d(packed)
            self.links = link_faults_place(self.cfg.n, jnp.asarray(packed))

    def _fetch(self, observation) -> np.ndarray:
        """A verb's int32 observation, fetched flat and charged to the
        transfer counter. A set lane's ``probes_lost`` rides the same
        transfer, four bytes more, and ``engine_link_probes_lost`` gets what
        the lane lost since its last fetch; the consensus-path counts,
        where a partition has ever been set, ride behind it, twelve bytes,
        and the three counters get what the rounds since the last fetch
        added (a ``step`` fetches nothing: its counts arrive with the next
        verb that does)."""
        tail = _lane_tail(
            None if self.links is None else self.links.probes_lost[None],
            self.paths,
        )
        if tail:
            observation = jnp.concatenate([jnp.ravel(observation), *tail])
        self._wait_begins()
        fetched = np.asarray(observation).reshape(-1)
        self._account_d2h(fetched.nbytes)
        if self.paths is not None:
            counts = fetched[-len(self._paths_seen):].astype(np.int64)
            for name, more in zip(
                exposition.CONSENSUS_PATH_COUNTERS, counts - self._paths_seen
            ):
                self.metrics.inc(name, int(more))
            self._paths_seen = counts
            fetched = fetched[: -len(counts)]
        if self.links is not None:
            lost = int(fetched[-1])
            self.metrics.inc("engine_link_probes_lost", lost - self._link_lost_seen)
            self._link_lost_seen = lost
            fetched = fetched[:-1]
        return fetched

    def set_flaky_edges(self, probe_fail: np.ndarray) -> None:
        """Arbitrary per-(subject, ring) probe failures — asymmetric/one-way
        link patterns."""
        # Cast on host first: what crosses the boundary (and what the byte
        # counter charges) is the 1-byte bool array, not the caller's dtype.
        arr = np.asarray(probe_fail, dtype=bool)
        self.faults = self.faults._replace(probe_fail=self._upload("probe_fail", arr))

    def stagger_fd_counts(self, rng: np.random.Generator, spread_rounds: int) -> None:
        """Randomize per-edge detection latency: failure detectors fire up to
        ``spread_rounds`` rounds apart (negative initial counters). This is
        the engine's analog of real-world detection jitter — the source of
        almost-everywhere-agreement conflicts the H/L watermarks absorb."""
        cdt = np.dtype(compaction_policy(self.cfg).counter)
        if spread_rounds >= np.iinfo(cdt).max:
            # Not an assert: python -O must not skip this — a wrapped offset
            # would silently invert the jitter direction.
            raise ValueError(
                f"spread_rounds {spread_rounds} exceeds the fd_count "
                f"envelope of the {cdt.name} compaction policy"
            )
        offsets = rng.integers(0, spread_rounds + 1, size=(self.cfg.n, self.cfg.k))
        # Cast host-side first: the byte counter charges what actually
        # uploads (the policy-dtype lane, not the rng's int64 draw).
        narrowed = (-offsets).astype(cdt)
        self.state = self.state._replace(fd_count=self._upload("fd_count", narrowed))

    def inject_join_wave(
        self, slots: Sequence[int], check_admissible: bool = True
    ) -> None:
        """Admit a batch of joiners: their gatekeepers (ring predecessors)
        emit UP alerts on all rings at once — the batched equivalent of the
        two-phase join's phase 2 (Cluster.java:406-437).

        The UP alerts ride the SAME delivery machinery as DOWN alerts: the
        gatekeeper becomes the joiner slot's observer (`obs_idx`), the edge
        is marked fired this round, and ``_deliver_alerts`` then applies the
        per-cohort rx-block masks and delivery-delay jitter — so receivers
        diverge on join reports exactly as they do on failure reports.

        Rejoin discipline: a node returning after removal must be admitted
        through a FRESH slot (new identity lanes), never by re-admitting its
        old slot — slot identities are the engine's UUIDs, and reusing one
        would reproduce a previous configuration id (the reference rejects
        reused UUIDs outright, UUIDAlreadySeenError).

        ``check_admissible=False`` skips the [j]-bool admissibility fetch —
        the streaming pipeline's spelling (rapid_tpu/serving): that fetch is
        a host sync that would stall every enqueued wave behind it, and the
        stream's churn generator already owns the slot bookkeeping (fresh
        slots only, never reused). Callers without that host-side guarantee
        must keep the check: an inadmissible joiner silently replays an old
        configuration id."""
        slots = np.asarray(slots)
        state = self.state
        idx = None
        if check_admissible:
            # Enforce the rejoin discipline host-side (the engine's
            # UUIDAlreadySeenError): current members, already-pending
            # joiners, and retired identity lanes are not admissible. Index
            # on device first so the ONE device->host fetch carries [j]
            # bools, not the whole [n] state.
            with self._dispatch("inject_join_admit"):
                idx = self._slot_index(slots)
                bad = (state.alive | state.join_pending | state.retired)[idx]
                self._wait_begins()
                bad = np.asarray(bad)
                self._account_d2h(bad.nbytes)
            if bad.any():
                raise ValueError(
                    f"slots not admissible as joiners (member/pending/retired): "
                    f"{slots[bad].tolist()}"
                )

        with self._dispatch("inject_join_place"):
            if idx is None:
                idx = self._slot_index(slots, wave=True)
            # Expected observers (gatekeepers) of each joiner: the alive ring
            # predecessors of its keys. Everything below is device-side
            # gather/scatter — only the slot indices cross the boundary, which
            # is what keeps a bootstrap wave from paying O(k*n) transfer traffic.
            # One masked maximum a joiner over the slots' static ring
            # positions, no order of the rings built: this sits in
            # bootstrap's timed path.
            pred = predecessor_of_keys(
                state.ring_pos, state.ring_perm, state.alive, idx
            )  # [k, j]

            # The gatekeeper IS the joiner's observer pre-admission (for both
            # alert delivery and implicit invalidation). predecessor_of_keys
            # computes at int32; the scatter narrows to the lane's policy dtype.
            pred_n = pred.astype(state.obs_idx.dtype)
            self.state = state._replace(
                join_pending=state.join_pending.at[idx].set(True),
                obs_idx=state.obs_idx.at[:, idx].set(pred_n),
                inval_obs=state.inval_obs.at[:, idx].set(pred_n),
            )
            # Mark each (joiner, ring) edge as fired now where a gatekeeper
            # exists; delivery (rx-block + jitter) happens in the round body.
            self._stamp_fired_edges(idx, (pred >= 0).T)
            self._note_placement()

    def assign_cohorts(self, cohort_of: np.ndarray) -> None:
        # Host-side cast first so the transfer counter charges the bytes
        # that actually upload — the policy's cohort-index dtype (int32
        # wide, int8/int16 compact), not the caller's int64.
        arr = np.asarray(
            cohort_of, dtype=np.dtype(compaction_policy(self.cfg).cohort)
        )
        self.state = self.state._replace(cohort_of=self._upload("cohort_of", arr))

    def assign_cohorts_roundrobin(self) -> None:
        """Spread the N slots evenly over the C receiver cohorts — the
        sampled-divergence deployment: each cohort is an independently
        jittered receiver whose fast-round vote is shared by ~N/C members."""
        self.assign_cohorts(np.arange(self.cfg.n, dtype=np.int32) % self.cfg.c)

    def set_rx_block(self, rx_block: np.ndarray) -> None:
        """Change per-cohort receive blocking. Re-stamps every fired edge to
        the current round: the round body cond-skips delivery work once all
        fired alerts have matured (their delivered set is static while
        rx-blocks are fixed), so healing a partition mid-configuration must
        re-open delivery or newly-hearable cohorts would never receive the
        old alerts. Re-stamped alerts redeliver within ``delivery_spread``
        rounds — a re-broadcast after the topology change."""
        arr = np.asarray(rx_block, dtype=bool)  # charge the uploaded width
        with self._dispatch("inject_partition"):
            self._place_rx_block(self._upload("rx_block", arr))

    def _place_rx_block(self, lane: jnp.ndarray) -> None:
        """A new receive-block lane, however it was made, and the re-stamp
        of the fired edges that goes with it (see :meth:`set_rx_block`)."""
        self.faults = self.faults._replace(rx_block=lane)
        self.state = self.state._replace(
            fire_round=jnp.where(
                self.state.fd_fired,
                self.state.round_idx.astype(self.state.fire_round.dtype),
                self.state.fire_round,
            )
        )
        self._note_placement()

    def set_partition(self, cohorts: Sequence[int], senders: Sequence[int]) -> None:
        """A one-way partition at the receivers: the named ``cohorts`` stop
        hearing the named ``senders`` (slots) — their alerts, their votes and
        their classic-round messages, everything ``rx_block`` governs — and
        the senders hear and send as before; everybody else as before. The
        call replaces whatever receive blocking stood before; with no
        cohorts or no senders it clears it. Device-side scatter: only the
        two index lists cross the host->device boundary, in ONE upload (a
        dense lane is ``c * n`` bytes, :meth:`set_rx_block`), and the state
        and faults afterwards are those of ``set_rx_block`` of the equal
        dense array, the re-stamp of the fired edges included.

        From the first call on the cluster counts its consensus path on the
        device (``engine_classic_rounds``: rounds in which the classic
        attempt ran; ``engine_classic_decisions`` / ``engine_fast_decisions``:
        which arm decided a committed cut): twelve bytes more in every
        observation fetched, in ``metrics`` and in the scrape from then on.
        A cluster that never sets a partition never grows them.

        Off under a mesh (the placement makes the lane whole on one device;
        ``set_rx_block`` places a host-made lane on its shards)."""
        if self.mesh is not None:
            raise ValueError(
                "set_partition is off under a mesh: the placement program "
                "makes the lane on one device (use set_rx_block)"
            )
        deaf = self._checked_slots(cohorts, self.cfg.c, "cohort")
        unheard = self._checked_slots(senders)
        with self._dispatch("inject_partition"):
            if self.paths is None:
                # Minted with the first partition, so the series are in
                # every scrape from then on.
                for name in exposition.CONSENSUS_PATH_COUNTERS:
                    self.metrics.inc(name, 0)
                self.paths = jnp.zeros((len(self._paths_seen),), dtype=jnp.int32)
            if deaf.size and unheard.size:
                packed = np.concatenate([deaf, unheard])
                self._account_h2d(packed)
                lane = partition_place(
                    self.cfg.c, self.cfg.n, int(deaf.size), jnp.asarray(packed)
                )
            else:  # healed: nothing crosses
                lane = jnp.zeros((self.cfg.c, self.cfg.n), dtype=bool)
            self._place_rx_block(lane)

    # -- execution ------------------------------------------------------

    def _advance(self, verb: str, *controls, max_cuts: Optional[int] = None):
        """Dispatch ``verb``'s round program ("step", "decision", "wave")
        on the pytrees this driver carries and keep what comes back;
        returns the program's observations. One body for a one-device
        cluster and a meshed one: what differs is where the program comes
        from, by what the constructor was handed (a mesh or none)."""
        carried = tuple(
            tree for tree in (self.state, self.telem, self.trace_ring)
            if tree is not None
        )
        if self.mesh is None:
            program = functools.partial(
                _ROUND_PROGRAMS[verb][len(carried) - 1], self.cfg
            )
            if max_cuts is not None:  # the wave's static argument, by position
                controls = (*controls[:2], max_cuts, *controls[2:])
        else:
            program = _mesh_lib().sharded_program(
                verb, self.cfg, self.mesh, len(carried), max_cuts
            )
        if self.links is not None and self.links is not self._links_kept:
            self._link_lost_seen = 0
        out, self.links, self.paths = _lane_off(
            program(
                *carried, self.faults, *controls,
                **_set_lanes(links=self.links, paths=self.paths),
            ),
            self.links, self.paths,
        )
        self._links_kept = self.links
        self.state = out[0]
        if self.telem is not None:
            self.telem = out[1]
        if self.trace_ring is not None:
            self.trace_ring = out[2]
        self._note_placement()
        return out[len(carried):]

    def _control(self, value: int) -> jnp.ndarray:
        """A verb's control scalar (a round budget, a target) as a device
        ``int32``, made once a value: a verb called again with the numbers
        it had before uploads nothing. Every transfer is a call the host
        waits in before it can dispatch: on the chip's machine 0.40 ms a
        ``jnp.int32`` made in the call, 0.19 ms a host scalar handed to the
        jitted call, against 0.38 ms for the whole dispatch with the scalars
        on the device already (PERF section 6, PR 38)."""
        scalar = self._controls.get(value)
        if scalar is None:
            if len(self._controls) >= 64:  # a stream of ever-new targets
                self._controls.clear()
            scalar = self._controls[value] = jnp.int32(value)
        return scalar

    def _step(self, phase: str, **tags) -> StepEvents:
        """ONE body for both step spellings: only the dispatch-phase label
        (and the span's tags) differ, so a change here cannot diverge the
        streamed path from the batch path the bit-identity tests pin.
        Without a mesh the step carries the per-edge masks
        (:func:`engine_step_carried_impl`); on a mesh it is ``engine_step``'s
        sharded form, which builds them in every round."""
        self.metrics.inc("engine_steps")
        self.metrics.inc("engine_convergence_steps")
        with self._dispatch(phase, **tags):
            if self.mesh is not None:
                (events,) = self._advance("step")
            else:
                events, masks = self._advance(
                    "step", self._carried.for_step(self)
                )
                self._carried.keep(self, masks)
        return events

    def step(self) -> StepEvents:
        return self._step("step")

    def stream_step(self, wave: Optional[int] = None) -> StepEvents:
        """One ENQUEUED engine round for the streaming pipeline
        (rapid_tpu/serving): the same compiled program as :meth:`step`
        (``engine_step``'s math on carried masks, bit-identical to it: see
        :meth:`_step`), accounted under the
        ``stream_enqueue`` phase and guaranteed fetch-free, so the host
        returns as soon as JAX has queued the dispatch. The returned events
        stay device-resident (they are the stream driver's completion
        ticket); reading them here would put a host sync on the pipeline.
        ``wave`` (the stream driver's wave index) tags the round's span so a
        wave's enqueues and the fetch that retires it share an identifier."""
        return self._step("stream_enqueue", wave=wave)

    def sync(self) -> int:
        """Force completion of all pending uploads/compute on the cluster
        state and return a cheap checksum (``sync_checksum_impl`` — one
        compiled dispatch, audited by the device_program gate)."""
        with self._dispatch("sync"):
            checksum = sync_checksum(self.state, self.faults, *_lane_tail(self.links))
            self._wait_begins()
            checksum = int(checksum)
            self._account_d2h(4)
        self._refresh_activity()
        return checksum

    def _refresh_activity(self) -> None:
        """Fetch the telemetry digest and refresh the host-side activity
        cache. Called ONLY from host-sync boundaries (sync / stream drain /
        fleet health scans) — the cache, not the device lanes, is what
        ``telemetry_snapshot`` reads, so scrapes never add a device fetch."""
        if self.telem is None:
            return
        # telemetry-fetch-ok: sync barrier — the driver is already paying a
        # blocking device round trip here.
        digest = np.asarray(telemetry_digest(self.telem))
        self._account_d2h(digest.nbytes)
        self._activity = engine_telemetry.activity_summary(
            digest, self.cfg.n, self.cfg.c
        )
        if self.trace_ring is not None:
            # telemetry-fetch-ok: sync barrier — same blocking round trip.
            tdigest = np.asarray(trace_digest(self.trace_ring))
            self._account_d2h(tdigest.nbytes)
            self._trace = engine_telemetry.trace_summary(tdigest, self.cfg.trace)

    @property
    def activity(self) -> Optional[dict]:
        """The last host-sync boundary's activity summary (a copy), or
        None on a telemetry=0 engine — reading it never touches the
        device."""
        return dict(self._activity) if self._activity is not None else None

    @property
    def trace(self) -> Optional[dict]:
        """The last host-sync boundary's decoded trace-ring summary (a
        copy; ``records`` oldest -> newest with global round ordinals), or
        None on a trace=0 engine — reading it never touches the device."""
        if self._trace is None:
            return None
        out = dict(self._trace)
        out["records"] = [dict(r) for r in self._trace["records"]]
        return out

    def run_until_converged(self, max_steps: int = 64) -> Tuple[int, Optional[StepEvents]]:
        """Run rounds until a view change commits; returns (rounds, events)."""
        for round_idx in range(max_steps):
            events = self.step()
            if bool(events.decided):
                return round_idx + 1, events
        return max_steps, None

    def run_to_decision(self, max_steps: int = 64) -> Tuple[int, bool, jnp.ndarray, int]:
        """Single-dispatch convergence: the whole round loop runs on device
        (lax.while_loop); returns (rounds, decided, winner_mask, n_members).
        The winner mask stays on device — every scalar observation travels in
        ONE packed fetch (each device->host fetch blocks the host on the
        device), including the post-cut membership so churn loops don't pay
        an extra fetch per view change."""
        if max_steps > 255:  # not an assert: python -O must not skip this
            raise ValueError(f"max_steps packs into 8 bits, got {max_steps}")
        with self._dispatch("run_to_decision"):
            steps, decided, winner = self._advance(
                "decision", self._control(max_steps)
            )
            if self.cfg.n < (1 << 22):
                # Layout: bits 0-7 steps, bit 8 decided, bits 9-30 membership
                # — one scalar fetch total.
                packed = int(self._fetch(
                    steps
                    | (decided.astype(jnp.int32) << 8)
                    | (self.state.n_members << 9)
                )[0])
                rounds = packed & 0xFF
                was_decided = bool((packed >> 8) & 1)
                members = packed >> 9
            else:
                # Membership no longer fits beside the flags in a positive
                # int32: pay a second fetch rather than return garbage.
                packed = int(self._fetch(steps | (decided.astype(jnp.int32) << 8))[0])
                rounds = packed & 0xFF
                was_decided = bool(packed >> 8)
                members = int(self.state.n_members)
                self._account_d2h(4)
            self._rounds = rounds
        self.metrics.inc("engine_convergence_steps", rounds)
        if was_decided:
            self.metrics.inc("engine_cuts_committed")
        return rounds, was_decided, winner, members

    def run_until_membership(
        self, target: int, max_steps: int = 192, max_cuts: int = 8,
        min_cuts: int = 0,
    ) -> Tuple[int, int, bool, Tuple[int, ...]]:
        """Multi-cut single-dispatch: run convergences — view changes
        applied ON DEVICE between them — until the membership reaches
        ``target``; returns (rounds, cuts_committed, resolved,
        intermediate_sizes).

        A churn that resolves in two cuts, or a bootstrap admission wave of
        several, costs ONE dispatch and ONE small fetch instead of one
        dispatch+fetch per cut. The observation comes back as one small
        int32 vector (a 16+4*max_cuts-byte transfer is the same round trip
        a packed scalar is); intermediate_sizes is the
        membership after each committed cut — the paper's Table 1
        "intermediate views" instrument for free."""
        if not 0 <= target <= self.cfg.n:
            # Not an assert: python -O must not skip this.
            raise ValueError(f"target must be in [0, {self.cfg.n}]: {target}")
        with self._dispatch("run_until_membership"):
            steps, cuts, resolved, sizes = self._advance(
                "wave", self._control(target), self._control(max_steps),
                self._control(min_cuts), max_cuts=int(max_cuts),
            )
            obs = self._fetch(
                jnp.concatenate(
                    [jnp.stack([steps, cuts, resolved.astype(jnp.int32)]), sizes]
                )
            )
            rounds = self._rounds = int(obs[0])
        n_cuts = int(obs[1])
        self.metrics.inc("engine_convergence_steps", rounds)
        self.metrics.inc("engine_cuts_committed", n_cuts)
        return rounds, n_cuts, bool(obs[2]), tuple(obs[3 : 3 + n_cuts].tolist())

    # -- observers ------------------------------------------------------

    @property
    def membership_size(self) -> int:
        self._account_d2h(4)
        return int(self.state.n_members)

    @property
    def alive_mask(self) -> np.ndarray:
        mask = np.asarray(self.state.alive)
        self._account_d2h(mask.nbytes)
        return mask

    @property
    def config_epoch(self) -> int:
        self._account_d2h(4)
        return int(self.state.config_epoch)

    @property
    def config_id(self) -> int:
        self._account_d2h(8)
        return (int(self.state.config_hi) << 32) | int(self.state.config_lo)

    def delivery_delays(self, slots) -> np.ndarray:
        """``int32[c, len(slots), k]``: :func:`delivery_delays` of these
        slots' edges in the configuration the cluster is in now (set-up
        only: one eager computation and one fetch)."""
        out = np.asarray(delivery_delays(self.cfg, self.state.config_epoch, slots))
        self._account_d2h(out.nbytes)
        return out

    # -- observability (utils/exposition.py schema) ---------------------

    def health(self) -> NodeHealth:
        """Cluster-wide health of the N virtual members, in the same
        vocabulary host nodes report (utils/health.py). The engine executes
        every node's round in one fused program, so its aggregate IS the
        cluster view: churn still in flight — a crashed slot not yet evicted
        or a join wave not yet admitted — reads PROPOSING (alerts, cut
        detection, and consensus all progress each round); otherwise STABLE.
        One packed scalar fetch."""
        pending = int(
            jnp.sum(self.state.alive & self.faults.crashed, dtype=jnp.int32)
            + jnp.sum(self.state.join_pending, dtype=jnp.int32)
        )
        self._account_d2h(4)
        return NodeHealth.PROPOSING if pending else NodeHealth.STABLE

    def telemetry_snapshot(self) -> dict:
        """The engine's unified telemetry snapshot — same schema as
        ``MembershipService.telemetry_snapshot`` minus the per-message
        instruments (transport stats, flight recorder) that have no device
        analog, so one scrape pipeline serves host nodes and the engine
        alike. The ``engine`` section carries the device-tier instruments:
        process-wide compile/persistent-cache stats (engine_telemetry) and
        best-effort device memory gauges; dispatch latency histograms and
        transfer-byte counters ride the ordinary ``metrics`` section."""
        return {
            "node": f"virtual-cluster/{self.cfg.n}",
            "configuration_id": self.config_id,
            "membership_size": self.membership_size,
            "health": self.health().value,
            "config_epoch": self.config_epoch,
            "metrics": self.metrics.summary(),
            "engine": {
                "n": self.cfg.n,
                "cohorts": self.cfg.c,
                "use_pallas": self.cfg.use_pallas,
                "compile": engine_telemetry.compile_snapshot(),
                "setup": engine_telemetry.setup_snapshot(),
                "memory": engine_telemetry.device_memory_snapshot(),
                # Streaming tier (rapid_tpu/serving): present only when a
                # StreamDriver is attached — batch-only scrapes keep their
                # series set (golden names pinned either way).
                **(
                    {"stream": self.stream.snapshot()}
                    if self.stream is not None
                    else {}
                ),
                # Supervision tier: present only when a Supervisor is
                # attached (same stable-series rule).
                **(
                    {"recovery": self.recovery.snapshot()}
                    if self.recovery is not None
                    else {}
                ),
                # Device telemetry plane (cfg.telemetry == 1): the HOST
                # CACHE, zero-minted at attach and refreshed only at sync
                # boundaries — a scrape never fetches from device.
                **(
                    {"activity": dict(self._activity)}
                    if self._activity is not None
                    else {}
                ),
                # Device round-trace ring (cfg.trace == R > 0): the same
                # host-cache discipline — decoded at sync boundaries,
                # zero-minted at attach, never fetched by a scrape.
                **(
                    {"trace": self.trace}
                    if self._trace is not None
                    else {}
                ),
            },
            "transport": {},
            "recorder": None,
        }

    def prometheus_text(self) -> str:
        return exposition.prometheus_text(self.telemetry_snapshot())
