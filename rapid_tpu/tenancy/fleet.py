"""The tenant fleet: B independent virtual clusters stepped as ONE compiled
program.

"Millions of users" means fleets of independent membership clusters, not one
giant one (ROADMAP item 4; the Rapid paper evaluates thousands of *single*
clusters' stability under churn — arXiv:1803.03620 §5). The TPU analog of
serving that fleet is batching whole clusters into one dispatch: every
engine impl (``engine_step_impl`` / ``run_to_decision_impl`` / the
whole-wave convergence loop) vmaps over a leading tenant axis of the
existing ``EngineState``/``FaultInputs`` pytrees, with independent seeds,
fault inputs, and PER-TENANT protocol knobs (H/L watermarks, failure
threshold, classic-fallback delay — :class:`TenantKnobs`, traced int32
lanes, so one executable serves every knob mix). Per-tenant results are
bit-identical to B separate ``VirtualCluster`` runs — the non-negotiable
parity bar, proved by the pinned differential grid in
``tests/test_tenancy.py`` exactly the way ``tests/test_parallel_2d.py``
pinned the 2-D mesh.

Sharding: the leading ``[t]`` axis shards on the ``'tenant'`` axis of the
3-D ``('tenant', 'cohort', 'nodes')`` mesh (``parallel/mesh.py``:
``fleet_state_shardings`` prepends the tenant axis to the SAME rule table —
an uncovered leaf stays a hard error). Tenants never communicate: no
collective in the compiled fleet program may carry the tenant axis in its
replica groups, and the ``device_program`` gate holds the live compiled
programs to that (``fleet3d_step``/``fleet3d_wave``,
``cross_tenant_collectives == 0`` — one such collective fails the build).

Batched-control-flow tradeoffs, stated plainly:

- vmap turns the per-cluster ``lax.cond`` view-change gate into a select —
  the commit math (sort-free ring rebuild, O(N) scans) runs for every
  tenant in every round and is masked away for the undecided. The chip said
  what that costs (ledger, PR 25, 256 tenants of 1,000 under a trickle):
  114 ms of view change on top of a round whose own phases take 31 ms, in
  seven rounds of eight in which no tenant decided. So there are two step
  programs, chosen by whether the caller hands a mesh, never by a knob:
  the MESHLESS step the drivers dispatch (:func:`fleet_step_gated_impl`)
  vmaps ``_compute_round`` alone and applies the view change under ONE
  scalar ``lax.cond(any(decided))`` taken outside the vmap — a round in
  which nobody decided skips the rebuild, a round in which anybody did pays
  it for all (per-tenant select inside the arm), and
  ``engine_fleet_commit_rounds`` counts how often that is. The same step
  carries the stacked per-edge masks from round to round and rebuilds them
  in that arm only (ledger, PR 27: 54 of a 100 ms round went into building
  them anew in every round); ``engine_edge_mask_builds`` counts the builds
  the driver dispatches because its inputs changed under it (and those the
  whole-wave loop runs after a commit, below). On a
  ``'tenant'``-sharded mesh that any() is a cross-tenant reduce, which the
  zero-cross-tenant budget forbids, so :func:`fleet_step_impl` (behind
  :func:`make_fleet_step` and the analyzers' ladder) keeps the lockstep
  select unchanged; a per-shard gate (``shard_map``, local any) belongs
  with the four-chip cell that can measure it (ROADMAP B7).
- the round's OWN conditionals (``deliver``, ``invalidation``, ``classic``)
  meet the same fate under an unnamed vmap: a batched predicate makes each a
  select that runs both arms for every tenant in every round. The chip said
  what that costs (ledger, PR 29, 256 tenants of 1,000): ``invalidation``
  17.6 ms and ``classic`` 6.3 ms of a 59-65 ms round, where a single cluster
  takes the first in a few rounds of a commit and the second in none. So the
  two MESHLESS programs the drivers dispatch (:func:`fleet_step_gated_impl`
  and :func:`fleet_run_to_decision_impl`) give their vmap a name
  (:data:`FLEET_BATCH_AXIS`) and hand it down to ``_compute_round``, where
  each conditional is then taken on "some tenant needs the arm", the
  predicate reduced over that axis to one scalar
  (``utils/dispatch.cond_across``): a round in which no tenant needs an arm
  skips it for the whole fleet, a round in which one does pays it for all
  (per-tenant select inside the arm, so each tenant's result is what the
  select gave). ``engine_fleet_invalidation_rounds`` and
  ``engine_fleet_classic_rounds`` count how often that is. In the fused
  decision a tenant that has decided is frozen by the batched while, but
  its frozen predicates still count in the reduce until the slowest tenant
  decides: that can cost time, never a result. Again the caller decides,
  never a knob: :func:`fleet_step_impl` and :func:`fleet_wave_lockstep_impl`
  (the mesh's and the analyzers' programs) name no axis and keep the select,
  because on a ``'tenant'``-sharded mesh that reduce is a cross-tenant
  collective in the hottest place of the program.
- the whole-wave loop has the same two forms. On a mesh it runs LOCKSTEP
  (:func:`fleet_wave_lockstep_impl`): a ``fori_loop`` over the whole step
  budget with per-tenant freeze masking, the masks built and the view
  change select-applied in every iteration for every tenant, because a
  batched while's predicate is an any() across tenants — a cross-tenant
  collective in the hottest location of the program, which the
  zero-cross-tenant budget forbids; its predicate is a replicated counter
  and finished tenants coast. The MESHLESS wave the driver dispatches
  (:func:`fleet_wave_impl`, ``TenantFleet.run_until_membership``) pays none
  of that: a ``while_loop`` over the gated step's own round
  (:func:`_gated_round`) that ends when no tenant is active, on masks built
  before the loop and rebuilt only at the head of a round that follows a
  commit: where they are next read, not where the topology changed, because
  the loop may end with that commit (the step, whose driver reads them in
  its next round, keeps the rebuild in the view-change arm). A bootstrap
  wave of three rounds and one cut runs three rounds, one view change and
  one mask build, not 192 of each. ``engine_fleet_wave_rounds`` counts the
  lockstep rounds such loops ran, the three gate counters take the loop's
  share, and ``engine_edge_mask_builds`` the builds it ran after a commit
  (0 for a wave that lands in one cut). (``fleet_run_to_decision`` is the
  same kind of program: a dynamic batched while for single-device driver
  use, where there is no mesh and the any() is free.)
- joins reach a stacked fleet through :meth:`TenantFleet.inject_join_wave`:
  ``(tenant, slot)`` pairs, padded per tenant on the device and placed by
  ``predecessor_of_keys`` vmapped over the tenant axis
  (:func:`fleet_join_place_impl`: a masked maximum over each tenant's slot
  axis a query, which reads ``alive`` and the slots' static ring positions
  and builds no ring order), bit-identical to
  ``VirtualCluster.inject_join_wave`` on every tenant before stacking.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rapid_tpu.models.state import (
    LINK_LOSS_DEAD,
    EngineConfig,
    EngineState,
    FaultInputs,
    LinkFaults,
    StepEvents,
    TelemetryLanes,
    TraceRing,
    compaction_policy,
    initial_telemetry,
    initial_trace,
)
from rapid_tpu.models.virtual_cluster import (
    CarriedMasks,
    VirtualCluster,
    _compute_round,
    _count_dense_commit,
    _edge_masks,
    _lane_off,
    _lane_tail,
    _observer_loss,
    _set_lanes,
    apply_view_change_impl,
    delivery_delays,
    engine_step_impl,
    jit_per_observer_count,
    run_to_decision_impl,
    telemetry_digest_impl,
    trace_digest_impl,
)
from rapid_tpu.ops.rings import predecessor_of_keys
from rapid_tpu.parallel.mesh import (
    TENANT_AXIS,
    Mesh,
    NamedSharding,
    _resolve_spec,
    fleet_fault_shardings,
    fleet_state_shardings,
    match_partition_rules,
)
from rapid_tpu.utils import engine_telemetry, exposition
from rapid_tpu.utils.dispatch import DispatchSeam, scope, setup_stage
from rapid_tpu.utils.health import NodeHealth
from rapid_tpu.utils.metrics import Metrics

#: The EngineConfig fields that vary per tenant, as traced
#: :class:`TenantKnobs` lanes. EVERY other config field must be IDENTICAL
#: across a fleet's tenants (they pin array shapes or Python-level trace
#: structure — static branches, unrolled loops), so the static set is
#: DERIVED, not enumerated: a field appended to EngineConfig later is
#: fleet-static by default and fails closed in :meth:`TenantFleet.from_clusters`
#: rather than silently running every tenant with tenant 0's value.
KNOB_FIELDS = ("h", "l", "fd_threshold", "fallback_rounds")

FLEET_STATIC_FIELDS = tuple(
    f for f in EngineConfig._fields if f not in KNOB_FIELDS
)

#: The name the two meshless fleet programs give their ``vmap``'s batch axis
#: (module docstring: the round's conditionals are taken on "some tenant
#: needs the arm"). Not ``TENANT_AXIS``: that is a mesh's axis, and a program
#: on a mesh names no batch axis.
FLEET_BATCH_AXIS = "fleet_tenants"

#: The counters behind the device-carried ``gate_rounds`` vector, in its
#: order: the rounds in which the step's view-change gate opened, and those
#: in which the round's ``invalidation`` and ``classic`` arms ran.
GATE_ROUND_COUNTERS = (
    "engine_fleet_commit_rounds",
    "engine_fleet_invalidation_rounds",
    "engine_fleet_classic_rounds",
)

#: The counters behind the whole-wave loop's ``loop_rounds`` vector, in its
#: order: the lockstep rounds it ran, its share of the three gate counters,
#: and the mask builds it ran at the head of a round that followed a commit.
WAVE_LOOP_COUNTERS = (
    "engine_fleet_wave_rounds", *GATE_ROUND_COUNTERS, "engine_edge_mask_builds",
)

#: Partition rules for the fleet-level knob pytree, in the exact
#: ``parallel/mesh.py`` table style (the ``sharding`` analyzer parses this
#: table too): every knob lane is a [t] array sharded on the tenant axis.
PARTITION_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"h|l|fd_threshold|fallback_rounds", (TENANT_AXIS,)),
)


class TenantKnobs(NamedTuple):
    """Per-tenant protocol knobs as traced int32 lanes — the K/H/L settings
    the reference would spread across B separate JVM configs, batched so one
    executable serves every mix (and the online autotuner can sweep them,
    rapid_tpu/tenancy/autotune.py)."""

    h: jnp.ndarray  # [t] int32 — high watermark
    l: jnp.ndarray  # [t] int32 — low watermark
    fd_threshold: jnp.ndarray  # [t] int32 — failed windows before alerting
    fallback_rounds: jnp.ndarray  # [t] int32 — classic-Paxos recovery delay

    @staticmethod
    def from_configs(cfgs: Sequence[EngineConfig]) -> "TenantKnobs":
        return TenantKnobs(
            h=jnp.asarray([c.h for c in cfgs], dtype=jnp.int32),
            l=jnp.asarray([c.l for c in cfgs], dtype=jnp.int32),
            fd_threshold=jnp.asarray(
                [c.fd_threshold for c in cfgs], dtype=jnp.int32
            ),
            fallback_rounds=jnp.asarray(
                [c.fallback_rounds for c in cfgs], dtype=jnp.int32
            ),
        )


def knob_shardings(mesh: Mesh) -> TenantKnobs:
    """NamedShardings for the knob pytree from :data:`PARTITION_RULES` (the
    [t] lanes shard on 'tenant'; on a mesh without the axis they
    replicate)."""
    specs = match_partition_rules(PARTITION_RULES, TenantKnobs._fields)
    return TenantKnobs(
        **{
            field: NamedSharding(mesh, _resolve_spec(specs[field], mesh))
            for field in TenantKnobs._fields
        }
    )


def _tenant_cfg(cfg: EngineConfig, knobs: TenantKnobs) -> EngineConfig:
    """The per-tenant engine config inside the vmapped trace: the shared
    static geometry with this tenant's traced knob scalars woven in. Every
    knob field is used only in jnp comparisons inside the round body, so a
    tracer is as good as the Python int a single cluster compiles with —
    and lowers to the identical arithmetic."""
    return cfg._replace(
        h=knobs.h,
        l=knobs.l,
        fd_threshold=knobs.fd_threshold,
        fallback_rounds=knobs.fallback_rounds,
    )


def fleet_step_impl(
    cfg: EngineConfig,
    state: EngineState,
    faults: FaultInputs,
    knobs: TenantKnobs,
) -> Tuple[EngineState, StepEvents]:
    """One protocol round for EVERY tenant: ``engine_step_impl`` vmapped
    over the leading tenant axis. Events come back stacked ([t] scalars,
    [t, n] winner masks)."""

    def one(state, faults, kn):
        # no named axis: a nested conditional would be a select (both forms)
        return engine_step_impl(
            _tenant_cfg(cfg, kn), state, faults, dense_arms=True
        )

    return jax.vmap(one)(state, faults, knobs)


def fleet_edge_masks_impl(cfg: EngineConfig, state: EngineState, faults: FaultInputs):
    """``_edge_masks`` for every tenant (it reads no knob): the stacked
    masks the gated step carries."""
    return jax.vmap(lambda s, f: _edge_masks(cfg, s, f))(state, faults)


def fleet_link_faults_place_impl(cfg: EngineConfig, tenants: int, packed) -> LinkFaults:
    """A new stacked link-fault lane in one upload and one dispatch, the
    batched ``link_faults_place``: ``packed`` is ``uint32[4 * t + 2 * m]``,
    every tenant's four controls (``[4, t]``: loss in permille, on rounds,
    off rounds, seed) and then the ``m`` faulty ``(tenant, slot)`` pairs. A
    faulty member gets its own tenant's loss, a tenant without a pair loss 0
    everywhere. Every clock and every count starts at 0."""
    controls = packed[: 4 * tenants].reshape(4, tenants)
    loss, on_rounds, off_rounds = controls[:3].astype(jnp.int32)
    idx = packed[4 * tenants:].reshape(-1, 2).astype(jnp.int32)
    blank = LinkFaults.none(cfg, tenants)
    return blank._replace(
        loss_permille=blank.loss_permille.at[idx[:, 0], idx[:, 1]].set(loss[idx[:, 0]]),
        on_rounds=on_rounds,
        off_rounds=off_rounds,
        seed=controls[3],
    )


def fleet_join_admit_impl(state: EngineState, idx):
    """The rejoin discipline's lane at ``idx`` (``[m, 2]`` of ``(tenant,
    slot)``): ``[m]`` bools, True where the slot is a member, already
    pending or retired, so not admissible as a joiner."""
    occupied = state.alive | state.join_pending | state.retired
    return occupied[idx[:, 0], idx[:, 1]]


@scope("join_predecessors")
def fleet_join_place_impl(cfg: EngineConfig, state: EngineState, idx, width: int):
    """``VirtualCluster.inject_join_wave``'s placement for every tenant at
    once: ``idx`` is ``[m, 2]`` of ``(tenant, slot)``, ``width`` (static) the
    most joiners any one tenant has in it. The pairs are laid out ``[t,
    width]`` on the device (a stable sort by tenant; a tenant with fewer
    joiners, or none, is padded with slot ``n``, which every scatter drops),
    then each tenant's gatekeepers come from ``predecessor_of_keys`` over its
    own rings (one masked maximum over the slots' ring positions a query, no
    ring order rebuilt) and go into ``join_pending``, ``obs_idx``,
    ``inval_obs`` and the fired-edge stamps exactly as the cluster's method
    writes them."""
    n, (m, tenants) = cfg.n, (idx.shape[0], state.alive.shape[0])
    never = compaction_policy(cfg).fire_never
    order = jnp.argsort(idx[:, 0], stable=True)
    tenant, slot = idx[order, 0], idx[order, 1]
    count = jnp.zeros((tenants,), jnp.int32).at[tenant].add(1)
    place = jnp.arange(m, dtype=jnp.int32) - (jnp.cumsum(count) - count)[tenant]
    slots = jnp.full((tenants, width), n, jnp.int32).at[tenant, place].set(slot)

    def one(state, slots):
        at = jnp.minimum(slots, n - 1)  # a padded query asks for a real slot; its answer is dropped
        pred = predecessor_of_keys(
            state.ring_pos, state.ring_perm, state.alive, at
        )  # [k, width]
        pred_n = pred.astype(state.obs_idx.dtype)
        fired = (pred >= 0).T  # [width, k]
        rdt = state.fire_round.dtype
        return state._replace(
            join_pending=state.join_pending.at[slots].set(True, mode="drop"),
            obs_idx=state.obs_idx.at[:, slots].set(pred_n, mode="drop"),
            inval_obs=state.inval_obs.at[:, slots].set(pred_n, mode="drop"),
            fd_fired=state.fd_fired.at[slots].set(fired, mode="drop"),
            fire_round=state.fire_round.at[slots].set(
                jnp.where(fired, state.round_idx.astype(rdt), jnp.asarray(never, rdt)),
                mode="drop",
            ),
        )

    return jax.vmap(one)(state, slots)


def _gated_round(
    cfg: EngineConfig, state: EngineState, observers, faults, knobs, masks,
    active=None, rebuild_masks=True, links=None, observer_loss=None,
):
    """One protocol round for every tenant on the stacked ``masks`` it is
    handed, with the view change under ONE scalar gate: the body the gated
    step and the whole-wave loop share. ``_compute_round`` is vmapped alone
    under :data:`FLEET_BATCH_AXIS`, so its own conditionals stay conditionals
    on "some tenant needs the arm" (module docstring); the commit,
    ``apply_view_change_impl`` vmapped and the per-tenant select, sits in the
    taken arm of ``lax.cond(any(commits))`` outside the vmap, so a round in
    which no tenant commits runs no ring rebuild.

    ``rebuild_masks`` is a Python-level branch, the caller's: who reads the
    masks after this round. The step hands them to its driver, whose next
    rounds read them, so (``True``) ``_edge_masks`` vmapped over the
    committed state rides the same arm, and the masks returned are those of
    the state returned. The arm rebuilds them for EVERY tenant: they are a
    pure function of ``alive``, ``obs_idx`` and the faults, which a round
    leaves alone, so a tenant that does not commit gets its old values back
    and no per-tenant select is needed. A loop may end with this round, so
    (``False``) the arm builds nothing and the masks come back as they went
    in: STALE whenever ``gates[0]`` is set, for the loop to rebuild at the
    head of a round that will read them.

    ``active`` (a loop's ``[t]`` bools, or ``None`` for a single step) is a
    Python-level branch: with ``None`` every tenant that decided commits and
    not one traced operation is added. With a lane, a tenant outside it is
    FROZEN: it does not commit, and its state and observer lanes come back
    as they went in (its masks with them, by the rule above).

    ``links`` (the stacked link-fault lane, ``None`` for a fleet that set
    none: a Python-level branch that then adds not one traced operation)
    rides the vmap to ``_compute_round`` and comes back LAST, a round older;
    a frozen tenant's clock and count stand still with its state.
    ``observer_loss`` is the lane's stacked gather (``_observer_loss`` of
    every tenant, ``uint32[t, n, k]``) where the caller's loop carries it
    beside the masks; without it every round gathers its own.

    Returns ``(state, observers, masks, commits[t], events, gates)`` and,
    with a lane, the lane; ``gates`` is ``int32[3]`` in
    :data:`GATE_ROUND_COUNTERS`' order: whether the view-change gate opened,
    and whether ``invalidation`` and ``classic`` ran, in this round."""

    def one_round(state, faults, kn, masks, *observers, **lane):
        return _compute_round(
            _tenant_cfg(cfg, kn), state, faults, masks, *observers,
            batch_axis=FLEET_BATCH_AXIS, **lane,
        )

    # the round's one output that is one value for the fleet: which arms ran
    (round_state, decided, winner, events, *round_observers, arms_ran), new_links = _lane_off(
        jax.vmap(
            one_round, axis_name=FLEET_BATCH_AXIS,
            out_axes=(*(0,) * (4 + len(observers)), None, *(0,) * len(_lane_tail(links))),
        )(
            state, faults, knobs, masks, *observers,
            **_set_lanes(links=links, observer_loss=observer_loss),
        ),
        links,
    )
    commits = decided if active is None else decided & active

    def commit_one(kn, round_state, winner, commits):
        committed, took_dense = apply_view_change_impl(
            _tenant_cfg(cfg, kn), round_state, winner,
            batch_axis=FLEET_BATCH_AXIS, commits=commits,
        )
        with scope("view_change"):
            return jax.tree_util.tree_map(
                lambda com, rnd: jnp.where(commits, com, rnd), committed, round_state
            ), took_dense

    def commit(s):
        # named, so that the view change's overflow arm stays a conditional
        # on "some committing tenant's cut overflows" (apply_view_change_impl)
        return jax.vmap(commit_one, axis_name=FLEET_BATCH_AXIS)(
            knobs, s, winner, commits
        )

    def commit_and_build(s):
        committed, took_dense = commit(s)
        return committed, fleet_edge_masks_impl(cfg, committed, faults), took_dense

    any_commits = jnp.any(commits)
    nobody = jnp.zeros_like(commits)
    if rebuild_masks:
        new_state, masks, took_dense = jax.lax.cond(
            any_commits, commit_and_build,
            scope("view_keep")(lambda s: (s, masks, nobody)), round_state,
        )
    else:
        new_state, took_dense = jax.lax.cond(
            any_commits, commit, scope("view_keep")(lambda s: (s, nobody)),
            round_state,
        )
    round_observers = _count_dense_commit(round_observers, took_dense)
    gates = jnp.concatenate([any_commits.astype(jnp.int32)[None], arms_ran])
    if active is not None:
        (new_state, round_observers), new_links = _lane_off(
            jax.vmap(
                lambda on, new, old: jax.tree_util.tree_map(
                    lambda n, o: jnp.where(on, n, o), new, old
                )
            )(
                active, (new_state, round_observers, *_lane_tail(new_links)),
                (state, list(observers), *_lane_tail(links)),
            ),
            links,
        )
    return (
        new_state, round_observers, masks, commits, events, gates,
        *_lane_tail(new_links),
    )


def fleet_step_gated_impl(cfg: EngineConfig, state: EngineState, *rest, links=None):
    """The MESHLESS fleet step the drivers dispatch (module docstring): one
    protocol round for every tenant with the view change under ONE scalar
    gate and the per-edge masks CARRIED from round to round
    (:func:`_gated_round`). ``rest`` is ``(*observers, faults, knobs,
    gate_rounds, masks)``, the round programs' one convention
    (``models/virtual_cluster.py``). Per-tenant results are bit-identical to
    :func:`fleet_step_impl` (and to B separate ``VirtualCluster.step``
    runs): the same functions on the same values, only the place of the
    condition and of the build differs.

    ``masks`` must be :func:`fleet_edge_masks_impl` of exactly ``(state,
    faults)`` (the driver's business: ``CarriedMasks``); those returned are
    the masks of ``(new_state, faults)``.

    ``gate_rounds`` is the device-carried ``int32[3]`` behind
    :data:`GATE_ROUND_COUNTERS`: the rounds in which the view-change gate
    opened and those in which ``invalidation`` and ``classic`` ran, fetched
    only at the driver's host-sync boundaries.

    Returns ``(state, *observers, gate_rounds, masks, events)`` and, with a
    link-fault lane (the keyword ``links``), the lane, last."""
    *observers, faults, knobs, gate_rounds, masks = rest
    (new_state, observers, masks, _, events, gates), links = _lane_off(
        _gated_round(cfg, state, observers, faults, knobs, masks, links=links),
        links,
    )
    return (
        new_state, *observers, gate_rounds + gates, masks, events,
        *_lane_tail(links),
    )


def fleet_run_to_decision_impl(
    cfg: EngineConfig, state: EngineState, *rest, links=None
):
    """Per-tenant single-dispatch convergence: ``run_to_decision_impl``
    vmapped; ``rest`` is ``(*observers, faults, knobs, max_steps)``, the
    observers' lanes stacked like the state. The batched while's predicate
    reduces across tenants (vmap's any()), so this entrypoint is for
    SINGLE-DEVICE driver dispatch — the mesh-audited fleet entrypoints are
    the step and the lockstep wave — and it names :data:`FLEET_BATCH_AXIS`
    like the gated step: a round of the loop in which no tenant needs
    ``invalidation`` or ``classic`` runs neither (module docstring).

    Returns ``(state, *observers, steps[t], decided[t], winner[t, n],
    arm_rounds)``, ``arm_rounds`` being ``int32[2]``: the loop's rounds in
    which ``invalidation`` and ``classic`` ran; and, with a link-fault lane
    (the keyword ``links``), the lane, last. Each tenant's ``_converge``
    makes the lane's one gather before its rounds, and the batched while
    freezes a decided tenant's clock and count with the rest of its carry."""
    *observers, faults, knobs, max_steps = rest

    def one(state, *rest, **lane):
        *observers, faults, kn = rest
        return run_to_decision_impl(
            _tenant_cfg(cfg, kn), state, *observers, faults, max_steps,
            batch_axis=FLEET_BATCH_AXIS, **lane,
        )

    (*out, arm_rounds), links = _lane_off(
        jax.vmap(one, axis_name=FLEET_BATCH_AXIS)(
            state, *observers, faults, knobs, **_set_lanes(links=links)
        ),
        links,
    )
    # The batched while freezes a tenant's carry once it has decided, its
    # count with it: the tenant that ran longest counted every round.
    return (*out, jnp.max(arm_rounds, axis=0), *_lane_tail(links))


def fleet_wave_impl(cfg: EngineConfig, state: EngineState, *rest, links=None):
    """The fleet's whole-wave loop: every tenant runs convergences through
    MULTIPLE view changes until its own ``target`` membership (at least its
    own ``min_cuts`` cuts), all in one dispatch — the batched twin of
    ``run_until_membership_impl`` at the gated step's price. A
    ``while_loop`` over :func:`_gated_round` that ends when no tenant is
    active (or at ``max_steps``): the per-edge masks are built once before
    the loop and again only at the head of a round that follows a commit
    (one carried scalar, ``stale``: the build sits where the masks are next
    read, so the commit the wave ends with builds none), the view change
    runs in the rounds in which some active tenant decided, and the round's
    ``deliver`` / ``invalidation`` / ``classic`` arms in the rounds in which
    some tenant needs them (module docstring). A tenant that has resolved,
    or has spent its cuts or its steps, is frozen in place by the round's
    ``active`` lane until the slowest one is done. Per-tenant results are
    bit-identical to the nested per-cluster loop — the same
    ``_compute_round`` / ``apply_view_change_impl`` sequence on the same
    values, only the loop skeleton differs (pinned by tests/test_tenancy.py's
    differential grid).

    ``rest`` is ``(*observers, faults, knobs, target, max_steps, max_cuts,
    min_cuts)``. The observers' lanes are frozen by the SAME ``active`` lane
    that freezes a finished tenant's state: a tenant that coasts after
    resolving accumulates no phantom rounds, its ring's cursor holds still
    and its slots are never overwritten (quarantined tenants — done from
    iteration 0 — record nothing), so counters and decoded ring stay
    bit-identical to a per-cluster ``run_until_membership`` drive (pinned
    with the state parity in tests/test_telemetry_plane.py and
    tests/test_trace_ring.py). The loop's predicate and its gates reduce
    over the tenants, so this is a SINGLE-DEVICE program like the gated step
    and the fused decision; a ``'tenant'``-sharded mesh takes
    :func:`fleet_wave_lockstep_impl`.

    Returns ``(state, *observers, steps[t], cuts[t], resolved[t],
    sizes[t, max_cuts], loop_rounds)``, the last ``int32[5]``: the lockstep
    rounds the loop ran (the slowest tenant's count), then, in
    :data:`GATE_ROUND_COUNTERS`' order, those in which the view-change gate
    opened and in which ``invalidation`` and ``classic`` ran, then the mask
    builds the loop ran (its ``stale`` arm: the rounds that followed a
    commit; the build before the loop is not among them).

    With a link-fault lane (the keyword ``links``) the lane rides the carry
    and comes back LAST. Its one gather, the loss at every edge's observer,
    reads the lane and ``obs_idx`` alone, so it lives where the masks live:
    made before the loop and again only in the ``stale`` arm, never once a
    round (tenants commit in different rounds, so no convergence brackets
    it as the cluster's does). A tenant outside ``active`` keeps its
    ``age`` and ``probes_lost`` as it keeps its state.
    """
    *observers, faults, knobs, target, max_steps, max_cuts, min_cuts = rest
    tenants = target.shape[0]

    def lookups(state, links):
        """What a round reads of the topology as it stands: the per-edge
        masks and, with a lane, its loss at every edge's observer."""
        return (
            fleet_edge_masks_impl(cfg, state, faults),
            None if links is None else jax.vmap(
                lambda s, lane: _observer_loss(cfg, s, lane)
            )(state, links),
        )

    def active_of(steps, done):
        return ~done & (steps < max_steps)

    def cond(carry):
        *_, steps, _, _, done, _ = carry
        return jnp.any(active_of(steps, done))

    def body(carry):
        (state, *observers, masks, observer_loss, links, stale, steps, cuts, sizes,
         done, loop_rounds) = carry
        active = active_of(steps, done)
        # The round before this one committed: its view change left the
        # masks behind, and this round is the first to read them.
        masks, observer_loss = jax.lax.cond(
            stale, lambda: lookups(state, links), lambda: (masks, observer_loss)
        )
        (state, observers, masks, commits, _, gates), links = _lane_off(
            _gated_round(
                cfg, state, observers, faults, knobs, masks, active,
                rebuild_masks=False, links=links, observer_loss=observer_loss,
            ),
            links,
        )
        with scope("loop_result"):
            steps = steps + active.astype(jnp.int32)
            sizes = jnp.where(
                commits[:, None],
                jax.vmap(lambda row, at, members: row.at[at].set(members))(
                    sizes, cuts, state.n_members
                ),
                sizes,
            )
            cuts = cuts + commits.astype(jnp.int32)
            resolved = (state.n_members == target) & (cuts >= min_cuts)
            done = done | (commits & resolved) | (cuts >= max_cuts)
            loop_rounds = loop_rounds + jnp.concatenate(
                [jnp.ones((1,), jnp.int32), gates, stale.astype(jnp.int32)[None]]
            )
        return (
            state, *observers, masks, observer_loss, links, gates[0] > 0, steps,
            cuts, sizes, done, loop_rounds,
        )

    init = (
        state,
        *observers,
        *lookups(state, links),
        links,
        jnp.bool_(False),
        jnp.zeros((tenants,), jnp.int32),
        jnp.zeros((tenants,), jnp.int32),
        jnp.full((tenants, max_cuts), -1, dtype=jnp.int32),
        # The equal-churn trap guard, same as the nested loop's entry
        # condition: already-at-target only resolves vacuously when no cuts
        # are demanded.
        (state.n_members == target) & (min_cuts <= 0),
        jnp.zeros((len(WAVE_LOOP_COUNTERS),), jnp.int32),
    )
    (
        state, *observers, _, _, links, _, steps, cuts, sizes, _, loop_rounds
    ) = jax.lax.while_loop(cond, body, init)
    with scope("loop_result"):
        resolved = (state.n_members == target) & (cuts >= min_cuts)
    return (
        state, *observers, steps, cuts, resolved, sizes, loop_rounds,
        *_lane_tail(links),
    )


def fleet_wave_lockstep_impl(cfg: EngineConfig, state: EngineState, *rest):
    """The whole-wave loop for a ``'tenant'``-sharded MESH
    (:func:`make_fleet_wave`; the drivers dispatch :func:`fleet_wave_impl`),
    restructured LOCKSTEP (module docstring): one flat ``fori_loop`` over
    the shared step budget, each iteration one engine round per tenant with
    the masks built anew, the view change select-applied and finished
    tenants frozen in place, so that nothing in it reduces across tenants.
    Per-tenant results are bit-identical to the nested per-cluster loop and
    to :func:`fleet_wave_impl`.

    ``rest`` is ``(*observers, faults, knobs, target, max_steps, max_cuts,
    min_cuts)``. The observers' lanes are select-gated by the SAME
    ``active`` mask that freezes a finished tenant's state: a tenant that
    coasts after resolving accumulates no phantom rounds, its ring's cursor
    holds still and its slots are never overwritten (quarantined tenants —
    done from iteration 0 — record nothing), so counters and decoded ring
    stay bit-identical to a per-cluster ``run_until_membership`` drive
    (pinned with the state parity in tests/test_telemetry_plane.py and
    tests/test_trace_ring.py). No reduction ever touches the lanes here —
    the digest is the only cross-shard telemetry reduction, and it runs at
    fetch boundaries, never inside this loop.

    Returns ``(state, *observers, steps[t], cuts[t], resolved[t],
    sizes[t, max_cuts])``.
    """
    *observers, faults, knobs, target, max_steps, max_cuts, min_cuts = rest

    def one(state, *rest):
        *observers, faults, kn, tgt, mc = rest
        tcfg = _tenant_cfg(cfg, kn)

        def body(_i, carry):
            state, *observers, steps, cuts, sizes, done = carry
            active = ~done & (steps < max_steps)
            round_state, decided, winner, _, *round_observers = _compute_round(
                tcfg, state, faults, None, *observers, dense_arms=True
            )
            committed, took_dense = apply_view_change_impl(
                tcfg, round_state, winner, dense_arms=True
            )
            commit = active & decided
            round_observers = _count_dense_commit(round_observers, commit & took_dense)
            picked = jax.tree_util.tree_map(
                lambda old, rnd, com: jnp.where(
                    active, jnp.where(commit, com, rnd), old
                ),
                state, round_state, committed,
            )
            observers = jax.tree_util.tree_map(
                lambda old, new: jnp.where(active, new, old),
                observers, round_observers,
            )
            steps = jnp.where(active, steps + 1, steps)
            sizes = jnp.where(
                commit, sizes.at[cuts].set(committed.n_members), sizes
            )
            cuts = cuts + commit.astype(jnp.int32)
            resolved = (picked.n_members == tgt) & (cuts >= mc)
            done = done | (commit & resolved) | (cuts >= max_cuts)
            return (picked, *observers, steps, cuts, sizes, done)

        init = (
            state,
            *observers,
            jnp.int32(0),
            jnp.int32(0),
            jnp.full((max_cuts,), -1, dtype=jnp.int32),
            # The equal-churn trap guard, same as the nested loop's
            # entry condition: already-at-target only resolves vacuously
            # when no cuts are demanded.
            (state.n_members == tgt) & (mc <= jnp.int32(0)),
        )
        state, *observers, steps, cuts, sizes, _ = jax.lax.fori_loop(
            0, max_steps, body, init
        )
        resolved = (state.n_members == tgt) & (cuts >= mc)
        return (state, *observers, steps, cuts, resolved, sizes)

    return jax.vmap(one)(state, *observers, faults, knobs, target, min_cuts)


# ---------------------------------------------------------------------------
# The observers at fleet grain: the SAME TelemetryLanes / TraceRing pytrees
# with a leading [t] axis, riding through the programs above as optional
# pytrees (an observers-off fleet traces none of their code: the invariant
# said on ``_compute_round``).
# ---------------------------------------------------------------------------


def initial_fleet_telemetry(cfg: EngineConfig, tenants: int) -> TelemetryLanes:
    """All-zero telemetry lanes for ``tenants`` clusters: the single-cluster
    lanes with a leading tenant axis, matching the stacked state layout."""
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((tenants,) + x.shape, x.dtype),
        initial_telemetry(cfg),
    )


def initial_fleet_trace(cfg: EngineConfig, tenants: int) -> TraceRing:
    """All-zero trace rings for ``tenants`` clusters: the single-cluster
    ring with a leading tenant axis, matching the stacked lane layout."""
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((tenants,) + x.shape, x.dtype),
        initial_trace(cfg),
    )


def tenant_health_impl(cfg: EngineConfig, state: EngineState) -> jnp.ndarray:
    """The cheap device-side health reduction: one [t] bool lane, True =
    the tenant's state satisfies the protocol invariants. This is the
    serving tier's poisoned-tenant tripwire (rapid_tpu/serving/supervisor):
    every lane is integral, so "finite" materializes as range/consistency
    checks — the device-side twin of ``models/state.validate_envelope``
    plus the cross-lane invariants a corrupted tenant breaks first:

    - ``n_members`` equals the alive population and sits in [0, n];
    - no slot is simultaneously alive and retired (identities are spent
      exactly once);
    - the per-configuration counters (round_idx, rounds_undecided,
      classic_epoch, promised classic ranks) are non-negative, and under a
      compact layout round_idx sits inside ROUND_ENVELOPE (the
      validate_envelope tripwire — past it the narrow fire_round sentinel
      stops being distinguishable).

    Reductions only (no gathers, no cross-tenant ops): the compiled cost is
    one pass over the [t, ...] lanes, and the hlo budgets are untouched —
    this helper is deliberately NOT a registered device_program entrypoint.
    """
    from rapid_tpu.models.state import ROUND_ENVELOPE

    def one(s: EngineState) -> jnp.ndarray:
        ok = s.n_members == jnp.sum(s.alive, dtype=jnp.int32)
        ok &= (s.n_members >= 0) & (s.n_members <= cfg.n)
        ok &= ~jnp.any(s.alive & s.retired)
        ok &= s.round_idx >= 0
        ok &= s.rounds_undecided.astype(jnp.int32) >= 0
        ok &= s.classic_epoch.astype(jnp.int32) >= 0
        ok &= jnp.all(s.cp_rnd_r.astype(jnp.int32) >= 0)
        ok &= s.config_epoch >= 0
        if cfg.compact:
            ok &= s.round_idx <= ROUND_ENVELOPE
        return ok

    return jax.vmap(one)(state)


tenant_health = jax.jit(tenant_health_impl, static_argnums=(0,))  # donate-ok: read-only health reduction — the state must survive the scan

#: The build program: dispatched by the driver only when the masks it
#: carries are not those of the inputs it is about to pass.
fleet_edge_masks = jax.jit(fleet_edge_masks_impl, static_argnums=(0,))  # donate-ok: reads four leaves of a state that stays live
fleet_join_admit = jax.jit(fleet_join_admit_impl)  # donate-ok: reads three lanes of a state that stays live
#: The join wave's placement: the state is donated (five lanes are written in
#: place, the rest pass through), ``width`` is static.
fleet_join_place = jax.jit(
    fleet_join_place_impl, static_argnums=(0, 3), donate_argnums=(1,)
)
#: The link-fault lane's placement: one upload in, a new stacked lane out.
fleet_link_faults_place = jax.jit(fleet_link_faults_place_impl, static_argnums=(0, 1))
#: A fleet verb's programs by observer count, like the cluster's
#: ``_ROUND_PROGRAMS`` (the knobs ride after the faults). The step's
#: gate-round counters and carried masks (its last two arguments) are donated
#: too; the wave's static ``max_cuts`` sits after faults, knobs and two controls.
#: Each takes the stacked link-fault lane as the keyword ``links`` and hands it
#: back last, the round programs' one convention; a call without the keyword
#: is the call, and the program, of a fleet that has none.
_FLEET_PROGRAMS = {
    "step": jit_per_observer_count(fleet_step_gated_impl, donated=(4, 5)),
    "decision": jit_per_observer_count(fleet_run_to_decision_impl),
    "wave": jit_per_observer_count(fleet_wave_impl, static=(6,)),
}
# donate-ok: read-only boundary fetch — the per-tenant lanes stay live.
fleet_telemetry_digest = jax.jit(jax.vmap(telemetry_digest_impl))
# donate-ok: read-only boundary fetch — the per-tenant rings stay live.
fleet_trace_digest = jax.jit(jax.vmap(trace_digest_impl))


def _no_lane_on_a_mesh(links) -> None:
    """The mesh factories' refusal, in ``VirtualCluster.set_link_faults``'s
    words: the lane has no partition rule, stacked or not."""
    if links is not None:
        raise ValueError(
            "link faults are off under a mesh: the lane has no "
            "partition rule (parallel/mesh.PARTITION_RULES)"
        )


def make_fleet_step(cfg: EngineConfig, mesh: Mesh, links=None):
    """jit the fleet step with explicit in-shardings over a
    ``('tenant', 'cohort', 'nodes')`` mesh — the audited batched-step
    entrypoint (``fleet3d_step`` in the ``device_program`` registry: zero
    cross-tenant collectives, donation fully aliased). ``links`` is the
    fleet's link-fault lane, which has to be ``None``: a mesh takes none."""
    _no_lane_on_a_mesh(links)
    st_sh = fleet_state_shardings(mesh)
    ft_sh = fleet_fault_shardings(mesh)
    kn_sh = knob_shardings(mesh)

    return jax.jit(
        lambda state, faults, knobs: fleet_step_impl(cfg, state, faults, knobs),
        in_shardings=(st_sh, ft_sh, kn_sh),
        # The state output is pinned to the input table so a driver loop can
        # feed it straight back (XLA propagation is free to "improve" a
        # replicated dimension onto an idle axis, which would then mismatch
        # the declared in_shardings on the next dispatch); events propagate.
        out_shardings=(st_sh, None),
        donate_argnums=(0,),
    )


def make_fleet_wave(cfg: EngineConfig, mesh: Mesh, max_cuts: int = 8, links=None):
    """jit the lockstep fleet wave with the mesh's shardings — the audited
    batched-wave entrypoint (``fleet3d_wave``). ``target``/``min_cuts`` are
    [t] lanes sharded on 'tenant'; ``max_steps`` is a replicated scalar (it
    is the lockstep loop's only predicate input — the reason the compiled
    hot loop carries no cross-tenant collective). ``links`` as
    :func:`make_fleet_step` takes it: ``None``, or the factory raises."""
    _no_lane_on_a_mesh(links)
    st_sh = fleet_state_shardings(mesh)
    ft_sh = fleet_fault_shardings(mesh)
    kn_sh = knob_shardings(mesh)
    lane = NamedSharding(mesh, _resolve_spec((TENANT_AXIS,), mesh))

    return jax.jit(
        lambda state, faults, knobs, target, max_steps, min_cuts: (
            fleet_wave_lockstep_impl(
                cfg, state, faults, knobs, target, max_steps, max_cuts,
                min_cuts,
            )
        ),
        in_shardings=(st_sh, ft_sh, kn_sh, lane, None, lane),
        # State pinned to the input table (round-trippable, donation-exact);
        # the [t] observation lanes propagate.
        out_shardings=(st_sh, None, None, None, None),
        donate_argnums=(0,),
    )


def stack_pytrees(trees: Sequence):
    """Stack B same-shape pytrees along a new leading tenant axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


class TenantFleet(DispatchSeam):
    """Host driver over the batched engine: owns the stacked state, the
    per-tenant knobs, and the dispatch telemetry (the shared
    :class:`DispatchSeam` — one phase vocabulary across every driver).

    Construction is by stacking ordinary per-tenant ``VirtualCluster``
    builds (:meth:`from_clusters`) — the rx-block, flaky-edge and cohort
    seams stay the single-cluster API, run per tenant BEFORE stacking; the
    fleet then steps all of them per dispatch. ``tests/test_tenancy.py``
    pins that this round-trip is bit-identical to driving the B clusters
    separately. Crashes and join waves also reach the STACKED state
    (:meth:`stream_crash`, :meth:`inject_join_wave`: ``(tenant, slot)``
    pairs, device-side), bit-identical to the cluster's methods run per
    tenant before stacking (``tests/test_fleet_joins.py``). So do one-way
    link faults (:meth:`set_link_faults`): the clusters' ``LinkFaults`` lane
    stacked over the tenants, ``None`` until set, riding every verb as the
    cluster's rides its own (``tests/test_fleet_link_faults.py``)."""

    def __init__(
        self,
        cfg: EngineConfig,
        state: EngineState,
        faults: FaultInputs,
        knobs: TenantKnobs,
    ) -> None:
        b = int(knobs.h.shape[0])
        for leaf in jax.tree_util.tree_leaves((state, faults, knobs)):
            if leaf.shape[:1] != (b,):
                raise ValueError(
                    f"fleet pytrees must share the leading tenant axis "
                    f"({b}); got a leaf of shape {leaf.shape}"
                )
        DispatchSeam.__init__(self)
        self.cfg = cfg
        self.state = state
        self.faults = faults
        self.knobs = knobs
        self.b = b
        self.metrics = Metrics()
        # Attached by rapid_tpu.serving.StreamDriver (None = batch-only).
        self.stream = None
        # Attached by rapid_tpu.serving.supervisor.Supervisor (None = no
        # supervision tier — batch scrapes keep their series set).
        self.recovery = None
        # tenant -> raw frozen membership captured at quarantine time (the
        # per-tenant freeze-lane inputs; see quarantine()).
        self._quarantined: dict = {}
        # Rounds in which the step's gates opened (GATE_ROUND_COUNTERS):
        # carried on the device (the jitted step adds to it), mirrored into
        # the counters only at host-sync boundaries; ``_seen`` is what was
        # mirrored so far.
        self._gate_rounds = jnp.zeros((len(GATE_ROUND_COUNTERS),), dtype=jnp.int32)
        self._gate_rounds_seen = np.zeros((len(GATE_ROUND_COUNTERS),), dtype=np.int32)
        self._gate_rounds_stale = False
        self._carried = CarriedMasks(fleet_edge_masks)
        # The stacked link-fault lane (models/state.LinkFaults under a
        # leading tenant axis): None until ``set_link_faults`` sets one, and
        # while it is None every verb dispatches the program of a fleet that
        # has none.
        # ``_link_lost_seen`` is every tenant's ``probes_lost`` as last
        # fetched and ``_links_kept`` the lane the last dispatch handed back:
        # a lane put there from outside (the setter, a restored copy) starts
        # the fetched counts again.
        self.links: Optional[LinkFaults] = None
        self._links_kept: Optional[LinkFaults] = None
        self._link_lost_seen = np.zeros((b,), dtype=np.int64)
        # Device telemetry plane: per-tenant lanes + the host-side activity
        # cache, zero-minted at attach (every series exists from scrape 0)
        # and refreshed ONLY at host-sync boundaries.
        self.telem = (
            initial_fleet_telemetry(cfg, b) if cfg.telemetry else None
        )
        self._activity = (
            [engine_telemetry.zero_activity_summary(cfg.n, cfg.c)
             for _ in range(b)]
            if cfg.telemetry else None
        )
        # Round-trace ring, per tenant (trace=R refines the telemetry plane;
        # VirtualCluster.__init__ already rejects trace without telemetry,
        # and EngineConfig validation runs there for every construction
        # path, so a fleet config reaching here is consistent).
        self.trace_ring = (
            initial_fleet_trace(cfg, b) if cfg.trace else None
        )
        self._trace = (
            [engine_telemetry.zero_trace_summary(cfg.trace)
             for _ in range(b)]
            if cfg.trace else None
        )
        engine_telemetry.install()

    # -- construction ---------------------------------------------------

    @classmethod
    def from_clusters(cls, clusters: Sequence[VirtualCluster]) -> "TenantFleet":
        """Stack B prepared single-tenant clusters into one fleet. The
        static geometry (slot count, rings, cohorts, delivery model) must
        match across tenants — it pins the one compiled program; the
        per-tenant knobs (H/L, fd_threshold, fallback delay) may differ
        freely and ride :class:`TenantKnobs`."""
        if not clusters:
            raise ValueError("a fleet needs at least one tenant")
        cfgs = [vc.cfg for vc in clusters]
        base = cfgs[0]
        for i, cfg in enumerate(cfgs[1:], start=1):
            diffs = [
                f"{f}: {getattr(base, f)!r} != {getattr(cfg, f)!r}"
                for f in FLEET_STATIC_FIELDS
                if getattr(base, f) != getattr(cfg, f)
            ]
            if diffs:
                raise ValueError(
                    f"tenant {i} differs from tenant 0 in fleet-static "
                    f"config fields ({'; '.join(diffs)}) — these pin the "
                    f"one compiled program; only the TenantKnobs fields "
                    f"may vary per tenant"
                )
        for i, cfg in enumerate(cfgs):
            if not 1 <= cfg.l <= cfg.h <= cfg.k:
                raise ValueError(
                    f"tenant {i}: watermarks must satisfy 1 <= L <= H <= K, "
                    f"got L={cfg.l} H={cfg.h} K={cfg.k}"
                )
            if cfg.fd_window and cfg.fd_threshold > cfg.fd_window:
                raise ValueError(
                    f"tenant {i}: fd_threshold ({cfg.fd_threshold}) cannot "
                    f"exceed fd_window ({cfg.fd_window})"
                )
        fleet = cls(
            base,
            stack_pytrees([vc.state for vc in clusters]),
            stack_pytrees([vc.faults for vc in clusters]),
            TenantKnobs.from_configs(cfgs),
        )
        # The stack re-uploads every tenant's state: charge it once here
        # (the per-cluster builders already charged their own uploads to
        # their own metrics registries, which the fleet does not inherit).
        fleet._account_h2d(*jax.tree_util.tree_leaves(fleet.state))
        if any(vc.links is not None for vc in clusters):
            # The clusters' link-fault lanes, clocks and counts as they
            # stand; a cluster that set none rides as one that names nobody.
            fleet.links = stack_pytrees([
                LinkFaults.none(base) if vc.links is None else vc.links
                for vc in clusters
            ])
            fleet.metrics.inc("engine_link_probes_lost", 0)
            fleet._account_h2d(*jax.tree_util.tree_leaves(fleet.links))
        if base.telemetry:
            # Carry each tenant's accumulated lanes into the stack (a fleet
            # assembled mid-run keeps its tenants' activity stories).
            fleet.telem = stack_pytrees([vc.telem for vc in clusters])
            fleet._account_h2d(*jax.tree_util.tree_leaves(fleet.telem))
        if base.trace:
            # Same carry for the rings: a mid-run stack keeps each tenant's
            # last-R rounds (cursor and wraps included).
            fleet.trace_ring = stack_pytrees(
                [vc.trace_ring for vc in clusters]
            )
            fleet._account_h2d(*jax.tree_util.tree_leaves(fleet.trace_ring))
        return fleet

    @classmethod
    def create(
        cls,
        tenants: int,
        n_members: int,
        n_slots: Optional[int] = None,
        k: int = 10,
        cohorts: int = 2,
        seeds: Optional[Sequence[int]] = None,
        knobs: Optional[Sequence[Tuple[int, int, int]]] = None,
        **engine_kwargs,
    ) -> "TenantFleet":
        """Synthetic fleet: B independent synthetic clusters (independent
        identity seeds), round-robin cohorts, optional per-tenant
        ``(h, l, fd_threshold)`` knob triples."""
        if seeds is None:
            seeds = list(range(tenants))
        if len(seeds) != tenants:
            raise ValueError(f"need {tenants} seeds, got {len(seeds)}")
        if knobs is not None and len(knobs) != tenants:
            raise ValueError(f"need {tenants} knob triples, got {len(knobs)}")
        with setup_stage("fleet_create"):
            clusters = []
            with setup_stage("fleet_create.tenants"):
                for i in range(tenants):
                    h, l, fd = knobs[i] if knobs is not None else (9, 4, 3)
                    vc = VirtualCluster.create(
                        n_members, n_slots=n_slots, k=k, h=h, l=l, cohorts=cohorts,
                        fd_threshold=fd, seed=seeds[i], **engine_kwargs,
                    )
                    vc.assign_cohorts_roundrobin()
                    clusters.append(vc)
            with setup_stage("fleet_create.stack"):
                fleet = cls.from_clusters(clusters)
        return fleet

    # -- execution ------------------------------------------------------

    def _advance(self, verb: str, *controls, max_cuts: Optional[int] = None):
        """Dispatch ``verb``'s fleet program ("step", "decision", "wave") on
        the pytrees this driver carries and keep what comes back; returns
        the program's observations. The short twin of
        ``VirtualCluster._advance``: a fleet takes no mesh, and its knobs
        ride after the faults."""
        carried = tuple(
            tree for tree in (self.state, self.telem, self.trace_ring)
            if tree is not None
        )
        if max_cuts is not None:  # the wave's static argument, by position
            controls = (*controls[:2], max_cuts, *controls[2:])
        if self.links is not None and self.links is not self._links_kept:
            self._link_lost_seen = np.zeros((self.b,), dtype=np.int64)
        out, self.links = _lane_off(
            _FLEET_PROGRAMS[verb][len(carried) - 1](
                self.cfg, *carried, self.faults, self.knobs, *controls,
                **_set_lanes(links=self.links),
            ),
            self.links,
        )
        self._links_kept = self.links
        self.state = out[0]
        if self.telem is not None:
            self.telem = out[1]
        if self.trace_ring is not None:
            self.trace_ring = out[2]
        return out[len(carried):]

    def step(self) -> StepEvents:
        """One protocol round for every tenant — one dispatch, B clusters
        (``engine_dispatch_ms{phase="fleet_step"}``).

        Events come back DEVICE-resident, so ``engine_tenant_cuts`` is
        deliberately not bumped here: reading ``events.decided`` would put
        a host sync on the hot path. The fetching entrypoints
        (:meth:`run_to_decision` / :meth:`run_until_membership`) do the cut
        accounting; a step-driven loop that fetches events itself (the
        autotune sweep) observes its cuts in its own results."""
        return self._step("fleet_step")

    def stream_step(self, wave: Optional[int] = None) -> StepEvents:
        """One ENQUEUED batched round for the streaming pipeline
        (rapid_tpu/serving): the same compiled ``fleet_step`` program as
        :meth:`step` (``fleet_step_impl``'s math under one gate and on carried
        masks, bit-identical to it per tenant), accounted under the
        ``stream_enqueue`` phase and guaranteed fetch-free; the stacked
        events stay device-resident (the stream driver's ticket); ``wave``
        tags the round's span with the stream driver's wave index."""
        return self._step("stream_enqueue", wave=wave)

    def _step(self, phase: str, **tags) -> StepEvents:
        """ONE body for both step spellings: only the dispatch-phase label
        (and the span's tags) differ, so a change here cannot diverge the
        streamed path from the batch path the bit-identity tests pin."""
        self.metrics.inc("engine_tenant_rounds", self.b)
        self._gate_rounds_stale = True
        with self._dispatch(phase, **tags):
            self._gate_rounds, masks, events = self._advance(
                "step", self._gate_rounds, self._carried.for_step(self)
            )
            self._carried.keep(self, masks)
        return events

    def _checked_pairs(self, pairs) -> np.ndarray:
        """``(tenant, slot)`` pairs as ``int32[m, 2]``, bounds-checked on
        the host: jnp gathers and scatters CLAMP out-of-range indices, which
        would silently touch tenant b-1 / slot n-1 on a typo."""
        arr = np.asarray(pairs, dtype=np.int32).reshape(-1, 2)
        if arr.size and (
            arr[:, 0].min() < 0 or arr[:, 0].max() >= self.b
            or arr[:, 1].min() < 0 or arr[:, 1].max() >= self.cfg.n
        ):
            raise IndexError(
                f"(tenant, slot) pairs out of range [0, {self.b}) x "
                f"[0, {self.cfg.n}): {arr.tolist()}"
            )
        return arr

    def _pair_index(self, pairs) -> jnp.ndarray:
        """:meth:`_checked_pairs`, then the upload."""
        arr = self._checked_pairs(pairs)
        self._account_h2d(arr)
        return jnp.asarray(arr)

    def stream_crash(self, pairs) -> None:
        """Crash ``(tenant, slot)`` pairs mid-stream: one device-side
        scatter onto the stacked crash mask — only the [m, 2] int32 index
        array crosses the host->device boundary, and the update enqueues
        behind the in-flight dispatches (no fetch, no sync)."""
        with self._dispatch("inject_crash"):
            idx = self._pair_index(pairs)
            self.faults = self.faults._replace(
                crashed=self.faults.crashed.at[idx[:, 0], idx[:, 1]].set(True)
            )

    def _per_tenant(self, name: str, value) -> np.ndarray:
        """A control given as one scalar or one value a tenant, as ``int64[t]``."""
        try:
            return np.broadcast_to(np.asarray(value, dtype=np.int64), (self.b,))
        except ValueError:
            raise ValueError(
                f"{name} takes a scalar or one value for each of the "
                f"{self.b} tenants, got shape {np.shape(value)}"
            ) from None

    def set_link_faults(
        self, pairs, loss_permille=LINK_LOSS_DEAD, on_rounds=0, off_rounds=0,
        seeds=0,
    ) -> None:
        """One-way link faults on ``(tenant, slot)`` pairs: the batched
        ``VirtualCluster.set_link_faults`` (its docstring has the fault), and
        bit-identical to calling it on every tenant before stacking. The
        loss, the schedule and the seed of the probe draws are one scalar for
        the fleet or one value a tenant; tenants may have different numbers
        of faulty members, and one without a pair gets loss 0 everywhere. The
        call replaces whatever lane stood before; with no pairs it clears it,
        and the fleet is back on the programs of a fleet that never had one.
        ONE upload (every tenant's four controls and the pairs) and ONE
        placement program, enqueued without a fetch. The carried per-edge
        masks stay valid (they read ``alive``, ``crashed`` and ``rx_block``
        only)."""
        loss = self._per_tenant("loss_permille", loss_permille)
        on = self._per_tenant("on_rounds", on_rounds)
        off = self._per_tenant("off_rounds", off_rounds)
        if ((loss < 0) | (loss > LINK_LOSS_DEAD)).any():
            raise ValueError(
                f"loss_permille must be in [0, {LINK_LOSS_DEAD}], got {loss.tolist()}"
            )
        if ((on < 0) | (off < 0) | ((off > 0) & (on == 0))).any():
            raise ValueError(
                f"need on_rounds >= 0 and off_rounds >= 0, and an on-phase "
                f"where there is an off-phase: got {on.tolist()} on, {off.tolist()} off"
            )
        seeds = self._per_tenant("seeds", seeds) & 0xFFFFFFFF
        idx = self._checked_pairs(pairs)
        with self._dispatch("inject_link_faults"):
            # Minted with the first lane, so the series is in every scrape
            # from then on; a fleet that never sets one never grows it.
            self.metrics.inc("engine_link_probes_lost", 0)
            if not len(idx):
                self.links = None
                return
            packed = np.concatenate([loss, on, off, seeds, idx.reshape(-1)]).astype(np.uint32)
            self._account_h2d(packed)
            self.links = fleet_link_faults_place(self.cfg, self.b, jnp.asarray(packed))

    def link_probes_lost(self) -> np.ndarray:
        """``int64[t]``: the probes each tenant's lane has failed since it
        was set, as the last fetching verb brought them (``run_to_decision``
        / ``run_until_membership``; a ``step`` fetches nothing). Reading it
        never touches the device; their sum since the lane was set is what
        ``engine_link_probes_lost`` got."""
        return self._link_lost_seen.copy()

    def _fetch(self, *parts) -> np.ndarray:
        """A verb's int32 observation: ``parts`` concatenated on the device,
        fetched flat in one transfer and charged to the transfer counter. A
        set lane's ``probes_lost[t]`` rides the same transfer, 4·t bytes
        more, and ``engine_link_probes_lost`` gets what the tenants' lanes
        lost since their last fetch."""
        packed = jnp.concatenate(
            [*parts, *_lane_tail(None if self.links is None else self.links.probes_lost)]
        )
        self._wait_begins()
        fetched = np.asarray(packed)
        self._account_d2h(fetched.nbytes)
        if self.links is not None:
            fetched, lost = fetched[: -self.b], fetched[-self.b:].astype(np.int64)
            self.metrics.inc(
                "engine_link_probes_lost", int((lost - self._link_lost_seen).sum())
            )
            self._link_lost_seen = lost
        return fetched

    def inject_join_wave(self, pairs, check_admissible: bool = True) -> None:
        """Admit ``(tenant, slot)`` joiners into the STACKED state: the
        batched ``VirtualCluster.inject_join_wave`` (its docstring has the
        protocol and the rejoin discipline), bit-identical to calling it on
        every tenant before stacking. Tenants may have different numbers of
        joiners in one call, or none. Only the ``[m, 2]`` index array
        crosses to the device; with ``check_admissible`` one fetch of
        ``[m]`` bools comes back (``inject_join_admit``), and the placement
        (``inject_join_place``: every joiner's gatekeepers on every ring,
        the scatters and the fired-edge stamps, one program) enqueues
        without a fetch. The state comes back as new arrays, so the carried
        masks are rebuilt before the next step by ``CarriedMasks``' own
        identity rule."""
        pairs = np.asarray(pairs, dtype=np.int32).reshape(-1, 2)
        idx = None
        if check_admissible:
            with self._dispatch("inject_join_admit"):
                idx = self._pair_index(pairs)
                bad = fleet_join_admit(self.state, idx)
                self._wait_begins()
                bad = np.asarray(bad)
                self._account_d2h(bad.nbytes)
            if bad.any():
                raise ValueError(
                    f"(tenant, slot) pairs not admissible as joiners "
                    f"(member/pending/retired): {pairs[bad].tolist()}"
                )
        with self._dispatch("inject_join_place"):
            if idx is None:
                idx = self._pair_index(pairs)
            if len(pairs):
                # the one host-side number the layout needs: the most joiners
                # a tenant has in this call (a new value is a new program)
                width = int(np.bincount(pairs[:, 0]).max())
                self.state = fleet_join_place(self.cfg, self.state, idx, width)

    def run_to_decision(self, max_steps: int = 64):
        """Every tenant runs to its own first view change in one dispatch;
        returns ``(rounds[t], decided[t], winner[t, n] on device,
        members[t])`` with one packed observation fetch, which also brings
        the loop's rounds in which ``invalidation`` and ``classic`` ran."""
        with self._dispatch("fleet_decision"):
            steps, decided, winner, arm_rounds = self._advance(
                "decision", jnp.int32(max_steps)
            )
            obs = self._fetch(
                steps, decided.astype(jnp.int32), self.state.n_members, arm_rounds
            )
            self._rounds = int(obs[: self.b].max())  # lockstep: the slowest tenant's
        self._refresh_gate_rounds()
        rounds, was_decided, members, arm_rounds = np.split(
            obs, [self.b, 2 * self.b, 3 * self.b]
        )
        was_decided = was_decided.astype(bool)
        self.metrics.inc("engine_tenant_rounds", int(rounds.sum()))
        self.metrics.inc("engine_tenant_cuts", int(was_decided.sum()))
        for name, ran in zip(GATE_ROUND_COUNTERS[1:], arm_rounds):
            self.metrics.inc(name, int(ran))
        return rounds, was_decided, winner, members

    def run_until_membership(
        self,
        targets,
        max_steps: int = 192,
        max_cuts: int = 8,
        min_cuts=0,
    ):
        """The fleet wave: every tenant resolves its own churn — through
        its own number of view changes — to its own target membership, in
        ONE dispatch that ends when the slowest tenant is done
        (:func:`fleet_wave_impl`). ``targets``/``min_cuts`` broadcast from
        scalars or give one value per tenant. Returns ``(rounds[t],
        cuts[t], resolved[t], sizes[t, max_cuts])`` as host arrays; the same
        fetch brings the lockstep rounds the loop ran
        (``engine_fleet_wave_rounds``), those in which its gates opened
        (:data:`GATE_ROUND_COUNTERS`) and the mask builds it ran after a
        commit (into ``engine_edge_mask_builds``, beside the builds the
        step's driver dispatches)."""
        targets = np.broadcast_to(
            np.asarray(targets, dtype=np.int32), (self.b,)
        ).copy()
        min_cuts = np.broadcast_to(
            np.asarray(min_cuts, dtype=np.int32), (self.b,)
        ).copy()
        # Quarantined tenants ride the wave FROZEN: their target lane is
        # pinned to the raw membership captured at quarantine time and
        # min_cuts to 0, so the lockstep loop's done lane is True from
        # iteration 0 — the tenant's state never changes, inside the SAME
        # compiled program (data, not a recompile). The captured value may
        # be garbage (that is WHY the tenant was quarantined), so the range
        # check below applies only to the serving lanes.
        serving = np.ones(self.b, dtype=bool)
        for t, frozen_members in self._quarantined.items():
            targets[t] = frozen_members
            min_cuts[t] = 0
            serving[t] = False
        bad = targets[serving]
        if bad.size and (bad.min() < 0 or bad.max() > self.cfg.n):
            raise ValueError(
                f"targets must be in [0, {self.cfg.n}]: {targets.tolist()}"
            )
        self._account_h2d(targets, min_cuts)
        with self._dispatch("fleet_wave"):
            steps, cuts, resolved, sizes, loop_rounds = self._advance(
                "wave", jnp.asarray(targets), jnp.int32(max_steps),
                jnp.asarray(min_cuts), max_cuts=int(max_cuts),
            )
            obs = self._fetch(
                steps, cuts, resolved.astype(jnp.int32), sizes.reshape(-1), loop_rounds
            )
            # the loop's own count of the lockstep rounds it ran
            self._rounds = int(obs[-len(WAVE_LOOP_COUNTERS)])
        self._refresh_gate_rounds()
        b = self.b
        rounds, n_cuts, resolved_h, sizes_h, loop_rounds = np.split(
            obs, [b, 2 * b, 3 * b, (3 + max_cuts) * b]
        )
        self.metrics.inc("engine_tenant_rounds", int(rounds.sum()))
        self.metrics.inc("engine_tenant_cuts", int(n_cuts.sum()))
        for name, ran in zip(WAVE_LOOP_COUNTERS, loop_rounds):
            self.metrics.inc(name, int(ran))
        return rounds, n_cuts, resolved_h.astype(bool), sizes_h.reshape(b, max_cuts)

    def sync(self) -> None:
        """Complete all pending uploads/compute on the fleet state, a set
        link-fault lane's placement included."""
        jax.block_until_ready((self.state, *_lane_tail(self.links)))
        self._refresh_activity()

    def _refresh_activity(self) -> None:
        """Refresh the per-tenant activity cache from the device lanes —
        called ONLY at host-sync boundaries (sync / health_scan / the
        stream driver's fetch seam), never on the dispatch hot path. The
        step's gate-round counters ride the same boundaries."""
        self._refresh_gate_rounds()
        if self.telem is None:
            return
        # telemetry-fetch-ok: host-sync boundary — the caller is already
        # paying a blocking device round trip here.
        digest = np.asarray(fleet_telemetry_digest(self.telem))
        self._account_d2h(digest.nbytes)
        self._activity = [
            engine_telemetry.activity_summary(
                digest[t], self.cfg.n, self.cfg.c
            )
            for t in range(self.b)
        ]
        if self.trace_ring is not None:
            # telemetry-fetch-ok: same host-sync boundary — one stacked
            # [t, 2 + 9R] digest fetch decodes every tenant's ring.
            tdigest = np.asarray(fleet_trace_digest(self.trace_ring))
            self._account_d2h(tdigest.nbytes)
            self._trace = [
                engine_telemetry.trace_summary(tdigest[t], self.cfg.trace)
                for t in range(self.b)
            ]

    def _refresh_gate_rounds(self) -> None:
        """Mirror the device-carried counts of rounds in which the step's
        gates opened into :data:`GATE_ROUND_COUNTERS` (one 12-byte fetch,
        charged; host-sync boundaries only, and only if a step ran since the
        last one — a fleet driven by the fused loops pays nothing). Over the
        count of ``engine_dispatch_ms{phase="fleet_step"|"stream_enqueue"}``
        they are the shares of fleet rounds that paid a view change, an
        ``invalidation`` and a ``classic`` attempt."""
        if not self._gate_rounds_stale:
            return
        # a copy: the step donates the device's vector away
        totals = np.array(self._gate_rounds)  # host-sync-ok: the caller's boundary
        self._account_d2h(totals.nbytes)
        for name, total, seen in zip(
            GATE_ROUND_COUNTERS, totals, self._gate_rounds_seen
        ):
            self.metrics.inc(name, int(total - seen))
        self._gate_rounds_seen = totals
        self._gate_rounds_stale = False

    @property
    def activity(self) -> Optional[dict]:
        """The fleet-wide activity aggregate from the last host-sync
        boundary (counters summed, peaks maxed across tenants), or None on
        a telemetry=0 fleet — reading it never touches the device."""
        if self._activity is None:
            return None
        return engine_telemetry.aggregate_activity(
            self._activity, self.cfg.n, self.cfg.c
        )

    @property
    def tenant_activity(self) -> Optional[List[dict]]:
        """Per-tenant activity summaries (copies) from the last host-sync
        boundary, or None on a telemetry=0 fleet."""
        if self._activity is None:
            return None
        return [dict(a) for a in self._activity]

    @property
    def tenant_trace(self) -> Optional[List[dict]]:
        """Per-tenant decoded ring digests (deep copies — records included)
        from the last host-sync boundary, or None on a trace=0 fleet.
        Reading it never touches the device."""
        if self._trace is None:
            return None
        out = []
        for tr in self._trace:
            d = dict(tr)
            d["records"] = [dict(r) for r in tr["records"]]
            out.append(d)
        return out

    # -- health & quarantine (the serving supervision tier's seams) ------

    def health_scan(self) -> np.ndarray:
        """Run the device-side health reduction
        (:func:`tenant_health_impl`) over every tenant: one dispatch, one
        [t]-bool fetch; returns the POISONED mask (True = invariants
        violated). Cheap enough to run between waves — the supervisor's
        poisoned-tenant tripwire."""
        with self._dispatch("health_scan"):
            ok = tenant_health(self.cfg, self.state)
            self._wait_begins()
            ok = np.asarray(ok)
            self._account_d2h(ok.nbytes)
        self._refresh_activity()
        return ~ok

    def tenant_health_report(self, t: int) -> List[str]:
        """Host-side diagnosis of ONE tenant: the named violations behind a
        health_scan hit (the repro's violations.txt). Mirrors
        :func:`tenant_health_impl` check for check — the device scan is the
        cheap tripwire, this is the loud explanation, and the two cannot
        disagree on a poisoned tenant because both read the same lanes."""
        from rapid_tpu.models.state import ROUND_ENVELOPE

        if not 0 <= t < self.b:
            raise IndexError(f"tenant index {t} out of range [0, {self.b})")
        s = self.tenant_state(t)
        violations: List[str] = []
        alive = int(np.sum(np.asarray(s.alive)))
        members = int(s.n_members)
        self._account_d2h(np.asarray(s.alive).nbytes + 4)
        if members != alive:
            violations.append(
                f"tenant {t}: n_members={members} != alive population {alive}"
            )
        if not 0 <= members <= self.cfg.n:
            violations.append(
                f"tenant {t}: n_members={members} outside [0, {self.cfg.n}]"
            )
        if bool(np.any(np.asarray(s.alive) & np.asarray(s.retired))):
            violations.append(
                f"tenant {t}: slot(s) simultaneously alive and retired"
            )
        for lane in ("round_idx", "rounds_undecided", "classic_epoch"):
            value = int(getattr(s, lane))
            if value < 0:
                violations.append(f"tenant {t}: {lane}={value} negative")
        if int(np.min(np.asarray(s.cp_rnd_r))) < 0:
            violations.append(f"tenant {t}: negative promised classic rank")
        if int(s.config_epoch) < 0:
            violations.append(
                f"tenant {t}: config_epoch={int(s.config_epoch)} negative"
            )
        if self.cfg.compact and int(s.round_idx) > ROUND_ENVELOPE:
            violations.append(
                f"tenant {t}: round_idx={int(s.round_idx)} past the compact "
                f"envelope {ROUND_ENVELOPE} (validate_envelope tripwire)"
            )
        return violations

    def quarantine(self, tenants: Sequence[int]) -> None:
        """Quarantine tenants inside the RUNNING compiled program: capture
        each tenant's raw membership (one [t] fetch, shared) and pin its
        wave-path freeze lanes to it — the lockstep ``done`` mask the fleet
        wave already carries holds the tenant bit-frozen from iteration 0,
        with no recompile (the lanes are data) and zero effect on the other
        B-1 tenants (vmap independence, the zero-cross-tenant budget of the
        ``device_program`` gate). The batched STEP path has no freeze lane (a
        per-tenant gate there would be a new program input — a recompile,
        which this mechanism exists to avoid): step dispatches keep
        executing the quarantined tenant's rounds, harmlessly to the
        others; serving callers stop feeding it churn and exclude it from
        their accounting (the supervision tier does both). Idempotent per
        tenant; never reversible within a fleet's lifetime (a poisoned
        state has no un-poison story — export the repro and re-admit a
        fresh tenant instead)."""
        members = np.asarray(self.state.n_members)
        self._account_d2h(members.nbytes)
        for t in tenants:
            t = int(t)
            if not 0 <= t < self.b:
                raise IndexError(
                    f"tenant index {t} out of range [0, {self.b})"
                )
            if t not in self._quarantined:
                self._quarantined[t] = int(members[t])
                self.metrics.inc("engine_tenant_quarantines")

    @property
    def quarantined(self) -> Tuple[int, ...]:
        """The quarantined tenant indices, sorted."""
        return tuple(sorted(self._quarantined))

    # -- observers ------------------------------------------------------

    def tenant_state(self, i: int) -> EngineState:
        """Tenant ``i``'s state slice (device-resident views)."""
        if not 0 <= i < self.b:
            raise IndexError(f"tenant index {i} out of range [0, {self.b})")
        return jax.tree_util.tree_map(lambda x: x[i], self.state)

    def membership_sizes(self) -> np.ndarray:
        out = np.asarray(self.state.n_members)
        self._account_d2h(out.nbytes)
        return out

    def config_ids(self) -> List[int]:
        """Per-tenant 64-bit configuration ids, one packed fetch."""
        obs = np.asarray(jnp.stack([self.state.config_hi, self.state.config_lo]))
        self._account_d2h(obs.nbytes)
        return [
            (int(hi) << 32) | int(lo) for hi, lo in zip(obs[0], obs[1])
        ]

    def config_epochs(self) -> np.ndarray:
        out = np.asarray(self.state.config_epoch)
        self._account_d2h(out.nbytes)
        return out

    def delivery_delays(self, slots) -> List[np.ndarray]:
        """Per tenant ``int32[c, len(slots[t]), k]``: the delivery delay of
        every (cohort, slot, ring) edge of ``slots[t]`` in the configuration
        tenant t is in now (``models/virtual_cluster.delivery_delays``).
        Set-up only: the delay is a function of the epoch and not of the
        tenant, so it is one eager computation over all slots and one fetch
        for each distinct epoch, indexed on the host."""
        if len(slots) != self.b:
            raise ValueError(f"need {self.b} slot lists, got {len(slots)}")
        epochs = self.config_epochs()
        tables = {
            int(epoch): np.asarray(
                delivery_delays(self.cfg, epoch, np.arange(self.cfg.n))
            )
            for epoch in np.unique(epochs)
        }
        self._account_d2h(sum(table.nbytes for table in tables.values()))
        return [
            tables[int(epoch)][:, np.asarray(tenant_slots, dtype=np.int64)]
            for epoch, tenant_slots in zip(epochs, slots)
        ]

    def health(self) -> NodeHealth:
        """Fleet-wide health in the host vocabulary: PROPOSING while any
        tenant has churn in flight, STABLE otherwise (one scalar fetch)."""
        pending = int(
            jnp.sum(self.state.alive & self.faults.crashed, dtype=jnp.int32)
            + jnp.sum(self.state.join_pending, dtype=jnp.int32)
        )
        self._account_d2h(4)
        return NodeHealth.PROPOSING if pending else NodeHealth.STABLE

    # -- observability (utils/exposition.py schema) ---------------------

    def telemetry_snapshot(self) -> dict:
        """The fleet's unified telemetry snapshot — the engine schema plus
        a ``tenancy`` section (tenant count, per-dispatch tenant
        throughput), so one scrape pipeline serves host nodes, single
        clusters, and fleets alike (golden names pinned in
        tests/test_engine_telemetry.py)."""
        counters = self.metrics.counters
        dispatches = counters.get("engine_dispatches", 0)
        tenant_rounds = counters.get("engine_tenant_rounds", 0)
        return {
            "node": f"tenant-fleet/{self.b}x{self.cfg.n}",
            "membership_size": int(self.membership_sizes().sum()),
            "health": self.health().value,
            "metrics": self.metrics.summary(),
            "engine": {
                "n": self.cfg.n,
                "cohorts": self.cfg.c,
                "use_pallas": self.cfg.use_pallas,
                "compile": engine_telemetry.compile_snapshot(),
                "setup": engine_telemetry.setup_snapshot(),
                "memory": engine_telemetry.device_memory_snapshot(),
                "tenancy": {
                    "tenants": self.b,
                    "tenant_rounds_total": int(tenant_rounds),
                    "tenant_cuts_total": int(
                        counters.get("engine_tenant_cuts", 0)
                    ),
                    "fleet_commit_rounds_total": int(
                        counters.get("engine_fleet_commit_rounds", 0)
                    ),
                    "fleet_invalidation_rounds_total": int(
                        counters.get("engine_fleet_invalidation_rounds", 0)
                    ),
                    "fleet_classic_rounds_total": int(
                        counters.get("engine_fleet_classic_rounds", 0)
                    ),
                    "fleet_wave_rounds_total": int(
                        counters.get("engine_fleet_wave_rounds", 0)
                    ),
                    "tenant_rounds_per_dispatch": round(
                        tenant_rounds / dispatches, 3
                    ) if dispatches else 0.0,
                    "quarantined": len(self._quarantined),
                },
                # Device telemetry plane: present only when the fleet was
                # built with telemetry=1 (the stable-series rule — a
                # telemetry=0 fleet's scrape vocabulary is unchanged). The
                # aggregate pools every tenant; the per-tenant list feeds
                # the exposition's tenant=<idx> labelled variants.
                **(
                    {
                        "activity": engine_telemetry.aggregate_activity(
                            self._activity, self.cfg.n, self.cfg.c
                        ),
                        "tenant_activity": [
                            dict(a) for a in self._activity
                        ],
                    }
                    if self._activity is not None
                    else {}
                ),
                # Round-trace ring: per-tenant decoded digests, present only
                # on trace>0 fleets (the same stable-series rule).
                **(
                    {"tenant_trace": self.tenant_trace}
                    if self._trace is not None
                    else {}
                ),
                # Streaming tier: present only when a StreamDriver is
                # attached (the VirtualCluster rule — batch-only scrapes
                # keep their series set).
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
            },
            "transport": {},
            "recorder": None,
        }

    def prometheus_text(self) -> str:
        return exposition.prometheus_text(self.telemetry_snapshot())
