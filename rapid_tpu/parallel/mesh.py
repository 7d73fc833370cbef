"""Device-mesh sharding for the virtual-cluster engine.

Two scale axes, one rule table. The engine's state is data-parallel over N
(virtual members) AND over C (receiver cohorts): every per-slot array
partitions on its N dimension over the ``nodes`` mesh axis, and — since the
cohort-meshed refactor — every cohort-dimensioned array partitions on its C
dimension over the ``cohort`` mesh axis. ``make_mesh()`` builds the classic
1-D ``('nodes',)`` mesh; ``make_mesh(shape=(dc, dn))`` builds the 2-D
``('cohort', 'nodes')`` mesh the 1M+ headline benchmark targets. One
regex-driven rule table (:data:`PARTITION_RULES`, the SNIPPETS [1]
``match_partition_rules`` pattern keyed on pytree field names) produces the
sharding tables for EITHER mesh: an axis name absent from the target mesh
drops to replicated on that axis, so the 1-D mesh keeps its exact
historical layout and a new ``EngineState`` leaf that matches no rule is a
hard error — it can never silently replicate.

All of the engine's global reductions (watermark tallies, vote counts, set
hashes) are sums/anys over N or cross-cohort decision reductions over C,
which XLA lowers to psum over ICI; ring topology is re-derived only on view
changes — sort-free O(N) scans over the static key-order perms
(``ring_topology_from_perm``; the one argsort runs at state creation) — and
its cross-shard permutation gathers are the one collective-heavy op (XLA
inserts what it needs). This is not just a docstring claim:
``tools/collective_audit.py`` classifies every collective in the compiled
HLO (EVALUATION.md §3c), ``tests/test_parallel.py`` pins the invariants,
and ``tests/test_hlo_gate.py`` asserts, on both the 1-D and the 2-D compiled
wave, that every donated leaf is aliased and what the round loop carries
outside a conditional: on the 1-D mesh all-reduces alone, of scalar or [n]
class; on the 2-D mesh also two all-to-alls and two all-gathers of scalar
class every round, GSPMD's re-layout of the [c]-sized tally across the
cohort axis (an open cost, ROADMAP A12). [c,n]-scale gathers sit in
lax.cond branches or in the per-convergence mask build on both.

This is the TPU equivalent of the reference's scale story (§ SURVEY 5.7):
the reference keeps per-node load O(K) as N grows; here the whole cluster's
protocol state is data-parallel over the mesh, and per-device cohort state
shrinks by the cohort-axis size instead of replicating.

Compaction: the rule table is keyed on FIELD NAMES, so the config-derived
narrow layout (``EngineConfig.compact=1`` — models/state.compaction_policy)
and the opt-in bit-packed mask representation (``state.pack_masks``: [n] ->
[n/8] uint8 along the slot axis, ranks preserved) shard through the SAME
rules with no second table: per-device bytes shrink by the dtype ratio on
top of the 1/dn axis split. :func:`shard_pytree`'s up-front divisibility
validation covers the packed shapes too — a packed [n/8] lane that does
not divide the node axis raises the same named ``ShardingShapeError``
(pack after padding: ``pad_to_multiple(n, 8 * node_devices)``).
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rapid_tpu.models.state import (
    EngineConfig,
    EngineState,
    FaultInputs,
    TelemetryLanes,
    TraceRing,
    initial_state,
)
from rapid_tpu.models.virtual_cluster import (
    engine_step_impl,
    run_to_decision_impl,
    run_until_membership_impl,
)

NODE_AXIS = "nodes"
COHORT_AXIS = "cohort"
#: The multi-tenant batch axis (rapid_tpu/tenancy): a LEADING [t] dimension
#: stacked over the whole engine pytree, sharded fully parallel — tenants
#: never communicate, so no collective may ever carry the tenant axis in
#: its replica groups (the device_program gate holds it to zero).
TENANT_AXIS = "tenant"

#: Spec tuples are PartitionSpec entries by position: an axis name, or None
#: (that array dimension is not meshed). Empty tuple = fully replicated.
Spec = Tuple[Optional[str], ...]


class ShardingShapeError(ValueError):
    """A pytree leaf's shape does not divide the mesh axes it shards over
    (or its sharding targets a different mesh). Raised by
    :func:`shard_pytree` with the leaf and axis named — XLA's own error for
    the same condition is an opaque HLO sharding failure deep inside
    ``make_array_from_callback``."""


#: Regex-driven partition rules over the engine pytree field names
#: (``EngineState`` + ``FaultInputs`` share one namespace — no field name
#: collides). First match wins; matching is ``re.fullmatch`` so a rule can
#: never accidentally claim a superstring field. The ``sharding`` analyzer
#: family lint-checks this table: every state/fault array leaf must match a
#: rule, a rule matching no leaf is dead, and a fully-replicating rule must
#: justify itself with ``# replicated-ok: <reason>`` on its line.
PARTITION_RULES: Tuple[Tuple[str, Spec], ...] = (
    # [k, n] ring/key/topology tables: slots on the last axis.
    (r"key_hi|key_lo|ring_perm|ring_pos|ring_alive|obs_idx|inval_obs", (None, NODE_AXIS)),
    # [n, k] per-edge failure-detector state: slots on the first axis.
    (r"fd_count|fd_hist|fd_fired|fire_round|probe_fail", (NODE_AXIS, None)),
    # [c] cohort lanes (watermark flags + proposal-id lanes): sharded over
    # the cohort mesh axis — these replicated on every device before the
    # cohort axis was meshed.
    (r"seen_down|announced|prop_hi|prop_lo", (COHORT_AXIS,)),
    # [c, n] cohort-by-slot watermark/delivery state: both axes meshed.
    (r"report_bits|released|prop_mask|rx_block", (COHORT_AXIS, NODE_AXIS)),
    # [n] per-slot lanes (identity, membership, votes, classic-Paxos
    # acceptor state, fault masks).
    (
        r"id_hi|id_lo|alive|join_pending|cohort_of|vote_hi|vote_lo"
        r"|vote_valid|cp_rnd_r|cp_rnd_i|cp_vrnd_r|cp_vrnd_i|cp_vval_src"
        r"|retired|crashed",
        (NODE_AXIS,),
    ),
    (
        r"config_epoch|config_hi|config_lo|n_members|rounds_undecided"
        r"|classic_epoch|round_idx",
        (),  # replicated-ok: per-configuration scalar lanes
    ),
    # Telemetry plane (models/state.TelemetryLanes): the [c, n] activity and
    # invalidation masks shard exactly like the watermark state they
    # observe; the [c] proposal counter rides the cohort axis.
    (r"tl_active|tl_invalidated", (COHORT_AXIS, NODE_AXIS)),
    (r"tl_proposals", (COHORT_AXIS,)),
    (
        r"tl_rounds|tl_alerts|tl_tally_sum|tl_fast_decisions"
        r"|tl_classic_decisions|tl_conflict_rounds|tl_dissent"
        r"|tl_invalidation_rounds|tl_invalidation_dense_rounds"
        r"|tl_view_change_dense"
        r"|tl_undecided_hist",
        (),  # replicated-ok: per-engine scalar counters + the 8-bucket histogram
    ),
    # Round-trace ring (models/state.TraceRing): every lane is a per-round
    # scalar record stretched over the [R] ring axis (no n/c dimension to
    # shard) plus the cursor/wrap scalars.
    (
        r"tr_round|tr_epoch|tr_active|tr_alerts|tr_proposals|tr_tally"
        r"|tr_path|tr_conflict|tr_undecided|tr_cursor|tr_wraps",
        (),  # replicated-ok: [R]-ring per-round scalar records + cursor/wrap counters
    ),
)


def make_mesh(
    devices: Optional[Sequence] = None,
    shape: Optional[Tuple[int, ...]] = None,
) -> Mesh:
    """The engine device mesh: 1-D ``('nodes',)`` by default, 2-D
    ``('cohort', 'nodes')`` when ``shape=(cohort_devices, node_devices)`` is
    given, or 3-D ``('tenant', 'cohort', 'nodes')`` when
    ``shape=(tenant_devices, cohort_devices, node_devices)`` is given (the
    multi-tenant fleet mesh — rapid_tpu/tenancy). The shape product must
    equal the device count."""
    devices = list(devices) if devices is not None else jax.devices()
    if shape is None:
        return Mesh(np.array(devices), (NODE_AXIS,))
    if len(shape) == 2:
        axis_names: Tuple[str, ...] = (COHORT_AXIS, NODE_AXIS)
    elif len(shape) == 3:
        axis_names = (TENANT_AXIS, COHORT_AXIS, NODE_AXIS)
    else:
        raise ValueError(
            f"mesh shape must be (cohort, nodes) or (tenant, cohort, "
            f"nodes), got {shape}"
        )
    if any(d < 1 for d in shape):
        raise ValueError(f"mesh shape must be positive, got {shape}")
    total = 1
    for d in shape:
        total *= d
    if total != len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {total} devices, got {len(devices)}"
        )
    return Mesh(np.array(devices).reshape(shape), axis_names)


def match_partition_rules(
    rules: Sequence[Tuple[str, Spec]], fields: Sequence[str]
) -> Dict[str, Spec]:
    """field name -> spec via the first rule whose regex fullmatches — the
    SNIPPETS [1] ``match_partition_rules`` pattern, keyed on NamedTuple
    field names instead of flax parameter paths. Raises on an uncovered
    field: a new engine-state leaf must be placed in the table before it
    can shard (silent replication of [n]- or [c,n]-scale state is exactly
    the failure mode this table exists to prevent)."""
    out: Dict[str, Spec] = {}
    for name in fields:
        for pattern, spec in rules:
            if re.fullmatch(pattern, name):
                out[name] = spec
                break
        else:
            raise ValueError(
                f"no partition rule matches engine leaf {name!r} — add it "
                f"to rapid_tpu.parallel.mesh.PARTITION_RULES"
            )
    return out


def _resolve_spec(spec: Spec, mesh: Mesh) -> P:
    """A rule spec as a PartitionSpec on ``mesh``: axis names the mesh does
    not carry drop to None (the 1-D ``('nodes',)`` mesh replicates the
    cohort dimension, exactly the pre-2-D layout)."""
    return P(*(ax if ax is None or ax in mesh.axis_names else None for ax in spec))


@functools.lru_cache(maxsize=None)
def _shardings_for(cls, mesh: Mesh):
    specs = match_partition_rules(PARTITION_RULES, cls._fields)
    return cls(
        **{
            field: NamedSharding(mesh, _resolve_spec(specs[field], mesh))
            for field in cls._fields
        }
    )


def state_shardings(mesh: Mesh) -> EngineState:
    """A NamedSharding pytree matching EngineState, built from
    :data:`PARTITION_RULES` for the given 1-D or 2-D mesh."""
    return _shardings_for(EngineState, mesh)


def fault_shardings(mesh: Mesh) -> FaultInputs:
    return _shardings_for(FaultInputs, mesh)


def telemetry_shardings(mesh: Mesh) -> TelemetryLanes:
    """NamedShardings for the telemetry lanes — the SAME rule table (the
    ``tl_`` rules), so the plane shards wherever the state it observes
    shards."""
    return _shardings_for(TelemetryLanes, mesh)


def trace_shardings(mesh: Mesh) -> TraceRing:
    """NamedShardings for the round-trace ring — the SAME rule table (the
    ``tr_`` rules): ring lanes replicate (per-round scalars, no meshed
    dimension), so the ring never adds cross-shard traffic to a round."""
    return _shardings_for(TraceRing, mesh)


def _fleet_shardings_for(cls, mesh: Mesh):
    """The tenant-stacked sharding table: the SAME rule table, with the
    leading ``[t]`` axis of every stacked leaf sharded on ``'tenant'`` and
    the existing rules unchanged underneath — a scalar lane becomes a [t]
    array on 'tenant', a [c, n] leaf becomes [t, c, n] on ('tenant',
    'cohort', 'nodes'). There is deliberately NO second rule table: a leaf
    uncovered by :data:`PARTITION_RULES` is exactly as hard an error for
    the fleet as for a single cluster."""
    specs = match_partition_rules(PARTITION_RULES, cls._fields)
    return cls(
        **{
            field: NamedSharding(
                mesh, _resolve_spec((TENANT_AXIS, *specs[field]), mesh)
            )
            for field in cls._fields
        }
    )


def fleet_state_shardings(mesh: Mesh) -> EngineState:
    """NamedShardings for a tenant-STACKED EngineState ([t, ...] leaves)."""
    return _fleet_shardings_for(EngineState, mesh)


def fleet_fault_shardings(mesh: Mesh) -> FaultInputs:
    return _fleet_shardings_for(FaultInputs, mesh)


def fleet_telemetry_shardings(mesh: Mesh) -> TelemetryLanes:
    """NamedShardings for tenant-STACKED telemetry lanes ([t, ...])."""
    return _fleet_shardings_for(TelemetryLanes, mesh)


def fleet_trace_shardings(mesh: Mesh) -> TraceRing:
    """NamedShardings for tenant-STACKED trace rings ([t, ...]): the tenant
    axis shards, the ring lanes replicate within a tenant block."""
    return _fleet_shardings_for(TraceRing, mesh)


def shard_fleet_state(state: EngineState, mesh: Mesh) -> EngineState:
    """Place a tenant-stacked state onto a ``('tenant', 'cohort', 'nodes')``
    mesh. A tenant count that does not divide the tenant axis raises
    :class:`ShardingShapeError` naming the leaf and ``pad_to_multiple``
    (pad the fleet with idle tenants — an all-dead spare cluster steps for
    free)."""
    return shard_pytree(state, fleet_state_shardings(mesh), mesh=mesh)


def shard_fleet_faults(faults: FaultInputs, mesh: Mesh) -> FaultInputs:
    return shard_pytree(faults, fleet_fault_shardings(mesh), mesh=mesh)


def pad_to_multiple(value: int, multiple: int) -> int:
    """Smallest count >= ``value`` divisible by ``multiple`` — size N slots
    (or C cohorts) so they divide a mesh axis: ``n_slots=pad_to_multiple(n,
    mesh.shape[NODE_AXIS])`` (spare slots stay dead until a join wave uses
    them; spare cohorts simply receive no members)."""
    if multiple < 1 or value < 0:
        raise ValueError(f"pad_to_multiple({value}, {multiple})")
    return ((value + multiple - 1) // multiple) * multiple


def _validate_leaf(label: str, shape: Tuple[int, ...], sharding: NamedSharding) -> None:
    spec = sharding.spec
    for dim, axes in enumerate(spec):
        if axes is None:
            continue
        names = (axes,) if isinstance(axes, str) else tuple(axes)
        size = 1
        for name in names:
            size *= dict(zip(sharding.mesh.axis_names, sharding.mesh.devices.shape))[
                name
            ]
        if dim >= len(shape) or shape[dim] % size:
            got = shape[dim] if dim < len(shape) else "<missing>"
            raise ShardingShapeError(
                f"leaf {label} shape {tuple(shape)}: dimension {dim} "
                f"(= {got}) does not divide mesh axis {'*'.join(names)} "
                f"(size {size}) — pad it to "
                f"pad_to_multiple({got}, {size}) slots (see "
                f"rapid_tpu.parallel.mesh.pad_to_multiple)"
            )


def shard_pytree(tree, shardings, mesh: Optional[Mesh] = None):
    """Place host-computed arrays onto a mesh — single-process OR global
    (multi-controller). ``jax.device_put`` only targets addressable devices,
    so every leaf is assembled via ``jax.make_array_from_callback``: each
    process supplies exactly its addressable shards. In a multi-controller
    job this requires every process to have computed identical host values
    (deterministic seeds) — the standard multi-controller contract.

    ``shardings`` leaves are NamedShardings, or bare PartitionSpecs when an
    explicit ``mesh`` is passed. Every leaf is validated up front: its
    shape must divide the mesh axes it shards over, and (when ``mesh`` is
    given) its sharding must live on that mesh — violations raise
    :class:`ShardingShapeError` naming the leaf and the axis instead of
    XLA's opaque per-shard shape mismatch."""

    def place(path, x, sharding):
        x = np.asarray(x)
        if isinstance(sharding, P):
            if mesh is None:
                raise ShardingShapeError(
                    f"leaf {jax.tree_util.keystr(path)}: a bare "
                    f"PartitionSpec needs an explicit mesh= argument"
                )
            sharding = NamedSharding(mesh, sharding)
        if mesh is not None and sharding.mesh != mesh:
            raise ShardingShapeError(
                f"leaf {jax.tree_util.keystr(path)}: sharding targets mesh "
                f"{sharding.mesh.axis_names}{sharding.mesh.devices.shape}, "
                f"not the requested {mesh.axis_names}{mesh.devices.shape}"
            )
        _validate_leaf(jax.tree_util.keystr(path), x.shape, sharding)
        return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])

    return jax.tree_util.tree_map_with_path(place, tree, shardings)


def shard_state(state: EngineState, mesh: Mesh) -> EngineState:
    """Place an existing (host/single-device) state onto the mesh."""
    return shard_pytree(state, state_shardings(mesh), mesh=mesh)


def shard_faults(faults: FaultInputs, mesh: Mesh) -> FaultInputs:
    return shard_pytree(faults, fault_shardings(mesh), mesh=mesh)


def adopt(tree, mesh: Mesh):
    """A pytree of engine leaves (``EngineState``, ``FaultInputs``,
    ``TelemetryLanes``, ``TraceRing``) onto ``mesh`` by the rule table:
    arrays that already lie on devices move device to device (nothing moves
    where a leaf is on the table already), host arrays go up shard by shard.
    Shapes are validated first, like :func:`shard_pytree`'s."""
    shardings = _shardings_for(type(tree), mesh)

    def place(path, x, sharding):
        if not isinstance(x, jax.Array):
            return shard_pytree(x, sharding, mesh=mesh)
        _validate_leaf(jax.tree_util.keystr(path), x.shape, sharding)
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map_with_path(place, tree, shardings)


def place_leaf(field: str, host_array, mesh: Mesh):
    """One host array up to the shards of engine leaf ``field``."""
    spec = match_partition_rules(PARTITION_RULES, (field,))[field]
    return shard_pytree(
        np.asarray(host_array), NamedSharding(mesh, _resolve_spec(spec, mesh)),
        mesh=mesh,
    )


def off_table(tree, mesh: Mesh) -> Tuple[str, ...]:
    """The fields of an engine pytree whose sharding is not the rule
    table's on ``mesh`` (a ``[k,n]`` leaf the compiler replicated, a lane
    an eager scatter gathered to one device). Host work only."""
    shardings = _shardings_for(type(tree), mesh)
    return tuple(
        field
        for field, leaf, want in zip(tree._fields, tree, shardings)
        if not leaf.sharding.is_equivalent_to(want, leaf.ndim)
    )


@functools.lru_cache(maxsize=None)
def _fresh_program(make, cfg: EngineConfig, mesh: Mesh):
    kind = type(jax.eval_shape(functools.partial(make, cfg)))
    return jax.jit(  # donate-ok: no arguments; the leaves are made on their shards
        functools.partial(make, cfg), out_shardings=_shardings_for(kind, mesh)
    )


def fresh_on_mesh(make, cfg: EngineConfig, mesh: Mesh):
    """``make(cfg)`` (``FaultInputs.none``, ``initial_telemetry``,
    ``initial_trace``) with every leaf made on its own shards: nothing is
    staged whole on one device or uploaded from the host."""
    return _fresh_program(make, cfg, mesh)()


@functools.lru_cache(maxsize=None)
def _initial_state_program(cfg: EngineConfig, mesh: Mesh):
    return jax.jit(  # donate-ok: the identity arrays are inputs, not state
        functools.partial(initial_state, cfg), out_shardings=state_shardings(mesh)
    )


def initial_state_on_mesh(cfg: EngineConfig, mesh: Mesh, key_hi, key_lo, id_hi, id_lo, alive):
    """``initial_state`` built on the mesh: the host's identity arrays go up
    shard by shard, and the one sort, the topology and every zeroed lane are
    made by one program whose outputs are the rule table's. A slot count or
    a cohort count that does not divide its axis raises
    :class:`ShardingShapeError` naming the leaf."""
    shapes = jax.eval_shape(
        functools.partial(initial_state, cfg), key_hi, key_lo, id_hi, id_lo, alive
    )
    for field, leaf, sharding in zip(shapes._fields, shapes, state_shardings(mesh)):
        _validate_leaf(field, leaf.shape, sharding)
    names = ("key_hi", "key_lo", "id_hi", "id_lo", "alive")
    placed = [
        place_leaf(name, x, mesh)
        for name, x in zip(names, (key_hi, key_lo, id_hi, id_lo, alive))
    ]
    return _initial_state_program(cfg, mesh)(*placed)


#: A driver verb's device program: one body, whatever rides beside the state.
_ROUND_IMPLS = {
    "step": engine_step_impl,
    "decision": run_to_decision_impl,
    "wave": run_until_membership_impl,
}
#: Per verb: scalar control arguments after the faults, and observations
#: after the carried pytrees (their placement is the compiler's).
_ROUND_ARITY = {"step": (0, 1), "decision": (1, 3), "wave": (3, 4)}


@functools.lru_cache(maxsize=None)
def sharded_program(
    verb: str, cfg: EngineConfig, mesh: Mesh, carried: int = 1,
    max_cuts: Optional[int] = None,
):
    """THE sharded form of a driver verb, the one ``VirtualCluster`` on a
    mesh dispatches: ``step`` (one round), ``decision``
    (``run_to_decision``) or ``wave`` (``run_until_membership``, multiple
    view changes in one dispatch) jitted over ``mesh`` (1-D or 2-D).
    ``carried`` pytrees (state; + telemetry lanes; + trace ring) only size
    the sharding tables and the donation (the body is the same): they are
    donated and come back with the rule table's shardings, stated and not left to
    propagation: no leaf can drift or silently replicate between verbs, and
    donation aliases every buffer. Call as ``program(*carried, faults,
    *controls) -> (*carried, *observations)``; the wave's controls are
    ``(target, max_steps, min_cuts)`` and ``max_cuts`` is its static bound
    (the other verbs take none). One program per (verb, cfg, mesh):
    a second cluster of the same shape compiles nothing."""
    impl = _ROUND_IMPLS[verb]
    controls, observed = _ROUND_ARITY[verb]
    tables = (
        state_shardings(mesh), telemetry_shardings(mesh), trace_shardings(mesh)
    )[:carried]
    # The invalidation arm's compacted form reduces over the cohort axis and
    # compacts over the node axis, and the view change's bounded update of
    # ``ring_alive`` compacts its cut over the node axis too; a mesh shards
    # both, so every sharded program keeps the dense forms: the dense loop
    # (ops/cut_detection.py) and the whole ``alive[ring_perm]`` gather
    # (ops/rings.ring_liveness), the operations these programs always ran.
    if verb == "wave":
        def program(*args):
            return impl(
                cfg, *args[:-1], max_cuts, args[-1], dense_arms=True
            )
    else:
        def program(*args):
            return impl(cfg, *args, dense_arms=True)
    return jax.jit(
        program,
        in_shardings=(*tables, fault_shardings(mesh), *(None,) * controls),
        out_shardings=(*tables, *(None,) * observed),
        donate_argnums=tuple(range(carried)),
    )


# The names the analyzers register (tools/analysis/device_program.py,
# tools/collective_audit.py) and tests/test_parallel*.py drive; each is the
# driver's own program.


def make_sharded_step(cfg: EngineConfig, mesh: Mesh):
    """``sharded_program("step", ...)``: ``step(state, faults) ->
    (state, events)``."""
    return sharded_program("step", cfg, mesh)


def make_sharded_wave(cfg: EngineConfig, mesh: Mesh, max_cuts: int = 8):
    """``sharded_program("wave", ...)``: ``wave(state, faults, target,
    max_steps, min_cuts) -> (state, steps, cuts, resolved, sizes)``."""
    return sharded_program("wave", cfg, mesh, 1, max_cuts)


def make_sharded_step_telem(cfg: EngineConfig, mesh: Mesh):
    """``sharded_program("step", ..., carried=2)``: the audited
    ``sharded_step_telem`` entrypoint, ``step(state, telem, faults) ->
    (state, telem, events)``."""
    return sharded_program("step", cfg, mesh, 2)
