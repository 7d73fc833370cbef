"""Check family 17: jaxpr dataflow provenance gate (``dataflow``).

The ``device_program`` family reads the compiled artifact. This family
reads its INFLUENCE STRUCTURE: every registered ``device_program``
entrypoint is traced (no XLA compile — ``jitted.trace``) to its closed
jaxpr and a deterministic per-lane taint propagation runs over it, through
``pjit``/``scan``/``while``/``cond`` sub-jaxprs, producing a lane -> lane
influence relation per entrypoint (which input lanes can affect which
output lanes, with ``while``/``scan`` carries tracked PER SLOT so
carry/donated-buffer reuse never fabricates an edge). Two proofs run over
the live trace, and nothing is compared with a committed file:

``dataflow-observer-effect``
    No telemetry (``tl_*``) or trace-ring (``tr_*``) lane may influence
    any ``EngineState`` lane or step event. The trace-on/off bit-identity
    grids in the test suite sample this; here it is a whole-program proof
    over the jaxpr — an observer that perturbs its subject cannot trace.

``dataflow-cross-tenant``
    Under the fleet vmap, no un-batched influence edge between
    tenant-indexed lanes: a tenant-axis abstract interpretation tracks
    which dimension of every intermediate is the tenant axis and proves
    no data output mixes tenants (while-loop PREDICATES legitimately
    reduce over tenants — vmap lockstep semantics — and are exempt; data
    lanes are not). Complements the HLO gate's zero-cross-tenant-
    collective budget at the dataflow level.

``dataflow-probe-error``
    A corpus module's audit program failed to execute or to trace.

Tracing is cheap (~2 s for the whole registry, no compile), and cached
once a session like the compiled facts.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

from . import core
from .core import Finding

#: Containers whose fields become lane-label prefixes. Anything else
#: labels by field path alone (corpus probes may define their own
#: NamedTuples under these names and get the same treatment).
_CONTAINER_PREFIX = {
    "EngineState": "state",
    "TelemetryLanes": "telem",
    "TraceRing": "trace",
    "FaultInputs": "faults",
    "StepEvents": "events",
}

#: Observer planes: lanes on these containers (or with these field-name
#: spellings) must never influence a subject lane.
_OBSERVER_CONTAINERS = ("telem", "trace")
_OBSERVER_FIELDS = ("tl_", "tr_")
#: Subject planes the observer-effect proof protects.
_SUBJECT_CONTAINERS = ("state", "events")


def _is_literal(atom: Any) -> bool:
    return hasattr(atom, "val")


def _is_dropvar(var: Any) -> bool:
    return type(var).__name__ == "DropVar"


# ---------------------------------------------------------------------------
# lane labeling
# ---------------------------------------------------------------------------


def _lane_labels(tree: Any, role: str) -> List[str]:
    """One label per flattened leaf, in jax flatten order: NamedTuple
    containers contribute their registered prefix (``state.alive``),
    positional nesting contributes indices, bare leaves fall back to
    ``<role><i>``. The order contract (matching ``tree_leaves``) is
    asserted by the caller against the jaxpr's invar count."""
    labels: List[str] = []

    def walk(node: Any, prefix: str, fallback: str) -> None:
        if node is None:
            return
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            cname = _CONTAINER_PREFIX.get(
                type(node).__name__, type(node).__name__.lower()
            )
            base = f"{prefix}.{cname}" if prefix else cname
            for field in node._fields:
                walk(getattr(node, field), f"{base}.{field}", f"{base}.{field}")
            return
        if isinstance(node, (tuple, list)):
            for i, item in enumerate(node):
                walk(item, f"{prefix}[{i}]" if prefix else "", f"{fallback}[{i}]")
            return
        if isinstance(node, dict):
            for key in sorted(node):
                sub = f"{prefix}.{key}" if prefix else str(key)
                walk(node[key], sub, sub)
            return
        labels.append(prefix or fallback)

    if isinstance(tree, tuple) and not hasattr(tree, "_fields"):
        for i, arg in enumerate(tree):
            walk(arg, "", f"{role}{i}")
    else:
        walk(tree, "", f"{role}0")
    return labels


def _field_of(label: str) -> str:
    return label.rsplit(".", 1)[-1]


def _container_of(label: str) -> str:
    return label.split(".", 1)[0] if "." in label else ""


def _is_observer_lane(label: str) -> bool:
    return _container_of(label) in _OBSERVER_CONTAINERS or _field_of(
        label
    ).startswith(_OBSERVER_FIELDS)


def _is_subject_lane(label: str) -> bool:
    return _container_of(label) in _SUBJECT_CONTAINERS and not _field_of(
        label
    ).startswith(_OBSERVER_FIELDS)


# ---------------------------------------------------------------------------
# taint interpreter (lane -> lane influence)
# ---------------------------------------------------------------------------


def _sub_jaxpr(params: Dict[str, Any]):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = params.get(key)
        if sub is not None and (hasattr(sub, "jaxpr") or hasattr(sub, "invars")):
            return sub
    return None


def _taint_closed(closed: Any, in_taints: List[FrozenSet[int]]) -> List[FrozenSet[int]]:
    """Per-output taint sets (indices into the caller's lane space) for
    one (closed) jaxpr. A sub-jaxpr whose arity does not match the
    call-site operands (exotic custom-call packing) degrades soundly to
    union-of-everything instead of guessing an alignment."""
    jaxpr = getattr(closed, "jaxpr", closed)
    if len(in_taints) != len(jaxpr.invars):
        union: FrozenSet[int] = frozenset().union(*in_taints) if in_taints else frozenset()
        return [union] * len(jaxpr.outvars)
    env: Dict[Any, FrozenSet[int]] = {}
    for var in jaxpr.constvars:
        env[var] = frozenset()
    for var, taint in zip(jaxpr.invars, in_taints):
        env[var] = taint

    def read(atom: Any) -> FrozenSet[int]:
        if _is_literal(atom):
            return frozenset()
        return env.get(atom, frozenset())

    for eqn in jaxpr.eqns:
        outs = _eqn_taints(eqn, [read(a) for a in eqn.invars])
        for var, taint in zip(eqn.outvars, outs):
            if not _is_dropvar(var):
                env[var] = taint
    return [read(v) for v in jaxpr.outvars]


def _eqn_taints(eqn: Any, in_t: List[FrozenSet[int]]) -> List[FrozenSet[int]]:
    prim = eqn.primitive.name
    params = eqn.params
    n_out = len(eqn.outvars)
    if prim == "cond":
        # Control dependence: the predicate decides WHICH branch's values
        # flow, so it taints every output.
        pred, ops = in_t[0], in_t[1:]
        outs = [frozenset(pred) for _ in range(n_out)]
        for branch in params["branches"]:
            branch_outs = _taint_closed(branch, list(ops))
            for i in range(min(n_out, len(branch_outs))):
                outs[i] = outs[i] | branch_outs[i]
        return outs
    if prim == "while":
        cn = params["cond_nconsts"]
        bn = params["body_nconsts"]
        cond_consts, body_consts = in_t[:cn], in_t[cn:cn + bn]
        carry = list(in_t[cn + bn:])
        # Per-slot fixpoint: carries are tracked separately so slot reuse
        # (aliasing/donation at the buffer level) cannot fabricate an
        # influence edge between unrelated lanes. The predicate taints
        # every carry (it decides how many updates run).
        while True:
            pred_outs = _taint_closed(params["cond_jaxpr"], cond_consts + carry)
            pred = pred_outs[0] if pred_outs else frozenset()
            body_outs = _taint_closed(params["body_jaxpr"], body_consts + carry)
            merged = [c | b | pred for c, b in zip(carry, body_outs)]
            if merged == carry:
                return carry
            carry = merged
    if prim == "scan":
        nc, nk = params["num_consts"], params["num_carry"]
        consts, xs = in_t[:nc], list(in_t[nc + nk:])
        carry = list(in_t[nc:nc + nk])
        while True:
            outs = _taint_closed(params["jaxpr"], consts + carry + xs)
            merged = [c | o for c, o in zip(carry, outs[:nk])]
            if merged == carry:
                return carry + list(outs[nk:])
            carry = merged
    sub = _sub_jaxpr(params)
    if sub is not None:
        return _taint_closed(sub, list(in_t))
    union = frozenset().union(*in_t) if in_t else frozenset()
    return [union] * n_out


# ---------------------------------------------------------------------------
# tenant-axis abstract interpretation (cross-tenant proof)
# ---------------------------------------------------------------------------

_MIXED = "mixed"

_ELEMENTWISE_SAFE = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "max", "min",
    "and", "or", "xor", "not", "neg", "sign", "abs", "floor", "ceil",
    "round", "exp", "log", "log1p", "expm1", "sqrt", "rsqrt", "tanh",
    "logistic", "sin", "cos", "is_finite", "eq", "ne", "lt", "le", "gt",
    "ge", "select_n", "convert_element_type", "stop_gradient", "copy",
    "clamp", "nextafter", "population_count", "clz", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "erf", "erf_inv",
    "erfc", "square", "real", "imag", "device_put", "optimization_barrier",
    "reduce_precision", "exp2", "atan2", "sharding_constraint",
})


def _unify_axes(axes: List[Any]) -> Any:
    """None (uniform) / int (tenant dim) / 'mixed' lattice join for
    equal-shape operands."""
    seen = {a for a in axes if a is not None}
    if not seen:
        return None
    if _MIXED in seen or len(seen) > 1:
        return _MIXED
    return seen.pop()


def _axis_closed(closed: Any, in_axes: List[Any], tenants: int,
                 fallbacks: List[str]) -> List[Any]:
    jaxpr = getattr(closed, "jaxpr", closed)
    if len(in_axes) != len(jaxpr.invars):
        worst = _MIXED if any(a is not None for a in in_axes) else None
        return [worst] * len(jaxpr.outvars)
    env: Dict[Any, Any] = {}
    for var in jaxpr.constvars:
        env[var] = None
    for var, axis in zip(jaxpr.invars, in_axes):
        env[var] = axis

    def read(atom: Any) -> Any:
        if _is_literal(atom):
            return None
        return env.get(atom)

    for eqn in jaxpr.eqns:
        outs = _axis_eqn(eqn, [read(a) for a in eqn.invars], tenants, fallbacks)
        for var, axis in zip(eqn.outvars, outs):
            if not _is_dropvar(var):
                env[var] = axis
    return [read(v) for v in jaxpr.outvars]


def _axis_eqn(eqn: Any, in_a: List[Any], tenants: int,
              fallbacks: List[str]) -> List[Any]:
    prim = eqn.primitive.name
    params = eqn.params
    n_out = len(eqn.outvars)
    if all(a is None for a in in_a):
        return [None] * n_out
    if prim in _ELEMENTWISE_SAFE:
        return [_unify_axes(in_a)] * n_out
    if prim == "bitcast_convert_type":
        # Elementwise between dtypes of one width (the ring walk's
        # uint32 <-> int32 scan word). A width change adds or drops a
        # trailing dimension: not tracked, so it falls through to mixed.
        widths = {v.aval.dtype.itemsize for v in (eqn.invars[0], eqn.outvars[0])}
        if len(widths) == 1:
            return [in_a[0]] * n_out
    if prim == "broadcast_in_dim":
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        return [params["broadcast_dimensions"][axis]] * n_out
    if prim == "transpose":
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        return [list(params["permutation"]).index(axis)] * n_out
    if prim == "squeeze":
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        removed = params["dimensions"]
        if axis in removed:
            return [_MIXED] * n_out
        return [axis - sum(1 for d in removed if d < axis)] * n_out
    if prim == "expand_dims":
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        added = params["dimensions"]
        return [axis + sum(1 for d in added if d <= axis)] * n_out
    if prim == "reshape":
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        operand = eqn.invars[0].aval.shape
        new_sizes = params["new_sizes"]
        prefix = 1
        for d in range(axis):
            prefix *= operand[d]
        running = 1
        for e, size in enumerate(new_sizes):
            if running == prefix and size == operand[axis]:
                return [e] * n_out
            running *= size
        return [_MIXED] * n_out
    if prim.startswith("reduce_") or prim in ("argmax", "argmin"):
        axis = _unify_axes(in_a)
        if axis in (None, _MIXED):
            return [axis] * n_out
        axes = params.get("axes", ())
        if axis in axes:
            return [_MIXED] * n_out
        return [axis - sum(1 for d in axes if d < axis)] * n_out
    if prim.startswith("cum"):
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        return [_MIXED if params.get("axis") == axis else axis] * n_out
    if prim == "concatenate":
        axis = _unify_axes(in_a)
        if axis in (None, _MIXED):
            return [axis] * n_out
        return [_MIXED if params["dimension"] == axis else axis] * n_out
    if prim == "pad":
        return [in_a[0]] * n_out
    if prim == "slice":
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        shape = eqn.invars[0].aval.shape
        keeps_all = (
            params["start_indices"][axis] == 0
            and params["limit_indices"][axis] == shape[axis]
        )
        return [axis if keeps_all else _MIXED] * n_out
    if prim == "rev":
        axis = in_a[0]
        if axis in (None, _MIXED):
            return [axis] * n_out
        return [_MIXED if axis in params["dimensions"] else axis] * n_out
    if prim == "iota":
        return [None] * n_out
    if prim == "sort":
        axis = _unify_axes(in_a)
        if axis in (None, _MIXED):
            return [axis] * n_out
        return [_MIXED if params["dimension"] == axis else axis] * n_out
    if prim == "dynamic_slice":
        operand_axis = in_a[0]
        if any(a is not None for a in in_a[1:]):
            return [_MIXED] * n_out
        if operand_axis in (None, _MIXED):
            return [operand_axis] * n_out
        full = params["slice_sizes"][operand_axis] == tenants
        return [operand_axis if full else _MIXED] * n_out
    if prim == "dynamic_update_slice":
        operand_axis, update_axis = in_a[0], in_a[1]
        if any(a is not None for a in in_a[2:]):
            return [_MIXED] * n_out
        if _MIXED in (operand_axis, update_axis):
            return [_MIXED] * n_out
        if operand_axis is None and update_axis is None:
            return [None] * n_out
        if operand_axis == update_axis:
            return [operand_axis] * n_out
        return [_MIXED] * n_out
    if prim == "gather":
        return [_axis_gather(eqn, in_a, fallbacks)] * n_out
    if prim.startswith("scatter"):
        return [_axis_scatter(eqn, in_a)] * n_out
    if prim == "cond":
        branch_axes = [
            _axis_closed(b, list(in_a[1:]), tenants, fallbacks)
            for b in params["branches"]
        ]
        return [
            _unify_axes([bo[i] for bo in branch_axes if i < len(bo)])
            for i in range(n_out)
        ]
    if prim == "while":
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        carry = list(in_a[cn + bn:])
        # The loop PREDICATE reduces over all tenants by design (vmap
        # lockstep: iterate while ANY tenant still runs) — a mixed pred is
        # the batching rule's own semantics, not a data leak, so it is
        # deliberately not joined into the carries.
        while True:
            body = _axis_closed(params["body_jaxpr"], in_a[cn:cn + bn] + carry,
                                tenants, fallbacks)
            merged = [_unify_axes([c, b]) for c, b in zip(carry, body)]
            if merged == carry:
                return carry
            carry = merged
    if prim == "scan":
        nc, nk = params["num_consts"], params["num_carry"]
        carry = list(in_a[nc:nc + nk])
        xs = list(in_a[nc + nk:])
        while True:
            outs = _axis_closed(params["jaxpr"], in_a[:nc] + carry + xs,
                                tenants, fallbacks)
            merged = [_unify_axes([c, o]) for c, o in zip(carry, outs[:nk])]
            if merged == carry:
                return carry + list(outs[nk:])
            carry = merged
    sub = _sub_jaxpr(params)
    if sub is not None:
        return _axis_closed(sub, list(in_a), tenants, fallbacks)
    fallbacks.append(prim)
    return [_MIXED] * n_out


def _axis_gather(eqn: Any, in_a: List[Any], fallbacks: List[str]) -> Any:
    """A gather is tenant-safe only as the BATCHED per-tenant gather vmap
    produces: the tenant dims of operand and indices are declared as
    batching dims, which pins every lookup inside its own tenant block.
    Any other gather touching a tenant-indexed operand is a potential
    cross-tenant read -> mixed."""
    operand_axis, indices_axis = in_a[0], in_a[1]
    if operand_axis is None and indices_axis is None:
        return None
    if _MIXED in (operand_axis, indices_axis):
        return _MIXED
    dnums = eqn.params["dimension_numbers"]
    op_batch = tuple(getattr(dnums, "operand_batching_dims", ()) or ())
    idx_batch = tuple(getattr(dnums, "start_indices_batching_dims", ()) or ())
    if operand_axis is not None and operand_axis in op_batch:
        # Batched per-tenant gather (vmap may stack further batch dims —
        # the fleet's [tenant, ring] gathers batch both): the tenant dim
        # must pair with the indices' tenant dim, and it surfaces in the
        # output at the slot its indices batch dim maps to (indices batch
        # dims fill the non-offset output positions in order).
        pos = list(op_batch).index(operand_axis)
        paired = list(idx_batch)[pos] if pos < len(idx_batch) else None
        if paired is not None and (indices_axis is None or indices_axis == paired):
            out_ndim = eqn.outvars[0].aval.ndim
            offset = set(dnums.offset_dims)
            batch_slots = [p for p in range(out_ndim) if p not in offset]
            if paired < len(batch_slots):
                return batch_slots[paired]
        return _MIXED
    if operand_axis is not None and indices_axis is None and not op_batch:
        # Uniform indices selecting along NON-tenant dims, with the tenant
        # dim carried whole inside every slice: the same per-tenant rows
        # come out for every tenant — no cross-tenant read. The tenant dim
        # lands at the offset_dims slot its (non-collapsed) operand rank
        # maps to.
        d = operand_axis
        collapsed = tuple(dnums.collapsed_slice_dims)
        if (
            d not in dnums.start_index_map
            and d not in collapsed
            and eqn.params["slice_sizes"][d] == eqn.invars[0].aval.shape[d]
        ):
            surviving = [
                dim for dim in range(eqn.invars[0].aval.ndim)
                if dim not in collapsed
            ]
            return tuple(dnums.offset_dims)[surviving.index(d)]
    return _MIXED


def _axis_scatter(eqn: Any, in_a: List[Any]) -> Any:
    """Tenant-safe only as the batched per-tenant scatter vmap produces:
    every non-uniform input tracks the same tenant dim, declared as a
    batching dim on both the operand and the indices — each tenant's
    updates then land inside its own batch slice. A uniform operand is
    fine (scattering per-tenant data into a shared zero buffer); the
    output keeps the tenant dim at the operand's batching position."""
    if all(a is None for a in in_a):
        return None
    if _MIXED in in_a:
        return _MIXED
    dnums = eqn.params["dimension_numbers"]
    op_batch = tuple(getattr(dnums, "operand_batching_dims", ()) or ())
    idx_batch = tuple(getattr(dnums, "scatter_indices_batching_dims", ()) or ())
    operand_axis, indices_axis = in_a[0], in_a[1]
    axes = {a for a in in_a if a is not None}
    if len(axes) == 1:
        d = axes.pop()
        if (
            (operand_axis is None or operand_axis == d)
            and d in op_batch
            and (indices_axis is None or d in idx_batch)
        ):
            return d
    return _MIXED


# ---------------------------------------------------------------------------
# entrypoint tracing
# ---------------------------------------------------------------------------


def _trace_entry(name: str, spec: Dict[str, Any]) -> Dict[str, Any]:
    traced = spec["jit"].trace(*spec["args"])
    closed = traced.jaxpr
    in_labels = _lane_labels(spec["args"], "in")
    out_labels = _lane_labels(traced.out_info, "out")
    n_in, n_out = len(closed.jaxpr.invars), len(closed.jaxpr.outvars)
    if len(in_labels) != n_in or len(out_labels) != n_out:
        raise RuntimeError(
            f"{name}: lane labels do not align with the jaxpr "
            f"({len(in_labels)}/{n_in} inputs, {len(out_labels)}/{n_out} "
            f"outputs) — the labeler must mirror jax flatten order"
        )
    return {
        "name": name,
        "closed": closed,
        "in_labels": in_labels,
        "out_labels": out_labels,
    }


def _registry_with_fleet() -> Dict[str, Dict[str, Any]]:
    """The device-program registry plus the MESHLESS vmapped fleet step at
    the audit geometry — the cross-tenant proof must also cover the
    non-GSPMD tenancy path (what single-host deployments run)."""
    from . import device_program

    registry = dict(device_program._build_registry())
    registry["fleet_step"] = device_program.fleet_step_spec()
    return registry


def _tenant_in_axes(entry: Dict[str, Any], spec: Dict[str, Any],
                    tenants: int) -> List[Any]:
    import jax

    axes: List[Any] = []
    for leaf in jax.tree_util.tree_leaves(spec["args"]):
        shape = getattr(leaf, "shape", ())
        axes.append(0 if (len(shape) >= 1 and shape[0] == tenants) else None)
    if len(axes) != len(entry["in_labels"]):
        raise RuntimeError(
            f"{entry['name']}: tenant axis assignment does not align with "
            f"the flattened arguments"
        )
    return axes


# ---------------------------------------------------------------------------
# proof checks
# ---------------------------------------------------------------------------


def observer_effect_findings(
    entry: Dict[str, Any], out_taints: List[FrozenSet[int]],
    loc: Tuple[str, int],
) -> List[Finding]:
    path, lineno = loc
    findings = []
    labels = entry["in_labels"]
    for out_label, taint in zip(entry["out_labels"], out_taints):
        if not _is_subject_lane(out_label):
            continue
        leaks = sorted(labels[i] for i in taint if _is_observer_lane(labels[i]))
        if leaks:
            findings.append(Finding(
                path, lineno, "dataflow-observer-effect",
                f"{entry['name']}: observer lane(s) {', '.join(leaks)} "
                f"influence subject lane {out_label} — telemetry and the "
                f"trace ring must be write-only planes; an observer that "
                f"perturbs the engine invalidates every trace it records",
            ))
    return findings


def cross_tenant_findings(
    entry: Dict[str, Any], out_axes: List[Any], fallbacks: List[str],
    loc: Tuple[str, int],
) -> List[Finding]:
    path, lineno = loc
    findings = []
    for out_label, axis in zip(entry["out_labels"], out_axes):
        if axis == _MIXED:
            findings.append(Finding(
                path, lineno, "dataflow-cross-tenant",
                f"{entry['name']}: output lane {out_label} mixes tenants — "
                f"an influence edge crosses the fleet's tenant axis"
                + (
                    f" (conservatively, via unhandled primitive(s) "
                    f"{', '.join(sorted(set(fallbacks)))})"
                    if fallbacks else ""
                ),
            ))
    return findings


# ---------------------------------------------------------------------------
# collection + tree mode
# ---------------------------------------------------------------------------

_DATAFLOW_CACHE: Optional[Tuple[Dict[str, Any], List[Finding], bool]] = None


def collect_dataflow(
    force: bool = False, require_mesh: bool = True,
) -> Tuple[Dict[str, Any], List[Finding]]:
    """Trace the full registry and run both proofs: ``(proofs, findings)``,
    cached per session like the HLO facts (the trace is compile-free).
    ``proofs["observer_silent"][name]`` is the observer verdict of every
    entrypoint, ``proofs["tenant_isolation"][name]`` the
    tenant-axis verdict of every fleet one (``proven``, ``mixed_outputs``,
    ``axis_rule_fallbacks``). Raises RuntimeError without the 8-device mesh
    when ``require_mesh`` — a partial registry proves nothing about the
    mesh programs."""
    global _DATAFLOW_CACHE
    import jax

    from . import device_program

    have_mesh = jax.device_count() >= device_program.AUDIT_DEVICES
    if require_mesh and not have_mesh:
        raise RuntimeError(
            f"dataflow audit needs {device_program.AUDIT_DEVICES} devices, "
            f"have {jax.device_count()} — force them before jax initializes "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{device_program.AUDIT_DEVICES})"
        )
    if _DATAFLOW_CACHE is not None and not force and _DATAFLOW_CACHE[2] == have_mesh:
        return _DATAFLOW_CACHE[0], _DATAFLOW_CACHE[1]

    proofs, findings = _prove_registry()
    _DATAFLOW_CACHE = (proofs, findings, have_mesh)
    return proofs, findings


def _prove_registry() -> Tuple[Dict[str, Any], List[Finding]]:
    from . import device_program

    loc = (device_program.REGISTRY_REL, 1)
    registry = _registry_with_fleet()
    findings: List[Finding] = []
    observer_silent: Dict[str, bool] = {}
    tenant_isolation: Dict[str, Dict[str, Any]] = {}

    tenants = device_program.AUDIT_TENANTS
    for name in sorted(registry):
        spec = registry[name]
        entry = _trace_entry(name, spec)
        in_taints = [frozenset([i]) for i in range(len(entry["in_labels"]))]
        out_taints = _taint_closed(entry["closed"], in_taints)
        leaks = observer_effect_findings(entry, out_taints, loc)
        findings.extend(leaks)
        observer_silent[name] = not leaks

        if name.startswith("fleet"):
            in_axes = _tenant_in_axes(entry, spec, tenants)
            fallbacks: List[str] = []
            out_axes = _axis_closed(entry["closed"], in_axes, tenants, fallbacks)
            findings.extend(cross_tenant_findings(entry, out_axes, fallbacks, loc))
            mixed = sorted(
                lbl for lbl, a in zip(entry["out_labels"], out_axes)
                if a == _MIXED
            )
            tenant_isolation[name] = {
                "proven": not mixed,
                "mixed_outputs": mixed,
                "axis_rule_fallbacks": sorted(set(fallbacks)),
            }
    return (
        {"observer_silent": observer_silent, "tenant_isolation": tenant_isolation},
        findings,
    )


def check_dataflow_proofs(trees: Sequence[Tuple[ast.AST, str]]) -> List[Finding]:
    """Tree-mode gate: trace the registry (session-cached) and report what
    the two proofs find. Presence-gated on the engine sources exactly like
    the HLO gate, so retargeted test trees never pay a trace."""
    from . import device_program

    if not device_program.covers_registry(trees):
        return []
    return list(collect_dataflow()[1])


# ---------------------------------------------------------------------------
# per-file corpus mode
# ---------------------------------------------------------------------------


def check_dataflow(
    path: Path, source: Optional[str] = None, tree: Optional[ast.AST] = None,
) -> List[Finding]:
    """Corpus/per-file mode: execute a module that declares
    ``DATAFLOW_AUDIT_PROGRAMS`` (name -> {"build": zero-arg callable
    returning a registry-shaped spec, "checks": subset of
    ("observer-effect", "cross-tenant"), optional "tenants"}) and run the
    requested proofs over each traced program. Findings anchor at the
    program's dict-key line, mirroring the device_program corpus
    convention. Files without the marker are skipped — this family's tree
    mode runs against the real registry."""
    rel = _rel(path)
    if source is None:
        try:
            source = path.read_text()
        except OSError:
            return []
    if "DATAFLOW_AUDIT_PROGRAMS" not in source:
        return []
    if tree is None:
        try:
            tree = ast.parse(source, filename=str(path))
        except SyntaxError:
            return []
    linenos = _program_key_linenos(tree)
    namespace: Dict[str, Any] = {"__name__": f"_dataflow_corpus_{path.stem}"}
    try:
        exec(compile(source, str(path), "exec"), namespace)  # noqa: S102
    except Exception as exc:  # noqa: BLE001 — a broken probe is a finding
        return [Finding(rel, 1, "dataflow-probe-error",
                        f"dataflow audit module failed to execute: {exc!r}")]
    programs = namespace.get("DATAFLOW_AUDIT_PROGRAMS")
    if not isinstance(programs, dict):
        return []
    findings: List[Finding] = []
    for name in sorted(programs):
        cfg = programs[name]
        lineno = linenos.get(name, 1)
        loc = (rel, lineno)
        try:
            spec = cfg["build"]()
            entry = _trace_entry(name, spec)
        except Exception as exc:  # noqa: BLE001
            findings.append(Finding(
                rel, lineno, "dataflow-probe-error",
                f"{name}: audit program failed to trace: {exc!r}"))
            continue
        checks = tuple(cfg.get("checks", ()))
        n_in = len(entry["in_labels"])
        in_taints = [frozenset([i]) for i in range(n_in)]
        if "observer-effect" in checks:
            out_taints = _taint_closed(entry["closed"], in_taints)
            findings.extend(observer_effect_findings(entry, out_taints, loc))
        if "cross-tenant" in checks:
            tenants = int(cfg.get("tenants", 0))
            in_axes = _tenant_in_axes(entry, spec, tenants)
            fallbacks: List[str] = []
            out_axes = _axis_closed(entry["closed"], in_axes, tenants, fallbacks)
            findings.extend(
                cross_tenant_findings(entry, out_axes, fallbacks, loc))
    return sorted(set(findings), key=lambda f: (f.lineno, f.check, f.message))


def _rel(path: Path) -> str:
    try:
        return str(Path(path).resolve().relative_to(core.REPO)).replace(
            "\\", "/"
        )
    except ValueError:
        return str(path)


def _program_key_linenos(tree: ast.AST) -> Dict[str, int]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = [
                t.id for t in node.targets if isinstance(t, ast.Name)
            ]
            if "DATAFLOW_AUDIT_PROGRAMS" in targets and isinstance(
                node.value, ast.Dict
            ):
                return {
                    key.value: key.lineno
                    for key in node.value.keys
                    if isinstance(key, ast.Constant)
                    and isinstance(key.value, str)
                }
    return {}
