"""Check family 13: engine sharding discipline (source-level lint).

The compiled-artifact gate (family 12, ``device_program``) catches what XLA
actually emitted; this family catches the source patterns that PRODUCE bad
compiled programs, over ``rapid_tpu/ops/``, ``rapid_tpu/models/``, and
``rapid_tpu/parallel/``:

- ``missing-partition-spec`` — every array leaf of the engine state pytree
  (``EngineState``/``FaultInputs`` in models/state.py) must be covered by
  ``parallel/mesh.py``'s partition declarations. Two declaration styles are
  understood: the regex rule table (``PARTITION_RULES`` — the current
  engine style: every leaf must fullmatch a rule, a rule matching no leaf
  is a dead entry, and a rule whose spec names no mesh axis must justify
  the replication with ``# replicated-ok: <reason>`` on its spec line) and
  the legacy explicit constructor table (``state_shardings`` /
  ``fault_shardings`` keyword-per-leaf — same leaf coverage + justified
  ``sh()`` discipline). An uncovered leaf silently replicates [n]- or
  [c,n]-scale state onto every device. Since the cohort axis became a real
  mesh axis (the 2-D ``('cohort', 'nodes')`` mesh), any surviving
  ``cohort axis is not meshed`` replication justification is itself a
  finding — the annotation's premise is false.
- ``host-sync-in-hot-path`` — ``jax.device_get`` / ``.block_until_ready()``
  / ``.item()`` / ``float(...)`` / ``np.asarray(...)`` inside the traced
  convergence seams (jitted functions, the ``*_impl`` engine convention,
  and callables handed to ``lax.while_loop``/``lax.cond``/``lax.scan``):
  each is a device->host round trip the fused-dispatch design exists to
  avoid. Escape hatch ``# host-sync-ok: <reason>``.
- ``host-sync-in-stream`` — the streaming-pipeline sibling of the check
  above, over ``rapid_tpu/serving/``: a blocking read
  (``block_until_ready`` — method or ``jax.block_until_ready`` —,
  ``.item()``, ``jax.device_get``, ``np.asarray``, and the scalar-fetch
  casts ``int(jnp...)``/``float(jnp...)`` over resolvable jax calls)
  ANYWHERE in the pipeline module body stalls every enqueued wave behind
  it, so each one must be an explicit fetch boundary justified with
  ``# host-sync-ok: <reason>``. Unlike the hot-path check this one is not
  limited to traced functions: the stream driver's whole value is that
  its HOST code never blocks outside declared boundaries.
- ``donation-mismatch`` — a ``jax.jit`` application whose wrapped callable
  takes the engine ``state`` pytree but whose ``donate_argnums`` does not
  cover it: the long-running driver loop then holds two copies of the
  state between steps. Deliberate non-donating variants carry
  ``# donate-ok: <reason>``.
- ``retrace-hazard`` — a bare Python numeric literal passed in a traced
  position of a same-file jitted entrypoint: the first such call traces
  with ``weak_type=True``, a later ``jnp.int32(...)``-wrapped call traces
  again — one silent recompile per spelling. Wrap the constant
  (``jnp.int32(x)``) or pin the parameter static. Escape hatch
  ``# retrace-ok: <reason>``.
- ``dtype-widening`` — inline arithmetic stored back into a
  policy-NARROWED engine lane (``models/state.NARROWABLE_LANES`` — int8/
  int16/uint8 under the compact policy) without an explicit cast: jnp
  type promotion silently re-widens the whole lane to int32/uint32 the
  moment a wide operand touches the expression, un-doing the compaction
  byte-for-byte while every test keeps passing (wide mode compiles
  identically). Convicts a ``_replace(...)``/state-constructor keyword
  for a narrowed lane whose value contains a BinOp not wrapped in an
  ``.astype(...)``; name-only stores pass (the round body's convention:
  compute, cast, bind, store the name). Escape hatch
  ``# widen-ok: <reason>``.

Resolution is conservative (skip-don't-guess), matching the rest of the
package: only same-file jit applications are resolved, only direct
parameter/keyword shapes convict.

``check_sharding`` is the per-file entry (prefix-gated; the lint corpus
keeps miniature state+table pairs in one module);
``check_partition_specs`` is the tree-mode entry that merges the real
state.py/mesh.py pair on full sweeps.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from . import core
from .core import Finding
from .trace_safety import _dotted, _import_aliases, _jitted_functions

SHARDING_PREFIXES = (
    "rapid_tpu/ops/",
    "rapid_tpu/models/",
    "rapid_tpu/parallel/",
    "rapid_tpu/tenancy/",
)

#: The streaming-pipeline prefix: every blocking read here must be a
#: justified fetch boundary (``host-sync-in-stream``), not just the ones
#: inside traced functions.
STREAM_PREFIXES = ("rapid_tpu/serving/",)

#: The real files the tree-mode partition-spec check merges.
STATE_FILE = "rapid_tpu/models/state.py"
MESH_FILE = "rapid_tpu/parallel/mesh.py"

#: State-pytree classes and the sharding-table functions that must cover
#: their array leaves, by name (the engine convention).
_PYTREE_TABLES = {
    "EngineState": "state_shardings",
    "FaultInputs": "fault_shardings",
    "TenantKnobs": "knob_shardings",
    "TelemetryLanes": "telemetry_shardings",
    "TraceRing": "trace_shardings",
}

_LAX_LOOP_FNS = frozenset({
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.cond", "lax.cond",
    "jax.lax.scan", "lax.scan",
    "jax.lax.fori_loop", "lax.fori_loop",
})

_HOST_SYNC_METHODS = frozenset({"block_until_ready", "item"})


def _comment_ok(source_lines: List[str], lineno: int, marker: str) -> bool:
    if 1 <= lineno <= len(source_lines):
        return marker in source_lines[lineno - 1]
    return False


# -- host-sync-in-hot-path / host-sync-in-stream -----------------------------


def _blocking_read(node: ast.Call, aliases: Dict[str, str]) -> Optional[str]:
    """The blocking-read spelling of a call node — the one classifier both
    host-sync checks share, so the two can never disagree about what counts
    as a device->host sync. None = not a blocking read."""
    dotted = _dotted(node.func, aliases)
    if dotted == "jax.device_get":
        return "jax.device_get"
    if dotted == "jax.block_until_ready":
        return "jax.block_until_ready(...)"
    if dotted in ("numpy.asarray", "np.asarray", "numpy.array", "np.array"):
        # Both spellings materialize a device array on host (np.array just
        # also copies); classifying only asarray would leave np.array as a
        # silent undeclared-sync spelling.
        return f"{dotted} (implicit device fetch)"
    if (
        isinstance(node.func, ast.Attribute)
        and node.func.attr in _HOST_SYNC_METHODS
    ):
        return f".{node.func.attr}()"
    return None


def _traced_functions(tree: ast.AST, aliases: Dict[str, str]) -> List[ast.AST]:
    """Every function node the engine traces: jit-applied (trace_safety's
    resolution), ``*_impl``-named (the repo's traced-impl convention), and
    callables handed to the lax control-flow combinators."""
    traced: Dict[int, ast.AST] = {}
    for fn, _static in _jitted_functions(tree, aliases):
        traced[id(fn)] = fn
    by_name: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name[node.name] = node
            if node.name.endswith("_impl"):
                traced[id(node)] = node
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _dotted(node.func, aliases) in _LAX_LOOP_FNS):
            continue
        for arg in node.args:
            if isinstance(arg, ast.Lambda):
                traced[id(arg)] = arg
            elif isinstance(arg, ast.Name) and arg.id in by_name:
                fn = by_name[arg.id]
                traced[id(fn)] = fn
    return list(traced.values())


def _check_host_sync(
    tree: ast.AST,
    aliases: Dict[str, str],
    rel: str,
    source_lines: List[str],
    findings: List[Finding],
) -> None:
    seen: Set[int] = set()
    for fn in _traced_functions(tree, aliases):
        label = getattr(fn, "name", "<lambda>")
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            what = _blocking_read(node, aliases)
            if what is None and (
                isinstance(node.func, ast.Name)
                and node.func.id == "float"
                and node.args
                and not isinstance(node.args[0], ast.Constant)
            ):
                what = "float(...) (scalar fetch)"
            if what is None:
                continue
            seen.add(id(node))
            if _comment_ok(source_lines, node.lineno, "# host-sync-ok:"):
                continue
            findings.append(Finding(
                rel, node.lineno, "host-sync-in-hot-path",
                f"{what} inside traced {label!r}: a device->host sync in "
                f"the convergence hot path — keep the value on device "
                f"(jnp ops / lax.cond), or justify with "
                f"`# host-sync-ok: <reason>`",
            ))


def _cast_of_device_value(
    node: ast.Call, aliases: Dict[str, str]
) -> Optional[str]:
    """The scalar-fetch cast spelling: ``int(...)``/``float(...)`` whose
    argument computes through a ``jax.*``/``jax.numpy.*`` call — e.g.
    ``int(jnp.sum(state.config_epoch))``, the drain-fetch spelling the
    pipeline itself uses. Casts of host values (numpy rng draws, plain
    attributes) pass: an AST pass cannot know a bare name holds a device
    array, so this branch is precise on the calls it CAN resolve rather
    than noisy on everything."""
    if not (
        isinstance(node.func, ast.Name)
        and node.func.id in ("int", "float")
        and node.args
    ):
        return None
    for sub in ast.walk(node.args[0]):
        if isinstance(sub, ast.Call):
            dotted = _dotted(sub.func, aliases) or ""
            if dotted.startswith(("jax.", "jnp.")):
                return f"{node.func.id}({dotted}(...)) (scalar fetch)"
    return None


def _check_stream_host_sync(
    tree: ast.AST,
    aliases: Dict[str, str],
    rel: str,
    source_lines: List[str],
    findings: List[Finding],
) -> None:
    """The streaming-pipeline variant: every blocking-read spelling in a
    serving module is a pipeline stall (JAX async dispatch only overlaps
    host work with device compute while the host never blocks), so each one
    must be a declared fetch boundary — hatch ``# host-sync-ok: <reason>``
    — not just the ones inside traced functions. Covers the shared
    classifier's spellings plus the scalar-fetch casts over resolvable
    jax/jnp calls (:func:`_cast_of_device_value`)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        what = _blocking_read(node, aliases) or _cast_of_device_value(
            node, aliases
        )
        if what is None:
            continue
        if _comment_ok(source_lines, node.lineno, "# host-sync-ok:"):
            continue
        findings.append(Finding(
            rel, node.lineno, "host-sync-in-stream",
            f"{what} in the streaming pipeline: a blocking read here "
            f"stalls every enqueued wave behind it — keep the pipeline "
            f"fetch-free (enqueue-only dispatches, device-resident "
            f"tickets), or declare the fetch boundary with "
            f"`# host-sync-ok: <reason>`",
        ))


# -- donation-mismatch -------------------------------------------------------


def _callable_params(
    target: ast.AST, by_name: Dict[str, ast.AST]
) -> Optional[List[str]]:
    """Positional parameter names of a jit-wrapped callable: a same-file
    def referenced by name, or an inline lambda. None = unresolvable."""
    if isinstance(target, ast.Lambda):
        return [a.arg for a in (*target.args.posonlyargs, *target.args.args)]
    if isinstance(target, ast.Name) and target.id in by_name:
        fn = by_name[target.id]
        return [a.arg for a in (*fn.args.posonlyargs, *fn.args.args)]
    return None


def _int_tuple(node: Optional[ast.AST]) -> Optional[Tuple[int, ...]]:
    """A donate_argnums/static_argnums value as ints; None = unresolvable
    (dynamic spec: skip, don't guess). Missing keyword -> empty tuple is
    the CALLER's choice (pass a Constant sentinel)."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


def _str_tuple(node: Optional[ast.AST]) -> Optional[Tuple[str, ...]]:
    """A *_argnames value as strings; None = unresolvable, () = absent."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


def _jit_keyword(call: ast.Call, name: str) -> Optional[ast.AST]:
    return next((kw.value for kw in call.keywords if kw.arg == name), None)


def _check_donation(
    tree: ast.AST,
    aliases: Dict[str, str],
    rel: str,
    source_lines: List[str],
    findings: List[Finding],
) -> None:
    by_name = {
        n.name: n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _dotted(node.func, aliases) == "jax.jit"):
            continue
        if not node.args:
            continue
        params = _callable_params(node.args[0], by_name)
        if params is None or "state" not in params:
            continue
        state_idx = params.index("state")
        donate = _int_tuple(_jit_keyword(node, "donate_argnums"))
        donate_names = _str_tuple(_jit_keyword(node, "donate_argnames"))
        if donate is None or donate_names is None:
            continue  # dynamic spec: skip, don't guess
        if state_idx in donate or "state" in donate_names:
            continue
        if _comment_ok(source_lines, node.lineno, "# donate-ok:"):
            continue
        findings.append(Finding(
            rel, node.lineno, "donation-mismatch",
            f"jax.jit application does not donate the engine state pytree "
            f"(param 'state' at index {state_idx}, donate_argnums="
            f"{donate}): the driver loop holds two state copies between "
            f"steps — add donate_argnums=({state_idx},) or justify with "
            f"`# donate-ok: <reason>`",
        ))


# -- retrace-hazard ----------------------------------------------------------


def _jitted_bindings(
    tree: ast.AST, aliases: Dict[str, str], by_name: Dict[str, ast.AST]
) -> Dict[str, Tuple[int, Tuple[int, ...]]]:
    """Module-level ``name = jax.jit(fn, ...)`` bindings: name ->
    (positional arity of the wrapped callable, static argnums). Only
    statically-resolvable specs are included."""
    out: Dict[str, Tuple[int, Tuple[int, ...]]] = {}
    for node in tree.body:
        if not (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Call)
            and _dotted(node.value.func, aliases) == "jax.jit"
            and node.value.args
        ):
            continue
        params = _callable_params(node.value.args[0], by_name)
        if params is None:
            continue
        static = _int_tuple(_jit_keyword(node.value, "static_argnums"))
        static_names = _str_tuple(_jit_keyword(node.value, "static_argnames"))
        if static is None or static_names is None:
            continue  # dynamic spec: skip, don't guess
        # static_argnames pins by NAME; jax maps positional calls onto the
        # named parameters, so a bare literal at that position never
        # retraces — resolve the names to indices and merge.
        name_idx = tuple(
            params.index(n) for n in static_names if n in params
        )
        out[node.targets[0].id] = (len(params), tuple(set(static) | set(name_idx)))
    return out


def _check_retrace(
    tree: ast.AST,
    aliases: Dict[str, str],
    rel: str,
    source_lines: List[str],
    findings: List[Finding],
) -> None:
    by_name = {
        n.name: n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    jitted = _jitted_bindings(tree, aliases, by_name)
    if not jitted:
        return
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id in jitted
        ):
            continue
        _arity, static = jitted[node.func.id]
        for idx, arg in enumerate(node.args):
            if idx in static:
                continue
            if not (
                isinstance(arg, ast.Constant)
                and isinstance(arg.value, (int, float))
                and not isinstance(arg.value, bool)
            ):
                continue
            if _comment_ok(source_lines, arg.lineno, "# retrace-ok:"):
                continue
            findings.append(Finding(
                rel, arg.lineno, "retrace-hazard",
                f"bare Python literal {arg.value!r} passed in traced "
                f"position {idx} of jitted {node.func.id!r}: mixing bare "
                f"and wrapped spellings retraces per weak-type — wrap it "
                f"(jnp.int32({arg.value!r})) or pin the parameter in "
                f"static_argnums/static_argnames",
            ))


# -- dtype-widening ----------------------------------------------------------

#: The engine lanes the compact policy stores below 32 bits — the LITERAL
#: mirror of ``rapid_tpu/models/state.NARROWABLE_LANES`` (the analysis
#: package imports no jax-bearing library module; the two sets are pinned
#: equal by tests/test_state_compaction.py so they cannot drift).
NARROWED_LANES = frozenset({
    "ring_perm", "ring_pos", "obs_idx", "inval_obs", "cohort_of",
    "fd_count", "fd_hist", "fire_round", "report_bits",
    "cp_rnd_r", "cp_rnd_i", "cp_vrnd_r", "cp_vrnd_i", "cp_vval_src",
    "classic_epoch", "rounds_undecided",
})

#: Call shapes whose keywords are lane STORES: the NamedTuple ``_replace``
#: method and the state-pytree constructors themselves.
_STORE_CONSTRUCTORS = frozenset({"EngineState", "FaultInputs"})


def _binop_outside_astype(node: ast.AST, inside: bool = False) -> bool:
    """True when the expression contains a BinOp not enclosed by an
    ``.astype(...)`` call — arithmetic whose result dtype is promotion's
    choice, not the lane's. Comparisons and boolean ops are excluded (they
    produce bools, which no narrowed lane stores)."""
    if isinstance(node, ast.Call) and (
        isinstance(node.func, ast.Attribute) and node.func.attr == "astype"
    ):
        inside = True
    if isinstance(node, ast.BinOp) and not inside:
        return True
    return any(
        _binop_outside_astype(child, inside) for child in ast.iter_child_nodes(node)
    )


def _check_dtype_widening(
    tree: ast.AST,
    rel: str,
    source_lines: List[str],
    findings: List[Finding],
) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        is_replace = (
            isinstance(node.func, ast.Attribute) and node.func.attr == "_replace"
        )
        is_ctor = (
            isinstance(node.func, ast.Name)
            and node.func.id in _STORE_CONSTRUCTORS
        )
        if not (is_replace or is_ctor):
            continue
        for kw in node.keywords:
            if kw.arg not in NARROWED_LANES:
                continue
            if not _binop_outside_astype(kw.value):
                continue
            if _comment_ok(source_lines, kw.value.lineno, "# widen-ok:"):
                continue
            findings.append(Finding(
                rel, kw.value.lineno, "dtype-widening",
                f"arithmetic stored into policy-narrowed lane {kw.arg!r} "
                f"without an explicit cast: jnp type promotion re-widens "
                f"the lane to 32 bits the moment a wide operand appears — "
                f"accumulate in int32 and `.astype(...)` the store (or "
                f"justify with `# widen-ok: <reason>`)",
            ))


# -- missing-partition-spec --------------------------------------------------

#: The regex rule table's module-level name (parallel/mesh.py).
RULES_NAME = "PARTITION_RULES"

#: The tenant batch axis (rapid_tpu/parallel/mesh.TENANT_AXIS): a pytree
#: leaf whose shape annotation declares a leading ``[t`` dimension is a
#: TENANT-STACKED leaf, and its rule must shard dimension 0 on this axis —
#: an unmeshed tenant dimension replicates every tenant's state onto every
#: tenant's devices, the exact failure mode the fleet mesh exists to
#: prevent.
TENANT_AXIS_NAME = "tenant"
_TENANT_SHAPE_RE = re.compile(r"#\s*\[t[\],]")

#: A replication justification whose premise died with the 1-D mesh: the
#: cohort axis IS meshed now, so any surviving instance is a finding.
STALE_REPLICATION_REASON = "cohort axis is not meshed"


def _partition_rules(tree: ast.AST) -> Optional[Tuple[int, List[Dict[str, Any]]]]:
    """The module-level ``PARTITION_RULES`` tuple literal, parsed to
    (assignment lineno, [{pattern, meshed_axes, lineno, spec_lineno}]).
    None when the module declares no rule table. Only statically-resolvable
    (pattern-Constant, spec-Tuple) rules are kept — skip, don't guess."""
    for node in tree.body:
        value = None
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == RULES_NAME
        ):
            value = node.value
        elif (
            isinstance(node, ast.AnnAssign)
            and isinstance(node.target, ast.Name)
            and node.target.id == RULES_NAME
        ):
            value = node.value
        if not isinstance(value, ast.Tuple):
            continue
        rules: List[Dict[str, Any]] = []
        for elt in value.elts:
            if not (isinstance(elt, ast.Tuple) and len(elt.elts) == 2):
                continue
            pat, spec = elt.elts
            if not (isinstance(pat, ast.Constant) and isinstance(pat.value, str)):
                continue
            if not isinstance(spec, ast.Tuple):
                continue  # a computed spec: skip, don't guess
            meshed = sum(
                1
                for a in spec.elts
                if not (isinstance(a, ast.Constant) and a.value is None)
            )

            def _is_tenant_axis(node: ast.AST) -> bool:
                if isinstance(node, ast.Name):
                    return node.id == "TENANT_AXIS"
                return (
                    isinstance(node, ast.Constant)
                    and node.value == TENANT_AXIS_NAME
                )

            rules.append({
                "pattern": pat.value,
                "meshed_axes": meshed,
                "dim0_tenant": bool(spec.elts) and _is_tenant_axis(spec.elts[0]),
                "lineno": pat.lineno,
                "spec_lineno": spec.lineno,
            })
        return node.lineno, rules
    return None


def _stale_annotation_findings(rel: str, source_lines: List[str]) -> List[Finding]:
    return [
        Finding(
            rel, lineno, "missing-partition-spec",
            f"stale replication justification {STALE_REPLICATION_REASON!r}: "
            f"the cohort axis IS a mesh axis (2-D ('cohort', 'nodes') mesh) "
            f"— shard the leaf over it or state the real reason",
        )
        for lineno, line in enumerate(source_lines, 1)
        if STALE_REPLICATION_REASON in line
    ]


def _tenant_leaves(tree: ast.AST, source_lines: List[str]) -> Set[str]:
    """Field names of the module's state-pytree classes whose shape
    annotation comment declares a LEADING tenant dimension (``# [t]`` /
    ``# [t, ...]``) — the leaves the tenant-axis rule discipline covers."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef) and node.name in _PYTREE_TABLES):
            continue
        for stmt in node.body:
            if not (
                isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            ):
                continue
            if 1 <= stmt.lineno <= len(source_lines) and _TENANT_SHAPE_RE.search(
                source_lines[stmt.lineno - 1]
            ):
                out.add(stmt.target.id)
    return out


def _rule_findings(
    fields_by_class: Dict[str, List[str]],
    assign_lineno: int,
    rules: List[Dict[str, Any]],
    rel: str,
    source_lines: List[str],
    tenant_leaves: Optional[Set[str]] = None,
) -> List[Finding]:
    """Coverage of the engine pytree leaves by the regex rule table: every
    leaf fullmatches a rule (first match wins, mirroring
    ``mesh.match_partition_rules``), no rule is dead, and a rule that
    replicates (names no mesh axis) justifies itself on its spec line."""
    findings: List[Finding] = []
    compiled: List[Optional["re.Pattern"]] = []
    for rule in rules:
        try:
            compiled.append(re.compile(rule["pattern"]))
        except re.error as exc:
            compiled.append(None)
            findings.append(Finding(
                rel, rule["lineno"], "missing-partition-spec",
                f"{RULES_NAME} rule {rule['pattern']!r} is not a valid "
                f"regex ({exc}) — it can cover nothing",
            ))
    all_fields = sorted({f for fields in fields_by_class.values() for f in fields})
    matched_fields: Dict[int, List[str]] = {}
    for field in all_fields:
        hit = None
        for idx, pattern in enumerate(compiled):
            if pattern is not None and pattern.fullmatch(field):
                hit = idx
                break
        if hit is None:
            findings.append(Finding(
                rel, assign_lineno, "missing-partition-spec",
                f"engine pytree leaf {field!r} matches no rule in "
                f"{RULES_NAME} — an uncovered leaf silently replicates "
                f"onto every device",
            ))
        else:
            matched_fields.setdefault(hit, []).append(field)
    for idx, rule in enumerate(rules):
        if compiled[idx] is None:
            continue
        fields = matched_fields.get(idx, [])
        if not fields:
            findings.append(Finding(
                rel, rule["lineno"], "missing-partition-spec",
                f"{RULES_NAME} rule {rule['pattern']!r} matches no engine "
                f"pytree leaf — dead table entry",
            ))
        elif rule["meshed_axes"] == 0 and not _comment_ok(
            source_lines, rule["spec_lineno"], "# replicated-ok:"
        ):
            findings.append(Finding(
                rel, rule["spec_lineno"], "missing-partition-spec",
                f"{RULES_NAME} rule {rule['pattern']!r} fully replicates "
                f"leaves {fields} without a `# replicated-ok: <reason>` "
                f"justification",
            ))
        stacked = sorted(set(fields) & (tenant_leaves or set()))
        if stacked and not rule["dim0_tenant"]:
            findings.append(Finding(
                rel, rule["spec_lineno"], "missing-partition-spec",
                f"{RULES_NAME} rule {rule['pattern']!r} covers "
                f"tenant-stacked leaves {stacked} ([t, ...] shape "
                f"annotation) but does not shard dimension 0 on the "
                f"'{TENANT_AXIS_NAME}' axis — an unmeshed tenant dimension "
                f"replicates every tenant's state onto every tenant's "
                f"devices",
            ))
    findings.extend(_stale_annotation_findings(rel, source_lines))
    return findings


def _pytree_array_fields(tree: ast.AST) -> Dict[str, List[str]]:
    """Array-leaf field names of each state-pytree NamedTuple present in
    the module (annotation mentions ``ndarray``)."""
    out: Dict[str, List[str]] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef) and node.name in _PYTREE_TABLES):
            continue
        fields = []
        for stmt in node.body:
            if not (
                isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            ):
                continue
            ann = ast.dump(stmt.annotation)
            if "ndarray" in ann or "Array" in ann:
                fields.append(stmt.target.id)
        if fields:
            out[node.name] = fields
    return out


def _table_constructor_calls(
    tree: ast.AST,
) -> Dict[str, Tuple[ast.Call, int]]:
    """class name -> (the pytree constructor Call inside its sharding-table
    function, the function's lineno)."""
    out: Dict[str, Tuple[ast.Call, int]] = {}
    fn_for = {fn: cls for cls, fn in _PYTREE_TABLES.items()}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.FunctionDef) and node.name in fn_for):
            continue
        cls = fn_for[node.name]
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
                and sub.func.id == cls
                and sub.keywords
            ):
                out[cls] = (sub, node.lineno)
                break
    return out


def _partition_spec_findings(
    fields_by_class: Dict[str, List[str]],
    tables_tree: ast.AST,
    tables_rel: str,
    tables_source: str,
) -> List[Finding]:
    findings: List[Finding] = []
    source_lines = tables_source.splitlines()
    calls = _table_constructor_calls(tables_tree)
    for cls, fields in sorted(fields_by_class.items()):
        if cls not in calls:
            continue  # presence-gated: no table for this pytree here
        call, fn_lineno = calls[cls]
        declared = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        table_fn = _PYTREE_TABLES[cls]
        for field in fields:
            if field not in declared:
                findings.append(Finding(
                    tables_rel, call.lineno, "missing-partition-spec",
                    f"{cls} array leaf {field!r} has no declared "
                    f"PartitionSpec in {table_fn}() — an undeclared leaf "
                    f"silently replicates onto every device",
                ))
                continue
            value = declared[field]
            if not (
                isinstance(value, ast.Call)
                and isinstance(value.func, ast.Name)
                and value.func.id == "sh"
            ):
                continue  # a non-sh() spec: skip, don't guess
            has_axis = any(
                not (isinstance(a, ast.Constant) and a.value is None)
                for a in value.args
            )
            if not has_axis and not _comment_ok(
                source_lines, value.lineno, "# replicated-ok:"
            ):
                findings.append(Finding(
                    tables_rel, value.lineno, "missing-partition-spec",
                    f"{cls} leaf {field!r} is declared fully replicated "
                    f"(sh() with no axes) without a `# replicated-ok: "
                    f"<reason>` justification",
                ))
        for kw in call.keywords:
            if kw.arg and kw.arg not in fields:
                findings.append(Finding(
                    tables_rel, kw.value.lineno, "missing-partition-spec",
                    f"{table_fn}() declares a spec for {kw.arg!r}, which is "
                    f"not an array leaf of {cls} — dead table entry",
                ))
    findings.extend(_stale_annotation_findings(tables_rel, source_lines))
    return findings


# -- entry points ------------------------------------------------------------


def check_sharding(
    path: Path,
    source: Optional[str] = None,
    tree: "Optional[ast.AST]" = None,
) -> List[Finding]:
    """Per-file sharding lint (prefix-gated). The partition-spec section
    runs only when the file holds BOTH a state pytree and its sharding
    table (the corpus miniatures); the real split pair is merged by the
    tree-mode check."""
    rel = core.rel(path)
    posix = rel.replace("\\", "/")
    is_stream = any(posix.startswith(p) for p in STREAM_PREFIXES)
    if not is_stream and not any(posix.startswith(p) for p in SHARDING_PREFIXES):
        return []
    src = source if source is not None else path.read_text()
    if tree is None:
        tree = ast.parse(src, filename=str(path))
    aliases = _import_aliases(tree)
    source_lines = src.splitlines()
    findings: List[Finding] = []
    if is_stream:
        # Serving modules get the strict whole-module discipline (every
        # blocking read is a declared boundary) and none of the jit-seam
        # checks — the pipeline is host code in front of already-audited
        # compiled entrypoints.
        _check_stream_host_sync(tree, aliases, rel, source_lines, findings)
        return sorted(set(findings), key=lambda f: (f.lineno, f.check, f.message))
    _check_host_sync(tree, aliases, rel, source_lines, findings)
    _check_donation(tree, aliases, rel, source_lines, findings)
    _check_retrace(tree, aliases, rel, source_lines, findings)
    _check_dtype_widening(tree, rel, source_lines, findings)
    fields = _pytree_array_fields(tree)
    rules = _partition_rules(tree)
    if fields and rules is not None:
        findings.extend(_rule_findings(
            fields, rules[0], rules[1], rel, source_lines,
            tenant_leaves=_tenant_leaves(tree, source_lines),
        ))
    elif fields and _table_constructor_calls(tree):
        findings.extend(_partition_spec_findings(fields, tree, rel, src))
    return sorted(set(findings), key=lambda f: (f.lineno, f.check, f.message))


def check_partition_specs(
    trees: Sequence[Tuple[ast.AST, str]]
) -> List[Finding]:
    """Tree-mode entry: merge the real state.py/mesh.py pair. Presence-
    gated on both files being part of the sweep (tests retargeting
    ``core.REPO`` at temporary trees skip silently)."""
    state_tree = mesh_tree = None
    for tree, rel in trees:
        posix = rel.replace("\\", "/")
        if posix == STATE_FILE:
            state_tree = tree
        elif posix == MESH_FILE:
            mesh_tree = tree
    if state_tree is None or mesh_tree is None:
        return []
    fields = _pytree_array_fields(state_tree)
    if not fields:
        return []
    mesh_path = core.REPO / MESH_FILE
    mesh_source = mesh_path.read_text()
    rules = _partition_rules(mesh_tree)
    if rules is not None:
        state_source = (core.REPO / STATE_FILE).read_text()
        return _rule_findings(
            fields, rules[0], rules[1], MESH_FILE, mesh_source.splitlines(),
            tenant_leaves=_tenant_leaves(state_tree, state_source.splitlines()),
        )
    return _partition_spec_findings(fields, mesh_tree, MESH_FILE, mesh_source)
