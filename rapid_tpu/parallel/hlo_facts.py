"""Compiled-HLO fact extraction: collectives, transfers, donation aliases.

The canonical home of the classifier that started life as
``rapid_tpu/parallel/audit.py`` (now a thin re-export): pure text parsing
over ``compiled.as_text()``, no jax import, stdlib only — which is why it
lives IN the packaged library (an installed wheel must be able to import
it) while the ``device_program`` analyzer family
(tools/analysis/device_program.py), the evidence-table CLI
(tools/collective_audit.py), and the sharded-engine invariants test
(tests/test_parallel.py) all consume it from here (tools depends on the
library, never the reverse).

Everything here is derived from two pieces of metadata XLA records in the
compiled artifact: the shape string of each op (payload accounting) and the
``op_name`` jax attaches (location attribution — "…/while/body/…" is a
loop level, the one around the round's ``fd_tick`` scope the round loop,
"…/cond/…" a lax.cond branch). The module header's ``input_output_alias``
table is the compiled truth about buffer donation:
a ``donate_argnums`` argument either appears there or was dropped.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

#: Host<->device transfer ops: a compiled engine program must not smuggle
#: host round-trips into the dispatch (the whole point of the fused-engine
#: design); any of these appearing in a registered program is a finding.
TRANSFER_OPS = (
    "infeed",
    "outfeed",
    "send",
    "send-done",
    "recv",
    "recv-done",
)

#: Bits per element by HLO dtype token. Bits, not bytes: the sub-byte
#: dtypes (s4/u4) pack two elements per byte and a byte table would have to
#: lie about them.
DTYPE_BITS = {
    "pred": 8,
    "s4": 4, "u4": 4,
    "s8": 8, "u8": 8, "f8e4m3": 8, "f8e5m2": 8, "f8e4m3fn": 8,
    "f8e4m3b11fnuz": 8, "f8e5m2fnuz": 8, "f8e4m3fnuz": 8,
    "s16": 16, "u16": 16, "bf16": 16, "f16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64,
    "c128": 128,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str: str, unknown: Optional[List[str]] = None) -> int:
    """'(u32[64]{0}, …)' or 'u32[2,1024]{0,1}' -> total payload bytes.

    Handles tuple shapes with nested layout annotations (the ``{0,1}``
    suffixes are not shape tokens and are ignored). A dtype missing from
    ``DTYPE_BITS`` is never silently guessed: it is appended to ``unknown``
    when a list is passed, else raises ``ValueError`` — the analyzer turns
    collected unknowns into findings (``hlo-unknown-dtype``)."""
    total_bits = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        bits = DTYPE_BITS.get(dtype)
        if bits is None:
            if unknown is None:
                raise ValueError(f"unknown HLO dtype {dtype!r} in {shape_str!r}")
            unknown.append(dtype)
            continue
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        total_bits += elems * bits
    return (total_bits + 7) // 8


def entry_parameter_bytes(
    compiled_text: str, unknown: Optional[List[str]] = None
) -> Dict[str, int]:
    """Per-dtype payload bytes of the ENTRY computation's parameters —
    the compiled-artifact proof that a dtype-narrowing policy actually
    landed (a compact engine program's signature carries s8/s16/u8/u16
    argument lanes where the wide oracle carries only s32/u32/pred).

    Parses the ``ENTRY %name (arg: dtype[dims], ...) -> ...`` header line;
    nested computations' parameters (while bodies etc.) are deliberately
    excluded — only the entry signature is the program's argument surface.
    Sub-byte dtypes price at their true bit width via :data:`DTYPE_BITS`."""
    for line in compiled_text.splitlines():
        stripped = line.strip()
        if not stripped.startswith("ENTRY "):
            continue
        head, sep, _tail = stripped.partition(") -> ")
        if not sep:
            continue
        params = head.partition("(")[2]
        out: Dict[str, int] = {}
        for dtype, dims in _SHAPE_RE.findall(params):
            bits = DTYPE_BITS.get(dtype)
            if bits is None:
                if unknown is None:
                    raise ValueError(
                        f"unknown HLO dtype {dtype!r} in ENTRY parameters"
                    )
                unknown.append(dtype)
                continue
            elems = 1
            for d in dims.split(","):
                if d:
                    elems *= int(d)
            out[dtype] = out.get(dtype, 0) + (elems * bits + 7) // 8
        return out
    return {}


#: One enclosing loop level of an op_name: the body or the predicate of a
#: ``lax.while_loop``, in the plain spelling and in the batched
#: ``vmap(while)`` one the tenant fleet's vmapped loops trace under.
_LOOP_SCOPE_RE = re.compile(r"(?:/while|vmap\(while\))/(?:body|cond)")

#: The scope every engine round opens first (``fd_tick`` of
#: ``utils/dispatch.ENGINE_SCOPES``), plain and as a top-level ``vmap``
#: spells it: the loop around it IS the round loop, whatever else nests.
_ROUND_SCOPE_RE = re.compile(r"[/(]fd_tick[/)]")

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def round_loop(compiled_text: str) -> Optional[str]:
    """The op_name prefix of the body of the program's ROUND loop: the
    innermost loop around the round's own ``fd_tick`` scope
    (``jit(program)/while/body/while/body`` in a wave). None when the
    program names no round (``sync``, a corpus miniature) or runs its one
    round outside any loop (``step``). A program that names rounds under
    two different loops has no one round loop, and raises."""
    bodies = set()
    for name in _OP_NAME_RE.findall(compiled_text):
        scope = _ROUND_SCOPE_RE.search(name)
        if scope is None:
            continue
        loops = list(_LOOP_SCOPE_RE.finditer(name, 0, scope.start()))
        if loops:
            bodies.add(name[:loops[-1].end()])
    if len(bodies) > 1:
        raise ValueError(f"rounds under more than one loop: {sorted(bodies)}")
    return bodies.pop() if bodies else None


def classify_location(op_name: str, round_body: Optional[str] = None) -> str:
    """hot-loop / hot-loop-cond / wave-loop / wave-loop-cond / cond /
    prologue, from op_name metadata.

    ``round_body`` is the program's :func:`round_loop`
    (:func:`audit_collectives` derives it from the text). ``hot-loop`` is
    that loop alone, with whatever nests inside it: a wave program runs it
    inside the per-convergence loop, and every other loop level reads
    ``wave-loop`` (the mask build at the head of each convergence is
    per-cut work, not per-round work). Without a ``round_body`` every loop
    level is ``hot-loop``.

    A ``-cond`` suffix (and plain ``cond`` outside any loop) marks an op
    under a ``lax.cond`` branch. A loop PREDICATE runs unconditionally
    every iteration: its ``/while/cond`` scope is a loop level, never a
    gated branch. Both loop spellings count: a fleet hot-loop collective
    must never pass as prologue.
    """
    first = _LOOP_SCOPE_RE.search(op_name)
    if first is None:
        return "cond" if "/cond/" in op_name else "prologue"
    in_round = round_body is None or op_name.startswith(
        (round_body + "/", round_body[:-len("body")] + "cond/")
    )
    level = "hot-loop" if in_round else "wave-loop"
    inside = _LOOP_SCOPE_RE.sub("", op_name[first.start():])
    return level + "-cond" if "/cond/" in inside else level


def source_of(op_name: str) -> str:
    """Human label for the jax op a collective lowered from."""
    markers = (
        ("ring_topology", "view-change topology rebuild"),
        ("classic_attempt", "classic-fallback attempt"),
        ("tally_candidates", "fast-round vote tally"),
        ("cumsum", "classic-fallback attempt"),
        ("reduce_or", "round-body reduction"),
        ("reduce_sum", "round-body reduction"),
        ("reduce_max", "round-body reduction"),
        ("gather", "cross-slot gather"),
        ("sort", "sort"),
        ("reduce", "reduction"),
        # Lowering-artifact spellings: GSPMD re-shards around these ops and
        # the resulting collectives inherit their op_name leaf.
        ("scatter", "scatter update"),
        ("concatenate", "concatenate"),
        ("dynamic_slice", "dynamic slice"),
        ("squeeze", "reshape"),
        ("slice", "slice"),
    )
    for needle, label in markers:
        if needle in op_name:
            return label
    return "other"


def payload_class(nbytes: int, n: int, c: int) -> str:
    """Scale class of a collective payload at engine shapes: ``cn`` ([c,n]
    or larger), ``n`` (at least [n]-proportional), ``scalar`` otherwise.
    The invariants are stated over the CLASS, not raw bytes, so a benign
    constant tweak does not trip them while a scale-class jump always does."""
    if nbytes >= c * n:
        return "cn"
    if nbytes >= n:
        return "n"
    return "scalar"


PAYLOAD_CLASS_RANK = {"scalar": 0, "n": 1, "cn": 2}


def audit_collectives(compiled_text: str, n: int, c: int) -> List[Dict]:
    """One row per collective op in the HLO text: kind, global shape,
    payload bytes, location, source, scale flags (n_scale = at least
    [n]-proportional payload, cn_scale = at least [c,n]), and any unknown
    dtype tokens the payload accounting could not size.

    Matches both synchronous ops and the async ``-start`` halves TPU
    compiles emit (``all-reduce-start``/``all-reduce-done`` pairs — the
    ``-done`` half is skipped so pairs are not double-counted)."""
    round_body = round_loop(compiled_text)
    rows = []
    for line in compiled_text.splitlines():
        m = re.search(
            r"= (\([^)]*\)|\S+?) ("
            + "|".join(COLLECTIVE_KINDS)
            + r")(-start)?\(",
            line,
        )
        if not m:
            continue
        shape, kind = m.group(1), m.group(2)
        op_name_m = _OP_NAME_RE.search(line)
        op_name = op_name_m.group(1) if op_name_m else ""
        unknown: List[str] = []
        payload = shape_bytes(shape, unknown=unknown)
        rows.append({
            "kind": kind,
            "shape": shape.split("{")[0],
            # The TOTAL payload: a variadic collective carries a tuple
            # shape, summed over its operands.
            "bytes": payload,
            "location": classify_location(op_name, round_body),
            "source": source_of(op_name),
            "cn_scale": payload >= c * n,
            "n_scale": payload >= n,
            "groups": collective_groups(line),
            "unknown_dtypes": sorted(set(unknown)),
        })
    return rows


#: replica_groups in the explicit list form: {{0,1},{2,3}}.
_RG_LIST_RE = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}")
_RG_GROUP_RE = re.compile(r"\{([\d,]*)\}")
#: replica_groups in the iota (v2) form: [4,2]<=[2,2,2]T(0,2,1) — groups =
#: transpose(iota(prod).reshape(reshape_dims), perm).reshape(G, S) rows.
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"
)
#: collective-permute carries (source, target) device pairs instead.
_STP_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def _iota_groups(g: int, s: int, rdims: List[int], perm: List[int]) -> List[List[int]]:
    """Expand the iota replica-group form without numpy (this module is
    stdlib-only): devices = transpose(arange(prod).reshape(rdims), perm)
    flattened row-major, chunked into G groups of S."""
    strides = [0] * len(rdims)
    acc = 1
    for d in range(len(rdims) - 1, -1, -1):
        strides[d] = acc
        acc *= rdims[d]
    shape_t = [rdims[p] for p in perm]
    devices: List[int] = []
    idx_t = [0] * len(shape_t)
    total = acc
    for _ in range(total):
        devices.append(
            sum(idx_t[j] * strides[perm[j]] for j in range(len(perm)))
        )
        for j in range(len(shape_t) - 1, -1, -1):
            idx_t[j] += 1
            if idx_t[j] < shape_t[j]:
                break
            idx_t[j] = 0
    return [devices[i * s : (i + 1) * s] for i in range(g)]


def collective_groups(line: str) -> Optional[List[List[int]]]:
    """The device groups one collective HLO line communicates within:
    ``replica_groups`` (explicit-list or iota form) as group lists, or
    ``source_target_pairs`` (collective-permute) as one two-device group
    per pair. None when the line names neither — which for a partitioned
    module means ALL devices participate (callers must treat None as one
    all-device group, never as "no communication")."""
    m = _RG_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        rdims = [int(x) for x in m.group(3).split(",")]
        perm = (
            [int(x) for x in m.group(4).split(",")]
            if m.group(4) else list(range(len(rdims)))
        )
        return _iota_groups(g, s, rdims, perm)
    m = _RG_LIST_RE.search(line)
    if m:
        groups = [
            [int(x) for x in body.split(",") if x]
            for body in _RG_GROUP_RE.findall(m.group(1))
        ]
        # ``replica_groups={}`` is XLA's spelling for ONE group containing
        # every participant — fold it into the None (all-devices) case so
        # it can never read as "no communication".
        return groups or None
    m = _STP_RE.search(line)
    if m:
        return [
            [int(x) for x in body.split(",")]
            for body in _RG_GROUP_RE.findall(m.group(0))
        ]
    return None


def groups_cross_blocks(
    groups: Optional[List[List[int]]], block: int
) -> bool:
    """True when any group spans two device blocks of size ``block`` —
    with the tenant axis leading the mesh, device ids are contiguous per
    tenant slice, so a group containing ids from two blocks is a
    cross-tenant collective. ``None`` groups (all-participants) cross by
    definition whenever more than one block exists."""
    if groups is None:
        return True
    for group in groups:
        if len({device // block for device in group}) > 1:
            return True
    return False


def collective_violations(rows: List[Dict]) -> Dict[str, List[Dict]]:
    """The two invariants the sharded design guarantees on the 1-D mesh.
    The first is not true of the 2-D one, whose round loop also re-lays the
    [c]-sized tally out across the cohort axis every round (scalar-class
    all-to-alls and all-gathers; tests/test_hlo_gate.py holds them to that
    class, ROADMAP A12 would remove them)."""
    return {
        # Every round, unconditionally: reductions only — an unconditional
        # gather here would ship O(n)+ bytes per round for no reason.
        "hot_loop_non_reduce": [
            r for r in rows
            if r["location"] == "hot-loop" and r["kind"] != "all-reduce"
        ],
        # [c,n]-sized traffic must be cond-gated (implicit invalidation,
        # classic attempt, view-change re-sort) — never unconditional. The
        # prologue may hold the hoisted [n]-scale edge gathers, nothing
        # [c,n]-scale.
        "unconditional_cn_anywhere": [
            r for r in rows if r["cn_scale"] and "cond" not in r["location"]
        ],
    }


def count_transfer_ops(compiled_text: str) -> Dict[str, int]:
    """Host<->device transfer ops per kind (zero entries omitted)."""
    counts: Dict[str, int] = {}
    pattern = re.compile(
        r"= (?:\([^)]*\)|\S+?) (" + "|".join(TRANSFER_OPS) + r")\("
    )
    for line in compiled_text.splitlines():
        m = pattern.search(line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


#: One alias-table entry: ``{output_index}: (param, {param_index}, kind)``.
#: Parsed straight off the ``HloModule`` header line — the entry shape is
#: specific enough that no other header field matches it, which sidesteps
#: brace-balancing the ``input_output_alias={...}`` table (its entries
#: contain ``}, `` themselves).
_ALIAS_ENTRY_RE = re.compile(
    r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(may-alias|must-alias)\)"
)


def input_output_aliases(compiled_text: str) -> List[Tuple[int, str]]:
    """The module header's donation outcomes: one ``(parameter_number,
    alias_kind)`` per output buffer XLA agreed to alias onto an input.
    Empty when nothing was donated — or when every donation was dropped."""
    header = compiled_text.splitlines()[0] if compiled_text else ""
    if "input_output_alias=" not in header:
        return []
    return [
        (int(param), kind)
        for param, kind in _ALIAS_ENTRY_RE.findall(header)
    ]
