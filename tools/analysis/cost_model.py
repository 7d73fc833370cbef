"""Check family 16: the scaling-law cost-model gate (cost.lock.json).

The HLO gate (family 12) freezes compiled-program budgets at ONE audit
shape — which means a refactor that silently turns an O(N) payload into
O(N·K) or O(N²) still passes it, because a single shape cannot tell the
classes apart. This family compiles each registered entrypoint across a
small geometry **ladder** (N ∈ {64, 128, 256, 512} at fixed K/C, a K
ladder for the round-body entrypoints, a tenant-count ladder for the
fleet), extracts per-shape facts via ``rapid_tpu/parallel/hlo_facts.py``
(total and largest collective payload bytes, per-device argument/temp/
codegen bytes, transfer ops, and ``compiled.cost_analysis()`` FLOPs /
bytes-accessed where the backend exposes them — None-tolerant, never
guessed), and FITS each fact to a scaling class:

    O(1) < O(log N) < O(N) < O(N*K) < O(N^2)

by non-negative least squares over the nested basis ``{1, log2 N, N,
N·K, N²}`` — smallest class whose model explains every ladder point
within the fact's tolerance wins; if none does, the fit REFUSES
(``cost-unexplained``) rather than guess. Plain log-log slope matching is
deliberately not used: the real facts are affine mixtures (argument bytes
at the audit geometry are exactly ``108 + 253·N + 38·N·K``) whose log-log
slope sits between classes.

Fitted classes + leading coefficients freeze into the committed
``tools/analysis/cost.lock.json`` via ``staticcheck --update-cost-lock``
(refuses while any fit is unexplained, any fact exceeds its ceiling, or
the hlo.lock differentials disagree; regeneration is byte-identical when
nothing changed). Drift fails the gate with named findings:

- ``cost-scaling-regression`` — an entrypoint/fact whose fitted class
  worsened vs the lock (the silent-asymptotics failure this family
  exists to catch);
- ``cost-superlinear`` — any fact exceeding its per-entrypoint ceiling
  (nothing in the round body may exceed O(N*K): Rapid's central claim);
- ``cost-quiescent`` — drift of ``quiescent_round_cost``, the zero-churn
  round's per-round FLOPs and collective payload, frozen next to PR 15's
  ``quiescent_round_activity == 0`` fact so ROADMAP item 3's sparse
  restructure has its artifact-provable before/after predicate;
- ``cost-unexplained`` / ``cost-lock-drift`` — unclassifiable facts and
  ordinary lock staleness.

Ladder compiles are session-cached like the HLO gate's (one collection
per process, shared by the tree sweep, the lock regenerator, the bench's
``hlo_audit`` stage and every test); the base point (N=256, K=4) reuses
``device_program.collect_facts`` outright, and the tenant ladder uses the
MESHLESS vmapped fleet step so no extra GSPMD compiles are paid.

``check_cost_model`` is the per-file mode for the seeded lint corpus: a
module defining ``COST_AUDIT_PROGRAMS`` (name -> builder taking ``n``)
plus an inline ``COST_LOCK`` is compiled across its own miniature ladder
and compared — the corpus way to pin an injected O(N²) payload, finding
by finding.
"""

from __future__ import annotations

import ast
import json
import math
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import core, device_program
from .core import Finding

#: The committed freeze of the fitted scaling classes, repo-relative.
COST_LOCK_REL = "tools/analysis/cost.lock.json"

#: Scaling-class vocabulary, weakest to strongest. ASCII spellings so the
#: lockfile and findings survive every terminal; prose may write O(N·K).
CLASSES = ("O(1)", "O(log N)", "O(N)", "O(N*K)", "O(N^2)")
CLASS_RANK = {cls: rank for rank, cls in enumerate(CLASSES)}

#: Nothing in the round body may exceed O(N·K) — the paper's per-node
#: O(K) claim priced at the whole-cluster grain. Every registered
#: entrypoint carries this ceiling unless COST_CEILINGS overrides it.
DEFAULT_CEILING = "O(N*K)"
COST_CEILINGS: Dict[str, str] = {}

#: The geometry ladders. BASE_* mirror the HLO gate's audit shapes so the
#: base point reuses the session's ``collect_facts`` compile verbatim.
BASE_N = device_program.AUDIT_N
BASE_K = device_program.AUDIT_K
BASE_C = device_program.AUDIT_C
N_LADDER = (64, 128, 256, 512)
K_LADDER = (2, 4, 8)
TENANT_LADDER = (2, 4, 8)
#: Per-tenant slot count for the fleet ladder: tenant count T maps to
#: N_eff = T * FLEET_TENANT_N, so linearity in tenants fits as O(N) in
#: the shared class vocabulary (the fleet's whole-fleet cost must scale
#: with total slots, never faster).
FLEET_TENANT_N = 64

#: Entrypoints the ladder sweeps and the axes each varies. ``n`` is the
#: N_LADDER at K=BASE_K; ``k`` adds the K_LADDER at N=BASE_N (only the
#: central round-body step pays the extra compiles — every other
#: entrypoint shares its round body, and each ladder compile costs
#: seconds of every tier-1 session); ``tenants`` is the fleet ladder over
#: the meshless vmapped step. The mesh-gated GSPMD entrypoints are
#: deliberately absent (see LADDER_ENTRYPOINTS); their base-shape facts
#: still feed the quiescent cost block.
COST_REGISTRY: Dict[str, Dict[str, Any]] = {
    "step": {"axes": ("n", "k")},
    "run_to_decision": {"axes": ("n",)},
    "run_until_membership": {"axes": ("n",)},
    "sync": {"axes": ("n",)},
    # The compact layout's bytes-per-slot is a STEP function of n (the
    # config-derived min_index_dtype widens int8 -> int16 at n=128), so a
    # ladder spanning dtype regimes would conflate policy steps with
    # scaling — the fit refuses it, correctly. The compact ladder stays
    # inside the int16 regime instead: same 4-point fit power, one regime.
    "step_compact": {"axes": ("n",), "n_ladder": (128, 192, 256, 512)},
    "step_telem": {"axes": ("n",)},
    "step_trace": {"axes": ("n",)},
    "fleet_step": {"axes": ("tenants",)},
}

#: Per-fact fit tolerance (max relative residual). Shape-determined facts
#: are tight: argument bytes and collective payloads follow exactly from
#: the program's shapes, so anything their model cannot explain to 2% is
#: a real mixture term. Scheduler-determined facts (buffer assignment,
#: codegen) legitimately wobble; the analytic cost model's FLOPs /
#: bytes-accessed sit in between.
FACT_TOLERANCES = {
    "collective_payload_bytes": 0.02,
    "collective_largest_payload_bytes": 0.02,
    "argument_bytes": 0.02,
    "transfer_ops": 0.02,
    "temp_bytes": 0.35,
    "generated_code_bytes": 0.35,
    "flops": 0.08,
    "bytes_accessed": 0.15,
}
DEFAULT_TOLERANCE = 0.10

#: Facts whose per-point VALUES freeze into the lock and compare exactly
#: (shape-determined — a byte of drift is a program change); the rest
#: compare class-only (their constants wobble across XLA versions).
EXACT_FACTS = (
    "collective_payload_bytes",
    "collective_largest_payload_bytes",
    "argument_bytes",
    "transfer_ops",
)

#: A fit needs at least this many ladder points, and strictly more points
#: than model bases (an exactly-determined system "fits" anything —
#: overfit is how noise would sneak into a class).
MIN_LADDER_POINTS = 3

#: Relative tolerance for the quiescent FLOPs / bytes-accessed comparison
#: (the analytic cost model's constants wobble a little across XLA
#: versions; payload bytes compare exactly).
QUIESCENT_REL_TOL = 0.10

_REGEN_HINT = (
    "if this scaling change is intentional, regenerate via "
    "`python tools/staticcheck.py --update-cost-lock` and review the diff"
)


# -- the fitter -------------------------------------------------------------


def _basis_1(n: float, k: float) -> float:
    return 1.0


def _basis_log(n: float, k: float) -> float:
    return math.log2(n)


def _basis_n(n: float, k: float) -> float:
    return n


def _basis_nk(n: float, k: float) -> float:
    return n * k


def _basis_n2(n: float, k: float) -> float:
    return n * n


def _model_bases(cls: str, k_varies: bool):
    """The basis columns of one class's candidate model, leading term
    LAST. ``O(N*K)`` is only distinguishable when the ladder varies K —
    with K fixed it degenerates to O(N) and is skipped (the O(N) model
    already covers it; classifying O(N*K) off an N-only ladder would be a
    guess)."""
    if cls == "O(1)":
        return [_basis_1]
    if cls == "O(log N)":
        return [_basis_1, _basis_log]
    if cls == "O(N)":
        return [_basis_1, _basis_n]
    if cls == "O(N*K)":
        if not k_varies:
            return None
        return [_basis_1, _basis_n, _basis_nk]
    if cls == "O(N^2)":
        bases = [_basis_1, _basis_n, _basis_n2]
        if k_varies:
            bases.insert(2, _basis_nk)
        return bases
    raise ValueError(f"unknown scaling class {cls!r}")


def _gauss_solve(a: List[List[float]], b: List[float]) -> Optional[List[float]]:
    """Gaussian elimination with partial pivoting; None when singular.
    Pure python (5x5 at most) so the fit — and therefore the lockfile —
    is bit-deterministic with no numerics dependency."""
    m = len(b)
    a = [row[:] for row in a]
    b = b[:]
    for col in range(m):
        piv = max(range(col, m), key=lambda r: abs(a[r][col]))
        if abs(a[piv][col]) < 1e-12:
            return None
        a[col], a[piv] = a[piv], a[col]
        b[col], b[piv] = b[piv], b[col]
        for r in range(m):
            if r != col and a[r][col] != 0.0:
                f = a[r][col] / a[col][col]
                for cc in range(col, m):
                    a[r][cc] -= f * a[col][cc]
                b[r] -= f * b[col]
    return [b[i] / a[i][i] for i in range(m)]


def _lstsq(cols: List[List[float]], y: List[float]) -> Optional[List[float]]:
    """Least squares over column-max-scaled normal equations (the raw
    columns span 1 .. N², so scaling keeps the 5x5 solve conditioned)."""
    m = len(cols)
    pts = len(y)
    scales = [max((abs(v) for v in col), default=0.0) or 1.0 for col in cols]
    ata = [
        [
            sum(cols[i][p] / scales[i] * cols[j][p] / scales[j] for p in range(pts))
            for j in range(m)
        ]
        for i in range(m)
    ]
    aty = [
        sum(cols[i][p] / scales[i] * y[p] for p in range(pts)) for i in range(m)
    ]
    sol = _gauss_solve(ata, aty)
    if sol is None:
        return None
    return [sol[i] / scales[i] for i in range(m)]


def _nnls(cols: List[List[float]], y: List[float]) -> Optional[List[float]]:
    """Non-negative least squares by iterated dropping of the most
    negative column. Costs can only ADD with scale — a model that needs a
    negative N² coefficient to bend around noise is not evidence of an N²
    term, so negative solutions shed columns until none remain."""
    active = list(range(len(cols)))
    while active:
        coef = _lstsq([cols[j] for j in active], y)
        if coef is None:
            return None
        worst = min(range(len(active)), key=lambda i: coef[i])
        if coef[worst] >= -1e-9:
            out = [0.0] * len(cols)
            for i, j in enumerate(active):
                out[j] = max(coef[i], 0.0)
            return out
        active.pop(worst)
    return [0.0] * len(cols)


def fit_scaling(
    points: Sequence[Tuple[Tuple[float, float], float]], tol: float
) -> Dict[str, Any]:
    """Fit one fact's ladder — ``(((n, k), value), ...)`` — to the
    smallest adequately-fitting scaling class.

    Returns ``{"class", "coeff", "residual"}`` on success (``coeff`` is
    the leading-term coefficient) or ``{"error": ...}`` when the ladder is
    too short or no eligible model explains every point within ``tol``
    (the caller turns that into a ``cost-unexplained`` finding — skip,
    don't guess)."""
    pts = [((float(n), float(k)), float(v)) for (n, k), v in points]
    if len(pts) < MIN_LADDER_POINTS:
        return {
            "error": (
                f"ladder too short to classify ({len(pts)} point(s), "
                f"need {MIN_LADDER_POINTS})"
            )
        }
    if all(v == 0.0 for _, v in pts):
        # A fact that is zero at every shape (e.g. collective payload of a
        # single-device program) is a meaningful frozen fact: O(1), zero.
        return {"class": "O(1)", "coeff": 0.0, "residual": 0.0}
    k_varies = len({k for (_n, k), _ in pts}) > 1
    y = [v for _, v in pts]
    best: Optional[Tuple[str, float]] = None
    for cls in CLASSES:
        bases = _model_bases(cls, k_varies)
        if bases is None or len(pts) < len(bases) + 1:
            continue
        cols = [[b(n, k) for (n, k), _ in pts] for b in bases]
        coef = _nnls(cols, y)
        if coef is None:
            continue
        residual = max(
            abs(sum(c * col[p] for c, col in zip(coef, cols)) - y[p])
            / max(abs(y[p]), 1.0)
            for p in range(len(pts))
        )
        if best is None or residual < best[1]:
            best = (cls, residual)
        if residual <= tol:
            return {"class": cls, "coeff": coef[-1], "residual": residual}
    if best is None:
        return {
            "error": (
                f"no eligible scaling model for {len(pts)} ladder point(s) "
                f"(every candidate needs more points than bases)"
            )
        }
    return {
        "error": (
            f"no scaling class explains the ladder: best candidate "
            f"{best[0]} leaves relative residual {best[1]:.3g} > "
            f"tolerance {tol:g}"
        )
    }


# -- ladder collection ------------------------------------------------------


def ladder_points(name: str) -> List[Dict[str, int]]:
    """The geometry points one entrypoint's ladder sweeps, each with the
    effective scale ``n_eff`` the fit regresses against (for the fleet,
    tenants * FLEET_TENANT_N — total slots across the fleet)."""
    axes = COST_REGISTRY[name]["axes"]
    if "tenants" in axes:
        return [
            {
                "n": FLEET_TENANT_N,
                "k": BASE_K,
                "tenants": t,
                "n_eff": t * FLEET_TENANT_N,
            }
            for t in TENANT_LADDER
        ]
    n_ladder = COST_REGISTRY[name].get("n_ladder", N_LADDER)
    pts = [{"n": n, "k": BASE_K, "n_eff": n} for n in n_ladder]
    if "k" in axes:
        pts.extend(
            {"n": BASE_N, "k": k, "n_eff": BASE_N}
            for k in K_LADDER
            if k != BASE_K
        )
    return pts


def point_key(pt: Dict[str, int]) -> str:
    return f"n{pt['n_eff']}_k{pt['k']}"


def entry_cost_facts(entry: Dict[str, Any]) -> Dict[str, float]:
    """The cost-fact vector of one ``extract_facts`` entry. Facts the
    platform did not expose (no memory analysis, no cost analysis) are
    ABSENT, never guessed — the fit skips a fact unless every ladder
    point carries it."""
    rows = entry["rows"]
    memory = entry.get("memory") or {}
    cost = entry.get("cost") or {}
    facts: Dict[str, float] = {
        # Total payload sums tuple operands (hlo_facts prices a variadic
        # all-reduce by the SUM of its operand bytes), so multi-operand
        # fusion cannot hide growth from the ladder fit; the largest
        # single operand rides alongside.
        "collective_payload_bytes": float(sum(r["bytes"] for r in rows)),
        "collective_largest_payload_bytes": float(
            max((r["largest_operand_bytes"] for r in rows), default=0)
        ),
        "transfer_ops": float(sum(entry["transfers"].values())),
    }
    for key in ("argument_bytes", "temp_bytes", "generated_code_bytes"):
        if key in memory:
            facts[key] = float(memory[key])
    for key in ("flops", "bytes_accessed"):
        if key in cost:
            facts[key] = float(cost[key])
    return facts


#: (table, complete) — session cache, one ladder collection per process.
_LADDER_CACHE: Optional[Tuple[Dict[str, List[Dict[str, Any]]], bool]] = None


def collect_ladder(
    force: bool = False, require_mesh: bool = True
) -> Dict[str, List[Dict[str, Any]]]:
    """Compile the ladder and extract cost facts — once per process.

    Returns ``name -> [{"key", "n_eff", "k", "facts"}, ...]``. The base
    point (N=256, K=4) reuses the session's ``collect_facts`` entry (which
    the HLO gate has usually already paid for); every other point compiles
    via ``build_ladder_spec``. ``require_mesh``
    propagates to the base collection: the GATE needs the full registry
    (its quiescent block reads the sharded step), observational consumers
    (the bench on a single-chip backend) pass False and take whatever the
    process can build."""
    global _LADDER_CACHE
    import jax

    have_mesh = jax.device_count() >= device_program.AUDIT_DEVICES
    if _LADDER_CACHE is not None and not force:
        table, complete = _LADDER_CACHE
        if complete or not require_mesh:
            return table
    base_facts = device_program.collect_facts(require_mesh=require_mesh)
    table: Dict[str, List[Dict[str, Any]]] = {}
    for name in COST_REGISTRY:
        series: List[Dict[str, Any]] = []
        for pt in ladder_points(name):
            is_base = (
                "tenants" not in pt
                and pt["n"] == BASE_N
                and pt["k"] == BASE_K
                and name in base_facts
            )
            if is_base:
                entry = base_facts[name]
            else:
                spec = device_program.build_ladder_spec(
                    name, pt["n"], pt["k"], BASE_C,
                    tenants=pt.get("tenants"),
                )
                compiled, _reasons = device_program._compile_program(spec)
                entry = device_program.extract_facts(
                    compiled, spec["donated_leaves"], pt["n"], BASE_C
                )
            series.append({
                "key": point_key(pt),
                "n_eff": pt["n_eff"],
                "k": pt["k"],
                "facts": entry_cost_facts(entry),
            })
        table[name] = series
    _LADDER_CACHE = (table, have_mesh)
    return table


def collect_quiescent_cost(
    require_mesh: bool = True,
) -> Optional[Dict[str, Any]]:
    """The zero-churn round's compiled cost, read off the SHARDED step at
    the audit shape (the dense-round program the sparse restructure must
    shrink): total and hot-loop collective payload bytes (exact), plus
    FLOPs / bytes-accessed where the backend prices them. None when the
    collection has no sharded step (single-chip observational runs)."""
    facts = device_program.collect_facts(require_mesh=require_mesh)
    entry = facts.get("sharded_step")
    if entry is None:
        return None
    rows = entry["rows"]
    out: Dict[str, Any] = {
        "entrypoint": "sharded_step",
        "collective_payload_bytes": int(sum(r["bytes"] for r in rows)),
        "hot_loop_payload_bytes": int(
            sum(r["bytes"] for r in rows if r["location"].startswith("hot-loop"))
        ),
    }
    cost = entry.get("cost") or {}
    for key in ("flops", "bytes_accessed"):
        if key in cost:
            out[key] = cost[key]
    return out


# -- fitting + lock construction --------------------------------------------


def fit_ladder(
    table: Dict[str, List[Dict[str, Any]]]
) -> Tuple[Dict[str, Dict[str, Dict[str, Any]]], List[Tuple[str, str, str]]]:
    """Fit every (entrypoint, fact) series. Returns ``(fits, refusals)``:
    ``fits[name][fact] = {"class", "coeff", "residual", "points"}`` and
    one ``(name, fact, why)`` per refused fit. A fact absent at any ladder
    point is skipped entirely (None-tolerant — a partially-exposed fact is
    not evidence of anything)."""
    fits: Dict[str, Dict[str, Dict[str, Any]]] = {}
    refusals: List[Tuple[str, str, str]] = []
    for name, series in table.items():
        per: Dict[str, Dict[str, Any]] = {}
        fact_names = sorted({f for pt in series for f in pt["facts"]})
        for fact in fact_names:
            if not all(fact in pt["facts"] for pt in series):
                continue
            fitted = fit_scaling(
                [((pt["n_eff"], pt["k"]), pt["facts"][fact]) for pt in series],
                FACT_TOLERANCES.get(fact, DEFAULT_TOLERANCE),
            )
            if "error" in fitted:
                refusals.append((name, fact, fitted["error"]))
                continue
            fitted["points"] = {
                pt["key"]: _as_number(pt["facts"][fact]) for pt in series
            }
            per[fact] = fitted
        fits[name] = per
    return fits, refusals


def _as_number(value: float):
    return int(value) if float(value).is_integer() else float(value)


def _round_sig(value: float, digits: int) -> float:
    return float(f"{float(value):.{digits}g}")


def ceiling_for(name: str) -> str:
    return COST_CEILINGS.get(name, DEFAULT_CEILING)


def superlinear_findings(
    fits: Dict[str, Dict[str, Dict[str, Any]]], loc: Tuple[str, int]
) -> List[Finding]:
    """One ``cost-superlinear`` per (entrypoint, fact) whose fitted class
    exceeds the entrypoint's ceiling — never freezable (update_cost_lock
    refuses it, like a dropped donation)."""
    path, lineno = loc
    findings = []
    for name in sorted(fits):
        ceiling = ceiling_for(name)
        for fact in sorted(fits[name]):
            fit = fits[name][fact]
            if CLASS_RANK[fit["class"]] > CLASS_RANK[ceiling]:
                findings.append(Finding(
                    path, lineno, "cost-superlinear",
                    f"{name}: {fact} fitted {fit['class']} (leading coeff "
                    f"{_round_sig(fit['coeff'], 4)}) exceeds the "
                    f"entrypoint's {ceiling} ceiling — the round body must "
                    f"never scale past O(N*K); fix the program (this budget "
                    f"cannot be locked in)",
                ))
    return findings


def _ladder_config() -> Dict[str, Any]:
    return {
        "base": {"n": BASE_N, "k": BASE_K, "c": BASE_C},
        "n_ladder": list(N_LADDER),
        "n_ladder_overrides": {
            name: list(spec["n_ladder"])
            for name, spec in sorted(COST_REGISTRY.items())
            if "n_ladder" in spec
        },
        "k_ladder": list(K_LADDER),
        "tenant_ladder": list(TENANT_LADDER),
        "fleet_tenant_n": FLEET_TENANT_N,
        "classes": list(CLASSES),
    }


def fits_to_lock(
    fits: Dict[str, Dict[str, Dict[str, Any]]],
    quiescent: Optional[Dict[str, Any]],
) -> Dict[str, Any]:
    """The canonical freeze: per-entrypoint fitted classes + rounded
    leading coefficients (+ exact per-point values for shape-determined
    facts), the ladder geometry, and the quiescent cost block. Fully
    deterministic — same facts regenerate the same bytes."""
    lock: Dict[str, Any] = {
        "ladder_config": _ladder_config(),
        "entrypoints": {},
    }
    for name in sorted(fits):
        block: Dict[str, Any] = {}
        for fact in sorted(fits[name]):
            fit = fits[name][fact]
            entry: Dict[str, Any] = {
                "class": fit["class"],
                "coeff": _round_sig(fit["coeff"], 6),
                "residual": _round_sig(fit["residual"], 3),
            }
            if fact in EXACT_FACTS:
                entry["points"] = dict(sorted(fit["points"].items()))
            block[fact] = entry
        lock["entrypoints"][name] = {
            "ceiling": ceiling_for(name), "facts": block,
        }
    if quiescent is not None:
        lock["quiescent_round_cost"] = dict(quiescent)
    return lock


# -- comparison -------------------------------------------------------------


def compare_fact_fit(
    name: str,
    fact: str,
    fit: Dict[str, Any],
    locked: Dict[str, Any],
    loc: Tuple[str, int],
) -> List[Finding]:
    """Drift report for ONE (entrypoint, fact) fit against its locked
    entry: a class that worsened is a scaling regression by name; a class
    that improved, or exact per-point byte drift at the same class, is
    ordinary lock drift."""
    path, lineno = loc
    findings: List[Finding] = []
    old_cls = locked.get("class")
    if old_cls not in CLASS_RANK:
        findings.append(Finding(
            path, lineno, "cost-lock-drift",
            f"{name}: {fact} carries unknown locked class {old_cls!r} — "
            f"{_REGEN_HINT}",
        ))
        return findings
    new_cls = fit["class"]
    if CLASS_RANK[new_cls] > CLASS_RANK[old_cls]:
        findings.append(Finding(
            path, lineno, "cost-scaling-regression",
            f"{name}: {fact} scaling class WORSENED {old_cls} -> {new_cls} "
            f"(leading coeff {_round_sig(fit['coeff'], 4)}, residual "
            f"{_round_sig(fit['residual'], 3)}) — the compiled artifact "
            f"now grows faster with cluster size than the lock permits",
        ))
        return findings
    if CLASS_RANK[new_cls] < CLASS_RANK[old_cls]:
        findings.append(Finding(
            path, lineno, "cost-lock-drift",
            f"{name}: {fact} scaling class improved {old_cls} -> {new_cls} "
            f"— {_REGEN_HINT}",
        ))
        return findings
    if fact in EXACT_FACTS and "points" in locked:
        cur_pts = fit.get("points", {})
        for key in sorted(set(cur_pts) | set(locked["points"])):
            if cur_pts.get(key) != locked["points"].get(key):
                findings.append(Finding(
                    path, lineno, "cost-lock-drift",
                    f"{name}: {fact} at ladder point {key}: "
                    f"{locked['points'].get(key)} in the lock, "
                    f"{cur_pts.get(key)} now — {_REGEN_HINT}",
                ))
    return findings


def compare_quiescent(
    current: Optional[Dict[str, Any]],
    locked: Dict[str, Any],
    lock_path: str,
) -> List[Finding]:
    """Drift report for the ``quiescent_round_cost`` block. Payload bytes
    compare exactly; FLOPs / bytes-accessed under QUIESCENT_REL_TOL and
    presence-gated (a backend that stops pricing them is not drift)."""
    findings: List[Finding] = []
    if current is None:
        return findings
    for key in ("collective_payload_bytes", "hot_loop_payload_bytes"):
        if locked.get(key) != current.get(key):
            findings.append(Finding(
                lock_path, 1, "cost-quiescent",
                f"quiescent_round_cost: {key} {locked.get(key)} in the "
                f"lock, {current.get(key)} now — the zero-churn round's "
                f"collective payload moved; {_REGEN_HINT}",
            ))
    for key in ("flops", "bytes_accessed"):
        if key in locked and key in current:
            old, new = float(locked[key]), float(current[key])
            if abs(new - old) > QUIESCENT_REL_TOL * max(abs(old), 1.0):
                findings.append(Finding(
                    lock_path, 1, "cost-quiescent",
                    f"quiescent_round_cost: {key} drifted beyond "
                    f"{QUIESCENT_REL_TOL:.0%}: {old} in the lock, {new} "
                    f"now — {_REGEN_HINT}",
                ))
    return findings


def compare_cost_lock(
    fits: Dict[str, Dict[str, Dict[str, Any]]],
    quiescent: Optional[Dict[str, Any]],
    locked: Dict[str, Any],
    lock_path: str,
) -> List[Finding]:
    findings: List[Finding] = []
    locked_eps: Dict[str, Any] = locked.get("entrypoints", {})
    for name in sorted(set(fits) | set(locked_eps)):
        if name not in locked_eps:
            findings.append(Finding(
                lock_path, 1, "cost-lock-drift",
                f"entrypoint {name} fitted but has no entry in the cost "
                f"lock — {_REGEN_HINT}",
            ))
            continue
        if name not in fits:
            findings.append(Finding(
                lock_path, 1, "cost-lock-drift",
                f"entrypoint {name} is in the cost lock but no longer "
                f"cost-registered — {_REGEN_HINT}",
            ))
            continue
        locked_facts = locked_eps[name].get("facts", {})
        for fact in sorted(fits[name]):
            if fact not in locked_facts:
                findings.append(Finding(
                    lock_path, 1, "cost-lock-drift",
                    f"{name}: fact {fact} fitted but absent from the cost "
                    f"lock — {_REGEN_HINT}",
                ))
                continue
            findings.extend(compare_fact_fit(
                name, fact, fits[name][fact], locked_facts[fact],
                (lock_path, 1),
            ))
        # A locked fact the platform no longer exposes is skipped, not
        # drift (None-tolerant both ways: locks are generated where the
        # backend prices flops; a leaner backend must still gate what it
        # CAN measure).
    if "quiescent_round_cost" not in locked:
        findings.append(Finding(
            lock_path, 1, "cost-lock-drift",
            f"cost lock carries no quiescent_round_cost block — "
            f"{_REGEN_HINT}",
        ))
    else:
        findings.extend(compare_quiescent(
            quiescent, locked["quiescent_round_cost"], lock_path
        ))
    return findings


# -- tree-mode gate ----------------------------------------------------------


def check_cost_lock(trees: Sequence[Tuple[ast.AST, str]]) -> List[Finding]:
    """Tree-mode gate the driver runs on full sweeps: fit the ladder
    (session-cached compiles) and compare against the committed cost lock.
    Presence-gated on the engine sources exactly like the HLO gate, so
    retargeted test trees never pay a compile."""
    rels = {rel.replace("\\", "/") for _, rel in trees}
    if not all(src in rels for src in device_program.REGISTRY_SOURCES):
        return []
    try:
        table = collect_ladder()
        quiescent = collect_quiescent_cost()
    except RuntimeError as exc:
        return [Finding(COST_LOCK_REL, 1, "cost-lock-drift",
                        f"cannot fit the cost ladder: {exc}")]
    fits, refusals = fit_ladder(table)
    findings: List[Finding] = [
        Finding(
            COST_LOCK_REL, 1, "cost-unexplained",
            f"{name}: {fact} refused to classify — {why}; fix the fact or "
            f"widen the ladder, never guess a class",
        )
        for name, fact, why in refusals
    ]
    findings.extend(superlinear_findings(fits, (COST_LOCK_REL, 1)))
    lock_path = core.REPO / COST_LOCK_REL
    if not lock_path.exists():
        findings.append(Finding(
            COST_LOCK_REL, 1, "cost-lock-drift",
            "cost lockfile missing — generate it via "
            "`python tools/staticcheck.py --update-cost-lock`",
        ))
        return findings
    try:
        locked = json.loads(lock_path.read_text())
    except json.JSONDecodeError as exc:
        findings.append(Finding(
            COST_LOCK_REL, 1, "cost-lock-drift",
            f"cost lockfile is not valid JSON ({exc.msg}) — regenerate via "
            f"`python tools/staticcheck.py --update-cost-lock`",
        ))
        return findings
    if locked.get("ladder_config") != _ladder_config():
        findings.append(Finding(
            COST_LOCK_REL, 1, "cost-lock-drift",
            f"cost lock ladder_config {locked.get('ladder_config')} does "
            f"not match the registry's {_ladder_config()} — {_REGEN_HINT}",
        ))
        return findings
    findings.extend(compare_cost_lock(fits, quiescent, locked, COST_LOCK_REL))
    return findings


def update_cost_lock() -> Tuple[List[Finding], Optional[Path]]:
    """Regenerate the cost lockfile from freshly-fitted ladders. Refuses
    while any fit is unexplained, any fact exceeds its ceiling, or the HLO
    lock's differentials (wide<->compact, trace-on<->trace-off) disagree —
    a scaling the gate would immediately fail, or a ladder measured
    against an engine that no longer matches its own oracles, must be
    fixed, not frozen. Regeneration is byte-identical when nothing
    changed (the fit is pure deterministic arithmetic)."""
    try:
        table = collect_ladder()
        quiescent = collect_quiescent_cost()
    except RuntimeError as exc:
        return [Finding(COST_LOCK_REL, 1, "cost-lock-drift", str(exc))], None
    fits, refusals = fit_ladder(table)
    blocking: List[Finding] = [
        Finding(
            COST_LOCK_REL, 1, "cost-unexplained",
            f"refusing to freeze {name}/{fact}: {why}",
        )
        for name, fact, why in refusals
    ]
    blocking.extend(superlinear_findings(fits, (COST_LOCK_REL, 1)))
    for probe in (
        device_program.compaction_differential_ok,
        device_program.trace_differential_ok,
    ):
        mismatch = probe()
        if mismatch:
            blocking.append(
                Finding(COST_LOCK_REL, 1, "cost-lock-drift", mismatch)
            )
    if quiescent is None:
        blocking.append(Finding(
            COST_LOCK_REL, 1, "cost-quiescent",
            "refusing to freeze a cost lock without quiescent_round_cost — "
            "the sharded step was not in the collection (need the 8-device "
            "mesh)",
        ))
    if blocking:
        return blocking, None
    lock_path = core.REPO / COST_LOCK_REL
    payload = {
        "_comment": (
            "Fitted scaling classes of the registered engine entrypoints "
            "across the N/K/tenant geometry ladders: each fact's class "
            "(O(1)/O(log N)/O(N)/O(N*K)/O(N^2)), leading coefficient, fit "
            "residual, and — for shape-determined facts — the exact "
            "per-point values; plus the zero-churn quiescent_round_cost "
            "block ROADMAP item 3's sparse restructure must shrink. "
            "Generated by `python tools/staticcheck.py --update-cost-lock`; "
            "do not edit by hand — any drift from the live compiled "
            "artifacts fails the staticcheck gate."
        ),
        **fits_to_lock(fits, quiescent),
    }
    lock_path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return [], lock_path


# -- per-file mode (the seeded lint corpus) ---------------------------------


def _program_key_linenos(tree: ast.AST) -> Dict[str, int]:
    """lineno of each string key in the module's COST_AUDIT_PROGRAMS dict
    literal — where corpus findings anchor (the `# expect:` markers sit on
    these lines)."""
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "COST_AUDIT_PROGRAMS"
            and isinstance(node.value, ast.Dict)
        ):
            return {
                key.value: key.lineno
                for key in node.value.keys
                if isinstance(key, ast.Constant) and isinstance(key.value, str)
            }
    return {}


def check_cost_model(
    path: Path,
    source: Optional[str] = None,
    tree: "Optional[ast.AST]" = None,
) -> List[Finding]:
    """Corpus mode: compile the module's own miniature programs across its
    inline ladder and compare the fitted classes against its inline
    ``COST_LOCK``. A module defines ``COST_AUDIT_PROGRAMS`` (name -> a
    builder taking ``n`` and returning ``{"jit", "args", ...}``),
    ``COST_LADDER`` (the n values to sweep), and ``COST_LOCK`` (name ->
    ``{"ceiling", "facts": {fact: {"class": ...}}}``; only the facts a
    lock entry names are fitted). Modules without the registry are skipped
    outright — this check never executes ordinary library files."""
    src = source if source is not None else path.read_text()
    if "COST_AUDIT_PROGRAMS" not in src:
        return []
    if tree is None:
        tree = ast.parse(src, filename=str(path))
    linenos = _program_key_linenos(tree)
    if not linenos:
        return []
    rel = core.rel(path)
    namespace: Dict[str, Any] = {"__name__": f"_cost_corpus_{path.stem}"}
    exec(compile(src, str(path), "exec"), namespace)  # noqa: S102 — the
    # corpus is this repo's own fixture tree; per-file mode only ever runs
    # on explicitly-named files, never on sweeps.
    programs = namespace["COST_AUDIT_PROGRAMS"]
    locked = namespace.get("COST_LOCK", {})
    ladder = tuple(namespace.get("COST_LADDER", (8, 16, 32, 64)))
    c = namespace.get("AUDIT_C", 1)
    findings: List[Finding] = []
    for name, builder in programs.items():
        loc = (rel, linenos.get(name, 1))
        entry_lock = locked.get(name, {})
        fact_names = sorted(entry_lock.get("facts", {}))
        series = []
        for n in ladder:
            spec = builder(n)
            compiled, _reasons = device_program._compile_program(spec)
            entry = device_program.extract_facts(
                compiled, spec.get("donated_leaves", 0), n, c
            )
            series.append((n, entry_cost_facts(entry)))
        ceiling = entry_lock.get("ceiling", DEFAULT_CEILING)
        for fact in fact_names:
            if not all(fact in facts for _n, facts in series):
                continue
            fitted = fit_scaling(
                [((n, 1), facts[fact]) for n, facts in series],
                FACT_TOLERANCES.get(fact, DEFAULT_TOLERANCE),
            )
            if "error" in fitted:
                findings.append(Finding(
                    *loc, "cost-unexplained",
                    f"{name}: {fact} refused to classify — "
                    f"{fitted['error']}",
                ))
                continue
            if CLASS_RANK[fitted["class"]] > CLASS_RANK[ceiling]:
                findings.append(Finding(
                    *loc, "cost-superlinear",
                    f"{name}: {fact} fitted {fitted['class']} (leading "
                    f"coeff {_round_sig(fitted['coeff'], 4)}) exceeds "
                    f"the entrypoint's {ceiling} ceiling",
                ))
                continue
            findings.extend(compare_fact_fit(
                name, fact, fitted,
                entry_lock.get("facts", {}).get(fact, {}), loc,
            ))
    return sorted(set(findings), key=lambda f: (f.lineno, f.check, f.message))
