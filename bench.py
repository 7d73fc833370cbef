"""Benchmark: view-change convergence wall-clock for the TPU virtual-cluster
engine.

Scenario (BASELINE.json config 4 / BASELINE.md targets table, bottom row):
N = 100K virtual members with **5% churn** — a simultaneous join wave and
crash set — under contested conditions: 64 independently-jittered receiver
cohorts (delivery-delay skew + staggered failure detectors), the implicit-
invalidation pass live (joins in flight while DOWN alerts spread), and two
racing classic-fallback coordinators armed. Measured: wall-clock from fault
injection to the cluster converging on the final membership (every churn
event resolved through consensus — one combined UP+DOWN cut, or two
sequential cuts, depending on how the jittered deliveries interleave).
Target: < 500 ms on one TPU v5e chip.

The HEADLINE scale number is ``n1M_crash1pct_ms``: 1M members, 1% crash,
one single-dispatch convergence (ROADMAP item 1 promoted it from side
metric to first-class). It has its own ledger stage (``xl_point``) with
device-memory telemetry recorded alongside — and it is never silently
absent: the emitted JSON always carries the measured value or an explicit
``n1M_status`` marker (a CPU run exercises the full stage path at a
ramped-down N). ``RAPID_TPU_BENCH_STRETCH=10M`` opts into
the 10M stretch point (``stretch_point`` stage, ``n10M_crash1pct_ms``).

The scenario is deliberately hard enough that a CPU run cannot hide
behind it: per round it does O(C·N·K) delivery work that the TPU's VPU chews
through in microseconds.

Execution structure: ONE process. ``main()`` parses arguments, opens the
ledger and calls ``run_workload`` in-process; the process that imports jax
holds the chip. A run that finds no TPU exits nonzero — unless the caller
set ``JAX_PLATFORMS=cpu`` itself, which asks for the ramped-down CPU smoke
path of the same stages (counts and control flow, never device timings).

Observability: every run appends an append-only JSONL ledger
(rapid_tpu/utils/ledger.py; ``--ledger PATH``, default ``bench_ledger.jsonl``)
— run/stage begin+end events with durations, compile/persistent-cache stats
and device memory from the engine-telemetry tier, and provenance (git rev +
code hash over the measurement paths) — so every number in the trajectory is
attributable and a failed run points at exactly the stage it died in (render
with ``tools/perfview.py``). On success the bench emits its ONE JSON line:
{"metric", "value", "unit", "vs_baseline", ...extras}.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

_START = time.monotonic()


def _env_flag(name: str) -> bool:
    """Truthy env flag: unset, empty, '0', and 'false' all mean OFF."""
    return os.environ.get(name, "").lower() not in ("", "0", "false")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def _autotuned_lanes(n: int, env_name: str, default: int = 128) -> int:
    """Delivery-kernel tile width for an N-slot shape: the caller's env
    override if set (RAPID_TPU_BENCH_LANES for the main workload at any N —
    the capture sweep plumbs per-point widths through it — and
    RAPID_TPU_BENCH_LANES_1M for the separate XL point), else the best
    width from the newest committed autotune evidence
    (evidence/*/autotune.jsonl by mtime, written on hardware by
    examples/delivery_autotune.py) for the nearest measured shape — so a
    driver-invoked live run benefits from captured tuning with no env
    plumbing. Falls back to the default width on any gap."""
    if os.environ.get(env_name, ""):
        return _env_int(env_name, default)
    root = os.path.dirname(os.path.abspath(__file__))
    paths = glob.glob(os.path.join(root, "evidence", "*", "autotune.jsonl"))
    try:
        paths.sort(key=os.path.getmtime)  # oldest first; newest overwrites
    except OSError:
        paths.sort()
    best: dict = {}
    for path in paths:
        try:
            lines = open(path).read().splitlines()
        except OSError:
            continue
        for line in lines:
            try:
                d = json.loads(line)
                width = d.get("best_width")
                # Trust only sane hardware-measured widths.
                if d.get("platform") == "tpu" and width in (128, 256, 512, 1024):
                    best[d["shape"][1]] = width
            except (json.JSONDecodeError, KeyError, IndexError, TypeError):
                continue  # one bad line never poisons the rest
    # Only inherit a tuned width from a comparable shape: a 2K smoke run
    # must not pick up the 100K-tuned width (1024 lanes on a 2K-slot array
    # is pad-dominated). Within 4x of a measured N the tiling economics
    # carry over; among eligible shapes the closest by RATIO wins (absolute
    # distance would bias toward the largest measured shape).
    eligible = {
        shape_n: width
        for shape_n, width in best.items()
        if shape_n / 4 <= n <= shape_n * 4
    }
    if not eligible:
        return default
    nearest = min(eligible, key=lambda shape_n: max(n / shape_n, shape_n / n))
    return eligible[nearest]


def _mark(msg: str) -> None:
    """Timestamped progress line on stderr: a driver-side timeout log shows
    exactly how far the run got."""
    print(f"bench[{time.monotonic() - _START:7.1f}s] {msg}", file=sys.stderr, flush=True)


class _heartbeat:
    """Context manager emitting periodic ``_mark`` liveness lines from a
    daemon thread while a long silent stage (state build, XLA compile)
    runs, so a log cut off at a time limit says which stage was open."""

    def __init__(self, stage: str, period_s: float = 20.0) -> None:
        self._stage = stage
        self._period_s = period_s

    def __enter__(self):
        import threading

        self._stop = threading.Event()

        def beat() -> None:
            started = time.monotonic()
            while not self._stop.wait(self._period_s):
                _mark(f"{self._stage}: still running ({time.monotonic() - started:.0f}s)")

        self._thread = threading.Thread(target=beat, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=1.0)


# ---------------------------------------------------------------------------
# Derived bench metrics: pure functions, unit-audited and pinned by
# tests/test_bench_snapshot.py with plausibility bounds.
# ---------------------------------------------------------------------------


def derived_metrics(*, n: int, n_join: int, n_crash: int, k_rings: int,
                    cohorts: int, value_ms: float) -> dict:
    """Derived throughput metrics of one churn resolution.

    Units audit (the r03-r05 trajectory carried
    ``alert_deliveries_per_sec ≈ 4.96e10``, a physically implausible rate):
    the old formula multiplied every fired alert by all N members as if each
    were an independent receiver, but the engine's delivery grain is the
    COHORT — ``_deliver_alerts`` materializes one delivered-bit per
    (cohort, edge), and the ~N/C members of a cohort share that delivery.
    The honest rates are therefore:

    - ``alerts_per_sec``: fired (subject, ring) edge alerts per second —
      (joins + crashes) × K rings over the resolution wall-clock;
    - ``alert_deliveries_per_sec``: per-cohort deliveries of those alerts
      per second — alerts × C receiver cohorts over the same wall-clock
      (the BASELINE's alerts/sec axis at the engine's actual grain).
    """
    if value_ms <= 0:
        raise ValueError(f"resolution wall-clock must be positive: {value_ms}")
    alerts_fired = (n_crash + n_join) * k_rings
    seconds = value_ms / 1000.0
    return {
        "alerts_fired": alerts_fired,
        "alerts_per_sec": round(alerts_fired / seconds, 0),
        "alert_deliveries_per_sec": round(alerts_fired * cohorts / seconds, 0),
    }


#: The deployment-sizing ladder the ROADMAP's 100M question is answered
#: over: measured-validated bytes/member projected to each scale (the
#: policy re-derives per N — index lanes re-widen to int32 past 32k slots,
#: so the 10M/100M rows are honest, not a small-N extrapolation).
MEM_SIZING_SCALES = (("100k", 100_000), ("1M", 1_000_000),
                     ("10M", 10_000_000), ("100M", 100_000_000))


def memory_report(hlo_audit: dict, *, n: int, k_rings: int, cohorts: int,
                  fd_window: int = 0, use_pallas: bool = False) -> dict:
    """The bench's memory-footprint fields (ISSUE 13): bytes/member under
    the wide / compact / compact+bit-packed layouts at THIS run's geometry,
    the run's total state bytes, a 100k->100M sizing table, and a
    never-silently-absent ``mem_status``.

    ``mem_status`` is ``live:hlo-audit`` when the compiled-program audit
    measured argument bytes for both the wide and compact step entrypoints
    (memory_analysis() — the formula is then cross-checked against the
    artifact by tests/test_hlo_gate.py), else ``computed:<why>`` — the
    formula alone (exact over LANE_SPECS, which the state constructors are
    pinned against)."""
    from rapid_tpu.models.state import EngineConfig, state_bytes_per_member

    def cfg_at(n_at: int, compact: int) -> "EngineConfig":
        return EngineConfig(
            n=n_at, k=k_rings, h=9, l=4, c=min(cohorts, n_at),
            fd_window=fd_window, use_pallas=use_pallas, compact=compact,
        )

    wide_bpm = state_bytes_per_member(cfg_at(n, 0))
    compact_bpm = state_bytes_per_member(cfg_at(n, 1))
    packed_bpm = state_bytes_per_member(cfg_at(n, 1), packed=True)
    if isinstance(hlo_audit, dict) and not ("error" in hlo_audit):
        have = {
            name: entry.get("argument_bytes")
            for name, entry in hlo_audit.items()
            if isinstance(entry, dict)
        }
        if have.get("step") and have.get("step_compact"):
            mem_status = "live:hlo-audit"
        else:
            mem_status = "computed:audit-lacks-step-memory"
    else:
        reason = (
            hlo_audit.get("error", "absent") if isinstance(hlo_audit, dict)
            else "absent"
        )
        mem_status = f"computed:{reason.splitlines()[0][:80]}"
    sizing = {}
    for label, n_at in MEM_SIZING_SCALES:
        w = state_bytes_per_member(cfg_at(n_at, 0))
        c = state_bytes_per_member(cfg_at(n_at, 1))
        p = state_bytes_per_member(cfg_at(n_at, 1), packed=True)
        sizing[label] = {
            "n": n_at,
            "wide_gb": round(w * n_at / 1e9, 3),
            "compact_gb": round(c * n_at / 1e9, 3),
            "packed_gb": round(p * n_at / 1e9, 3),
            "bytes_per_member": round(c, 2),
        }
    return {
        "bytes_per_member": round(compact_bpm, 2),
        "bytes_per_member_wide": round(wide_bpm, 2),
        "bytes_per_member_packed": round(packed_bpm, 2),
        "state_bytes_total": int(compact_bpm * n),
        "mem_status": mem_status,
        "mem_sizing": sizing,
    }


def hlo_audit_summary() -> dict:
    """Per-entrypoint compiled-program facts at the fixed audit shapes
    (tools/analysis/device_program.py, session-cached): collective counts
    split hot-loop (the round loop alone since PR 47; every loop level in
    earlier rounds) vs total, payload bytes, temp memory, and donation
    outcomes — the communication-budget companion to the latency metrics,
    diffable across BENCH_r* rounds by tools/perfview.py. Any failure
    (too few devices, an import gap) degrades to ``{"error": ...}`` —
    the audit must never take down the bench that embeds it."""
    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools_dir not in sys.path:
        sys.path.append(tools_dir)
    try:
        from analysis import device_program

        # Observational mode: on a single-chip backend (the TPU v5 lite0,
        # or un-forced CPU) the four single-device entrypoints still audit;
        # the sharded pair joins whenever >= 8 devices exist. The strict
        # full-registry requirement belongs to the staticcheck GATE, not here.
        facts = device_program.collect_facts(require_mesh=False)
    except Exception as exc:  # noqa: BLE001 — strictly observational: any
        # compile/import failure reports the reason in-line instead of
        # wedging the run.
        return {"error": str(exc)}
    summary = {}
    for name, entry in sorted(facts.items()):
        colls = entry["collectives"]
        # "hot-loop/" precisely: "hot-loop-cond/*" ops are GATED (they run
        # on view changes, not every round), and lumping them in would hide
        # exactly the cond->unconditional migration the gate exists to
        # catch from perfview's drift diff.
        hot = {k: v for k, v in colls.items() if k.startswith("hot-loop/")}
        summary[name] = {
            "collectives": sum(v["count"] for v in colls.values()),
            "collective_bytes": sum(v["bytes"] for v in colls.values()),
            "hot_loop_collectives": sum(v["count"] for v in hot.values()),
            "hot_loop_bytes": sum(v["bytes"] for v in hot.values()),
            "temp_bytes": entry["memory"].get("temp_bytes"),
            # Per-device argument bytes (memory_analysis): the measured
            # side of the bytes/member story — step vs step_compact is the
            # compaction saving at the audit shape.
            "argument_bytes": entry["memory"].get("argument_bytes"),
            "donation_dropped": entry["donation"]["dropped"],
        }
    return summary


# ---------------------------------------------------------------------------
# The workload.
# ---------------------------------------------------------------------------

#: Per-stage time budgets (seconds), stamped into each stage_begin so a
#: reader of the ledger sees which stage overran. A single env override
#: (RAPID_TPU_BENCH_STAGE_TIMEOUT_S) replaces every budget for smoke runs.
STAGE_TIMEOUTS_S = {
    "devices_init": 300,
    "native_build": 300,
    "ramp": 600,
    "state_build": 900,
    "warmup_compile": 1500,
    "timed_samples": 900,
    "rtt_probe": 120,
    "xl_point": 1500,
    "stretch_point": 3000,
    "loss_variant": 900,
    "tenant_fleet": 900,
    "stream": 900,
    "chaos": 900,
    "recovery": 600,
    "hlo_audit": 600,
    "profile": 600,
}


def _stage_timeout(name: str) -> int:
    override = _env_int("RAPID_TPU_BENCH_STAGE_TIMEOUT_S", 0)
    return override if override > 0 else STAGE_TIMEOUTS_S[name]


def headline_plan(platform: str, elapsed_s: float) -> "tuple[int, str]":
    """The 1M-headline decision, pure over (platform, elapsed seconds) +
    env: returns (N to run, n1M_status). N == 0 means the point is skipped
    — but the status STILL lands in the emitted JSON, so the headline is
    never silently absent. On the accelerator (or RAPID_TPU_BENCH_XL=1)
    the point runs at the true 1M; a CPU run exercises the full stage path
    at a ramped-down N (RAPID_TPU_BENCH_XL_N, default 4096); past the XL
    time budget it is skipped-budget (a slow day must not starve the 100K
    number); RAPID_TPU_BENCH_NO_XL=1 suppresses it everywhere.
    Unit-pinned in tests/test_bench_ledger.py."""
    n_headline = 1_000_000
    if _env_flag("RAPID_TPU_BENCH_NO_XL"):
        return 0, "suppressed"
    forced = _env_flag("RAPID_TPU_BENCH_XL")
    budget_s = _env_int("RAPID_TPU_BENCH_XL_BUDGET_S", 1500)
    if elapsed_s > budget_s and not forced:
        return 0, "skipped-budget"
    if platform == "tpu" or forced:
        return n_headline, "live"
    n_ramped = _env_int("RAPID_TPU_BENCH_XL_N", 4096)
    return n_ramped, f"ramped:{n_ramped}"


def fleet_plan(platform: str, elapsed_s: float) -> "tuple[int, int, str]":
    """The multi-tenant fleet decision, pure over (platform, elapsed
    seconds) + env: returns (tenant count B, members per tenant N,
    tenant_fleet_status). B == 0 means the stage is skipped — but the
    status STILL lands in the emitted JSON, so the fleet metric is never
    silently absent (the n1M_status discipline, ISSUE 10). On the
    accelerator (or RAPID_TPU_BENCH_FLEET=1) the fleet runs at 256 tenants
    x 1024 members; a CPU run exercises the full stage path ramped down
    (RAPID_TPU_BENCH_FLEET_B/_N, default 8 x 64); past the budget
    (RAPID_TPU_BENCH_FLEET_BUDGET_S, defaulting to the XL budget) it is
    skipped-budget; RAPID_TPU_BENCH_NO_FLEET=1 suppresses it everywhere.
    Unit-pinned in tests/test_bench_ledger.py."""
    if _env_flag("RAPID_TPU_BENCH_NO_FLEET"):
        return 0, 0, "suppressed"
    forced = _env_flag("RAPID_TPU_BENCH_FLEET")
    budget_s = _env_int(
        "RAPID_TPU_BENCH_FLEET_BUDGET_S",
        _env_int("RAPID_TPU_BENCH_XL_BUDGET_S", 1500),
    )
    if elapsed_s > budget_s and not forced:
        return 0, 0, "skipped-budget"
    if platform == "tpu" or forced:
        return (
            _env_int("RAPID_TPU_BENCH_FLEET_B", 256),
            _env_int("RAPID_TPU_BENCH_FLEET_N", 1024),
            "live",
        )
    b = _env_int("RAPID_TPU_BENCH_FLEET_B", 8)
    n_t = _env_int("RAPID_TPU_BENCH_FLEET_N", 64)
    return b, n_t, f"ramped:{b}x{n_t}"


def stream_plan(platform: str, elapsed_s: float) -> "tuple[int, int, str]":
    """The streaming-serving decision, pure over (platform, elapsed
    seconds) + env: returns (waves to drive, members per cluster N,
    stream_status). waves == 0 means the stage is skipped — but the status
    STILL lands in the emitted JSON, so the sustained-throughput metrics
    are never silently absent (the n1M_status discipline). On the
    accelerator (or RAPID_TPU_BENCH_STREAM=1) the stage drives 64 waves at
    N=4096; a CPU run exercises the full pipeline ramped down
    (RAPID_TPU_BENCH_STREAM_WAVES/_N, default 12 x 96); past the budget
    (RAPID_TPU_BENCH_STREAM_BUDGET_S, defaulting to the XL budget) it is
    skipped-budget; RAPID_TPU_BENCH_NO_STREAM=1 suppresses it everywhere.
    Unit-pinned in tests/test_bench_ledger.py."""
    if _env_flag("RAPID_TPU_BENCH_NO_STREAM"):
        return 0, 0, "suppressed"
    forced = _env_flag("RAPID_TPU_BENCH_STREAM")
    budget_s = _env_int(
        "RAPID_TPU_BENCH_STREAM_BUDGET_S",
        _env_int("RAPID_TPU_BENCH_XL_BUDGET_S", 1500),
    )
    if elapsed_s > budget_s and not forced:
        return 0, 0, "skipped-budget"
    if platform == "tpu" or forced:
        return (
            _env_int("RAPID_TPU_BENCH_STREAM_WAVES", 64),
            _env_int("RAPID_TPU_BENCH_STREAM_N", 4096),
            "live",
        )
    waves = _env_int("RAPID_TPU_BENCH_STREAM_WAVES", 12)
    n_s = _env_int("RAPID_TPU_BENCH_STREAM_N", 96)
    return waves, n_s, f"ramped:{waves}x{n_s}"


def chaos_plan(platform: str, elapsed_s: float) -> "tuple[int, str]":
    """The adversarial-chaos decision, pure over (platform, elapsed
    seconds) + env: returns (fleet tenant count B, chaos_status). B == 0
    means the stage is skipped — but the status STILL lands in the emitted
    JSON, so the chaos throughput metric is never silently absent (the
    n1M_status discipline). On the accelerator (or RAPID_TPU_BENCH_CHAOS=1)
    the stage resolves 256 mixed hostile scenarios per fleet; a CPU run
    exercises the full stage path ramped down (RAPID_TPU_BENCH_CHAOS_B,
    default 12 — at least one tenant per fleet family); past the budget
    (RAPID_TPU_BENCH_CHAOS_BUDGET_S, defaulting to the XL budget) it is
    skipped-budget; RAPID_TPU_BENCH_NO_CHAOS=1 suppresses it everywhere.
    Unit-pinned in tests/test_bench_ledger.py."""
    if _env_flag("RAPID_TPU_BENCH_NO_CHAOS"):
        return 0, "suppressed"
    forced = _env_flag("RAPID_TPU_BENCH_CHAOS")
    budget_s = _env_int(
        "RAPID_TPU_BENCH_CHAOS_BUDGET_S",
        _env_int("RAPID_TPU_BENCH_XL_BUDGET_S", 1500),
    )
    if elapsed_s > budget_s and not forced:
        return 0, "skipped-budget"
    if platform == "tpu" or forced:
        return _env_int("RAPID_TPU_BENCH_CHAOS_B", 256), "live"
    from rapid_tpu.sim.fuzz import N_SLOTS

    # The ramped marker's shape is BxN: B tenants at the fuzz families'
    # shared per-tenant slot geometry (derived, so a geometry retune can't
    # leave the published status lying about what ran).
    b = _env_int("RAPID_TPU_BENCH_CHAOS_B", 12)
    return b, f"ramped:{b}x{N_SLOTS}"


def recovery_plan(platform: str, elapsed_s: float) -> "tuple[int, int, str]":
    """The self-healing drill decision (ISSUE 15), pure over (platform,
    elapsed seconds) + env: returns (members per cluster N, waves to
    stream, recovery_status). N == 0 means the stage is skipped — but the
    status STILL lands in the emitted JSON, so the MTTR metric is never
    silently absent (the n1M_status discipline). The drill: a supervised
    stream with an injected transient failure and a simulated process kill
    mid-schedule, checkpoint-cadence writes, a deterministic resume (the
    measured MTTR), and a bit-identity check against the uninterrupted
    twin. On the accelerator (or RAPID_TPU_BENCH_RECOVERY=1) it runs at
    N=4096 x 16 waves; a CPU run exercises the full drill ramped down
    (RAPID_TPU_BENCH_RECOVERY_N/_WAVES, default 64 x 6); past the budget
    (RAPID_TPU_BENCH_RECOVERY_BUDGET_S, defaulting to the XL budget) it is
    skipped-budget; RAPID_TPU_BENCH_NO_RECOVERY=1 suppresses it
    everywhere. Unit-pinned in tests/test_bench_ledger.py."""
    if _env_flag("RAPID_TPU_BENCH_NO_RECOVERY"):
        return 0, 0, "suppressed"
    forced = _env_flag("RAPID_TPU_BENCH_RECOVERY")
    budget_s = _env_int(
        "RAPID_TPU_BENCH_RECOVERY_BUDGET_S",
        _env_int("RAPID_TPU_BENCH_XL_BUDGET_S", 1500),
    )
    if elapsed_s > budget_s and not forced:
        return 0, 0, "skipped-budget"
    if platform == "tpu" or forced:
        return (
            _env_int("RAPID_TPU_BENCH_RECOVERY_N", 4096),
            _env_int("RAPID_TPU_BENCH_RECOVERY_WAVES", 16),
            "live",
        )
    n_r = _env_int("RAPID_TPU_BENCH_RECOVERY_N", 64)
    waves = _env_int("RAPID_TPU_BENCH_RECOVERY_WAVES", 6)
    return n_r, waves, f"ramped:{waves}x{n_r}"


def activity_status(stream_fields: dict, stream_status: str) -> str:
    """Device telemetry plane (ISSUE 16): the never-silently-absent status
    for the lane-derived activity numbers — "measured" when the stream
    stage actually fetched a numeric active fraction, otherwise the stage's
    own skip reason (ramped:WxN / skipped-budget / suppressed), so
    perfview's activity-missing flag only ever fires on instrumentation
    LOSS (an audited round that dropped both value and status)."""
    if isinstance(stream_fields.get("stream_active_fraction"), (int, float)):
        return "measured"
    return stream_status


def trace_status(stream_fields: dict, stream_status: str) -> str:
    """Round-trace ring (ISSUE 17): the never-silently-absent status for
    the ring-derived trajectory digest — "measured" when the stream stage
    drained a numeric rounds-to-decision p99 out of the decoded rings,
    otherwise the stage's own skip reason (ramped:WxN / skipped-budget /
    suppressed), so perfview's trace-missing flag only ever fires on
    instrumentation LOSS (an audited round that dropped both the digest
    and the status)."""
    trajectory = stream_fields.get("round_trajectory") or {}
    if isinstance(
        trajectory.get("rounds_to_decision_p99"), (int, float)
    ):
        return "measured"
    return stream_status


def _parse_scale(spec: str) -> int:
    """'10M' -> 10_000_000, '250k' -> 250_000, bare ints pass through; 0 on
    anything unparseable (the stretch point is opt-in — a typo'd env value
    must skip it loudly, never crash the whole bench)."""
    s = spec.strip().lower()
    mult = 1
    if s.endswith("m"):
        mult, s = 1_000_000, s[:-1]
    elif s.endswith("k"):
        mult, s = 1_000, s[:-1]
    try:
        return int(s) * mult
    except ValueError:
        return 0


class NoAcceleratorError(RuntimeError):
    """The run found no TPU and the caller did not ask for the CPU smoke."""


def run_workload(ledger, profile_dir=None) -> None:
    from rapid_tpu.utils.ledger import LedgerEvent

    with ledger.stage("devices_init", timeout_s=_stage_timeout("devices_init")):
        import jax

        platform = jax.devices()[0].platform
        _mark(f"devices initialized: platform={platform} count={len(jax.devices())}")
        if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
            # jax falls back to the host when it finds no accelerator; a
            # bench that followed it there would report a CPU run as if it
            # were the chip's.
            raise NoAcceleratorError(
                f"platform is {platform!r}, not 'tpu'; set JAX_PLATFORMS=cpu "
                "yourself to run the ramped-down CPU smoke of the stages"
            )
        from rapid_tpu.utils.platform import enable_compile_cache

        _mark(f"persistent compilation cache at {enable_compile_cache()}")

    import numpy as np

    from rapid_tpu.utils import engine_telemetry

    with ledger.stage("native_build", timeout_s=_stage_timeout("native_build")):
        from rapid_tpu.utils._native import ensure_built

        ensure_built()  # compile the native host library outside any event loop
        _mark("native library built")

    from rapid_tpu.models.virtual_cluster import VirtualCluster

    # N is env-overridable for smoke-testing the bench machinery itself
    # (ledger, JSON shape) at small scale; the real scenario is the 100K
    # default.
    n = _env_int("RAPID_TPU_BENCH_N", 100_000)
    churn_frac = 0.05  # BASELINE config 4: 5% churn (half joins, half crashes)
    n_join = int(n * churn_frac / 2)
    n_crash = int(n * churn_frac / 2)
    fd_threshold = 3
    k_rings = 10
    cohorts = 64
    delivery_spread = 2
    baseline_target_ms = 500.0
    max_view_changes = 4  # churn resolves in >=2 cuts; allow stragglers

    # On the chip the Mosaic delivery kernel IS the path: a compiler refusal
    # propagates with its own message. Off it the jnp core runs (the kernel
    # only interprets there).
    use_pallas = platform == "tpu"
    # Resolved once: env override or newest committed autotune evidence.
    lanes_main = _autotuned_lanes(n, "RAPID_TPU_BENCH_LANES")
    lanes_xl = _autotuned_lanes(1_000_000, "RAPID_TPU_BENCH_LANES_1M")

    # Staged N ramp: tiny engine convergences BEFORE committing to the
    # multi-minute full-N state build + compile, each its own ledger stage —
    # a broken backend dies at a cheap, named stage instead of inside the
    # long warm-up. Default: one 4K step on the accelerator, none on CPU
    # (it would pay compile time twice for no diagnostic value there).
    ramp_spec = os.environ.get(
        "RAPID_TPU_BENCH_RAMP", "4096" if platform == "tpu" else ""
    )
    for ramp_field in ramp_spec.split(","):
        if not ramp_field.strip():
            continue
        ramp_n = int(ramp_field)
        with ledger.stage("ramp", timeout_s=_stage_timeout("ramp"), n=ramp_n), \
                _heartbeat(f"ramp N={ramp_n}"):
            vcr = VirtualCluster.create(
                ramp_n, k=k_rings, h=9, l=4, cohorts=min(cohorts, ramp_n),
                fd_threshold=fd_threshold, seed=0, use_pallas=use_pallas,
                delivery_spread=delivery_spread, pallas_lanes=128,
            )
            vcr.assign_cohorts_roundrobin()
            vcr.crash(
                np.random.default_rng(0).choice(
                    ramp_n, size=max(1, ramp_n // 100), replace=False
                )
            )
            vcr.sync()
            _, ramp_decided, _, _ = vcr.run_to_decision(max_steps=96)
            _mark(f"ramp N={ramp_n}: decided={ramp_decided}")
            del vcr

    def build(seed: int, spread: int = delivery_spread, prob_permille: int = 1000):
        vc = VirtualCluster.create(
            n,
            n_slots=n + n_join,
            k=k_rings,
            h=9,
            l=4,
            cohorts=cohorts,
            fd_threshold=fd_threshold,
            seed=seed,
            use_pallas=use_pallas,
            delivery_spread=spread,
            concurrent_coordinators=2,
            delivery_prob_permille=prob_permille,
            pallas_lanes=lanes_main,
        )
        vc.assign_cohorts_roundrobin()
        rng = np.random.default_rng(seed + 1000)
        vc.stagger_fd_counts(rng, spread_rounds=3)
        victims = rng.choice(n, size=n_crash, replace=False)
        joiners = np.arange(n, n + n_join)
        vc.crash(victims)
        vc.inject_join_wave(joiners)
        return vc, victims

    def resolve_churn(vc) -> int:
        """Resolve the whole churn in ONE device dispatch: the multi-cut
        loop applies every view change on device and the observation comes
        back in one small fetch — zero per-cut round trips."""
        # min_cuts=1: joins == crashes, so the TARGET equals the starting
        # membership — at least one committed cut distinguishes "resolved"
        # from "never started".
        rounds, cuts, resolved, sizes = vc.run_until_membership(
            n, max_steps=96 * max_view_changes, max_cuts=max_view_changes,
            min_cuts=1,
        )
        assert resolved, (
            f"churn unresolved after {cuts} view changes in {rounds} rounds "
            f"(sizes {sizes})"
        )
        return cuts

    # Warm-up: compile every branch the timed run takes (convergence loop,
    # view-change application, second-cut re-entry). Heartbeat throughout:
    # state build + compile is the longest mark-silent stretch of the run.
    with ledger.stage("state_build", timeout_s=_stage_timeout("state_build"), n=n):
        with _heartbeat(f"N={n} state build"):
            vc, _ = build(seed=0)
            vc.sync()
    _mark(f"N={n} state built and on device; compiling engine (warm-up run)")
    with ledger.stage("warmup_compile", timeout_s=_stage_timeout("warmup_compile"), n=n):
        with engine_telemetry.CompileDelta() as warmup_compiles:
            with _heartbeat(f"N={n} warm-up compile"):
                resolve_churn(vc)
    ledger.emit(LedgerEvent.COMPILE_STATS, stage="warmup_compile",
                **warmup_compiles.delta)
    ledger.emit(LedgerEvent.DEVICE_MEMORY, stage="warmup_compile",
                **engine_telemetry.device_memory_snapshot())
    _mark("warm-up convergence done (executables cached)")

    # Timed runs on fresh state (same shapes -> cached executables).
    samples = []
    cuts_per_sample = []
    with ledger.stage("timed_samples", timeout_s=_stage_timeout("timed_samples"), n=n):
        for rep in range(3):
            vc, victims = build(seed=rep)
            # State upload/init must complete before the clock starts.
            vc.sync()
            start = time.perf_counter()
            cuts = resolve_churn(vc)
            elapsed_ms = (time.perf_counter() - start) * 1000.0
            # resolve_churn's membership_size reads are scalar fetches — the
            # clock stops after real device completion.
            assert vc.membership_size == n
            assert not vc.alive_mask[victims].any()
            assert vc.alive_mask[n : n + n_join].all()
            samples.append(elapsed_ms)
            cuts_per_sample.append(cuts)
            _mark(f"sample {rep + 1}/3: {elapsed_ms:.1f} ms ({cuts} view changes)")

    # Fixed cost of one dispatch + scalar fetch in this environment: the
    # floor under every number above that ends in a host read.
    import jax.numpy as jnp

    with ledger.stage("rtt_probe", timeout_s=_stage_timeout("rtt_probe")):
        probe = jax.jit(lambda a: a + 1)
        int(probe(jnp.int32(1)))
        t0 = time.perf_counter()
        int(probe(jnp.int32(2)))
        rtt_ms = (time.perf_counter() - t0) * 1000.0

    # The crash-1% scale-point family: the 1M-member HEADLINE metric
    # (n1M_crash1pct_ms — ROADMAP item 1 promoted it from side metric to
    # the first-class scale number) and the opt-in 10M stretch point. One
    # measurement recipe per point: fresh state, warm-up compile, fresh
    # state again, one timed single-dispatch convergence — its own ledger
    # stage, per-device memory from the engine-telemetry tier recorded
    # alongside.
    def crash1pct_point(stage: str, n_point: int, lanes_point: int):
        # The bracketing ledger stage is opened by the CALLER with a literal
        # name (the ledger lint's vocabulary rule); ``stage`` here only
        # labels marks and the returned telemetry.
        cohorts_point = min(8, n_point)
        n_crash_point = max(1, n_point // 100)

        def build_point(seed: int):
            vcx = VirtualCluster.create(
                n_point,
                k=10,
                h=9,
                l=4,
                cohorts=cohorts_point,
                fd_threshold=fd_threshold,
                seed=seed,
                use_pallas=use_pallas,
                delivery_spread=delivery_spread,
                pallas_lanes=lanes_point,
            )
            vcx.assign_cohorts_roundrobin()
            vcx.crash(
                np.random.default_rng(seed).choice(
                    n_point, size=n_crash_point, replace=False
                )
            )
            return vcx

        with _heartbeat(f"{stage} N={n_point} state build"):
            vcx = build_point(7)
            vcx.sync()
        _mark(f"{stage}: N={n_point} state on device; compiling (warm-up)")
        with engine_telemetry.CompileDelta() as point_compiles:
            with _heartbeat(f"{stage} warm-up compile"):
                vcx.run_to_decision(max_steps=96)  # warm-up/compile
        vcx = build_point(8)
        vcx.sync()
        t0 = time.perf_counter()
        _, decided_pt, _, _ = vcx.run_to_decision(max_steps=96)
        point_ms = (time.perf_counter() - t0) * 1000.0
        assert decided_pt and vcx.membership_size == n_point - n_crash_point
        _mark(f"{stage}: N={n_point} crash1pct {point_ms:.1f} ms")
        return point_ms, point_compiles.delta, engine_telemetry.device_memory_snapshot()

    # Headline policy (headline_plan, pure + unit-pinned) — the point is
    # NEVER silently absent: the emitted JSON always carries either the
    # measured 1M number or an explicit n1M_status marker.
    n_headline = 1_000_000
    xl_ms = None
    xl_memory = None
    xl_n, xl_status = headline_plan(platform, time.monotonic() - _START)
    if xl_n == 0:
        _mark(f"headline 1M point not run: {xl_status}")
    else:
        with ledger.stage("xl_point", timeout_s=_stage_timeout("xl_point"), n=xl_n):
            xl_ms, xl_compiles, xl_memory = crash1pct_point(
                "xl_point", xl_n, lanes_xl if xl_n >= n_headline else 128
            )
        ledger.emit(LedgerEvent.COMPILE_STATS, stage="xl_point", **xl_compiles)
        ledger.emit(LedgerEvent.DEVICE_MEMORY, stage="xl_point", **xl_memory)

    # The 10M stretch point, strictly opt-in: RAPID_TPU_BENCH_STRETCH=10M
    # (any <int>[M|k] spec works — a small value exercises the stage on
    # CPU). Its own registered ledger stage.
    stretch_ms = None
    stretch_n = None
    stretch_spec = os.environ.get("RAPID_TPU_BENCH_STRETCH", "")
    if stretch_spec:
        stretch_n = _parse_scale(stretch_spec)
        if stretch_n <= 0:
            _mark(f"unparseable RAPID_TPU_BENCH_STRETCH={stretch_spec!r}; skipping")
            stretch_n = None
        else:
            with ledger.stage(
                "stretch_point",
                timeout_s=_stage_timeout("stretch_point"),
                n=stretch_n,
            ):
                stretch_ms, stretch_compiles, stretch_memory = crash1pct_point(
                    "stretch_point",
                    stretch_n,
                    lanes_xl if stretch_n >= n_headline else 128,
                )
            ledger.emit(LedgerEvent.COMPILE_STATS, stage="stretch_point",
                        **stretch_compiles)
            ledger.emit(LedgerEvent.DEVICE_MEMORY, stage="stretch_point",
                        **stretch_memory)

    # Adverse-network variant: the SAME churn resolved under the chaos
    # subsystem's churn_under_loss fault schedule (rapid_tpu/sim) — its 5%
    # symmetric loss compiled onto the engine's delivery knobs by the shared
    # definition (sim/faults.loss_as_engine_delivery: a lost broadcast is a
    # delivery delayed into the redelivery horizon). This is the perf
    # trajectory's first adverse-network axis: resolution latency under
    # loss, not just clean-network. Skipped past the XL budget like the 1M
    # point (a slow day must not starve the headline number).
    from rapid_tpu.sim.faults import loss_as_engine_delivery
    from rapid_tpu.sim.fuzz import churn_under_loss

    loss_ms = None
    loss_permille = max(
        int(e.args["permille"])
        for e in churn_under_loss(0).events
        if e.kind == "loss"
    )
    loss_knobs = loss_as_engine_delivery(loss_permille)
    loss_budget_s = _env_int("RAPID_TPU_BENCH_XL_BUDGET_S", 1500)
    if _env_flag("RAPID_TPU_BENCH_NO_LOSS"):
        # Operator knob (sweeps, smoke runs): drop the adverse-network
        # variant without touching the shared XL budget that also gates
        # the headline point.
        _mark("skipping churn_under_loss variant: RAPID_TPU_BENCH_NO_LOSS")
    elif time.monotonic() - _START <= loss_budget_s:
        with ledger.stage("loss_variant", timeout_s=_stage_timeout("loss_variant"), n=n):
            vc, _ = build(
                seed=100,
                spread=loss_knobs["delivery_spread"],
                prob_permille=loss_knobs["delivery_prob_permille"],
            )
            vc.sync()
            _mark(f"loss variant ({loss_permille} permille): compiling (warm-up)")
            with _heartbeat("loss-variant warm-up compile"):
                resolve_churn(vc)
            loss_samples = []
            for rep in range(2):
                vc, victims = build(
                    seed=101 + rep,
                    spread=loss_knobs["delivery_spread"],
                    prob_permille=loss_knobs["delivery_prob_permille"],
                )
                vc.sync()
                t0 = time.perf_counter()
                cuts = resolve_churn(vc)
                loss_samples.append((time.perf_counter() - t0) * 1000.0)
                assert vc.membership_size == n and not vc.alive_mask[victims].any()
                _mark(
                    f"loss sample {rep + 1}/2: {loss_samples[-1]:.1f} ms ({cuts} view changes)"
                )
            loss_ms = min(loss_samples)
    else:
        _mark("skipping churn_under_loss variant: past the XL time budget")

    # Multi-tenant fleet point (ISSUE 10 / ROADMAP item 4): B independent
    # clusters — a MIXED bag of scenario families (crash wave, join wave,
    # equal-churn) with independent seeds and per-tenant H/L knobs —
    # resolved in ONE lockstep fleet-wave dispatch (rapid_tpu/tenancy).
    # The metric is tenant_view_changes_per_sec: total view changes
    # committed across the fleet over the wall clock of the single
    # dispatch. Never silently absent: tenant_fleet_status always lands in
    # the emitted JSON (the n1M_status discipline); CPU runs exercise the
    # stage ramped-down.
    fleet_b, fleet_n, fleet_status = fleet_plan(
        platform, time.monotonic() - _START
    )
    fleet_vcps = None
    fleet_cuts_total = None
    fleet_wall_ms = None
    fleet_memory = None
    fleet_activity = None
    fleet_conflict_rates = None
    if fleet_b == 0:
        _mark(f"tenant fleet stage not run: {fleet_status}")
    else:
        from rapid_tpu.tenancy import TenantFleet

        fleet_max_steps = 96  # fixed lockstep recipe: the metric divides by
        # the wall clock of exactly this many batched rounds

        def build_fleet(seed0: int):
            """B tenants cycling three scenario families, per-tenant knob
            mix, independent seeds; returns (fleet, targets, min_cuts)."""
            n_extra = max(2, fleet_n // 50)
            clusters, targets = [], []
            for i in range(fleet_b):
                h, l = ((9, 4), (8, 3))[i % 2]
                vc = VirtualCluster.create(
                    fleet_n, n_slots=fleet_n + n_extra, k=k_rings, h=h, l=l,
                    cohorts=min(8, fleet_n), fd_threshold=fd_threshold,
                    seed=seed0 + i, delivery_spread=delivery_spread,
                    telemetry=True,
                )
                vc.assign_cohorts_roundrobin()
                rng = np.random.default_rng(seed0 + 10_000 + i)
                vc.stagger_fd_counts(rng, spread_rounds=3)
                family = i % 3
                if family == 0:  # crash wave
                    vc.crash(rng.choice(fleet_n, size=n_extra, replace=False))
                    targets.append(fleet_n - n_extra)
                elif family == 1:  # join wave
                    vc.inject_join_wave(
                        np.arange(fleet_n, fleet_n + n_extra)
                    )
                    targets.append(fleet_n + n_extra)
                else:  # equal churn: joins == crashes, target == start —
                    # min_cuts=1 below is what distinguishes "resolved"
                    # from "never started" for these tenants
                    vc.crash(rng.choice(fleet_n, size=n_extra, replace=False))
                    vc.inject_join_wave(
                        np.arange(fleet_n, fleet_n + n_extra)
                    )
                    targets.append(fleet_n)
                clusters.append(vc)
            return TenantFleet.from_clusters(clusters), targets

        with ledger.stage(
            "tenant_fleet", timeout_s=_stage_timeout("tenant_fleet"),
            n=fleet_b * fleet_n,
        ):
            with _heartbeat(f"tenant_fleet B={fleet_b} N={fleet_n} warm-up"):
                with engine_telemetry.CompileDelta() as fleet_compiles:
                    fleet, targets = build_fleet(seed0=50_000)
                    fleet.sync()
                    fleet.run_until_membership(
                        targets, max_steps=fleet_max_steps, max_cuts=4,
                        min_cuts=1,
                    )
            fleet, targets = build_fleet(seed0=60_000)
            fleet.sync()
            t0 = time.perf_counter()
            _, cuts, resolved, _ = fleet.run_until_membership(
                targets, max_steps=fleet_max_steps, max_cuts=4, min_cuts=1,
            )
            fleet_wall_ms = (time.perf_counter() - t0) * 1000.0
            assert resolved.all(), (
                f"fleet tenants unresolved: {np.nonzero(~resolved)[0].tolist()}"
            )
            fleet_cuts_total = int(cuts.sum())
            fleet_vcps = fleet_cuts_total / (fleet_wall_ms / 1000.0)
            # Device telemetry plane (ISSUE 16): the per-tenant conflict
            # rates from the fleet's lanes — the sync boundary below is
            # what refreshes the host cache (timing already captured).
            fleet.sync()
            fleet_activity = fleet.activity
            fleet_conflict_rates = [
                round(a["conflict_rate"], 6) for a in fleet.tenant_activity
            ]
            fleet_memory = engine_telemetry.device_memory_snapshot()
            _mark(
                f"tenant_fleet: {fleet_b} tenants x {fleet_n} members, "
                f"{fleet_cuts_total} view changes in {fleet_wall_ms:.1f} ms "
                f"({fleet_vcps:.1f}/s)"
            )
        ledger.emit(LedgerEvent.COMPILE_STATS, stage="tenant_fleet",
                    **fleet_compiles.delta)
        ledger.emit(LedgerEvent.DEVICE_MEMORY, stage="tenant_fleet",
                    **fleet_memory)

    # Streaming serving point (ISSUE 11 / ROADMAP item 4): sustained
    # throughput under CONTINUOUS Poisson churn through the pipelined
    # dispatch path (rapid_tpu/serving) — per-wave fault deltas double-
    # buffered against in-flight dispatches, host sync only at explicit
    # fetch boundaries. Both serving paths stream: the single cluster
    # (crash+join churn) and the tenant fleet (per-tenant crash streams).
    # The emitted numbers are the ones a serving system publishes —
    # sustained view-changes/sec, p99 alert->commit latency, and the
    # overlap-efficiency ratio (1 - host-fetch-blocked/wall, computed from
    # the stream_fetch dispatch-phase histogram the dashboards also
    # render). Never silently absent: stream_status always lands in the
    # emitted JSON (the n1M_status discipline).
    stream_waves, stream_n, stream_status = stream_plan(
        platform, time.monotonic() - _START
    )
    stream_fields = {}
    stream_memory = None
    if stream_waves == 0:
        _mark(f"stream stage not run: {stream_status}")
    else:
        from rapid_tpu.serving import (
            FleetPoissonChurn, PoissonChurn, StreamDriver,
        )
        from rapid_tpu.tenancy import TenantFleet
        from rapid_tpu.utils.histogram import LogHistogram as _StreamHist

        stream_b = 4  # fleet-path tenants: enough to exercise the stacked pipe
        rounds_per_wave = _env_int("RAPID_TPU_BENCH_STREAM_ROUNDS", 8)
        # Round-trace ring capacity (ISSUE 17): sized to the whole stage by
        # default so every wave's span survives to the drain decode (the
        # trajectory quantiles cover all waves, waves_evicted == 0); a
        # smaller override exercises the eviction accounting instead.
        stream_trace_r = _env_int(
            "RAPID_TPU_BENCH_TRACE_R", stream_waves * rounds_per_wave
        )
        # Fresh-slot headroom for the join half of the churn: the generator
        # never reuses a slot (the engine's UUID discipline), so the slot
        # table must hold every joiner the whole stream can admit.
        stream_slots = stream_n + 2 * stream_waves

        def build_stream_cluster(seed: int):
            # telemetry=True: the stream stage is where the device telemetry
            # plane's activity numbers come from (ISSUE 16) — the lanes ride
            # the same donated dispatches and the digest is fetched only at
            # the drain boundary, so the measured overlap is unchanged.
            # trace=R: the ring rides the same donated dispatches and is
            # decoded from the drain-boundary digest fetch — the measured
            # overlap is unchanged (trace-on/off bit-identity is pinned in
            # tests/test_trace_ring.py).
            vcs = VirtualCluster.create(
                stream_n, n_slots=stream_slots, k=k_rings, h=9, l=4,
                cohorts=min(8, stream_n), fd_threshold=fd_threshold,
                seed=seed, delivery_spread=delivery_spread, telemetry=True,
                trace=stream_trace_r,
            )
            vcs.assign_cohorts_roundrobin()
            return vcs

        def build_stream_fleet(seed0: int):
            clusters = []
            for i in range(stream_b):
                vcs = VirtualCluster.create(
                    stream_n, k=k_rings, h=9, l=4,
                    cohorts=min(8, stream_n), fd_threshold=fd_threshold,
                    seed=seed0 + i, delivery_spread=delivery_spread,
                    telemetry=True, trace=stream_trace_r,
                )
                vcs.assign_cohorts_roundrobin()
                clusters.append(vcs)
            return TenantFleet.from_clusters(clusters)

        with ledger.stage(
            "stream", timeout_s=_stage_timeout("stream"),
            n=stream_waves * rounds_per_wave,
        ):
            with _heartbeat(f"stream warm-up N={stream_n}"):
                with engine_telemetry.CompileDelta() as stream_compiles:
                    # Warm the compiled programs the stream enqueues —
                    # engine_step at the cluster shape, fleet_step at the
                    # stacked shape, AND the churn-injection programs
                    # (crash scatter, predecessor_of_keys + the join
                    # scatters) — so the timed stream measures dispatch
                    # overlap, not XLA compiles. Per-delta-SIZE shapes
                    # (a 2-crash wave, a 3-join wave) still compile fresh
                    # mid-stream; stream_mid_stream_compiles below keeps
                    # that residual pollution observable instead of
                    # pretending it away.
                    warm = build_stream_cluster(seed=7_000)
                    warm.crash([0])
                    warm.inject_join_wave([stream_n])
                    warm.step()
                    warm.sync()
                    warm_fleet = build_stream_fleet(seed0=7_100)
                    warm_fleet.stream_crash([(0, 1)])
                    warm_fleet.step()
                    warm_fleet.sync()
                    del warm, warm_fleet
            with engine_telemetry.CompileDelta() as stream_mid:
                # Single-cluster path: seeded Poisson crash+join churn,
                # waves pipelined `depth` deep behind in-flight dispatches.
                vcs = build_stream_cluster(seed=7_200)
                vcs.sync()
                stream_driver = StreamDriver(
                    vcs, rounds_per_wave=rounds_per_wave, depth=2
                )
                for wave in PoissonChurn(
                    stream_n, stream_slots, rate=2.0, seed=7_300
                ).waves(stream_waves):
                    stream_driver.submit(wave)
                cluster_stream = stream_driver.drain()
                _mark(
                    f"stream cluster: {cluster_stream.cuts} view changes over "
                    f"{cluster_stream.waves} waves in {cluster_stream.wall_ms:.1f} ms "
                    f"(overlap {cluster_stream.overlap_efficiency})"
                )
                # Fleet path: the same pipeline over the stacked engine.
                fleet_s = build_stream_fleet(seed0=7_400)
                fleet_s.sync()
                fleet_stream_driver = StreamDriver(
                    fleet_s, rounds_per_wave=rounds_per_wave, depth=2
                )
                for wave in FleetPoissonChurn(
                    stream_b, stream_n, rate=0.5, seed=7_500
                ).waves(stream_waves):
                    fleet_stream_driver.submit(wave)
                fleet_stream = fleet_stream_driver.drain()
                _mark(
                    f"stream fleet: {fleet_stream.cuts} view changes over "
                    f"{fleet_stream.waves} waves in {fleet_stream.wall_ms:.1f} ms"
                )
            # Combined sustained metrics over BOTH paths: total committed
            # view changes over total stream wall clock, p99 over the
            # merged alert->commit histograms, overlap over the summed
            # fetch-blocked time (all three checkable from the per-target
            # telemetry scrapes).
            wall_ms_total = cluster_stream.wall_ms + fleet_stream.wall_ms
            cuts_total = cluster_stream.cuts + fleet_stream.cuts
            fetch_ms_total = (
                cluster_stream.fetch_blocked_ms + fleet_stream.fetch_blocked_ms
            )
            merged_latency = _StreamHist.merged(
                hist for target in (vcs, fleet_s)
                if (hist := target.metrics.timings.get(
                    "engine_stream_alert_to_commit"
                )) is not None
            )
            stream_fields = {
                "stream_view_changes_per_sec": (
                    round(cuts_total / (wall_ms_total / 1000.0), 2)
                    if wall_ms_total > 0 else None
                ),
                "stream_p99_alert_to_commit_ms": (
                    round(float(merged_latency.quantile(0.99)), 3)
                    if merged_latency.count else None
                ),
                "stream_overlap_efficiency": (
                    round(max(0.0, min(1.0, 1.0 - fetch_ms_total / wall_ms_total)), 4)
                    if wall_ms_total > 0 else None
                ),
                "stream_waves": stream_waves,
                "stream_rounds_per_wave": rounds_per_wave,
                "stream_n": stream_n,
                "stream_fleet_tenants": stream_b,
                "stream_view_changes": cuts_total,
                "stream_wall_ms": round(wall_ms_total, 3),
                # Always floats post-drain (0.0 on degenerate streams —
                # the ISSUE-15 rate-math contract), never None.
                "stream_cluster_view_changes_per_sec": round(
                    cluster_stream.view_changes_per_sec, 2
                ),
                "stream_fleet_view_changes_per_sec": round(
                    fleet_stream.view_changes_per_sec, 2
                ),
                "stream_h2d_bytes": cluster_stream.h2d_bytes + fleet_stream.h2d_bytes,
                # Compiles that landed INSIDE the timed stream (per-delta-
                # size scatter shapes the warm-up cannot enumerate): the
                # reader's gauge for how much of wall_ms/p99 is compile
                # pollution rather than dispatch overlap.
                "stream_mid_stream_compiles": stream_mid.delta.get("compiles", 0),
                "stream_mid_stream_compile_ms": stream_mid.delta.get(
                    "compile_ms", 0.0
                ),
            }
            # Device telemetry plane (ISSUE 16): the activity numbers from
            # BOTH serving paths' lanes, refreshed by the drains above. The
            # two paths run different slot-table geometries, so the mean
            # active fraction is rounds-weighted over per-engine fractions
            # rather than pooled over raw counters.
            activity_summaries = [
                a for a in (
                    vcs.activity, *(fleet_s.tenant_activity or ())
                ) if a is not None
            ]
            activity_rounds = sum(s["rounds"] for s in activity_summaries)
            decisions_fast = sum(
                s["decisions_fast"] for s in activity_summaries
            )
            decisions_total = decisions_fast + sum(
                s["decisions_classic"] for s in activity_summaries
            )
            if activity_rounds:
                stream_fields.update({
                    "stream_active_fraction": round(
                        sum(
                            s["active_fraction"] * s["rounds"]
                            for s in activity_summaries
                        ) / activity_rounds, 6,
                    ),
                    "stream_peak_active_fraction": round(
                        max(
                            s["peak_active_fraction"]
                            for s in activity_summaries
                        ), 6,
                    ),
                    "stream_fast_path_share": round(
                        decisions_fast / decisions_total, 4,
                    ) if decisions_total else 0.0,
                })
            # Round-trace ring digest (ISSUE 17): per-wave rounds-to-
            # decision quantiles and the active-trajectory p99, decoded
            # from BOTH serving paths' rings at their drain boundaries
            # (StreamDriver.last_trajectory — pure host arithmetic over
            # the one drain-time digest fetch). The headline numbers take
            # the WORST path (a serving p99 is the slowest story told).
            trajectories = {
                "cluster": stream_driver.last_trajectory,
                "fleet": fleet_stream_driver.last_trajectory,
            }
            drained = [t for t in trajectories.values() if t]

            def _worst(key):
                vals = [
                    t[key] for t in drained
                    if isinstance(t.get(key), (int, float))
                ]
                return max(vals) if vals else None

            stream_fields["round_trajectory"] = {
                "trace_capacity": stream_trace_r,
                "rounds_to_decision_p50": _worst("rounds_to_decision_p50"),
                "rounds_to_decision_p99": _worst("rounds_to_decision_p99"),
                "rounds_to_decision_max": _worst("rounds_to_decision_max"),
                "active_p99": _worst("active_p99"),
                "waves_evicted": sum(
                    t.get("waves_evicted") or 0 for t in drained
                ),
                **trajectories,
            }
            # Zero-churn stability soak: a quiet engine must READ zero —
            # published explicitly (0.0 is a measurement, not an absence;
            # perfview's activity-missing flag polices exactly this).
            quiet = build_stream_cluster(seed=7_600)
            for _ in range(rounds_per_wave):
                quiet.step()
            quiet.sync()
            stream_fields["quiescent_active_fraction"] = float(
                quiet.activity["active_fraction"]
            )
            del quiet
            stream_memory = engine_telemetry.device_memory_snapshot()
            _mark(
                f"stream: {cuts_total} view changes in {wall_ms_total:.1f} ms "
                f"({stream_fields['stream_view_changes_per_sec']}/s, overlap "
                f"{stream_fields['stream_overlap_efficiency']})"
            )
        ledger.emit(LedgerEvent.COMPILE_STATS, stage="stream",
                    **stream_compiles.delta)
        ledger.emit(LedgerEvent.DEVICE_MEMORY, stage="stream",
                    **stream_memory)

    # Adversarial-chaos point (ISSUE 12): B mixed hostile scenarios —
    # Byzantine false alerts against the H/L watermarks, committee crashes
    # inside the hier reconfiguration window, plus the honest families —
    # compiled per tenant and resolved in batched fleet-wave dispatches
    # with the stability soak (rapid_tpu/tenancy/chaos.py). The metric is
    # chaos_scenarios_per_sec: scenarios resolved (and oracle-checked
    # clean) per second of fleet dispatch. Never silently absent:
    # chaos_status always lands in the emitted JSON (the n1M_status
    # discipline); CPU runs exercise the stage ramped-down.
    chaos_b, chaos_status = chaos_plan(platform, time.monotonic() - _START)
    chaos_fields = {}
    if chaos_b == 0:
        _mark(f"chaos stage not run: {chaos_status}")
    else:
        from rapid_tpu.tenancy import chaos as tchaos

        with ledger.stage("chaos", timeout_s=_stage_timeout("chaos"), n=chaos_b):
            with _heartbeat(f"chaos fleet B={chaos_b} warm-up"):
                with engine_telemetry.CompileDelta() as chaos_compiles:
                    # Warm the batched wave/step executables at the exact
                    # [B, geometry] shape, so the timed round measures
                    # dispatch throughput, not XLA compiles.
                    tchaos.fuzz_fleet(
                        chaos_b, base_seed=70_000, shrink_failures=False
                    )
            chaos_summary = tchaos.fuzz_fleet(
                chaos_b, base_seed=71_000, shrink_failures=False
            )
            assert not chaos_summary["violations"], (
                "chaos fleet violations:\n"
                + "\n".join(chaos_summary["violations"])
            )
            chaos_fields = {
                "chaos_scenarios_per_sec": chaos_summary["scenarios_per_sec"],
                "chaos_tenants": chaos_b,
                "chaos_dispatches": chaos_summary["dispatches"],
                "chaos_view_changes": chaos_summary["total_cuts"],
                "chaos_wall_ms": chaos_summary["wall_ms"],
                "chaos_families": len(chaos_summary["families"]),
            }
            _mark(
                f"chaos: {chaos_b} hostile scenarios over "
                f"{len(chaos_summary['families'])} families in "
                f"{chaos_summary['wall_ms']:.1f} ms "
                f"({chaos_summary['scenarios_per_sec']:.1f} scenarios/s), "
                f"0 violations"
            )
        ledger.emit(LedgerEvent.COMPILE_STATS, stage="chaos",
                    **chaos_compiles.delta)

    # Self-healing drill (ISSUE 15): a supervised stream with an injected
    # transient dispatch failure and a simulated process kill mid-schedule;
    # the supervisor retries on seeded backoff, writes checkpoint-cadence
    # fleet checkpoints, and the drill resumes from the newest valid one —
    # the measured resume duration is recovery_mttr_ms, and the resumed
    # run's final state must be BIT-IDENTICAL to an uninterrupted twin
    # (asserted, not assumed). Never silently absent: recovery_status
    # always lands in the emitted JSON (the n1M_status discipline).
    recovery_n, recovery_waves, recovery_status = recovery_plan(
        platform, time.monotonic() - _START
    )
    recovery_fields = {}
    if recovery_n == 0:
        _mark(f"recovery stage not run: {recovery_status}")
    else:
        import tempfile

        from rapid_tpu.serving import (
            PoissonChurn as _RecChurn,
            SimulatedProcessKill,
            Supervisor,
            SupervisorFaultPlan,
            recovery as serving_recovery,
        )

        rec_rounds = _env_int("RAPID_TPU_BENCH_RECOVERY_ROUNDS", 4)
        rec_slots = recovery_n + 2 * recovery_waves
        rec_kill_after = recovery_waves // 2
        rec_every = max(1, recovery_waves // 3)

        def build_recovery_cluster(seed: int):
            vcr = VirtualCluster.create(
                recovery_n, n_slots=rec_slots, k=k_rings, h=9, l=4,
                cohorts=min(8, recovery_n), fd_threshold=fd_threshold,
                seed=seed, delivery_spread=delivery_spread,
            )
            vcr.assign_cohorts_roundrobin()
            return vcr

        with ledger.stage(
            "recovery", timeout_s=_stage_timeout("recovery"),
            n=recovery_n,
        ):
            with _heartbeat(f"recovery drill N={recovery_n}"):
                # Uninterrupted twin: the bit-identity oracle.
                twin = build_recovery_cluster(seed=8_000)
                twin_sup = Supervisor(twin, rounds_per_wave=rec_rounds)
                for wave in _RecChurn(
                    recovery_n, rec_slots, rate=2.0, seed=8_100
                ).waves(recovery_waves):
                    twin_sup.submit(wave)
                twin_sup.drain()
                # The drill: transient failure at wave 1, kill mid-schedule.
                ckpt_dir = tempfile.mkdtemp(prefix="rapid-recovery-")
                drill = build_recovery_cluster(seed=8_000)
                drill_sup = Supervisor(
                    drill, rounds_per_wave=rec_rounds,
                    checkpoint_dir=ckpt_dir, checkpoint_every=rec_every,
                    fault_plan=SupervisorFaultPlan(
                        transient_submit=((1, 1),),
                        kill_after_wave=rec_kill_after,
                    ),
                    ledger=ledger, ledger_stage="recovery",
                )
                churn = _RecChurn(recovery_n, rec_slots, rate=2.0, seed=8_100)
                killed_at = None
                try:
                    for wave_idx in range(recovery_waves):
                        drill_sup.submit(churn.wave())
                except SimulatedProcessKill as exc:
                    killed_at = exc.wave_index
                assert killed_at is not None, "drill kill never fired"
                t_rec = time.monotonic()
                resumed_sup, next_wave = serving_recovery.resume(
                    ckpt_dir, checkpoint_every=rec_every,
                    ledger=ledger, ledger_stage="recovery",
                )
                churn2 = serving_recovery.fast_forward(
                    _RecChurn(recovery_n, rec_slots, rate=2.0, seed=8_100),
                    next_wave,
                )
                for wave_idx in range(next_wave, recovery_waves):
                    resumed_sup.submit(churn2.wave())
                resumed = resumed_sup.drain()
                mttr_ms = resumed_sup.last_resume_ms
                resume_to_serving_ms = (time.monotonic() - t_rec) * 1000.0
                import jax as _jax

                bit_identical = bool(_jax.tree_util.tree_all(
                    _jax.tree_util.tree_map(
                        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
                        resumed_sup.target.state, twin.state,
                    )
                )) and resumed_sup.target.config_id == twin.config_id
                assert bit_identical, (
                    "resumed drill diverged from the uninterrupted twin"
                )
            recovery_fields = {
                "recovery_mttr_ms": round(mttr_ms, 3),
                "recovery_resume_to_serving_ms": round(
                    resume_to_serving_ms, 3
                ),
                "recovery_killed_after_wave": killed_at,
                "recovery_resumed_wave": next_wave,
                "recovery_waves": recovery_waves,
                "recovery_n": recovery_n,
                "recovery_checkpoints": int(
                    drill.metrics.counters.get("engine_recovery_checkpoints", 0)
                ),
                "recovery_retries": int(
                    drill.metrics.counters.get("engine_recovery_retries", 0)
                ),
                "recovery_replayed_cuts": resumed.cuts,
                "recovery_bit_identical": bit_identical,
            }
            _mark(
                f"recovery: killed after wave {killed_at}, resumed at wave "
                f"{next_wave} in {mttr_ms:.1f} ms (serving again in "
                f"{resume_to_serving_ms:.1f} ms), final state bit-identical"
            )

    # Compiled-program audit (ISSUE 8, analysis family 12): compile the
    # registered engine entrypoints at the fixed audit shapes ON THIS
    # PLATFORM and embed the per-entrypoint collective/memory table, so the
    # BENCH_r* trajectory carries the communication budget alongside the
    # latency numbers and tools/perfview.py can flag collective-count
    # drift between rounds. On TPU this is the first compiled-collective
    # evidence per round; the staticcheck GATE (CPU-pinned) stays in the test
    # session — here the facts are recorded, not judged.
    with ledger.stage("hlo_audit", timeout_s=_stage_timeout("hlo_audit")):
        with _heartbeat("hlo audit compile"):
            hlo_audit = hlo_audit_summary()
        if "error" in hlo_audit:
            _mark(f"hlo audit unavailable: {hlo_audit['error']}")
        else:
            _mark(f"hlo audit: {len(hlo_audit)} entrypoints compiled")
        # Memory-footprint fields (ISSUE 13): bytes/member at this run's
        # geometry + the 100k->100M sizing table, status-stamped from the
        # audit's memory_analysis — never silently absent.
        mem_fields = memory_report(
            hlo_audit, n=n, k_rings=k_rings, cohorts=cohorts,
            use_pallas=use_pallas,
        )
        _mark(
            f"memory: {mem_fields['bytes_per_member']:.0f} B/member compact "
            f"vs {mem_fields['bytes_per_member_wide']:.0f} wide "
            f"({mem_fields['mem_status']}); 100M sizing "
            f"{mem_fields['mem_sizing']['100M']['compact_gb']:.0f} GB"
        )

    # Opt-in jax.profiler capture (--profile DIR): one extra resolved churn
    # under utils/profiling.trace, as its own budgeted stage — TensorBoard/
    # Perfetto-grade device timelines when the operator asks for them,
    # zero cost otherwise.
    if profile_dir:
        from rapid_tpu.utils.profiling import trace

        with ledger.stage("profile", timeout_s=_stage_timeout("profile"), n=n):
            vc, _ = build(seed=999)
            vc.sync()
            with _heartbeat("profiled convergence"):
                with trace(profile_dir):
                    resolve_churn(vc)
            _mark(f"profile captured into {profile_dir}")

    value = min(samples)
    # Bounded log-bucketed histogram of the timed samples (the same
    # fixed-schedule instrument the membership service uses for its phase
    # SLOs, utils/histogram.py): the bench trajectory records quantiles —
    # p50/p90/p99/max plus mergeable bucket counts — not just the min/mean,
    # so cross-round comparisons can see tail behavior.
    from rapid_tpu.utils.histogram import LogHistogram

    sample_hist = LogHistogram()
    for s in samples:
        sample_hist.observe(s)
    engine_compiles = engine_telemetry.compile_snapshot()
    result = {
        "metric": f"churn_resolution_ms_n{n}_churn{int(churn_frac * 100)}pct",
        "value": round(value, 3),
        "unit": "ms",
        "vs_baseline": round(baseline_target_ms / value, 3),
        "platform": platform,
        # The HEADLINE scale number (ROADMAP item 1): 1M members, 1% crash,
        # one single-dispatch convergence. Never silently absent —
        # n1M_status says exactly what the point is when the value itself
        # is missing ("ramped:<n>" = CPU stage-path exercise at a small N,
        # reported under xl_point_ms; "skipped-budget"; "suppressed").
        "n1M_status": xl_status,
        **(
            {"n1M_crash1pct_ms": round(xl_ms, 3), "lanes_1m": lanes_xl}
            if xl_ms is not None and xl_n == n_headline
            else {}
        ),
        **(
            {"xl_point_ms": round(xl_ms, 3), "xl_n": xl_n}
            if xl_ms is not None and xl_n != n_headline
            else {}
        ),
        **({"xl_device_memory": xl_memory} if xl_memory is not None else {}),
        # The opt-in stretch point (RAPID_TPU_BENCH_STRETCH): first-class
        # only at the named 10M goal, generic otherwise (mutually
        # exclusive, like the n1M_crash1pct_ms / xl_point_ms pair).
        **(
            {"n10M_crash1pct_ms": round(stretch_ms, 3)}
            if stretch_ms is not None and stretch_n == 10_000_000
            else {"stretch_ms": round(stretch_ms, 3), "stretch_n": stretch_n}
            if stretch_ms is not None
            else {}
        ),
        # Multi-tenant fleet point (ISSUE 10): total view changes committed
        # across B independent clusters per second of the ONE lockstep
        # dispatch. Never silently absent — tenant_fleet_status says
        # exactly what the point is when the value itself is missing
        # ("ramped:BxN" = CPU stage-path exercise; "skipped-budget";
        # "suppressed").
        "tenant_fleet_status": fleet_status,
        **(
            {
                "tenant_view_changes_per_sec": round(fleet_vcps, 1),
                "fleet_tenants": fleet_b,
                "fleet_tenant_members": fleet_n,
                "fleet_view_changes": fleet_cuts_total,
                "fleet_wall_ms": round(fleet_wall_ms, 3),
            }
            if fleet_vcps is not None
            else {}
        ),
        # Device telemetry plane, fleet half (ISSUE 16): the pooled and
        # per-tenant conflict rates from the lanes the fleet wave carried.
        **(
            {
                "tenant_conflict_rate": round(
                    fleet_activity["conflict_rate"], 6
                ),
                "tenant_conflict_rates": fleet_conflict_rates,
                "fleet_fast_path_share": round(
                    fleet_activity["fast_path_share"], 4
                ),
            }
            if fleet_activity is not None
            else {}
        ),
        **({"fleet_device_memory": fleet_memory} if fleet_memory is not None else {}),
        # Streaming serving point (ISSUE 11): sustained view-changes/sec,
        # p99 alert->commit, and overlap efficiency through the pipelined
        # dispatch path over BOTH serving shapes (single cluster + fleet).
        # Never silently absent — stream_status says exactly what the point
        # is when the values themselves are missing ("ramped:WxN" = CPU
        # pipeline exercise; "skipped-budget"; "suppressed").
        "stream_status": stream_status,
        **{k: v for k, v in stream_fields.items() if v is not None},
        # Device telemetry plane status (ISSUE 16): never silently absent —
        # see activity_status for the policy.
        "activity_status": activity_status(stream_fields, stream_status),
        # Round-trace ring status (ISSUE 17): never silently absent — see
        # trace_status for the policy.
        "trace_status": trace_status(stream_fields, stream_status),
        **({"stream_device_memory": stream_memory} if stream_memory is not None else {}),
        # Adversarial-chaos point (ISSUE 12): hostile scenarios resolved
        # (and oracle-checked clean) per second of batched fleet dispatch.
        # Never silently absent — chaos_status says exactly what the point
        # is when the value itself is missing ("ramped:Bx12" = CPU
        # stage-path exercise; "skipped-budget"; "suppressed").
        "chaos_status": chaos_status,
        **{k: v for k, v in chaos_fields.items() if v is not None},
        # Self-healing drill point (ISSUE 15): MTTR of the deterministic
        # checkpoint-resume after an injected mid-stream kill, with the
        # bit-identity verdict beside it. Never silently absent —
        # recovery_status says exactly what the point is when the value
        # itself is missing ("ramped:WxN" = CPU drill; "skipped-budget";
        # "suppressed").
        "recovery_status": recovery_status,
        **{k: v for k, v in recovery_fields.items() if v is not None},
        "samples_ms": [round(s, 3) for s in samples],
        "churn_resolution_hist": sample_hist.summary(),
        "view_changes": cuts_per_sample,
        "n_members": n,
        "joins": n_join,
        "crashes": n_crash,
        "cohorts": cohorts,
        "delivery_spread": delivery_spread,
        # Derived throughput rates at the engine's actual delivery grain
        # (per-cohort) — unit-audited in derived_metrics, plausibility
        # bounds pinned by tests/test_bench_snapshot.py.
        **derived_metrics(
            n=n, n_join=n_join, n_crash=n_crash, k_rings=k_rings,
            cohorts=cohorts, value_ms=value,
        ),
        "device_rtt_ms": round(rtt_ms, 3),
        # Compiled-program audit table (per-entrypoint collective/memory
        # facts at the fixed audit shapes, or {"error": ...}): the
        # trajectory's communication-budget axis — perfview flags
        # collective-count drift between rounds from this.
        "hlo_audit": hlo_audit,
        # State-compaction memory axis (ISSUE 13): bytes/member under the
        # wide/compact/packed layouts, the run's total state bytes, the
        # 100k->100M deployment sizing, and the never-silently-absent
        # mem_status — perfview renders the MEM column from these.
        **mem_fields,
        # Engine-tier provenance for the trajectory: how much compile time
        # this run paid and whether the persistent cache carried it.
        "compiles": engine_compiles["compiles"],
        "compile_ms_total": round(float(engine_compiles["compile_ms"]["sum"]), 3),
        "persistent_cache_hits": engine_compiles["persistent_cache_hits"],
        "persistent_cache_misses": engine_compiles["persistent_cache_misses"],
        # Adverse-network axis: the same churn under the sim
        # subsystem's 5%-loss schedule (None when budget-skipped).
        **(
            {
                "churn_under_loss_ms": round(loss_ms, 3),
                "loss_permille": loss_permille,
                "loss_delivery_spread": loss_knobs["delivery_spread"],
            }
            if loss_ms is not None
            else {}
        ),
        # Delivery-kernel tile width in effect for the main workload
        # (autotune provenance); the headline fields near the top carry the
        # 1M width when the full point ran.
        "pallas_lanes": lanes_main,
    }
    ledger.emit(LedgerEvent.METRIC, **result)
    print(json.dumps(result), flush=True)


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------


def _run_events(path: str, run_id: str) -> list:
    """This run's events from a ledger file that may hold many runs (the
    default bench_ledger.jsonl accumulates across invocations)."""
    from rapid_tpu.utils.ledger import read_ledger

    events, _ = read_ledger(path)
    return [e for e in events if e.get("run_id") == run_id]


# Source paths whose content determines what bench.py measures: the run
# ledger's code-hash roots.
_MEASUREMENT_PATHS = ("bench.py", "rapid_tpu", "native")


def _parse_args(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        description="rapid_tpu convergence benchmark (see module docstring)"
    )
    parser.add_argument(
        "--ledger", default=None, metavar="PATH",
        help="append-only JSONL run ledger (default: $RAPID_TPU_BENCH_LEDGER "
             "or ./bench_ledger.jsonl); render with tools/perfview.py",
    )
    parser.add_argument(
        "--profile", default=os.environ.get("RAPID_TPU_BENCH_PROFILE") or None,
        metavar="DIR",
        help="capture a jax.profiler trace of one resolved churn into DIR "
             "(opt-in 'profile' ledger stage; view with TensorBoard/Perfetto)",
    )
    return parser.parse_args(argv)


def _ledger_path(args: argparse.Namespace) -> str:
    return (
        args.ledger
        or os.environ.get("RAPID_TPU_BENCH_LEDGER")
        or "bench_ledger.jsonl"
    )


def main(argv=None) -> int:
    from rapid_tpu.utils.ledger import (
        LedgerEvent,
        RunLedger,
        last_completed_stage,
        provenance,
    )

    args = _parse_args(argv)
    root = os.path.dirname(os.path.abspath(__file__))
    ledger = RunLedger(_ledger_path(args))
    ledger.emit(LedgerEvent.RUN_BEGIN, mode="inline",
                argv=sys.argv[1:] if argv is None else list(argv),
                **provenance(root, _MEASUREMENT_PATHS))
    try:
        run_workload(ledger, profile_dir=args.profile)
        ledger.emit(LedgerEvent.RUN_END, outcome="completed")
    except BaseException as exc:
        ledger.emit(LedgerEvent.RUN_FAIL, error=repr(exc),
                    last_completed_stage=last_completed_stage(
                        _run_events(ledger.path, ledger.run_id)))
        if isinstance(exc, NoAcceleratorError):
            print(f"bench: {exc}", file=sys.stderr, flush=True)
            return 1
        raise
    finally:
        ledger.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
