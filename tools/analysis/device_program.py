"""Check family 12: compiled-program conformance (the HLO gate).

The engine's communication story is a claim about what XLA emits, so this
family checks the compiled artifact itself: every registered jitted engine
entrypoint (the ``VirtualCluster`` dispatch surface plus the
``parallel/mesh.py`` sharded variants under a forced 8-device CPU mesh) is
compiled via ``jax.jit(...).lower().compile()`` and its facts extracted
from ``as_text()`` + ``memory_analysis()``:

- every cross-device collective, classified by kind, payload bytes/class,
  and location (hot-loop / wave-loop / cond / prologue — the ``hlo_facts``
  classifier that absorbed ``rapid_tpu/parallel/audit.py``);
- host<->device transfer ops (infeed/outfeed/send/recv);
- donation outcomes: each ``donate_argnums`` leaf either aliased in the
  compiled output (``input_output_alias``) or dropped — a drop without an
  explicit registry waiver is a finding;
- argument/output/temp/generated-code memory bytes.

No fact is compared with a committed number. The tree sweep
(:func:`check_compiled_programs`) reports what is wrong with the LIVE
programs whatever they were yesterday: an unknown HLO dtype, an unwaived
dropped donation, a collective that crosses tenants, a host transfer.
What holds of each program beyond that (which collectives sit in the round
loop, what the compact layout saves) is asserted, one case an entrypoint,
by ``tests/test_hlo_gate.py`` over the same facts.

Compiling is expensive relative to AST checks (~25 s for the thirteen
entrypoints), so facts are collected ONCE per process and cached: the
tree sweep, the bench's ``hlo_audit`` stage and every test share one
collection. ``check_device_program`` is the per-file mode for the seeded
lint corpus: a module defining ``HLO_AUDIT_PROGRAMS`` (name -> zero-arg
builder returning ``{"jit": jitted, "args": (...), "donated_leaves":
int}``) and ``HLO_LOCK`` is compiled and compared against its own inline
claim — the corpus way to pin an injected hot-loop all-gather or a dropped
donation, finding by finding.
"""

from __future__ import annotations

import ast
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import core, hlo_facts
from .core import Finding

#: Where a finding about a registered program anchors: the registry's home.
REGISTRY_REL = "tools/analysis/device_program.py"

#: The source files the registry compiles — the tree-mode gate only runs
#: when a sweep actually covers this repo's engine (tests that retarget
#: ``core.REPO`` at a temporary tree must not trigger 15 s of compiles).
REGISTRY_SOURCES = (
    "rapid_tpu/models/virtual_cluster.py",
    "rapid_tpu/parallel/mesh.py",
    "rapid_tpu/tenancy/fleet.py",
)

#: Audit shapes: small enough to compile in seconds, large enough that the
#: payload classes ([n]-scale vs [c,n]-scale) are unambiguous. The mesh
#: axis needs AUDIT_DEVICES to divide AUDIT_N; the 2-D ``('cohort',
#: 'nodes')`` variant reshapes the same devices to (AUDIT_COHORT_DEVICES,
#: AUDIT_DEVICES // AUDIT_COHORT_DEVICES), which must divide AUDIT_C and
#: AUDIT_N respectively.
AUDIT_N = 256
AUDIT_C = 8
AUDIT_K = 4
AUDIT_DEVICES = 8
AUDIT_COHORT_DEVICES = 2
#: The fleet audit: AUDIT_TENANTS tenant clusters over the 3-D
#: ``('tenant', 'cohort', 'nodes')`` reshape of the same devices. The
#: tenant axis leads, so device ids are contiguous per tenant slice —
#: ``AUDIT_TENANT_BLOCK`` devices per tenant — which is what the
#: cross-tenant replica-group check keys on.
AUDIT_TENANTS = 4
AUDIT_FLEET_MESH = (2, 2, 2)
AUDIT_TENANT_BLOCK = AUDIT_DEVICES // AUDIT_FLEET_MESH[0]
#: Ring capacity of the ``step_trace`` entrypoint and of the trace-on side
#: of :func:`trace_differential_ok`: small, so that the ring's argument
#: bytes stay a rounding error next to the state.
AUDIT_TRACE_R = 8

_REGEN_HINT = (
    "if this compiled-program change is intentional, update the module's "
    "inline HLO_LOCK and review the diff"
)


# -- program registry -------------------------------------------------------


def _build_registry() -> "Dict[str, Dict[str, Any]]":
    """name -> {"jit": jitted, "args": tuple, "donated_leaves": int,
    "waiver": Optional[str]} for every registered engine entrypoint, at the
    audit shapes. Imports jax and the engine lazily: the rest of the
    analysis package stays importable without a backend."""
    import jax
    import jax.numpy as jnp

    from rapid_tpu.models.state import initial_telemetry, initial_trace
    from rapid_tpu.models.virtual_cluster import (
        VirtualCluster,
        engine_step_impl,
        run_to_decision_impl,
        run_until_membership_impl,
        sync_checksum_impl,
    )
    from rapid_tpu.parallel.mesh import (
        make_mesh,
        make_sharded_step,
        make_sharded_step_telem,
        make_sharded_wave,
        shard_faults,
        shard_pytree,
        shard_state,
        telemetry_shardings,
    )

    vc = VirtualCluster.create(
        AUDIT_N - AUDIT_DEVICES, n_slots=AUDIT_N, k=AUDIT_K, h=3, l=1,
        fd_threshold=2, cohorts=AUDIT_C, delivery_spread=2, seed=0,
    )
    vc.assign_cohorts_roundrobin()
    cfg = vc.cfg
    state, faults = vc.state, vc.faults
    state_leaves = len(jax.tree_util.tree_leaves(state))

    # The compact-state twin (ISSUE 13): identical geometry/seed, state
    # stored at the config-derived narrow dtypes. Registered so the
    # per-device argument-byte saving of the [k,n]/[c,n]-dominated
    # entrypoints is read off the compiled artifact beside the wide layout
    # above, and so the compact path is audited like every other entrypoint.
    vc_c = VirtualCluster.create(
        AUDIT_N - AUDIT_DEVICES, n_slots=AUDIT_N, k=AUDIT_K, h=3, l=1,
        fd_threshold=2, cohorts=AUDIT_C, delivery_spread=2, seed=0,
        compact=True,
    )
    vc_c.assign_cohorts_roundrobin()
    cfg_c = vc_c.cfg
    state_c, faults_c = vc_c.state, vc_c.faults

    registry: Dict[str, Dict[str, Any]] = {
        "step": {
            "jit": jax.jit(
                lambda s, f: engine_step_impl(cfg, s, f), donate_argnums=(0,)
            ),
            "args": (state, faults),
            "donated_leaves": state_leaves,
        },
        "run_to_decision": {
            "jit": jax.jit(
                lambda s, f: run_to_decision_impl(cfg, s, f, jnp.int32(96)),
                donate_argnums=(0,),
            ),
            "args": (state, faults),
            "donated_leaves": state_leaves,
        },
        "run_until_membership": {
            "jit": jax.jit(
                lambda s, f: run_until_membership_impl(
                    cfg, s, f, jnp.int32(AUDIT_N - AUDIT_DEVICES),
                    jnp.int32(192), 8, jnp.int32(0),
                ),
                donate_argnums=(0,),
            ),
            "args": (state, faults),
            "donated_leaves": state_leaves,
        },
        "sync": {
            "jit": jax.jit(sync_checksum_impl),
            "args": (state, faults),
            "donated_leaves": 0,
        },
        # Only the compact STEP is registered (the PR-9 convention that
        # kept the 2-D step unregistered): the wave's argument surface is
        # byte-identical to the step's modulo three trailing int32 control
        # scalars, so the step alone shows the compaction saving, while
        # a second compact while-loop compile would cost ~10 s of every
        # tier-1 session. The compact wave path stays differentially
        # driven against the wide oracle in tests/test_state_compaction.py
        # (the adverse grid rides check.sh's unfiltered pass).
        "step_compact": {
            "jit": jax.jit(
                lambda s, f: engine_step_impl(cfg_c, s, f), donate_argnums=(0,)
            ),
            "args": (state_c, faults_c),
            "donated_leaves": state_leaves,
        },
    }
    # The telemetry-plane step (ISSUE 16): identical geometry with
    # telemetry=1 and the TelemetryLanes pytree donated alongside the
    # state. Registered so the plane's entire compiled cost is audited —
    # the lanes' argument bytes, ZERO new hot-loop collectives
    # (the digest is a separate boundary dispatch, never traced here),
    # and zero host<->device transfer ops. Only the STEP is registered
    # (the step_compact convention): the telem wave shares the round
    # body and every extra while-loop compile costs ~10 s of tier-1;
    # the wave path is differentially driven against the telemetry=0
    # oracle in tests/test_telemetry_plane.py.
    cfg_t = cfg._replace(telemetry=1)
    telem = initial_telemetry(cfg_t)
    telem_leaves = len(jax.tree_util.tree_leaves(telem))
    registry["step_telem"] = {
        "jit": jax.jit(
            lambda s, t, f: engine_step_impl(cfg_t, s, t, f),
            donate_argnums=(0, 1),
        ),
        "args": (state, telem, faults),
        "donated_leaves": state_leaves + telem_leaves,
    }
    # The round-trace ring step (ISSUE 17): the telemetry geometry with an
    # AUDIT_TRACE_R-slot TraceRing donated alongside the state and lanes.
    # Registered so the ring's entire compiled footprint is audited —
    # its argument bytes, ZERO new hot-loop collectives (ring writes are
    # slot-local dynamic-update-slices; the digest is a boundary dispatch,
    # never traced here) and zero host<->device transfer ops. Only the STEP
    # is registered (the step_telem convention): the fused and fleet trace
    # variants share the round body, and each extra while-loop compile
    # costs ~10 s of tier-1 — those paths are differentially driven
    # against the trace=0 oracle in tests/test_trace_ring.py.
    cfg_tr = cfg_t._replace(trace=AUDIT_TRACE_R)
    trace_ring = initial_trace(cfg_tr)
    trace_leaves = len(jax.tree_util.tree_leaves(trace_ring))
    registry["step_trace"] = {
        "jit": jax.jit(
            lambda s, t, r, f: engine_step_impl(cfg_tr, s, t, r, f),
            donate_argnums=(0, 1, 2),
        ),
        "args": (state, telem, trace_ring, faults),
        "donated_leaves": state_leaves + telem_leaves + trace_leaves,
    }
    if jax.device_count() >= AUDIT_DEVICES:
        mesh = make_mesh(jax.devices()[:AUDIT_DEVICES])
        sh_state = shard_state(state, mesh)
        sh_faults = shard_faults(faults, mesh)
        registry["sharded_step"] = {
            "jit": make_sharded_step(cfg, mesh),
            "args": (sh_state, sh_faults),
            "donated_leaves": state_leaves,
        }
        registry["sharded_wave"] = {
            "jit": make_sharded_wave(cfg, mesh),
            "args": (
                sh_state, sh_faults, jnp.int32(AUDIT_N - AUDIT_DEVICES),
                jnp.int32(192), jnp.int32(0),
            ),
            "donated_leaves": state_leaves,
        }
        # The telemetry step under GSPMD: proves the plane adds zero
        # collectives on a real mesh too (the [c, n] lanes accumulate
        # shard-locally), not just on one device.
        sh_telem = shard_pytree(telem, telemetry_shardings(mesh), mesh=mesh)
        registry["sharded_step_telem"] = {
            "jit": make_sharded_step_telem(cfg_t, mesh),
            "args": (sh_state, sh_telem, sh_faults),
            "donated_leaves": state_leaves + telem_leaves,
        }
        # The 2-D ('cohort', 'nodes') variant — the 1M+ headline bench
        # configuration: same devices, reshaped so the cohort lanes and the
        # [c, n] watermark state genuinely shard over the cohort axis. The
        # 1-D entries above stay registered as the hot-loop baseline the
        # 2-D program is budget-compared against (test_hlo_gate.py). Only
        # the WAVE is registered: it contains the step's entire compiled
        # surface (round body + cond-gated view change + per-cut prologue)
        # and every extra two-axis GSPMD compile costs ~10 s of the tier-1
        # session — the step variant is still differentially driven against
        # the single-device engine in tests/test_parallel_2d.py and by the
        # multichip dry run.
        mesh2d = make_mesh(
            jax.devices()[:AUDIT_DEVICES],
            shape=(AUDIT_COHORT_DEVICES, AUDIT_DEVICES // AUDIT_COHORT_DEVICES),
        )
        sh2_state = shard_state(state, mesh2d)
        sh2_faults = shard_faults(faults, mesh2d)
        registry["sharded2d_wave"] = {
            "jit": make_sharded_wave(cfg, mesh2d),
            "args": (
                sh2_state, sh2_faults, jnp.int32(AUDIT_N - AUDIT_DEVICES),
                jnp.int32(192), jnp.int32(0),
            ),
            "donated_leaves": state_leaves,
        }
        # The multi-tenant fleet pair (rapid_tpu/tenancy) on the 3-D
        # ('tenant', 'cohort', 'nodes') reshape of the same devices:
        # AUDIT_TENANTS independent clusters with per-tenant H/L/fd knob
        # lanes, batched into one program. These entries carry
        # ``tenant_block`` so extract_facts computes the cross-tenant
        # replica-group count — the budget the fleet holds at ZERO
        # (tenants never communicate).
        from jax.sharding import NamedSharding, PartitionSpec

        from rapid_tpu.parallel.mesh import (
            TENANT_AXIS,
            shard_fleet_faults,
            shard_fleet_state,
        )
        from rapid_tpu.tenancy.fleet import (
            knob_shardings,
            make_fleet_step,
            make_fleet_wave,
        )

        fleet = _audit_fleet()
        mesh3d = make_mesh(jax.devices()[:AUDIT_DEVICES], shape=AUDIT_FLEET_MESH)
        fl_state = shard_fleet_state(fleet.state, mesh3d)
        fl_faults = shard_fleet_faults(fleet.faults, mesh3d)
        fl_knobs = jax.tree_util.tree_map(
            jax.device_put, fleet.knobs, knob_shardings(mesh3d)
        )
        lane = NamedSharding(mesh3d, PartitionSpec(TENANT_AXIS))
        targets = jax.device_put(
            jnp.full((AUDIT_TENANTS,), AUDIT_N - AUDIT_DEVICES, jnp.int32),
            lane,
        )
        min_cuts = jax.device_put(
            jnp.zeros((AUDIT_TENANTS,), jnp.int32), lane
        )
        registry["fleet3d_step"] = {
            "jit": make_fleet_step(fleet.cfg, mesh3d),
            "args": (fl_state, fl_faults, fl_knobs),
            "donated_leaves": state_leaves,
            "tenant_block": AUDIT_TENANT_BLOCK,
        }
        registry["fleet3d_wave"] = {
            "jit": make_fleet_wave(fleet.cfg, mesh3d),
            "args": (fl_state, fl_faults, fl_knobs, targets, jnp.int32(64),
                     min_cuts),
            "donated_leaves": state_leaves,
            "tenant_block": AUDIT_TENANT_BLOCK,
        }
    return registry


def _audit_fleet() -> Any:
    """AUDIT_TENANTS tenant clusters at the audit geometry, two H/L knob
    settings alternating, as one ``TenantFleet``."""
    from rapid_tpu.models.virtual_cluster import VirtualCluster
    from rapid_tpu.tenancy.fleet import TenantFleet

    tenants = []
    for i in range(AUDIT_TENANTS):
        h, l = ((3, 1), (4, 2))[i % 2]
        tvc = VirtualCluster.create(
            AUDIT_N - AUDIT_DEVICES, n_slots=AUDIT_N, k=AUDIT_K, h=h, l=l,
            fd_threshold=2, cohorts=AUDIT_C, delivery_spread=2, seed=i,
        )
        tvc.assign_cohorts_roundrobin()
        tenants.append(tvc)
    return TenantFleet.from_clusters(tenants)


def fleet_step_spec() -> Dict[str, Any]:
    """The MESHLESS vmapped fleet step at the audit geometry, registry
    shaped: what single-host deployments run, and what the dataflow
    family's tenant-isolation proof must cover beside the mesh pair. It is
    traced, never compiled, so it is no entry of :func:`_build_registry`."""
    import jax

    from rapid_tpu.tenancy.fleet import fleet_step_impl

    fleet = _audit_fleet()
    fcfg = fleet.cfg
    return {
        "jit": jax.jit(
            lambda s, f, kb: fleet_step_impl(fcfg, s, f, kb),
            donate_argnums=(0,),
        ),
        "args": (fleet.state, fleet.faults, fleet.knobs),
        "donated_leaves": len(jax.tree_util.tree_leaves(fleet.state)),
    }


# -- fact extraction --------------------------------------------------------


def extract_facts(
    compiled: Any,
    donated_leaves: int,
    n: int,
    c: int,
    donation_reasons: Optional[List[str]] = None,
    tenant_block: Optional[int] = None,
) -> Dict[str, Any]:
    """All budget-relevant facts of one compiled executable. ``rows`` holds
    the per-collective detail (the evidence-table grain); everything else
    is summed per location and kind. ``tenant_block`` (devices per tenant
    slice, fleet entrypoints only) additionally counts collectives whose
    replica groups span tenant blocks — the ``cross_tenant_collectives``
    fact the fleet holds at zero."""
    text = compiled.as_text()
    rows = hlo_facts.audit_collectives(text, n, c)
    collectives: Dict[str, Dict[str, Any]] = {}
    unknown: List[str] = []
    for row in rows:
        key = f"{row['location']}/{row['kind']}"
        entry = collectives.setdefault(key, {"count": 0, "bytes": 0, "max_bytes": 0})
        entry["count"] += 1
        entry["bytes"] += row["bytes"]
        entry["max_bytes"] = max(entry["max_bytes"], row["bytes"])
        unknown.extend(row["unknown_dtypes"])
    for entry in collectives.values():
        # Scale class of the LARGEST single payload in the group: "class
        # increase" means one collective jumped a scale tier ([n] -> [c,n]),
        # not that a count bump nudged the aggregate over a threshold.
        entry["class"] = hlo_facts.payload_class(entry["max_bytes"], n, c)
    aliased = len(hlo_facts.input_output_aliases(text))
    memory = {}
    analysis = None
    try:
        analysis = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — memory analysis is platform-optional
        # (mirrors engine_telemetry.compiled_memory_analysis); the section
        # is then empty.
        analysis = None
    if analysis is not None:
        memory = {
            "argument_bytes": int(analysis.argument_size_in_bytes),
            "output_bytes": int(analysis.output_size_in_bytes),
            "temp_bytes": int(analysis.temp_size_in_bytes),
            "generated_code_bytes": int(analysis.generated_code_size_in_bytes),
        }
    facts = {
        "collectives": collectives,
        # Which loop the "hot-loop" keys above mean: the body of the loop
        # around the round's own scope (tests pin it per entrypoint).
        "round_loop": hlo_facts.round_loop(text),
        # Entry-signature bytes per dtype: the artifact-level proof of the
        # state-compaction policy (compact entrypoints carry s8/s16/u8
        # argument lanes; the wide oracle only s32/u32/pred). An unknown
        # dtype here surfaces through the same hlo-unknown-dtype finding as
        # the payload accounting.
        "parameter_dtype_bytes": hlo_facts.entry_parameter_bytes(
            text, unknown=unknown
        ),
        "transfers": hlo_facts.count_transfer_ops(text),
        "donation": {
            "donated_leaves": donated_leaves,
            "aliased": aliased,
            "dropped": max(donated_leaves - aliased, 0),
            "reasons": sorted(set(donation_reasons or [])),
        },
        "memory": memory,
        "unknown_dtypes": sorted(set(unknown)),
        "rows": rows,
    }
    if tenant_block is not None:
        facts["cross_tenant_collectives"] = sum(
            1 for row in rows
            if hlo_facts.groups_cross_blocks(row["groups"], tenant_block)
        )
    return facts


def _compile_program(spec: Dict[str, Any]) -> Tuple[Any, List[str]]:
    """Lower+compile one registry entry, capturing XLA/jax donation
    complaints (the "Some donated buffers were not usable" class) as the
    drop reasons the findings report."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = spec["jit"].lower(*spec["args"]).compile()
    reasons = [
        str(w.message).splitlines()[0]
        for w in caught
        if "donat" in str(w.message).lower()
    ]
    return compiled, reasons


#: (facts, complete) — ``complete`` records whether the sharded mesh
#: entrypoints were included, so a partial (observational) collection can
#: never satisfy the gate's full-registry requirement.
_FACTS_CACHE: Optional[Tuple[Dict[str, Any], bool]] = None

def collect_facts(
    force: bool = False, require_mesh: bool = True
) -> Dict[str, Any]:
    """Compile every registered entrypoint and extract its facts — once per
    process (compiles dominate the gate's cost; every consumer shares this
    cache).

    ``require_mesh=True`` (the gate): raises RuntimeError when the process
    cannot provide the 8-device mesh, rather than silently passing with
    sharded entrypoints unaudited. ``require_mesh=False`` (observational consumers, e.g. the
    bench's ``hlo_audit`` stage on a single-chip backend): audits whatever
    the registry can build — the four single-device entrypoints always,
    the sharded pair when devices allow. A partial collection never
    satisfies a later full-gate call."""
    global _FACTS_CACHE
    import jax

    have_mesh = jax.device_count() >= AUDIT_DEVICES
    if _FACTS_CACHE is not None and not force:
        facts, complete = _FACTS_CACHE
        if complete or not require_mesh:
            return facts
    if require_mesh and not have_mesh:
        raise RuntimeError(
            f"device_program audit needs {AUDIT_DEVICES} devices, have "
            f"{jax.device_count()} — force them before jax initializes "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{AUDIT_DEVICES}, as tests/conftest.py and the staticcheck "
            f"CLI do)"
        )
    registry = _build_registry()
    facts = {}
    for name, spec in registry.items():
        compiled, reasons = _compile_program(spec)
        entry = extract_facts(
            compiled, spec["donated_leaves"], AUDIT_N, AUDIT_C,
            donation_reasons=reasons,
            tenant_block=spec.get("tenant_block"),
        )
        if spec.get("waiver"):
            entry["donation"]["waiver"] = spec["waiver"]
        facts[name] = entry
    _FACTS_CACHE = (facts, have_mesh)
    return facts


def live_findings(
    name: str,
    entry: Dict[str, Any],
    loc: Tuple[str, int],
    waiver: Optional[str] = None,
) -> List[Finding]:
    """What is wrong with ONE compiled program whatever it is compared
    with: a dtype the payload accounting cannot size, a collective that
    crosses tenants, a donated buffer dropped without a waiver."""
    path, lineno = loc
    findings: List[Finding] = []
    if entry["unknown_dtypes"]:
        findings.append(Finding(
            path, lineno, "hlo-unknown-dtype",
            f"{name}: collective payload uses HLO dtype(s) "
            f"{entry['unknown_dtypes']} missing from hlo_facts.DTYPE_BITS — "
            f"payload accounting cannot size them; add the dtype, do not "
            f"guess",
        ))
    # The fleet's hard budget: tenants never communicate.
    cross = entry.get("cross_tenant_collectives")
    if cross:
        findings.append(Finding(
            path, lineno, "hlo-cross-tenant-collective",
            f"{name}: {cross} collective(s) carry the tenant axis in their "
            f"replica groups — tenants must never communicate; fix the "
            f"batched program (this budget is ZERO)",
        ))
    donation = entry["donation"]
    if donation["dropped"] > 0 and not (donation.get("waiver") or waiver):
        reasons = "; ".join(donation.get("reasons", [])) or "no XLA reason captured"
        findings.append(Finding(
            path, lineno, "hlo-donation-dropped",
            f"{name}: {donation['dropped']} of {donation['donated_leaves']} "
            f"donated buffer(s) NOT aliased in the compiled output "
            f"({reasons}) — donation silently dropped; fix the "
            f"entrypoint or add an explicit registry waiver",
        ))
    return findings


def compare_facts(
    name: str,
    entry: Dict[str, Any],
    locked: Dict[str, Any],
    loc: Tuple[str, int],
) -> List[Finding]:
    """The corpus comparison: ONE compiled program against the claim its
    module makes inline (``HLO_LOCK``), each finding naming the entrypoint
    and the delta, after :func:`live_findings`. Sections present in the
    claim are enforced; absent sections are skipped (the corpus claims pin
    only the facts each defect class is about)."""
    path, lineno = loc
    findings = live_findings(
        name, entry, loc, waiver=locked.get("donation", {}).get("waiver")
    )

    def fail(check: str, message: str) -> None:
        findings.append(Finding(path, lineno, check, f"{message} — {_REGEN_HINT}"))

    cross = entry.get("cross_tenant_collectives") or 0
    if not cross and locked.get("cross_tenant_collectives", 0) != 0:
        fail("hlo-lock-drift",
             f"{name}: cross_tenant_collectives "
             f"{locked['cross_tenant_collectives']} claimed, 0 now")

    if "collectives" in locked:
        cur = entry["collectives"]
        old = locked["collectives"]
        for key in sorted(set(cur) | set(old)):
            location, kind = key.split("/", 1)
            if key not in old:
                hot = "NEW HOT-LOOP collective" if location.startswith(
                    "hot-loop") else "new collective"
                fail("hlo-collective-budget",
                     f"{name}: {hot} {kind} in location {location} "
                     f"({cur[key]['count']} op(s), {cur[key]['bytes']} bytes, "
                     f"class {cur[key]['class']}) not in the claim")
            elif key not in cur:
                fail("hlo-collective-budget",
                     f"{name}: collective {kind} in location {location} "
                     f"vanished (claimed {old[key]['count']} op(s), "
                     f"{old[key]['bytes']} bytes)")
            else:
                rank_old = hlo_facts.PAYLOAD_CLASS_RANK[old[key]["class"]]
                rank_cur = hlo_facts.PAYLOAD_CLASS_RANK[cur[key]["class"]]
                if rank_cur > rank_old:
                    fail("hlo-collective-budget",
                         f"{name}: payload-class INCREASE for {kind} in "
                         f"{location}: {old[key]['class']} -> "
                         f"{cur[key]['class']} (largest payload "
                         f"{old[key].get('max_bytes', old[key]['bytes'])} -> "
                         f"{cur[key]['max_bytes']} bytes)")
                elif (cur[key]["count"], cur[key]["bytes"]) != (
                    old[key]["count"], old[key]["bytes"]
                ):
                    fail("hlo-collective-budget",
                         f"{name}: collective budget drift for {kind} in "
                         f"{location}: {old[key]['count']} op(s)/"
                         f"{old[key]['bytes']} bytes -> "
                         f"{cur[key]['count']} op(s)/{cur[key]['bytes']} "
                         f"bytes")

    if "transfers" in locked:
        cur_t = entry["transfers"]
        old_t = locked["transfers"]
        for op in sorted(set(cur_t) | set(old_t)):
            if cur_t.get(op, 0) != old_t.get(op, 0):
                fail("hlo-transfer-budget",
                     f"{name}: host<->device transfer op {op}: "
                     f"{old_t.get(op, 0)} -> {cur_t.get(op, 0)}")

    if "donation" in locked:
        cur_d = entry["donation"]
        old_d = locked["donation"]
        dropped = any(f.check == "hlo-donation-dropped" for f in findings)
        if not dropped and (cur_d["donated_leaves"], cur_d["aliased"]) != (
            old_d.get("donated_leaves"), old_d.get("aliased")
        ):
            fail("hlo-lock-drift",
                 f"{name}: donation outcome drift: "
                 f"{old_d.get('aliased')}/{old_d.get('donated_leaves')} "
                 f"aliased in the claim, "
                 f"{cur_d['aliased']}/{cur_d['donated_leaves']} now")
    return findings


# -- tree-mode gate ----------------------------------------------------------


def covers_registry(trees: Sequence[Tuple[ast.AST, str]]) -> bool:
    """Whether a sweep holds the engine sources the registry compiles: the
    presence gate of this family's tree mode and the dataflow family's."""
    rels = {rel.replace("\\", "/") for _, rel in trees}
    return all(src in rels for src in REGISTRY_SOURCES)


def check_compiled_programs(
    trees: Sequence[Tuple[ast.AST, str]],
) -> List[Finding]:
    """Tree-mode gate the driver runs on full sweeps: compile the registered
    entrypoints (session-cached) and report, from the live facts alone,
    :func:`live_findings` and any host<->device transfer op. Presence-gated
    on the engine sources being part of the sweep, so tests that retarget
    ``core.REPO`` at temporary trees never pay a compile."""
    if not covers_registry(trees):
        return []
    loc = (REGISTRY_REL, 1)
    findings: List[Finding] = []
    for name, entry in sorted(collect_facts().items()):
        findings.extend(live_findings(name, entry, loc))
        for op, count in sorted(entry["transfers"].items()):
            findings.append(Finding(
                loc[0], loc[1], "hlo-transfer-budget",
                f"{name}: {count} host<->device transfer op(s) {op} in a "
                f"registered engine program — a dispatch must not hold a "
                f"host round-trip",
            ))
    return findings


def compaction_differential_ok() -> Optional[str]:
    """Run a small mixed crash+join scenario through the WIDE engine and
    the COMPACT engine (same geometry/seed) and compare the widened compact
    state leaf-for-leaf. Returns None on bit-identity, else a message
    naming the first divergent lane (``tests/test_hlo_gate.py`` holds it
    to None)."""
    import numpy as np

    from rapid_tpu.models.state import widen_state
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    def drive(compact: bool) -> VirtualCluster:
        vc = VirtualCluster.create(
            56, n_slots=64, k=3, h=3, l=1, cohorts=4, fd_threshold=2,
            delivery_spread=1, seed=17, compact=compact,
        )
        vc.assign_cohorts_roundrobin()
        vc.crash([1, 9, 20])
        vc.inject_join_wave([60, 61])
        vc.run_until_membership(55, min_cuts=2)
        return vc

    wide, compact = drive(False), drive(True)
    widened = widen_state(compact.cfg, compact.state)
    for field in wide.state._fields:
        a = np.asarray(getattr(wide.state, field))
        b = np.asarray(getattr(widened, field))
        if a.dtype != b.dtype or not (a == b).all():
            return (
                f"wide<->compact differential disagrees on state lane "
                f"{field!r} (crash+join scenario at n=64)"
            )
    if wide.config_id != compact.config_id:
        return "wide<->compact differential disagrees on the configuration id"
    return None


def trace_differential_ok() -> Optional[str]:
    """Run the compaction differential's crash+join scenario through the
    telemetry engine with the trace ring OFF and ON (same geometry/seed)
    and compare state AND telemetry leaf-for-leaf. Returns None on
    bit-identity, else a message naming the first divergent lane
    (``tests/test_hlo_gate.py`` holds it to None): the ring is write-only
    by construction, so a trace knob that perturbs the engine or its
    telemetry is a recorder bug."""
    import numpy as np

    from rapid_tpu.models.virtual_cluster import VirtualCluster

    def drive(trace: int) -> VirtualCluster:
        vc = VirtualCluster.create(
            56, n_slots=64, k=3, h=3, l=1, cohorts=4, fd_threshold=2,
            delivery_spread=1, seed=17, telemetry=True, trace=trace,
        )
        vc.assign_cohorts_roundrobin()
        vc.crash([1, 9, 20])
        vc.inject_join_wave([60, 61])
        vc.run_until_membership(55, min_cuts=2)
        return vc

    off, on = drive(0), drive(AUDIT_TRACE_R)
    for label, a_tree, b_tree in (
        ("state", off.state, on.state),
        ("telemetry", off.telem, on.telem),
    ):
        for field in a_tree._fields:
            a = np.asarray(getattr(a_tree, field))
            b = np.asarray(getattr(b_tree, field))
            if a.dtype != b.dtype or not (a == b).all():
                return (
                    f"trace-on<->trace-off differential disagrees on "
                    f"{label} lane {field!r} (crash+join scenario at n=64) "
                    f"— the ring must be write-only"
                )
    if off.config_id != on.config_id:
        return (
            "trace-on<->trace-off differential disagrees on the "
            "configuration id"
        )
    return None


# -- per-file mode (the seeded lint corpus) ---------------------------------


def _program_key_linenos(tree: ast.AST) -> Dict[str, int]:
    """lineno of each string key in the module's HLO_AUDIT_PROGRAMS dict
    literal — where corpus findings anchor (the `# expect:` markers sit on
    these lines)."""
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "HLO_AUDIT_PROGRAMS"
            and isinstance(node.value, ast.Dict)
        ):
            return {
                key.value: key.lineno
                for key in node.value.keys
                if isinstance(key, ast.Constant) and isinstance(key.value, str)
            }
    return {}


def check_device_program(
    path: Path,
    source: Optional[str] = None,
    tree: "Optional[ast.AST]" = None,
) -> List[Finding]:
    """Corpus mode: compile the module's own miniature programs and compare
    them against its inline ``HLO_LOCK``. Modules without an
    ``HLO_AUDIT_PROGRAMS`` registry are skipped outright (this check never
    executes ordinary library files)."""
    src = source if source is not None else path.read_text()
    if "HLO_AUDIT_PROGRAMS" not in src:
        return []
    if tree is None:
        tree = ast.parse(src, filename=str(path))
    linenos = _program_key_linenos(tree)
    if not linenos:
        return []
    rel = core.rel(path)
    namespace: Dict[str, Any] = {"__name__": f"_hlo_corpus_{path.stem}"}
    exec(compile(src, str(path), "exec"), namespace)  # noqa: S102 — the
    # corpus is this repo's own fixture tree; per-file mode only ever runs
    # on explicitly-named files, never on sweeps.
    programs = namespace["HLO_AUDIT_PROGRAMS"]
    locked = namespace.get("HLO_LOCK", {})
    n = namespace.get("AUDIT_N", AUDIT_N)
    c = namespace.get("AUDIT_C", AUDIT_C)
    findings: List[Finding] = []
    for name, builder in programs.items():
        spec = builder()
        compiled, reasons = _compile_program(spec)
        entry = extract_facts(
            compiled, spec.get("donated_leaves", 0), n, c,
            donation_reasons=reasons,
        )
        if spec.get("waiver"):
            entry["donation"]["waiver"] = spec["waiver"]
        findings.extend(compare_facts(
            name, entry, locked.get(name, {}),
            (rel, linenos.get(name, 1)),
        ))
    return sorted(set(findings), key=lambda f: (f.lineno, f.check, f.message))
