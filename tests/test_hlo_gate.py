"""The compiled-program conformance gate (analysis family 12-13 pins).

What holds of the LIVE compiled programs, one case an entrypoint, every
assertion over the session-cached ``collect_facts()`` (one collection pays
for all of them, and for the tree sweeps in test_lint.py /
test_staticcheck.py); nothing is compared with a committed number:

- the registry is the thirteen names, compiled once a session inside its
  budget;
- every ``donate_argnums`` leaf is aliased in the compiled output (or
  carries an explicit waiver), on the forced 8-device CPU mesh;
- the one-device programs hold no collective and no host transfer;
- ``hot-loop`` means, in every registered program, the loop around the
  round's own ``fd_tick`` scope, at the nest pinned here an entrypoint;
- the mesh programs hold collectives; a wave's ROUND loop carries, outside
  a conditional, all-reduces of scalar / [n] class (and on the 2-D mesh the
  scalar-class re-layout of the [c] tally); the mask build's [c, n]-class
  gather is per-convergence work one loop level up; the fleet pair holds
  zero cross-tenant collectives;
- the compact and the wide state agree, and trace-on equals trace-off;
- an injected hot-loop all-gather or a dropped donation in a
  corpus-compiled program fails naming the entrypoint, the location class
  and the payload delta; the payload accounting never guesses a dtype;
- each registered entrypoint recalled with fresh same-shape inputs does
  NOT recompile (the executable check behind ``retrace-hazard``).
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import staticcheck  # noqa: E402
from analysis import device_program, hlo_facts  # noqa: E402

CORPUS = REPO / "tests" / "data" / "lint_corpus"

#: ``device_program._build_registry()``, by name: the parametrised cases
#: below are collected before anything is compiled.
ONE_DEVICE = (
    "step", "run_to_decision", "run_until_membership", "sync",
    "step_compact", "step_telem", "step_trace",
)
MESH = (
    "sharded_step", "sharded_step_telem", "sharded_wave", "sharded2d_wave",
    "fleet3d_step", "fleet3d_wave",
)
REGISTRY = ONE_DEVICE + MESH

#: The loop nest around each program's round, below its ``jit(...)`` name
#: (``facts[name]["round_loop"]``): what "hot-loop" means there. The rest
#: run one round outside any loop, or none (``sync``).
ROUND_LOOP = {
    "run_to_decision": "while/body",
    "run_until_membership": "while/body/while/body",
    "sharded_wave": "while/body/while/body",
    "sharded2d_wave": "while/body/while/body",
    "fleet3d_wave": "vmap()/while/body",
}


# ---------------------------------------------------------------------------
# Payload accounting (the _shape_bytes dtype-table satellite)
# ---------------------------------------------------------------------------


def test_shape_bytes_covers_narrow_and_complex_dtypes():
    # The dtypes the old table silently guessed as 4 bytes each.
    assert hlo_facts.shape_bytes("f8e4m3[8]") == 8
    assert hlo_facts.shape_bytes("f8e5m2[16]{0}") == 16
    assert hlo_facts.shape_bytes("s4[16]") == 8  # two elements per byte
    assert hlo_facts.shape_bytes("u4[7]") == 4  # rounds UP to whole bytes
    assert hlo_facts.shape_bytes("c64[2]") == 16
    assert hlo_facts.shape_bytes("c128[2]") == 32


def test_shape_bytes_tuple_shapes_with_nested_layouts():
    # Layout annotations ({1,0}) are not shape tokens; scalars ([]) are one
    # element.
    assert hlo_facts.shape_bytes("(u32[64]{0}, bf16[2,3]{1,0})") == 256 + 12
    assert hlo_facts.shape_bytes("(f32[], (pred[8]{0}, s64[2,2]{1,0}))") == (
        4 + 8 + 32
    )


def test_unknown_dtype_is_never_a_silent_guess():
    with pytest.raises(ValueError, match="unknown HLO dtype 'q7'"):
        hlo_facts.shape_bytes("q7[4]")
    unknown = []
    assert hlo_facts.shape_bytes("(q7[4], u32[2])", unknown=unknown) == 8
    assert unknown == ["q7"]


def test_unknown_dtype_surfaces_as_a_finding():
    entry = {
        "collectives": {}, "transfers": {}, "memory": {},
        "donation": {"donated_leaves": 0, "aliased": 0, "dropped": 0},
        "unknown_dtypes": ["q7"],
    }
    findings = device_program.compare_facts("probe", entry, {}, ("hlo.lock", 1))
    assert [f.check for f in findings] == ["hlo-unknown-dtype"]
    assert "q7" in findings[0].message and "do not guess" in findings[0].message


# ---------------------------------------------------------------------------
# The live registry: one collection, one case an entrypoint
# ---------------------------------------------------------------------------


def _round_loop(collectives):
    """The unconditional collectives of a wave's round loop."""
    return {k: v for k, v in collectives.items() if k.startswith("hot-loop/")}


def test_the_registry_is_the_thirteen_names_compiled_once_inside_its_budget(
    record_property,
):
    # When THIS call is the session's first collection (it is, in both
    # tier-1 and check.sh ordering), it pays the fresh backend compiles —
    # budget them here, where the cost is guaranteed to be real (test_lint's
    # sweep budget would otherwise measure a cache hit). 90 s since the
    # tenant-fleet pair joined the registry (two- and three-axis GSPMD
    # partitioning costs real compile time — the compile-inclusive budget
    # may grow, the analysis-only sweep budget in test_lint.py must not).
    import time

    fresh = device_program._FACTS_CACHE is None
    started = time.process_time()
    facts = staticcheck.collect_facts()
    elapsed = time.process_time() - started
    if fresh:
        record_property("fresh_compile_cpu_s", round(elapsed, 2))
        assert elapsed < 90.0, (
            f"fresh entrypoint compile collection used {elapsed:.1f}s CPU "
            f"(budget 90s)"
        )
    assert set(facts) == set(REGISTRY)
    trees = [(None, rel) for rel in device_program.REGISTRY_SOURCES]
    assert device_program.check_compiled_programs(trees) == []
    assert staticcheck.collect_facts() is facts  # cached, not recompiled


@pytest.mark.parametrize("name", ONE_DEVICE)
def test_a_one_device_program_holds_no_collective_and_no_transfer(name):
    entry = staticcheck.collect_facts()[name]
    assert entry["collectives"] == {}
    assert entry["transfers"] == {}


@pytest.mark.parametrize("name", REGISTRY)
def test_hot_loop_means_the_loop_around_the_rounds_own_scope(name):
    # The classifier anchors "hot-loop" on the loop around ``fd_tick``, not
    # on a depth: a loop that later nests in a round, or beside one under a
    # wave-level branch, cannot move the name to another loop unseen.
    found = staticcheck.collect_facts()[name]["round_loop"]
    nest = found.split("/", 1)[1] if found else None
    assert nest == ROUND_LOOP.get(name)


@pytest.mark.parametrize("name", MESH)
def test_a_mesh_program_keeps_its_round_loop_reduce_class(name):
    """A mesh program holds collectives and no host transfer. In a wave,
    the ROUND loop (the one around ``fd_tick``) outside a conditional
    carries all-reduces of scalar / [n] class, and on the 2-D mesh the
    scalar-class re-layout of the [c]-sized tally across the cohort axis;
    [c, n]-scale traffic is cond-gated, or it is the mask build at the head
    of each convergence, one loop level up (``wave-loop``, since PR 34).
    The fleet pair holds zero cross-tenant collectives: its step is
    straight-line (all prologue), its wave rides the vmapped loop and must
    be classified there (a vmap(while) scope must never pass as
    prologue)."""
    entry = staticcheck.collect_facts()[name]
    collectives = entry["collectives"]
    assert collectives
    assert entry["transfers"] == {}
    locations = {key.split("/")[0] for key in collectives}
    if name in ("sharded_wave", "sharded2d_wave"):
        round_loop = _round_loop(collectives)
        assert "hot-loop/all-reduce" in round_loop
        for key, group in round_loop.items():
            if key == "hot-loop/all-reduce":
                assert group["class"] in ("scalar", "n"), (key, group)
            else:
                assert name == "sharded2d_wave", (key, group)
                assert group["class"] == "scalar", (key, group)
        assert collectives["wave-loop/all-gather"]["class"] in ("n", "cn")
        assert locations <= {
            "hot-loop", "hot-loop-cond", "wave-loop", "wave-loop-cond",
        }
    elif name == "fleet3d_wave":
        assert locations == {"hot-loop"}
    else:
        assert locations <= {"prologue", "cond"}
    if name.startswith("fleet3d"):
        assert entry["cross_tenant_collectives"] == 0


#: Where a mesh program's view change lies (``hlo_facts.classify_location``)
#: and the collective KINDS that location holds: kinds, not counts or bytes,
#: so a compiler's regrouping moves nothing here.
MESH_COMMIT_KINDS = {
    "sharded_step": ("cond", {"all-gather", "all-reduce", "collective-permute"}),
    "sharded_step_telem": ("cond", {"all-gather", "all-reduce", "collective-permute"}),
    "sharded_wave": ("wave-loop-cond", {"all-gather", "all-reduce", "collective-permute"}),
    "sharded2d_wave": (
        "wave-loop-cond", {"all-gather", "all-reduce", "all-to-all", "collective-permute"},
    ),
    # under the fleet's unnamed ``vmap`` the commit is a select and lies where
    # the round does
    "fleet3d_step": (
        "prologue", {"all-gather", "all-reduce", "all-to-all", "collective-permute"},
    ),
    "fleet3d_wave": (
        "hot-loop", {"all-gather", "all-reduce", "all-to-all", "collective-permute"},
    ),
}


@pytest.mark.parametrize("name", sorted(MESH_COMMIT_KINDS))
def test_a_mesh_programs_commit_holds_the_collective_kinds_it_always_held(name):
    """``EngineState.ring_alive`` (PR 50) is kept on a mesh by the gather the
    walk always made there (``dense_arms``: no compaction over a sharded node
    axis), so the arm that holds the view change communicates as it did
    before the lane: the kinds below are the parent's, and the lane adds
    none. Nor does PR 52's repair of the observer table, which is the
    one-device programs' alone: a mesh's view change still walks every ring
    and scatters its table whole (``tests/test_spans.py`` holds the mesh
    programs to the parent's text)."""
    where, kinds = MESH_COMMIT_KINDS[name]
    found = {
        key.split("/", 1)[1]
        for key in staticcheck.collect_facts()[name]["collectives"]
        if key.split("/", 1)[0] == where
    }
    assert found == kinds


def test_2d_wave_round_loop_adds_only_scalar_kinds_to_the_live_1d_waves():
    """ISSUE 9 acceptance, against the LIVE 1-D wave: meshing the cohort
    axis must not smuggle new [n]-or-larger unconditional traffic into the
    round loop. What the 2-D round loop holds beyond the 1-D one's kinds
    (all-reduce alone) is scalar class: two all-to-alls and two all-gathers
    of the [c]-sized tally, 266 bytes between them at the audit shape."""
    facts = staticcheck.collect_facts()
    one_d = _round_loop(facts["sharded_wave"]["collectives"])
    two_d = _round_loop(facts["sharded2d_wave"]["collectives"])
    assert set(one_d) == {"hot-loop/all-reduce"}
    assert set(one_d) <= set(two_d)
    for key in set(two_d) - set(one_d):
        assert two_d[key]["class"] == "scalar", (key, two_d[key])


@pytest.mark.parametrize("name", REGISTRY)
def test_every_donated_leaf_is_aliased_or_waived(name):
    # Every donate_argnums declaration is verified against the compiled
    # artifact; on this backend all of them land.
    donation = staticcheck.collect_facts()[name]["donation"]
    assert donation["dropped"] == 0 or donation.get("waiver"), donation
    if name != "sync":
        assert donation["aliased"] == donation["donated_leaves"] > 0


@pytest.mark.parametrize("differential", ["compaction", "trace"])
def test_a_differential_drive_is_bit_identical(differential):
    # wide == widened compact, and trace-on == trace-off, leaf for leaf,
    # on a crash+join scenario: None, or the first divergent lane.
    check = {
        "compaction": device_program.compaction_differential_ok,
        "trace": device_program.trace_differential_ok,
    }[differential]
    assert check() is None


def test_the_tree_gate_reports_live_defects_without_a_committed_number(
    monkeypatch,
):
    # The sweep's bite: a host transfer, an unwaived dropped donation, a
    # cross-tenant collective and an unknown dtype in a registered program
    # are findings whatever the program was yesterday.
    clean = {
        "collectives": {}, "transfers": {}, "memory": {}, "unknown_dtypes": [],
        "donation": {"donated_leaves": 2, "aliased": 2, "dropped": 0},
    }
    sick = {
        "collectives": {}, "transfers": {"outfeed": 1}, "memory": {},
        "donation": {"donated_leaves": 2, "aliased": 1, "dropped": 1},
        "unknown_dtypes": ["q7"], "cross_tenant_collectives": 3,
    }
    waived = dict(clean, donation={
        "donated_leaves": 1, "aliased": 0, "dropped": 1, "waiver": "scalar",
    })
    monkeypatch.setattr(
        device_program, "collect_facts",
        lambda: {"clean": clean, "sick": sick, "waived": waived},
    )
    trees = [(None, rel) for rel in device_program.REGISTRY_SOURCES]
    findings = device_program.check_compiled_programs(trees)
    assert sorted(f.check for f in findings) == [
        "hlo-cross-tenant-collective", "hlo-donation-dropped",
        "hlo-transfer-budget", "hlo-unknown-dtype",
    ]
    assert all(f.message.startswith("sick: ") for f in findings)
    # Retargeted trees (no engine sources in the sweep) never pay a compile.
    assert device_program.check_compiled_programs([(None, "other.py")]) == []


def test_the_classifier_tells_the_round_loop_from_the_wave_loop():
    """A wave nests the round loop in the per-convergence loop. Given the
    round loop's body, ``hot-loop`` is that loop with whatever nests in it,
    and every other loop level reads ``wave-loop``; a loop predicate is its
    loop's level, never a gated branch; without a round loop every loop
    level is hot."""
    classify = hlo_facts.classify_location
    wave, rounds = "jit(p)/while/body", "jit(p)/while/body/while/body"
    assert classify(rounds + "/fd_tick/reduce_or", rounds) == "hot-loop"
    assert classify(rounds + "/cond/branch_1_fun/classic/gather", rounds) == (
        "hot-loop-cond"
    )
    assert classify(wave + "/while/cond/lt", rounds) == "hot-loop"
    assert classify(rounds + "/tally/while/body/add", rounds) == "hot-loop"
    assert classify(wave + "/edge_masks/gather", rounds) == "wave-loop"
    assert classify(wave + "/cond/branch_1_fun/view_change/gather", rounds) == (
        "wave-loop-cond"
    )
    # As deep as the round loop, and not it: a loop under a wave-level branch.
    assert classify(
        wave + "/cond/branch_1_fun/view_change/while/body/add", rounds
    ) == "wave-loop-cond"
    assert classify("jit(p)/while/cond/lt", rounds) == "wave-loop"
    assert classify(wave + "/edge_masks/gather", wave) == "hot-loop"
    assert classify(wave + "/edge_masks/gather") == "hot-loop"
    fleet = "jit(f)/vmap(while)/body"
    assert classify(fleet + "/tally/reduce_sum", fleet) == "hot-loop"
    assert classify("jit(f)/vmap(while)/cond/lt", fleet) == "hot-loop"
    assert classify("jit(p)/cond/branch_1_fun/classic/gather", rounds) == "cond"
    assert classify("jit(p)/edge_masks/gather", rounds) == "prologue"

    def module(*op_names):
        return "HloModule m\n" + "".join(
            f'  %v{i} = pred[] all-reduce(%x), metadata={{op_name="{name}"}}\n'
            for i, name in enumerate(op_names)
        )

    text = module(
        wave + "/edge_masks/gather", rounds + "/fd_tick/reduce_or",
        rounds + "/tally/while/body/reduce_sum",
    )
    assert hlo_facts.round_loop(text) == rounds  # not the deepest loop
    assert [r["location"] for r in hlo_facts.audit_collectives(text, 256, 8)] == [
        "wave-loop", "hot-loop", "hot-loop",
    ]
    assert hlo_facts.round_loop(module("jit(f)/vmap(fd_tick)/add")) is None
    assert hlo_facts.round_loop(module(wave + "/add")) is None
    with pytest.raises(ValueError, match="rounds under more than one loop"):
        hlo_facts.round_loop(
            module(wave + "/fd_tick/add", rounds + "/fd_tick/add")
        )


def test_2d_cohort_state_memory_is_sharded_not_replicated():
    """ISSUE 9 acceptance, asserted from memory_analysis(): with the rule
    table's cohort-axis specs, per-device [c]/[c,n] state bytes are
    1/cohort-axis-size of what the SAME 2-D mesh pays when the cohort axis
    is left unmeshed (the old `replicated-ok` layout) — the compiled
    program's own argument accounting shows the saving."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from rapid_tpu.models.virtual_cluster import (
        VirtualCluster,
        engine_step_impl,
    )
    from rapid_tpu.parallel.mesh import (
        COHORT_AXIS,
        fault_shardings,
        make_mesh,
        state_shardings,
    )

    n, c = device_program.AUDIT_N, device_program.AUDIT_C
    dc = device_program.AUDIT_COHORT_DEVICES
    dn = device_program.AUDIT_DEVICES // dc
    vc = VirtualCluster.create(
        n - 8, n_slots=n, k=device_program.AUDIT_K, h=3, l=1, fd_threshold=2,
        cohorts=c, delivery_spread=2, seed=0,
    )
    vc.assign_cohorts_roundrobin()
    cfg = vc.cfg
    mesh = make_mesh(jax.devices()[:8], shape=(dc, dn))
    rules_st = state_shardings(mesh)
    rules_ft = fault_shardings(mesh)

    def drop_cohort(sh):
        return NamedSharding(
            sh.mesh, P(*(None if ax == COHORT_AXIS else ax for ax in sh.spec))
        )

    repl_st = jax.tree.map(drop_cohort, rules_st)
    repl_ft = jax.tree.map(drop_cohort, rules_ft)

    # The rules-table side IS the registry's sharded2d_wave (identical cfg
    # + shardings): reuse its session-cached memory facts; only the
    # cohort-replicated counterfactual needs a fresh compile — the STEP
    # program, whose (state, faults) arguments are byte-identical to the
    # wave's modulo three trailing int32 scalars (12 bytes of noise
    # against a ~KB saving), at roughly half the wave's compile cost.
    del rules_st, rules_ft
    rules_args = staticcheck.collect_facts()["sharded2d_wave"]["memory"][
        "argument_bytes"
    ]
    repl_args = (
        jax.jit(
            lambda s, f: engine_step_impl(cfg, s, f),
            in_shardings=(repl_st, repl_ft),
            donate_argnums=(0,),
        )
        .lower(vc.state, vc.faults)
        .compile()
        .memory_analysis()
        .argument_size_in_bytes
    )
    cohort_leaves = (
        vc.state.report_bits, vc.state.released, vc.state.prop_mask,
        vc.faults.rx_block, vc.state.seen_down, vc.state.announced,
        vc.state.prop_hi, vc.state.prop_lo,
    )
    global_bytes = sum(int(leaf.nbytes) for leaf in cohort_leaves)
    # Cohort-meshed leaves hold 1/(dc*dn) of global per device; the
    # unmeshed layout holds 1/dn. The argument accounting must show at
    # least 90% of that saving (ε = scheduler slack on the remainder).
    expected_saving = global_bytes * (1 / dn - 1 / (dc * dn))
    saved = repl_args - rules_args
    assert saved >= 0.9 * expected_saving, (
        saved, expected_saving, repl_args, rules_args,
    )


def test_compact_entrypoints_shrink_argument_bytes():
    """ISSUE 13 acceptance, from the compiled artifact: the compact-policy
    step carries >= 30% fewer per-device argument bytes than the wide
    oracle at the audit shape (the wave's argument surface is
    byte-identical modulo three int32 control scalars — the registered
    step stands for both, the PR-9 single-representative convention), its entry signature actually carries the narrow dtypes
    (s16/s8/u8 — the policy landed, not just the formula), donation stays
    fully aliased, and its hot-loop collective and transfer budgets match
    the wide twin's (empty/none on the single-device audit programs —
    compaction adds no communication)."""
    facts = staticcheck.collect_facts()
    for wide_name, compact_name in (
        ("step", "step_compact"),
    ):
        wide_args = facts[wide_name]["memory"]["argument_bytes"]
        compact_args = facts[compact_name]["memory"]["argument_bytes"]
        assert compact_args <= 0.7 * wide_args, (
            wide_name, wide_args, compact_args,
        )
        dtypes = facts[compact_name]["parameter_dtype_bytes"]
        assert {"s16", "s8", "u8"} <= set(dtypes), dtypes
        wide_dtypes = facts[wide_name]["parameter_dtype_bytes"]
        assert set(wide_dtypes) <= {"pred", "s32", "u32"}, wide_dtypes
        donation = facts[compact_name]["donation"]
        assert donation["dropped"] == 0
        assert donation["aliased"] == donation["donated_leaves"] > 0
        # No new hot-loop collectives and no host<->device transfers vs
        # the wide twin.
        hot_wide = {
            k for k in facts[wide_name]["collectives"] if k.startswith("hot-loop/")
        }
        hot_compact = {
            k for k in facts[compact_name]["collectives"]
            if k.startswith("hot-loop/")
        }
        assert hot_compact <= hot_wide
        assert facts[compact_name]["transfers"] == facts[wide_name]["transfers"]


@pytest.mark.parametrize("name", ["step", "step_compact", "step_telem", "step_trace"])
def test_the_bytes_formulas_match_the_compiled_argument_bytes(name):
    """The bench's bytes/member formula (models/state.state_bytes_total),
    and the observers' (telemetry_bytes_total, trace_bytes_total), are the
    compiled artifact's own argument accounting: state+faults bytes at the
    audit geometry, plus the lanes and the ring where the entrypoint
    carries them, equal memory_analysis()'s argument bytes (a step carries
    no control scalars)."""
    from rapid_tpu.models.state import (
        EngineConfig,
        state_bytes_total,
        telemetry_bytes_total,
        trace_bytes_total,
    )

    cfg = EngineConfig(
        n=device_program.AUDIT_N, k=device_program.AUDIT_K, h=3, l=1,
        c=device_program.AUDIT_C, fd_threshold=2, delivery_spread=2,
        compact=int(name == "step_compact"),
    )
    formula = state_bytes_total(cfg)
    if name in ("step_telem", "step_trace"):
        formula += telemetry_bytes_total(cfg)
    if name == "step_trace":
        formula += trace_bytes_total(
            cfg._replace(trace=device_program.AUDIT_TRACE_R)
        )
    measured = staticcheck.collect_facts()[name]["memory"]["argument_bytes"]
    assert measured == formula


def test_cross_tenant_collective_is_a_blocking_finding():
    """A fleet program with a tenant-spanning collective must fail the gate
    with its own check name."""
    entry = {
        "collectives": {}, "transfers": {}, "memory": {},
        "donation": {"donated_leaves": 0, "aliased": 0, "dropped": 0},
        "unknown_dtypes": [], "cross_tenant_collectives": 2,
    }
    findings = device_program.compare_facts(
        "fleet3d_step", entry, {"cross_tenant_collectives": 0}, ("hlo.lock", 1)
    )
    assert [f.check for f in findings] == ["hlo-cross-tenant-collective"]
    assert "2 collective(s)" in findings[0].message
    assert "never communicate" in findings[0].message
    # Zero-vs-claimed drift (an inline claim of nonzero) is ordinary drift.
    entry["cross_tenant_collectives"] = 0
    findings = device_program.compare_facts(
        "fleet3d_step", entry, {"cross_tenant_collectives": 1}, ("hlo.lock", 1)
    )
    assert [f.check for f in findings] == ["hlo-lock-drift"]


def test_replica_group_parsing_covers_all_hlo_spellings():
    """The cross-tenant check's parser: explicit-list replica_groups, the
    iota v2 form (with and without transpose), collective-permute
    source_target_pairs, and the all-participants default."""
    groups = hlo_facts.collective_groups(
        'x = u32[8] all-reduce(y), replica_groups={{0,1},{2,3},{4,5},{6,7}}'
    )
    assert groups == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert hlo_facts.collective_groups(
        'x = u32[8] all-gather(y), replica_groups=[4,2]<=[8], dimensions={0}'
    ) == [[0, 1], [2, 3], [4, 5], [6, 7]]
    # Transposed iota: arange(8).reshape(2,2,2).transpose(0,2,1) rows.
    assert hlo_facts.collective_groups(
        'x = u32[8] all-gather(y), replica_groups=[4,2]<=[2,2,2]T(0,2,1)'
    ) == [[0, 2], [1, 3], [4, 6], [5, 7]]
    assert hlo_facts.collective_groups(
        'x = pred[2] collective-permute(y), source_target_pairs={{0,1},{5,4}}'
    ) == [[0, 1], [5, 4]]
    assert hlo_facts.collective_groups('x = u32[8] all-reduce(y)') is None
    # replica_groups={} is XLA's ONE-group-of-everyone spelling — it must
    # fold into the all-participants None, never parse as "no groups" (an
    # empty list would read as no communication and slip the cross-tenant
    # budget).
    assert hlo_facts.collective_groups(
        'x = u32[8] all-reduce(y), replica_groups={}'
    ) is None

    block = device_program.AUDIT_TENANT_BLOCK
    assert not hlo_facts.groups_cross_blocks([[0, 1], [4, 5]], block)
    assert hlo_facts.groups_cross_blocks([[0, 4]], block)  # spans tenants
    assert hlo_facts.groups_cross_blocks(None, block)  # all-participants


# ---------------------------------------------------------------------------
# The injected-defect acceptance case (corpus-compiled)
# ---------------------------------------------------------------------------


def test_injected_hot_loop_all_gather_fails_with_entrypoint_and_delta():
    findings = staticcheck.check_device_program(
        REPO / "rapid_tpu/models/_corpus.py",
        source=(CORPUS / "hot_loop_collective.py").read_text(),
    )
    assert len(findings) == 1
    f = findings[0]
    assert f.check == "hlo-collective-budget"
    assert "hot_loop_gather" in f.message  # the entrypoint
    assert "HOT-LOOP" in f.message and "hot-loop" in f.message  # location
    assert "all-gather" in f.message
    assert "256 bytes" in f.message and "class n" in f.message  # the delta
    assert "inline HLO_LOCK" in f.message


def test_dropped_donation_reports_xla_reason():
    findings = staticcheck.check_device_program(
        REPO / "rapid_tpu/models/_corpus.py",
        source=(CORPUS / "donation_dropped.py").read_text(),
    )
    assert len(findings) == 1
    f = findings[0]
    assert f.check == "hlo-donation-dropped"
    assert "sum_donating" in f.message
    assert "1 of 1" in f.message
    # XLA's own reason rides the finding (captured from the compile-time
    # warning); degrade gracefully if a future jax stops warning.
    assert ("not usable" in f.message) or ("no XLA reason" in f.message)


# ---------------------------------------------------------------------------
# Retrace regression: recall with fresh same-shape inputs never recompiles
# ---------------------------------------------------------------------------


def _fresh_cluster(seed: int):
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    vc = VirtualCluster.create(
        56, n_slots=64, k=4, h=3, l=1, fd_threshold=2, cohorts=4,
        delivery_spread=1, seed=seed,
    )
    vc.assign_cohorts_roundrobin()
    vc.crash([1, 2])
    return vc


def _drive_all_entrypoints(seed: int) -> None:
    vc = _fresh_cluster(seed)
    vc.sync()
    vc.step()
    rounds, decided, _, _ = vc.run_to_decision(max_steps=32)
    assert decided, rounds
    vc2 = _fresh_cluster(seed + 100)
    vc2.run_until_membership(target=54, max_steps=64, max_cuts=4)


def test_entrypoints_compile_exactly_once_across_recalls():
    # The executable check behind the retrace-hazard lint: every library
    # entrypoint (step / run_to_decision / run_until_membership / sync)
    # driven twice with FRESH same-shape inputs reuses its executable —
    # zero new XLA compiles on the second pass, pinned via the
    # engine_telemetry compile counter. A weak-type or static-argnum
    # regression at any callsite shows up here as a recompile.
    from rapid_tpu.utils import engine_telemetry

    _drive_all_entrypoints(seed=0)  # warm: compiles (or persistent-cache hits)
    with engine_telemetry.CompileDelta() as delta:
        _drive_all_entrypoints(seed=1)
    assert delta.delta.get("compiles", 0) == 0, delta.delta
