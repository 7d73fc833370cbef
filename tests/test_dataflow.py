"""Proof + unit gate for the jaxpr dataflow provenance family.

The expensive half traces the REAL registry once per session (compile
free — ``jitted.trace``) and asserts the two proofs on the live trace, one
case an entrypoint: no observer lane (``telem.*`` / ``trace.*``) sits in
the influence set of a ``state.*`` / ``events.*`` output, and every output
lane of a fleet program keeps the tenant axis with no axis rule falling
back. The cheap half runs synthetic jaxprs through the interpreters — most
importantly the scan-carry / donated-buffer aliasing cases where a
union-carry interpreter would fabricate influence edges the per-slot
fixpoint must not, and the same-width bitcast the ring walk's scan word
takes.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import staticcheck  # noqa: E402
from analysis import dataflow, device_program  # noqa: E402
from analysis.core import Finding  # noqa: E402
from tests.test_hlo_gate import REGISTRY  # noqa: E402

#: What the proofs trace: the compiled registry and the meshless fleet step.
TRACED = REGISTRY + ("fleet_step",)
FLEET = ("fleet3d_step", "fleet3d_wave", "fleet_step")


# ---------------------------------------------------------------------------
# The proofs over the real registry (session-cached trace)
# ---------------------------------------------------------------------------


def _findings_about(name):
    return [
        str(f) for f in staticcheck.collect_dataflow()[1]
        if f.message.startswith(name + ": ")
    ]


@pytest.mark.parametrize("name", TRACED)
def test_no_observer_lane_influences_the_engine(name):
    proofs, _ = staticcheck.collect_dataflow()
    assert set(proofs["observer_silent"]) == set(TRACED)
    assert proofs["observer_silent"][name] is True, _findings_about(name)


@pytest.mark.parametrize("name", FLEET)
def test_every_output_lane_of_a_fleet_program_keeps_the_tenant_axis(name):
    proofs, _ = staticcheck.collect_dataflow()
    assert set(proofs["tenant_isolation"]) == set(FLEET)
    proof = proofs["tenant_isolation"][name]
    assert proof["axis_rule_fallbacks"] == [], proof
    assert proof["mixed_outputs"] == [], _findings_about(name)
    assert proof["proven"] is True


def test_the_tree_gate_reports_the_live_proofs_findings(monkeypatch):
    # The sweep reports what the proofs find on the live trace; trees
    # without the engine sources (a tmp_path unit-test tree) never pay a
    # registry trace.
    leak = Finding(
        device_program.REGISTRY_REL, 1, "dataflow-observer-effect",
        "step_telem: observer lane(s) telem.tl_enq influence subject lane "
        "state.cuts",
    )
    monkeypatch.setattr(dataflow, "collect_dataflow", lambda: ({}, [leak]))
    trees = [(ast.parse(""), src) for src in device_program.REGISTRY_SOURCES]
    assert staticcheck.check_dataflow_proofs(trees) == [leak]
    other = [(ast.parse(""), "some/other/module.py")]
    assert staticcheck.check_dataflow_proofs(other) == []


# ---------------------------------------------------------------------------
# Taint interpreter: carry aliasing must not fabricate influence edges
# ---------------------------------------------------------------------------


def _out_taints(jitted, args):
    entry = dataflow._trace_entry("probe", {"jit": jitted, "args": args})
    n = len(entry["in_labels"])
    return dataflow._taint_closed(
        entry["closed"], [frozenset([i]) for i in range(n)]
    )


def test_scan_carry_slots_stay_separate():
    # carry = (a, b); the body never mixes them. A union-carry
    # interpreter (one taint set for the whole carry) would report a's
    # lineage in b_final and vice versa — the per-slot fixpoint must not.
    def step(carry, x):
        a, b = carry
        return (a + 1.0, b * 2.0), b + x

    jitted = jax.jit(lambda a, b, xs: jax.lax.scan(step, (a, b), xs))
    args = (
        jnp.float32(0.0),
        jnp.float32(1.0),
        jnp.zeros((4,), jnp.float32),
    )
    a_final, b_final, ys = _out_taints(jitted, args)
    assert a_final == frozenset([0])
    assert b_final == frozenset([1])
    assert ys == frozenset([1, 2])


def test_a_same_width_bitcast_keeps_the_tenant_axis_and_a_narrowing_one_does_not():
    # The ring walk's scan word is a uint32 <-> int32 bitcast: elementwise,
    # so a vmap'd round trip keeps every tenant in its own row. A bitcast
    # to a narrower dtype grows a trailing dimension the axis interpreter
    # does not track: reported, conservatively.
    tenants = 4

    def axes(fn):
        spec = {
            "jit": jax.jit(jax.vmap(fn)),
            "args": (jnp.arange(tenants * 8, dtype=jnp.uint32).reshape(tenants, 8),),
        }
        entry = dataflow._trace_entry("probe", spec)
        fallbacks = []
        out = dataflow._axis_closed(
            entry["closed"], dataflow._tenant_in_axes(entry, spec, tenants),
            tenants, fallbacks,
        )
        return out, fallbacks

    def round_trip(word):
        signed = jax.lax.bitcast_convert_type(word, jnp.int32)
        return jax.lax.bitcast_convert_type(signed + 1, jnp.uint32)

    assert axes(round_trip) == ([0], [])
    out, fallbacks = axes(lambda word: jax.lax.bitcast_convert_type(word, jnp.uint8))
    assert out == [dataflow._MIXED]
    assert fallbacks == ["bitcast_convert_type"]


def test_donated_while_carry_reuse_keeps_slots_apart():
    # Donated buffers mean the compiled program reuses the carry slots in
    # place — at the jaxpr level the slots are still distinct variables,
    # and the fixpoint must keep them apart. The loop counter drives the
    # predicate, so BOTH data slots legitimately inherit its taint
    # (iteration count is influence); the data slots must not inherit
    # each other's.
    def loop(state):
        def cond(s):
            return s[0] < 3

        def body(s):
            return (s[0] + 1, s[1] + 1.0, s[2] * 2.0)

        return jax.lax.while_loop(cond, body, state)

    jitted = jax.jit(loop, donate_argnums=(0,))
    args = ((jnp.int32(0), jnp.float32(0.0), jnp.float32(1.0)),)
    counter, a_final, b_final = _out_taints(jitted, args)
    assert counter == frozenset([0])
    assert a_final == frozenset([0, 1])
    assert b_final == frozenset([0, 2])


# ---------------------------------------------------------------------------
# Corpus mode plumbing (the probes themselves live in the lint corpus)
# ---------------------------------------------------------------------------


def test_corpus_mode_skips_files_without_the_marker(tmp_path):
    probe = tmp_path / "plain.py"
    probe.write_text("X = 1\n")
    assert staticcheck.check_dataflow(probe) == []


def test_corpus_mode_reports_a_broken_probe_as_a_finding(tmp_path):
    probe = tmp_path / "broken_probe.py"
    probe.write_text(
        "DATAFLOW_AUDIT_PROGRAMS = {}\nraise RuntimeError('boom')\n"
    )
    findings = staticcheck.check_dataflow(probe)
    assert [f.check for f in findings] == ["dataflow-probe-error"]
    assert "failed to execute" in findings[0].message
