"""Sharded-collective audit: compile the engine under a device mesh and
classify every cross-device collective in the resulting HLO.

Substantiates parallel/mesh.py's communication claims (VERDICT r2 missing #4)
with compiled evidence rather than docstring assertion:

  - the convergence hot loop's unconditional collectives are psum-class
    all-reduces of scalar/[c] operands only;
  - the per-edge [n]-sized gathers (observer aliveness + packed rx-block
    words, rapid_tpu/models/virtual_cluster.py::_edge_masks) sit OUTSIDE the
    while body — hoisted once per convergence;
  - anything [c,n]-sized or larger moves only inside lax.cond branches that
    execute on view changes (sort-free topology rebuild), classic-fallback attempts, or
    the implicit-invalidation pass.

This CLI is the evidence-table front end of the ``device_program`` check
family (tools/analysis/device_program.py): classification lives in
``rapid_tpu/parallel/hlo_facts.py`` (re-exported by rapid_tpu/parallel/audit.py,
pinned by tests/test_parallel.py), fact extraction — including donation
outcomes and XLA memory analysis — in ``device_program.extract_facts``. The
difference from the gate: the gate compiles at fixed small audit shapes
and asserts on the live facts; this tool compiles at evidence scale (10K+
slots) and writes the full table.

    python tools/collective_audit.py [--n 10240] [--devices 8] \
        [--cohort-devices 2] [--out FILE]

``--cohort-devices D`` audits the 2-D ``('cohort', 'nodes')`` mesh (D rows
by devices/D columns — the 1M+ headline configuration's layout) instead of
the default 1-D ``('nodes',)`` mesh.

Writes a JSON table and prints a markdown summary (EVALUATION.md
§collectives is generated from this).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=10240)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--cohorts", type=int, default=64)
    parser.add_argument(
        "--cohort-devices", type=int, default=0, metavar="D",
        help="audit the 2-D ('cohort','nodes') mesh with D cohort rows "
             "(must divide --devices and --cohorts); 0 = the 1-D mesh",
    )
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    if args.cohort_devices and (
        args.devices % args.cohort_devices or args.cohorts % args.cohort_devices
    ):
        parser.error("--cohort-devices must divide --devices and --cohorts")

    from rapid_tpu.utils.platform import force_platform

    force_platform("cpu", n_host_devices=args.devices)
    import jax

    from analysis.device_program import _compile_program, extract_facts
    from analysis.hlo_facts import collective_violations
    from rapid_tpu.models.virtual_cluster import (
        VirtualCluster,
        run_to_decision_impl,
    )
    from rapid_tpu.parallel.mesh import (
        fault_shardings,
        make_mesh,
        make_sharded_step,
        shard_faults,
        shard_state,
        state_shardings,
    )

    n_slots = args.n
    n_members = n_slots - args.devices  # leave a few dead slots
    vc = VirtualCluster.create(
        n_members, n_slots=n_slots, k=10, h=9, l=4, fd_threshold=2,
        cohorts=args.cohorts, delivery_spread=2, seed=0,
    )
    vc.assign_cohorts_roundrobin()
    if args.cohort_devices:
        mesh = make_mesh(
            jax.devices()[: args.devices],
            shape=(args.cohort_devices, args.devices // args.cohort_devices),
        )
    else:
        mesh = make_mesh(jax.devices()[: args.devices])
    state = shard_state(vc.state, mesh)
    faults = shard_faults(vc.faults, mesh)
    n_leaves = len(jax.tree_util.tree_leaves(state))

    report = {"n_slots": n_slots, "cohorts": args.cohorts,
              "devices": args.devices,
              "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
              "programs": {}, "facts": {}}
    cfg = vc.cfg

    # Program 1: the single-dispatch CONVERGENCE loop (the product path for
    # run_to_decision) — while_loop around the round body, edge gathers
    # hoisted into the prologue. Donating, like the product entrypoint.
    conv = jax.jit(
        lambda state, faults: run_to_decision_impl(cfg, state, faults, 96),
        in_shardings=(state_shardings(mesh), fault_shardings(mesh)),
        donate_argnums=(0,),
    )
    # Program 2: one engine step (the per-round driver used by the sharded
    # dry run / host-driven stepping) — pays the prologue gathers per call.
    step = make_sharded_step(cfg, mesh)

    for name, jitted, spec_args in (
        ("convergence_loop", conv, (state, faults)),
        ("engine_step", step, (state, faults)),
    ):
        compiled, reasons = _compile_program({"jit": jitted, "args": spec_args})
        facts = extract_facts(
            compiled, n_leaves, n_slots, args.cohorts, donation_reasons=reasons
        )
        report["programs"][name] = facts.pop("rows")
        report["facts"][name] = facts

    violations = collective_violations(report["programs"]["convergence_loop"])
    report["violations"] = violations
    report["ok"] = not any(violations.values())

    # Markdown summary.
    def summarize(rows):
        agg = {}
        for r in rows:
            key = (r["location"], r["kind"], r["source"])
            agg.setdefault(key, {"count": 0, "bytes": 0})
            agg[key]["count"] += 1
            agg[key]["bytes"] += r["bytes"]
        return agg

    print("\n| program | location | kind | source | count | payload bytes |")
    print("|---|---|---|---|---|---|")
    for prog, rows in report["programs"].items():
        for (loc, kind, src), v in sorted(summarize(rows).items()):
            print(f"| {prog} | {loc} | {kind} | {src} | {v['count']} | {v['bytes']} |")
    for prog, facts in report["facts"].items():
        d = facts["donation"]
        m = facts["memory"] or {}
        print(
            f"\n{prog}: donation {d['aliased']}/{d['donated_leaves']} aliased"
            f" ({d['dropped']} dropped), temp {m.get('temp_bytes', '?')} B,"
            f" args {m.get('argument_bytes', '?')} B"
        )
    print(f"\nok={report['ok']} violations=" + json.dumps(
        {k: len(v) for k, v in violations.items()}))

    out = args.out or "evidence/round3/collective_audit.json"
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
