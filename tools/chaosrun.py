"""Run, fuzz, and replay deterministic chaos scenarios (rapid_tpu/sim).

Three subcommands:

``run``     one named scenario family at one seed (or a schedule JSON file),
            through the full oracle battery, writing the repro artifact
            directory (schedule + per-node flight recordings + outcome) and,
            with ``--chrome``, a Chrome trace-event file of the merged
            timeline with fault-injection annotations (via tools/traceview).

``fuzz``    N seeded random schedules; every oracle violation is shrunk to a
            minimal repro and written under the output directory. With
            ``--fleet B`` the round instead compiles B mixed scenarios —
            honest, adversarial (Byzantine false alerts against the H/L
            watermarks), and hier cross-product families — onto one batched
            engine fleet (rapid_tpu/tenancy/chaos.py), resolves them in wave
            dispatches plus the stability soak, and prints wall clock,
            first-class scenarios/sec, and per-family violation tallies;
            a violating tenant is shrunk (quiescent-filler probes at the
            same fleet shape) and written as a single-tenant fleet repro.

``replay``  re-run a written repro directory; exits nonzero iff the recorded
            violations reproduce (they must — a repro that stops failing is
            itself news worth printing). Fleet repros (the ``fleet.json``
            marker) replay through the engine fleet path with the recorded
            per-tenant knobs; quarantine exports (``fleet.json`` carrying
            ``kind: "quarantine"`` — the serving supervisor's poisoned-
            tenant artifact, rapid_tpu/serving/recovery.py) reload the
            captured state slice and re-run the deterministic health scan;
            sim repros replay through the host runner. Fleet repros written
            with a ``trace.json`` artifact (the verify run's decoded
            round-trace ring) additionally get a round-granular diff: a
            divergent replay names the FIRST round where the two engine
            histories fork, not just that the verdicts changed.

Usage:

    python tools/chaosrun.py run partition_heal --seed 3 --artifacts /tmp/r
    python tools/chaosrun.py run --schedule repro/schedule.json
    python tools/chaosrun.py fuzz --seeds 20 --out /tmp/fuzz
    python tools/chaosrun.py fuzz --fleet 256 --out /tmp/fleet
    python tools/chaosrun.py replay /tmp/fuzz/seed7
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path
from typing import List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from rapid_tpu.utils.platform import force_platform  # noqa: E402

force_platform("cpu")  # chaos simulation is a host workload; never take the chip

from rapid_tpu.sim import fuzz as simfuzz  # noqa: E402
from rapid_tpu.sim.faults import FaultSchedule, ScheduleError  # noqa: E402
from rapid_tpu.sim.oracles import check_all  # noqa: E402


def _write_chrome(artifacts: Path, out: str) -> None:
    import traceview

    events = traceview.merge_events(traceview.scenario_snapshots(artifacts))
    traceview.write_chrome(events, out)
    print(f"wrote {out} ({len(events)} events)")


def cmd_run(args: argparse.Namespace) -> int:
    if args.schedule:
        schedule = FaultSchedule.from_json(Path(args.schedule).read_text())
    else:
        if not args.family:
            print("chaosrun run: need a family name or --schedule", file=sys.stderr)
            return 2
        schedule = simfuzz.scenario_family(args.family, args.seed)
    result = simfuzz.run_schedule(schedule)
    violations = check_all(result)
    artifacts = Path(
        args.artifacts
        or tempfile.mkdtemp(prefix=f"chaosrun-{schedule.name.replace('/', '-')}-")
    )
    simfuzz.write_repro(result, violations, artifacts)
    print(f"scenario {schedule.name or '(file)'}: {len(result.cuts)} cut(s), "
          f"converged={result.final_converged}, artifacts in {artifacts}")
    if args.chrome:
        _write_chrome(artifacts, args.chrome)
    for v in violations:
        print(f"VIOLATION {v}")
    return 1 if violations else 0


def cmd_fuzz(args: argparse.Namespace) -> int:
    out = Path(args.out) if args.out else Path(tempfile.mkdtemp(prefix="chaosfuzz-"))
    if args.fleet:
        return _fuzz_fleet(args, out)
    seeds = range(args.base_seed, args.base_seed + args.seeds)
    summaries = simfuzz.fuzz(seeds, out_dir=out)
    failing = [s for s in summaries if s["violations"]]
    for s in summaries:
        status = "FAIL" if s["violations"] else "ok"
        extra = (
            f" -> shrunk {s['events']}->{s['shrunk_events']} events, "
            f"repro {s.get('repro', '(not written)')}"
            if s["violations"]
            else ""
        )
        print(f"seed {s['seed']}: {status}{extra}")
        for v in s["violations"]:
            print(f"  {v}")
    print(f"{len(summaries) - len(failing)}/{len(summaries)} seeds clean; "
          f"repros under {out}" if failing else
          f"{len(summaries)}/{len(summaries)} seeds clean")
    return 1 if failing else 0


def _fuzz_fleet(args: argparse.Namespace, out: Path) -> int:
    """The batched adversarial round: B scenarios per dispatch through the
    tenancy fleet, scenarios/sec as the headline, per-family tallies."""
    from rapid_tpu.tenancy import chaos as tchaos

    summary = tchaos.fuzz_fleet(
        args.fleet, base_seed=args.base_seed, out_dir=out
    )
    for family in sorted(summary["families"]):
        total = summary["families"][family]
        bad = summary["family_violations"].get(family, 0)
        print(f"family {family}: {total - bad}/{total} clean"
              + (f" ({bad} violating)" if bad else ""))
    for v in summary["violations"]:
        print(f"VIOLATION {v}")
    if "shrunk_tenant" in summary:
        print(f"shrunk tenant {summary['shrunk_tenant']} to "
              f"{summary['shrunk_events']} event(s) in "
              f"{summary['shrink_runs']} probe run(s); repro "
              f"{summary.get('repro', '(not written)')}")
    print(f"{summary['tenants']} scenarios in {summary['dispatches']} "
          f"dispatch(es), {summary['total_cuts']} view changes, "
          f"{summary['wall_ms']:.0f} ms wall — "
          f"{summary['scenarios_per_sec']:.1f} scenarios/sec")
    return 1 if summary["violations"] else 0


def cmd_replay(args: argparse.Namespace) -> int:
    if (Path(args.repro) / "fleet.json").exists():
        return _replay_fleet(args)
    recorded_path = Path(args.repro) / "violations.txt"
    recorded = (
        [line for line in recorded_path.read_text().splitlines()
         if line and line != "(none)"]
        if recorded_path.exists()
        else []
    )
    result, violations = simfuzz.replay(args.repro)
    for v in violations:
        print(f"VIOLATION {v}")
    if recorded and sorted(map(str, violations)) != sorted(recorded):
        print("chaosrun replay: violations DIVERGED from the recorded repro:",
              file=sys.stderr)
        for line in recorded:
            print(f"  recorded: {line}", file=sys.stderr)
        return 1
    if args.chrome:
        with tempfile.TemporaryDirectory() as fresh:
            simfuzz.write_repro(result, violations, fresh)
            _write_chrome(Path(fresh), args.chrome)
    return 1 if violations else 0


def _replay_fleet(args: argparse.Namespace) -> int:
    """Replay a single-tenant FLEET repro through the engine fleet path:
    shrinker artifacts (the per-tenant quiescent-filler repro) re-run the
    recorded schedule with the recorded knobs; quarantine exports (the
    serving supervisor's ``kind: "quarantine"`` marker) reload the captured
    poisoned state slice and re-run the deterministic health scan."""
    from rapid_tpu.tenancy import chaos as tchaos

    recorded_path = Path(args.repro) / "violations.txt"
    recorded = (
        [line for line in recorded_path.read_text().splitlines()
         if line and line != "(none)"]
        if recorded_path.exists()
        else []
    )
    recipe = json.loads((Path(args.repro) / "fleet.json").read_text())
    if recipe.get("kind") == "quarantine":
        from rapid_tpu.serving import recovery

        violations = recovery.replay_quarantine_repro(args.repro)
    else:
        _result, violations = tchaos.replay_fleet_repro(args.repro)
    for v in violations:
        print(f"VIOLATION {v}")
    diverged = recorded and sorted(map(str, violations)) != sorted(recorded)
    if recipe.get("kind") != "quarantine":
        # Round-granular divergence instrument: diff the replayed engine's
        # decoded trace ring against the write-time trace.json. Pre-trace
        # repro dirs (no artifact) skip this silently — they stay
        # replayable on verdicts alone.
        trace_diff = tchaos.replay_trace_divergence(args.repro)
        if trace_diff is not None:
            fork = trace_diff["first_divergent_round"]
            if fork is None:
                print(
                    f"trace: rings agree record-for-record "
                    f"({trace_diff['replayed_rounds']} round(s) recorded)"
                )
            else:
                print(
                    f"trace: round histories FORK at round {fork} "
                    f"(written {trace_diff['written_rounds']} round(s), "
                    f"replayed {trace_diff['replayed_rounds']})",
                    file=sys.stderr,
                )
                diverged = True
    if diverged:
        print("chaosrun replay: violations DIVERGED from the recorded repro:",
              file=sys.stderr)
        for line in recorded:
            print(f"  recorded: {line}", file=sys.stderr)
        return 1
    return 1 if violations else 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="chaosrun",
        description="deterministic chaos scenarios: run, fuzz, replay",
    )
    sub = parser.add_subparsers(dest="command", required=True)

    run_p = sub.add_parser("run", help="run one named scenario or schedule file")
    # choices= comes straight from the FAMILIES registry (never a re-typed
    # list): a typo'd family errors with the real vocabulary, and the
    # chaosvocab lint pins that this wiring cannot drift.
    run_p.add_argument("family", nargs="?", default=None,
                       choices=sorted(simfuzz.FAMILIES),
                       help="scenario family (hier-profile families boot the "
                            "two-level hierarchical protocol, rapid_tpu/hier; "
                            "traceview lanes their artifacts by cohort)")
    run_p.add_argument("--seed", type=int, default=0)
    run_p.add_argument("--schedule", default=None, metavar="JSON",
                       help="run this schedule file instead of a named family")
    run_p.add_argument("--artifacts", default=None, metavar="DIR",
                       help="repro artifact directory (default: a fresh tmpdir)")
    run_p.add_argument("--chrome", default=None, metavar="OUT.json",
                       help="also write a Chrome trace of the merged timeline")
    run_p.set_defaults(fn=cmd_run)

    fuzz_p = sub.add_parser("fuzz", help="fuzz N random schedules, shrink failures")
    fuzz_p.add_argument("--seeds", type=int, default=10)
    fuzz_p.add_argument("--base-seed", type=int, default=0)
    fuzz_p.add_argument("--out", default=None, metavar="DIR")
    fuzz_p.add_argument("--fleet", type=int, default=0, metavar="B",
                        help="instead of host-runner seeds, compile B mixed "
                             "scenarios (honest + adversarial + hier "
                             "cross-product families, independent seeds) "
                             "onto one batched engine fleet and report "
                             "scenarios/sec + per-family violation tallies; "
                             "violating tenants shrink to single-tenant "
                             "fleet repros")
    fuzz_p.set_defaults(fn=cmd_fuzz)

    replay_p = sub.add_parser("replay", help="re-run a written repro directory")
    replay_p.add_argument("repro")
    replay_p.add_argument("--chrome", default=None, metavar="OUT.json")
    replay_p.set_defaults(fn=cmd_replay)

    args = parser.parse_args(argv)
    try:
        return args.fn(args)
    except (ScheduleError, FileNotFoundError, json.JSONDecodeError) as exc:
        print(f"chaosrun: {exc}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
