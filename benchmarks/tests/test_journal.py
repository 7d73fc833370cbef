"""The readers of the program's dispatch journal (``benchmarks/journal.py`` and
the seven ``metrics/`` stems over it): the window's selection on a hand-made
journal, and the identities on a tiny CPU rehearsal of a closed-loop cell and of
a stream. Run with ``python -m pytest benchmarks/tests/test_journal.py -q`` from
the root (not tier-1).
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks import journal
from benchmarks.metrics import (
    change_ms_drift, change_ms_max, change_ms_p50, change_unphased_ms, fetch_enqueue_ms,
    fetch_wait_share, slow_dispatches,
)
from benchmarks.tests import tiny
from rapid_tpu.utils import engine_telemetry

BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
STEMS = (
    "fetch_wait_share", "fetch_enqueue_ms", "change_unphased_ms", "change_ms_p50",
    "change_ms_max", "slow_dispatches", "change_ms_drift",
)
NEW = [stem + suffix for stem in STEMS for suffix in (".commit", ".tput")]

PHASES = ("sync", "inject_crash", "run_to_decision")
NAN = float("nan")


def _rows(*rows):
    """(phase, driver, seq, change, t_start, t_wait, t_end, rounds, cum_ms)."""
    out = np.zeros(len(rows), dtype=engine_telemetry.DISPATCH_RECORD)
    for i, (phase, driver, seq, change, t_start, t_wait, t_end, rounds, cum_ms) in enumerate(rows):
        out[i] = (PHASES.index(phase), driver, seq, change, t_start, t_wait, t_end, 0, 0.0, 4, rounds, cum_ms)
    return out


def _changes(*rows):
    out = np.zeros(len(rows), dtype=engine_telemetry.CHANGE_RECORD)
    for i, row in enumerate(rows):
        out[i] = row
    return out


def hand_made():
    """Driver 7 runs three commits (crash, sync, decision) of which the second
    and the third are the window's; driver 3 (a cluster that set-up built) has a
    ``sync`` whose sum happens to lie inside the window's range."""
    dispatches = _rows(
        ("sync", 3, 1, 0, 0.0, 0.001, 0.004, 0, 14.0),              # another driver's
        ("inject_crash", 7, 1, 1, 1.000, NAN, 1.002, 0, 2.0),          # before the window
        ("sync", 7, 2, 1, 1.002, 1.003, 1.010, 0, 8.0),
        ("run_to_decision", 7, 3, 1, 1.010, 1.012, 1.050, 4, 40.0),
        ("inject_crash", 7, 4, 2, 2.000, NAN, 2.002, 0, 4.0),          # the window's
        ("sync", 7, 5, 2, 2.003, 2.004, 2.012, 0, 17.0),
        ("run_to_decision", 7, 6, 2, 2.012, 2.015, 2.052, 4, 80.0),
        ("inject_crash", 7, 7, 3, 3.000, NAN, 3.002, 0, 6.0),
        ("sync", 7, 8, 3, 3.002, 3.003, 3.011, 0, 26.0),
        ("run_to_decision", 7, 9, 3, 3.011, 3.013, 3.171, 4, 240.0),   # a stall
        ("sync", 7, 10, 0, 4.000, 4.001, 4.003, 0, 29.0),              # after the window
    )
    changes = _changes(
        (1, 7, 1.000, 1.050, 1, 3, 0.050),
        (2, 7, 2.000, 2.052, 4, 6, 0.051),
        (3, 7, 3.000, 3.171, 7, 9, 0.171),
    )
    kept = {
        "phases": PHASES, "dispatches": dispatches, "dispatches_written": len(dispatches),
        "changes": changes, "changes_written": len(changes),
    }
    before = {"sync": 8.0, "inject_crash": 2.0, "run_to_decision": 40.0}
    after = {"sync": 26.0, "inject_crash": 6.0, "run_to_decision": 240.0}
    return kept, before, after


def test_the_window_is_the_rows_between_the_two_sums_of_the_windows_driver():
    kept, before, after = hand_made()
    found = journal.select(kept, before, after)
    assert found["dispatches"]["seq"].tolist() == [4, 5, 6, 7, 8, 9]
    assert set(found["dispatches"]["driver"].tolist()) == {7}  # driver 3's sync lies in range
    assert found["changes"]["change"].tolist() == [2, 3]       # a change is in iff its last dispatch is
    assert len(found["all"]) == len(kept["dispatches"])


def test_a_window_with_no_dispatch_or_a_ring_that_dropped_its_rows_reads_nothing():
    kept, before, after = hand_made()
    quiet = journal.select(kept, after, after)
    assert len(quiet["dispatches"]) == 0 and len(quiet["changes"]) == 0
    # the ring's oldest row is the window's and older rows were overwritten
    window_only = dict(kept, dispatches=kept["dispatches"][4:], dispatches_written=70_000)
    assert journal.select(window_only, before, after) is None
    assert journal.select(dict(window_only, dispatches_written=7), before, after) is not None
    assert journal.select(dict(kept, dispatches=kept["dispatches"][:0]), before, after) is None


def test_the_six_readers_on_the_hand_made_window(capsys):
    kept, before, after = hand_made()
    run = {"journal_window": journal.select(kept, before, after), "window_s": 2.0,
           "commit_ms": [52.0, 171.0], "attempted": 2}
    # waits: sync 8 + 8 ms, decisions 37 + 158 ms, over 2 s
    assert fetch_wait_share.read(run) == pytest.approx(100.0 * 0.211 / 2.0)
    # enqueue parts: sync 1 + 1 ms, decisions 3 + 2 ms, over two steps
    assert fetch_enqueue_ms.read(run) == pytest.approx(3.5)
    assert change_ms_p50.read(run) == pytest.approx(111.5)
    assert change_ms_max.read(run) == pytest.approx(171.0)
    # change 2: 52 ms pending, its rows cover 2 + 9 + 40; change 3's rows abut
    assert journal.unphased_ms(run["journal_window"]).tolist() == pytest.approx([1.0, 0.0], abs=1e-9)
    assert change_unphased_ms.read(run) == pytest.approx(0.5)
    # ms a round: decisions 10 and 40 against a median of 25 -> none; one more
    # ordinary decision moves the median to 10 and the stall stands out
    assert slow_dispatches.read(run) == 0
    third = dict(after, run_to_decision=280.0)
    more = dict(kept, dispatches=np.concatenate([kept["dispatches"], _rows(
        ("run_to_decision", 7, 11, 0, 5.0, 5.002, 5.040, 4, 280.0))]), dispatches_written=12)
    run = {"journal_window": journal.select(more, before, third)}
    capsys.readouterr()
    assert slow_dispatches.read(run) == 1
    printed = capsys.readouterr().out
    assert printed.startswith("slow dispatch: phase=run_to_decision seq=9 change=3 ms=160.000 rounds=4 ")
    assert "compiles=0 gc_ms=0.000 bytes=4" in printed and "wait_ms=158.000" in printed


def ramp(n: int):
    """One driver, ``n`` changes of one crash and one decision each: a change is
    pending 60 ms at the window's start and 1/8 ms less with every change, and
    all of the difference is the crash's (the host's enqueue); the decision's
    wait is 30 ms throughout."""
    dispatches, changes, crash_ms, decide_ms = [], [], 0.0, 0.0
    for i in range(n):
        t, crash_s = 10.0 + i, (28.0 - i / 8) / 1e3
        crash_ms, decide_ms = crash_ms + crash_s * 1e3, decide_ms + 32.0
        dispatches += [
            ("inject_crash", 7, 2 * i + 1, i + 1, t, NAN, t + crash_s, 0, crash_ms),
            ("run_to_decision", 7, 2 * i + 2, i + 1, t + crash_s, t + crash_s + 0.002, t + crash_s + 0.032, 4, decide_ms),
        ]
        changes.append((i + 1, 7, t, t + crash_s + 0.032, 2 * i + 1, 2 * i + 2, crash_s + 0.032))
    kept = {"phases": PHASES, "dispatches": _rows(*dispatches), "dispatches_written": 2 * n,
            "changes": _changes(*changes), "changes_written": n}
    return kept, {}, {"inject_crash": crash_ms, "run_to_decision": decide_ms}


@pytest.mark.parametrize("n, drift", [(80, 7.5), (40, 3.75), (39, 0.0)])
def test_change_ms_drift_on_a_hand_made_ramp(capsys, n, drift):
    run = {"journal_window": journal.select(*ramp(n))}
    assert len(run["journal_window"]["changes"]) == n
    enqueue, wait = journal.split_ms(run["journal_window"])
    assert wait.tolist() == pytest.approx([30.0] * n)
    assert (enqueue + wait).tolist() == pytest.approx(journal.change_ms(run["journal_window"]).tolist())
    capsys.readouterr()
    # quarters of n/4 changes: their medians lie 3n/4 changes apart, 1/8 ms a change
    assert change_ms_drift.read(run) == pytest.approx(drift)
    printed = capsys.readouterr().out
    if not drift:
        assert printed == ""  # fewer than 40 changes: no quarters worth a line
        return
    assert printed.startswith("change_ms by quarter of the window, median ms (enqueue, wait): ")
    quarters = [
        [float(number) for number in quarter.replace("(", "").replace(")", "").replace(",", "").split()]
        for quarter in printed.split(": ", 1)[1].split(" | ")
    ]
    first, last = 60.0 - (n / 4 - 1) / 16, 60.0 - (7 * n / 4 - 1) / 16
    assert len(quarters) == 4
    assert quarters[0] == pytest.approx([first, first - 30.0, 30.0], abs=1e-3)  # the slow start is
    assert quarters[3] == pytest.approx([last, last - 30.0, 30.0], abs=1e-3)    # the enqueue's


def test_the_fourteen_metrics_are_additions_to_per_layer():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(NEW[0])  # appended together, behind everything PR 52 had
    assert names[first:] == NEW  # ISSUE 53's twelve in its order, then the drift's two
    assert "link_probes_lost_per_round.tput" in names[:first]
    units = dict(zip(STEMS, ("%", "ms", "ms", "ms", "ms", "count", "ms")))
    for name in NEW:
        stem, split = name.split(".")
        blocked = entries["host_blocked_share." + split]
        assert entries[name] == {
            "name": name, "unit": units[stem], "better": "lower", "source": "program_span",
            "layer": blocked["layer"], "moves": blocked["moves"], "workloads": blocked["workloads"],
        }
        assert os.path.exists(os.path.join(tiny.REPO, "benchmarks", "metrics", stem + ".py"))


#: ``run.py`` with the run kept, so that what the readers were handed can be
#: held against the harness's own numbers after the result line is out.
IDENTITY_STUB = '''
import json, os, statistics, sys, time
T0 = time.perf_counter()
sys.path.insert(0, os.getcwd())
import numpy as np
from benchmarks import harness, journal, targets
seen = {}
find = journal._find
def keep(run):
    seen["run"] = run
    return find(run)
journal._find = keep
code = harness.main(sys.argv[1:], T0)
run = seen["run"]
found = run["journal_window"]
rows, names = found["dispatches"], found["phases"]
before, after = run["counters_before"]["dispatch_ms"], run["counters_after"]["dispatch_ms"]
phases = {}
for name in after:
    mine = rows[rows["phase"] == names.index(name)] if name in names else rows[:0]
    phases[name] = [float(journal.durations_ms(mine).sum()), after[name] - before.get(name, 0.0)]
waited = journal.waiting(rows)
blocking = waited[np.isin(waited["phase"], [names.index(p) for p in targets.BLOCKING_PHASES if p in names])]
print("IDENTITY " + json.dumps({
    "phases": phases,
    "blocked_ms": float(((blocking["t_wait"] - blocking["t_start"]) + (blocking["t_end"] - blocking["t_wait"])).sum() * 1e3),
    "blocked_ms_by_sums": sum(after.get(p, 0.0) - before.get(p, 0.0) for p in targets.BLOCKING_PHASES),
    "unwaited_blocking_rows": int((np.isnan(rows["t_wait"]) & np.isin(
        rows["phase"], [names.index(p) for p in targets.BLOCKING_PHASES if p in names])).sum()),
    "changes": len(found["changes"]), "steps": journal.steps(run),
    "unphased_min_ms": float(journal.unphased_ms(found).min()),
    "change_ms_p50": float(np.median(journal.change_ms(found))),
    "commit_ms_p50": statistics.median(run["commit_ms"]) if run.get("commit_ms") else None,
    "window_ms": run["window_s"] * 1e3,
}))
sys.exit(code)
'''

#: ``run.py`` over a program that keeps no journal: the parent's.
PARENT_STUB = '''
import os, sys, time
T0 = time.perf_counter()
sys.path.insert(0, os.getcwd())
from rapid_tpu.utils import engine_telemetry
del engine_telemetry.journal_snapshot
from benchmarks import harness
sys.exit(harness.main(sys.argv[1:], T0))
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_journal")))
    for name, text in (("identity_stub.py", IDENTITY_STUB), ("parent_stub.py", PARENT_STUB)):
        with open(os.path.join(where, name), "w", encoding="utf-8") as handle:
            handle.write(text)
    return where


@pytest.fixture(scope="module", params=["cluster-100k.churn5", "paper-fleet-1k.trickle"])
def rehearsal(request, checkout):
    done = tiny.run_cell(checkout, request.param, trace=1, script="identity_stub.py")
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    identity = next(json.loads(l[len("IDENTITY "):]) for l in lines if l.startswith("IDENTITY "))
    result = next(json.loads(l) for l in reversed(lines) if l.startswith("{"))
    return request.param, result, identity


def test_a_traced_tiny_run_reports_the_seven_metrics_of_its_split(rehearsal):
    cell, result, _ = rehearsal
    split = ".commit" if cell.endswith("churn5") else ".tput"
    assert result["correct"]
    got = {stem: result["metrics"][stem + split] for stem in STEMS}
    assert not any(stem + other in result["metrics"] for stem in STEMS for other in {".commit", ".tput"} - {split})
    assert got["fetch_wait_share"]["unit"] == "%" and got["slow_dispatches"]["unit"] == "count"
    blocked = result["metrics"]["host_blocked_share" + split]["value"]
    # the wait is a part of the blocking phases' time (churn5's admissibility
    # fetch waits too, outside them, and is a few tenths of a millisecond)
    assert 0 < got["fetch_wait_share"]["value"] <= blocked + 1.0
    assert got["fetch_enqueue_ms"]["value"] > 0 and got["change_unphased_ms"]["value"] >= 0
    assert 0 < got["change_ms_p50"]["value"] <= got["change_ms_max"]["value"]
    assert got["slow_dispatches"]["value"] >= 0
    assert abs(got["change_ms_drift"]["value"]) <= got["change_ms_max"]["value"]


def test_the_journals_durations_are_the_histograms_sums_phase_by_phase(rehearsal):
    _, _, identity = rehearsal
    assert identity["phases"]
    for name, (by_rows, by_sums) in identity["phases"].items():
        assert by_rows == pytest.approx(by_sums, rel=1e-6, abs=1e-9), name
    # enqueue + wait over the blocking phases IS host_blocked_share's numerator
    assert identity["unwaited_blocking_rows"] == 0
    assert identity["blocked_ms"] == pytest.approx(identity["blocked_ms_by_sums"], rel=1e-6)
    assert 0 < identity["blocked_ms"] < identity["window_ms"]


def test_a_change_is_a_step_and_none_is_shorter_than_its_dispatches(rehearsal):
    cell, _, identity = rehearsal
    assert identity["unphased_min_ms"] >= 0.0
    if cell.endswith("churn5"):
        # one commit, one change, seen from inside the same two ends
        assert identity["changes"] == identity["steps"]
        assert identity["change_ms_p50"] == pytest.approx(
            identity["commit_ms_p50"], abs=max(0.3, 0.02 * identity["commit_ms_p50"]))
    else:
        # a wave is a change; those still in flight at the window's last
        # submit are retired by its drain, inside the window
        assert identity["changes"] == identity["steps"]


def test_a_program_without_a_journal_leaves_the_metrics_out(checkout):
    done = tiny.run_cell(checkout, "cluster-100k.churn5", trace=1, script="parent_stub.py")
    result = tiny.result_of(done)
    assert result["correct"]
    assert not set(NEW) & set(result["metrics"])
    assert {"host_blocked_share.commit", "sync_ms.commit", "device_idle_share.commit"} <= set(result["metrics"])
