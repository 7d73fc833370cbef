"""The files this benchmark gained with the program's own spans: the scope
reduction (on a recorded chip trace, on hand-made traces and on a hand-made
``.xplane.pb``), the six readers of the new dispatch phases, and the traced
tiny runs that report them. Run with ``python -m pytest benchmarks/tests -q``
from the repository's root.
"""

from __future__ import annotations

import json
import os
import statistics

import pytest

from benchmarks import scope_reduce
from benchmarks.harness import load_reader
from benchmarks.tests import tiny

SCOPES = scope_reduce.scope_list()
DATA = os.path.join(os.path.dirname(__file__), "data")
#: Read off ``scope_reduce.reduce`` of the recording when it was made.
with open(os.path.join(DATA, "recorded_scope_trace.expected.json"), encoding="utf-8") as _handle:
    RECORDED = json.load(_handle)
NEW_COMMIT = ["inject_crash_ms.commit", "inject_join_admit_ms.commit",
              "inject_join_place_ms.commit", "sync_ms.commit"]
NEW_STREAM = ["stream_enqueue_ms.tput", "stream_backpressure_ms.tput"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.checkout(str(tmp_path_factory.mktemp("bench_scopes")))


# -- the reduction on a recorded chip trace ---------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return scope_reduce.undump(os.path.join(DATA, "recorded_scope_trace.json"))


def test_reduction_of_the_recorded_chip_trace(recorded):
    """One step of ``cluster-100k.churn5`` on the chip (my chip run, PR 25):
    restore, inject, resolve in eight rounds, check."""
    out = scope_reduce.reduce(recorded, SCOPES)
    assert out["busy_s"] == pytest.approx(RECORDED["busy_s"], abs=1e-9)
    assert out["scoped_share"] > 0.98 and 0.75 < out["by_path_share"] < out["scoped_share"]
    for scope, seconds in RECORDED["scope_s"].items():
        assert out["scope_s"][scope] == pytest.approx(seconds, abs=1e-9), scope
    loop = out["modules"]["jit_run_until_membership_impl"]
    # eight rounds of one commit, each with one call of the Mosaic kernel; one
    # view change and two builds of the edge masks a commit
    assert loop["deliver"]["calls"] == loop["cut_detection"]["calls"] == loop["tally"]["calls"] == 8
    assert loop["view_change"]["calls"] == 1 and loop["edge_masks"]["calls"] == 1
    assert loop["invalidation"]["calls"] == RECORDED["invalidation_rounds"]
    assert set(out["modules"]["jit_predecessor_of_keys"]) == {"join_predecessors"}
    ops = {(op, module): (scope, how) for op, module, scope, how, _, _ in out["ops"]}
    # the ledger's top operations of this cell, by the names its breakdowns use
    assert ops[("fusion.17 s32[1025000]", "jit_run_until_membership_impl")] == ("view_change", "path")
    assert ops[("fusion.15 pred[1025000]", "jit_run_until_membership_impl")] == ("edge_masks", "path")
    assert ops[("fusion.22 s32[1025000]", "jit_run_until_membership_impl")] == ("view_change", "neighbour")
    assert ops[("delivery_new_bits_pallas.1 u32[64,102528]", "jit_run_until_membership_impl")] == ("deliver", "path")
    assert {name: n for name, (_, n) in out["span_s"].items()} == {
        "bench:restore": 1, "bench:inject": 1, "bench:resolve": 1, "bench:check": 1,
        "rapid:inject_crash": 1, "rapid:inject_join_admit": 1, "rapid:inject_join_place": 1,
        "rapid:sync": 1, "rapid:run_until_membership": 1,
    }
    # every idle gap over half a millisecond inside the injection is under a program span
    inject = [row for row in out["gaps"]["by_span"] if row[0] == "bench:inject"]
    assert inject and all(row[1] and row[1].startswith("rapid:") for row in inject)


def test_inside_a_span_only_that_spans_operations_count(recorded):
    out = scope_reduce.reduce(recorded, SCOPES, inside="rapid:run_until_membership")
    assert set(out["modules"]) <= {"jit_run_until_membership_impl", "jit_concatenate", "jit_convert_element_type",
                                   "jit_broadcast_in_dim", "jit_squeeze", "jit__squeeze"}
    assert out["scope_s"].get("unscoped", 0.0) < 1e-4 * out["busy_s"]
    assert "join_predecessors" not in out["scope_s"]


# -- the reduction on hand-made traces ----------------------------------------------


def _loaded(events, spans=()):
    return {"devices": {"/device:TPU:0": events}, "spans": sorted(spans, key=lambda s: s[1])}


def test_children_are_taken_out_and_the_deepest_registered_scope_wins():
    ms = 10**9  # picoseconds
    loaded = _loaded([
        ("while.1", "jit(f)/while", "jit_f", 0, 100 * ms),
        ("fusion.1 s32[8]", "jit(f)/while/body/fd_tick/and", "jit_f", 10 * ms, 30 * ms),
        ("fusion.2 s32[8]", "jit(f)/while/body/cut_detection/cond/branch_1_fun/invalidation/or", "jit_f", 50 * ms, 40 * ms),
        ("copy.3 s32[8]", "", "jit_scatter", 200 * ms, 50 * ms),
    ])
    out = scope_reduce.reduce(loaded, SCOPES)
    assert out["busy_s"] == pytest.approx(0.150)
    # the while keeps what its children leave (30 ms); it has no path, nothing ran
    # before or after it at its own level, so it stays unscoped beside the eager copy
    assert out["scope_s"] == pytest.approx({"fd_tick": 0.030, "invalidation": 0.040, "unscoped": 0.080})
    assert out["modules"]["jit_f"]["fd_tick"] == {"self_s": pytest.approx(0.030), "by_path_s": pytest.approx(0.030), "calls": 1, "ops": 1}
    assert out["modules"]["jit_scatter"]["unscoped"]["calls"] == 0
    assert out["scoped_share"] == pytest.approx(70 / 150) and out["by_path_share"] == pytest.approx(70 / 150)


def test_an_operation_without_a_path_takes_its_neighbours_scope():
    us = 10**6
    events = [("conditional.1", "", "jit_f", 0, 100 * us)]
    events += [("fusion.1 s32[8]", "jit(f)/cond/branch_1_fun/view_change/gather", "jit_f", 10 * us, 10 * us),
               ("fusion.2 s32[8]", "", "jit_f", 30 * us, 40 * us),  # the compiler's scatter
               ("copy.9 s32[8]", "jit(f)/cond", "jit_f", 80 * us, 10 * us)]
    events += [("fusion.7 s32[8]", "jit(f)/vmap(tally)/add", "jit_f", 200 * us, 10 * us)]
    out = scope_reduce.reduce(_loaded(events), SCOPES)
    rows = {op: (scope, how) for op, _, scope, how, _, _ in out["ops"]}
    assert rows["fusion.2 s32[8]"] == ("view_change", "neighbour")
    assert rows["copy.9 s32[8]"] == ("view_change", "neighbour")
    assert rows["fusion.1 s32[8]"] == ("view_change", "path") and rows["fusion.7 s32[8]"] == ("tally", "path")
    # the conditional itself runs before any scoped operation of its module: it
    # takes the first one after it at its own level
    assert rows["conditional.1"] == ("tally", "neighbour")
    assert out["modules"]["jit_f"]["view_change"]["calls"] == 1


def test_calls_are_the_most_any_one_operation_of_the_scope_ran():
    us = 10**6
    events = []
    for round_no in range(4):
        base = round_no * 100 * us
        events.append(("fusion.1 s32[8]", "jit(f)/while/body/fd_tick/and", "jit_f", base, 10 * us))
        if round_no % 2 == 0:
            events.append(("kernel.1 u32[8]", "jit(f)/while/body/cond/branch_1_fun/deliver/call", "jit_f", base + 20 * us, 10 * us))
        else:
            events.append(("broadcast.1 u32[8]", "jit(f)/while/body/cond/branch_0_fun/deliver_skip/zeros", "jit_f", base + 20 * us, us))
    table = scope_reduce.reduce(_loaded(events), SCOPES)["modules"]["jit_f"]
    assert (table["fd_tick"]["calls"], table["deliver"]["calls"], table["deliver_skip"]["calls"]) == (4, 2, 2)


def test_a_gap_is_named_by_the_innermost_program_span_and_the_benchmarks_under_it():
    ms = 10**9
    events = [("a", "x/fd_tick/a", "m", 0, 10 * ms), ("b", "x/fd_tick/b", "m", 12 * ms, ms),
              ("c", "x/fd_tick/c", "m", 20 * ms, ms), ("d", "x/fd_tick/d", "m", 21 * ms + ms // 4, ms), ("e", "x/tally/e", "m", 40 * ms, ms)]
    spans = [("bench:inject", 0, 30 * ms, {}), ("rapid:inject_join_place", 9 * ms, 5 * ms, {"seq": 3}),
             ("rapid:sync", 14 * ms, 16 * ms, {"seq": 4}), ("bench:resolve", 30 * ms, 20 * ms, {})]
    gaps = scope_reduce.reduce(_loaded(events, spans), SCOPES)["gaps"]
    # 10..12 under inject_join_place, 13..20 under sync, 22.25..40 under resolve with no program span;
    # the quarter millisecond between c and d is under the threshold
    assert gaps["by_span"] == [
        ["bench:resolve", None, pytest.approx(0.01775), 1],
        ["bench:inject", "rapid:sync", pytest.approx(0.007), 1],
        ["bench:inject", "rapid:inject_join_place", pytest.approx(0.002), 1],
    ]
    assert gaps["total_s"] == pytest.approx(0.02675) and gaps["longest"][0][1:] == ["bench:resolve", None]


@pytest.mark.parametrize("path,scope", [
    ("jit(run_until_membership_impl)/while/body/while/body/fd_tick/and", "fd_tick"),
    ("jit(fleet_step_impl)/vmap(view_change)/vmap(jit(_where))/select_n", "view_change"),
    ("jit(f)/while/body/cut_detection/cond/branch_1_fun/invalidation/gather", "invalidation"),
    ("jit(f)/vmap(while)/body/vmap(jit(deliver))/sub", "deliver"),
    ("jit(engine_step_impl)/cond", "unscoped"),
    ("state.report_bits", "unscoped"),
    ("", "unscoped"),
    ("jit(f)/fd_ticker/and", "unscoped"),
])
def test_scope_of_a_path(path, scope):
    assert scope_reduce.scope_of(path, SCOPES) == scope


# -- the reader of the .xplane.pb's wire format ---------------------------------------


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    raw = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(raw)) + raw


def _entry(number: int, ident: int, message: bytes) -> bytes:
    return _field(number, _field(1, ident) + _field(2, message))


def test_load_reads_paths_modules_and_tagged_spans_from_the_wire_format(tmp_path):
    stat_names = b"".join(_entry(5, i, _field(1, i) + _field(2, name))
                          for i, name in ((1, "tf_op"), (2, "program_id"), (3, "seq"), (4, "wave"), (5, "_pt")))
    device = _field(2, "/device:TPU:0") + stat_names
    device += _entry(4, 7, _field(1, 7) + _field(2, "jit_engine_step_impl(42)"))
    device += _entry(4, 8, _field(1, 8) + _field(2, "%fusion.18 = pred[1025000]{0:T(1024)} fusion(%p), kind=kLoop")
                     + _field(5, _field(1, 1) + _field(5, "jit(engine_step_impl)/edge_masks/gather:"))
                     + _field(5, _field(1, 2) + _field(3, 42)))
    device += _entry(4, 9, _field(1, 9) + _field(2, "%copy.3 = s32[8]{0} copy(%q)") + _field(5, _field(1, 2) + _field(3, 42)))
    device += _field(3, _field(2, "XLA Modules") + _field(3, 1000) + _field(4, _field(1, 7) + _field(2, 0) + _field(3, 9000)))
    device += _field(3, _field(2, "XLA Ops") + _field(3, 1000)
                     + _field(4, _field(1, 8) + _field(2, 500) + _field(3, 2500))
                     + _field(4, _field(1, 9) + _field(2, 4000) + _field(3, 100)))
    host = _field(2, "/host:CPU") + stat_names
    host += _entry(4, 1, _field(1, 1) + _field(2, "rapid:stream_enqueue")) + _entry(4, 2, _field(1, 2) + _field(2, "PjitFunction(f)"))
    host += _field(3, _field(2, "python") + _field(3, 1001)
                   + _field(4, _field(1, 1) + _field(2, 250) + _field(3, 700)
                            + _field(4, _field(1, 3) + _field(4, 12)) + _field(4, _field(1, 4) + _field(3, 5))
                            + _field(4, _field(1, 5) + _field(3, 99)))
                   + _field(4, _field(1, 2) + _field(2, 10) + _field(3, 20)))
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_field(1, device) + _field(1, host))
    loaded = scope_reduce.load(str(tmp_path))
    assert loaded["devices"] == {"/device:TPU:0": [
        ("fusion.18 pred[1025000]", "jit(engine_step_impl)/edge_masks/gather", "jit_engine_step_impl", 1000500, 2500),
        ("copy.3 s32[8]", "", "jit_engine_step_impl", 1004000, 100),
    ]}
    assert loaded["spans"] == [("rapid:stream_enqueue", 1001250, 700, {"seq": 12, "wave": 5})]
    with pytest.raises(RuntimeError, match="no .xplane.pb"):
        scope_reduce.load(str(tmp_path / "plugins" / "profile" / "run" / "nothing"))


def test_a_loaded_trace_survives_the_small_file(tmp_path):
    loaded = _loaded([("a", "x/fd_tick/a", "m", 5000, 10), ("b", "", "m", 6000, 20)], [("rapid:sync", 5500, 100, {"seq": 2})])
    scope_reduce.dump(scope_reduce.cut(loaded, 5000, 7000), str(tmp_path / "small.json"))
    back = scope_reduce.undump(str(tmp_path / "small.json"))
    assert back["devices"]["/device:TPU:0"] == [("a", "x/fd_tick/a", "m", 0, 10), ("b", "", "m", 1000, 20)]
    assert back["spans"] == [("rapid:sync", 500, 100, {"seq": 2})]
    assert scope_reduce.cut(loaded, 5900, 7000)["devices"]["/device:TPU:0"] == [("b", "", "m", 6000, 20)]


# -- the readers of the new dispatch phases ----------------------------------------------


def _run(before, after, **more):
    return {"counters_before": {"dispatch_ms": before}, "counters_after": {"dispatch_ms": after}, **more}


CHURN = _run(
    {"sync": 100.0, "inject_crash": 10.0, "inject_join_admit": 20.0, "inject_join_place": 30.0, "run_until_membership": 1.0},
    {"sync": 500.0, "inject_crash": 50.0, "inject_join_admit": 100.0, "inject_join_place": 270.0, "run_until_membership": 999.0},
    commit_ms=[130.0] * 16, attempted=16, kind="closed_loop")
FLEET_STREAM = _run(
    {"stream_enqueue": 40.0, "stream_fetch": 1000.0, "inject_crash": 2.0},
    {"stream_enqueue": 400.0, "stream_fetch": 13000.0, "inject_crash": 26.0},
    attempted=12, wave_ms=[1500.0] * 12, kind="stream")
#: What the same windows leave on a program without the injection phases.
PARENT_CHURN = _run({"sync": 100.0}, {"sync": 500.0, "run_until_membership": 999.0}, commit_ms=[130.0] * 16, attempted=16)
PARENT_STREAM = _run({"stream_enqueue": 40.0, "stream_fetch": 1000.0}, {"stream_enqueue": 400.0, "stream_fetch": 13000.0},
                     attempted=12, wave_ms=[1500.0] * 12, kind="stream")


@pytest.mark.parametrize("metric,run,value", [
    ("inject_crash_ms.commit", CHURN, 2.5), ("inject_join_admit_ms.commit", CHURN, 5.0),
    ("inject_join_place_ms.commit", CHURN, 15.0), ("sync_ms.commit", CHURN, 25.0),
    # a phase that never ran in the window (no join under a fleet) reads 0 in the sum
    ("stream_enqueue_ms.tput", FLEET_STREAM, 32.0), ("stream_backpressure_ms.tput", FLEET_STREAM, 1000.0),
    # a program that has no such phase: nothing to read, and nothing raised
    ("inject_crash_ms.commit", PARENT_CHURN, None), ("inject_join_admit_ms.commit", PARENT_CHURN, None),
    ("inject_join_place_ms.commit", PARENT_CHURN, None), ("stream_enqueue_ms.tput", PARENT_STREAM, None),
    # ... while the phases it does have are read there too
    ("sync_ms.commit", PARENT_CHURN, 25.0), ("stream_backpressure_ms.tput", PARENT_STREAM, 1000.0),
])
def test_reader_of_a_dispatch_phase(metric, run, value):
    assert load_reader(metric)(run) == (pytest.approx(value) if value is not None else None)


# -- the traced tiny runs -------------------------------------------------------------------


def test_traced_churn_reports_the_injection_split_and_it_adds_up(checkout):
    done = tiny.run_cell(checkout, "cluster-100k.churn5", seed=2**31 + 11, seconds=0.3, trace=1)
    metrics = tiny.result_of(done)["metrics"]
    assert set(NEW_COMMIT) <= set(metrics) and all(metrics[name]["unit"] == "ms" for name in NEW_COMMIT)
    assert all(metrics[name]["value"] > 0 for name in NEW_COMMIT)
    line = next(l for l in done.stdout.splitlines() if l.startswith("commits"))
    inject = [float(token.split(":")[2].split("+")[0]) for token in line.split(": ", 1)[1].split()]
    inside = sum(metrics[name]["value"] for name in NEW_COMMIT)
    # what is left is host time outside every program span: Python between the calls
    assert 0 <= statistics.mean(inject) - inside < 0.15 * statistics.mean(inject)


def test_traced_fleet_trickle_reports_the_wave_split(checkout):
    done = tiny.run_cell(checkout, "paper-fleet-1k.trickle", seed=13, seconds=0.3, trace=1)
    metrics = tiny.result_of(done)["metrics"]
    assert set(NEW_STREAM) <= set(metrics) and all(metrics[name]["value"] > 0 for name in NEW_STREAM)
    line = next(l for l in done.stdout.splitlines() if l.startswith("waves"))
    waves = [float(token) for token in line.split(": ", 1)[1].split()]
    assert sum(metrics[name]["value"] for name in NEW_STREAM) < 1.2 * statistics.mean(waves)


def test_scope_reduce_runs_a_cell_traced_and_keeps_the_trace(checkout, tmp_path):
    done = tiny.run_cell(checkout, "cluster-100k.churn5", seed=5, seconds=0.3, script="benchmarks/scope_reduce.py",
                         extra=("--inside", "rapid:run_until_membership", "--record", "1", "--trace-dir", str(tmp_path)))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    assert list(tmp_path.rglob("*.xplane.pb"))  # the trace is kept
    out = json.load(open(os.path.join(checkout, "chiprun_out", "scope_reduce", "cluster-100k.churn5.json")))
    whole = out["tables"]["whole_window"]
    # the CPU backend records no op-name path, so a rehearsal reads unscoped; the spans are the program's
    assert whole["busy_s"] > 0 and set(whole["scope_s"]) == {"unscoped"}
    assert {"rapid:inject_crash", "rapid:inject_join_admit", "rapid:inject_join_place", "rapid:sync",
            "rapid:run_until_membership", "bench:inject"} <= set(whole["span_s"])
    assert "inside rapid:run_until_membership" in out["tables"]
    small = scope_reduce.undump(os.path.join(checkout, "chiprun_out", "scope_reduce", "cluster-100k.churn5.recorded.json"))
    assert [name for name, *_ in small["spans"] if name.startswith("bench:")] == [
        "bench:restore", "bench:inject", "bench:resolve", "bench:check"]
