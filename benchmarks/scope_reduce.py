"""From a profiler trace to the program's own vocabulary: device time per
engine scope, and idle gaps named by the program's host spans.

``trace_reduce`` names a device operation by its instruction
(``fusion.18 pred[1025000]``), which is the compiler's name and moves with
every change, and names a gap by the benchmark's span. The program now names
both itself: every device operation's op-name path carries the
``jax.named_scope`` it was traced under (``.../while/body/fd_tick/and``), and
every driver operation is a ``rapid:<phase>`` host span. This module reads
those from the ``.xplane.pb`` and reduces them:

(a) device self time per scope and per XLA module, children taken out of
    ``while``/``conditional`` as ``trace_reduce._self_times`` does, keyed on
    the deepest registered scope of each operation's path, with an
    ``unscoped`` row;
(b) every idle gap over ``GAP_MS`` named by the innermost ``rapid:`` span at
    its middle and, under it, the ``bench:`` span;
(c) calls per scope (the most any one operation of the scope ran), so that
    "this round took ``deliver``, that one ``deliver_skip``" is a count.

``reduce`` is a pure function of a loaded trace and a scope list. The scope
list is data of the benchmark (``scopes.json``), not an import from the
program. ``jax.profiler.ProfileData`` does not hand out the per-operation
metadata that holds the op-name path (``tf_op``), so ``load`` reads the
protobuf's wire format itself (the few fields of ``xplane.proto`` it needs).

``python3 benchmarks/scope_reduce.py --workload <cell> --seed <n> --seconds
<s>`` runs one cell traced through the harness's ``Context`` and the cell's
generator, keeps the trace, prints the tables and writes them under
``chiprun_out/scope_reduce/``. It is the builder's tool until a ``benchmark``
issue folds it into the harness's result line.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import re
import struct
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
GAP_MS = 0.5
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("rapid:", "bench:")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HLO_LINE = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def scope_list() -> list:
    with open(os.path.join(HERE, "scopes.json"), encoding="utf-8") as handle:
        return json.load(handle)["scopes"]


# -- the wire format of xplane.proto, as far as it is needed -------------------


def _varint(buf, at: int):
    """(value, next position) of the base-128 integer at ``buf[at]``."""
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value = buf[at:at + size]
            at += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = buf[at:at + size]
            at += size
        else:
            raise ValueError(f"wire type {kind} is not in xplane.proto")
        yield key >> 3, kind, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names):
    """One XStat as (name, value). A ``ref_value`` points at a stat name."""
    name = value = None
    for number, kind, raw in _fields(buf):
        if number == 1:
            name = stat_names.get(raw)
        elif number == 2:
            value = struct.unpack("<d", raw)[0]
        elif number == 3:
            value = raw
        elif number == 4:
            value = _signed(raw)
        elif number in (5, 6):
            value = bytes(raw).decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(raw, raw)
    return name, value


def _map_value(buf):
    """The value message of one ``map<int64, message>`` entry."""
    for number, _, raw in _fields(buf):
        if number == 2:
            return raw
    return b""


def _plane(buf):
    """One XPlane: its name, its lines still raw, and what its events'
    metadata says: ``{id: (name, {stat: value})}``."""
    name, lines, raw_events, stat_names = "", [], [], {}
    for number, _, raw in _fields(buf):
        if number == 2:
            name = bytes(raw).decode()
        elif number == 3:
            lines.append(raw)
        elif number == 4:
            raw_events.append(raw)
        elif number == 5:
            ident = label = None
            for n, _, r in _fields(_map_value(raw)):
                if n == 1:
                    ident = r
                elif n == 2:
                    label = bytes(r).decode()
            stat_names[ident] = label
    return name, lines, raw_events, stat_names


def _event_metadata(raw_events, stat_names, wanted):
    """``{id: (name, {stat: value})}``, keeping the stats named in ``wanted``."""
    out = {}
    for entry in raw_events:
        ident, name, stats = None, "", {}
        for n, _, r in _fields(_map_value(entry)):
            if n == 1:
                ident = r
            elif n == 2:
                name = bytes(r).decode("utf-8", "replace")
            elif n == 5:
                key, value = _stat(r, stat_names)
                if key in wanted:
                    stats[key] = value
        out[ident] = (name, stats)
    return out


def _line(buf):
    """(name, timestamp_ns, raw events) of one XLine."""
    name, stamp, events = "", 0, []
    for number, _, raw in _fields(buf):
        if number == 2:
            name = bytes(raw).decode()
        elif number == 3:
            stamp = _signed(raw)
        elif number == 4:
            events.append(raw)
    return name, stamp, events


def _event(buf, stat_names=None):
    """(metadata id, offset_ps, duration_ps, {stat: value}) of one XEvent;
    the stats are read only when ``stat_names`` is given."""
    ident = offset = duration = 0
    stats = {}
    for number, _, raw in _fields(buf):
        if number == 1:
            ident = raw
        elif number == 2:
            offset = _signed(raw)
        elif number == 3:
            duration = _signed(raw)
        elif number == 4 and stat_names is not None:
            key, value = _stat(raw, stat_names)
            stats[key] = value
    return ident, offset, duration, stats


def op_name(event_name: str) -> str:
    """``fusion.18 pred[1025000]`` from the whole HLO line, as
    ``trace_reduce.op_name`` (the ledger's breakdowns use these names)."""
    match = _HLO_LINE.match(event_name)
    if not match:
        return event_name
    return match.group(1) + (" " + match.group(2) if match.group(2) else "")


def load(trace_dir: str) -> dict:
    """``{"devices": {plane: [(op, path, module, start_ps, duration_ps)]},
    "spans": [(name, start_ps, duration_ps, {tag: value})]}``.

    ``op`` is the instruction's name and first result shape, ``path`` its
    op-name path (``tf_op``; empty where the compiler made the operation and
    gave it none) and ``module`` the XLA module it ran in. Spans are the
    host's ``rapid:`` and ``bench:`` trace annotations, on the same clock.
    A CPU rehearsal has no device plane: the host events that carry an
    ``hlo_op`` stand in, with no path (the CPU backend records none)."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise RuntimeError(f"the profiler left no .xplane.pb under {trace_dir}")
    with open(sorted(found)[-1], "rb") as handle:
        space = memoryview(handle.read())
    devices, spans = {}, []
    for number, _, raw in _fields(space):
        if number != 1:
            continue
        name, lines, raw_events, stat_names = _plane(raw)
        if _DEVICE_PLANE.match(name):
            metadata = _event_metadata(raw_events, stat_names, ("tf_op", "program_id"))
            modules, ops = {}, []
            for line in lines:
                line_name, stamp, events = _line(line)
                if line_name == "XLA Modules":
                    for event in events:
                        match = _MODULE.match(metadata[_event(event)[0]][0])
                        if match:
                            modules[match.group(2)] = match.group(1)
                elif line_name == "XLA Ops":
                    ops = [(stamp,) + _event(event)[:3] for event in events]
            devices[name] = []
            for stamp, ident, offset, duration in ops:
                hlo, stats = metadata[ident]
                program = stats.get("program_id")
                module = modules.get(str(program % (1 << 64)) if isinstance(program, int) else "", "unknown_module")
                path = str(stats.get("tf_op") or "").rstrip(":")
                devices[name].append((op_name(hlo), path, module, stamp * 1000 + offset, duration))
        elif name.startswith("/host:"):
            metadata = _event_metadata(raw_events, stat_names, ())
            for line in lines:
                _, stamp, events = _line(line)
                for event in events:
                    ident, offset, duration, stats = _event(event, stat_names)
                    label = metadata[ident][0]
                    start = stamp * 1000 + offset
                    if label.startswith(SPAN_PREFIXES):
                        tags = {k: v for k, v in stats.items() if not k.startswith("_")}
                        spans.append((label, start, duration, tags))
                    elif duration and "hlo_op" in stats:
                        devices.setdefault("/host:CPU (rehearsal)", []).append(
                            (label, "", str(stats.get("hlo_module", "unknown_module")), start, duration))
    if not any(devices.values()):
        raise RuntimeError("the trace holds no device operation")
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


# -- a loaded trace as a small file (the tests' recorded chip trace) -----------


def cut(loaded: dict, start_ps: int, end_ps: int) -> dict:
    """The part of a loaded trace that starts inside ``[start_ps, end_ps)``."""
    return {
        "devices": {plane: [e for e in events if start_ps <= e[3] < end_ps]
                    for plane, events in loaded["devices"].items()},
        "spans": [s for s in loaded["spans"] if start_ps <= s[1] < end_ps],
    }


def dump(loaded: dict, path: str) -> None:
    """Write a loaded trace as JSON with its strings interned."""
    strings, index = [], {}

    def intern(text: str) -> int:
        if text not in index:
            index[text] = len(strings)
            strings.append(text)
        return index[text]

    origin = min(e[3] for events in loaded["devices"].values() for e in events)
    out = {
        "devices": {
            plane: [[intern(op), intern(p), intern(m), start - origin, duration] for op, p, m, start, duration in events]
            for plane, events in loaded["devices"].items()
        },
        "spans": [[intern(name), start - origin, duration, tags] for name, start, duration, tags in loaded["spans"]],
        "strings": strings,
    }
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(out, handle, separators=(",", ":"))


def undump(path: str) -> dict:
    with open(path, encoding="utf-8") as handle:
        raw = json.load(handle)
    text = raw["strings"]
    return {
        "devices": {
            plane: [(text[op], text[p], text[m], start, duration) for op, p, m, start, duration in events]
            for plane, events in raw["devices"].items()
        },
        "spans": [(text[name], start, duration, tags) for name, start, duration, tags in raw["spans"]],
    }


# -- the reduction ---------------------------------------------------------------


def scope_of(path: str, scopes) -> str:
    """The deepest registered scope of an op-name path. A transform wraps
    the names under it (``vmap(fd_tick)``, ``jit(main)``): the name inside
    the brackets counts."""
    found = UNSCOPED
    for part in path.split("/"):
        while True:
            if part in scopes:
                found = part
                break
            match = _WRAPPED.match(part)
            if not match:
                break
            part = match.group(1)
    return found


def _attribute(events, scopes):
    """Every device event with its self time and its scope.

    Returns ``[(op, path, module, self_ps, scope, how)]``. Self time is an
    operation's time less that of the operations inside it (a ``while``
    spans its body's operations), as ``trace_reduce._self_times`` has it.
    ``how`` is ``"path"`` where the operation's own op-name path names a
    registered scope. The compiler also makes operations of its own (the
    scatter and scan expansions, copies between memories, a conditional's
    plumbing) and gives them no path, or the bare path of their loop: such an
    operation takes the scope of the operation that ran before it inside the
    same enclosing operation (after it, at the start; the enclosing one's,
    alone), with ``how`` ``"neighbour"``, and stays ``unscoped`` where there
    is none to take."""
    order = sorted(events, key=lambda e: (e[3], -e[4]))
    rows, stack = [], []  # stack: (index, end_ps)
    last, waiting = {}, {}  # per enclosing operation: last scope seen, rows still waiting for one
    for index, (op, path, module, start, duration) in enumerate(order):
        end = start + duration
        while stack and stack[-1][1] <= start:
            stack.pop()
        parent = stack[-1][0] if stack else module
        if stack:
            rows[parent][3] -= min(end, stack[-1][1]) - start
        scope, how = scope_of(path, scopes), "path"
        if scope == UNSCOPED:
            how = "neighbour"
            if parent in last:
                scope = last[parent]
            elif stack and rows[parent][4] != UNSCOPED:
                scope = rows[parent][4]
            else:
                waiting.setdefault(parent, []).append(index)
        if scope != UNSCOPED:
            last[parent] = scope
            for early in waiting.pop(parent, ()):
                rows[early][4] = scope
        rows.append([op, path, module, duration, scope, how])
        stack.append((index, end))
    return [tuple(row) for row in rows]


def _merge(events):
    merged = []
    for *_, start, duration in sorted(events, key=lambda e: e[3]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + duration)
        else:
            merged.append([start, start + duration])
    return merged


def _innermost(spans, prefix: str, at_ps: int):
    """The last-started span of ``prefix`` that holds ``at_ps``."""
    held = [name for name, start, duration, _ in spans
            if name.startswith(prefix) and start <= at_ps < start + duration]
    return held[-1] if held else None


def reduce(loaded: dict, scopes, inside: str | None = None, top: int = 40) -> dict:
    """The tables of the module docstring. ``inside`` names a span
    (``rapid:run_until_membership``): the device tables then count only the
    operations that start inside a span of that name."""
    spans = loaded["spans"]
    events = loaded["devices"][sorted(loaded["devices"])[0]]
    if inside is not None:
        windows = [(start, start + duration) for name, start, duration, _ in spans if name == inside]
        events = [e for e in events if any(a <= e[3] < b for a, b in windows)]
    modules, per_op = {}, {}
    path_ps = total_ps = 0
    for op, path, module, ps, scope, how in _attribute(events, scopes):
        row = modules.setdefault(module, {}).setdefault(
            scope, {"self_s": 0.0, "by_path_s": 0.0, "calls": 0, "ops": set(), "_calls": {}})
        row["self_s"] += ps / 1e12
        row["ops"].add((op, path))
        total_ps += ps
        if how == "path":
            row["by_path_s"] += ps / 1e12
            row["_calls"][(op, path)] = row["_calls"].get((op, path), 0) + 1
            path_ps += ps
        entry = per_op.setdefault((op, module), [op, module, scope, how, 0.0, 0])
        entry[4] += ps / 1e12
        entry[5] += 1
    by_scope = {}
    for table in modules.values():
        for scope, row in table.items():
            # Calls: the most any one operation that names the scope itself ran.
            row["calls"] = max(row.pop("_calls").values(), default=0)
            row["ops"] = len(row["ops"])
            by_scope[scope] = by_scope.get(scope, 0.0) + row["self_s"]
    ops = sorted(per_op.values(), key=lambda row: -row[4])

    merged = _merge(events)
    gaps, by_span = [], {}
    for (_, a_end), (b_start, _) in zip(merged, merged[1:]):
        if b_start - a_end <= GAP_MS * 1e9:
            continue
        middle = a_end + (b_start - a_end) // 2
        named = (_innermost(spans, "bench:", middle), _innermost(spans, "rapid:", middle))
        gaps.append([(b_start - a_end) / 1e12, *named])
        entry = by_span.setdefault(named, [0.0, 0])
        entry[0] += (b_start - a_end) / 1e12
        entry[1] += 1
    gaps.sort(key=lambda gap: -gap[0])
    busy_s = sum(end - start for start, end in merged) / 1e12
    return {
        "busy_s": busy_s,
        # Shares of the operations' self time (which sums to the busy time
        # where the device runs one operation at a time).
        "scoped_share": 1.0 - by_scope.get(UNSCOPED, 0.0) * 1e12 / total_ps if total_ps else 0.0,
        "by_path_share": path_ps / total_ps if total_ps else 0.0,
        "scope_s": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "modules": modules,
        "ops": ops[:top],
        "gaps": {
            "over_ms": GAP_MS,
            "total_s": sum(gap[0] for gap in gaps),
            "by_span": sorted(([b, r, s, n] for (b, r), (s, n) in by_span.items()), key=lambda row: -row[2]),
            "longest": gaps[:10],
        },
        "span_s": _span_seconds(spans),
    }


def _span_seconds(spans) -> dict:
    out = {}
    for name, _, duration, _ in spans:
        entry = out.setdefault(name, [0.0, 0])
        entry[0] += duration / 1e12
        entry[1] += 1
    return out


def render(out: dict, rounds: int = 0) -> str:
    """The tables as text; with ``rounds``, also microseconds a round."""
    per = (lambda s: f" {s * 1e6 / rounds:10.1f} us/round") if rounds else (lambda s: "")
    lines = [f"device busy {out['busy_s']:.4f} s, {100 * out['scoped_share']:.2f} % under a registered scope "
             f"({100 * out['by_path_share']:.2f} % by the operations' own paths, the rest by their neighbours)"]
    for scope, seconds in out["scope_s"].items():
        lines.append(f"  {scope:18s} {seconds:9.4f} s {100 * seconds / out['busy_s']:6.2f} %{per(seconds)}")
    small = []
    for module, table in sorted(out["modules"].items(), key=lambda kv: -sum(r["self_s"] for r in kv[1].values())):
        total = sum(row["self_s"] for row in table.values())
        if total < 5e-4 * out["busy_s"]:
            small.append(f"{module} {total:.5f}")
            continue
        lines.append(f"module {module}: {total:.4f} s")
        for scope, row in sorted(table.items(), key=lambda kv: -kv[1]["self_s"]):
            lines.append(f"  {scope:18s} {row['self_s']:9.4f} s  by path {row['by_path_s']:9.4f} s  "
                         f"calls {row['calls']:6d}  ops {row['ops']:5d}{per(row['self_s'])}")
    if small:
        lines.append("modules under 0.05 % of busy (s): " + ", ".join(small))
    lines.append("operations by self time (op, module, scope, how, s, calls):")
    lines += [f"  {op:44s} {module:32s} {scope:14s} {how:9s} {s:8.4f} {n:6d}" for op, module, scope, how, s, n in out["ops"]]
    gaps = out["gaps"]
    lines.append(f"idle gaps over {gaps['over_ms']} ms: {gaps['total_s']:.4f} s (bench span, rapid span, s, count):")
    lines += [f"  {str(b):16s} {str(r):28s} {s:8.4f} {n:5d}" for b, r, s, n in gaps["by_span"]]
    lines.append("host spans (s, count): " + ", ".join(
        f"{name} {s:.3f}/{n}" for name, (s, n) in sorted(out["span_s"].items())))
    return "\n".join(lines)


# -- one traced run of one cell, with the trace kept ------------------------------


def main(argv) -> int:
    t_process_start = time.perf_counter()
    sys.path.insert(0, os.path.dirname(HERE))
    from benchmarks import harness

    parser = argparse.ArgumentParser(prog="benchmarks/scope_reduce.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=harness.TRACE_WINDOW_S)
    parser.add_argument("--inside", default=None, help="count device time only inside spans of this name")
    parser.add_argument("--out", default=os.path.join(harness.ROOT, "chiprun_out", "scope_reduce"))
    parser.add_argument("--trace-dir", default=None, help="where the trace is kept (default: a new temporary directory)")
    parser.add_argument("--record", type=int, default=0, help="also keep the first N benchmark steps as a small file")
    args = parser.parse_args(argv)

    cell, config, traffic = harness.find_cell(harness.load_json("BENCHMARK.json"), args.workload)

    import jax

    platform = jax.devices()[0].platform
    print(f"device: platform={platform} kind={jax.devices()[0].device_kind}", flush=True)
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"benchmarks/scope_reduce.py: platform is {platform!r}, not 'tpu'", file=sys.stderr)
        return 1

    from rapid_tpu.utils import engine_telemetry
    from rapid_tpu.utils.platform import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The persistent cache's key leaves metadata out by default, so a cache
    # filled by another build would serve programs that carry that build's op
    # names. This tool reads the names: it keys its entries on them.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    enable_compile_cache()
    engine_telemetry.install()

    # The trace is kept (it is what a builder opens in Perfetto next), but not
    # under --out by default: a real window's trace is tens of megabytes.
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="scope_trace_")
    os.makedirs(trace_dir, exist_ok=True)
    os.makedirs(args.out, exist_ok=True)
    print(f"trace kept in {trace_dir}", flush=True)
    ctx = harness.Context(cell, config, traffic, args.seed, min(args.seconds, harness.TRACE_WINDOW_S),
                          platform, trace_dir, t_process_start)
    record = importlib.import_module("benchmarks.generators." + traffic["kind"]).run(ctx)
    run = {**ctx.run, **record}
    print(f"window: {run['attempted']} {run['kind']} steps, {run['rounds']} engine rounds in {run['window_s']:.3f} s")

    loaded = load(trace_dir)
    scopes = scope_list()
    tables = {"whole_window": reduce(loaded, scopes)}
    if args.inside:
        tables["inside " + args.inside] = reduce(loaded, scopes, inside=args.inside)
    for title, out in tables.items():
        print(f"== {args.workload}: {title}")
        print(render(out, run["rounds"]))
    summary = {"workload": args.workload, "seed": args.seed, "rounds": run["rounds"],
               "attempted": run["attempted"], "window_s": run["window_s"], "tables": tables}
    with open(os.path.join(args.out, args.workload + ".json"), "w", encoding="utf-8") as handle:
        json.dump(summary, handle, default=str)
    if args.record:
        step_spans = [s for s in loaded["spans"] if s[0] in ("bench:restore", "bench:submit")]
        if len(step_spans) > args.record:
            small = cut(loaded, step_spans[0][1], step_spans[args.record][1])
            dump(small, os.path.join(args.out, args.workload + ".recorded.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
