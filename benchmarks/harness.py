"""One run of one cell: set-up, a measured window, the check, one result line.

Everything that belongs to one cell is data found by name: the workload's
entry in ``BENCHMARK.json``, its configuration file, ``traffic/<traffic>.json``
(whose ``kind`` names the module of ``generators/`` that drives it) and one
reader in ``metrics/`` for each metric the cell reports. Adding a cell, a
configuration, a traffic mix or a metric edits no file that is here.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
#: A traced run times a short window of its own: traces are large and the
#: tracer slows the host, and the per-layer numbers are shares and means.
TRACE_WINDOW_S = 10.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as handle:
        return json.load(handle)


def find_cell(bench: dict, workload: str):
    cells = {cell["name"]: cell for cell in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, load_json(config_entry["file"]), load_json("benchmarks", "traffic", cell["traffic"] + ".json")


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The metrics of ``group`` this cell reports: those that list it, and
    those that list no cells (reported wherever their reader finds something)."""
    return [m for m in bench[group] if workload in m.get("workloads", [workload])]


def load_reader(name: str):
    """``metrics/<stem>.py`` where the stem is the metric's name up to its
    first dot: a metric split by cells (``x.commit``, ``x.tput``) shares
    ``metrics/x.py``."""
    stem = name.split(".")[0]
    if not os.path.exists(os.path.join(HERE, "metrics", stem + ".py")):
        raise SystemExit(f"no reader benchmarks/metrics/{stem}.py for metric {name!r}")
    return importlib.import_module("benchmarks.metrics." + stem).read


class Window:
    """The measured window: a clock, the compile counters around it and, in
    a traced run, the profiler."""

    def __init__(self, ctx, target):
        self._ctx, self._target = ctx, target

    def __enter__(self):
        import jax

        from rapid_tpu.utils import engine_telemetry

        ctx = self._ctx
        made = engine_telemetry.compile_snapshot()
        ctx.run["warmup_programs"] = self._programs0 = made["compiles"]
        ctx.run["warmup_compiled"] = made["persistent_cache_misses"]
        if ctx.trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=options)
        # Whatever set-up built stays out of the collector's sweeps, so a full
        # collection inside the window costs what the window itself allocates.
        gc.collect()
        gc.freeze()
        ctx.run["counters_before"] = self._target.counters()
        self._t0 = time.perf_counter()
        ctx.run["setup_s"] = self._t0 - ctx.t_process_start
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    def __exit__(self, *exc):
        import jax

        from rapid_tpu.utils import engine_telemetry

        ctx = self._ctx
        ctx.run["window_s"] = self.elapsed()
        ctx.run["counters_after"] = self._target.counters()
        ctx.run["compiles_in_window"] = engine_telemetry.compile_snapshot()["compiles"] - self._programs0
        if ctx.trace_dir is not None:
            jax.profiler.stop_trace()
        return False


class Context:
    """What a generator is handed: the cell's data, the seed, and the hooks
    that put its work on the benchmark's clocks."""

    def __init__(self, cell, config, traffic, seed, seconds, platform, trace_dir, t_process_start):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.platform = seed, seconds, platform
        self.trace_dir, self.t_process_start = trace_dir, t_process_start
        self.run: dict = {}

    def build_target(self, seed: int):
        from benchmarks import targets

        return targets.build(self.config, seed, self.platform)

    def span(self, name: str):
        """A host span on the profiler's clock (free when no trace runs)."""
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def window(self, target) -> Window:
        return Window(self, target)


def device_line(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(max(peaks)),
    }


def main(argv, t_process_start: float) -> int:
    parser = argparse.ArgumentParser(prog="benchmarks/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell, config, traffic = find_cell(bench, args.workload)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    print(f"device: platform={platform} kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    # Chip or fail. A rehearsal on the CPU is the caller's explicit choice.
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"benchmarks/run.py: platform is {platform!r}, not 'tpu'", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"benchmarks/run.py: {args.workload} needs {cell['chips']} chip(s), "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[: cell["chips"]]

    from rapid_tpu.utils import engine_telemetry
    from rapid_tpu.utils.platform import enable_compile_cache

    # Keep every program, however quickly it compiled: most of a warm-up is
    # small programs, and the default keeps only those that took a second.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = enable_compile_cache()
    if not engine_telemetry.install():
        print("benchmarks/run.py: jax.monitoring is missing, compiles cannot be counted", file=sys.stderr)
        return 1
    print(f"compile cache: {cache_dir}", flush=True)

    generator = importlib.import_module("benchmarks.generators." + traffic["kind"])
    seconds = min(args.seconds, TRACE_WINDOW_S) if args.trace else args.seconds
    with contextlib.ExitStack() as stack:
        trace_dir = stack.enter_context(tempfile.TemporaryDirectory(prefix="bench_trace_")) if args.trace else None
        ctx = Context(cell, config, traffic, args.seed, seconds, platform, trace_dir, t_process_start)
        record = generator.run(ctx)
        run = {**ctx.run, **record, "config": config, "device_kind": devices[0].device_kind}
        if args.trace:
            from benchmarks import trace_reduce

            run["trace"] = trace_reduce.reduce(trace_reduce.load(trace_dir, platform))

    from benchmarks import membership_model

    checks = dict(run["checks"], compiles_in_window=run["compiles_in_window"])
    limits = dict(membership_model.LIMITS, compiles_in_window=0)
    for name, value in checks.items():
        print(f"check {name}: value={value} limit={limits[name]}")
    correct = all(value <= limits[name] for name, value in checks.items()) and run["attempted"] > 0
    print(f"window: {run['attempted']} {run['kind']} steps, {run['view_changes']} view changes, "
          f"{run['rounds']} engine rounds in {run['window_s']:.3f} s; set-up {run['setup_s']:.1f} s "
          f"({run['warmup_programs']} programs, {run['warmup_compiled']} not from the cache)")
    if run.get("commit_ms"):
        print("commits (plan:rounds:inject+resolve ms): " + " ".join(
            f"{p}:{r}:{a:.1f}+{b:.1f}" for p, r, (a, b) in
            zip(run["commit_plan"], run["commit_rounds"], run["commit_parts_ms"])))
    if run.get("wave_ms"):
        print("waves (ms between submits): " + " ".join(f"{ms:.1f}" for ms in run["wave_ms"]))

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in metrics_of(bench, group, args.workload):
        value = load_reader(entry["name"])(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    result = {
        "correct": bool(correct), "attempted": int(run["attempted"]), "failed": int(run["failed"]),
        "metrics": metrics, "device": device_line(devices),
    }
    if args.trace:
        trace = run["trace"]
        result["device"].update(busy_s=trace["busy_s"], window_s=run["window_s"])
        result["breakdown"] = {"device_ops": trace["top_ops"], "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(result), flush=True)
    return 0
