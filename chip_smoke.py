"""Smoke of the engine's main path on one TPU chip: the quickest proof that
the system still starts there.

Run ``python chip_smoke.py`` from the repo root on a machine with a chip. It
is one process, imports jax once, and exits nonzero unless the platform is
``tpu`` — it never picks a platform for itself. Through the normal drivers
(``VirtualCluster.create`` -> churn injection -> ``sync`` ->
``run_until_membership`` / ``run_to_decision``) it resolves

- 100,000 members in 102,500 slots, {K,H,L} = {10,9,4}, 64 cohorts, 5 % churn
  with the Mosaic delivery kernel, checks the result, re-runs the same seed on
  the jnp core (the kernel's plain reference) and requires identical
  outcomes, then repeats on fresh state with zero compiles allowed;
- 1,000,000 members, 8 cohorts, 1 % crash in one ``run_to_decision``;
- one profiler trace, which must hold a device plane with events;
- on a four-device host, the 1M crash again through the same driver built on
  a ('cohort','nodes') = (2,2) mesh (``VirtualCluster.create(..., mesh=...)``),
  with every device holding its shards.

Each stage prints one line when it completes, so a failure names its stage.
Every time printed is a smoke reading of set-up cost, not a result. The last
line of stdout is one JSON object naming the device.

``run_smoke`` is a function of explicit sizes so a test can call it tiny on
CPU; ``main`` always runs the real ones.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import time

_START = time.monotonic()


def _done(stage: str, **fields) -> None:
    detail = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{time.monotonic() - _START:7.1f}s] stage {stage} ok {detail}", flush=True)


def _require(ok: bool, what: str) -> None:
    # Not an assert: python -O must not turn the smoke into a no-op.
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_trace_events(trace_dir: str) -> dict:
    """Events per plane of the profiler trace under ``trace_dir``. Raises
    unless an ``.xplane.pb`` landed there and holds a device plane with
    events in it: a trace that silently did not start, or saw only the host,
    measured nothing."""
    import jax

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    _require(len(found) > 0, f"an .xplane.pb landed under {trace_dir}")
    events = {
        plane.name: sum(len(list(line.events)) for line in plane.lines)
        for plane in jax.profiler.ProfileData.from_file(found[0]).planes
    }
    _require(
        any(name.startswith("/device:") and count > 0 for name, count in events.items()),
        f"trace holds a device plane with events (planes: {events})",
    )
    return events


def run_smoke(
    *,
    n_churn: int,
    cohorts_churn: int,
    n_xl: int,
    cohorts_xl: int,
    use_pallas: bool,
    twin: bool,
    trace: bool,
    repeats: int = 3,
    mesh_devices=None,
) -> dict:
    """The smoke's body. ``use_pallas`` is passed, never detected. ``twin``
    re-runs the churn on the other delivery core and compares; ``trace``
    captures and checks one profiler trace; ``mesh_devices`` (four devices)
    adds the 1M-shape crash on a sharded cluster. Returns the compile totals
    it observed."""
    import jax
    import numpy as np

    from rapid_tpu.models.virtual_cluster import VirtualCluster
    from rapid_tpu.utils import engine_telemetry

    _require(engine_telemetry.install(), "engine_telemetry.install() returned True")

    # BASELINE.json config 5, as bench.py builds it: half the churn joins,
    # half crashes, staggered failure detectors, two racing coordinators.
    n_join = n_crash = int(n_churn * 0.05 / 2)
    joiners = np.arange(n_churn, n_churn + n_join)
    max_cuts = 4

    def build_churn(seed: int, pallas: bool):
        vc = VirtualCluster.create(
            n_churn, n_slots=n_churn + n_join, k=10, h=9, l=4,
            cohorts=cohorts_churn, fd_threshold=3, seed=seed,
            use_pallas=pallas, delivery_spread=2, concurrent_coordinators=2,
            pallas_lanes=128,
        )
        vc.assign_cohorts_roundrobin()
        rng = np.random.default_rng(seed + 1000)
        vc.stagger_fd_counts(rng, spread_rounds=3)
        victims = rng.choice(n_churn, size=n_crash, replace=False)
        vc.crash(victims)
        vc.inject_join_wave(joiners)
        vc.sync()
        return vc, victims

    def resolve_churn(vc, victims):
        """One dispatch; the outcome every check and comparison reads."""
        rounds, cuts, resolved, sizes = vc.run_until_membership(
            n_churn, max_steps=96 * max_cuts, max_cuts=max_cuts, min_cuts=1
        )
        alive = vc.alive_mask
        _require(resolved, f"churn resolved (cuts={cuts} rounds={rounds} sizes={sizes})")
        _require(vc.membership_size == n_churn, f"membership == {n_churn}")
        _require(not alive[victims].any(), "no victim alive")
        _require(alive[joiners].all(), "every joiner alive")
        return {"alive": alive, "config_id": vc.config_id, "cuts": cuts, "sizes": sizes}

    # -- churn: warm-up (compiles everything), checked outside any timing --
    t0 = time.monotonic()
    with engine_telemetry.CompileDelta() as warmup:
        vc, victims = build_churn(0, use_pallas)
        got = resolve_churn(vc, victims)
    _require(warmup.delta["compiles"] > 0, "compile counters moved during warm-up")
    _done(
        "churn_warmup", n=n_churn, cohorts=cohorts_churn, use_pallas=use_pallas,
        cuts=got["cuts"], sizes=got["sizes"], wall_s=round(time.monotonic() - t0, 1),
        compiles=warmup.delta["compiles"],
        compile_s=round(warmup.delta["compile_ms"] / 1000.0, 1),
    )

    # -- the other delivery core on the same seed is the plain reference --
    if twin:
        vc, victims = build_churn(0, not use_pallas)
        want = resolve_churn(vc, victims)
        _require((got["alive"] == want["alive"]).all(), "twin: same alive mask")
        for key in ("config_id", "cuts", "sizes"):
            _require(got[key] == want[key], f"twin: same {key} ({got[key]} vs {want[key]})")
        _done("churn_twin", use_pallas=not use_pallas, config_id=hex(want["config_id"]))

    # -- repeats on fresh state: nothing may compile any more --
    readings = []
    with engine_telemetry.CompileDelta() as window:
        for rep in range(1, repeats + 1):
            vc, victims = build_churn(rep, use_pallas)
            t0 = time.perf_counter()
            resolve_churn(vc, victims)
            readings.append(round((time.perf_counter() - t0) * 1000.0, 1))
    _require(
        window.delta["compiles"] == 0,
        f"0 compiles in the repeat window (saw {window.delta['compiles']})",
    )
    _done("churn_repeats", repeats=repeats, compiles=0, smoke_reading_ms=readings)

    # -- 1M members, 1 % crash, one single-dispatch convergence --
    n_crash_xl = n_xl // 100

    def build_xl(mesh=None):
        # use_pallas stays off under a mesh (state.py: EngineConfig.use_pallas).
        vcx = VirtualCluster.create(
            n_xl, k=10, h=9, l=4, cohorts=cohorts_xl, fd_threshold=3, seed=7,
            use_pallas=use_pallas and mesh is None, delivery_spread=2,
            pallas_lanes=128, mesh=mesh,
        )
        vcx.assign_cohorts_roundrobin()
        victims_xl = np.random.default_rng(7).choice(n_xl, size=n_crash_xl, replace=False)
        vcx.crash(victims_xl)
        vcx.sync()
        return vcx, victims_xl

    t0 = time.monotonic()
    vcx, victims_xl = build_xl()
    rounds, decided, _, members = vcx.run_to_decision(max_steps=96)
    _require(decided, f"xl point decided (rounds={rounds})")
    _require(members == n_xl - n_crash_xl, f"xl membership == {n_xl - n_crash_xl}, got {members}")
    alive_xl = vcx.alive_mask
    _require(not alive_xl[victims_xl].any(), "xl: no victim alive")
    stats = jax.devices()[0].memory_stats() or {}
    _done(
        "crash_xl", n=n_xl, cohorts=cohorts_xl, rounds=rounds, members=members,
        wall_s=round(time.monotonic() - t0, 1),
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
    )
    del vcx

    # -- one profiler trace; a trace that did not start is a failure --
    if trace:
        vc, victims = build_churn(repeats + 1, use_pallas)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_trace_") as trace_dir:
            with jax.profiler.trace(trace_dir):
                resolve_churn(vc, victims)
            events = device_trace_events(trace_dir)
        _done("trace", events_per_plane=events)

    # -- four devices: the 1M crash through the driver on a (2,2) mesh --
    if mesh_devices is not None:
        from rapid_tpu.parallel.mesh import make_mesh, off_table

        t0 = time.monotonic()
        vcs, _ = build_xl(make_mesh(mesh_devices, shape=(2, 2)))
        rounds, decided, _, members = vcs.run_to_decision(max_steps=96)
        _require(decided, f"sharded xl point decided (rounds={rounds})")
        _require(members == n_xl - n_crash_xl, "sharded membership")
        _require(
            (vcs.alive_mask == alive_xl).all(),
            "sharded cluster: same alive mask as the one-device run",
        )
        state = vcs.state
        _require(
            off_table(state, vcs.mesh) == () and off_table(vcs.faults, vcs.mesh) == (),
            "every leaf on the rule table's sharding after the run",
        )
        for name, lane, parts in (
            ("alive", state.alive, (2,)), ("report_bits", state.report_bits, (2, 2)),
        ):
            holders = {shard.device for shard in lane.addressable_shards}
            _require(holders == set(mesh_devices), f"{name}: a shard on every device")
            want_shape = tuple(d // p for d, p in zip(lane.shape, parts))
            _require(
                all(s.data.shape == want_shape for s in lane.addressable_shards),
                f"{name}: shards are {want_shape} slices, not replicas",
            )
        _done(
            "sharded_xl", mesh="cohort=2,nodes=2", rounds=rounds, members=members,
            wall_s=round(time.monotonic() - t0, 1),
        )

    return engine_telemetry.compile_snapshot()


def main() -> int:
    import jax

    devices = jax.devices()
    platform, kind, count = devices[0].platform, devices[0].device_kind, len(devices)
    print(
        f"chip_smoke: jax={jax.__version__} platform={platform} "
        f"device_kind={kind} count={count}",
        flush=True,
    )
    if platform != "tpu":
        print(
            f"chip_smoke: platform is {platform!r}, not 'tpu': this smoke "
            "only passes on the chip (tests call run_smoke tiny on CPU)",
            file=sys.stderr, flush=True,
        )
        return 1

    from rapid_tpu.utils import _native
    from rapid_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    _native.ensure_built()
    host_hashing = "native_library" if _native.get_lib() is not None else "python_twin"
    _done("start", cache_dir=cache_dir, host_hashing=host_hashing)

    totals = run_smoke(
        n_churn=100_000, cohorts_churn=64, n_xl=1_000_000, cohorts_xl=8,
        use_pallas=True, twin=True, trace=True,
        mesh_devices=devices if count == 4 else None,
    )
    _done(
        "all", compiles=totals["compiles"],
        compile_s=round(totals["compile_ms"]["sum"] / 1000.0, 1),
        persistent_cache_hits=totals["persistent_cache_hits"],
        persistent_cache_misses=totals["persistent_cache_misses"],
    )
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": kind, "count": count},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
