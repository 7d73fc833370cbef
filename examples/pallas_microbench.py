"""Microbenchmark: the Pallas delivery kernel vs the engine's jnp path,
plus a per-convergence profile of the engine.

Answers the "prove the Pallas kernel" ask with numbers: per-call
on-device latency of the engine's fused delivery pass on both paths at
engine-realistic shapes (the measurement that keeps the kernel honest —
round 2's equivalent run killed a slower watermark Mosaic kernel), the
XLA-fused watermark pass for the op-level record, and (with
``--profile DIR``) a TensorBoard/Perfetto trace of one full churn
convergence for the op-level breakdown.

Run on the accelerator (the Pallas path is TPU-gated; off-TPU this prints
the jnp numbers and notes the kernel was skipped):

    python examples/pallas_microbench.py [--platform tpu] [--profile /tmp/tr]

Timing discipline: every device→host fetch carries a constant dispatch +
fetch cost that can swamp a millisecond-scale kernel if each sample ends in
its own fetch. Each sample therefore runs a
``lax.fori_loop`` chaining ITERS dependent kernel applications on device
(outputs fed back into inputs so nothing can be hoisted or elided) behind
ONE terminal scalar fetch, at two loop lengths; the reported per-call time
is the slope ``(t_hi − t_lo) / (iters_hi − iters_lo)``, which cancels the
constant dispatch + fetch term exactly. The constant itself is
reported as ``fetch_overhead_ms``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

ITERS_LO, ITERS_HI = 2, 18


def timed(fn, reps: int = 10) -> float:
    """Min-of-reps wall ms per call; each call ends in a scalar fetch."""
    fn()  # warm (compile)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) * 1000.0)
    return best


def slope_timed(make_chained) -> tuple[float, float]:
    """(per-iteration ms, constant-overhead ms) from two chained-loop lengths.

    ``make_chained(iters)`` must return a zero-arg callable that executes
    ``iters`` dependent kernel applications on device and ends in exactly
    one scalar fetch.
    """
    t_lo = timed(make_chained(ITERS_LO))
    t_hi = timed(make_chained(ITERS_HI))
    per_call = (t_hi - t_lo) / (ITERS_HI - ITERS_LO)
    overhead = max(t_lo - ITERS_LO * per_call, 0.0)
    return per_call, overhead


def speedup_of(jnp_ms: float, pallas_ms: float):
    """Ratio from the UNROUNDED slopes, or None when the measurement is too
    small/noisy to divide (a sub-resolution or negative slope — possible at
    tiny shapes now that the constant overhead no longer pads every
    sample)."""
    if jnp_ms <= 0.0 or pallas_ms <= 1e-6:
        return None
    return round(jnp_ms / pallas_ms, 2)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--platform", default=None,
                        help="force a jax platform (e.g. cpu); default: environment's")
    parser.add_argument("--n", type=int, default=1_000_000)
    parser.add_argument("--cohorts", type=int, default=8)
    parser.add_argument("--profile", default=None,
                        help="also trace one 100K-member churn convergence into DIR")
    args = parser.parse_args()

    if args.platform:
        from rapid_tpu.utils.platform import force_platform

        if not force_platform(args.platform):
            raise RuntimeError(
                f"could not force jax platform {args.platform!r} (a backend "
                "was already initialized); refusing to time the wrong backend"
            )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from rapid_tpu.ops.pallas_kernels import watermark_merge_classify

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    h, l, k = 9, 4, 10

    rng = np.random.default_rng(0)
    shape = (args.cohorts, args.n)
    old = jnp.asarray(rng.integers(0, 1 << k, size=shape, dtype=np.uint32))
    new = jnp.asarray(rng.integers(0, 1 << k, size=shape, dtype=np.uint32))
    mask = jnp.asarray(rng.random(shape) < 0.95)

    from functools import partial

    import jax.lax as lax

    def run_watermark():
        def make_chained(iters: int):
            @partial(jax.jit, static_argnums=(3,))
            def loop(old_b, new_b, mask_b, n_iter):
                def body(i, carry):
                    acc, cur = carry
                    bits, cls = watermark_merge_classify(
                        old_b, cur ^ i.astype(jnp.uint32), mask_b, h, l,
                    )
                    # Feed bits back as next iteration's input and fold the
                    # full classification into the accumulator: every element
                    # of both outputs is live, so XLA can neither elide the
                    # pass nor compute a slice of it.
                    return acc + jnp.sum(cls.astype(jnp.uint32)), bits

                acc, final = lax.fori_loop(
                    0, n_iter, body, (jnp.uint32(0), new_b))
                return acc + final[0, 0]

            return lambda: int(loop(old, new, mask, iters))

        return slope_timed(make_chained)

    # XLA-fused watermark pass: the jnp core IS the shipped path (a Mosaic
    # version measured 0.69x of this and was deleted); timed for the
    # op-level record and to notice any fusion regression.
    jnp_ms, jnp_ovh = run_watermark()
    results = {
        "watermark_shape": list(shape),
        "xla_fused_ms": round(jnp_ms, 3),
        "fetch_overhead_ms": round(jnp_ovh, 3),
    }
    print(json.dumps(results))

    # Delivery kernel: the fused (cohort-word x ring) pass vs the engine's
    # jnp loop, at engine-realistic shapes ([w*k, n] packed rx-block rows).
    from rapid_tpu.models.virtual_cluster import VirtualCluster, _deliver_alerts, _edge_masks

    def delivery_run(use_pallas: bool, n: int, c: int):
        vc = VirtualCluster.create(
            n, cohorts=c, fd_threshold=1, seed=1, use_pallas=use_pallas,
            delivery_spread=2,
        )
        vc.assign_cohorts_roundrobin()
        vc.crash(np.asarray(rng.choice(n, size=max(1, n // 100), replace=False)))
        vc.step()  # compile + fire the detectors

        cfg, state, faults = vc.cfg, vc.state, vc.faults

        def make_chained(iters: int):
            @partial(jax.jit, static_argnums=(2,))
            def loop(state, faults, n_iter):
                _, blocked_rows = _edge_masks(cfg, state, faults)

                def body(i, acc):
                    # Each iteration's fire_round perturbation depends on the
                    # ACCUMULATED output of all previous iterations (acc % 2
                    # is unknowable before they execute), so the chain is a
                    # true data dependence — no unrolling/CSE can collapse
                    # it — and summing the output keeps every element live.
                    out = _deliver_alerts(
                        cfg, state,
                        state.fire_round - (acc % 2).astype(jnp.int32),
                        blocked_rows)
                    return acc + jnp.sum(out)

                return lax.fori_loop(0, n_iter, body, jnp.uint32(0))

            return lambda: int(loop(state, faults, iters))

        return slope_timed(make_chained)

    n_d, c_d = min(args.n, 100_000), 64
    d_jnp_ms, d_ovh = delivery_run(False, n_d, c_d)
    results_d = {
        "platform": platform,
        "delivery_shape": [c_d, n_d],
        "jnp_ms": round(d_jnp_ms, 3),
        "fetch_overhead_ms": round(d_ovh, 3),
    }
    if on_tpu:
        d_pallas_ms, _ = delivery_run(True, n_d, c_d)
        results_d["pallas_ms"] = round(d_pallas_ms, 3)
        results_d["speedup"] = speedup_of(d_jnp_ms, d_pallas_ms)
    else:
        results_d["pallas_ms"] = None
        results_d["note"] = "Mosaic delivery kernel is TPU-gated; re-run on the accelerator"
    print(json.dumps(results_d))

    if args.profile:
        from rapid_tpu.models.virtual_cluster import VirtualCluster
        from rapid_tpu.utils.profiling import trace

        n = 100_000

        def build_churn(seed: int):
            vc = VirtualCluster.create(
                n, n_slots=n + 2500, cohorts=64, fd_threshold=3, seed=seed,
                use_pallas=on_tpu, delivery_spread=2,
            )
            vc.assign_cohorts_roundrobin()
            vc.crash(np.random.default_rng(seed + 1).choice(n, size=2500, replace=False))
            vc.inject_join_wave(np.arange(n, n + 2500))
            vc.sync()
            return vc

        build_churn(0).run_to_decision(max_steps=96)  # warm/compile outside the trace
        vc2 = build_churn(1)
        with trace(args.profile):
            vc2.run_to_decision(max_steps=96)
        print(f"profile written to {args.profile}")


if __name__ == "__main__":
    main()
