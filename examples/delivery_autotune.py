"""Autotune the Pallas delivery kernel's lane-tile width per engine shape.

The delivery kernel tiles slots over lanes with a static tile width
(``EngineConfig.pallas_lanes``, default 128). At small N the width barely
matters; at N=1M the grid has N/width steps, so wider tiles amortize
per-step overhead — but too wide overflows VMEM or starves the pipeline.
Outputs are bit-identical across widths (the jitter hash is salted by the
GLOBAL slot index), so this is purely a latency knob.

This sweeps widths at the two headline shapes ([64, 100K] — the BASELINE
churn scenario — and [8, 1M] — the scale point) with the slope method from
pallas_microbench (two chained-loop lengths; cancels the constant
dispatch + fetch term exactly, which would otherwise swamp a millisecond
kernel). Prints one JSON line per shape with the per-width slopes and the
winner; run on the chip:

    python examples/delivery_autotune.py [--widths 128,256,512,1024]

bench.py picks the winners up automatically from the committed
``evidence/*/autotune.jsonl`` (env overrides: RAPID_TPU_BENCH_LANES for
the main workload, RAPID_TPU_BENCH_LANES_1M for the 1M point).
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--widths", default="128,256,512,1024")
    parser.add_argument("--platform", default=None)
    parser.add_argument("--interpret", action="store_true",
                        help="run the kernel in interpret mode (CPU smoke "
                        "of the sweep machinery; timings meaningless)")
    args = parser.parse_args()
    widths = [int(w) for w in args.widths.split(",")]

    if args.interpret and not args.platform:
        # The interpret smoke is a CPU run; it must not take the chip.
        args.platform = "cpu"
    if args.platform:
        from rapid_tpu.utils.platform import force_platform

        if not force_platform(args.platform):
            raise RuntimeError(f"could not force platform {args.platform!r}")

    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    import numpy as np

    from examples.pallas_microbench import slope_timed
    from rapid_tpu.models.virtual_cluster import VirtualCluster, _edge_masks
    from rapid_tpu.ops.pallas_kernels import delivery_new_bits_pallas

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.interpret:
        raise RuntimeError(
            "autotune needs the TPU (Mosaic path); pass --interpret for a "
            "CPU smoke of the machinery"
        )

    shapes = [(64, 100_000, 2), (8, 1_000_000, 2)]
    rng = np.random.default_rng(0)
    for c, n, spread in shapes:
        if args.interpret:
            n = min(n, 4_000)  # CPU interpret mode is slow; smoke only
        vc = VirtualCluster.create(
            n, cohorts=c, fd_threshold=1, seed=1, delivery_spread=spread,
        )
        vc.assign_cohorts_roundrobin()
        vc.crash(np.asarray(rng.choice(n, size=max(1, n // 100), replace=False)))
        vc.step()  # fire the detectors
        cfg, state = vc.cfg, vc.state
        _, blocked_rows = _edge_masks(cfg, state, vc.faults)
        age_kn = state.round_idx - state.fire_round.T
        epoch = state.config_epoch.astype(jnp.uint32).reshape(1)

        result = {"platform": platform, "shape": [c, n], "spread": spread,
                  "per_width_ms": {}}
        baseline_out = None
        for width in widths:
            if not args.interpret:
                def make_chained(iters, width=width):
                    @partial(jax.jit, static_argnums=(2,))
                    def loop(blocked, age, n_iter):
                        def body(i, acc):
                            out = delivery_new_bits_pallas(
                                blocked,
                                age - (acc % 2).astype(jnp.int32),
                                epoch, cfg.k, spread, 1000,
                                lanes=width,
                            )
                            return acc + jnp.sum(out)

                        return lax.fori_loop(0, n_iter, body, jnp.uint32(0))

                    return lambda: int(loop(blocked_rows, age_kn, iters))

                per_call, _ = slope_timed(make_chained)
                result["per_width_ms"][str(width)] = round(per_call, 4)
            # Cross-width equivalence (the bit-identical claim). In
            # --interpret smoke mode this is the whole test: slope-timing
            # interpreted Mosaic would take minutes per width for numbers
            # that mean nothing.
            out = delivery_new_bits_pallas(
                blocked_rows, age_kn, epoch, cfg.k, spread, 1000,
                interpret=args.interpret, lanes=width,
            )
            if baseline_out is None:
                baseline_out = np.asarray(out)  # fetch ONCE ([32, n] uint32)
            else:
                np.testing.assert_array_equal(np.asarray(out), baseline_out)
        if result["per_width_ms"]:
            best = min(result["per_width_ms"], key=result["per_width_ms"].get)
            result["best_width"] = int(best)
        else:
            result["best_width"] = None
            result["note"] = "interpret smoke: equivalence only, no timing"
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
