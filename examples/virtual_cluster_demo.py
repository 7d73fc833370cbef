"""Demo: drive the TPU virtual-cluster engine through the BASELINE scenarios.

Runs (scaled to the attached accelerator):
  1. 1K virtual nodes, 1% crash-fault injection
  2. 10K virtual nodes, batched 512-node join wave
  3. 50K virtual nodes, asymmetric one-way partition
  4. 100K virtual nodes, 5% concurrent churn

Usage: python examples/virtual_cluster_demo.py [--small]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def timed(label, fn):
    start = time.perf_counter()
    result = fn()
    elapsed = (time.perf_counter() - start) * 1000
    print(f"  {label}: {elapsed:.1f} ms -> {result}")
    return result


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--small", action="store_true", help="scale down for quick runs")
    parser.add_argument(
        "--platform",
        default=None,
        help="force a jax platform (e.g. cpu). Default: whatever jax finds "
        "(the accelerator when there is one)",
    )
    args = parser.parse_args()
    scale = 10 if args.small else 1

    if args.platform:
        from rapid_tpu.utils.platform import force_platform

        if not force_platform(args.platform):
            raise RuntimeError(f"could not force jax platform {args.platform!r}")

    import jax
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    print(f"devices: {jax.devices()}")

    # 1. crash faults
    n = 1000 // scale * scale
    print(f"[1] N={n}, 1% crash")
    vc = VirtualCluster.create(n, fd_threshold=3, seed=0)
    victims = np.random.default_rng(0).choice(n, size=max(1, n // 100), replace=False)
    vc.crash(victims)
    vc.run_until_converged()  # warm-up compile included
    print(f"  converged: members {vc.membership_size}, epoch {vc.config_epoch}")

    # 2. join wave
    n = 10_000 // scale
    wave = 512 // scale
    print(f"[2] N={n}, {wave}-node join wave")
    vc = VirtualCluster.create(n, n_slots=n + wave, fd_threshold=3, seed=1)
    vc.inject_join_wave(list(range(n, n + wave)))
    timed("join wave, rounds", lambda: vc.run_until_converged()[0])
    # The driver times its own calls: every round above is one sample of
    # engine_dispatch_ms{phase="step"} (first round: with the compile).
    steps = vc.metrics.phase_timings["engine_dispatch"]["step"]
    print(f"  members {vc.membership_size}; {steps.count} rounds, "
          f"{steps.sum:.1f} ms inside the step phase")

    # 3. asymmetric one-way partition
    n = 50_000 // scale
    print(f"[3] N={n}, one-way partition on 10 nodes")
    vc = VirtualCluster.create(n, fd_threshold=3, seed=2)
    faulty = list(range(100, 110))
    probe_fail = np.zeros((vc.cfg.n, vc.cfg.k), dtype=bool)
    probe_fail[faulty, :] = True  # all observers see these nodes as dead
    vc.set_flaky_edges(probe_fail)
    vc.run_until_converged()
    removed = ~vc.alive_mask[faulty]
    print(f"  removed exactly the faulty set: {removed.all()} "
          f"(members {vc.membership_size})")

    # 4. churn
    n = 100_000 // scale
    print(f"[4] N={n}, 5% churn")
    vc = VirtualCluster.create(n, n_slots=int(n * 1.05), fd_threshold=3, seed=3)
    rng = np.random.default_rng(3)
    crash = rng.choice(n, size=n // 20, replace=False)
    vc.crash(crash)
    vc.inject_join_wave(list(range(n, int(n * 1.05))))
    epochs = 0
    start = time.perf_counter()
    while epochs < 2:
        rounds, events = vc.run_until_converged(max_steps=32)
        if events is None:
            break
        epochs = vc.config_epoch
    elapsed = (time.perf_counter() - start) * 1000
    print(f"  churn settled in {elapsed:.1f} ms: members {vc.membership_size}, "
          f"epochs {vc.config_epoch}")


if __name__ == "__main__":
    main()
