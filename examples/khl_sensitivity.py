"""K/H/L sensitivity of almost-everywhere agreement (paper Fig. 11 analog).

The paper's experiment (§Evaluation, "K, H, L sensitivity study"): 1000
processes, F random failures; "We generate alert messages from the F
processes' observers and deliver these alerts to each process in a uniform
random order. We count the number of processes that announce a membership
proposal that did not include all F processes (a conflict)." — i.e. the
receivers differ ONLY in alert arrival ORDER, each order an independent
uniform permutation of the F*K alerts, and the conflict rate is the
FRACTION OF PROCESSES that announced early (a proposal missing >= 1 victim).

The engine reproduces that model BY DERIVATION, not tuning:

  * every (cohort, edge) delivery delay is an independent uniform draw in
    [0, spread] (hash streams, `_deliver_alerts`); as spread grows, the
    induced per-cohort arrival order converges to exactly the paper's
    independent uniform permutation (ties have probability 1/(spread+1)
    per pair and vanish);
  * all alerts fire simultaneously (stagger=0), matching "we generate
    alert messages from the F processes' observers" as one event;
  * the metric is the paper's: the fraction of receiver cohorts whose
    FIRST announced proposal misses >= 1 victim. (Each cohort is one
    sampled receiver state shared by ~N/C members.)

The only approximation is time discretization: simultaneous arrivals within
one round are tallied atomically, which can only HIDE an early announcement
(the batch is the favorable order), so measured rates approach the paper's
from below as --delivery-spread grows. Default 128 puts the per-pair tie
probability under 1%. No parameter is fitted to the paper's reported rates.

Usage: python examples/khl_sensitivity.py [--n 1000] [--reps 20] [--cohorts 64]
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _detector_experiment_fn():
    """Build the jitted detector-only experiment (cached across cells).

    The paper's Fig. 11 study has NO consensus — it is a pure cut-detector
    experiment run until every receiver announces. Driving the full engine
    would let the cluster DECIDE (and apply the view change) long before
    slow receivers announce, truncating the sample; so this loop drives
    exactly the engine's delivery + cut-detection kernels
    (`_deliver_alerts` + `_cohort_cut_detection`, the same code the engine
    executes per round) and latches each cohort's FIRST announced proposal
    mask, entirely on device in one dispatch per run.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from rapid_tpu.models.virtual_cluster import (
        _cohort_cut_detection,
        _deliver_alerts,
    )

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def experiment(cfg, state, blocked_rows, budget):
        def cond(carry):
            _, _, got, t = carry
            return (~jnp.all(got)) & (t < budget)

        def body(carry):
            state, first_mask, got, t = carry
            new_bits = _deliver_alerts(cfg, state, state.fire_round, blocked_rows)
            heard_down = jnp.any((new_bits != 0) & state.alive[None, :], axis=1)
            (report_bits, released, announced, seen_down, proposed_now,
             prop_masks, *_) = _cohort_cut_detection(cfg, state, new_bits, heard_down)
            state = state._replace(
                report_bits=report_bits, released=released,
                announced=announced, seen_down=seen_down,
                round_idx=state.round_idx + 1,
            )
            newly = proposed_now & ~got
            first_mask = jnp.where(newly[:, None], prop_masks, first_mask)
            return (state, first_mask, got | proposed_now, t + 1)

        init = (
            state,
            jnp.zeros((cfg.c, cfg.n), dtype=bool),
            jnp.zeros((cfg.c,), dtype=bool),
            jnp.int32(0),
        )
        _, first_mask, got, t = jax.lax.while_loop(cond, body, init)
        return first_mask, got, t

    return experiment


_EXPERIMENT = None


def run_once(n, k, h, l, f, cohorts, seed, delivery_spread=128, stagger=0,
             loss=0.0, delay_permille=1000) -> tuple:
    """One paper-experiment run.

    Returns (conflicted_cohorts, announced_cohorts, rounds_to_all_announced).
    A cohort is conflicted iff its first announced proposal differs from the
    full victim set (the paper's per-process conflict metric)."""
    global _EXPERIMENT
    import jax.numpy as jnp

    from rapid_tpu.models.virtual_cluster import VirtualCluster, _edge_masks

    if _EXPERIMENT is None:
        _EXPERIMENT = _detector_experiment_fn()

    rng = np.random.default_rng(seed)
    vc = VirtualCluster.create(
        n, k=k, h=h, l=l, cohorts=cohorts, fd_threshold=1, seed=seed,
        delivery_spread=delivery_spread, delivery_prob_permille=delay_permille,
    )
    cohort_of = rng.integers(0, cohorts, size=n).astype(np.int32)
    vc.assign_cohorts(cohort_of)
    if loss > 0:
        rx_block = np.zeros((cohorts, vc.cfg.n), dtype=bool)
        for c in range(1, cohorts):
            rx_block[c] = rng.random(vc.cfg.n) < loss
        vc.set_rx_block(rx_block)

    victims = rng.choice(n, size=f, replace=False)
    vc.crash(victims)
    # "We generate alert messages from the F processes' observers": fire all
    # victim edges as one event (stamped at the current round; optional
    # per-edge stagger delays firing like real detection jitter would).
    vc._stamp_fired_edges(jnp.asarray(victims), np.ones((f, k), dtype=bool))
    if stagger:
        # Spread fire rounds over [0, stagger] (delivery uses
        # round - fire_round). np.array, not asarray: jax buffers view as
        # read-only numpy.
        offs = rng.integers(0, stagger + 1, size=(f, k)).astype(np.int32)
        fire = np.array(vc.state.fire_round)
        fire[victims] = offs  # [f, k] rows for victim slots
        vc.state = vc.state._replace(fire_round=jnp.asarray(fire))

    _, blocked_rows = _edge_masks(vc.cfg, vc.state, vc.faults)
    budget = delivery_spread + stagger + 64
    first_mask, got, t = _EXPERIMENT(vc.cfg, vc.state, blocked_rows, budget)

    got = np.asarray(got)
    first_mask = np.asarray(first_mask)
    victims_mask = np.zeros(n, dtype=bool)
    victims_mask[victims] = True
    conflicted = int(
        (got & (first_mask[:, :n] != victims_mask[None, :]).any(axis=1)).sum()
    )
    return conflicted, int(got.sum()), int(t)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=1000)
    parser.add_argument("--reps", type=int, default=20,
                        help="paper: 20 repetitions per combination")
    parser.add_argument("--cohorts", type=int, default=64,
                        help="independent receiver states sampled per run")
    parser.add_argument("--delivery-spread", type=int, default=128,
                        help="uniform delay support per (cohort, edge); large "
                        "spread => per-cohort arrival order converges to the "
                        "paper's independent uniform permutation (see module "
                        "docstring — derived, not tuned)")
    parser.add_argument("--stagger", type=int, default=0,
                        help="max rounds of per-edge detection jitter (paper "
                        "model: 0 — alerts all generated at once)")
    parser.add_argument("--delay-permille", type=int, default=1000,
                        help="probability (permille, per cohort-edge) of a "
                        "nonzero delay — models milder-than-paper sub-round "
                        "skew; 1000 = the full uniform draw the paper model "
                        "derives to")
    parser.add_argument("--loss", type=float, default=0.0,
                        help="one-way loss fraction per non-primary cohort (paper sim: 0)")
    parser.add_argument(
        "--platform",
        default="cpu",
        help="jax platform (default cpu: the sweep is small and need not "
        "hold the chip; pass the accelerator platform explicitly to run "
        "there)",
    )
    args = parser.parse_args()

    from rapid_tpu.utils.platform import force_platform

    if not force_platform(args.platform):
        raise RuntimeError(
            f"could not force jax platform {args.platform!r} (a backend was "
            "already initialized); refusing to sweep on an unintended backend"
        )

    k = 10
    print(f"N={args.n}, K={k}, cohorts={args.cohorts}, reps={args.reps}, "
          f"spread={args.delivery_spread} (paper-permutation mode)")
    print(f"{'H':>3} {'L':>3} {'F':>4} {'conflict%':>10} {'silent%':>8} "
          f"{'avg rounds':>11}")
    for h in (9, 8, 7, 6):
        for l in (1, 2, 3, 4):
            if l >= h:
                continue
            for f in (2, 4, 8, 16):
                conflicted_total, announced_total, rounds_sum = 0, 0, 0
                total = args.cohorts * args.reps
                for rep in range(args.reps):
                    conflicted, announced, rounds = run_once(
                        args.n, k, h, l, f, args.cohorts,
                        seed=hash((h, l, f, rep)) % 2**31,
                        delivery_spread=args.delivery_spread,
                        stagger=args.stagger,
                        loss=args.loss,
                        delay_permille=args.delay_permille,
                    )
                    conflicted_total += conflicted
                    announced_total += announced
                    rounds_sum += rounds
                # Conflict rate over ANNOUNCED receivers; cohorts that never
                # announced (possible only under --loss, which can blind a
                # cohort below H forever) are surfaced as silent%, never
                # silently counted as conflict-free.
                print(
                    f"{h:>3} {l:>3} {f:>4} "
                    f"{100.0 * conflicted_total / max(announced_total, 1):>9.2f}% "
                    f"{100.0 * (total - announced_total) / total:>7.1f}% "
                    f"{rounds_sum / args.reps:>11.1f}"
                )


if __name__ == "__main__":
    main()
