"""Per-tenant K/H/L knob autotune over a tenant fleet — one sweep, one
dispatch per round.

``examples/khl_sensitivity.py`` sweeps (H, L) sequentially, one engine run
per cell; this example runs the whole candidate grid as ONE
:class:`~rapid_tpu.tenancy.TenantFleet` (one tenant per knob setting,
identical scenario) and picks the winner with the khl_sensitivity conflict
metric as the objective — the ``delivery_autotune.py`` winner-selection
shape (a per-candidate table + one ``best_knob`` field), batched.

Usage:

    python examples/fleet_khl_autotune.py [--n 1000] [--f 8] \
        [--knobs 9/4,8/3,7/2] [--spread 8] [--seed 0]

Prints one JSON line per seed (the ``rapid_tpu.tenancy.autotune.sweep_khl``
artifact).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=1000)
    parser.add_argument("--f", type=int, default=8,
                        help="simultaneous failures per scenario")
    parser.add_argument("--knobs", default="9/4,8/3,7/2,6/2,5/1",
                        help="comma-separated H/L candidates, one tenant each")
    parser.add_argument("--cohorts", type=int, default=16)
    parser.add_argument("--spread", type=int, default=8,
                        help="delivery-delay support (rounds) — the skew "
                        "that makes low H conflict-prone")
    parser.add_argument("--seeds", default="0",
                        help="comma-separated scenario seeds, one sweep each")
    parser.add_argument(
        "--platform", default="cpu",
        help="jax platform (default cpu: the sweep is small and need not "
        "hold the chip)",
    )
    args = parser.parse_args()

    from rapid_tpu.utils.platform import force_platform

    if not force_platform(args.platform):
        raise RuntimeError(
            f"could not force jax platform {args.platform!r} (a backend was "
            "already initialized); refusing to sweep on an unintended backend"
        )

    from rapid_tpu.tenancy.autotune import sweep_khl

    knob_grid = [
        tuple(int(part) for part in cell.split("/"))
        for cell in args.knobs.split(",")
    ]
    for seed in (int(s) for s in args.seeds.split(",")):
        result = sweep_khl(
            n=args.n, f=args.f, knob_grid=knob_grid, cohorts=args.cohorts,
            seed=seed, delivery_spread=args.spread,
        )
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
