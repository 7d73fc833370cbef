"""Pallas TPU kernel for the protocol hot path, plus the uint32-bitmask
watermark core it rides on.

The hot per-round computation is the ALERT DELIVERY pass: per (cohort, ring)
bitwise work over gathered rx-block words plus a per-edge jitter hash draw.
The Mosaic kernel below (``delivery_new_bits_pallas``) runs the whole
(cohort-word x ring) loop nest in VMEM — measured 2.25x over XLA's fusion at
engine shapes (evidence/round2/microbench_slope.json) and on by default on
TPU via ``EngineConfig.use_pallas``.

The cut detector's watermark pass (merge report bits, popcount, classify
against H/L — ``MultiNodeCutDetector.java:84-128``) lives here too as
``watermark_merge_classify``, but as a plain jnp elementwise core: a
hand-written Mosaic version of it was benchmarked at 0.69x of XLA's own
fusion at engine shapes (2.52 ms vs 3.67 ms at [8, 1M], EVALUATION.md) and
was deleted — XLA already fuses an elementwise OR+popcount+compare sweep
optimally, so the kernel carried maintenance cost for negative return.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rapid_tpu.ops.hashing import mix32 as _mix32

_LANES = 128


def _popcount32(v):
    """Branch-free 32-bit popcount (Hacker's Delight 5-1), VPU-friendly."""
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def watermark_merge_classify_impl(
    old_bits: jnp.ndarray,
    new_bits: jnp.ndarray,
    subject_mask: jnp.ndarray,
    h,
    l,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge per-subject report bitmasks and classify against H/L.

    old_bits/new_bits: uint32 ring-report bitmasks; subject_mask: bool
    (present members + pending joiners — reports for anything else clear to 0,
    the filter invariant of MembershipService.java:644-675). Any shape:
    elementwise, shape-preserving (no resharding of distributed inputs); XLA
    fuses the whole sweep (see module docstring for why there is deliberately
    no Mosaic version).
    ``h``/``l`` may be Python ints (the classic static engine config) or
    traced int32 scalars — the tenant fleet (rapid_tpu/tenancy) vmaps this
    pass with PER-TENANT watermarks, so the comparisons must trace; both
    spellings lower to the identical compare ops.
    Returns (merged_bits at the INPUT bitmask dtype, cls int32: 0 none /
    1 flux / 2 stable), shaped like the inputs. Dtype-preserving on
    purpose: the compact engine stores report bitmasks at uint8/uint16
    (models/state.compaction_policy) and a uint32 operand here would
    silently re-widen the lane — the weak-typed zero keeps the merge at
    the lane's own width while the popcount accumulates at int32.
    """
    merged = jnp.where(subject_mask, old_bits | new_bits, 0)
    tally = _popcount32(merged)
    stable = tally >= h
    flux = (tally >= l) & (tally < h)
    cls = jnp.where(stable, jnp.int32(2), jnp.where(flux, jnp.int32(1), jnp.int32(0)))
    return merged, cls


#: The standalone jitted entry (host twins / tests); the engine's round body
#: calls the impl directly so traced per-tenant h/l stay legal.
watermark_merge_classify = jax.jit(
    watermark_merge_classify_impl, static_argnames=()
)


def _delivery_kernel(k, w, spread, permille, lanes, blocked_ref, age_ref, epoch_ref, out_ref):
    """Fused per-cohort alert delivery for one ``lanes``-slot tile.

    The engine's delivery pass (virtual_cluster._deliver_alerts) is, per
    round, K iterations of [c, n] bitwise work over gathered rx-block words
    plus a per-(cohort, edge) hash draw — bandwidth-bound elementwise
    traffic. This kernel runs the whole (cohort-word x ring) loop nest in
    VMEM: one read of the blocked words and ages, one write of the packed
    result, nothing materialized per ring.

    Layout: 32 cohorts per uint32 word ride the sublane axis as a
    [32, lanes] tile; slots ride lanes (lanes = tile width, a multiple of
    128 — tunable per shape, examples/delivery_autotune.py); cohort words
    and rings are static Python loops. Hash streams are bit-identical to
    the jnp path AND across tile widths (the draw is salted by the GLOBAL
    slot index, tile*lanes + lane).
    """
    lane = jax.lax.broadcasted_iota(jnp.uint32, (32, lanes), 1)
    j = jax.lax.broadcasted_iota(jnp.uint32, (32, lanes), 0)  # cohort-in-word
    tile = pl.program_id(0)
    slot = tile.astype(jnp.uint32) * jnp.uint32(lanes) + lane
    slot_salt = slot * jnp.uint32(0x85EBCA77)
    epoch_salt = epoch_ref[0] * jnp.uint32(0x27D4EB2F)
    for wi in range(w):
        acc = jnp.zeros((32, lanes), jnp.uint32)
        cohort_term = (jnp.uint32(wi * 32) + j) * jnp.uint32(0x9E3779B1)
        for ring in range(k):
            words = blocked_ref[wi * k + ring : wi * k + ring + 1, :]  # [1, lanes]
            blocked_bit = (jnp.broadcast_to(words, (32, lanes)) >> j) & jnp.uint32(1)
            age = jnp.broadcast_to(age_ref[ring : ring + 1, :], (32, lanes))
            if spread > 0:
                rnd = _mix32(
                    cohort_term
                    ^ slot_salt
                    ^ jnp.uint32((ring * 0xC2B2AE3D) & 0xFFFFFFFF)
                    ^ epoch_salt
                )
                if permille >= 1000:
                    delay = (rnd % jnp.uint32(spread + 1)).astype(jnp.int32)
                else:
                    gate = (
                        _mix32(rnd ^ jnp.uint32(0xA511E9B3)) % jnp.uint32(1000)
                    ) < jnp.uint32(permille)
                    delay = jnp.where(
                        gate, 1 + (rnd % jnp.uint32(spread)).astype(jnp.int32), 0
                    )
            else:
                delay = jnp.int32(0)
            delivered = (age >= delay) & (blocked_bit == 0)
            acc = acc | (delivered.astype(jnp.uint32) << jnp.uint32(ring))
        out_ref[wi * 32 : (wi + 1) * 32, :] = acc


@functools.partial(
    jax.jit, static_argnames=("k", "spread", "permille", "interpret", "lanes")
)
def delivery_new_bits_pallas(
    blocked_rows: jnp.ndarray,
    age_kn: jnp.ndarray,
    epoch: jnp.ndarray,
    k: int,
    spread: int,
    permille: int,
    interpret: bool = False,
    lanes: int = _LANES,
) -> jnp.ndarray:
    """Fused delivery pass: ``new_bits[w*32, n]`` from packed rx-block rows.

    blocked_rows: [w*k, n] uint32 — row wi*k+ring = the wi-th cohort word of
    ring's per-slot block bits (virtual_cluster._edge_masks layout).
    age_kn: [k, n] int32 rounds since each edge fired (negative = unfired).
    epoch: [1] uint32 configuration epoch (salts the delay draws).
    Returns all w*32 cohort lanes; callers slice [:c]. Slots are padded to
    the ``lanes``-wide tile internally (padding ages are hugely negative,
    so the pad lanes deliver nothing). ``lanes`` (multiple of 128) sets the
    per-grid-step tile width — wider tiles amortize grid overhead at large
    N; outputs are bit-identical across widths.
    """
    if lanes % _LANES or lanes <= 0:
        raise ValueError(f"lanes must be a positive multiple of {_LANES}: {lanes}")
    wk, n = blocked_rows.shape
    w = wk // k
    n_pad = (-n) % lanes
    if n_pad:
        blocked_rows = jnp.pad(blocked_rows, ((0, 0), (0, n_pad)))
        age_kn = jnp.pad(age_kn, ((0, 0), (0, n_pad)), constant_values=-(1 << 29))
    total = n + n_pad
    grid = (total // lanes,)
    out = pl.pallas_call(
        functools.partial(_delivery_kernel, k, w, spread, permille, lanes),
        out_shape=jax.ShapeDtypeStruct((w * 32, total), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((wk, lanes), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, lanes), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (w * 32, lanes), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(blocked_rows, age_kn, epoch.astype(jnp.uint32))
    return out[:, :n]


def reports_matrix_to_bits(reports: jnp.ndarray) -> jnp.ndarray:
    """[..., n, k] bool report matrix -> [..., n] uint32 bitmasks."""
    k = reports.shape[-1]
    weights = (jnp.uint32(1) << jnp.arange(k, dtype=jnp.uint32))
    return jnp.sum(reports.astype(jnp.uint32) * weights, axis=-1, dtype=jnp.uint32)


def bits_to_reports_matrix(bits: jnp.ndarray, k: int) -> jnp.ndarray:
    """[..., n] uint32 bitmasks -> [..., n, k] bool report matrix."""
    shifts = jnp.arange(k, dtype=jnp.uint32)
    return ((bits[..., None] >> shifts) & 1).astype(bool)
