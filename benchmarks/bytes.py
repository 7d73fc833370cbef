"""Operations and bytes a kernel call needs, from its shapes alone.

The yardstick for ``<kernel>_roofline`` metrics: the least a chip could do
for the call, whatever the program does inside it.
"""

from __future__ import annotations

#: Integer operations the delivery pass needs per (cohort, ring, slot): the
#: salted 32-bit mix (two multiplies, three shifts, six xors), the modulo of
#: the delay draw, two compares, the rx-block bit (shift, and), and the pack
#: (shift, or). They run on the vector unit, whose peak is far under the
#: matrix unit's; the table's peak is the chip's bf16 figure, so the share
#: this gives is an upper bound on the compute side.
DELIVERY_OPS_PER_ELEMENT = 18


def delivery_new_bits(n_slots: int, cohorts: int, k: int, lanes: int = 128) -> dict:
    """``ops/pallas_kernels.py::delivery_new_bits_pallas`` for one round:
    reads the packed rx-block rows [w*k, n] and the edge ages [k, n], writes
    the report bits [w*32, n], all 32-bit, with n padded to the lane tile and
    w = ceil(cohorts / 32) cohort words."""
    words = -(-cohorts // 32)
    n_padded = -(-n_slots // lanes) * lanes
    return {
        "bytes": 4 * n_padded * (words * k + k + words * 32),
        "ops": DELIVERY_OPS_PER_ELEMENT * words * 32 * k * n_padded,
    }


def least_seconds(need: dict, peak: dict) -> tuple:
    """(seconds, which bound) of the roofline: the larger of operations over
    peak operations and bytes over peak bandwidth."""
    compute = need["ops"] / peak["bf16_flops_per_s"]
    memory = need["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute > memory else (memory, "memory")
