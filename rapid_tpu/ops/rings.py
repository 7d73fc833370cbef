"""Device kernels for the K-ring expander topology.

The reference maintains K TreeSets and answers successor/predecessor queries
one node at a time (``MembershipView.java:234-322``). On TPU the whole
topology is one batched computation: N node slots carry K seeded 64-bit hash
keys (as uint32 hi/lo lanes); for each ring we argsort the alive slots and
read every node's observer (ring successor) and subject (ring predecessor) in
one gather. Dynamic membership is a padded ``alive`` mask — adds/deletes flip
mask bits and the next ``ring_topology`` call re-derives the permutations,
keeping all shapes static for XLA.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from rapid_tpu.ops.cut_detection import first_set_slots
from rapid_tpu.ops.hashing import lex_argsort
from rapid_tpu.protocol.view import ring_key
from rapid_tpu.utils.dispatch import cond_across, scope


class RingTopology(NamedTuple):
    """Batched observer/subject tables for all K rings.

    obs_idx[k, i]  = slot of the observer (ring-k successor) of slot i, or -1
    subj_idx[k, i] = slot of the subject (ring-k predecessor) of slot i, or -1
    order[k, p]    = slot at sorted ring position p (alive slots first)

    Entries are -1 for dead slots and when fewer than 2 nodes are alive
    (matching MembershipView.java:240-242's empty observer list).
    """

    obs_idx: jnp.ndarray
    subj_idx: jnp.ndarray
    order: jnp.ndarray


def endpoint_ring_keys(endpoints, k: int, topology: str = "native"):
    """Host-side: K seeded 64-bit ring keys per endpoint, split into uint32
    lanes of shape [K, N]. Uses the exact key function of the host view so
    device and host topologies agree bit-for-bit. The native C library (when
    built) computes the whole batch at memory bandwidth; the Python fallback
    is bit-identical.

    Native topology only: the u64 keyspace and unsigned ring order are what
    the device kernels assume. ``TOPOLOGY_JAVA`` views order rings by SIGNED
    4-byte-port hashes (``view.ring_key_java``); feeding those through this
    seam would silently compute divergent ring orders, so it is rejected."""
    if topology != "native":
        raise ValueError(
            f"the device/engine path requires the native topology; got {topology!r} "
            "(java-compat ring order is host-path only)"
        )
    from rapid_tpu.utils._native import native_ring_keys_batch

    keys = native_ring_keys_batch(
        [ep.hostname.encode("utf-8") for ep in endpoints],
        [ep.port for ep in endpoints],
        k,
    )
    if keys is None:
        keys = np.asarray(
            [[ring_key(ep, seed) for ep in endpoints] for seed in range(k)],
            dtype=np.uint64,
        )
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return jnp.asarray(hi), jnp.asarray(lo)


def _ring_topology_single(key_hi, key_lo, alive):
    """One ring: returns (obs_idx[N], subj_idx[N], order[N])."""
    n = key_hi.shape[0]
    dead = (~alive).astype(jnp.uint32)
    order = lex_argsort((dead, key_hi, key_lo))  # alive slots first, by 64-bit key
    n_alive = jnp.sum(alive.astype(jnp.int32))

    positions = jnp.arange(n, dtype=jnp.int32)
    in_ring = positions < n_alive
    succ_pos = jnp.where(positions + 1 >= n_alive, 0, positions + 1)
    pred_pos = jnp.where(positions - 1 < 0, n_alive - 1, positions - 1)
    valid = in_ring & (n_alive >= 2)
    succ_slot = jnp.where(valid, order[succ_pos], -1)
    pred_slot = jnp.where(valid, order[pred_pos], -1)

    obs_idx = jnp.full((n,), -1, dtype=jnp.int32).at[order].set(succ_slot)
    subj_idx = jnp.full((n,), -1, dtype=jnp.int32).at[order].set(pred_slot)
    return obs_idx, subj_idx, order.astype(jnp.int32)


@jax.jit
def ring_topology(key_hi: jnp.ndarray, key_lo: jnp.ndarray, alive: jnp.ndarray) -> RingTopology:
    """All K rings at once: key_hi/key_lo are [K, N] uint32, alive is [N] bool."""
    obs, subj, order = jax.vmap(_ring_topology_single, in_axes=(0, 0, None))(
        key_hi, key_lo, alive
    )
    return RingTopology(obs_idx=obs, subj_idx=subj, order=order)


def ring_perms(key_hi: jnp.ndarray, key_lo: jnp.ndarray) -> jnp.ndarray:
    """Static per-ring key-order permutations, [K, N] int32: perm[k, p] is
    the slot at position p of ring k's FIXED key order (aliveness ignored).

    Ring keys never change after slot creation, so this is computed ONCE;
    every later topology query is O(N) scans over it
    (``ring_topology_from_perm``) instead of an O(N log N) re-sort per view
    change — at N=1M the per-view-change K-ring argsort is the single
    largest block of the commit path.
    """
    # lex_argsort already batches over leading axes (it sorts dimension=-1).
    return lex_argsort((jnp.asarray(key_hi), jnp.asarray(key_lo))).astype(jnp.int32)


def ring_walk_pieces(n: int) -> tuple[int, int]:
    """``(piece_bits, pieces)`` of the ring walk's scan word at ring length
    ``n`` (:func:`_from_perm_single`): a position takes the top
    ``b = bit_length(n - 1)`` bits of a 32-bit lane, a piece of the slot the
    ``32 - b`` bits under it, and a slot (``b`` bits too) goes in
    ``ceil(b / (32 - b))`` pieces, one scan pair a piece: 1 piece up to
    65,536 slots (the fleets' 1,000 and 2,000), 2 up to 2,097,152
    (``cluster-100k``'s 102,500, ``cluster-1m``), 3 up to 16,777,216
    (``cluster-10m``). A static fact of the shape, like
    :data:`RING_AT_A_TIME_SLOTS`: no option, and nothing to scrape."""
    slot_bits = max(1, (n - 1).bit_length())
    piece_bits = 32 - slot_bits
    return piece_bits, -(-slot_bits // piece_bits)


_INT32_MIN, _INT32_MAX = np.int32(-(2**31)), np.int32(2**31 - 1)


def _ordered_int32(word):
    """uint32 -> the int32 that orders the same (top bit flipped), so that
    the walk scans int32 lanes: the TPU compiler runs a ``cummax`` / reverse
    ``cummin`` pair over uint32 slower and compiles it twice as long (at
    ``[1000000]`` 2.55 ms against 1.75, first call 95 s against 45; at
    ``[102500]`` 1.21 against 0.92 ms, 46 s against 7; PR 37, TPU v5e)."""
    return jax.lax.bitcast_convert_type(word ^ jnp.uint32(0x80000000), jnp.int32)


def _ordered_uint32(word):
    """The inverse of :func:`_ordered_int32`."""
    return jax.lax.bitcast_convert_type(word, jnp.uint32) ^ jnp.uint32(0x80000000)


def _neighbour_slots(perm, ao):
    """One ring's walk proper: ``(succ_slot[N], pred_slot[N])`` as uint32, the
    slot at the first alive position strictly after each ring position and at
    the last one strictly before it (cyclic), from the static key order
    ``perm`` and the alive bit per ring position ``ao``. Defined at DEAD
    positions too (a dead position's neighbours are those its slot would have
    if it were alive), which is what a repair of the table at a cut's
    positions reads (:func:`_repair_observers`); arbitrary where nobody is
    alive.

    The walk scans WHO sits at the neighbouring position, not WHERE it is:
    the scanned 32-bit word holds the position in its high bits and a piece
    of the slot at that position in its low ``piece_bits``
    (:func:`ring_walk_pieces`). Among alive positions the words order as the
    positions do, so a suffix-min returns (in the low bits) the slot of the
    first alive position at or after p and a prefix-max that of the last at
    or before p; a shift by one along the ring makes the neighbour strict,
    and a slot wider than the lane's spare bits takes one scan pair a piece,
    put together again with shifts. No ``perm[...]`` by a computed position
    is left (PR 37; before it the scans carried positions and two gathers
    read the slots: 6.63 ms each of a 34.7 ms ring at 1M, where a scan pair
    is 1.8 ms; the ring is 22.0 ms now, the fleet's ``[256, 10, 1000]``
    52.0 ms for 115.2, TPU v5e). The words are unsigned, the lanes scanned
    are int32 (:func:`_ordered_int32`).

    Dead positions scan as the least word (prefix-max) and the greatest
    (suffix-min). Either can tie with a real word (position 0 holding slot
    piece 0; at n an exact power of two, the last position holding an
    all-ones piece), and a tie carries the same low bits, so it is harmless;
    "no alive position on that side" is never read off a word but off two
    scalars, the least and the greatest alive word: at or past the last
    alive position the walk wraps to the first alive slot, at or before the
    first to the last. They are a ``min`` and a ``max`` of their own, not
    ends of the scans, so each side needs its own scan only: a caller that
    takes ``obs_idx`` alone (the engine's rebuild) compiles one scan a piece
    and one scatter."""
    n = perm.shape[0]
    piece_bits, pieces = ring_walk_pieces(n)
    pos = jnp.arange(n, dtype=jnp.uint32)
    pos_field = pos << piece_bits
    slot = perm.astype(jnp.uint32)  # perm may come at int8 / int16 (compact)
    piece_mask = jnp.uint32((1 << piece_bits) - 1)

    succ_slot = pred_slot = jnp.zeros((n,), dtype=jnp.uint32)
    for piece in range(pieces):
        shift = piece * piece_bits
        word = _ordered_int32(pos_field | ((slot >> shift) & piece_mask))
        floor = jnp.where(ao, word, _INT32_MIN)  # a dead position: the least word,
        ceiling = jnp.where(ao, word, _INT32_MAX)  # or the greatest
        suffix_min = _ordered_uint32(jax.lax.cummin(ceiling, reverse=True))
        prefix_max = _ordered_uint32(jax.lax.cummax(floor))
        first_alive = _ordered_uint32(jnp.min(ceiling))
        last_alive = _ordered_uint32(jnp.max(floor))
        nxt = jnp.where(
            pos >= (last_alive >> piece_bits),  # nobody alive further on: wrap
            first_alive,
            jnp.concatenate([suffix_min[1:], first_alive[None]]),
        )
        prv = jnp.where(
            pos <= (first_alive >> piece_bits),  # nobody alive before: wrap
            last_alive,
            jnp.concatenate([last_alive[None], prefix_max[:-1]]),
        )
        succ_slot |= (nxt & piece_mask) << shift
        pred_slot |= (prv & piece_mask) << shift
    return succ_slot, pred_slot


@jax.jit
def _from_perm_single(perm, alive, ao=None):
    """One ring, sort-free: (obs_idx[N], subj_idx[N], order[N]) from the
    static key order. Successor among alive = slot at the next alive
    position in the fixed circular order, predecessor = slot at the
    previous one (:func:`_neighbour_slots`, the walk); the alive-first
    ``order`` is a stable partition (rank scans + one scatter).
    Bit-identical to ``_ring_topology_single``: restricting a fixed total
    order to the alive subset IS the alive order, and lex_argsort is stable
    so dead slots tie-break identically. With fewer than two alive every
    entry is -1.

    Jitted, so that an eager caller dispatches one program and not each of
    the walk's forty small operations: ``initial_state`` below
    :data:`RING_AT_A_TIME_SLOTS` is eager, and a fleet builds hundreds of
    tenants through it. Inside a traced caller the jit is inlined.

    ``ao`` is the alive bit per ring position, ``alive[perm]``, where the
    caller holds it (``EngineState.ring_alive``, kept exact by every view
    change: :func:`ring_tables_after_cut`); without it the walk gathers it.
    """
    n = perm.shape[0]
    if ao is None:
        ao = alive[perm]  # alive bit per ring position
    n_alive = jnp.sum(ao.astype(jnp.int32))
    succ_slot, pred_slot = _neighbour_slots(perm, ao)

    valid = ao & (n_alive >= 2)
    succ_slot = jnp.where(valid, succ_slot.astype(jnp.int32), -1)
    pred_slot = jnp.where(valid, pred_slot.astype(jnp.int32), -1)
    # full(-1), not zeros: if perm were ever not a permutation (corrupted
    # state), unwritten entries must read as the documented "no observer"
    # sentinel, never as valid slot 0.
    obs_idx = jnp.full((n,), -1, dtype=jnp.int32).at[perm].set(succ_slot)
    subj_idx = jnp.full((n,), -1, dtype=jnp.int32).at[perm].set(pred_slot)
    return obs_idx, subj_idx, _alive_first_order(perm, ao)


def _alive_first_order(perm, ao):
    """``lex_argsort((dead, keys...))`` without the sort: stable partition
    of the static key order into alive-first via rank scans + one scatter
    (``ao``: the alive bit per ring position)."""
    n = perm.shape[0]
    n_alive = jnp.sum(ao.astype(jnp.int32))
    alive_rank = jnp.cumsum(ao.astype(jnp.int32)) - 1
    dead_rank = n_alive + jnp.cumsum((~ao).astype(jnp.int32)) - 1
    return (
        jnp.zeros((n,), dtype=jnp.int32)
        .at[jnp.where(ao, alive_rank, dead_rank)]
        .set(perm)
    )


#: From this many slots on, ``ring_topology_from_perm`` takes the K rings
#: one at a time (a ``lax.map``) instead of all at once (a ``vmap``): one
#: algorithm under two schedules, chosen from the ring length the operand
#: shows. Measurements that place the bound (TPU v5e; PERF.md section 6):
#:
#: * It may not lie above 2**22. The TPU compiler handles a scan or a
#:   scatter over the long axis of a ``[K, N]`` array far worse than K of
#:   them over ``[N]``: at N = 10M on a (1,4) mesh the batched form compiles
#:   in 214 s with 8.4 GB of temporaries a device, the ring-at-a-time form in
#:   9 s with 0.17 GB (compiled for a described v5e:2x2, PR 27).
#: * One at a time also *ran* faster with the walk that gathered by position
#:   (before PR 37), by more the longer the ring. A whole view change with
#:   K = 10, batched against one at a time, a 1 % and a 5 % cut alike (PR 32,
#:   ms): 3.4 / 3.5 at 1,000 slots and 4.5 / 4.4 at 4,000 (a tie inside a
#:   call's spread), 8.9 / 8.5 at 16,000 (ranges touching), 15.2 / 13.0 at
#:   32,000 (apart from here on), 27.9 / 23.1 at 64,000, 45.2 / 37.6 at
#:   102,500, 104.4 / 93.3 at 262,144, 224.5 / 182.5 at 524,288, 466.6 /
#:   344.2 at 1,000,000. In the cells: a 1M commit 874 -> 705 ms (its view
#:   change 508.5 -> 340.3, compiling as long either way, 196 / 198 s), a
#:   100K churn commit 131.1 -> 123.9 ms, the 100K trickle 11.79 -> 12.17
#:   view changes/s, warm set-up the same.
#: * With the walk that carries the slot in its scan word (PR 37) the same
#:   sweep, re-taken beside the old walk in one process (old walk: 4.37 /
#:   4.30, 8.71 / 8.22, 15.12 / 12.97, 45.13 / 37.65), reads 3.74 / 3.71 at
#:   4,000, 5.66 / 6.31 at 16,000 (batched ahead), 8.60 / 8.67 at 32,000 and
#:   24.56 / 24.28 at 102,500 (ties inside a call's spread): the gathers by
#:   position were what the batched form paid most for, and without them the
#:   forms tie up to 102,500. Not re-taken: 262,144 and longer, where the
#:   first reason alone holds the bound down. The bound stays where it is
#:   (PERF.md section 7 has the open question).
#:
#: So the bound is the shortest swept length at which the two forms' timings
#: lay apart when it was placed. Under it all K rings stay in one program,
#: which is what the fleet's 1,000 slots under a tenant ``vmap`` run (there
#: a ``lax.map`` would sit inside the ``vmap``: another question, with
#: another control). A size read off the shape, not an option.
RING_AT_A_TIME_SLOTS = 32_000


def _per_ring(fn, *rows):
    """``fn`` over the leading (ring) axis of ``rows``, under the schedule
    the ring length picks (:data:`RING_AT_A_TIME_SLOTS`)."""
    if rows[0].shape[-1] >= RING_AT_A_TIME_SLOTS:
        return jax.lax.map(lambda ring: fn(*ring), rows)
    return jax.vmap(fn)(*rows)


def ring_topology_from_perm(
    perm: jnp.ndarray, alive: jnp.ndarray, ring_alive=None
) -> RingTopology:
    """``ring_topology`` without the sort: derive all K rings' topology from
    the static key-order permutations (``ring_perms``) and the current alive
    mask with O(N) scans. Output is bit-identical to ``ring_topology``
    (equivalence pinned in tests/test_ops_rings.py).

    Accepts ``perm`` at ANY integer dtype — the compact engine stores its
    ring_perm at the policy's index width (int8/int16,
    models/state.compaction_policy) and gathers/scatters index with it
    directly; the returned tables are int32 (position arithmetic
    accumulates wide here) and the caller narrows on store. Long rings go
    one at a time (:data:`RING_AT_A_TIME_SLOTS`), same values; a ring's walk
    scans one word pair a piece of the slot (:func:`ring_walk_pieces`).

    ``ring_alive`` is :func:`ring_liveness` of the same ``perm`` and
    ``alive`` where the caller holds it (the engine's state does): the walk
    then reads liveness by position from it and gathers nothing by ``perm``."""
    perm, alive = jnp.asarray(perm), jnp.asarray(alive, dtype=bool)
    rows = (perm,) if ring_alive is None else (perm, jnp.asarray(ring_alive, dtype=bool))
    obs, subj, order = _per_ring(
        lambda ring, ao=None: _from_perm_single(ring, alive, ao), *rows
    )
    return RingTopology(obs_idx=obs, subj_idx=subj, order=order)


def ring_liveness(perm: jnp.ndarray, alive: jnp.ndarray) -> jnp.ndarray:
    """Liveness by ring position, ``[K, N]`` bool: ``ring_alive[k, p] ==
    alive[perm[k, p]]``. The walk's one gather (a scalar look-up for every
    position of every ring: 7.6 ms a ring at 1M on the v5e, the dearest
    form there is), made ONCE where the state is made or loaded
    (``EngineState.ring_alive``); a view change then flips its cut's own
    positions (:func:`ring_tables_after_cut`) and comes here only for a
    cut its bucket cannot hold, or in a program whose node axis is
    sharded (a compaction is a global operation over that axis)."""
    perm, alive = jnp.asarray(perm), jnp.asarray(alive, dtype=bool)
    return _per_ring(lambda ring: alive[ring], perm)


def view_change_bucket(n: int) -> int:
    """Slots of a cut that a view change flips in place
    (:func:`ring_tables_after_cut`): an eighth of the ``n`` slots, rounded
    up to whole 128-lane tiles as ``ops/cut_detection.invalidation_bucket``
    is. The largest cut any cell commits is a bootstrap wave's 242 joiners
    of 2,000 slots (12.1 %; 5,000 of 102,500, 10,000 of 1,000,000 and 16 of
    1,000 elsewhere); a cut with more members gathers the lane whole
    (:func:`ring_liveness`). A size read off the shape, not an option."""
    return -(-n // (8 * 128)) * 128


def _flip_positions(ring_alive, at, stays):
    """``ring_alive`` with ``stays[j]``, the bit of slot j of a cut's bucket
    AFTER the cut, written at ``at[k, j]``, its position on every ring k
    (``ring_pos[:, slots]``, the K·B look-ups a view change's two updates
    share): K·B updates. Right for ANY slot, so the bucket
    may repeat a slot and may name slots whose bit did not change: no mask
    of valid entries. ONE ``[K, B]`` scatter at every ring length: unlike
    the walk's N-update scatter, which long rings take one at a time
    (:data:`RING_AT_A_TIME_SLOTS`), B updates a ring into ``[K, N]`` run
    faster together than a ring at a time (14.4 against 21.5 ms at 1M, 1.9
    against 3.1 at 102,500, a tie under the fleets' ``vmap``; ``bool``,
    ``int8`` and ``int32`` lanes within 15 % of each other: PERF.md section
    6, PR 50's probe, TPU v5e)."""
    rings = jnp.arange(ring_alive.shape[0], dtype=jnp.int32)[:, None]
    return ring_alive.at[rings, at].set(stays[None, :])


def _repair_observers(table, lane, ring_perm, at, slots, stays):
    """The observer table of the membership AFTER a cut, from ``table``, the
    one before it, by writing only the entries the cut can have changed:
    2·K·B updates where the rebuild (:func:`ring_topology_from_perm`)
    scatters K·N. ``lane`` is liveness by ring position after the cut,
    ``slots`` (``[B]``) names at least the cut's slots, ``at`` (``[K, B]``)
    their ring positions and ``stays`` whether each is alive after the cut.
    For each of them, at its position d on each ring:

    1. the slot itself reads its new successor ``succ'(d)``, the slot at the
       first position alive after the cut strictly after d, if it is alive
       after the cut, else -1;
    2. the slot at ``pred'(d)``, the last position alive after the cut
       strictly before d, reads the cut slot itself if that is alive after
       the cut and ``succ'(d)`` otherwise (nothing alive lies between
       ``pred'(d)`` and d, so that IS its successor).

    Nothing else changes: a position p alive before and after the cut and
    outside it has ``next(p) != next'(p)`` only if the nearer of the two is
    a cut position d, and p is then the last alive-after position before d,
    ``pred'(d)``. Both values are right for ANY d, changed or not, so
    ``slots`` may repeat, may name slots the cut left alone (the
    compaction's filler entries) and two entries that meet on one slot write
    the same value; the one exception is a slot that is a joiner still
    pending after the cut, whose column holds its gatekeepers and not a ring
    neighbour: write 1 puts -1 there, and the caller's select on
    ``still_pending`` puts the old column back
    (``models/virtual_cluster.apply_view_change_impl``). Needs two or more
    alive before and after the cut (else every entry changes to or from -1:
    the rebuild's case, :func:`ring_tables_after_cut`).

    ``succ'`` and the slot at ``pred'`` by position are the walk's own scans
    read before its mask (:func:`_neighbour_slots`). A ring's scans, its
    look-ups by position and its ONE ``[2B]`` scatter run under the schedule
    the ring length picks (:func:`_per_ring`), UNLIKE the lane's update: B
    look-ups a ring by a ring's own positions run faster a ring at a time
    than as one ``[K, B]`` look-up into ``[K, N]`` (the whole repair 48.6
    against 81.5 ms at 1M, where the walk it replaces is 70.4; 5.05 against
    5.89 at 102,500; a tie under the fleets' ``vmap``: PERF.md section 6,
    PR 52's probe, TPU v5e). Where a slot takes one piece of the walk's word
    (:func:`ring_walk_pieces`: up to 65,536 slots, so at most 16 bits) both
    neighbours ride ONE word and one look-up fetches both: a look-up by a
    computed position is the dearest operation here (13 ns an index under
    the fleets' ``vmap``), a shift is free. With two look-ups the repair
    ties with the walk there and gains nothing in a cell; with one it reads
    8.2 against 12.3 ms at ``[256, 10, 1000]`` (the walk 13.0), 7.5 against
    10.9 at ``[128, 10, 2000]``, 2.24 against 2.70 at ``[10, 50000]``, and a
    bootstrap 621.5 against 662.6 ms (the parent 655.7; same section, the
    second probe and its cells)."""
    _, pieces = ring_walk_pieces(ring_perm.shape[-1])

    def ring(row, ao, perm, at):
        succ, pred = _neighbour_slots(perm, ao)
        if pieces == 1:
            both = ((succ << 16) | pred)[at]
            succ_of, pred_of = (both >> 16).astype(jnp.int32), (both & 0xFFFF).astype(jnp.int32)
        else:
            succ_of, pred_of = succ[at].astype(jnp.int32), pred[at].astype(jnp.int32)
        return row.at[jnp.concatenate([slots, pred_of])].set(
            jnp.concatenate(
                [jnp.where(stays, succ_of, -1), jnp.where(stays, slots, succ_of)]
            ).astype(row.dtype)
        )

    return _per_ring(ring, table, lane, ring_perm, at)


def ring_tables_after_cut(
    table: jnp.ndarray, ring_alive: jnp.ndarray, ring_perm: jnp.ndarray,
    ring_pos: jnp.ndarray, alive: jnp.ndarray, cut: jnp.ndarray,
    batch_axis=None, commits=None,
):
    """A view change's two ring tables, ``(lane, table, took_dense)``:
    :func:`ring_liveness` of ``ring_perm`` and ``alive``, the membership
    AFTER a cut, and the observer table ``ring_topology_from_perm(ring_perm,
    alive).obs_idx`` (at ``table``'s dtype), from ``ring_alive`` and
    ``table``, those of the membership before it: the work is the cut's and
    not the ring's. ``cut`` (``[N]`` bool) names at least every slot whose
    bit differs; its set slots are compacted into one bucket read off the
    slot count (:func:`view_change_bucket`; the form of
    ``ops/cut_detection.first_set_slots``, whose filler entries are some
    slot), flipped where they sit on each ring (:func:`_flip_positions`) and
    the table repaired there and at their predecessors
    (:func:`_repair_observers`, which says what a caller with pending
    joiners still has to select). ``table`` must equal the walk's at every
    slot the caller keeps from the result (``EngineState.inval_obs`` does).

    A cut with more members than the bucket holds, and a ring with fewer
    than two alive before or after it, takes the whole gather and the whole
    walk (N-update scatter a ring): the overflow arm and the oracle, so the
    result is exact for any cut. ``took_dense`` (a bool) says this cut took
    it.

    ``batch_axis`` names the batch axis of an enclosing ``vmap`` (the
    meshless fleet programs hand one): the conditional is then taken on
    "some member's cut overflows" (``utils/dispatch.cond_across``) and stays
    a conditional, where an unnamed ``vmap`` would make it a select that
    runs the rebuild for everybody, always. ``commits`` (this member's
    ``[]`` bool, or ``None``) is whether the caller keeps this member's
    result at all: a cut it drops never opens the arm for the batch."""
    ring_pos = jnp.asarray(ring_pos)
    alive, cut = jnp.asarray(alive, dtype=bool), jnp.asarray(cut, dtype=bool)
    lane = jnp.asarray(ring_alive, dtype=bool)
    # Made outside the conditional: what an arm captures becomes its operand
    # and stays in HBM (PERF.md section 6, PR 43); the K·N arrays are there
    # anyway.
    bucket = view_change_bucket(cut.shape[0])
    slots = first_set_slots(cut, bucket)
    dense = (
        (jnp.sum(cut, dtype=jnp.int32) > bucket)
        | (jnp.sum(lane[0], dtype=jnp.int32) < 2)  # alive before the cut
        | (jnp.sum(alive, dtype=jnp.int32) < 2)
    )
    if commits is not None:
        dense = dense & commits

    def rebuild(lane, table, slots):
        lane = ring_liveness(ring_perm, alive)
        return lane, ring_topology_from_perm(ring_perm, alive, lane).obs_idx.astype(table.dtype)

    def repair(lane, table, slots):
        # stored at int8 / int16 by the compact engine
        at, stays = ring_pos[:, slots].astype(jnp.int32), alive[slots]
        lane = _flip_positions(lane, at, stays)
        return lane, _repair_observers(table, lane, ring_perm, at, slots, stays)

    (lane, table), _ = cond_across(
        batch_axis, dense, rebuild, repair, lane, jnp.asarray(table), slots
    )
    return lane, table, dense


@jax.jit
def ring_positions(perm: jnp.ndarray) -> jnp.ndarray:
    """The inverse of :func:`ring_perms`, at ``perm``'s dtype: ``pos[k, s]``
    is the position of slot ``s`` in ring k's fixed key order, so ``pos[k,
    perm[k, p]] == p``. Static like the keys, so made ONCE, beside the perms
    (``EngineState.ring_pos``): a slot's position is its whole 64-bit key's
    order in one word, which is what lets a joiner's gatekeeper be one
    single-word maximum (:func:`predecessor_of_keys`). One scatter a ring;
    long rings one at a time, as the rebuild takes them
    (:data:`RING_AT_A_TIME_SLOTS`)."""
    perm = jnp.asarray(perm)
    where = jnp.arange(perm.shape[-1], dtype=perm.dtype)
    return _per_ring(lambda ring: jnp.zeros_like(ring).at[ring].set(where), perm)


@jax.jit
@scope("join_predecessors")
def predecessor_of_keys(
    ring_pos: jnp.ndarray,
    ring_perm: jnp.ndarray,
    alive: jnp.ndarray,
    slots: jnp.ndarray,
) -> jnp.ndarray:
    """Expected observers of joiners: for each joiner slot and ring, the
    alive slot that precedes the joiner's key on that ring — the semantics
    of ``getExpectedObserversOf`` (MembershipView.java:292-322).

    ring_pos/ring_perm: [K, N] (:func:`ring_positions`, :func:`ring_perms`);
    alive: [N]; slots: [J], slots of the same N (a joiner holds its slot, and
    so its keys and ring positions, before it is admitted). Returns [K, J]
    int32 slot indices, -1 when no node is alive. The gatekeeper is the alive
    slot at the greatest ring position below the joiner's own, and with none
    below, the alive slot at the greatest position of all (the ring wraps):
    ONE masked single-word maximum over the slot axis a query — O(N·J) fused
    elementwise work, a ``max`` across the shards of a sharded N — then J
    look-ups of ``ring_perm``. No order of the alive slots is built, sorted
    or gathered: this sits inside a bootstrap wave's timed path, where J is
    a handful and N·K is not. The position orders slots by (64-bit key,
    slot), the order the view change's walk gives the joiner once admitted;
    for distinct keys that is ``order[rank - 1]`` of the alive-first key
    order (tests/test_ops_rings.py keeps that spelling as the reference).
    """
    ring_pos, alive = jnp.asarray(ring_pos), jnp.asarray(alive, dtype=bool)
    none = jnp.asarray(-1, ring_pos.dtype)
    own = ring_pos[:, jnp.asarray(slots)]  # [K, J]
    below = jnp.max(
        jnp.where(alive & (ring_pos[:, None, :] < own[:, :, None]), ring_pos[:, None, :], none),
        axis=-1,
    )
    last = jnp.max(jnp.where(alive, ring_pos, none), axis=-1)  # [K]
    at = jnp.where(below >= 0, below, last[:, None]).astype(jnp.int32)
    pred = jnp.take_along_axis(jnp.asarray(ring_perm), jnp.maximum(at, 0), axis=1)
    return jnp.where(at >= 0, pred.astype(jnp.int32), -1)
