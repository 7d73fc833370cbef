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

from rapid_tpu.ops.hashing import lex_argsort
from rapid_tpu.protocol.view import ring_key
from rapid_tpu.utils.dispatch import scope


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


def _from_perm_single(perm, alive):
    """One ring, sort-free: (obs_idx[N], subj_idx[N], order[N]) from the
    static key order. Successor among alive = next alive position in the
    fixed circular order (suffix-min scan); predecessor = previous
    (prefix-max scan); the alive-first ``order`` is a stable partition
    (rank scans + one scatter). Bit-identical to ``_ring_topology_single``:
    restricting a fixed total order to the alive subset IS the alive
    order, and lex_argsort is stable so dead slots tie-break identically.
    """
    n = perm.shape[0]
    ao = alive[perm]  # alive bit per ring position
    pos = jnp.arange(n, dtype=jnp.int32)
    n_alive = jnp.sum(ao.astype(jnp.int32))

    idx_succ = jnp.where(ao, pos, n)  # sentinel past the end
    suffix_min = jax.lax.cummin(idx_succ, reverse=True)
    first_alive = suffix_min[0]
    nxt = jnp.concatenate([suffix_min[1:], jnp.full((1,), n, dtype=jnp.int32)])
    succ_pos = jnp.where(nxt >= n, first_alive, nxt)  # wrap to ring start

    idx_pred = jnp.where(ao, pos, -1)
    prefix_max = jax.lax.cummax(idx_pred)
    last_alive = prefix_max[-1]
    prv = jnp.concatenate([jnp.full((1,), -1, dtype=jnp.int32), prefix_max[:-1]])
    pred_pos = jnp.where(prv < 0, last_alive, prv)  # wrap to ring end

    valid = ao & (n_alive >= 2)
    succ_slot = jnp.where(valid, perm[jnp.clip(succ_pos, 0, n - 1)], -1)
    pred_slot = jnp.where(valid, perm[jnp.clip(pred_pos, 0, n - 1)], -1)
    # full(-1), not zeros: if perm were ever not a permutation (corrupted
    # state), unwritten entries must read as the documented "no observer"
    # sentinel, never as valid slot 0.
    obs_idx = jnp.full((n,), -1, dtype=jnp.int32).at[perm].set(succ_slot)
    subj_idx = jnp.full((n,), -1, dtype=jnp.int32).at[perm].set(pred_slot)
    return obs_idx, subj_idx, _alive_first_order(perm, alive)


def _alive_first_order(perm, alive):
    """``lex_argsort((dead, keys...))`` without the sort: stable partition
    of the static key order into alive-first via rank scans + one scatter."""
    n = perm.shape[0]
    ao = alive[perm]
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
#: shows. Two measurements place the bound (TPU v5e; PERF.md section 6):
#:
#: * It may not lie above 2**22. The TPU compiler handles a scan or a
#:   scatter over the long axis of a ``[K, N]`` array far worse than K of
#:   them over ``[N]``: at N = 10M on a (1,4) mesh the batched form compiles
#:   in 214 s with 8.4 GB of temporaries a device, the ring-at-a-time form in
#:   9 s with 0.17 GB (compiled for a described v5e:2x2, PR 27).
#: * One at a time also *runs* faster, by more the longer the ring. A whole
#:   view change with K = 10, batched against one at a time, a 1 % and a 5 %
#:   cut alike (PR 32, ms): 3.4 / 3.5 at 1,000 slots and 4.5 / 4.4 at 4,000
#:   (a tie inside a call's spread), 8.9 / 8.5 at 16,000 (ranges touching),
#:   15.2 / 13.0 at 32,000 (apart from here on), 27.9 / 23.1 at 64,000,
#:   45.2 / 37.6 at 102,500, 104.4 / 93.3 at 262,144, 224.5 / 182.5 at
#:   524,288, 466.6 / 344.2 at 1,000,000. In the cells: a 1M commit 874 ->
#:   705 ms (its view change 508.5 -> 340.3, compiling as long either way,
#:   196 / 198 s), a 100K churn commit 131.1 -> 123.9 ms, the 100K trickle
#:   11.79 -> 12.17 view changes/s, warm set-up the same.
#:
#: So the bound is the shortest swept length at which the two forms' timings
#: lie apart. Under it the forms tie and all K rings stay in one program,
#: which is what the fleet's 1,000 slots under a tenant ``vmap`` run (there
#: a ``lax.map`` would sit inside the ``vmap``: another question, with
#: another control). A size read off the shape, not an option.
RING_AT_A_TIME_SLOTS = 32_000


def ring_topology_from_perm(perm: jnp.ndarray, alive: jnp.ndarray) -> RingTopology:
    """``ring_topology`` without the sort: derive all K rings' topology from
    the static key-order permutations (``ring_perms``) and the current alive
    mask with O(N) scans. Output is bit-identical to ``ring_topology``
    (equivalence pinned in tests/test_ops_rings.py).

    Accepts ``perm`` at ANY integer dtype — the compact engine stores its
    ring_perm at the policy's index width (int8/int16,
    models/state.compaction_policy) and gathers/scatters index with it
    directly; the returned tables are int32 (position arithmetic
    accumulates wide here) and the caller narrows on store. Long rings go
    one at a time (:data:`RING_AT_A_TIME_SLOTS`), same values."""
    perm, alive = jnp.asarray(perm), jnp.asarray(alive, dtype=bool)
    if perm.shape[-1] >= RING_AT_A_TIME_SLOTS:
        obs, subj, order = jax.lax.map(
            lambda ring: _from_perm_single(ring, alive), perm
        )
    else:
        obs, subj, order = jax.vmap(_from_perm_single, in_axes=(0, None))(
            perm, alive
        )
    return RingTopology(obs_idx=obs, subj_idx=subj, order=order)


@jax.jit
@scope("join_predecessors")
def predecessor_of_keys(
    key_hi: jnp.ndarray,
    key_lo: jnp.ndarray,
    alive: jnp.ndarray,
    query_hi: jnp.ndarray,
    query_lo: jnp.ndarray,
    perm: "jnp.ndarray | None" = None,
) -> jnp.ndarray:
    """Expected observers of joiners: for each query key (one per ring per
    joiner), the alive slot that precedes it on that ring — the semantics of
    ``getExpectedObserversOf`` (MembershipView.java:292-322).

    key_hi/key_lo: [K, N]; query_hi/query_lo: [K, J]. Returns [K, J] slot
    indices (-1 when no node is alive). Rank is computed by a masked
    comparison sum — O(N·J) elementwise work that maps cleanly onto sharded N.
    With ``perm`` (the static key-order permutations, ``ring_perms``) the
    alive-first order comes from O(N) partition scans instead of a K-ring
    argsort — this sits inside a bootstrap wave's timed path, where the
    engine passes its ``state.ring_perm``. Results are identical either way.
    """

    n_alive = jnp.sum(alive.astype(jnp.int32))

    if perm is None:
        dead = (~alive).astype(jnp.uint32)
        orders = jax.vmap(lambda h, low: lex_argsort((dead, h, low)))(
            key_hi, key_lo
        )
    else:
        orders = jax.vmap(_alive_first_order, in_axes=(0, None))(perm, alive)

    def one_ring(khi, klo, qhi, qlo, order):
        def one_query(h, low):
            less = (khi < h) | ((khi == h) & (klo < low))
            rank = jnp.sum((less & alive).astype(jnp.int32))
            # Predecessor = alive node at sorted position (rank - 1) mod n_alive.
            pred_pos = jnp.where(rank - 1 < 0, n_alive - 1, rank - 1)
            return jnp.where(n_alive >= 1, order[pred_pos], -1).astype(jnp.int32)

        return jax.vmap(one_query)(qhi, qlo)

    return jax.vmap(one_ring)(key_hi, key_lo, query_hi, query_lo, orders)
