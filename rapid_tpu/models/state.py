"""Engine state: N virtual membership endpoints as struct-of-arrays.

This is the TPU-native replacement for the reference's object-per-node
architecture: one ``EngineState`` pytree holds every virtual node's protocol
state in padded device arrays (static shapes; membership changes flip bits in
``alive``), so a whole cluster's protocol round is a single fused XLA program.

Cohorts: receivers with identical delivery experience share cut-detector
state. In a reliably-delivered co-located deployment all healthy nodes see
the same alert stream, so their detectors are bit-identical — cohort 0.
Divergence comes from two injectable sources: per-cohort rx-block masks
(asymmetric/one-way links) and per-(cohort, edge) delivery delay jitter
(``EngineConfig.delivery_spread`` — broadcast arrival skew, the paper's
Fig. 11 divergence regime). Delivery masks pack bitwise over cohorts
(uint32 words), so C scales to hundreds of independently-diverging receiver
states at N=100K+ (the reference's N independent ``MultiNodeCutDetector``
instances, ``MultiNodeCutDetector.java:31-37``, sampled at C of them).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rapid_tpu.ops.hashing import masked_set_hash
from rapid_tpu.ops.rings import (
    ring_liveness,
    ring_perms,
    ring_positions,
    ring_topology_from_perm,
)

# Sentinel for "this edge's alert has not fired": far enough in the future
# that (round_idx - FIRE_NEVER) stays hugely negative in int32. The compact
# int16 storage uses FIRE_NEVER_NARROW instead; the invariant (an unfired
# edge's age stays negative for every in-envelope round index, under the
# NARROWEST round dtype the policy can pick) is pinned by
# tests/test_state_compaction.py::test_fire_never_sentinel_invariant —
# a test, not just this comment.
FIRE_NEVER = 1 << 30
#: The int16-storage sentinel: fire rounds are real (< ROUND_ENVELOPE)
#: or this. Kept a power of two with headroom so (round_idx - sentinel)
#: is not merely negative but ~-2^14 at the envelope edge.
FIRE_NEVER_NARROW = 1 << 14
#: Operating envelope of the compact round counter: a single configuration
#: may run at most this many rounds before fire_round narrowing (int16,
#: FIRE_NEVER_NARROW sentinel) loses the unfired/fired distinction. Every
#: view change resets round_idx to 0; tier-1 dispatch budgets are <= 255
#: rounds, so the envelope holds ~64 maximal dispatches per configuration.
ROUND_ENVELOPE = FIRE_NEVER_NARROW - 1


class EngineConfig(NamedTuple):
    """Static (compile-time) engine parameters."""

    n: int  # padded virtual-node slots
    k: int  # rings
    h: int  # high watermark
    l: int  # low watermark
    c: int = 2  # receiver cohorts
    fd_threshold: int = 3  # consecutive failed probe windows before alerting
    # Run the engine's Pallas TPU kernel (rapid_tpu.ops.pallas_kernels) —
    # the fused alert-delivery kernel, measured 2.25x over XLA's fusion. Off
    # for sharded/CPU runs.
    use_pallas: bool = False
    # Rounds an announced proposal may sit undecided before the classic-Paxos
    # fallback fires (models FastPaxos.java:106-107's jittered recovery; the
    # coordinator rule then forces the plurality value, Paxos.java:271-328).
    fallback_rounds: int = 8
    # Max extra rounds of per-(cohort, edge) alert delivery delay, drawn
    # deterministically from a hash of (cohort, edge, configuration). 0 =
    # same-round delivery for every cohort (no timing divergence). This is
    # the engine's model of broadcast arrival skew — the reason real
    # receivers' cut detectors diverge (paper Fig. 11).
    delivery_spread: int = 0
    # Coordinators racing per classic-fallback attempt. The reference lets
    # any number of nodes start recovery concurrently, ordered by rank
    # (Paxos.java:93-97, 333-339); modeling R > 1 exercises that contention:
    # acceptors promise to every heard rank in order, so a lower-ranked
    # coordinator can win phase 1 yet have its phase 2a rejected wherever a
    # higher rank's phase 1a also arrived.
    concurrent_coordinators: int = 1
    # Failure-detection policy (NEW FIELDS APPEND HERE: EngineConfig loads
    # positionally from checkpoints). 0 = the reference code's
    # cumulative-failure counter (fd_count >= fd_threshold). W in [1, 32] =
    # the PAPER's windowed policy: an edge fires when >= fd_threshold of its
    # last W probe windows failed — kept per edge as a uint32 bit-history
    # (shift + popcount per round; rapid_tpu/monitoring/windowed.py is the
    # host twin). Intermittent blips age out instead of accumulating forever.
    fd_window: int = 0
    # Sub-round delivery-skew granularity. Values 0..999: probability (in
    # permille, per (cohort, edge)) that a delivery draws a NONZERO delay,
    # uniform in [1, delivery_spread] — P(delayed) is exactly permille/1000,
    # interpolating between "no timing divergence" (0) and "every delivery
    # skewed" (→1000). The default 1000 is a distinct LEGACY mode, not the
    # continuum endpoint: the original uniform draw over [0,
    # delivery_spread], whose delayed fraction is spread/(spread+1) (e.g.
    # 0.5 at spread=1 ≙ permille 500 on the dial). The paper's
    # continuous-latency simulation (Fig. 11) sits below one full round of
    # skew; see EVALUATION.md §2 for the calibration.
    delivery_prob_permille: int = 1000
    # (A pallas_watermark field once sat here: a Mosaic watermark kernel
    # measured SLOWER than XLA's own fusion — 2.52 ms vs 3.67 ms at [8, 1M],
    # evidence/round2/microbench_slope.json — and was deleted. Checkpoint
    # loads drop the stale value; see utils/checkpoint.py.)
    # Lane-tile width for the Pallas delivery kernel (multiple of 128).
    # Wider tiles amortize per-grid-step overhead at large N; outputs are
    # bit-identical across widths. Tune per shape with
    # examples/delivery_autotune.py on hardware.
    pallas_lanes: int = 128
    # State-compaction level (an int, not a string: EngineConfig persists
    # as an int64 vector in checkpoints). 0 = the historical wide
    # int32/uint32 layout (the differential oracle); 1 = config-derived
    # dtype narrowing per :func:`compaction_policy` — every lane stored at
    # the minimal legal dtype for this config's K/C/N/fd_window, arithmetic
    # accumulated at >= int32 and bit-identical to wide within the
    # documented envelopes (ROUND_ENVELOPE rounds and < 2^15 - 1 classic
    # attempts / fd events per configuration).
    compact: int = 0
    # Device-resident telemetry plane (an int knob, like ``compact``): 0 =
    # off — the round bodies trace NO telemetry code and compile
    # byte-identical programs (nothing of the plane is traced); 1 = a
    # :class:`TelemetryLanes` pytree rides beside the state through the
    # jitted round bodies, accumulating per-round activity/tally/conflict
    # counters on-device. Telemetry never changes engine results: the lanes
    # are write-only inside a round (nothing reads them back into protocol
    # state), pinned bit-identical on-vs-off by tests/test_telemetry_plane.py.
    telemetry: int = 0
    # Device round-trace ring capacity R (an int knob holding the SIZE, not
    # a boolean): 0 = off — the round bodies trace NO ring code and compile
    # byte-identical programs (nothing of the ring is traced, like
    # ``telemetry``); R > 0 = a :class:`TraceRing` of the last R per-round
    # records rides beside the state through the jitted round bodies. The
    # ring is a REFINEMENT of the telemetry plane (its active-subject count
    # reuses the telemetry block's cut-mask reduction), so trace > 0
    # requires telemetry == 1 — drivers enforce this at construction. Like
    # every EngineConfig field this appends at the END: checkpoints persist
    # the config positionally as an int64 vector.
    trace: int = 0


class CompactionPolicy(NamedTuple):
    """Per-lane storage dtypes, a pure function of :class:`EngineConfig`
    (:func:`compaction_policy`). Dtype fields are numpy dtype NAMES (strings
    keep the policy hashable and trivially serializable); ``fire_never`` is
    the "edge never fired" sentinel legal at the ``round`` dtype.

    Lane kinds:

    - ``idx``     — ring/topology index tables and cp rank indices, values
                    in [-1, n-1] plus the count n itself (jax index
                    normalization): int8 below 128 slots, int16 below
                    32768.
    - ``cohort``  — receiver-cohort indices, values in [-1, c-1] plus c:
                    int8 below 128 cohorts (c is capped at 1024 -> never
                    wider than int16).
    - ``counter`` — fd_count / classic-Paxos rank rounds / classic_epoch /
                    rounds_undecided: int16 (envelope: < 2^15 - 1 events
                    per configuration; every view change resets them).
    - ``hist``    — fd_hist bit-history: the minimal unsigned dtype holding
                    ``fd_window`` bits (uint8 for the counter mode's unused
                    lane and windows <= 8).
    - ``report``  — report_bits ring bitmasks: the minimal unsigned dtype
                    holding K bits. Held at uint32 under ``use_pallas``
                    (the Mosaic delivery kernel emits uint32 words).
    - ``round``   — fire_round: int16 with the FIRE_NEVER_NARROW sentinel
                    (envelope: ROUND_ENVELOPE rounds per configuration).
    """

    idx: str
    cohort: str
    counter: str
    hist: str
    report: str
    round: str
    fire_never: int


#: The historical layout — and the differential oracle the compact path is
#: pinned bit-identical against.
WIDE_POLICY = CompactionPolicy(
    idx="int32", cohort="int32", counter="int32", hist="uint32",
    report="uint32", round="int32", fire_never=FIRE_NEVER,
)

#: EngineState/FaultInputs lanes the derived policy may store below 32 bits
#: — the ``dtype-widening`` lint (tools/analysis/sharding.py) watches
#: arithmetic on exactly these names; the two sets are pinned equal by
#: tests/test_state_compaction.py.
NARROWABLE_LANES = frozenset({
    "ring_perm", "ring_pos", "obs_idx", "inval_obs", "cohort_of",
    "fd_count", "fd_hist", "fire_round", "report_bits",
    "cp_rnd_r", "cp_rnd_i", "cp_vrnd_r", "cp_vrnd_i", "cp_vval_src",
    "classic_epoch", "rounds_undecided",
})


def min_index_dtype(n: int) -> str:
    """Smallest signed dtype holding indices in [-1, n-1] AND the count
    ``n`` itself: jax's advanced indexing materializes the axis size in
    the index dtype when normalizing negative indices, so a dtype whose
    max is exactly ``n - 1`` overflows at trace time (n=128 under int8
    was the scaling-ladder-found boundary bug)."""
    if n < 1 << 7:
        return "int8"
    if n < 1 << 15:
        return "int16"
    return "int32"


def _min_bits_dtype(bits: int) -> str:
    """Smallest unsigned dtype holding a ``bits``-wide bitmask."""
    if bits <= 8:
        return "uint8"
    if bits <= 16:
        return "uint16"
    return "uint32"


def compaction_policy(cfg: "EngineConfig") -> CompactionPolicy:
    """THE config->dtype derivation (pure; the compiled program's layout is
    a function of the static config, so a policy change is a recompile,
    never a silent reinterpretation). ``cfg.compact == 0`` returns the wide
    oracle layout unchanged."""
    if not cfg.compact:
        return WIDE_POLICY
    return CompactionPolicy(
        idx=min_index_dtype(cfg.n),
        cohort=min_index_dtype(cfg.c),
        counter="int16",
        # fd_window == 0 (counter mode) leaves fd_hist unused — store the
        # all-zeros lane at the minimal width rather than special-casing.
        hist=_min_bits_dtype(max(cfg.fd_window, 1)),
        report="uint32" if cfg.use_pallas else _min_bits_dtype(cfg.k),
        round="int16",
        fire_never=FIRE_NEVER_NARROW,
    )


#: field -> (shape symbols over (n, k, c), policy-kind). One table for BOTH
#: pytrees (the namespaces share no field name); the policy kinds "uint32"
#: / "int32" / "bool" are fixed-width (hash lanes, scalars the drivers
#: fetch, membership masks).
LANE_SPECS: Dict[str, Tuple[Tuple[str, ...], str]] = {
    # EngineState
    "key_hi": (("k", "n"), "uint32"),
    "key_lo": (("k", "n"), "uint32"),
    "ring_perm": (("k", "n"), "idx"),
    "ring_pos": (("k", "n"), "idx"),
    "ring_alive": (("k", "n"), "bool"),
    "id_hi": (("n",), "uint32"),
    "id_lo": (("n",), "uint32"),
    "alive": (("n",), "bool"),
    "obs_idx": (("k", "n"), "idx"),
    "inval_obs": (("k", "n"), "idx"),
    "config_epoch": ((), "int32"),
    "config_hi": ((), "uint32"),
    "config_lo": ((), "uint32"),
    "n_members": ((), "int32"),
    "fd_count": (("n", "k"), "counter"),
    "fd_hist": (("n", "k"), "hist"),
    "fd_fired": (("n", "k"), "bool"),
    "fire_round": (("n", "k"), "round"),
    "join_pending": (("n",), "bool"),
    "cohort_of": (("n",), "cohort"),
    "report_bits": (("c", "n"), "report"),
    "seen_down": (("c",), "bool"),
    "released": (("c", "n"), "bool"),
    "announced": (("c",), "bool"),
    "prop_mask": (("c", "n"), "bool"),
    "prop_hi": (("c",), "uint32"),
    "prop_lo": (("c",), "uint32"),
    "vote_hi": (("n",), "uint32"),
    "vote_lo": (("n",), "uint32"),
    "vote_valid": (("n",), "bool"),
    "rounds_undecided": ((), "counter"),
    "cp_rnd_r": (("n",), "counter"),
    "cp_rnd_i": (("n",), "idx"),
    "cp_vrnd_r": (("n",), "counter"),
    "cp_vrnd_i": (("n",), "idx"),
    "cp_vval_src": (("n",), "cohort"),
    "classic_epoch": ((), "counter"),
    "round_idx": ((), "int32"),
    "retired": (("n",), "bool"),
    # FaultInputs
    "crashed": (("n",), "bool"),
    "probe_fail": (("n", "k"), "bool"),
    "rx_block": (("c", "n"), "bool"),
}


def lane_dtypes(cfg: "EngineConfig") -> Dict[str, str]:
    """field -> numpy dtype name under this config's policy, for every
    EngineState/FaultInputs lane."""
    pol = compaction_policy(cfg)
    kinds = {
        "idx": pol.idx, "cohort": pol.cohort, "counter": pol.counter,
        "hist": pol.hist, "report": pol.report, "round": pol.round,
        "uint32": "uint32", "int32": "int32", "bool": "bool",
    }
    return {field: kinds[kind] for field, (_shape, kind) in LANE_SPECS.items()}


class EngineState(NamedTuple):
    """Device state for one virtual cluster (all arrays padded to n slots).

    Dtype comments below are the WIDE (``compact=0``) layout; under
    ``compact=1`` every lane named in :data:`NARROWABLE_LANES` is stored at
    :func:`compaction_policy`'s minimal dtype instead (same shapes, same
    values, bit-identical protocol behavior within the documented
    envelopes)."""

    # Identity & topology (key lanes static per slot; topology re-derived on
    # view change).
    key_hi: jnp.ndarray  # [k, n] uint32
    key_lo: jnp.ndarray  # [k, n] uint32
    ring_perm: jnp.ndarray  # [k, n] int32 — static key-order permutation per ring
    ring_pos: jnp.ndarray  # [k, n] int32 — its inverse: a slot's position on each ring
    # Liveness by ring position: ring_alive[k, p] == alive[ring_perm[k, p]],
    # always. ``alive`` stays the source of truth; this is what the view
    # change's walk reads, kept exact by flipping a cut's own positions.
    ring_alive: jnp.ndarray  # [k, n] bool
    id_hi: jnp.ndarray  # [n] uint32 — node-identity lanes for set hashes
    id_lo: jnp.ndarray  # [n] uint32
    alive: jnp.ndarray  # [n] bool — current membership
    obs_idx: jnp.ndarray  # [k, n] int32 — ring successor (observer) per slot
    # The invalidation-observer table, and the TRUE ring topology of the
    # membership: inval_obs == ring_topology_from_perm(ring_perm, alive).obs_idx
    # at every slot that is not a pending joiner, always (a pending joiner's
    # column holds its gatekeepers). Writers: ``initial_state`` (the walk), a
    # join placement (gatekeepers, at pending columns only, into both lanes)
    # and the view change, which REPAIRS this lane at its cut's slots and
    # their ring predecessors and derives ``obs_idx`` from the same repaired
    # table (``ops/rings.ring_tables_after_cut``). A leave rewrites ``obs_idx``
    # alone; nothing else may write either lane, or the repair starts from a
    # table that is not the ring's. A load makes the lane whole off the
    # pending columns (``with_observer_table_rebuilt``), as it makes
    # ``ring_alive``: an archive is not trusted for it.
    inval_obs: jnp.ndarray  # [k, n] int32
    config_epoch: jnp.ndarray  # int32 — counts view changes
    config_hi: jnp.ndarray  # uint32 — commutative config-id lanes
    config_lo: jnp.ndarray  # uint32
    n_members: jnp.ndarray  # int32 — membership size of this configuration

    # Failure-detector state per monitoring edge (subject, ring).
    fd_count: jnp.ndarray  # [n, k] int32 cumulative failed windows
    fd_hist: jnp.ndarray  # [n, k] uint32 bit-history of outcomes (windowed mode)
    fd_fired: jnp.ndarray  # [n, k] bool alert already emitted
    fire_round: jnp.ndarray  # [n, k] int32 round the alert fired (FIRE_NEVER if not)

    # Joiner bookkeeping.
    join_pending: jnp.ndarray  # [n] bool — slots waiting to be admitted

    # Cut-detector state per cohort: reports are uint32 ring bitmasks per
    # subject (bit k = ring k reported; OR is the dedup).
    cohort_of: jnp.ndarray  # [n] int32 — receiver cohort of each node
    report_bits: jnp.ndarray  # [c, n] uint32
    seen_down: jnp.ndarray  # [c] bool
    released: jnp.ndarray  # [c, n] bool
    announced: jnp.ndarray  # [c] bool — cohort already proposed this config
    prop_mask: jnp.ndarray  # [c, n] bool — cohort's announced proposal
    prop_hi: jnp.ndarray  # [c] uint32
    prop_lo: jnp.ndarray  # [c] uint32

    # Fast-round votes.
    vote_hi: jnp.ndarray  # [n] uint32
    vote_lo: jnp.ndarray  # [n] uint32
    vote_valid: jnp.ndarray  # [n] bool

    # Rounds spent with an announced-but-undecided proposal (fallback timer).
    rounds_undecided: jnp.ndarray  # int32

    # Classic-Paxos acceptor state, message-level (Paxos.java:64-74): the
    # promised rank rnd and accepted (vrnd, vval) per node. Ranks are
    # (round, node-index) pairs; values are cohort indices into prop_mask
    # (every value in play is some cohort's announced cut); -1 = none.
    cp_rnd_r: jnp.ndarray  # [n] int32
    cp_rnd_i: jnp.ndarray  # [n] int32
    cp_vrnd_r: jnp.ndarray  # [n] int32
    cp_vrnd_i: jnp.ndarray  # [n] int32
    cp_vval_src: jnp.ndarray  # [n] int32 — cohort index of accepted value
    classic_epoch: jnp.ndarray  # int32 — classic attempts this configuration

    # Rounds elapsed in this configuration (drives delivery-delay maturity).
    round_idx: jnp.ndarray  # int32

    # Slots removed by some past view change: their identity lanes are spent
    # (the engine's UUIDAlreadySeenError — re-admitting one would replay an
    # old configuration id). Rejoiners must use fresh slots.
    retired: jnp.ndarray  # [n] bool


def initial_state(cfg: EngineConfig, key_hi, key_lo, id_hi, id_lo, alive) -> EngineState:
    """Build a configuration-consistent state from identity arrays."""
    if not 1 <= cfg.k <= 32:
        raise ValueError(
            f"K must be in [1, 32]: ring reports are uint32 bitmasks (got K={cfg.k})"
        )
    if cfg.c > 1024:
        raise ValueError(
            f"at most 1024 receiver cohorts (per-cohort state is [c, n]; "
            f"sample divergence, don't materialize every receiver), got {cfg.c}"
        )
    if cfg.delivery_spread < 0:
        raise ValueError(f"delivery_spread must be >= 0, got {cfg.delivery_spread}")
    if not 0 <= cfg.fd_window <= 32:
        raise ValueError(
            f"fd_window must be 0 (counter mode) or 1..32 (uint32 bit-history), "
            f"got {cfg.fd_window}"
        )
    if cfg.fd_window and cfg.fd_threshold > cfg.fd_window:
        raise ValueError(
            f"fd_threshold ({cfg.fd_threshold}) cannot exceed fd_window "
            f"({cfg.fd_window}): the edge could never fire"
        )
    alive = jnp.asarray(alive, dtype=bool)
    pol = compaction_policy(cfg)
    idt, cdt = jnp.dtype(pol.idx), jnp.dtype(pol.cohort)
    ndt, rdt = jnp.dtype(pol.counter), jnp.dtype(pol.round)
    # The one sort: ring keys are static per slot, so every topology after
    # this (including every view change) is O(N) scans over these perms.
    perm = ring_perms(jnp.asarray(key_hi), jnp.asarray(key_lo)).astype(idt)
    # The walk's one gather, made here for the state to keep.
    ring_alive = ring_liveness(perm, alive)
    topo = ring_topology_from_perm(perm, alive, ring_alive)
    config_hi, config_lo = masked_set_hash(jnp.asarray(id_hi), jnp.asarray(id_lo), alive)
    n, k, c = cfg.n, cfg.k, cfg.c
    return EngineState(
        key_hi=jnp.asarray(key_hi, dtype=jnp.uint32),
        key_lo=jnp.asarray(key_lo, dtype=jnp.uint32),
        ring_perm=perm,
        ring_pos=ring_positions(perm),
        ring_alive=ring_alive,
        id_hi=jnp.asarray(id_hi, dtype=jnp.uint32),
        id_lo=jnp.asarray(id_lo, dtype=jnp.uint32),
        alive=alive,
        obs_idx=topo.obs_idx.astype(idt),
        # A copy, not an alias: engine_step donates its input state, and the
        # runtime rejects the same buffer donated twice.
        inval_obs=jnp.copy(topo.obs_idx.astype(idt)),
        config_epoch=jnp.int32(0),
        config_hi=config_hi,
        config_lo=config_lo,
        n_members=jnp.sum(alive, dtype=jnp.int32),
        fd_count=jnp.zeros((n, k), dtype=ndt),
        fd_hist=jnp.zeros((n, k), dtype=jnp.dtype(pol.hist)),
        fd_fired=jnp.zeros((n, k), dtype=bool),
        fire_round=jnp.full((n, k), pol.fire_never, dtype=rdt),
        join_pending=jnp.zeros((n,), dtype=bool),
        cohort_of=jnp.zeros((n,), dtype=cdt),
        report_bits=jnp.zeros((c, n), dtype=jnp.dtype(pol.report)),
        seen_down=jnp.zeros((c,), dtype=bool),
        released=jnp.zeros((c, n), dtype=bool),
        announced=jnp.zeros((c,), dtype=bool),
        prop_mask=jnp.zeros((c, n), dtype=bool),
        prop_hi=jnp.zeros((c,), dtype=jnp.uint32),
        prop_lo=jnp.zeros((c,), dtype=jnp.uint32),
        vote_hi=jnp.zeros((n,), dtype=jnp.uint32),
        vote_lo=jnp.zeros((n,), dtype=jnp.uint32),
        vote_valid=jnp.zeros((n,), dtype=bool),
        rounds_undecided=jnp.zeros((), dtype=ndt),
        cp_rnd_r=jnp.zeros((n,), dtype=ndt),
        cp_rnd_i=jnp.zeros((n,), dtype=idt),
        cp_vrnd_r=jnp.zeros((n,), dtype=ndt),
        cp_vrnd_i=jnp.zeros((n,), dtype=idt),
        cp_vval_src=jnp.full((n,), -1, dtype=cdt),
        classic_epoch=jnp.zeros((), dtype=ndt),
        round_idx=jnp.int32(0),
        retired=jnp.zeros((n,), dtype=bool),
    )


@jax.jit
def _observer_table_off_pending(perm, ring_alive, alive, pending, saved):
    """:func:`with_observer_table_rebuilt`'s one program a load shape."""

    def one(perm, ring_alive, alive, pending, saved):
        walked = ring_topology_from_perm(perm, alive, ring_alive).obs_idx
        return jnp.where(pending[None, :], saved, walked.astype(saved.dtype))

    return (jax.vmap(one) if perm.ndim == 3 else one)(
        perm, ring_alive, alive, pending, saved
    )


def with_observer_table_rebuilt(state: EngineState) -> EngineState:
    """``state`` (a cluster's, or a fleet's stacked one; its masks may be
    bit-packed) with ``inval_obs`` made whole, as a loader makes the ring
    lanes: the walk's table of the perms and the membership it holds, the
    held column where a joiner is pending (gatekeepers are history, not
    topology). A view change repairs that lane in place and derives
    ``obs_idx`` from it, so a wrong column that came in with an archive
    would ride through every later commit; a sound state comes back bit for
    bit. One walk, a tenant at a time: ``utils/checkpoint``'s loaders call
    it, outside any round program."""
    alive, pending = (
        lane if lane.dtype == bool else unpack_bool(lane)
        for lane in (state.alive, state.join_pending)
    )
    return state._replace(inval_obs=_observer_table_off_pending(
        state.ring_perm, state.ring_alive, alive, pending, state.inval_obs
    ))


class FaultInputs(NamedTuple):
    """Per-step fault-injection masks (the device analog of the reference's
    StaticFailureDetector blacklist + MessageDropInterceptor fixtures)."""

    crashed: jnp.ndarray  # [n] bool — unresponsive; never votes or alerts
    probe_fail: jnp.ndarray  # [n, k] bool — extra per-edge probe failures
    rx_block: jnp.ndarray  # [c, n] bool — cohort c cannot hear from slot i

    @staticmethod
    def none(cfg: EngineConfig) -> "FaultInputs":
        return FaultInputs(
            crashed=jnp.zeros((cfg.n,), dtype=bool),
            probe_fail=jnp.zeros((cfg.n, cfg.k), dtype=bool),
            rx_block=jnp.zeros((cfg.c, cfg.n), dtype=bool),
        )


#: Loss in permille at which a member's ingress is wholly dead: every probe
#: into it and every reply to it is lost, and it hears no alert or proposal.
LINK_LOSS_DEAD = 1000


class LinkFaults(NamedTuple):
    """One-way link faults as a device-resident lane of its own beside
    :class:`FaultInputs`: per-member INGRESS loss and one on/off schedule
    for all faulty members (the paper's Fig. 9 flip-flopping one-way
    partition at 1000 permille, its Fig. 10 lossy ingress below that). A
    faulty member keeps sending: it is reported by its observers and reports
    its own subjects, whose replies it does not hear.

    The lane is OPTIONAL at the Python level, on the observers' pattern:
    the round programs take it as the keyword ``links`` and hand it back
    last; with ``None`` they trace not one operation more, so a cluster
    that never set it runs the programs it always ran. One engine round is
    one failure-detector interval. The lane keeps its own clock (``age``):
    ``round_idx`` starts again at every view change and a schedule must not.
    """

    loss_permille: jnp.ndarray  # [n] int32 — ingress loss per member, 0 = healthy
    on_rounds: jnp.ndarray  # [] int32 — rounds of a period the faults are on
    off_rounds: jnp.ndarray  # [] int32 — rounds they are off; 0 = always on
    seed: jnp.ndarray  # [] uint32 — salt of the probe draws
    age: jnp.ndarray  # [] int32 — rounds run since the lane was set
    probes_lost: jnp.ndarray  # [] int32 — probes the lane failed since it was set

    @staticmethod
    def none(cfg: EngineConfig, tenants: Optional[int] = None) -> "LinkFaults":
        """A set lane that names nobody (the setter scatters into it); with
        ``tenants``, a fleet's: every leaf under a leading tenant axis."""
        stacked = () if tenants is None else (tenants,)
        return LinkFaults(
            loss_permille=jnp.zeros((*stacked, cfg.n), dtype=jnp.int32),
            on_rounds=jnp.zeros(stacked, dtype=jnp.int32),
            off_rounds=jnp.zeros(stacked, dtype=jnp.int32),
            seed=jnp.zeros(stacked, dtype=jnp.uint32),
            age=jnp.zeros(stacked, dtype=jnp.int32),
            probes_lost=jnp.zeros(stacked, dtype=jnp.int32),
        )


class StepEvents(NamedTuple):
    """Observable outcomes of one engine step (host-side driver reads these)."""

    decided: jnp.ndarray  # scalar bool — consensus reached this step
    # Which path decided: True = one-step fast round; False = the classic
    # fallback's coordinator rule (only meaningful when decided). The engine
    # twin of the host event VIEW_CHANGE_ONE_STEP_FAILED.
    fast_decided: jnp.ndarray  # scalar bool
    winner_mask: jnp.ndarray  # [n] bool — the decided cut (flip set)
    proposals_announced: jnp.ndarray  # [c] bool — cohorts that proposed this step
    alerts_emitted: jnp.ndarray  # int32 — new edge alerts this step
    total_votes: jnp.ndarray  # int32
    max_votes: jnp.ndarray  # int32
    # Per-cohort announced-proposal hash lanes as of THIS round, captured
    # before any view-change reset (reading state.prop_* after a deciding
    # step sees post-reset zeros — observers must use these instead).
    prop_hi: jnp.ndarray  # [c] uint32
    prop_lo: jnp.ndarray  # [c] uint32


# ---------------------------------------------------------------------------
# Device-resident telemetry plane (EngineConfig.telemetry == 1)
# ---------------------------------------------------------------------------

#: Log2 bucket count of the rounds-undecided histogram: bucket b counts
#: decisions that sat undecided for r rounds with floor(log2(max(r, 1)))
#: == b (clamped into the last bucket), so bucket 0 is the one-round fast
#: path and bucket 7 holds every >= 128-round stall.
TELEMETRY_BUCKETS = 8

#: field -> shape symbols over (n, k, c, b) — the LANE_SPECS convention with
#: ``b`` = :data:`TELEMETRY_BUCKETS`. Every telemetry lane is int32: these
#: are accumulators, not protocol state, and the compaction policy never
#: narrows them (a saturating counter would silently lie). The ``telemetry``
#: analyzer family mirrors this exact field set (tools/analysis/telemetry.py)
#: so a new lane cannot skip the partition rules or the exposition surface.
TELEMETRY_LANE_SPECS: Dict[str, Tuple[str, ...]] = {
    "tl_rounds": (),
    "tl_alerts": (),
    "tl_active": ("c", "n"),
    "tl_invalidated": ("c", "n"),
    "tl_proposals": ("c",),
    "tl_tally_sum": (),
    "tl_fast_decisions": (),
    "tl_classic_decisions": (),
    "tl_conflict_rounds": (),
    "tl_dissent": (),
    "tl_invalidation_rounds": (),
    "tl_invalidation_dense_rounds": (),
    "tl_view_change_dense": (),
    "tl_undecided_hist": ("b",),
}


class TelemetryLanes(NamedTuple):
    """On-device activity/tally/conflict accumulators, carried alongside
    :class:`EngineState` through the jitted round bodies when
    ``EngineConfig.telemetry == 1`` and fetched ONLY at the existing
    host-sync boundaries (``sync`` / ``stream_fetch`` / ``health_scan``).

    Two grains, one discipline — zero new hot-loop collectives:

    - Scalar counters reuse reductions the round body already computes
      (``alerts_emitted``, the tally scalars, the decision flags), so
      accumulating them adds elementwise int adds only.
    - Per-slot lanes stay at their native [c, n] / [c] grain (sharded by
      the same :data:`rapid_tpu.parallel.mesh.PARTITION_RULES` table);
      cross-shard reductions over them happen in the separate
      ``telemetry_digest`` jit dispatched at fetch boundaries, never
      inside the convergence loop.

    Under the tenancy vmap every lane grows a leading ``[t]`` axis, so
    every metric is per-tenant for free."""

    tl_rounds: jnp.ndarray  # [] int32 — rounds stepped
    tl_alerts: jnp.ndarray  # [] int32 — edge alerts applied (sum of alerts_emitted)
    # Rounds each (cohort, subject) slot was ACTIVE: nonzero report bits or
    # a watermark tally in the [L, H) flux band. The quantity ROADMAP item
    # 3's sparse O(activity) rounds will skip work by.
    tl_active: jnp.ndarray  # [c, n] int32
    tl_invalidated: jnp.ndarray  # [c, n] int32 — implicit-invalidation events
    tl_proposals: jnp.ndarray  # [c] int32 — proposals released per cohort
    tl_tally_sum: jnp.ndarray  # [] int32 — winning-tally sizes, summed at decisions
    tl_fast_decisions: jnp.ndarray  # [] int32 — one-step fast-path decisions
    tl_classic_decisions: jnp.ndarray  # [] int32 — classic-fallback decisions
    # Rounds where some cohort had announced but the fast path did NOT
    # decide — the per-tenant conflict-rate numerator ("The Performance of
    # Paxos and Fast Paxos": the fast path's win hinges on collision rate).
    tl_conflict_rounds: jnp.ndarray  # [] int32
    # Cohorts that, at a decision, had announced a cut other than the decided
    # one, summed over decisions: the paper's own conflict count (Fig. 11
    # counts the receivers whose announced cut missed a victim), where
    # ``tl_conflict_rounds`` counts the ROUNDS the fast path stood undecided.
    tl_dissent: jnp.ndarray  # [] int32
    # Rounds in which this cluster (this tenant) needed the implicit-
    # invalidation arm (some cohort had a subject in flux after a DOWN
    # report), and those of them in which it took the DENSE loop over all n
    # slots: its subjects in flux overflowed the compacted form's bucket
    # (ops/cut_detection.invalidation_bucket), or the program traces the
    # dense loop alone (a mesh's). 0 of the second over a window of traffic
    # says the bucket held it.
    tl_invalidation_rounds: jnp.ndarray  # [] int32
    tl_invalidation_dense_rounds: jnp.ndarray  # [] int32
    # Commits whose view change gathered ``ring_alive`` whole instead of
    # flipping the cut's own positions: the cut overflowed
    # ``ops/rings.view_change_bucket``, or the program traces the gather
    # alone (a mesh's: every commit). 0 over a window of traffic says the
    # bucket held every cut.
    tl_view_change_dense: jnp.ndarray  # [] int32
    tl_undecided_hist: jnp.ndarray  # [TELEMETRY_BUCKETS] int32 — log2(rounds-undecided) at decision


def initial_telemetry(cfg: EngineConfig) -> TelemetryLanes:
    """All-zero telemetry lanes for this config's geometry."""
    dims = {"n": cfg.n, "k": cfg.k, "c": cfg.c, "b": TELEMETRY_BUCKETS}
    return TelemetryLanes(**{
        field: jnp.zeros(tuple(dims[s] for s in shape), dtype=jnp.int32)
        for field, shape in TELEMETRY_LANE_SPECS.items()
    })


def telemetry_bytes_total(cfg: EngineConfig) -> int:
    """At-rest bytes of one cluster's telemetry lanes (all int32), per
    device."""
    dims = {"n": cfg.n, "k": cfg.k, "c": cfg.c, "b": TELEMETRY_BUCKETS}
    total = 0
    for shape in TELEMETRY_LANE_SPECS.values():
        elems = 1
        for sym in shape:
            elems *= dims[sym]
        total += elems * 4
    return total


# ---------------------------------------------------------------------------
# Device round-trace ring (EngineConfig.trace == R > 0)
# ---------------------------------------------------------------------------

#: field -> shape symbols over (r,) with ``r`` = ``EngineConfig.trace`` (the
#: ring capacity R) — the LANE_SPECS convention, mirrored by the ``telemetry``
#: analyzer family (tools/analysis/telemetry.py) exactly like
#: :data:`TELEMETRY_LANE_SPECS`, so a new ring lane cannot skip the partition
#: rules, the decode vocabulary, or the exposition surface. Every lane is
#: int32 (records, not protocol state; compaction never narrows them).
TRACE_LANE_SPECS: Dict[str, Tuple[str, ...]] = {
    "tr_round": ("r",),
    "tr_epoch": ("r",),
    "tr_active": ("r",),
    "tr_alerts": ("r",),
    "tr_proposals": ("r",),
    "tr_tally": ("r",),
    "tr_path": ("r",),
    "tr_conflict": ("r",),
    "tr_undecided": ("r",),
    "tr_cursor": (),
    "tr_wraps": (),
}


class TraceRing(NamedTuple):
    """A bounded device-resident flight recorder of per-round records: the
    last ``EngineConfig.trace`` rounds, one slot per round, written inside
    the jitted round body and fetched ONLY at the existing host-sync
    boundaries (the telemetry plane's discipline — the ring is its
    round-resolution refinement, so ``trace > 0`` requires ``telemetry``).

    Cursor semantics (the wraparound contract the property tests pin):

    - ``tr_cursor`` counts records EVER written (monotone); the slot a
      round lands in is ``tr_cursor % R``, so the ring always holds the
      last ``min(R, tr_cursor)`` rounds.
    - ``tr_wraps`` increments each time the write fills slot ``R - 1`` —
      it reconciles with the cursor as ``tr_wraps == tr_cursor // R``, and
      with the telemetry plane as ``tr_cursor == tl_rounds``.
    - Decode order: rotate from ``tr_cursor % R`` when wrapped; the
      ``(tr_epoch, tr_round)`` pairs of the decoded records are strictly
      lexicographically increasing (``round_idx`` resets at each view
      change, ``config_epoch`` only grows) — monotone across a wrap.

    Under the tenancy vmap every lane grows a leading ``[t]`` axis; frozen
    or quarantined tenants coast with a GATED cursor (the wave's tree-level
    ``where`` holds cursor and slots alike), so a coasting tenant's ring
    never records phantom rounds."""

    tr_round: jnp.ndarray  # [R] int32 — round stamp (round_idx within the epoch)
    tr_epoch: jnp.ndarray  # [R] int32 — config_epoch the round executed in
    tr_active: jnp.ndarray  # [R] int32 — active (cohort, subject) slots this round
    tr_alerts: jnp.ndarray  # [R] int32 — edge alerts applied this round
    tr_proposals: jnp.ndarray  # [R] int32 — proposals released this round
    tr_tally: jnp.ndarray  # [R] int32 — winning-tally size (0 unless decided)
    tr_path: jnp.ndarray  # [R] int32 — decision path: 0 none, 1 fast, 2 classic
    tr_conflict: jnp.ndarray  # [R] int32 — announced-but-no-fast-decision flag
    tr_undecided: jnp.ndarray  # [R] int32 — rounds_undecided entering the round
    tr_cursor: jnp.ndarray  # [] int32 — records ever written (slot = cursor % R)
    tr_wraps: jnp.ndarray  # [] int32 — times the write filled slot R - 1


def initial_trace(cfg: EngineConfig) -> TraceRing:
    """All-zero trace ring for this config's capacity."""
    dims = {"r": cfg.trace}
    return TraceRing(**{
        field: jnp.zeros(tuple(dims[s] for s in shape), dtype=jnp.int32)
        for field, shape in TRACE_LANE_SPECS.items()
    })


def trace_bytes_total(cfg: EngineConfig) -> int:
    """At-rest bytes of one cluster's trace ring (all int32), per
    device: R rounds of history at a byte cost fixed by config, not by
    event rate."""
    dims = {"r": cfg.trace}
    total = 0
    for shape in TRACE_LANE_SPECS.values():
        elems = 1
        for sym in shape:
            elems *= dims[sym]
        total += elems * 4
    return total


# ---------------------------------------------------------------------------
# Wide <-> compact converters (the differential seam)
# ---------------------------------------------------------------------------


def _cast_lanes(tree, dtypes: Dict[str, str], fire_never_src: int, fire_never_out: int):
    """Cast every lane of an EngineState/FaultInputs pytree to ``dtypes``,
    remapping the source layout's fire_round sentinel to
    ``fire_never_out``. Elementwise converts only — jit-safe."""
    out = {}
    for field, value in tree._asdict().items():
        dt = jnp.dtype(dtypes[field])
        if field == "fire_round":
            value = jnp.where(
                value == jnp.asarray(fire_never_src, value.dtype),
                jnp.asarray(fire_never_out, dt),
                value.astype(dt),
            )
        out[field] = value.astype(dt)
    return type(tree)(**out)


def widen_state(cfg: EngineConfig, state: EngineState) -> EngineState:
    """A compact state as the wide int32/uint32 layout (sentinel remapped to
    :data:`FIRE_NEVER`). The identity on an already-wide state — which is
    what lets every wide-vs-compact differential compare
    ``widen_state(compact_cfg, compact_state)`` against the oracle's state
    leaf-for-leaf, bit-for-bit."""
    return _cast_lanes(
        state, lane_dtypes(cfg._replace(compact=0)),
        compaction_policy(cfg).fire_never, FIRE_NEVER,
    )


def narrow_state(cfg: EngineConfig, state: EngineState) -> EngineState:
    """A WIDE state at ``cfg``'s compact policy dtypes (inverse of
    :func:`widen_state` within the envelopes). Host callers migrating
    checkpoints should validate ranges first (:func:`validate_envelope`) —
    the cast itself wraps silently, as device casts do."""
    return _cast_lanes(
        state, lane_dtypes(cfg), FIRE_NEVER, compaction_policy(cfg).fire_never
    )


def validate_envelope(cfg: EngineConfig, state: EngineState) -> None:
    """Host-side (fetching) range check that a WIDE state fits ``cfg``'s
    compact policy: counters within int16, round_idx within
    ROUND_ENVELOPE, fire rounds real-or-sentinel. Raises ValueError naming
    the first offending lane — the loud alternative to a wrapping cast."""
    pol = compaction_policy(cfg)
    if pol == WIDE_POLICY:
        return
    limits = {
        "fd_count": (-(1 << 15), (1 << 15) - 1),
        "cp_rnd_r": (0, (1 << 15) - 1),
        "cp_vrnd_r": (0, (1 << 15) - 1),
        "classic_epoch": (0, (1 << 15) - 1),
        "rounds_undecided": (0, (1 << 15) - 1),
        "round_idx": (0, ROUND_ENVELOPE),
    }
    for field, (lo, hi) in limits.items():
        arr = np.asarray(getattr(state, field))
        if arr.size and (int(arr.min()) < lo or int(arr.max()) > hi):
            raise ValueError(
                f"state lane {field!r} range [{arr.min()}, {arr.max()}] "
                f"exceeds the compact envelope [{lo}, {hi}]"
            )
    fire = np.asarray(state.fire_round)
    real = fire[fire != FIRE_NEVER]
    if real.size and (int(real.min()) < 0 or int(real.max()) > ROUND_ENVELOPE):
        raise ValueError(
            f"fire_round carries a non-sentinel value outside "
            f"[0, {ROUND_ENVELOPE}]: [{real.min()}, {real.max()}]"
        )


# ---------------------------------------------------------------------------
# Opt-in bit-packed bool masks (pack/unpack ops + whole-pytree converters)
# ---------------------------------------------------------------------------

#: bool lane -> the SLOT axis it packs 8-to-a-byte along (the n dimension:
#: the only axis guaranteed large; [c]-only lanes stay bool — a cohort
#: count need not divide 8 and saves c/8 bytes total).
PACKED_MASK_AXES: Dict[str, int] = {
    "alive": 0, "join_pending": 0, "vote_valid": 0, "retired": 0,
    "fd_fired": 0, "released": 1, "prop_mask": 1,
    "crashed": 0, "probe_fail": 0, "rx_block": 1,
}


def pack_bool(mask: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Pack a bool array 8-to-a-byte along ``axis`` (little-endian within
    the byte: element i rides bit i%8 of word i//8). The axis length must
    divide 8 — pad the mask (``parallel.mesh.pad_to_multiple``) first."""
    mask = jnp.asarray(mask, dtype=bool)
    size = mask.shape[axis]
    if size % 8:
        raise ValueError(
            f"pack_bool axis {axis} has length {size}, not a multiple of 8"
        )
    moved = jnp.moveaxis(mask, axis, -1)
    grouped = moved.reshape(*moved.shape[:-1], size // 8, 8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    words = jnp.sum(grouped.astype(jnp.uint8) * weights, axis=-1, dtype=jnp.uint8)
    return jnp.moveaxis(words, -1, axis)


def unpack_bool(words: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Inverse of :func:`pack_bool`: uint8 words -> the bool mask (length
    8x along ``axis``)."""
    moved = jnp.moveaxis(jnp.asarray(words, dtype=jnp.uint8), axis, -1)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (moved[..., None] >> shifts) & jnp.uint8(1)
    flat = bits.reshape(*moved.shape[:-1], moved.shape[-1] * 8)
    return jnp.moveaxis(flat, -1, axis).astype(bool)


def pack_masks(tree):
    """The opt-in bit-packed representation of an EngineState/FaultInputs
    pytree: every bool lane in :data:`PACKED_MASK_AXES` packed along its
    slot axis (shape [n] -> [n/8], [c, n] -> [c, n/8], [n, k] -> [n/8, k]).
    Same field names — the :data:`parallel.mesh.PARTITION_RULES` table and
    :func:`parallel.mesh.shard_pytree`'s divisibility validation cover the
    packed shapes unchanged. Requires n % 8 == 0."""
    return type(tree)(**{
        field: (
            pack_bool(value, axis=PACKED_MASK_AXES[field])
            if field in PACKED_MASK_AXES
            else value
        )
        for field, value in tree._asdict().items()
    })


def unpack_masks(tree):
    """Inverse of :func:`pack_masks` (exact: pack/unpack is a bijection on
    whole bytes)."""
    return type(tree)(**{
        field: (
            unpack_bool(value, axis=PACKED_MASK_AXES[field])
            if field in PACKED_MASK_AXES
            else value
        )
        for field, value in tree._asdict().items()
    })


# ---------------------------------------------------------------------------
# Sizing: bytes/member as a pure function of the config (the bench's
# 10M/100M deployment-sizing table reads exactly this)
# ---------------------------------------------------------------------------


def _lane_elems(shape_syms: Tuple[str, ...], n: int, k: int, c: int) -> int:
    dims = {"n": n, "k": k, "c": c}
    total = 1
    for sym in shape_syms:
        total *= dims[sym]
    return total


def state_bytes_total(cfg: EngineConfig, packed: bool = False) -> int:
    """Total at-rest bytes of one cluster's EngineState + FaultInputs under
    ``cfg``'s policy (``packed=True`` additionally prices the opt-in
    bit-packed bool masks). Exact: LANE_SPECS mirrors the constructors
    field-for-field (pinned by tests/test_state_compaction.py against a
    real state pytree's leaf nbytes)."""
    dtypes = lane_dtypes(cfg)
    total = 0
    for field, (shape_syms, _kind) in LANE_SPECS.items():
        elems = _lane_elems(shape_syms, cfg.n, cfg.k, cfg.c)
        if packed and field in PACKED_MASK_AXES:
            # Packs along an n-sized axis: 1 bit per element.
            total += (elems + 7) // 8
        else:
            total += elems * np.dtype(dtypes[field]).itemsize
    return total


def state_bytes_per_member(cfg: EngineConfig, packed: bool = False) -> float:
    """Per-slot state footprint — the scale metric ROADMAP item 5's 100M
    sizing is computed from."""
    return state_bytes_total(cfg, packed=packed) / cfg.n


def pytree_nbytes(tree) -> int:
    """Logical bytes of a pytree's array leaves (works on ShapeDtypeStructs
    and concrete arrays alike — no fetch)."""
    import jax

    return sum(
        int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    )
