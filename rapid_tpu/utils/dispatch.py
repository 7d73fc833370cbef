"""The host-side engine dispatch seam, shared by every device driver.

``VirtualCluster`` and ``TenantFleet`` (and, through them, the streaming
pipeline in ``rapid_tpu/serving``) observe the device engine at the same
grain: transfer bytes charged at the host<->device boundary, and one bounded
latency histogram per dispatch phase (``engine_dispatch_ms{phase=...}``).
Before this seam was shared, the two drivers carried copy-pasted methods and
the phase labels were free strings — a typo'd phase would silently mint a
new histogram series and vanish from every dashboard keyed on the known
names. :data:`ENGINE_DISPATCH_PHASES` is the registered phase vocabulary,
enforced at WRITE time (the ledger's ``STAGE_NAMES`` discipline applied to
the telemetry tier): an unregistered phase raises instead of forking the
vocabulary.

The ``stream_enqueue`` / ``stream_fetch`` pair is the streaming pipeline's
split of the old dispatch+fetch grain: an enqueued dispatch returns as soon
as JAX has queued the program (host time spent *submitting*), while a fetch
phase brackets the explicit synchronization boundaries (host time spent
*blocked on the device*). Their separation is what makes overlap efficiency
measurable from the histograms alone (``serving/stream.py``).

One span vocabulary, host and device. Every ``_dispatch`` block is also a
``rapid:<phase>`` span on the JAX profiler's clock (``profiling.annotate``),
so a trace taken around live traffic shows the host phases beside the device
operations they enqueue; the histograms are the always-on sums and the
profiler trace is the span store. Inside the compiled programs the round's
phases and the arms of its conditionals carry ``jax.named_scope`` names from
:data:`ENGINE_SCOPES` (:func:`scope`), registered and enforced at write time
like the phases; :func:`cond_across` is the form those conditionals take, so
that a batched program which names its batch axis keeps them conditionals.

Set-up has the same treatment. The constructors' work runs inside
:func:`setup_stage` blocks, ``rapid:setup.<stage>`` spans from the registered
:data:`ENGINE_SETUP_STAGES`, whose wall seconds ``engine_telemetry.
setup_snapshot()`` adds up; and a dispatch phase or a stage, while open, is the
span under which ``engine_telemetry``'s collector files every trace, lowering,
compile and cache load that happens (``compile_snapshot()["by_span"]``).

The dispatch journal keeps the single calls the histograms add up. A closing
``_dispatch`` block writes one row (``engine_telemetry.DISPATCH_RECORD``): its
start, the moment its wait for the device began (:meth:`DispatchSeam.
_wait_begins`, stamped at the one line where a fetching phase starts to wait;
an enqueue-only phase has none) and its end, under the id of the membership
change it serves. A driver's first dispatch from :data:`INJECTING_PHASES`
after its last from :data:`DECIDING_PHASES` opens a change, and every
dispatch up to and including the next deciding one carries its id; a dispatch
outside carries 0. Under a ``StreamDriver`` a wave is the change: ``submit``
opens it and the wave's retirement closes it, so the driver's own injections
open nothing. A closed change is one row too (``CHANGE_RECORD``) and one
sample of ``engine_change_ms``; ``engine_telemetry.journal_snapshot()`` reads
both rings.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp

from rapid_tpu.utils import engine_telemetry
from rapid_tpu.utils.profiling import annotate

#: The registered dispatch-phase vocabulary — every ``_dispatch(...)`` entry
#: across the engine drivers. Parameterize by metric fields, never by
#: minting a phase name: renderers (clustertop's DISP99 merge, perfview,
#: scrape configs) key off these labels, and the golden-name tests pin the
#: series they produce.
ENGINE_DISPATCH_PHASES = frozenset({
    # VirtualCluster entrypoints.
    "step",
    "sync",
    "run_to_decision",
    "run_until_membership",
    # TenantFleet entrypoints.
    "fleet_step",
    "fleet_decision",
    "fleet_wave",
    # The per-tenant health reduction (the serving supervision tier's
    # poisoned-tenant tripwire, rapid_tpu/serving/supervisor.py).
    "health_scan",
    # Streaming pipeline (rapid_tpu/serving): enqueue-only dispatches and
    # the explicit fetch boundaries they synchronize at.
    "stream_enqueue",
    "stream_fetch",
    # Fault and membership injection (host work between dispatches): the
    # crash/revive scatter enqueue, the join wave's blocking admissibility
    # fetch, and its fetch-free gatekeeper placement.
    "inject_crash",
    "inject_join_admit",
    "inject_join_place",
    # The link-fault lane's setter: bounds check, index upload and one
    # placement program, enqueued without a fetch.
    "inject_link_faults",
    # The partition seams (``set_partition``: cohort indices and sender
    # slots in one upload and one placement program; ``set_rx_block``: the
    # whole lane uploaded), each with the re-stamp of the fired edges,
    # enqueued without a fetch.
    "inject_partition",
})


def _registered_phases(name: str, phases) -> frozenset:
    """``phases`` as a subset of :data:`ENGINE_DISPATCH_PHASES`: a name the
    vocabulary does not hold fails the import, as a typo'd phase fails its
    ``_dispatch``."""
    unknown = sorted(set(phases) - ENGINE_DISPATCH_PHASES)
    if unknown:
        raise ValueError(
            f"{name} names unregistered engine dispatch phases {unknown}; "
            f"add them to rapid_tpu.utils.dispatch.ENGINE_DISPATCH_PHASES"
        )
    return frozenset(phases)


#: The phases that hand the engine a fault or a membership request: a
#: driver's first of these after its last deciding dispatch opens a
#: membership change in the journal.
INJECTING_PHASES = _registered_phases("INJECTING_PHASES", {
    "inject_crash",
    "inject_join_admit",
    "inject_join_place",
    "inject_link_faults",
    "inject_partition",
})

#: The phases whose fetch carries a decision: one of these closes the change
#: that is open on its driver. (A stream has none: its waves close theirs.)
DECIDING_PHASES = _registered_phases("DECIDING_PHASES", {
    "run_to_decision",
    "run_until_membership",
    "fleet_decision",
    "fleet_wave",
})

#: Phase name -> the number the journal's rows store for it.
_PHASE_IDS = engine_telemetry.journal_phases(sorted(ENGINE_DISPATCH_PHASES))

#: Prefix of a dispatch phase's span on the profiler's clock.
SPAN_PREFIX = "rapid:"

#: The registered set-up stage vocabulary, placed by what the constructors
#: do: every ``setup_stage(...)`` block of ``VirtualCluster.create`` /
#: ``from_endpoints`` and ``TenantFleet.create``. A dotted stage lies inside
#: the stage before its dot, so its seconds are inside its parent's.
ENGINE_SETUP_STAGES = frozenset({
    # The whole of VirtualCluster.create / from_endpoints: the host's ring
    # keys and identity draws, then initial_state (the ring build on the
    # device; under a mesh with the identity arrays' placement, which only
    # enqueues: 0.05 s at 10M, so it has no stage), the driver's own lanes.
    "create",
    "create.keys",
    "create.state",
    # The whole of TenantFleet.create: the loop of VirtualCluster.create
    # calls, then the stack into one fleet state.
    "fleet_create",
    "fleet_create.tenants",
    "fleet_create.stack",
})


@contextmanager
def setup_stage(stage: str):
    """One block of a constructor's work: the span ``rapid:setup.<stage>`` on
    the profiler's clock (a ``profiling.trace`` around a restart shows the
    stages beside the device operations they enqueue), the span that the
    pipeline events inside it are filed under, and the block's wall seconds
    added to ``engine_telemetry.setup_snapshot()``. Like a dispatch phase, a
    stage outside :data:`ENGINE_SETUP_STAGES` raises here, at write time.
    The collector is installed here, not only by the driver the constructor
    ends in, so the first ``create`` of a process is heard compiling."""
    if stage not in ENGINE_SETUP_STAGES:
        raise ValueError(
            f"unregistered engine set-up stage {stage!r}; add it to "
            f"rapid_tpu.utils.dispatch.ENGINE_SETUP_STAGES"
        )
    engine_telemetry.install()
    name = engine_telemetry.SETUP_PREFIX + stage
    with annotate(SPAN_PREFIX + name):
        depth = engine_telemetry.push_span(name)
        start = time.perf_counter()
        try:
            yield
        finally:
            engine_telemetry.close_stage(stage, depth, time.perf_counter() - start)

#: The registered device-scope vocabulary: every ``jax.named_scope`` inside
#: the compiled engine programs (the round's phases, both arms of each
#: conditional, the injection and barrier programs). A device event's
#: op-name path carries the scopes it was traced under, so a profile reads
#: per phase instead of per ``fusion.<n>``. No name may contain a needle of
#: ``parallel/hlo_facts.source_of`` or a marker of ``classify_location``.
ENGINE_SCOPES = (
    "edge_masks",
    "fd_tick",
    "deliver",
    "deliver_skip",
    "cut_detection",
    "invalidation",
    "invalidation_skip",
    "tally",
    "classic",
    "classic_skip",
    "view_change",
    "view_keep",
    "observers",
    "join_predecessors",
    "sync_checksum",
    "loop_result",
)


def scope(name: str):
    """``jax.named_scope(name)`` for a registered engine scope: a context
    manager that also decorates a function. Metadata only — the compiled
    program computes the same thing — and, like a dispatch phase, a name
    outside :data:`ENGINE_SCOPES` raises here, at write time."""
    if name not in ENGINE_SCOPES:
        raise ValueError(
            f"unregistered engine scope {name!r}; add it to "
            f"rapid_tpu.utils.dispatch.ENGINE_SCOPES"
        )
    return jax.named_scope(name)


def cond_across(axis, pred, taken, skipped, *operands):
    """``lax.cond(pred, taken, skipped, *operands)`` for a conditional inside
    a round that may run under a ``vmap``; returns ``(result, opened)``.

    ``axis`` is the name the enclosing ``vmap`` gave its batch axis, or
    ``None``. With ``None`` this IS the plain ``lax.cond`` (``opened`` is
    ``pred``): a cluster, and a fleet program that names no axis, trace what
    they always traced, and under an unnamed ``vmap`` the batched predicate
    turns the conditional into a select that runs both arms for everybody.
    With a name the predicate is reduced over that axis to ``opened``, one
    scalar that ``vmap`` does not batch, so the conditional stays one: the
    taken arm computes ``taken`` for every member of the batch and selects
    per member by the member's own ``pred`` (what the select did, so each
    member's result is bit-identical), and a round in which nobody's
    ``pred`` holds runs ``skipped`` alone. On a mesh that shards the batch
    axis the reduce would be a collective across it; callers there pass no
    name."""
    if axis is None:
        return jax.lax.cond(pred, taken, skipped, *operands), pred
    opened = jax.lax.psum(pred.astype(jnp.int32), axis) > 0

    def taken_by_some(*operands):
        return jax.tree_util.tree_map(
            lambda t, s: jnp.where(pred, t, s), taken(*operands), skipped(*operands)
        )

    return jax.lax.cond(opened, taken_by_some, skipped, *operands), opened


#: ``t_wait`` of a block that never waited.
_NO_WAIT = float("nan")


class DispatchSeam:
    """Mixin: transfer-byte accounting + the phase-validated dispatch timer.

    Hosts must provide ``self.metrics`` (a :class:`rapid_tpu.utils.metrics.
    Metrics` registry) and ``self.stream`` (the attached ``StreamDriver`` or
    None), and call this constructor; everything here writes through the
    registry and into ``engine_telemetry``'s journal.
    """

    def __init__(self) -> None:
        #: This driver's id in the journal's rows.
        self._driver = engine_telemetry.new_driver()
        #: The membership change the next dispatch serves (0: none).
        self._change = 0
        #: Per open change ``[t_open, first seq, last seq, dispatch seconds]``:
        #: one entry for a batch driver, up to ``depth`` under a stream.
        self._open_changes: dict = {}
        # The open block's wait mark, reported rounds and fetched bytes.
        self._t_wait = _NO_WAIT
        self._rounds = self._fetched = 0

    def _account_h2d(self, *arrays) -> None:
        """Charge host->device uploads (indices, masks, initial state) to
        the transfer-byte counter. Host-side accounting at the driver seams:
        only arrays that originate on the host are charged, which is exactly
        the traffic that crosses the host-device link."""
        self.metrics.inc(
            "engine_h2d_bytes",
            int(sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)),
        )

    def _account_d2h(self, nbytes: int) -> None:
        self.metrics.inc("engine_d2h_bytes", int(nbytes))
        self._fetched += int(nbytes)  # the open block's row takes it

    @contextmanager
    def _dispatch(self, entry: str, **tags):
        """Time one driver operation (a device dispatch and any fetch the
        caller performs inside the block, or an injection's host work) into
        the bounded per-phase latency histogram
        (``engine_dispatch_ms{phase=<entry>}``) and bump the dispatch
        counter — the engine's per-dispatch observability grain. ``entry``
        must come from :data:`ENGINE_DISPATCH_PHASES`; a typo fails here,
        at write time, instead of silently forking the series set.

        The same block is the span ``rapid:<entry>`` on the profiler's
        clock, tagged ``seq`` (this driver's operation count, so the spans
        of one commit or one wave read in order and nesting on the thread
        gives the parent), ``change`` (the membership change it serves, 0
        for none: the spans of one view change share it) and the caller's
        ``tags`` (the stream's ``wave=<index>``). With no trace running the
        span is a flag test. While the block is open it is also the span
        that a compile inside it is filed under
        (``compile_snapshot()["by_span"][entry]``), and when it closes it is
        one row of the journal, on the two clock reads that feed the
        histogram."""
        phase_id = _PHASE_IDS.get(entry)
        if phase_id is None:
            raise ValueError(
                f"unregistered engine dispatch phase {entry!r}; add it to "
                f"rapid_tpu.utils.dispatch.ENGINE_DISPATCH_PHASES"
            )
        metrics = self.metrics
        metrics.inc("engine_dispatches")
        seq = metrics.counters["engine_dispatches"]
        # The open block's wait mark, reported rounds and fetched bytes
        # (blocks do not nest: one set of marks a driver).
        self._t_wait = _NO_WAIT
        self._rounds = self._fetched = 0
        start = time.perf_counter()
        change = self._change
        if not change and self.stream is None and entry in INJECTING_PHASES:
            change = self._change = self._open_change(start)
        with annotate(SPAN_PREFIX + entry, seq=seq, change=change, **tags):
            depth = engine_telemetry.push_span(entry)
            try:
                yield
            finally:
                compiles, gc_s = engine_telemetry.pop_span(depth)
                end = time.perf_counter()
                timed = metrics.record_ms(
                    "engine_dispatch", (end - start) * 1000.0, phase=entry
                )
                engine_telemetry.record_dispatch(
                    phase_id, self._driver, seq, change, start, self._t_wait, end,
                    compiles, gc_s, self._fetched, self._rounds, timed.sum,
                )
                if change:
                    opened = self._open_changes.get(change)
                    if opened is not None:
                        opened[1] = opened[1] or seq
                        opened[2] = seq
                        opened[3] += end - start
                        if entry in DECIDING_PHASES:
                            self._close_change(change, end)

    def _wait_begins(self) -> None:
        """Stamp the open block: the host starts to wait for the device on
        the next line. Before the stamp the block is the host's own work
        (the jitted call, any eager packing), after it the wait; a block
        that only enqueues is never stamped."""
        self._t_wait = time.perf_counter()

    def _open_change(self, t_open=None) -> int:
        """A membership change opens on this driver (a batch driver's first
        injection, a stream's ``submit``); returns its id, which the
        dispatches carry while ``self._change`` holds it."""
        change = engine_telemetry.new_change()
        self._open_changes[change] = [
            time.perf_counter() if t_open is None else t_open, 0, 0, 0.0,
        ]
        return change

    @contextmanager
    def _serving(self, change: int):
        """The dispatches inside the block serve ``change``: how a stream,
        which holds several changes open at once, names the wave it is
        enqueueing or retiring."""
        outer, self._change = self._change, change
        try:
            yield
        finally:
            self._change = outer

    def _forget_change(self, change: int) -> None:
        """An opened change that never came to be (a wave whose injection
        raised): no row, no sample."""
        self._open_changes.pop(change, None)

    def _close_change(self, change: int, t_close=None) -> None:
        """The change is decided (a deciding dispatch's end, a stream wave's
        retirement): one row of the journal's changes and one sample of
        ``engine_change_ms``, the time it was pending."""
        if self._change == change:
            self._change = 0
        opened = self._open_changes.pop(change, None)
        if opened is None:
            return
        t_open, seq_first, seq_last, dispatch_s = opened
        if t_close is None:
            t_close = time.perf_counter()
        engine_telemetry.record_change(
            change, self._driver, t_open, t_close, seq_first, seq_last, dispatch_s
        )
        self.metrics.record_ms("engine_change", (t_close - t_open) * 1000.0)
