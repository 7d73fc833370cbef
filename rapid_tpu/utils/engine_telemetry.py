"""Process-wide device-engine compile/memory telemetry.

The host protocol got its observability tier in PRs 1-2 (flight recorder,
exposition, phase SLOs); the jitted device engine had none — every XLA
compile, persistent-cache hit, and device allocation was invisible, which is
how the perf trajectory went blind (ROADMAP item 2). This module is the
engine-side counterpart: a process-global collector fed by ``jax.monitoring``
events, plus best-effort device-memory probes, consumed by
``VirtualCluster.telemetry_snapshot()`` and the bench ledger.

Compile events are inherently process-global (the XLA compilation cache and
the persistent on-disk cache are shared by every engine instance in the
process), so the collector is a module singleton: ``install()`` registers
the listeners once and ``compile_snapshot()`` reads the monotonic totals.
The collector keeps who and where itself: every trace, lowering and load
(a compile, or a read from the persistent cache) goes, with its exact seconds,
to the program that caused it and to the program span that was open
(``utils/dispatch.py``: a dispatch phase or a constructor's set-up stage), and
``setup_snapshot()`` holds the wall seconds of those stages. (``CompileDelta``
diffs two snapshots around a block, for the bench.)

Beside the span stack the collector keeps the dispatch journal: one row a
closed ``_dispatch`` block and one a closed membership change, in two
preallocated rings that overwrite (:data:`DISPATCH_RECORD`,
:data:`CHANGE_RECORD`, ``journal_snapshot()``). The histograms are the sums;
the journal keeps the single calls, each with what the process was doing
while it was open.

Everything degrades gracefully: a JAX build without ``jax.monitoring`` (or
without ``memory_stats``/``live_arrays``) yields zero counters / ``None``
gauges, never an exception — telemetry must not be able to take down the
engine it observes.
"""

from __future__ import annotations

import array
import collections
import gc
import itertools
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from rapid_tpu.utils.histogram import LogHistogram

logger = logging.getLogger(__name__)

#: jax.monitoring point-event names -> our counter names. The persistent
#: compilation cache emits hits/misses; ``compile_requests_use_cache``
#: counts every compile request that consulted it (hit + miss + disabled).
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
}

#: The duration event XLA records once per backend compile — its count is
#: the process's compile count, its sum the total compile wall time.
_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"

#: The three duration events jax 0.9.0 records with a ``fun_name`` (listed
#: from ``jax/_src/dispatch.py``; each also records a scalar of the same name
#: when it STARTS), by the stage of the pipeline they time. The last wraps
#: ``compile_or_get_cached``: a compile when cold, a cache read and a
#: deserialise when warm, hence "load".
_PIPELINE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _COMPILE_DURATION_EVENT: "load",
}

#: The cache's own durations (no ``fun_name``; recorded inside "load", on a
#: hit only): the read + deserialise, and the cache's estimate of the compile
#: seconds it spared (stored compile time minus the read; may be negative).
_CACHE_DURATION_EVENTS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved",
}

#: What ``by_span`` files an event under when no program span is open.
OUTSIDE = "outside"
#: ``by_program`` shows this many programs, the largest by total seconds, and
#: one more row, ``"other"``, that holds everybody else's sums.
PROGRAM_ROWS = 32
OTHER = "other"
#: ``recent`` remembers this many "load" events.
RECENT_LOADS = 64
#: Prefix of a set-up stage's name on the span stack and in ``by_span``
#: (``utils/dispatch.py::setup_stage``; a dispatch phase has none).
SETUP_PREFIX = "setup."
#: ``setup_snapshot``'s row for the outermost stage blocks alone.
OUTERMOST = "outermost"

_STAGE_FIELDS = ("trace_s", "lower_s", "load_s")

#: Rows each ring of the dispatch journal holds before it overwrites its
#: oldest (the busiest cell makes about 450 dispatches a second).
JOURNAL_CAPACITY = 65_536

#: One closed ``_dispatch`` block. Times are ``time.perf_counter()`` seconds.
DISPATCH_RECORD = np.dtype([
    ("phase", np.uint8),      # index into ``journal_snapshot()["phases"]``
    ("driver", np.uint32),    # the driver's id (``new_driver``)
    ("seq", np.int64),        # the driver's operation count: the span's ``seq``
    ("change", np.int64),     # the membership change it served, 0 for none
    ("t_start", np.float64),  # the block opened
    ("t_wait", np.float64),   # the host began to wait for the device; NaN: it never did
    ("t_end", np.float64),    # the block closed
    ("compiles", np.uint32),  # programs loaded (compiled or read from the cache) meanwhile
    ("gc_s", np.float64),     # seconds of garbage collection meanwhile
    ("bytes", np.int64),      # device->host bytes it was charged
    ("rounds", np.int32),     # engine rounds its observation reported, 0 for none
    ("cum_ms", np.float64),   # the phase's ``engine_dispatch`` sum of this driver after it
])

#: One closed membership change (``utils/dispatch.py`` says what opens and
#: closes one).
CHANGE_RECORD = np.dtype([
    ("change", np.int64),
    ("driver", np.uint32),
    ("t_open", np.float64),
    ("t_close", np.float64),
    ("seq_first", np.int64),   # ``seq`` of its first and last dispatch,
    ("seq_last", np.int64),    # 0 where it had none
    ("dispatch_s", np.float64),  # the sum of its dispatches' durations
])


#: ``array`` type codes of the record types' fields, by numpy type string.
_ARRAY_CODES = {"u1": "B", "u4": "I", "i4": "i", "i8": "q", "f8": "d"}


class _Ring:
    """``capacity`` preallocated rows of one record type, overwritten oldest
    first: one ``array.array`` a field (parallel arrays, so a row is scalar
    stores at one index and no Python object is kept for it). A writer claims
    its row with ``next(claims)``, which the interpreter makes atomic, so the
    hot path takes no lock; ``written`` counts the rows put. (``record_dispatch``
    stores into :attr:`columns` itself, unrolled: it runs in every driver call.)"""

    def __init__(self, dtype: np.dtype, capacity: int) -> None:
        self.dtype, self.capacity, self.written = dtype, capacity, 0
        self.claims = itertools.count()
        self.columns = tuple(
            array.array(code, bytes(capacity * array.array(code).itemsize))
            for code in (_ARRAY_CODES[dtype[name].str[1:]] for name in dtype.names)
        )

    def put(self, *fields) -> None:
        claimed = next(self.claims)
        i = claimed % self.capacity
        for column, value in zip(self.columns, fields):
            column[i] = value
        self.written = claimed + 1

    def ordered(self) -> np.ndarray:
        """A copy of the rows held as one structured array, oldest first."""
        held = min(self.written, self.capacity)
        oldest = self.written % self.capacity if self.written > self.capacity else 0
        out = np.empty(held, dtype=self.dtype)
        for name, column in zip(self.dtype.names, self.columns):
            out[name] = np.roll(np.frombuffer(column, dtype=self.dtype[name]), -oldest)[:held]
        return out


def _program_of(fun_name: Any) -> str:
    """One key for a program's three events: the trace event names the
    function (``outer_fn``), lowering and compilation name its module
    (``jit(outer_fn)``)."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1 : -1]
    return name


class _CompileCollector:
    """Monotonic process-wide compile/cache totals (thread-safe: monitoring
    callbacks can fire from compile worker threads), what the set-up stages
    took, and the stack of open program spans that says where an event ran.

    jax 0.9.0 records every event here synchronously on the thread that
    dispatched the program (verified by listening with the thread's id), so
    with one driving thread, as in every cell, the innermost open span is the
    driver call that caused the event. The span stack is the PROCESS's, not a
    thread's: an event from another thread is filed under whatever span is
    innermost at that moment, whoever opened it. The nesting of the pipeline's
    own intervals (a jit traced inside a jit's trace) is kept per thread."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._thread = threading.local()
        self.counters: Dict[str, int] = {
            name: 0 for name in _EVENT_COUNTERS.values()
        }
        self.compiles = 0
        self.compile_ms_hist = LogHistogram()
        self.pipeline_s: Dict[str, float] = dict.fromkeys(
            (*_PIPELINE_EVENTS.values(), *_CACHE_DURATION_EVENTS.values()), 0.0
        )
        self.by_span: Dict[str, Dict[str, float]] = {}
        self.by_program: Dict[str, Dict[str, float]] = {}
        self.recent: collections.deque = collections.deque(maxlen=RECENT_LOADS)
        self.spans: List[str] = []
        #: Per open span, what the process had done when it opened:
        #: ``(compiles, gc_s)``; :meth:`pop_span` hands back the differences.
        self._span_marks: List[Tuple[int, float]] = []
        self.setup: Dict[str, Dict[str, float]] = {
            OUTERMOST: {"count": 0, "wall_s": 0.0}
        }
        self.gc_s = 0.0
        self._gc_t0 = 0.0
        self.dispatches = _Ring(DISPATCH_RECORD, JOURNAL_CAPACITY)
        self.changes = _Ring(CHANGE_RECORD, JOURNAL_CAPACITY)
        self.phases: Tuple[str, ...] = ()
        self._driver_ids = itertools.count(1)
        self._change_ids = itertools.count(1)

    # -- listeners -------------------------------------------------------

    def on_event(self, event: str, **_kwargs: Any) -> None:
        name = _EVENT_COUNTERS.get(event)
        if name is not None:
            with self._lock:
                self.counters[name] += 1
            if name != "persistent_cache_misses":
                # Recorded inside the load they belong to, the request before
                # the hit and both before that load's duration: remembered
                # until then (``from_cache``). A miss is recorded only when
                # the compiled program is written back, so it tells nothing.
                self._thread.from_cache = name == "persistent_cache_hits"

    def on_start(self, event: str, _value: float, **_kwargs: Any) -> None:
        """A pipeline interval opens (jax records the start time as a scalar
        under the duration event's name): remember it, so that the interval's
        end can tell what of its seconds an inner interval already took."""
        stage = _PIPELINE_EVENTS.get(event)
        if stage is not None:
            self._open_intervals().append([stage, 0.0])

    def on_duration(self, event: str, duration_secs: float, **kwargs: Any) -> None:
        cache_sum = _CACHE_DURATION_EVENTS.get(event)
        if cache_sum is not None:
            with self._lock:
                self.pipeline_s[cache_sum] += duration_secs
            return
        stage = _PIPELINE_EVENTS.get(event)
        if stage is None:
            return
        # Exact seconds, each counted once: an interval inside one of its own
        # stage (a jit traced while its caller is traced) is its parent's, so
        # a trace goes whole to the program that caused it; one inside another
        # stage is taken out of its parent. Without the start scalars (an
        # older jax) every interval counts whole.
        own: Optional[float] = duration_secs
        intervals = self._open_intervals()
        if intervals and intervals[-1][0] == stage:
            inside = intervals.pop()[1]
            own = duration_secs - inside
            if intervals and intervals[-1][0] == stage:
                intervals[-1][1] += inside
                own = None
            elif intervals:
                intervals[-1][1] += duration_secs
        from_cache = None
        if stage == "load":
            from_cache = getattr(self._thread, "from_cache", None)
            self._thread.from_cache = None
        program = _program_of(kwargs.get("fun_name", "?"))
        with self._lock:
            if stage == "load":
                self.compiles += 1
                self.compile_ms_hist.observe(duration_secs * 1000.0)
            if own is None:
                return
            span = self.spans[-1] if self.spans else OUTSIDE
            field = stage + "_s"
            self.pipeline_s[stage] += own
            for table, key in ((self.by_span, span), (self.by_program, program)):
                row = table.get(key)
                if row is None:
                    row = table[key] = self._row(table is self.by_program)
                row[field] += own
            if stage == "load":
                self.by_span[span]["programs"] += 1
                row = self.by_program[program]
                row["count"] += 1
                row["from_cache"] += bool(from_cache)
                self.recent.append((program, own, span, from_cache))
            if len(self.by_program) > 2 * PROGRAM_ROWS:
                # Bounded whatever a long-lived process compiles. Twice the
                # shown rows are kept, so that a new program's three events
                # meet in one row before it is judged.
                self.by_program = self._folded()

    def _open_intervals(self) -> list:
        try:
            return self._thread.intervals
        except AttributeError:
            self._thread.intervals = []
            return self._thread.intervals

    @staticmethod
    def _row(program: bool) -> Dict[str, float]:
        row: Dict[str, float] = dict.fromkeys(_STAGE_FIELDS, 0.0)
        row.update({"count": 0, "from_cache": 0} if program else {"programs": 0})
        return row

    @staticmethod
    def _seconds(row: Dict[str, float]) -> float:
        return sum(row[field] for field in _STAGE_FIELDS)

    def _folded(self) -> Dict[str, Dict[str, float]]:
        """``by_program`` as it is shown: the :data:`PROGRAM_ROWS` largest
        rows by seconds, and ``"other"`` with everybody else's sums."""
        ranked = sorted(
            (item for item in self.by_program.items() if item[0] != OTHER),
            key=lambda item: self._seconds(item[1]), reverse=True,
        )
        table = {name: dict(row) for name, row in ranked[:PROGRAM_ROWS]}
        rest = [row for _, row in ranked[PROGRAM_ROWS:]]
        if OTHER in self.by_program:
            rest.append(self.by_program[OTHER])
        if rest:
            other = table[OTHER] = self._row(True)
            for row in rest:
                for field, value in row.items():
                    other[field] += value
        return table

    # -- spans and stages --------------------------------------------------

    def push_span(self, name: str) -> int:
        """Open a program span (a dispatch phase, ``setup.<stage>``); returns
        what :meth:`pop_span` takes to close it and everything above it."""
        self.spans.append(name)
        self._span_marks.append((self.compiles, self.gc_s))
        return len(self.spans) - 1

    def pop_span(self, depth: int) -> Tuple[int, float]:
        """Close the span at ``depth`` and everything above it; returns the
        programs loaded and the seconds of garbage collection while it was
        open (the journal's evidence, free to have)."""
        compiles, gc_s = self._span_marks[depth]
        del self.spans[depth:]
        del self._span_marks[depth:]
        return self.compiles - compiles, self.gc_s - gc_s

    def on_gc(self, phase: str, _info: Dict[str, Any]) -> None:
        """The ``gc.callbacks`` hook: adds up the seconds of the collections
        that start while a span is open, and does nothing while none is."""
        if phase == "start":
            self._gc_t0 = time.perf_counter() if self.spans else 0.0
        elif self._gc_t0:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = 0.0

    def close_stage(self, stage: str, depth: int, wall_s: float) -> None:
        """A set-up stage block ends: its span goes (:meth:`pop_span`) and its
        wall seconds are added to its stage and, if no other stage is open
        around it, to ``"outermost"``."""
        self.pop_span(depth)
        with self._lock:
            outermost = not any(name.startswith(SETUP_PREFIX) for name in self.spans)
            for name in (stage, OUTERMOST) if outermost else (stage,):
                row = self.setup.setdefault(name, {"count": 0, "wall_s": 0.0})
                row["count"] += 1
                row["wall_s"] += wall_s

    # -- the dispatch journal ------------------------------------------------

    def new_driver(self) -> int:
        """A driver's id in the journal, from 1 in order of construction."""
        return next(self._driver_ids)

    def new_change(self) -> int:
        """The next membership change's id: from 1, one sequence for the
        process, so a change is named by its id alone."""
        return next(self._change_ids)

    def journal_phases(self, names) -> Dict[str, int]:
        """The phase vocabulary of the journal's rows, registered once by
        ``utils/dispatch.py``: returns name -> the number a row stores for it
        (``journal_snapshot()["phases"]`` maps it back)."""
        self.phases = tuple(names)
        return {name: i for i, name in enumerate(self.phases)}

    def record_dispatch(
        self, phase_id, driver, seq, change, t_start, t_wait, t_end,
        compiles, gc_s, fetched, rounds, cum_ms,
    ) -> None:
        """One closed ``_dispatch`` block: :data:`DISPATCH_RECORD`'s fields,
        in order."""
        ring = self.dispatches
        (phase_c, driver_c, seq_c, change_c, t_start_c, t_wait_c, t_end_c,
         compiles_c, gc_s_c, bytes_c, rounds_c, cum_ms_c) = ring.columns
        claimed = next(ring.claims)
        i = claimed % ring.capacity
        phase_c[i] = phase_id
        driver_c[i] = driver
        seq_c[i] = seq
        change_c[i] = change
        t_start_c[i] = t_start
        t_wait_c[i] = t_wait
        t_end_c[i] = t_end
        compiles_c[i] = compiles
        gc_s_c[i] = gc_s
        bytes_c[i] = fetched
        rounds_c[i] = rounds
        cum_ms_c[i] = cum_ms
        ring.written = claimed + 1

    def journal_snapshot(self) -> Dict[str, Any]:
        return {
            "phases": self.phases,
            "dispatches": self.dispatches.ordered(),
            "dispatches_written": self.dispatches.written,
            "changes": self.changes.ordered(),
            "changes_written": self.changes.written,
        }

    # -- reading -----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self.counters)
            out["compiles"] = self.compiles
            out["compile_ms"] = self.compile_ms_hist.summary()
            out["pipeline_s"] = dict(self.pipeline_s)
            out["by_span"] = {name: dict(row) for name, row in self.by_span.items()}
            out["by_program"] = self._folded()
            out["recent"] = [list(event) for event in self.recent]
        return out

    def setup_snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {name: dict(row) for name, row in self.setup.items()}


_COLLECTOR = _CompileCollector()
_INSTALL_LOCK = threading.Lock()
_installed: Optional[bool] = None  # None = never attempted


def install() -> bool:
    """Register the monitoring listeners once per process; True iff compile
    events are being captured (False on a JAX without ``jax.monitoring``).
    Idempotent — every ``VirtualCluster`` constructor calls it."""
    global _installed
    with _INSTALL_LOCK:
        if _installed is not None:
            return _installed
        try:
            from jax import monitoring
        except ImportError:
            logger.warning(
                "jax.monitoring unavailable: engine compile telemetry disabled"
            )
            _installed = False
            return False
        try:
            monitoring.register_event_listener(_COLLECTOR.on_event)
            monitoring.register_event_duration_secs_listener(
                _COLLECTOR.on_duration
            )
        except Exception as exc:  # noqa: BLE001 — a monitoring-API mismatch
            # must degrade to "no compile telemetry", never break engine
            # construction: the collector is strictly an observer.
            logger.warning("engine compile telemetry disabled: %r", exc)
            _installed = False
            return False
        try:
            monitoring.register_scalar_listener(_COLLECTOR.on_start)
        except Exception as exc:  # noqa: BLE001 — without the start scalars
            # nested intervals count twice; the totals still mean something.
            logger.warning("pipeline intervals will not nest: %r", exc)
        gc.callbacks.append(_COLLECTOR.on_gc)
        _installed = True
        return True


def compile_snapshot() -> Dict[str, Any]:
    """Monotonic process-wide compile/cache totals:
    ``{compiles, compile_ms: <histogram summary>, persistent_cache_hits,
    persistent_cache_misses, cache_requests}``, all zeros when capture is
    unavailable (callers need not care), and who took the pipeline's seconds
    (exact float sums; an event this jax does not record reads 0):

    - ``pipeline_s``: ``{trace, lower, load, cache_retrieval, cache_saved}``,
      process totals; ``load`` is compile-or-read-from-the-cache and holds
      ``cache_retrieval``;
    - ``by_span``: per innermost open program span at the moment of the event
      (a dispatch phase, ``setup.<stage>``, or ``"outside"``):
      ``{trace_s, lower_s, load_s, programs}``;
    - ``by_program``: per ``fun_name`` ``{trace_s, lower_s, load_s, count,
      from_cache}`` (loads, and how many of them the persistent cache
      served), the 32 largest by seconds and ``"other"`` for the rest;
    - ``recent``: the last 64 loads as ``[fun_name, seconds, span,
      from_cache]`` (``from_cache`` None where the cache was not asked).
    """
    return _COLLECTOR.snapshot()


def setup_snapshot() -> Dict[str, Dict[str, float]]:
    """Wall seconds inside the set-up stage blocks (``utils/dispatch.py::
    setup_stage``): ``{stage: {count, wall_s}}``. Stages nest, a child's
    seconds are inside its parent's; ``"outermost"`` adds up the blocks that
    had no stage open around them, so it counts every second once."""
    return _COLLECTOR.setup_snapshot()


def journal_snapshot() -> Dict[str, Any]:
    """The dispatch journal as plain arrays, oldest row first:

    - ``dispatches``: the newest :data:`JOURNAL_CAPACITY` closed ``_dispatch``
      blocks (:data:`DISPATCH_RECORD`), ``phases[row["phase"]]`` their names;
    - ``changes``: the newest closed membership changes
      (:data:`CHANGE_RECORD`); ``t_close - t_open`` is the time a change was
      pending, and that less the union of its dispatches' intervals the host
      time inside it that no phase covers;
    - ``dispatches_written`` / ``changes_written``: rows ever written, so a
      reader knows how many the rings have dropped.

    The arrays are copies; nothing is written to disk."""
    return _COLLECTOR.journal_snapshot()


#: The span stack, the stage sums and the journal's writers, for
#: ``utils/dispatch.py`` (the one module that opens spans): ``_dispatch`` and
#: ``setup_stage`` push and pop, ``DispatchSeam`` writes the journal.
push_span = _COLLECTOR.push_span
pop_span = _COLLECTOR.pop_span
close_stage = _COLLECTOR.close_stage
new_driver = _COLLECTOR.new_driver
new_change = _COLLECTOR.new_change
journal_phases = _COLLECTOR.journal_phases
record_dispatch = _COLLECTOR.record_dispatch
record_change = _COLLECTOR.changes.put


class CompileDelta:
    """Attribute process-global compile activity to one phase: snapshot on
    enter, diff on exit (``delta`` holds the scalar differences).

    Only correct when nothing else compiles concurrently — true for the
    bench (one workload per process) and the tests that use it.
    """

    def __init__(self) -> None:
        self.delta: Dict[str, int] = {}
        self._before: Dict[str, Any] = {}

    def __enter__(self) -> "CompileDelta":
        self._before = compile_snapshot()
        return self

    def __exit__(self, *_exc: Any) -> None:
        after = compile_snapshot()
        self.delta = {
            key: after[key] - self._before[key]
            for key in after
            if isinstance(after[key], int)
        }
        self.delta["compile_ms"] = round(
            float(after["compile_ms"]["sum"])
            - float(self._before["compile_ms"]["sum"]),
            3,
        )


def device_memory_snapshot() -> Dict[str, Any]:
    """Best-effort device memory view: live-buffer census via
    ``jax.live_arrays()`` plus the backend allocator's
    ``bytes_in_use``/``peak_bytes_in_use`` where the platform reports them
    (TPU does; CPU returns None). Missing probes yield ``None`` values, so
    the snapshot shape is stable across platforms."""
    out: Dict[str, Any] = {
        "live_buffers": None,
        "live_buffer_bytes": None,
        "device_bytes_in_use": None,
        "device_peak_bytes": None,
    }
    try:
        import jax

        arrays = jax.live_arrays()
        out["live_buffers"] = len(arrays)
        out["live_buffer_bytes"] = int(
            sum(getattr(a, "nbytes", 0) or 0 for a in arrays)
        )
    except Exception as exc:  # noqa: BLE001 — a backend that cannot
        # enumerate live arrays (or a deleted-buffer race mid-census) means
        # "no census this scrape", never a failed scrape.
        logger.debug("live-array census unavailable: %r", exc)
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
        if stats:
            if "bytes_in_use" in stats:
                out["device_bytes_in_use"] = int(stats["bytes_in_use"])
            if "peak_bytes_in_use" in stats:
                out["device_peak_bytes"] = int(stats["peak_bytes_in_use"])
    except Exception as exc:  # noqa: BLE001 — memory_stats is
        # platform-optional (None/absent on CPU and some plugins); the
        # gauges stay None rather than poisoning the snapshot.
        logger.debug("device memory_stats unavailable: %r", exc)
    return out


# ---------------------------------------------------------------------------
# Device telemetry plane: digest decode (models/virtual_cluster.py's
# telemetry_digest_impl packs the lanes into one int32 vector at host-sync
# boundaries; this is the host-side vocabulary for unpacking it)
# ---------------------------------------------------------------------------

#: Scalar layout of the telemetry digest vector, in order; the
#: TELEMETRY_BUCKETS rounds-undecided histogram buckets follow. Shared by
#: ``telemetry_digest_impl`` (producer) and :func:`activity_summary`
#: (consumer) so the two cannot skew silently.
TELEMETRY_DIGEST_FIELDS = (
    "rounds",
    "alerts",
    "active_sum",
    "active_peak",
    "invalidations",
    "proposals",
    "tally_sum",
    "decisions_fast",
    "decisions_classic",
    "conflict_rounds",
    "dissent",
    "invalidation_rounds",
    "invalidation_dense_rounds",
    "view_change_dense",
)


def activity_summary(digest: Any, n: int, c: int) -> Dict[str, Any]:
    """The ``engine.activity`` snapshot section from one fetched digest
    vector: the raw counters plus the derived rates clustertop/perfview/
    bench read — mean/peak active-subject fraction (of the [c, n] detector
    slots, per round), the fast-path decision share, and the conflict rate
    (rounds some cohort sat announced-but-undecided, per round). Pure host
    arithmetic on an already-fetched vector — never fetches."""
    from rapid_tpu.models.state import TELEMETRY_BUCKETS

    vec = [int(v) for v in digest]
    expected = len(TELEMETRY_DIGEST_FIELDS) + TELEMETRY_BUCKETS
    if len(vec) != expected:
        raise ValueError(
            f"telemetry digest carries {len(vec)} values, expected {expected}"
        )
    out: Dict[str, Any] = dict(zip(TELEMETRY_DIGEST_FIELDS, vec))
    out["rounds_undecided_hist"] = vec[len(TELEMETRY_DIGEST_FIELDS):]
    rounds = out["rounds"]
    slots = n * c
    decisions = out["decisions_fast"] + out["decisions_classic"]
    out["active_fraction"] = (
        out["active_sum"] / (rounds * slots) if rounds else 0.0
    )
    out["peak_active_fraction"] = (
        out["active_peak"] / rounds if rounds else 0.0
    )
    out["fast_path_share"] = (
        out["decisions_fast"] / decisions if decisions else 0.0
    )
    out["conflict_rate"] = out["conflict_rounds"] / rounds if rounds else 0.0
    out["winning_tally_mean"] = (
        out["tally_sum"] / decisions if decisions else 0.0
    )
    return out


def zero_activity_summary(n: int, c: int) -> Dict[str, Any]:
    """The all-zero activity section minted at driver attach: every series
    the plane will ever export exists from the first scrape (the exposition
    never mints a series mid-run)."""
    from rapid_tpu.models.state import TELEMETRY_BUCKETS

    return activity_summary(
        [0] * (len(TELEMETRY_DIGEST_FIELDS) + TELEMETRY_BUCKETS), n, c
    )


def aggregate_activity(summaries: Any, n: int, c: int) -> Dict[str, Any]:
    """Fleet-level rollup of per-tenant activity summaries: the counters and
    the histogram sum across tenants, the peak lanes take the tenant max
    (a peak summed across independent clusters is not a peak), and the
    derived rates are recomputed over the pooled totals."""
    summaries = list(summaries)
    if not summaries:
        return zero_activity_summary(n, c)
    hist = [
        sum(s["rounds_undecided_hist"][b] for s in summaries)
        for b in range(len(summaries[0]["rounds_undecided_hist"]))
    ]
    vec = [sum(s[f] for s in summaries) for f in TELEMETRY_DIGEST_FIELDS]
    out = activity_summary(vec + hist, n, c)
    out["active_peak"] = max(s["active_peak"] for s in summaries)
    out["peak_active_fraction"] = max(
        s["peak_active_fraction"] for s in summaries
    )
    return out


# ---------------------------------------------------------------------------
# Device round-trace ring: digest decode (models/virtual_cluster.py's
# trace_digest_impl packs the ring into one int32 vector at host-sync
# boundaries; this is the host-side vocabulary for unpacking it)
# ---------------------------------------------------------------------------

#: Per-round record fields, in the lane order ``trace_digest_impl`` packs
#: (after the two leading ``[cursor, wraps]`` scalars, one ``[R]`` lane per
#: field). Shared by producer and consumer so the two cannot skew silently —
#: the same contract :data:`TELEMETRY_DIGEST_FIELDS` carries for the plane.
TRACE_RECORD_FIELDS = (
    "round",
    "epoch",
    "active",
    "alerts",
    "proposals",
    "tally",
    "path",
    "conflict",
    "undecided",
)

#: Decision-path code vocabulary (the ``path`` record field): the engine's
#: analog of the host protocol's decided_path label.
TRACE_PATH_NAMES = {0: "none", 1: "fast", 2: "classic"}


def trace_summary(digest: Any, capacity: int) -> Dict[str, Any]:
    """The ``engine.trace`` snapshot section from one fetched trace digest:
    the decoded ring — ``records`` oldest -> newest, each a dict of
    :data:`TRACE_RECORD_FIELDS` plus the global round ordinal ``seq`` (the
    i-th round ever recorded) — and the derived scalars the exposition /
    clustertop / perfview surfaces read. Pure host arithmetic on an
    already-fetched vector — never fetches.

    Decode contract (tests/test_trace_ring.py pins it): the ring holds
    exactly the last ``min(capacity, cursor)`` rounds; when wrapped, the
    oldest record sits at slot ``cursor % capacity``; the decoded
    ``(epoch, round)`` stamps are strictly lexicographically increasing."""
    vec = [int(v) for v in digest]
    expected = 2 + len(TRACE_RECORD_FIELDS) * capacity
    if len(vec) != expected:
        raise ValueError(
            f"trace digest carries {len(vec)} values, expected {expected}"
        )
    cursor, wraps = vec[0], vec[1]
    lanes = {
        field: vec[2 + i * capacity : 2 + (i + 1) * capacity]
        for i, field in enumerate(TRACE_RECORD_FIELDS)
    }
    held = min(cursor, capacity)
    start = cursor % capacity if cursor >= capacity else 0
    records = []
    for i in range(held):
        slot = (start + i) % capacity
        rec = {field: lanes[field][slot] for field in TRACE_RECORD_FIELDS}
        rec["seq"] = cursor - held + i
        records.append(rec)
    last = records[-1] if records else dict.fromkeys(TRACE_RECORD_FIELDS, 0)
    return {
        "capacity": capacity,
        "rounds_recorded": cursor,
        "wraps": wraps,
        "rounds_held": held,
        "decisions_held": sum(1 for r in records if r["path"]),
        "conflicts_held": sum(r["conflict"] for r in records),
        "last_round": last["round"],
        "last_epoch": last["epoch"],
        "last_active": last["active"],
        "last_path": last["path"],
        "last_undecided": last["undecided"],
        "records": records,
    }


def zero_trace_summary(capacity: int) -> Dict[str, Any]:
    """The all-zero trace section minted at driver attach (empty ring, no
    records) — same never-mint-a-series-mid-run rule as
    :func:`zero_activity_summary`."""
    return trace_summary(
        [0] * (2 + len(TRACE_RECORD_FIELDS) * capacity), capacity
    )


def trace_recorder_snapshot(
    summary: Dict[str, Any],
    node: str = "(engine)",
    t0_ms: float = 0.0,
    ms_per_round: float = 1.0,
    config_id: Optional[int] = None,
) -> Dict[str, Any]:
    """A decoded ring rendered as a flight-recorder snapshot dict — the
    per-node artifact shape ``tools/traceview.py`` merges — so device rounds
    join the host and ``(chaos)`` lanes of one causally-ordered timeline.

    Device rounds carry no wall clock, so timestamps are synthesized on an
    injected :class:`~rapid_tpu.utils.clock.ManualClock`: record ``seq``
    lands at ``t0_ms + seq * ms_per_round`` (callers aligning against a host
    recording pick the scenario's round cadence). Every round emits one
    registered ``ENGINE_ROUND`` event; conflict rounds add
    ``ENGINE_CONFLICT`` and deciding rounds ``ENGINE_DECISION`` — ranked so
    they interleave correctly with host consensus events at equal stamps."""
    from rapid_tpu.utils.clock import ManualClock
    from rapid_tpu.utils.flight_recorder import EventName, FlightRecorder

    clock = ManualClock()
    records = summary["records"]
    recorder = FlightRecorder(
        node, clock, capacity=max(1, summary["capacity"] * 3)
    )
    for rec in records:
        target = t0_ms + rec["seq"] * ms_per_round
        clock.advance_ms(target - clock.now_ms())
        recorder.record(
            EventName.ENGINE_ROUND,
            config_id=config_id,
            seq=rec["seq"],
            round=rec["round"],
            epoch=rec["epoch"],
            active=rec["active"],
            alerts=rec["alerts"],
            proposals=rec["proposals"],
            undecided=rec["undecided"],
        )
        if rec["conflict"]:
            recorder.record(
                EventName.ENGINE_CONFLICT,
                config_id=config_id,
                seq=rec["seq"],
                epoch=rec["epoch"],
                undecided=rec["undecided"],
            )
        if rec["path"]:
            recorder.record(
                EventName.ENGINE_DECISION,
                config_id=config_id,
                seq=rec["seq"],
                epoch=rec["epoch"],
                path=TRACE_PATH_NAMES.get(rec["path"], str(rec["path"])),
                tally=rec["tally"],
            )
    snap = recorder.snapshot()
    # The ring already dropped rounds before the decode window; surface the
    # TRUE totals so "dropped" reads as rounds lost to wraparound, not as
    # recorder-local arithmetic over the survivors.
    snap["recorded_total"] = summary["rounds_recorded"]
    snap["dropped"] = summary["rounds_recorded"] - summary["rounds_held"]
    return snap


def first_divergent_round(
    a: Dict[str, Any], b: Dict[str, Any]
) -> Optional[int]:
    """The global round ordinal (``seq``) of the first record where two
    decoded rings disagree, or None when their overlapping windows agree
    record-for-record. Compares the overlap of the two held windows plus
    the cursor frontier — the chaos repro artifact's divergence instrument
    (a write-time ring vs a replay-time ring of the same schedule)."""
    by_seq_a = {r["seq"]: r for r in a["records"]}
    by_seq_b = {r["seq"]: r for r in b["records"]}
    shared = sorted(set(by_seq_a) & set(by_seq_b))
    for seq in shared:
        ra, rb = by_seq_a[seq], by_seq_b[seq]
        if any(ra[f] != rb[f] for f in TRACE_RECORD_FIELDS):
            return seq
    if a["rounds_recorded"] != b["rounds_recorded"]:
        # One run recorded more rounds than the other: the first round the
        # shorter run never executed is where the histories fork.
        return min(a["rounds_recorded"], b["rounds_recorded"])
    return None


def compiled_memory_analysis(compiled: Any) -> Optional[Dict[str, int]]:
    """The XLA ``memory_analysis()`` of one compiled executable as a plain
    dict (argument/output/temp/generated-code bytes) — the per-config
    memory-delta instrument. None when the backend does not expose it."""
    try:
        analysis = compiled.memory_analysis()
        return {
            "argument_bytes": int(analysis.argument_size_in_bytes),
            "output_bytes": int(analysis.output_size_in_bytes),
            "temp_bytes": int(analysis.temp_size_in_bytes),
            "generated_code_bytes": int(analysis.generated_code_size_in_bytes),
        }
    except Exception as exc:  # noqa: BLE001 — memory analysis is a bonus
        # diagnostic; any backend without it reports None, not a failure.
        logger.debug("memory_analysis unavailable: %r", exc)
        return None
