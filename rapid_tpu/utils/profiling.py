"""JAX profiler hooks (SURVEY §5.1: the rebuild adds first-class profiling).

Wraps ``jax.profiler`` so engine convergences and kernel passes can be traced
to TensorBoard-compatible traces without touching call sites:

    from rapid_tpu.utils.profiling import trace
    with trace("/tmp/rapid-trace"):
        vc.run_to_decision()

Inside a trace every driver operation is a ``rapid:<phase>`` host span
(``utils/dispatch.py``) and every device operation's op-name path carries the
engine scope it was traced under (``ENGINE_SCOPES``), so Perfetto or xprof
shows the host phases and the round's phases on one clock.

Hardened for production use (bench.py wires it in as the opt-in
``--profile`` stage):

- **Graceful no-op** on platforms/builds where ``jax.profiler`` is missing
  or ``start_trace`` fails (some plugin backends raise): the enclosed block
  still runs, a WARNING says no trace was captured, and nothing crashes —
  profiling must never be able to take down the run it observes.
- **No nesting**: ``jax.profiler.start_trace`` inside an active trace is a
  runtime error deep in XLA with an unhelpful message; this wrapper rejects
  it eagerly with a clear one. (Module-level flag: the profiler itself is a
  process-wide singleton, so a process-wide guard is the correct scope.)
"""

from __future__ import annotations

import logging
from contextlib import contextmanager

logger = logging.getLogger(__name__)

#: True while a ``trace()`` block is active in this process (the underlying
#: profiler is process-global, so the guard is too).
_active = False


def profiler_available() -> bool:
    """True iff this JAX build exposes a usable ``jax.profiler``."""
    try:
        import jax

        return hasattr(jax, "profiler") and hasattr(jax.profiler, "start_trace")
    except ImportError:
        return False


@contextmanager
def trace(log_dir: str):
    """Capture a device+host profile of the enclosed block into ``log_dir``
    (view with TensorBoard or Perfetto). No-ops with a WARNING when the
    profiler is unavailable or fails to start; raises ``RuntimeError`` when
    called inside an active ``trace()`` block (the profiler cannot nest)."""
    global _active
    if _active:
        raise RuntimeError(
            "profiling.trace() does not nest: a trace is already active in "
            "this process — close it before starting another"
        )
    started = False
    _active = True
    try:
        if profiler_available():
            import jax

            try:
                jax.profiler.start_trace(log_dir)
                started = True
            except Exception as exc:  # noqa: BLE001 — profiling is an
                # opt-in diagnostic: a backend that cannot start a trace
                # (plugin without profiler support, busy session) must not
                # fail the profiled workload.
                logger.warning(
                    "jax.profiler.start_trace(%r) failed (%r); "
                    "running unprofiled", log_dir, exc,
                )
        else:
            logger.warning(
                "jax.profiler unavailable on this platform; running unprofiled"
            )
        yield
    finally:
        _active = False
        if started:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception as exc:  # noqa: BLE001 — a failed stop leaves
                # no trace file but the profiled block already ran; log,
                # don't mask the block's own outcome.
                logger.warning("jax.profiler.stop_trace() failed: %r", exc)


def annotate(name: str, **tags):
    """Named host span on the profiler's clock: THE place this package
    makes a ``jax.profiler.TraceAnnotation`` (``DispatchSeam._dispatch``
    opens its ``rapid:<phase>`` spans through here). ``tags`` become the
    span's arguments in the trace (``seq=12``, ``wave=3``; a tag that is
    None is left out). With no trace running the span costs a flag test;
    jax 0.9 is the one installation (PR 21), so there is no probe and no
    fallback."""
    import jax

    return jax.profiler.TraceAnnotation(
        name, **{key: value for key, value in tags.items() if value is not None}
    )
