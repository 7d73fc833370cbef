"""utils/profiling hardening: graceful no-op where jax.profiler is missing
or refuses to start, eager rejection of nested trace() blocks, and annotate
as the one maker of trace annotations (no probe, no fallback; free while no
trace runs). (The happy path — a real trace landing on disk around a real
convergence — is covered by tests/test_pallas_kernels.py; the program's own
spans in a real trace by tests/test_spans.py.)
"""

import logging

import pytest

from rapid_tpu.utils import profiling


@pytest.mark.slow
def test_nested_trace_is_rejected_eagerly(tmp_path):
    # Rides the unfiltered check.sh pass (~16 s wall: three REAL
    # jax.profiler trace starts). Tier-1 representative of the guard:
    # test_guard_resets_when_block_raises (one trace start, same
    # already-active latch).
    with profiling.trace(str(tmp_path / "outer")):
        with pytest.raises(RuntimeError, match="does not nest"):
            with profiling.trace(str(tmp_path / "inner")):
                pass  # pragma: no cover — must not be reached
    # The guard resets after exit: a fresh trace works again.
    with profiling.trace(str(tmp_path / "again")):
        pass


def test_guard_resets_when_block_raises(tmp_path):
    with pytest.raises(ValueError, match="inner failure"):
        with profiling.trace(str(tmp_path / "t")):
            raise ValueError("inner failure")
    with profiling.trace(str(tmp_path / "t2")):
        pass  # not "already active"


def test_noop_when_profiler_unavailable(tmp_path, monkeypatch, caplog):
    monkeypatch.setattr(profiling, "profiler_available", lambda: False)
    ran = []
    with caplog.at_level(logging.WARNING, logger="rapid_tpu.utils.profiling"):
        with profiling.trace(str(tmp_path)):
            ran.append(True)
    assert ran  # the block still executed
    assert any("unavailable" in r.message for r in caplog.records)
    # annotate has no probe of its own: it makes a real TraceAnnotation
    # whatever trace() decided, and with no trace running the span is free.
    import jax

    span = profiling.annotate("phase", seq=1)
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:
        ran.append(True)
    assert len(ran) == 2


def test_noop_when_start_trace_raises(tmp_path, monkeypatch, caplog):
    import jax

    def boom(log_dir):
        raise RuntimeError("backend has no profiler")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    stopped = []
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stopped.append(True))
    ran = []
    with caplog.at_level(logging.WARNING, logger="rapid_tpu.utils.profiling"):
        with profiling.trace(str(tmp_path)):
            ran.append(True)
    assert ran
    assert any("running unprofiled" in r.message for r in caplog.records)
    assert not stopped  # never started -> never stopped


def test_failed_stop_does_not_mask_block_result(tmp_path, monkeypatch, caplog):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda log_dir: None)

    def bad_stop():
        raise RuntimeError("flush failed")

    monkeypatch.setattr(jax.profiler, "stop_trace", bad_stop)
    with caplog.at_level(logging.WARNING, logger="rapid_tpu.utils.profiling"):
        with profiling.trace(str(tmp_path)):
            pass  # block succeeds; the failed stop must not raise
    assert any("stop_trace" in r.message for r in caplog.records)


def test_profiler_available_reports_bool():
    assert isinstance(profiling.profiler_available(), bool)
