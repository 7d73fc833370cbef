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
"""

from __future__ import annotations

import time
from contextlib import contextmanager

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
})


class DispatchSeam:
    """Mixin: transfer-byte accounting + the phase-validated dispatch timer.

    Hosts must provide ``self.metrics`` (a :class:`rapid_tpu.utils.metrics.
    Metrics` registry); everything here writes through it.
    """

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

    @contextmanager
    def _dispatch(self, entry: str):
        """Time one device dispatch (and any fetch the caller performs
        inside the block) into the bounded per-phase latency histogram
        (``engine_dispatch_ms{phase=<entry>}``) and bump the dispatch
        counter — the engine's per-dispatch observability grain. ``entry``
        must come from :data:`ENGINE_DISPATCH_PHASES`; a typo fails here,
        at write time, instead of silently forking the series set."""
        if entry not in ENGINE_DISPATCH_PHASES:
            raise ValueError(
                f"unregistered engine dispatch phase {entry!r}; add it to "
                f"rapid_tpu.utils.dispatch.ENGINE_DISPATCH_PHASES"
            )
        self.metrics.inc("engine_dispatches")
        start = time.perf_counter()
        try:
            yield
        finally:
            self.metrics.record_ms(
                "engine_dispatch",
                (time.perf_counter() - start) * 1000.0,
                phase=entry,
            )
