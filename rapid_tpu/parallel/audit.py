"""Compiled-HLO collective audit — thin re-export.

The classifier that lived here (collective-kind matching, payload
accounting, the hot-loop/cond/prologue location attribution) grew into
``rapid_tpu.parallel.hlo_facts`` when the ``device_program`` analyzer
family (tools/analysis/device_program.py) started reading its facts. This
module stays as the compatible
import surface for the existing consumers (``tests/test_parallel.py``,
``tools/collective_audit.py``): same names, one definition, and a plain
package-relative import — no path games, so an installed distribution of
``rapid_tpu`` keeps working without the repo checkout.
"""

from __future__ import annotations

from rapid_tpu.parallel.hlo_facts import (  # noqa: F401 — re-exported
    COLLECTIVE_KINDS,
    DTYPE_BITS,
    audit_collectives,
    classify_location,
    collective_violations,
    payload_class,
    shape_bytes,
    source_of,
)

__all__ = [
    "COLLECTIVE_KINDS",
    "DTYPE_BITS",
    "audit_collectives",
    "classify_location",
    "collective_violations",
    "payload_class",
    "shape_bytes",
    "source_of",
]
