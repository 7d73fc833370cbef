"""Tools-side shim over :mod:`rapid_tpu.parallel.hlo_facts`.

The classifier's canonical home is inside the packaged library (stdlib
only, importable from an installed wheel); the analysis package consumes
it from there so the dependency points tools -> library, never the
reverse. This shim resolves the repo root the way the rest of the
analysis driver does (``core.REPO``, inserted at the FRONT so a foreign
top-level ``rapid_tpu`` can never shadow this repo's) and re-exports the
surface under the name the family modules import.
"""

from __future__ import annotations

import sys

from . import core

_REPO = str(core.REPO)
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from rapid_tpu.parallel.hlo_facts import (  # noqa: E402,F401 — re-exported
    COLLECTIVE_KINDS,
    DTYPE_BITS,
    PAYLOAD_CLASS_RANK,
    TRANSFER_OPS,
    audit_collectives,
    classify_location,
    collective_groups,
    collective_violations,
    count_transfer_ops,
    entry_parameter_bytes,
    groups_cross_blocks,
    input_output_aliases,
    payload_class,
    round_loop,
    shape_bytes,
    source_of,
)

__all__ = [
    "COLLECTIVE_KINDS",
    "DTYPE_BITS",
    "PAYLOAD_CLASS_RANK",
    "TRANSFER_OPS",
    "audit_collectives",
    "classify_location",
    "collective_violations",
    "collective_groups",
    "count_transfer_ops",
    "entry_parameter_bytes",
    "groups_cross_blocks",
    "input_output_aliases",
    "payload_class",
    "round_loop",
    "shape_bytes",
    "source_of",
]
