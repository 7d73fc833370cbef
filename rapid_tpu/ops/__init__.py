from rapid_tpu.ops.consensus import TallyResult, tally_candidates, tally_sorted
from rapid_tpu.ops.cut_detection import (
    CutResult,
    CutState,
    alerts_to_report_matrix,
    process_alert_batch,
)
from rapid_tpu.ops.hashing import lex_argsort, masked_set_hash, mix32
from rapid_tpu.ops.rings import (
    RingTopology,
    endpoint_ring_keys,
    predecessor_of_keys,
    ring_liveness,
    ring_perms,
    ring_positions,
    ring_tables_after_cut,
    ring_topology,
    ring_topology_from_perm,
)

__all__ = [
    "TallyResult",
    "tally_candidates",
    "tally_sorted",
    "CutResult",
    "CutState",
    "alerts_to_report_matrix",
    "process_alert_batch",
    "lex_argsort",
    "masked_set_hash",
    "mix32",
    "RingTopology",
    "endpoint_ring_keys",
    "predecessor_of_keys",
    "ring_liveness",
    "ring_perms",
    "ring_positions",
    "ring_tables_after_cut",
    "ring_topology",
    "ring_topology_from_perm",
]
