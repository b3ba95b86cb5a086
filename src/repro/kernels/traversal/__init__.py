"""Device traversal (device-resident GCDI): CSR row-gather + neighbor
expansion + predicate evaluation + compaction per hop, chained into one
program per pattern, with a batched multi-query variant. Layout per the
family convention: traversal.py (the Pallas hop kernel, interpret-mode
only until its gathers are redesigned), ops.py (whole-chain drivers),
ref.py (the XLA hop the drivers run)."""
from .ops import COUNTERS, batched_traverse, traverse_chain
from .ref import batched_hop_ref, fused_hop_ref
from .traversal import batched_hop, fused_hop

__all__ = [
    "fused_hop", "batched_hop", "traverse_chain", "batched_traverse",
    "fused_hop_ref", "batched_hop_ref", "COUNTERS",
]
