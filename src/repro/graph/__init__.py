"""repro.graph — device-resident batched encrypted graph index
(DESIGN.md §15).

`csr` holds the fixed-degree CSR mirror of the owner-built HNSW
(bit-identical `.ppcol` round-trip with `core.hnsw`); `traverse` the
jitted lockstep walk (upper-layer greedy descent + layer-0 beam
search, perf and oblivious variants); `filter` the
`SecureSearchEngine` backend.
"""

from . import traverse  # noqa: F401  (before filter: import-cycle order)
from .csr import CSRGraph
from .filter import GraphFilter
from .traverse import beam_plan, graph_topk

__all__ = ["CSRGraph", "GraphFilter", "beam_plan", "graph_topk",
           "traverse"]
