"""GraphFilter — the batched device-resident HNSW filter backend
(DESIGN.md §15).

The drop-in successor of `HNSWGraphFilter`: same owner-built HNSW over
DCPE ciphertexts, but traversal runs as ONE jitted lockstep walk over
the CSR mirror for the whole query batch instead of a Python loop of
per-query host walks.  That buys the graph index everything the other
backends already had:

  * batching — beams expand for all queries per hop (`graph.traverse`);
  * quantization — edges scored with the ADC int8/pq8 surrogates of
    `core.adc` (codebook trained keylessly at attach, exactly like
    `ADCFilter`), with the same oversample-then-exact-refine contract;
  * a `hardened` tier — `oblivious=True` runs the bounded-hop,
    fixed-fanout variant (constant hop/edge counts; sec.leakage
    measures the residual address pattern via `last_scan_trace`);
  * zero steady-state recompiles — every shape is a bucket (row
    capacity R, beam capacity ef_cap, padded layer count LU), `ef`
    and validity are data.

The host walk stays as the parity oracle: ids are recall-identical at
fixed ef (tests/test_graph.py pins it), per the equivalence argument
in `graph.traverse`.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core import adc
from ..core.hnsw import HNSW
from ..obs.trace import to_host
from .csr import CSRGraph
from .traverse import beam_plan, graph_topk

__all__ = ["GraphFilter"]


class GraphFilter:
    """Batched CSR traversal filter backend for `SecureSearchEngine`.

    index: the owner-built `core.hnsw.HNSW` (over DCPE ciphertexts).
    quantization: None (exact f32 ciphertext distances) | "int8" |
    "pq8" (ADC surrogate edge scoring + candidate oversampling).
    oblivious: bounded-hop fixed-fanout traversal (the `hardened`
    profile's tier); returned ids are bit-identical to the perf
    variant (the latched-freeze contract in `graph.traverse`).
    """

    def __init__(self, index: HNSW, *, quantization: str | None = None,
                 refine_ratio: float | None = None, pq_m: int = 16,
                 oblivious: bool = False, seed: int = 0):
        if quantization not in (None, "int8", "pq8"):
            raise ValueError(f"GraphFilter quantization must be "
                             f"None|int8|pq8, got {quantization!r}")
        self.index = index
        self.quantization = quantization
        self.quant = quantization or "f32"
        self.name = ("graph" if quantization is None
                     else f"adc-graph-{quantization}")
        self.refine_ratio = (
            float(refine_ratio) if refine_ratio is not None
            else adc.default_refine_ratio(quantization)
            if quantization is not None else 1.0)
        self.pq_m = pq_m
        self.oblivious = oblivious
        self.seed = seed
        self.codebook = None
        self.csr: CSRGraph | None = None
        self._neigh0 = self._neigh_up = self._ok = None
        self._db = None
        self._row_bytes = 0
        self.last_filter_bytes = 0
        self.last_n_hops = 0
        self.last_n_edges_scanned = 0
        self.last_scan_trace: np.ndarray | None = None

    # --------------------------------------------------------------- setup

    def oversampled(self, kp: int) -> int:
        return max(kp, int(np.ceil(kp * self.refine_ratio)))

    def attach(self, C_sap: np.ndarray, engine=None):
        self.csr = CSRGraph.from_hnsw(self.index)
        g = self.csr
        self._neigh0 = jnp.asarray(g.neigh0)
        self._neigh_up = jnp.asarray(g.neigh_up)
        self._ok = jnp.asarray(g.levels >= 0)
        d = g.d
        if self.quantization is None:
            # g.X carries +inf for deleted rows; `ok` masks them, and
            # scores are computed in diff form so padded zeros are inert
            X = np.where(np.isfinite(g.X), g.X, 0.0).astype(np.float32)
            self._db = (jnp.asarray(X),)
            self._row_bytes = d * 4
            return
        rows = np.where(np.isfinite(g.X[: g.n]), g.X[: g.n], 0.0)
        rows = rows.astype(np.float32)
        self.codebook = adc.train_codebook(
            rows, self.quantization, m=self.pq_m, seed=self.seed)
        if self.quantization == "int8":
            codes, cn = self.codebook.encode(rows)
            c8 = np.zeros((g.R, d), np.int8)
            c8[: g.n] = codes
            cnp = np.zeros(g.R, np.int32)
            cnp[: g.n] = cn
            self._db = (jnp.asarray(c8), jnp.asarray(cnp))
        else:
            codes = self.codebook.encode(rows)          # (n, m) uint8
            ct = np.zeros((codes.shape[1], g.R), np.uint8)
            ct[:, : g.n] = codes.T
            self._db = (jnp.asarray(ct),)
        self._row_bytes = self.codebook.code_bytes_per_vector()

    # ---------------------------------------------------------- candidates

    def _query_operand(self, Q: np.ndarray):
        if self.quantization is None:
            return jnp.asarray(Q)
        if self.quantization == "int8":
            return jnp.asarray(self.codebook.encode_query(Q))
        return jnp.asarray(self.codebook.lut(Q))

    def candidates(self, Q_sap: np.ndarray, kp: int, ef_search: int):
        Q = np.asarray(Q_sap, np.float32)
        nq = Q.shape[0]
        g = self.csr
        kp2 = max(1, min(self.oversampled(kp), max(g.n, 1)))
        ef_eff, ef_cap, max_hops = beam_plan(kp2, max(ef_search, kp2))
        cand, _, visited, hops, edges = graph_topk(
            self._neigh0, self._neigh_up, self._ok, self._db,
            self._query_operand(Q), jnp.int32(g.entry),
            jnp.int32(ef_eff), kp=kp2, ef_cap=ef_cap,
            max_hops=max_hops, quant=self.quant,
            oblivious=self.oblivious)
        cand = to_host(cand, "candidates", np.int32)
        valid = cand >= 0
        cand = np.where(valid, cand, 0)
        n_edges = int(to_host(edges, "edges").sum())
        self.last_n_hops = int(to_host(hops, "hops").sum())
        self.last_n_edges_scanned = n_edges
        # every scored edge reads one row (f32) or one code row (ADC),
        # plus the entry-point read per query
        self.last_filter_bytes = (n_edges + nq) * self._row_bytes
        self.last_scan_trace = to_host(visited, "visited")
        return cand, valid, n_edges + nq
