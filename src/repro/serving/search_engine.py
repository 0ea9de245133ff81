"""Unified batched secure filter-and-refine engine (DESIGN.md §2).

This is the single search path behind every entry point in the repo:

  filter:  a pluggable backend produces k' candidate ids per query —
             * FlatScanFilter  — exhaustive scan via the l2_topk Pallas
               kernel (chunked MXU tiles, no (nq, n) matrix in HBM);
             * IVFScanFilter   — partition-pruned scan: host-side coarse
               probe over DCPE ciphertext centroids, then one jitted
               masked gather+scan over the probed rows;
             * HNSWGraphFilter — host-side graph traversal (pointer
               chasing stays on CPU, DESIGN.md §3).
  refine:  one jitted batched DCE tournament over the candidate sets,
           routed through the dce_comp Pallas kernel
           (`batched_top_k_by_wins`) — no per-query Python loop.

`SecureSearchEngine.search` is a thin batch-of-one wrapper over
`search_batch`, so the per-query path (`core.ppanns.Server.search`) and
the batched path provably return identical ids for every backend.  All
backends report the same `SearchStats` (latency, distance evaluations,
DCE comparisons, bytes up/down).

Privacy envelope: every backend sees only DCPE filter ciphertexts and
DCE refine ciphertexts / trapdoors — the engine never touches plaintexts
or true distances, only ciphertext distances and comparison signs (the
leakage proven in the paper, §VI).
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..core import adc, secure_knn
from ..core.hnsw import HNSW
from ..core.ivf import IVFIndex
from ..kernels.adc_topk import ops as adc_ops
from ..kernels.common import HIGHEST, next_bucket
from ..kernels.dce_comp import ops as dce_ops
from ..kernels.l2_topk import ops as l2_ops
from ..obs.trace import child_span, to_host

__all__ = ["SearchStats", "SecureSearchEngine", "FlatScanFilter",
           "IVFScanFilter", "HNSWGraphFilter", "ADCFilter",
           "refine_candidates", "layout_pools", "scan_ivf_pools",
           "pool_membership", "scan_ivf_oblivious",
           "traverse_graph_candidates"]


@dataclasses.dataclass
class SearchStats:
    """Uniform per-call search accounting (single query or batch).

    Communication model (paper §V-C): user -> server is the DCPE query
    ciphertext + DCE trapdoor + k (4 bytes); server -> user is the
    serialized id matrix — int64 ids, so 8 bytes per returned slot.
    """
    latency_s: float
    filter_dist_evals: int      # ciphertext distance evaluations (filter)
    refine_comparisons: int     # DCE DistanceComp sign evaluations (refine)
    bytes_up: int
    bytes_down: int
    n_queries: int = 1
    backend: str = ""
    # true bytes the filter touched this call: full-precision rows for
    # the f32 backends, codes (+ norms / LUT centroids) for quantized
    # ADC backends — the direct observable of the bandwidth win
    # (DESIGN.md §11).  0 for an empty collection.
    filter_bytes_scanned: int = 0
    # dummy padding rows injected by the scheduler under padding
    # security profiles (repro.sec, DESIGN.md §14).  Dummies ride the
    # engine call but never a user-visible future, and the telemetry
    # QPS/occupancy accounting excludes them.  Additive wire field:
    # results serialized before it decode with 0.
    n_dummy_queries: int = 0
    # graph-backend traversal accounting (repro.graph, DESIGN.md §15):
    # total beam/greedy hops and edges scored across the batch.  0 for
    # scan backends; additive wire fields — old payloads decode with 0.
    n_hops: int = 0
    n_edges_scanned: int = 0
    # failover accounting (repro.resilience, DESIGN.md §16): how many
    # shard GROUPS had no live replica when this call was served, and
    # whether the answer is therefore partial (`degraded=True` ⇒ ids
    # cover only alive shards' rows).  Additive wire fields — payloads
    # from before replication decode as healthy.
    n_shards_down: int = 0
    degraded: bool = False


# ---------------------------------------------------------------------------
# Batched refine — the one refine path every entry point routes through.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "use_kernel"))
def refine_candidates(C_dce, cand, T, valid, k: int, use_kernel: bool = True):
    """Exact DCE tournament refine of per-query candidate sets, batched.

    C_dce: (n, 4, D) refine ciphertexts; cand: (nq, kp) candidate ids;
    T: (nq, D) trapdoors; valid: (nq, kp) bool or None (padded-slot mask)
    -> (nq, k) ids, ascending true distance; -1 marks slots where a query
    had fewer than k real candidates (never a fabricated id).
    use_kernel=False swaps the Pallas Z-matrix for the einsum oracle (the
    GSPMD-safe formulation for mesh-sharded C_dce, see serving.ann_server).
    """
    Cc = jnp.take(C_dce, cand, axis=0)                  # (nq, kp, 4, D)
    local = dce_ops.batched_top_k_by_wins(
        Cc, T, k, valid=valid, use_kernel=use_kernel)   # (nq, k)
    local = local.astype(cand.dtype)
    ids = jnp.take_along_axis(cand, local, axis=1)
    if valid is None:
        return ids
    vsel = jnp.take_along_axis(valid, local, axis=1)
    return jnp.where(vsel, ids, -1)


# Most bytes of gathered probe rows one step of a pool scan holds.  At
# 1M rows a probe pool buckets to L = 2^19, so a 64-query batch would
# gather 16 GiB at once — more than a v5e chip's HBM.
POOL_GATHER_BYTES = 1 << 30


def pool_dists(C, Q, idx, mask):
    """(nq, L) ciphertext distances ||q||^2 - 2 q.x + ||x||^2 from each
    query to its gathered rows C[idx] (+inf where ~mask) — the l2_topk
    restructuring over a per-query gather, since each query probes
    different partitions.  Runs a few queries per step so the (step, L,
    d) gather stays under POOL_GATHER_BYTES whatever the batch; every
    pool scan (single-device and sharded) computes through here, so
    their floats agree element for element."""
    nq, L = idx.shape
    step = max(1, min(nq, POOL_GATHER_BYTES
                      // (L * C.shape[1] * C.dtype.itemsize)))

    def one(args):
        q, i, m = args
        rows = jnp.take(C, i, axis=0)                   # (L, d)
        xn = (rows * rows).sum(-1)
        cross = jnp.einsum("ld,d->l", rows, q, precision=HIGHEST)
        return jnp.where(m, (q * q).sum() - 2.0 * cross + xn, jnp.inf)

    return jax.lax.map(one, (Q, idx, mask), batch_size=step)


@functools.partial(jax.jit, static_argnames=("kp",))
def _masked_pruned_scan(C_sap, Q, cand, valid, kp: int):
    """IVF filter inner loop: ciphertext distances over probed rows only
    (`pool_dists`), masked at invalid slots.  Returns (ids, valid) of the
    per-query top-kp."""
    d = pool_dists(C_sap, Q, cand, valid)
    kp = min(kp, d.shape[1])
    _, pos = jax.lax.top_k(-d, kp)
    return (jnp.take_along_axis(cand, pos, axis=1),
            jnp.take_along_axis(valid, pos, axis=1))


# ---------------------------------------------------------------------------
# Filter backends.  Each returns (cand (nq, kp') int32, valid (nq, kp') bool,
# n_dist_evals) given a batch of DCPE-encrypted queries.
#
# The two shared scan/traversal bodies below are used both by the static
# backends here and by the runtime's mutable DeltaAwareBackend
# (serving/runtime/ingest.py) — one copy, so bucketing rules and eval
# accounting cannot diverge between the frozen and the mutating paths.
# ---------------------------------------------------------------------------


def layout_pools(nq: int, pools, kp: int, pool_mask=None):
    """Pad ragged probe pools to a 128-bucketed (nq, L) rectangle.

    Shared by the single-device masked scan and the sharded pool scan
    (serving/sharded.py) — one layout, so candidate order (and with it
    exact id parity across placements) cannot drift.  The power-of-two
    bucket on L matters: probe-pool sizes vary per batch and grow with
    ingestion, so a finer rounding would recompile the jitted scans at
    every boundary crossing — pow2 bounds the distinct widths to
    O(log n).  pool_mask(p) -> bool mask lets a caller pre-invalidate
    pool entries (e.g. tombstoned rows)."""
    L = next_bucket(max(kp, max((p.size for p in pools), default=1), 1),
                    minimum=128)
    cand = np.zeros((nq, L), np.int32)
    valid = np.zeros((nq, L), bool)
    for qi, p in enumerate(pools):                      # id layout only
        cand[qi, : p.size] = p
        valid[qi, : p.size] = True if pool_mask is None else pool_mask(p)
    return cand, valid


def scan_ivf_pools(C_dev, Q_sap: np.ndarray, pools, kp: int,
                   pool_mask=None):
    """Lay out the probe pools and run the jitted masked scan over
    C_dev.  Returns (ids (nq, kp), valid (nq, kp))."""
    nq = Q_sap.shape[0]
    cand, valid = layout_pools(nq, pools, kp, pool_mask)
    ids, vout = _masked_pruned_scan(
        C_dev, jnp.asarray(np.asarray(Q_sap, np.float32)),
        jnp.asarray(cand), jnp.asarray(valid), kp)
    return to_host(ids, "candidates"), to_host(vout, "valid")


@functools.partial(jax.jit, static_argnames=("kp",))
def _masked_full_scan(C_all, Q, member, kp: int):
    """Scan-oblivious IVF filter inner loop (DESIGN.md §14): ciphertext
    distances over EVERY resident row, masked afterwards by per-query
    pool membership.

    The access pattern is a constant — one (nq, bucket) matmul whose
    shape depends only on the row bucket, no data-dependent gather — so
    which rows a query's probes selected is not observable from the
    scan.  The distances themselves are the same ||q||^2 - 2 q.x +
    ||x||^2 values the pruned scan computes for member rows, so the
    surviving candidate set matches `_masked_pruned_scan` and the exact
    DCE refine returns identical ids (the cross-profile parity tests).
    Returns (ids, valid) of the per-query top-kp over member rows.
    """
    qn = (Q * Q).sum(-1)[:, None]
    xn = (C_all * C_all).sum(-1)[None, :]
    d = qn - 2.0 * jnp.matmul(Q, C_all.T, precision=HIGHEST) + xn
    d = jnp.where(member, d, jnp.inf)
    kp = min(kp, d.shape[1])
    _, pos = jax.lax.top_k(-d, kp)
    return (pos.astype(jnp.int32),
            jnp.take_along_axis(member, pos, axis=1))


def pool_membership(nq: int, pools, bucket: int, pool_mask=None):
    """(nq, bucket) bool membership mask for the oblivious scans:
    member[qi, r] iff row r is in query qi's probe pool (and passes
    pool_mask, e.g. tombstone filtering).  Host-side layout only — the
    device never sees the ragged pools."""
    member = np.zeros((nq, bucket), bool)
    for qi, p in enumerate(pools):
        member[qi, p] = True if pool_mask is None else pool_mask(p)
    return member


def scan_ivf_oblivious(C_dev, Q_sap: np.ndarray, pools, kp: int,
                       pool_mask=None):
    """Oblivious twin of `scan_ivf_pools`: full-bucket masked scan over
    the resident scan array.  Returns (ids (nq, kp), valid (nq, kp))."""
    nq = Q_sap.shape[0]
    member = pool_membership(nq, pools, int(C_dev.shape[0]), pool_mask)
    ids, vout = _masked_full_scan(
        C_dev, jnp.asarray(np.asarray(Q_sap, np.float32)),
        jnp.asarray(member), kp)
    return to_host(ids, "candidates"), to_host(vout, "valid")


def traverse_graph_candidates(index: HNSW, Q_sap: np.ndarray, kp: int,
                              ef_search: int):
    """Per-query host-side HNSW traversal (pointer chasing stays on CPU,
    DESIGN.md §3), padded to an (nq, kp) rectangle.
    Returns (cand, valid, n_dist_evals).

    Deprecated as a serving path: `repro.graph.GraphFilter` runs the
    same walk batched over the whole query set (recall-identical at
    fixed ef — the parity suite in tests/test_graph.py).  This loop is
    kept as the parity oracle."""
    warnings.warn(
        "the per-query host HNSW walk is deprecated as a serving path; "
        "use repro.graph.GraphFilter (batched, recall-identical at "
        "fixed ef) — the host walk remains as the parity oracle",
        DeprecationWarning, stacklevel=2)
    nq = Q_sap.shape[0]
    evals0 = index.n_dist_evals
    cand = np.zeros((nq, kp), np.int32)
    valid = np.zeros((nq, kp), bool)
    for qi in range(nq):
        ids, _ = index.search(np.asarray(Q_sap[qi]), kp,
                              ef=max(ef_search, kp))
        cand[qi, : ids.size] = ids
        valid[qi, : ids.size] = True
    return cand, valid, index.n_dist_evals - evals0

class FlatScanFilter:
    """Exhaustive Pallas l2_topk scan over all DCPE ciphertexts."""

    name = "flat"

    def __init__(self, use_kernel: bool = True, chunk: int = 4096):
        self.use_kernel = use_kernel
        self.chunk = chunk
        self._C = None
        self.last_filter_bytes = 0

    def attach(self, C_sap: np.ndarray, engine: "SecureSearchEngine"):
        self._C = jnp.asarray(C_sap)

    def candidates(self, Q_sap: np.ndarray, kp: int, ef_search: int):
        n = self._C.shape[0]
        _, idx = l2_ops.knn(jnp.asarray(Q_sap, jnp.float32), self._C,
                            min(kp, n), chunk=min(self.chunk, n),
                            use_kernel=self.use_kernel)
        cand = to_host(idx, "candidates", np.int32)
        valid = np.ones(cand.shape, bool)
        self.last_filter_bytes = int(self._C.size) * 4
        return cand, valid, Q_sap.shape[0] * n


class IVFScanFilter:
    """Partition-pruned scan: coarse k-means probe + jitted masked scan.

    The coarse quantizer is built over DCPE ciphertexts — the same privacy
    envelope as the HNSW graph (centroids are functions of ciphertexts
    only).  Probing is host-side (`IVFIndex.probe`, tiny: nq x
    n_clusters); the per-row distance work rides the MXU path in
    `_masked_pruned_scan`.
    """

    name = "ivf"

    def __init__(self, n_partitions: int = 64, nprobe: int = 8,
                 seed: int = 0):
        self.n_partitions = n_partitions
        self.nprobe = nprobe
        self.seed = seed
        self.ivf: IVFIndex | None = None
        self._C = None
        self.last_filter_bytes = 0

    def attach(self, C_sap: np.ndarray, engine: "SecureSearchEngine"):
        self._C = jnp.asarray(C_sap)
        self.ivf = IVFIndex(n_clusters=min(self.n_partitions,
                                           C_sap.shape[0]),
                            seed=self.seed).build(C_sap)

    def candidates(self, Q_sap: np.ndarray, kp: int, ef_search: int):
        Q = np.asarray(Q_sap, np.float32)
        nq = Q.shape[0]
        pools = [self.ivf.probe(q, self.nprobe) for q in Q]
        ids, vout = scan_ivf_pools(self._C, Q, pools, kp)
        evals = sum(p.size for p in pools) \
            + nq * self.ivf.centroids.shape[0]
        d = Q.shape[1]
        self.last_filter_bytes = (sum(p.size for p in pools) * d * 4
                                  + self.ivf.centroids.nbytes)
        return ids, vout, evals


class HNSWGraphFilter:
    """Host-side HNSW traversal over DCPE ciphertexts (DESIGN.md §3).

    Graph walks are sequential pointer chasing and stay on CPU even in
    the TPU deployment; only the filter phase loops over queries — the
    refine phase is batched regardless of backend.
    """

    name = "hnsw"

    def __init__(self, index: HNSW):
        self.index = index
        self.last_filter_bytes = 0

    def attach(self, C_sap: np.ndarray, engine: "SecureSearchEngine"):
        pass                      # the graph already stores its ciphertexts

    def candidates(self, Q_sap: np.ndarray, kp: int, ef_search: int):
        cand, valid, evals = traverse_graph_candidates(
            self.index, Q_sap, kp, ef_search)
        # pointer chasing re-reads per query: one full row per eval
        self.last_filter_bytes = int(evals) * Q_sap.shape[1] * 4
        return cand, valid, evals


class ADCFilter:
    """Quantized approximate-distance filter over ciphertext codes
    (DESIGN.md §11): the flat/IVF scan at 1 byte/dim (int8) or m
    bytes/vector (pq8) instead of 4 bytes/dim.

    The backend trains its codebook *keylessly* over the DCPE filter
    ciphertexts at attach (the server quantizes data it already holds —
    no new leakage), scans codes through the fused adc_topk kernel
    family, and **oversamples**: asked for k' candidates it returns
    k' * refine_ratio of them, so the unchanged exact DCE refine
    recovers the order that quantization blurred (`core.adc` holds the
    recall model and the per-kind defaults).

    kind="flat" streams all codes (Pallas `sq_adc_topk`/`pq_adc_topk`
    with the in-kernel running top-k); kind="ivf" probes the same
    coarse quantizer as `IVFScanFilter` (identical pools) and runs the
    ADC pool scan over the probed rows.

    use_kernel=True engages the Pallas path on actual TPU backends; on
    other backends the rank-identical XLA formulation runs instead —
    interpret-mode execution is a correctness harness, not a serving
    path (kernels/adc_topk/ops.py).  use_kernel=False forces XLA
    everywhere (the GSPMD-safe form the sharded backend uses).
    """

    def __init__(self, quantization: str = "int8", kind: str = "flat", *,
                 refine_ratio: float | None = None, use_kernel: bool = True,
                 n_partitions: int = 64, nprobe: int = 8, pq_m: int = 16,
                 seed: int = 0):
        if quantization not in ("int8", "pq8"):
            raise ValueError(f"ADCFilter needs quantization int8|pq8, "
                             f"got {quantization!r}")
        if kind not in ("flat", "ivf"):
            raise ValueError(f"ADCFilter kind must be flat|ivf, "
                             f"got {kind!r}")
        self.quantization = quantization
        self.kind = kind
        self.name = f"adc-{kind}-{quantization}"
        self.refine_ratio = (adc.default_refine_ratio(quantization)
                             if refine_ratio is None else
                             float(refine_ratio))
        self.use_kernel = use_kernel
        self.n_partitions = n_partitions
        self.nprobe = nprobe
        self.pq_m = pq_m
        self.seed = seed
        self.codebook = None
        self.ivf: IVFIndex | None = None
        self._c8 = self._cn = self._codes_t = None
        self._n = 0
        self.last_filter_bytes = 0
        self.last_merge_rounds: int | None = None   # flat kernel only
        self.last_merge_rounds_full = 0

    # --------------------------------------------------------- encoding

    def _use_pallas(self) -> bool:
        return self.use_kernel and jax.default_backend() == "tpu"

    def attach(self, C_sap: np.ndarray, engine: "SecureSearchEngine"):
        self._n = C_sap.shape[0]
        self.codebook = adc.train_codebook(
            C_sap, self.quantization, m=self.pq_m, seed=self.seed)
        if self.quantization == "int8":
            codes, cn = self.codebook.encode(C_sap)
            self._c8 = jnp.asarray(codes)
            self._cn = jnp.asarray(cn)
        else:
            codes = self.codebook.encode(C_sap)
            self._codes_t = jnp.asarray(np.ascontiguousarray(codes.T))
        if self.kind == "ivf":
            # the SAME coarse quantizer as IVFScanFilter — probe pools
            # are identical, only the per-row distance math changes
            self.ivf = IVFIndex(n_clusters=min(self.n_partitions,
                                               C_sap.shape[0]),
                                seed=self.seed).build(C_sap)

    def _code_bytes(self) -> int:
        return self.codebook.code_bytes_per_vector()

    def oversampled(self, kp: int) -> int:
        return max(kp, int(np.ceil(kp * self.refine_ratio)))

    # ------------------------------------------------------- candidates

    def candidates(self, Q_sap: np.ndarray, kp: int, ef_search: int):
        Q = np.asarray(Q_sap, np.float32)
        nq = Q.shape[0]
        kp2 = min(self.oversampled(kp), self._n)
        if self.kind == "flat":
            if self.quantization == "int8":
                q8 = self.codebook.encode_query(Q)
                _, idx = adc_ops.sq_knn(jnp.asarray(q8), self._c8,
                                        self._cn, kp2,
                                        use_kernel=self._use_pallas())
                block_n = adc_ops.DEFAULT_BLOCK_N_SQ
            else:
                lut = self.codebook.lut(Q)
                _, idx = adc_ops.pq_knn(jnp.asarray(lut), self._codes_t,
                                        kp2,
                                        use_kernel=self._use_pallas())
                block_n = adc_ops.DEFAULT_BLOCK_N_PQ
            cand, self.last_merge_rounds = adc_ops.split_merge_rounds(
                to_host(idx, "candidates", np.int32))
            self.last_merge_rounds_full = adc_ops.merge_rounds_full(
                self._n, kp2, block_n)
            # -1 marks slots beyond the valid-row count (kp' > n); the
            # refine sees them masked, never a wrapped gather index
            valid = cand >= 0
            cand = np.where(valid, cand, 0)
            self.last_filter_bytes = self._n * self._code_bytes()
            return cand, valid, nq * self._n

        pools = [self.ivf.probe(q, self.nprobe) for q in Q]
        cand, valid = layout_pools(nq, pools, kp2)
        if self.quantization == "int8":
            q8 = self.codebook.encode_query(Q)
            ids, vout = adc_ops.sq_pool_scan(
                self._c8, self._cn, jnp.asarray(q8), jnp.asarray(cand),
                jnp.asarray(valid), kp2)
        else:
            lut = self.codebook.lut(Q)
            ids, vout = adc_ops.pq_pool_scan(
                self._codes_t, jnp.asarray(lut), jnp.asarray(cand),
                jnp.asarray(valid), kp2)
        evals = sum(p.size for p in pools) \
            + nq * self.ivf.centroids.shape[0]
        self.last_filter_bytes = (sum(p.size for p in pools)
                                  * self._code_bytes()
                                  + self.ivf.centroids.nbytes)
        return (to_host(ids, "candidates"), to_host(vout, "valid"),
                evals)


_BACKENDS = {"flat": FlatScanFilter, "ivf": IVFScanFilter}


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

class SecureSearchEngine:
    """Batched filter-and-refine over an encrypted database.

    backend: "flat" | "ivf" | a filter-backend instance (e.g.
    `HNSWGraphFilter(index)` — pass the HNSW built by the data owner).
    quantization: None | "int8" | "pq8" — a non-None value swaps the
    string-selected flat/ivf backend for the quantized `ADCFilter`
    variant of the same kind (DESIGN.md §11); the refine is unchanged.
    use_kernel=False drops to the einsum refine (GSPMD-safe / debugging).
    """

    def __init__(self, C_sap: np.ndarray, C_dce: np.ndarray, *,
                 backend="flat", use_kernel: bool = True,
                 quantization: str | None = None, **backend_kw):
        if isinstance(backend, str):
            if backend == "hnsw":
                raise ValueError(
                    "pass HNSWGraphFilter(index) explicitly: the graph is "
                    "built by the data owner, not the engine")
            if backend == "graph":
                raise ValueError(
                    "pass repro.graph.GraphFilter(index) explicitly: the "
                    "graph is built by the data owner, not the engine")
            if quantization is not None:
                if backend not in ("flat", "ivf"):
                    raise ValueError(
                        f"quantization applies to flat|ivf backends, "
                        f"not {backend!r}")
                backend = ADCFilter(quantization, kind=backend,
                                    use_kernel=use_kernel, **backend_kw)
            else:
                backend = _BACKENDS[backend](**backend_kw)
        elif quantization is not None:
            raise ValueError("pass quantization to the backend instance, "
                             "not the engine, when supplying one")
        self.backend = backend
        self.use_kernel = use_kernel
        self.update_database(C_sap, C_dce)

    # -------------------------------------------------------------- state

    @property
    def n(self) -> int:
        return self._C_sap.shape[0]

    def update_database(self, C_sap: np.ndarray, C_dce: np.ndarray):
        """(Re)load ciphertexts, e.g. after owner-side insert (§V-D).

        Cheap: only marks backend acceleration state (device copies, IVF
        centroids) dirty; the rebuild happens lazily on the next search,
        so a burst of maintenance ops pays one refresh, not one per op."""
        self._C_sap = np.asarray(C_sap)
        self._C_dce = np.asarray(C_dce)
        self._dirty = True

    def _ensure_attached(self):
        if self._dirty:
            # a backend may manage the refine array's device residency
            # itself (the runtime's mutable store ships only appended
            # rows, DESIGN.md §8); default is a full upload
            provider = getattr(self.backend, "dce_device", None)
            self._C_dce_dev = (jnp.asarray(self._C_dce) if provider is None
                               else provider(self._C_dce))
            self.backend.attach(self._C_sap, self)
            self._dirty = False

    # ------------------------------------------------------------- search

    def search_batch(self, Q_sap: np.ndarray, T_q: np.ndarray, k: int,
                     ratio_k: float = 8.0, ef_search: int = 96,
                     refine: str = "tournament"):
        """Algorithm 2, batched: k'-ANN filter then exact DCE refine.

        Q_sap: (nq, d) DCPE query ciphertexts; T_q: (nq, 2d+16) trapdoors.
        Returns (ids (nq, k) int64, SearchStats); id -1 fills slots where
        a query had fewer than k real candidates (tiny database, sparse
        IVF probe).  refine: "tournament" (batched MXU tournament,
        default) | "none" (filter-only baseline, Fig. 6).  The paper's
        sequential heap refine is per-query only — use
        `search(..., refine="heap")`.
        """
        t0 = time.perf_counter()
        self._ensure_attached()
        Q_sap = np.atleast_2d(np.asarray(Q_sap))
        T_q = np.atleast_2d(np.asarray(T_q))
        nq = Q_sap.shape[0]
        kp = int(max(k, round(ratio_k * k)))
        # obs (DESIGN.md §13): when a scheduler's batch span is ambient,
        # filter/refine become its children; no-op spans otherwise
        with child_span("filter", backend=self.backend.name,
                        kp=kp, nq=nq) as fsp:
            cand, valid, dist_evals = self.backend.candidates(
                Q_sap, kp, ef_search)
            fsp.set(dist_evals=int(dist_evals),
                    bytes_scanned=int(
                        getattr(self.backend, "last_filter_bytes", 0)),
                    hops=int(getattr(self.backend, "last_n_hops", 0)),
                    edges_scanned=int(
                        getattr(self.backend, "last_n_edges_scanned", 0)))
            rounds = getattr(self.backend, "last_merge_rounds", None)
            if rounds is not None:
                fsp.set(merge_rounds=rounds,
                        merge_rounds_full=self.backend.last_merge_rounds_full)
        if cand.shape[1] < k:       # uniform (nq, k) contract: -1 fill
            pad = ((0, 0), (0, k - cand.shape[1]))
            cand = np.pad(cand, pad)
            valid = np.pad(valid, pad)

        with child_span("refine", mode=refine) as rsp:
            if refine == "tournament":
                # a backend may supply its own batched refine (the sharded
                # backend's tournament runs the candidate gather under the
                # mesh, serving/sharded.py); semantics are identical
                refine_fn = getattr(self.backend, "refine_batch", None)
                if refine_fn is not None:
                    out = refine_fn(self._C_dce_dev, jnp.asarray(cand),
                                    jnp.asarray(T_q), jnp.asarray(valid), k)
                else:
                    out = refine_candidates(
                        self._C_dce_dev, jnp.asarray(cand), jnp.asarray(T_q),
                        jnp.asarray(valid), k, self.use_kernel)
                ids = to_host(out, "ids", np.int64)
                nv = valid.sum(axis=1)
                ncmp = int((nv * (nv - 1)).sum())
            elif refine == "none":          # filter-only baseline
                ids = np.where(valid[:, :k], cand[:, :k], -1)\
                    .astype(np.int64)
                ncmp = 0
            else:
                raise ValueError(f"batched refine must be 'tournament' or "
                                 f"'none', got {refine!r}")
            rsp.set(comparisons=ncmp)

        stats = SearchStats(
            latency_s=time.perf_counter() - t0,
            filter_dist_evals=int(dist_evals),
            refine_comparisons=ncmp,
            bytes_up=Q_sap.nbytes + T_q.nbytes + 4 * nq,
            bytes_down=ids.nbytes,          # int64 ids: 8 bytes per slot
            n_queries=nq,
            backend=self.backend.name,
            filter_bytes_scanned=int(
                getattr(self.backend, "last_filter_bytes", 0)),
            n_hops=int(getattr(self.backend, "last_n_hops", 0)),
            n_edges_scanned=int(
                getattr(self.backend, "last_n_edges_scanned", 0)),
            n_shards_down=int(
                getattr(self.backend, "last_n_shards_down", 0)),
            degraded=bool(getattr(self.backend, "last_degraded", False)),
        )
        return ids, stats

    def search(self, C_sap_q: np.ndarray, T_q: np.ndarray, k: int,
               ratio_k: float = 8.0, ef_search: int = 96,
               refine: str = "tournament"):
        """Single-query search: a batch-of-one view of `search_batch`
        (identical ids by construction), plus the paper-faithful
        sequential refine modes ("heap")."""
        if refine in ("tournament", "none"):
            ids, stats = self.search_batch(
                C_sap_q[None], np.asarray(T_q)[None], k, ratio_k=ratio_k,
                ef_search=ef_search, refine=refine)
            return ids[0], stats

        if refine != "heap":
            raise ValueError(refine)
        # paper Algorithm 2: max-heap keyed by DCE comparison signs
        t0 = time.perf_counter()
        self._ensure_attached()
        kp = int(max(k, round(ratio_k * k)))
        cand, valid, dist_evals = self.backend.candidates(
            np.asarray(C_sap_q)[None], kp, ef_search)
        cids = cand[0][valid[0]].astype(np.int64)
        ids, ncmp = secure_knn.refine_heap(
            self._C_dce[cids], cids, np.asarray(T_q), k)
        stats = SearchStats(
            latency_s=time.perf_counter() - t0,
            filter_dist_evals=int(dist_evals),
            refine_comparisons=int(ncmp),
            bytes_up=np.asarray(C_sap_q).nbytes + np.asarray(T_q).nbytes + 4,
            bytes_down=np.asarray(ids, np.int64).nbytes,
            n_queries=1,
            backend=self.backend.name,
            filter_bytes_scanned=int(
                getattr(self.backend, "last_filter_bytes", 0)),
            n_hops=int(getattr(self.backend, "last_n_hops", 0)),
            n_edges_scanned=int(
                getattr(self.backend, "last_n_edges_scanned", 0)),
            n_shards_down=int(
                getattr(self.backend, "last_n_shards_down", 0)),
            degraded=bool(getattr(self.backend, "last_degraded", False)),
        )
        return ids, stats
