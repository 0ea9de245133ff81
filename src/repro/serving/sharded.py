"""Placement-aware sharded execution: the filter-and-refine pipeline
row-sharded across a device mesh (DESIGN.md §10).

`ShardedBackend` is a drop-in engine filter backend (the same
`attach`/`candidates` protocol as `runtime.ingest.DeltaAwareBackend`,
which it subclasses), so the micro-batcher, tenant routing, telemetry,
live encrypted ingestion, and `save`/`load` snapshots of the serving
runtime all work unchanged over a sharded collection.  What changes is
*where* the scan and the refine gather run:

  filter (flat):  the sentinel-padded ciphertext array is row-sharded
                  (`NamedSharding(P(axis, None))`); under `shard_map`
                  each shard scans its rows, takes a local top-k' with
                  *global* ids (`local_idx + shard * rows_per_shard` —
                  the stable-global-id offset), and an all-gather of
                  only k' rows per shard feeds the cross-shard top-k'
                  merge.
  filter (ivf):   coarse probing stays host-side (identical pools to
                  the single-device backend, so parity is exact); the
                  pool scan runs sharded — each shard computes the
                  distances for pool entries it owns, non-owned slots
                  are +inf, and a `pmin` over the axis reassembles the
                  full (nq, L) distance matrix bit-identically to the
                  single-device `_masked_pruned_scan`.
  filter (graph): per-shard subgraphs (DESIGN.md §15) — each shard owns
                  an independent HNSW over its contiguous row block,
                  mirrored into one shared (R, LU) CSR bucket; the
                  batched lockstep traversal runs per shard with one
                  reused executable and the k'-per-shard results merge
                  by surrogate distance (host-side; the traversal does
                  not run under the mesh).
  refine:         the DCE refine array is row-sharded too; each shard
                  extracts the candidate rows it owns (others zeroed)
                  and one `psum` of (nq, k', 4, D) — k' rows per query,
                  never the database — assembles the replicated
                  candidate tensor for the batched tournament, which
                  every device runs with the single-device refine's
                  own kernel (identical comparisons, identical ids).

Row -> shard routing is the block partition of the padded capacity
bucket: global row id r lives on shard `r // rows_per_shard`.  Ids are
the stable store row ids, so live inserts append to the tail shard(s)
and deletes tombstone in place; `shard_manifest()` reports the current
partition for persistence (the per-shard manifest in a `.ppcol`
snapshot).

Every jitted entry point here is module-level and specialised only on
bucketed shapes + (mesh, axis, k') statics, so a warmed-up collection
serves steady-state traffic with zero recompiles
(`runtime.telemetry.jit_cache_size` audits these functions too).

Failover (repro.resilience, DESIGN.md §16): every shard group carries
`n_replicas` logical replicas in a `ShardHealthRegistry`; a group is
servable while >= 1 replica is up, so killing one replica changes
nothing.  When a whole group is down the backend *routes around it*
instead of failing: the group's rows are masked out of the scans (mask
is data — the healthy path stays byte-identical and executable-
identical), the graph walk skips the dead subgraphs, and every answer
is stamped `last_degraded` / `last_n_shards_down` for
`SearchStats.degraded` / `n_shards_down`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.hnsw import HNSW
from ..graph.csr import CSRGraph
from ..graph.traverse import beam_plan, graph_topk
from ..kernels.adc_topk.ops import INT_BIG
from ..kernels.common import HIGHEST, next_bucket
from ..kernels.dce_comp import ops as dce_ops
from ..launch.mesh import make_mesh
from ..obs.trace import child_complete, current as obs_current, to_host
from ..resilience.health import ShardHealthRegistry
from .runtime.ingest import SENTINEL, DeltaAwareBackend
from .search_engine import layout_pools, pool_dists, pool_membership

__all__ = ["ShardedBackend", "sharded_mesh", "shard_bucket"]


def sharded_mesh(n_shards: int, data_axis: str = "data"):
    """A 1-D mesh over the first `n_shards` local devices."""
    n_dev = len(jax.devices())
    if n_shards > n_dev:
        raise ValueError(f"placement wants {n_shards} shards but only "
                         f"{n_dev} device(s) exist (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N to "
                         f"simulate more on CPU)")
    return make_mesh((n_shards,), (data_axis,))


def shard_bucket(n: int, n_shards: int, minimum: int = 256) -> int:
    """Padded row capacity: the store's power-of-two bucket, rounded up
    to a multiple of n_shards so the block partition is even.  (For the
    usual power-of-two shard counts the rounding is a no-op.)"""
    b = next_bucket(max(n, 1), minimum=minimum)
    return -(-b // n_shards) * n_shards


# ---------------------------------------------------------------------------
# Jitted sharded entry points.  Module-level, specialised on (mesh, axis,
# k') statics + bucketed shapes only — the zero-recompile contract.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_flat_topk(C_sh, Q, *, mesh, axis, kp: int):
    """Row-sharded exhaustive filter: per-shard distances + local top-k'
    with global id offsets, then a cross-shard merge that all-gathers
    only k' rows per shard (never the (nq, n) matrix)."""

    def body(C_loc, Q_rep):
        n_loc = C_loc.shape[0]
        qn = (Q_rep * Q_rep).sum(-1, keepdims=True)
        xn = (C_loc * C_loc).sum(-1)[None, :]
        dist = qn - 2.0 * jnp.matmul(Q_rep, C_loc.T, precision=HIGHEST) + xn
        kp_loc = min(kp, n_loc)
        neg, idx = jax.lax.top_k(-dist, kp_loc)           # local top-k'
        gidx = idx + jax.lax.axis_index(axis) * n_loc     # global ids
        vals = jax.lax.all_gather(-neg, axis, axis=1, tiled=True)
        gids = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        neg2, pos = jax.lax.top_k(-vals, min(kp, vals.shape[1]))
        return jnp.take_along_axis(gids, pos, axis=1)     # (nq, kp_out)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis, None), P(None, None)),
                     out_specs=P(None, None),
                     check_vma=False)(C_sh, Q)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_flat_topk_ok(C_sh, ok_sh, Q, *, mesh, axis, kp: int):
    """Degraded-mode twin of `_sharded_flat_topk` (DESIGN.md §16): the
    same scan with a row serve-mask as DATA, so rows of dead shard
    groups never reach the merge.  Compiled only on the first degraded
    call — the healthy path keeps its original executable untouched."""

    def body(C_loc, ok_loc, Q_rep):
        n_loc = C_loc.shape[0]
        qn = (Q_rep * Q_rep).sum(-1, keepdims=True)
        xn = (C_loc * C_loc).sum(-1)[None, :]
        dist = qn - 2.0 * jnp.matmul(Q_rep, C_loc.T, precision=HIGHEST) + xn
        dist = jnp.where(ok_loc[None, :], dist, jnp.inf)
        kp_loc = min(kp, n_loc)
        neg, idx = jax.lax.top_k(-dist, kp_loc)
        return _local_merge(axis, neg, idx, n_loc, kp)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis, None), P(axis), P(None, None)),
                     out_specs=P(None, None),
                     check_vma=False)(C_sh, ok_sh, Q)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_pool_scan(C_sh, Q, cand, valid, *, mesh, axis, kp: int):
    """Row-sharded IVF pool scan.  Each shard computes the (nq, L)
    distance entries whose candidate row it owns (+inf elsewhere); a
    pmin over the axis reassembles the full matrix — element-for-element
    the same float32 values as the single-device masked scan, so the
    top-k' that follows is bit-identical."""

    def body(C_loc, Q_rep, cand_rep, valid_rep):
        n_loc = C_loc.shape[0]
        base = jax.lax.axis_index(axis) * n_loc
        loc = cand_rep - base
        mine = (loc >= 0) & (loc < n_loc) & valid_rep
        d = pool_dists(C_loc, Q_rep, jnp.clip(loc, 0, n_loc - 1), mine)
        d = jax.lax.pmin(d, axis)                         # (nq, L) full
        kp_out = min(kp, d.shape[1])
        _, pos = jax.lax.top_k(-d, kp_out)
        return (jnp.take_along_axis(cand_rep, pos, axis=1),
                jnp.take_along_axis(valid_rep, pos, axis=1))

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis, None), P(None, None),
                               P(None, None), P(None, None)),
                     out_specs=(P(None, None), P(None, None)),
                     check_vma=False)(C_sh, Q, cand, valid)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_oblivious_scan(C_sh, Q, member, *, mesh, axis, kp: int):
    """Row-sharded scan-oblivious IVF filter (DESIGN.md §14): each shard
    scans ALL of its rows for every query — a constant-shape local
    matmul, no data-dependent gather — masks by its slice of the
    (nq, bucket) pool-membership matrix, and the usual local-top-k' /
    all-gather(k'/shard) merge follows.  Returns global ids only;
    validity is a host-side membership lookup (the mask is host data)."""

    def body(C_loc, Q_rep, m_loc):
        n_loc = C_loc.shape[0]
        qn = (Q_rep * Q_rep).sum(-1, keepdims=True)
        xn = (C_loc * C_loc).sum(-1)[None, :]
        d = qn - 2.0 * jnp.matmul(Q_rep, C_loc.T, precision=HIGHEST) + xn
        d = jnp.where(m_loc, d, jnp.inf)
        kp_loc = min(kp, n_loc)
        neg, idx = jax.lax.top_k(-d, kp_loc)
        return _local_merge(axis, neg, idx, n_loc, kp)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis, None), P(None, None),
                               P(None, axis)),
                     out_specs=P(None, None),
                     check_vma=False)(C_sh, Q, member)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_sq_oblivious(C8_sh, cn_sh, Q8, member, *, mesh, axis,
                          kp: int):
    """Row-sharded scan-oblivious int8 ADC IVF filter: full local code
    scan masked by the shard's membership columns + all-gather merge."""

    def body(C_loc, cn_loc, Q_rep, m_loc):
        n_loc = C_loc.shape[0]
        cross = jax.lax.dot_general(
            Q_rep.astype(jnp.float32), C_loc.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        d = cn_loc.astype(jnp.float32)[None, :] - 2.0 * cross
        d = jnp.where(m_loc, d, jnp.inf)
        kp_loc = min(kp, n_loc)
        neg, idx = jax.lax.top_k(-d, kp_loc)
        return _local_merge(axis, neg, idx, n_loc, kp)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis, None), P(axis), P(None, None),
                               P(None, axis)),
                     out_specs=P(None, None),
                     check_vma=False)(C8_sh, cn_sh, Q8, member)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_pq_oblivious(codes_t_sh, lut, member, *, mesh, axis,
                          kp: int):
    """Row-sharded scan-oblivious PQ ADC IVF filter: full local LUT
    accumulation masked by the shard's membership columns."""

    def body(ct_loc, lut_rep, m_loc):
        n_loc = ct_loc.shape[1]
        cc = jnp.broadcast_to(ct_loc.astype(jnp.int32)[None],
                              (lut_rep.shape[0],) + ct_loc.shape)
        g = jnp.take_along_axis(lut_rep, cc, axis=2)      # (nq, m, n_loc)
        d = jnp.where(m_loc, g.sum(axis=1), jnp.inf)
        kp_loc = min(kp, n_loc)
        neg, idx = jax.lax.top_k(-d, kp_loc)
        return _local_merge(axis, neg, idx, n_loc, kp)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(None, axis), P(None, None, None),
                               P(None, axis)),
                     out_specs=P(None, None),
                     check_vma=False)(codes_t_sh, lut, member)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "k"))
def _sharded_refine(C_dce_sh, cand, T, valid, *, mesh, axis, k: int):
    """Sharded batched DCE tournament: per-shard candidate-row extraction
    + one psum of (nq, k', 4, D) assembles the replicated candidate
    tensor; the tournament itself runs replicated on every device, as
    the very `batched_top_k_by_wins` the single-device refine runs (the
    dce_comp kernel on TPU).  f32 DCE comparisons resolve distance gaps
    only down to ~1e-4 relative, so two Z formulations can order a
    near-tie differently; one formulation keeps sharded ids identical
    to single-device ids.  A Pallas kernel cannot be auto-partitioned,
    hence the shard_map over replicated operands.  Same -1 semantics as
    `search_engine.refine_candidates` with a validity mask."""

    def gather(C_loc, cand_rep):
        n_loc = C_loc.shape[0]
        base = jax.lax.axis_index(axis) * n_loc
        loc = cand_rep - base
        mine = (loc >= 0) & (loc < n_loc)
        rows = jnp.take(C_loc, jnp.clip(loc, 0, n_loc - 1), axis=0)
        rows = jnp.where(mine[..., None, None], rows, 0.0)
        return jax.lax.psum(rows, axis)                   # (nq, kp, 4, D)

    Cc = shard_map(gather, mesh=mesh,
                   in_specs=(P(axis, None, None), P(None, None)),
                   out_specs=P(None, None, None, None),
                   check_vma=False)(C_dce_sh, cand)
    tournament = shard_map(
        lambda c, t, v: dce_ops.batched_top_k_by_wins(c, t, k, valid=v),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False)
    local = tournament(Cc, T, valid).astype(cand.dtype)
    ids = jnp.take_along_axis(cand, local, axis=1)
    vsel = jnp.take_along_axis(valid, local, axis=1)
    return jnp.where(vsel, ids, -1)


# ---------------------------------------------------------------------------
# Quantized ADC variants (DESIGN.md §11): the same collective shapes as
# the f32 entry points above — per-shard local work + all-gather(k') or
# pmin merges — with distances computed from per-shard *codes* instead
# of f32 ciphertexts.  XLA/einsum formulation throughout (the Pallas
# adc_topk path stays single-device; a mesh-sharded pallas_call would
# fight the partitioner, same argument as the refine, DESIGN.md §3).
# ---------------------------------------------------------------------------

_BIG_F = jnp.float32(INT_BIG)


@jax.jit
def _and_ok(ok, sok):
    """Failover mask composition (DESIGN.md §16): ADC row validity AND
    the per-row shard-group serve mask.  Validity is data, so the
    composed mask reuses the already-compiled ADC executables — the
    degraded path costs one tiny jit, not a re-specialised scan."""
    return jnp.where(sok > 0, ok, jnp.zeros((), ok.dtype))


def _local_merge(axis, neg, idx, n_loc, kp):
    """Shared tail of the sharded flat scans: local top-k' -> global ids
    -> all-gather(k'/shard) -> cross-shard top-k'."""
    gidx = idx + jax.lax.axis_index(axis) * n_loc
    vals = jax.lax.all_gather(-neg, axis, axis=1, tiled=True)
    gids = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
    _, pos = jax.lax.top_k(-vals, min(kp, vals.shape[1]))
    return jnp.take_along_axis(gids, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_sq_topk(C8_sh, cn_sh, ok_sh, Q8, *, mesh, axis, kp: int):
    """Row-sharded int8 ADC filter: per-shard surrogate distances
    cn - 2*(q8 . c8) over the shard's codes, then the existing
    all-gather(k'/shard) merge."""

    def body(C_loc, cn_loc, ok_loc, Q_rep):
        n_loc = C_loc.shape[0]
        cross = jax.lax.dot_general(
            Q_rep.astype(jnp.float32), C_loc.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        d = cn_loc.astype(jnp.float32)[None, :] - 2.0 * cross
        d = jnp.where(ok_loc[None, :] > 0, d, _BIG_F)
        kp_loc = min(kp, n_loc)
        neg, idx = jax.lax.top_k(-d, kp_loc)
        return _local_merge(axis, neg, idx, n_loc, kp)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis, None), P(axis), P(axis),
                               P(None, None)),
                     out_specs=P(None, None),
                     check_vma=False)(C8_sh, cn_sh, ok_sh, Q8)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_pq_topk(codes_t_sh, ok_sh, lut, *, mesh, axis, kp: int):
    """Row-sharded PQ ADC filter: per-shard LUT gather-accumulate over
    the shard's code columns, then the all-gather merge.  codes_t_sh is
    (m, n) sharded on its column axis."""

    def body(ct_loc, ok_loc, lut_rep):
        n_loc = ct_loc.shape[1]
        cc = jnp.broadcast_to(ct_loc.astype(jnp.int32)[None],
                              (lut_rep.shape[0],) + ct_loc.shape)
        g = jnp.take_along_axis(lut_rep, cc, axis=2)      # (nq, m, n_loc)
        d = jnp.where(ok_loc[None, :] > 0, g.sum(axis=1), jnp.inf)
        kp_loc = min(kp, n_loc)
        neg, idx = jax.lax.top_k(-d, kp_loc)
        return _local_merge(axis, neg, idx, n_loc, kp)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(None, axis), P(axis), P(None, None, None)),
                     out_specs=P(None, None),
                     check_vma=False)(codes_t_sh, ok_sh, lut)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_sq_pool_scan(C8_sh, cn_sh, Q8, cand, valid, *, mesh, axis,
                          kp: int):
    """Row-sharded int8 ADC pool scan: each shard fills the (nq, L)
    surrogate-distance entries it owns, pmin reassembles — the
    quantized twin of `_sharded_pool_scan`."""

    def body(C_loc, cn_loc, Q_rep, cand_rep, valid_rep):
        n_loc = C_loc.shape[0]
        base = jax.lax.axis_index(axis) * n_loc
        loc = cand_rep - base
        mine = (loc >= 0) & (loc < n_loc) & valid_rep
        safe = jnp.clip(loc, 0, n_loc - 1)
        rows = jnp.take(C_loc, safe, axis=0).astype(jnp.float32)
        cn_rows = jnp.take(cn_loc, safe).astype(jnp.float32)
        cross = jnp.einsum("qld,qd->ql", rows, Q_rep.astype(jnp.float32))
        d = jnp.where(mine, cn_rows - 2.0 * cross, jnp.inf)
        d = jax.lax.pmin(d, axis)                         # (nq, L) full
        kp_out = min(kp, d.shape[1])
        _, pos = jax.lax.top_k(-d, kp_out)
        return (jnp.take_along_axis(cand_rep, pos, axis=1),
                jnp.take_along_axis(valid_rep, pos, axis=1))

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis, None), P(axis), P(None, None),
                               P(None, None), P(None, None)),
                     out_specs=(P(None, None), P(None, None)),
                     check_vma=False)(C8_sh, cn_sh, Q8, cand, valid)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kp"))
def _sharded_pq_pool_scan(codes_t_sh, lut, cand, valid, *, mesh, axis,
                          kp: int):
    """Row-sharded PQ ADC pool scan (LUT gather over owned pool rows +
    pmin)."""

    def body(ct_loc, lut_rep, cand_rep, valid_rep):
        n_loc = ct_loc.shape[1]
        base = jax.lax.axis_index(axis) * n_loc
        loc = cand_rep - base
        mine = (loc >= 0) & (loc < n_loc) & valid_rep
        safe = jnp.clip(loc, 0, n_loc - 1)
        cc = jnp.take(ct_loc, safe, axis=1)               # (m, nq, L)
        cc = jnp.transpose(cc, (1, 0, 2)).astype(jnp.int32)
        g = jnp.take_along_axis(lut_rep, cc, axis=2)      # (nq, m, L)
        d = jnp.where(mine, g.sum(axis=1), jnp.inf)
        d = jax.lax.pmin(d, axis)
        kp_out = min(kp, d.shape[1])
        _, pos = jax.lax.top_k(-d, kp_out)
        return (jnp.take_along_axis(cand_rep, pos, axis=1),
                jnp.take_along_axis(valid_rep, pos, axis=1))

    return shard_map(body, mesh=mesh,
                     in_specs=(P(None, axis), P(None, None, None),
                               P(None, None), P(None, None)),
                     out_specs=(P(None, None), P(None, None)),
                     check_vma=False)(codes_t_sh, lut, cand, valid)


def cache_size() -> int:
    """Compiled-executable count of the sharded entry points (summed
    into `runtime.telemetry.jit_cache_size` for the recompile audit)."""
    return sum(f._cache_size() for f in
               (_sharded_flat_topk, _sharded_pool_scan, _sharded_refine,
                _sharded_sq_topk, _sharded_pq_topk,
                _sharded_sq_pool_scan, _sharded_pq_pool_scan,
                _sharded_oblivious_scan, _sharded_sq_oblivious,
                _sharded_pq_oblivious, _sharded_flat_topk_ok, _and_ok))


# ---------------------------------------------------------------------------
# The backend.
# ---------------------------------------------------------------------------

class ShardedBackend(DeltaAwareBackend):
    """Row-sharded flat / IVF / per-shard-graph filter + sharded refine
    over a mutable encrypted store.

    Reuses the delta-aware host-side machinery wholesale — mutation
    hooks, tombstone masking (`_mask_alive`), the IVF centroid build and
    incremental delta assignment — and replaces only the device layout
    (NamedSharding row partition) and the scan/refine executables
    (shard_map).  Engine parity therefore reduces to the collective
    formulation, which is tested id-exact against the single-device
    path (tests/test_placement.py).
    """

    def __init__(self, store, kind: str = "flat", *, n_shards: int,
                 n_replicas: int = 1, data_axis: str = "data", **kw):
        if kind not in ("flat", "ivf", "graph"):
            raise ValueError(
                f"sharded placement supports flat|ivf|graph filter "
                f"backends, not {kind!r} (the per-query host walk does "
                f"not shard; kind='graph' serves per-shard subgraphs, "
                f"DESIGN.md §3/§15)")
        self._hnsw_M = kw.get("hnsw_M", 16)
        self._hnsw_efc = kw.get("hnsw_ef_construction", 200)
        super().__init__(store, kind, **kw)
        self.n_shards = int(n_shards)
        self.axis = data_axis
        self.mesh = sharded_mesh(self.n_shards, data_axis)
        self.name = f"sharded-{self.name}"   # sharded-<kind | adc-...>
        self.use_kernel = False       # XLA scans under the mesh
        # failover state (DESIGN.md §16): the health registry is the one
        # mutable truth; masks derived from it are cached on its epoch
        self.n_replicas = int(n_replicas)
        self.health = ShardHealthRegistry(self.n_shards, self.n_replicas)
        self.last_degraded = False
        self.last_n_shards_down = 0
        self._ru_cache = (None, None)        # (epoch, bucket) -> row_up
        self._sok_cache: dict = {}           # device serve-mask rows
        self._sh_sap = NamedSharding(self.mesh, P(data_axis, None))
        self._sh_dce = NamedSharding(self.mesh, P(data_axis, None, None))
        self._sh_row = NamedSharding(self.mesh, P(data_axis))
        self._sh_codes_t = NamedSharding(self.mesh, P(None, data_axis))
        # per-shard subgraph state (kind="graph", DESIGN.md §15): each
        # shard owns an independent host HNSW over its contiguous row
        # block — graph edges never cross shards, so the batched
        # traversal runs per shard (one executable, reused across
        # shards: identical R/LU buckets) and the k'-per-shard results
        # merge by surrogate distance, the same collective shape as the
        # flat all-gather(k') merge.  The single global host graph of
        # the base class is disabled (its eager hooks assume node id ==
        # store row id, which a block partition breaks); mutations are
        # replayed shard-locally at the next attach instead.
        if kind == "graph":
            self.graph = None
        self._shard_graphs: list[HNSW] | None = None
        self._g_per = 0                    # rows per shard of the mirror
        self._g_built_n = 0                # store rows absorbed so far
        self._g_csrs: list[CSRGraph] | None = None
        self._g_dirty_sh: list[set] = []
        self._g_del_pending: list[int] = []
        self._g_neigh0_sh = self._g_neigh_up_sh = None

    # ------------------------------------------------------------ layout

    def _row_bucket(self, n: int) -> int:
        return shard_bucket(n, self.n_shards)

    @property
    def padded_rows(self) -> int:
        return self._row_bucket(self.store.n_total)

    def shard_manifest(self) -> list[dict]:
        """The current row -> shard block partition (persisted as the
        per-shard manifest of a sharded collection snapshot)."""
        st = self.store
        per = self.padded_rows // self.n_shards
        out = []
        for s in range(self.n_shards):
            start = min(s * per, st.n_total)
            stop = min((s + 1) * per, st.n_total)
            out.append({"shard": s, "row_start": int(start),
                        "row_stop": int(stop),
                        "n_alive": int(st.alive_view[start:stop].sum())})
        return out

    # ------------------------------------------------------------ attach

    def on_delete(self, row: int):
        if self.kind == "graph":
            # shard graphs sync lazily at attach (one replay per burst);
            # the store has already sentinelled the row, so a search
            # racing the replay still masks it via `_mask_alive`
            self._g_del_pending.append(int(row))
            return
        super().on_delete(row)
        if self.kind == "flat":
            # force a re-upload so the deleted row is sentinelled on
            # device too — keeps the sharded candidate sets identical to
            # the single-device backend's (which re-sentinels its main
            # array); ivf needs nothing: the row left its probe list
            self._scan_snapshot = (-1, -1)

    def _refresh_scan_array(self, C_sap: np.ndarray):
        """Sharded replacement for the parent's scan-array refresh: one
        sentinel-padded, row-sharded device array serving both the flat
        exhaustive scan and the ivf pool scan.  Same caching rule as the
        parent: insert bursts inside an unchanged bucket ship only the
        new rows (scatter preserves the NamedSharding), not the whole
        database; bucket growth, compaction, or a flat delete (which
        invalidates the snapshot) pay one full sharded re-upload."""
        st = self.store
        bucket = self._row_bucket(st.n_total)
        snapshot = (st.main_gen, st.n_total)
        if self._C_all is not None and self._scan_snapshot == snapshot:
            return
        old_gen, old_n = self._scan_snapshot
        if (self._C_all is not None and old_gen == st.main_gen
                and 0 <= old_n <= st.n_total
                and self._C_all.shape[0] == bucket):
            self._C_all = self._C_all.at[old_n: st.n_total].set(
                jnp.asarray(C_sap[old_n: st.n_total]))
        else:
            buf = np.full((bucket, st.d), SENTINEL, np.float32)
            buf[: st.n_total] = C_sap
            self._C_all = jax.device_put(buf, self._sh_sap)
        self._scan_snapshot = snapshot

    # sharded residency for the ADC code arrays (parent attach logic,
    # these placement hooks): codes row-sharded like the f32 scan
    # array, (m, n) PQ codes sharded on their column axis, per-row
    # norms/validity sharded 1-D — every shard streams only its codes
    def _put_codes(self, buf: np.ndarray):
        return jax.device_put(buf, self._sh_sap)

    def _put_codes_t(self, buf: np.ndarray):
        return jax.device_put(buf, self._sh_codes_t)

    def _put_rowvec(self, buf: np.ndarray):
        return jax.device_put(buf, self._sh_row)

    def attach(self, C_sap: np.ndarray, engine):
        if self.kind == "graph":
            self._attach_graph_sharded(C_sap)
            return
        if self.quantization is not None:
            if self.kind == "ivf":
                self._attach_ivf_index(C_sap)   # same pools as single
            self._attach_adc(C_sap)             # codes via our hooks
            return
        if self.kind == "ivf":
            self._attach_ivf(C_sap)       # parent logic; calls our
        else:                             # _refresh_scan_array override
            self._refresh_scan_array(C_sap)

    # ------------------------------------------- per-shard subgraphs

    def _ensure_shard_graphs(self, C_sap: np.ndarray):
        """Host-graph maintenance: one independent HNSW per shard over
        its contiguous row block (shard-local node id = row - shard
        base).  A bucket change or compaction rebuilds; otherwise the
        mutation burst replays shard-locally — appended rows insert
        into their owning tail shard(s), pending deletes repair in
        place — and only the changed rows are marked for CSR refresh."""
        st = self.store
        per = self._row_bucket(max(st.n_total, 1)) // self.n_shards
        rebuild = (self._shard_graphs is None or per != self._g_per
                   or self._attached_gen != st.main_gen)
        if rebuild:
            self._shard_graphs = [
                HNSW(dim=st.d, M=self._hnsw_M,
                     ef_construction=self._hnsw_efc, seed=self.seed + s)
                for s in range(self.n_shards)]
            self._g_per = per
            self._g_built_n = 0
            self._g_csrs = None
            self._g_dirty_sh = [set() for _ in range(self.n_shards)]
            self._g_del_pending.clear()   # tombstones replay from store
        built0 = self._g_built_n
        alive = st.alive_view
        for row in range(built0, st.n_total):
            # rows append in order, so each shard's inserts are its
            # contiguous local ids — node id == local offset by
            # construction (the sharded twin of the node==row invariant)
            s, local = divmod(row, per)
            g = self._shard_graphs[s]
            node = g.insert(C_sap[row])
            if node != local:
                raise RuntimeError(
                    f"shard {s} node id {node} != local row {local}: "
                    f"subgraph and store are desynchronized")
            dirty = self._g_dirty_sh[s]
            dirty.add(local)
            for lev in range(len(g.links)):
                nb = g.links[lev][local]
                if nb is not None:
                    dirty.update(int(v) for v in nb)
            if not alive[row]:      # tombstoned between attaches (or a
                dirty.update(g.delete(local))   # rebuild over dead rows)
        self._g_built_n = st.n_total
        for row in self._g_del_pending:
            if row < built0:        # rows >= built0 were handled above
                s, local = divmod(row, per)
                dirty = self._g_dirty_sh[s]
                dirty.add(local)
                dirty.update(self._shard_graphs[s].delete(local))
        self._g_del_pending.clear()
        self._attached_gen = st.main_gen

    def _attach_graph_sharded(self, C_sap: np.ndarray):
        """CSR mirrors + device arrays for the per-shard subgraphs.  All
        shards share one (R=per, LU) bucket so the jitted traversal
        compiles once and serves every shard."""
        st = self.store
        self._ensure_shard_graphs(C_sap)
        per = self._g_per
        graphs = self._shard_graphs
        if (self._g_csrs is None or self._g_csrs[0].R != per
                or any(not c.fits(g)
                       for c, g in zip(self._g_csrs, graphs))):
            LU = max(next_bucket(max(len(g.links) - 1, 1), minimum=4)
                     for g in graphs)
            if self._g_csrs is not None:
                LU = max(LU, self._g_csrs[0].LU)
            self._g_csrs = [CSRGraph.from_hnsw(g, R=per, LU=LU)
                            for g in graphs]
            for dirty in self._g_dirty_sh:
                dirty.clear()
        else:
            for s, (c, g) in enumerate(zip(self._g_csrs, graphs)):
                if self._g_dirty_sh[s]:
                    c.refresh_rows(g, sorted(self._g_dirty_sh[s]))
                    c.refresh_meta(g)
                    self._g_dirty_sh[s].clear()
        self._g_neigh0_sh = [jnp.asarray(c.neigh0) for c in self._g_csrs]
        self._g_neigh_up_sh = [jnp.asarray(c.neigh_up)
                               for c in self._g_csrs]
        if self.quantization is not None:
            self._attach_adc(C_sap)     # global codebook: surrogate
            self._g_ok = self._adc_ok > 0   # distances stay comparable
            self._g_db = ((self._adc_c8, self._adc_cn)   # across shards
                          if self.quantization == "int8"
                          else (self._adc_codes_t,))
        else:
            self._refresh_scan_array(C_sap)
            ok = np.zeros(per * self.n_shards, bool)
            ok[: st.n_total] = st.alive_view
            self._g_ok = jnp.asarray(ok)
            self._g_db = (self._C_all,)

    def dce_device(self, C_dce_padded: np.ndarray):
        """Row-sharded residency for the refine array, padded to the
        same bucket as the scan array so both partition identically.
        Same incremental rule as the parent: inside an unchanged bucket,
        ship only the rows appended since the last refresh (the scatter
        preserves the NamedSharding).  Tombstoned rows keep a stale
        device copy, exactly like the single-device backend — they are
        never valid candidates."""
        st = self.store
        bucket = self._row_bucket(st.n_total)
        old_bucket, old_n = self._dce_snapshot
        if self._C_dce_dev is not None and bucket == old_bucket:
            if st.n_total > old_n:
                self._C_dce_dev = self._C_dce_dev.at[old_n: st.n_total].set(
                    jnp.asarray(C_dce_padded[old_n: st.n_total]))
        else:
            buf = np.zeros((bucket,) + C_dce_padded.shape[1:], np.float32)
            buf[: st.n_total] = C_dce_padded[: st.n_total]
            self._C_dce_dev = jax.device_put(buf, self._sh_dce)
        self._dce_snapshot = (bucket, st.n_total)
        return self._C_dce_dev

    # ------------------------------------------- graph persistence

    def graph_arrays(self) -> dict:
        """Per-shard snapshot payload: each subgraph's `to_arrays`
        encoding under an `s<shard>__` prefix (restoring the exact
        host graphs keeps post-restore searches bit-identical — a
        rebuild would replay deletes in a different repair order)."""
        if self._shard_graphs is None:     # snapshot before first search
            self._ensure_shard_graphs(self.store.sap_view)
        out = {}
        for s, g in enumerate(self._shard_graphs):
            out.update({f"s{s}__{k}": v for k, v in
                        g.to_arrays().items()})
        return out

    def restore_graph(self, arrays: dict):
        st = self.store
        if not any(k.startswith("s0__") for k in arrays):
            # an owner-built *global* graph (EncryptedCorpus.index): a
            # single graph does not block-partition, so the service
            # builds its per-shard subgraphs over the uploaded DCPE
            # ciphertexts at the next attach (keyless-safe — the same
            # inputs the owner's build saw)
            self._shard_graphs = None
            self._attached_gen = -1
            return
        per = self._row_bucket(max(st.n_total, 1)) // self.n_shards
        graphs = []
        for s in range(self.n_shards):
            pre = f"s{s}__"
            sub = {k[len(pre):]: v for k, v in arrays.items()
                   if k.startswith(pre)}
            g = HNSW.from_arrays(sub)
            want = min(max(st.n_total - s * per, 0), per)
            if g.size != want:
                raise ValueError(
                    f"shard {s} graph has {g.size} nodes for {want} "
                    f"rows (snapshot from a different partition?)")
            graphs.append(g)
        self._shard_graphs = graphs
        self._g_per = per
        self._g_built_n = st.n_total
        self._g_csrs = None
        self._g_dirty_sh = [set() for _ in range(self.n_shards)]
        self._g_del_pending.clear()
        self._attached_gen = st.main_gen

    # ------------------------------------------------------- failover

    def _row_up(self, bucket: int) -> np.ndarray:
        """(bucket,) bool host mask: True where the row's shard group
        still has a live replica.  Cached on (health epoch, bucket) —
        the steady state never rebuilds it."""
        key = (self.health.epoch, bucket)
        if self._ru_cache[0] != key:
            per = bucket // self.n_shards
            self._ru_cache = (key,
                              np.repeat(self.health.serve_mask(), per))
        return self._ru_cache[1]

    def _sok_dev(self, bucket: int, dtype) -> jax.Array:
        """Device-resident, row-sharded copy of `_row_up` (dtype-matched
        so the composed ADC mask reuses the healthy executables)."""
        key = (self.health.epoch, bucket, np.dtype(dtype).str)
        hit = self._sok_cache.get(key)
        if hit is None:
            self._sok_cache = {k: v for k, v in self._sok_cache.items()
                               if k[0] == key[0]}   # drop stale epochs
            arr = self._row_up(bucket).astype(dtype)
            hit = self._sok_cache[key] = jax.device_put(arr, self._sh_row)
        return hit

    def _pool_alive(self):
        """Probe-pool validity for the IVF paths: alive, AND (degraded
        only) the row's shard group servable — host-side composition,
        so the pool-scan executables never change."""
        st = self.store
        if not self.last_degraded:
            return lambda p: st.alive_view[p]
        row_up = self._row_up(self._row_bucket(max(st.n_total, 1)))
        return lambda p: st.alive_view[p] & row_up[p]

    def _mask_alive(self, cand: np.ndarray, valid: np.ndarray):
        safe, v = super()._mask_alive(cand, valid)
        if self.last_degraded:
            # safety net: no id from a dead shard group survives, even
            # one a masked scan let through at +inf distance
            row_up = self._row_up(
                self._row_bucket(max(self.store.n_total, 1)))
            v = v & row_up[safe]
        return safe, v

    # ------------------------------------------------------- candidates

    def candidates(self, Q_sap: np.ndarray, kp: int, ef_search: int):
        sm = self.health.serve_mask()
        self.last_n_shards_down = int(self.n_shards - int(sm.sum()))
        self.last_degraded = bool(self.last_n_shards_down)
        if self.kind == "graph":
            out = self._candidates_graph(Q_sap, kp, ef_search)
        elif self.quantization is not None:
            kp2 = self.oversampled(kp)
            if self.kind == "flat":
                out = self._candidates_adc_flat(Q_sap, kp2)
            else:
                out = self._candidates_adc_ivf(Q_sap, kp2)
        elif self.kind == "flat":
            out = self._candidates_flat(Q_sap, kp)
        else:
            out = self._candidates_ivf(Q_sap, kp)
        if obs_current() is not None:
            # obs (DESIGN.md §13): one completed child span per shard
            # under the ambient filter span.  The collective computed all
            # shards' work inside one host call, so the per-shard spans
            # share the filter interval and carry the row partition each
            # shard scanned — attribution, not independent timing.
            for m in self.shard_manifest():
                child_complete(f"shard{m['shard']}", shard=m["shard"],
                               row_start=m["row_start"],
                               row_stop=m["row_stop"],
                               n_alive=m["n_alive"])
        return out

    def _candidates_adc_flat(self, Q_sap: np.ndarray, kp2: int):
        st = self.store
        nq = Q_sap.shape[0]
        bucket = int(self._adc_ok.shape[0])
        kp_eff = min(kp2, bucket)
        Q = np.asarray(Q_sap, np.float32)
        ok = self._adc_ok
        if self.last_degraded:   # mask is data: same executables (§16)
            ok = _and_ok(ok, self._sok_dev(bucket, np.int32))
        if self.quantization == "int8":
            q8 = self.adc_codebook.encode_query(Q)
            cand = _sharded_sq_topk(
                self._adc_c8, self._adc_cn, ok,
                jnp.asarray(q8), mesh=self.mesh, axis=self.axis,
                kp=kp_eff)
        else:
            lut = self.adc_codebook.lut(Q)
            cand = _sharded_pq_topk(
                self._adc_codes_t, ok, jnp.asarray(lut),
                mesh=self.mesh, axis=self.axis, kp=kp_eff)
        cand = to_host(cand, "candidates", np.int32)
        safe, valid = self._mask_alive(cand, np.ones(cand.shape, bool))
        self.last_filter_bytes = self._adc_code_bytes(bucket)
        return safe, valid, nq * st.n_total     # same accounting as the
        # f32 paths: rows present, incl. tombstones

    def _candidates_adc_ivf(self, Q_sap: np.ndarray, kp2: int):
        nq = Q_sap.shape[0]
        if self.ivf is None:                  # nothing alive to probe
            return (np.zeros((nq, kp2), np.int32),
                    np.zeros((nq, kp2), bool), 0)
        Q = np.asarray(Q_sap, np.float32)
        pools = [self.ivf.probe(q, self.nprobe) for q in Q]
        pm = self._pool_alive()
        if self.oblivious:
            bucket = int(self._adc_ok.shape[0])
            member = pool_membership(nq, pools, bucket, pool_mask=pm)
            kp_eff = min(kp2, bucket)
            if self.quantization == "int8":
                q8 = self.adc_codebook.encode_query(Q)
                ids = _sharded_sq_oblivious(
                    self._adc_c8, self._adc_cn, jnp.asarray(q8),
                    jnp.asarray(member), mesh=self.mesh, axis=self.axis,
                    kp=kp_eff)
            else:
                lut = self.adc_codebook.lut(Q)
                ids = _sharded_pq_oblivious(
                    self._adc_codes_t, jnp.asarray(lut),
                    jnp.asarray(member), mesh=self.mesh, axis=self.axis,
                    kp=kp_eff)
            ids = to_host(ids, "candidates", np.int32)
            # validity = host-side membership lookup at the merged ids
            vout = member[np.arange(nq)[:, None], np.clip(ids, 0, bucket - 1)]
            ids, vout = self._mask_alive(ids, vout)
            evals = nq * bucket + nq * self.ivf.centroids.shape[0]
            self.last_filter_bytes = (self._adc_code_bytes(bucket)
                                      + self.ivf.centroids.nbytes)
            return ids, vout, evals
        cand, valid = layout_pools(nq, pools, kp2, pool_mask=pm)
        if self.quantization == "int8":
            q8 = self.adc_codebook.encode_query(Q)
            ids, vout = _sharded_sq_pool_scan(
                self._adc_c8, self._adc_cn, jnp.asarray(q8),
                jnp.asarray(cand), jnp.asarray(valid),
                mesh=self.mesh, axis=self.axis, kp=kp2)
        else:
            lut = self.adc_codebook.lut(Q)
            ids, vout = _sharded_pq_pool_scan(
                self._adc_codes_t, jnp.asarray(lut), jnp.asarray(cand),
                jnp.asarray(valid), mesh=self.mesh, axis=self.axis,
                kp=kp2)
        evals = sum(p.size for p in pools) \
            + nq * self.ivf.centroids.shape[0]
        self.last_filter_bytes = (
            self._adc_code_bytes(sum(p.size for p in pools))
            + self.ivf.centroids.nbytes)
        return (to_host(ids, "candidates"), to_host(vout, "valid"),
                evals)

    def _candidates_flat(self, Q_sap: np.ndarray, kp: int):
        st = self.store
        nq = Q_sap.shape[0]
        bucket = int(self._C_all.shape[0])
        kp_eff = min(kp, bucket)
        Qd = jnp.asarray(np.asarray(Q_sap, np.float32))
        if self.last_degraded:
            cand = _sharded_flat_topk_ok(
                self._C_all, self._sok_dev(bucket, np.bool_), Qd,
                mesh=self.mesh, axis=self.axis, kp=kp_eff)
        else:
            cand = _sharded_flat_topk(self._C_all, Qd, mesh=self.mesh,
                                      axis=self.axis, kp=kp_eff)
        cand = to_host(cand, "candidates", np.int32)
        safe, valid = self._mask_alive(cand, np.ones(cand.shape, bool))
        self.last_filter_bytes = int(self._C_all.size) * 4
        return safe, valid, nq * st.n_total

    def _candidates_ivf(self, Q_sap: np.ndarray, kp: int):
        st = self.store
        nq = Q_sap.shape[0]
        if self.ivf is None:                  # nothing alive to probe
            return (np.zeros((nq, kp), np.int32),
                    np.zeros((nq, kp), bool), 0)
        Q = np.asarray(Q_sap, np.float32)
        pools = [self.ivf.probe(q, self.nprobe) for q in Q]
        pm = self._pool_alive()
        if self.oblivious:
            bucket = int(self._C_all.shape[0])
            member = pool_membership(nq, pools, bucket, pool_mask=pm)
            ids = to_host(_sharded_oblivious_scan(
                self._C_all, jnp.asarray(Q), jnp.asarray(member),
                mesh=self.mesh, axis=self.axis,
                kp=min(kp, bucket)), "candidates", np.int32)
            vout = member[np.arange(nq)[:, None], np.clip(ids, 0, bucket - 1)]
            ids, vout = self._mask_alive(ids, vout)
            evals = nq * bucket + nq * self.ivf.centroids.shape[0]
            self.last_filter_bytes = (bucket * st.d * 4
                                      + self.ivf.centroids.nbytes)
            return ids, vout, evals
        cand, valid = layout_pools(nq, pools, kp, pool_mask=pm)
        ids, vout = _sharded_pool_scan(
            self._C_all, jnp.asarray(Q), jnp.asarray(cand),
            jnp.asarray(valid), mesh=self.mesh, axis=self.axis, kp=kp)
        evals = sum(p.size for p in pools) \
            + nq * self.ivf.centroids.shape[0]
        self.last_filter_bytes = (sum(p.size for p in pools) * st.d * 4
                                  + self.ivf.centroids.nbytes)
        return (to_host(ids, "candidates"), to_host(vout, "valid"),
                evals)

    def _candidates_graph(self, Q_sap: np.ndarray, kp: int,
                          ef_search: int):
        """Per-shard batched traversal + cross-shard k' merge.  Each
        shard's lockstep walk returns its local top-k' with surrogate
        distances (one global codebook, so the scores are comparable
        across shards); the merged candidate list is the top-k' of the
        (nq, S*k') concatenation — the same k'-per-shard collective
        shape as the flat all-gather merge, assembled host-side because
        the traversal itself does not run under the mesh."""
        st = self.store
        Q = np.asarray(Q_sap, np.float32)
        nq = Q.shape[0]
        per = self._g_per
        kp2 = max(1, min(self.oversampled(kp), per))
        ef_eff, ef_cap, max_hops = beam_plan(kp2, max(ef_search, kp2))
        if self.quantization is None:
            qd = jnp.asarray(Q)
        elif self.quantization == "int8":
            qd = jnp.asarray(self.adc_codebook.encode_query(Q))
        else:
            qd = jnp.asarray(self.adc_codebook.lut(Q))
        sm = self.health.serve_mask()
        n_up = int(sm.sum())
        ids_p, d_p, vis_p = [], [], []
        hops_t = edges_t = 0
        for s in range(self.n_shards):
            if not sm[s]:
                continue       # dead group: no replica to walk (§16)
            lo, hi = s * per, (s + 1) * per
            if self.quantization is None:
                db = (self._C_all[lo:hi],)
            elif self.quantization == "int8":
                db = (self._adc_c8[lo:hi], self._adc_cn[lo:hi])
            else:
                db = (self._adc_codes_t[:, lo:hi],)
            cand, cand_d, visited, hops, edges = graph_topk(
                self._g_neigh0_sh[s], self._g_neigh_up_sh[s],
                self._g_ok[lo:hi], db, qd,
                jnp.int32(self._g_csrs[s].entry), jnp.int32(ef_eff),
                kp=kp2, ef_cap=ef_cap, max_hops=max_hops,
                quant=self.quantization or "f32",
                oblivious=self.oblivious)
            c = to_host(cand, "candidates", np.int32)
            ids_p.append(np.where(c >= 0, c + np.int32(lo), -1))
            d_p.append(np.where(c >= 0,
                                to_host(cand_d, "distances", np.float32),
                                np.inf))
            vis_p.append(to_host(visited, "visited"))
            hops_t += int(to_host(hops, "hops").sum())
            edges_t += int(to_host(edges, "edges").sum())
        if not ids_p:                  # every shard group is down
            self.last_n_hops = self.last_n_edges_scanned = 0
            self.last_filter_bytes = 0
            self.last_scan_trace = np.zeros((nq, 0), np.int32)
            return (np.zeros((nq, kp2), np.int32),
                    np.zeros((nq, kp2), bool), 0)
        ids = np.concatenate(ids_p, axis=1)
        dists = np.concatenate(d_p, axis=1)
        order = np.argsort(dists, axis=1, kind="stable")[:, :kp2]
        cand = np.take_along_axis(ids, order, axis=1)
        safe, valid = self._mask_alive(cand, cand >= 0)
        self.last_n_hops = hops_t
        self.last_n_edges_scanned = edges_t
        row_bytes = (st.d * 4 if self.quantization is None
                     else self.adc_codebook.code_bytes_per_vector())
        self.last_filter_bytes = (edges_t + nq * n_up) * row_bytes
        self.last_scan_trace = np.concatenate(vis_p, axis=1)
        return safe, valid, edges_t + nq * n_up

    # ----------------------------------------------------------- refine

    def refine_batch(self, C_dce_dev, cand, T, valid, k: int):
        """Engine hook: the sharded tournament replaces the single-device
        `refine_candidates` call (same semantics, same -1 fill)."""
        return _sharded_refine(C_dce_dev, cand, T, valid,
                               mesh=self.mesh, axis=self.axis, k=k)
