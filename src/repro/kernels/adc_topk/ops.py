"""Jitted public wrappers for the adc_topk kernel family.

`sq_knn` / `pq_knn` are the quantized analogues of `l2_topk.ops.knn`:
one call scans the whole code array and returns the top-k by ADC
surrogate distance.  With use_kernel=True the fused Pallas scan runs
(codes stream HBM -> VMEM once, the running top-k never leaves VMEM);
with use_kernel=False an XLA formulation of the *same ranking* runs —
the fast path on CPU hosts, where Pallas executes in interpret mode.

Both accept an optional `ok` row-validity vector: invalid rows
(padded bucket slots, tombstones — serving/runtime hands sentinel-
padded power-of-two buffers here) rank last without recompiling as
the valid count changes.

The XLA fallbacks are *chunked* scans with the same running-top-k
merge shape as `l2_topk.ops.knn` (distance block of `chunk` rows,
fold into the (nq, k) state): on CPU hosts this is ~2x faster than a
single-shot matmul + full-width top_k — the top_k over an (nq, n)
row is the bottleneck, not the arithmetic — and it never materializes
the (nq, n) distance matrix either.

The f32 fallback of `sq_knn` is bit-exact w.r.t. the int32 kernel
while the whole surrogate |cn - 2*(q8.c8)| stays below 2^24 — worst
case d <= ~346 (the cross-product alone is exact up to d <= 1040).
Beyond that, near-ties within a few ulp may round together or swap —
absorbed by the ADC oversampling + exact-refine contract (core.adc);
do not write bit-exactness parity tests at larger d.

The id block `sq_knn` / `pq_knn` return carries one more column, the
same in every row: the kernel's merge-round count (`adc_topk.
_merge_topk`), -1 from the XLA path, which runs no such merge.  It
rides the candidate read the engine makes anyway; `split_merge_rounds`
takes it off on the host, and `merge_rounds_full` (host arithmetic on
the shapes) gives the K x tiles a fixed merge would run.

`sq_pool_scan` / `pq_pool_scan` are the quantized analogues of the
engine's `_masked_pruned_scan` for IVF-pruned candidate pools
(per-query gathers — a gather workload, so they are XLA-only by
design; the Pallas path covers the streaming flat scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..common import pad_to, running_topk_scan
from . import adc_topk as _kernel
from . import ref as _ref  # noqa: F401  (parity tests import through ops)

sq_adc_topk = _kernel.sq_adc_topk
pq_adc_topk = _kernel.pq_adc_topk
INT_BIG = _kernel.INT_BIG
DEFAULT_BLOCK_N_SQ = _kernel.DEFAULT_BLOCK_N_SQ
DEFAULT_BLOCK_N_PQ = _kernel.DEFAULT_BLOCK_N_PQ
merge_rounds_full = _kernel.merge_rounds_full

DEFAULT_CHUNK = 8192


def _chunked_scan(dist_fn, n: int, nq: int, k: int, chunk: int,
                  big: float):
    """Shared fallback merge: fold `chunk`-row distance blocks into a
    running (nq, k) top-k via `kernels.common.running_topk_scan`.
    Slots whose distance never dropped below `big` (masked rows, or
    fewer than k valid rows) come back as id -1 — the same empty-slot
    convention as the fused Pallas merge."""
    best_d, best_i = running_topk_scan(dist_fn, n, nq, k, chunk)
    return best_d, jnp.where(best_d >= big, -1, best_i)


def _with_rounds(idx: jnp.ndarray, rounds) -> jnp.ndarray:
    col = jnp.full((idx.shape[0], 1), rounds, jnp.int32)
    return jnp.concatenate([idx, col], axis=1)


def split_merge_rounds(ids: np.ndarray) -> tuple[np.ndarray, int | None]:
    """Host side of the round column: (ids, merge_rounds), the count
    None from the XLA path."""
    rounds = int(ids[0, -1])
    return ids[:, :-1], (rounds if rounds >= 0 else None)


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "interpret", "use_kernel"))
def sq_knn(
    q8: jnp.ndarray,
    c8: jnp.ndarray,
    cn: jnp.ndarray,
    k: int,
    *,
    ok: jnp.ndarray | None = None,
    block_n: int = _kernel.DEFAULT_BLOCK_N_SQ,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k by int8 ADC surrogate distance cn - 2*(q8 . c8).

    q8: (nq, d) int8; c8: (n, d) int8; cn: (n,) int32; ok: optional
    (n,) row validity -> (dists (nq, k) ascending, idx (nq, k + 1)
    int32, its last column the merge-round count).  Kernel path
    returns int32 distances, fallback f32 — identical ranking for
    small-d surrogates (exactness bound in the module docstring).
    """
    nq = q8.shape[0]
    n = c8.shape[0]
    k = min(k, n)
    if ok is None:
        ok = jnp.ones((n,), jnp.int32)
    if use_kernel:
        dists, idx, rounds = _kernel.sq_adc_topk(
            q8, c8, cn, ok, k, block_n=block_n, interpret=interpret)
        return dists, _with_rounds(idx, rounds)
    chunk = min(DEFAULT_CHUNK, n)
    c8p = pad_to(c8, 0, chunk)
    cnp = pad_to(cn.astype(jnp.float32), 0, chunk)
    okp = pad_to(ok.astype(jnp.int32), 0, chunk, value=0)
    qf = q8.astype(jnp.float32)

    def dist_fn(start):
        xs = jax.lax.dynamic_slice_in_dim(c8p, start, chunk, axis=0)
        cs = jax.lax.dynamic_slice_in_dim(cnp, start, chunk, axis=0)
        os_ = jax.lax.dynamic_slice_in_dim(okp, start, chunk, axis=0)
        cross = jax.lax.dot_general(
            qf, xs.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return jnp.where(os_[None, :] > 0, cs[None, :] - 2.0 * cross,
                         jnp.float32(INT_BIG))

    dists, idx = _chunked_scan(dist_fn, n, nq, k, chunk, float(INT_BIG))
    return dists, _with_rounds(idx, -1)


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "interpret", "use_kernel"))
def pq_knn(
    lut: jnp.ndarray,
    codes_t: jnp.ndarray,
    k: int,
    *,
    ok: jnp.ndarray | None = None,
    block_n: int = _kernel.DEFAULT_BLOCK_N_PQ,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k by PQ ADC distance sum_m LUT[m, codes_t[m, i]].

    lut: (nq, m, 256) f32; codes_t: (m, n) uint8; ok: optional (n,)
    row validity -> (dists (nq, k) f32 ascending, idx (nq, k + 1)
    int32, its last column the merge-round count).
    """
    nq = lut.shape[0]
    n = codes_t.shape[1]
    k = min(k, n)
    if ok is None:
        ok = jnp.ones((n,), jnp.int32)
    if use_kernel:
        dists, idx, rounds = _kernel.pq_adc_topk(
            lut, codes_t, ok, k, block_n=block_n, interpret=interpret)
        return dists, _with_rounds(idx, rounds)
    chunk = min(DEFAULT_CHUNK, n)
    ctp = pad_to(codes_t, 1, chunk)
    okp = pad_to(ok.astype(jnp.int32), 0, chunk, value=0)

    def dist_fn(start):
        cs = jax.lax.dynamic_slice_in_dim(ctp, start, chunk, axis=1)
        os_ = jax.lax.dynamic_slice_in_dim(okp, start, chunk, axis=0)
        cc = jnp.broadcast_to(cs.astype(jnp.int32)[None],
                              (nq,) + cs.shape)
        g = jnp.take_along_axis(lut, cc, axis=2)    # (nq, m, chunk)
        return jnp.where(os_[None, :] > 0, g.sum(axis=1), jnp.inf)

    dists, idx = _chunked_scan(dist_fn, n, nq, k, chunk, float(jnp.inf))
    return dists, _with_rounds(idx, -1)


@functools.partial(jax.jit, static_argnames=("kp",))
def sq_pool_scan(c8_dev, cn_dev, q8, cand, valid, kp: int):
    """IVF-pruned int8 ADC scan: per-query gather over probed rows.

    c8_dev: (n, d) int8 codes; cn_dev: (n,) int32; q8: (nq, d) int8;
    cand/valid: (nq, L) pool layout (search_engine.layout_pools)
    -> (ids (nq, kp), valid (nq, kp)) — same contract as the engine's
    `_masked_pruned_scan`.
    """
    rows = jnp.take(c8_dev, cand, axis=0).astype(jnp.float32)
    cn_c = jnp.take(cn_dev, cand).astype(jnp.float32)
    cross = jnp.einsum("qld,qd->ql", rows, q8.astype(jnp.float32))
    d = jnp.where(valid, cn_c - 2.0 * cross, jnp.inf)
    kp = min(kp, d.shape[1])
    _, pos = jax.lax.top_k(-d, kp)
    return (jnp.take_along_axis(cand, pos, axis=1),
            jnp.take_along_axis(valid, pos, axis=1))


@functools.partial(jax.jit, static_argnames=("kp",))
def pq_pool_scan(codes_t, lut, cand, valid, kp: int):
    """IVF-pruned PQ ADC scan (LUT gather over probed rows).

    codes_t: (m, n) uint8; lut: (nq, m, 256) f32; cand/valid: (nq, L)
    -> (ids (nq, kp), valid (nq, kp)).
    """
    cc = jnp.take(codes_t, cand, axis=1)            # (m, nq, L)
    cc = jnp.transpose(cc, (1, 0, 2)).astype(jnp.int32)
    g = jnp.take_along_axis(lut, cc, axis=2)        # (nq, m, L)
    d = jnp.where(valid, g.sum(axis=1), jnp.inf)
    kp = min(kp, d.shape[1])
    _, pos = jax.lax.top_k(-d, kp)
    return (jnp.take_along_axis(cand, pos, axis=1),
            jnp.take_along_axis(valid, pos, axis=1))


@functools.partial(jax.jit, static_argnames=("kp",))
def sq_oblivious_scan(c8_dev, cn_dev, q8, member, kp: int):
    """Scan-oblivious int8 ADC IVF scan (DESIGN.md §14): surrogate
    distances over EVERY code row, masked by per-query pool membership.

    c8_dev: (n, d) int8 codes; cn_dev: (n,) int32; q8: (nq, d) int8;
    member: (nq, n) bool (search_engine.pool_membership) -> (ids
    (nq, kp), valid (nq, kp)).  One constant-shape matmul over the full
    code bucket — no data-dependent gather, so the access pattern
    reveals nothing about the probes.  Member rows get bit-identical
    cn - 2*(q8.c8) values to `sq_pool_scan` (exact int accumulation in
    f32 below 2^24), so the candidate set matches the pruned scan.
    """
    cross = jax.lax.dot_general(
        q8.astype(jnp.float32), c8_dev.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    d = cn_dev.astype(jnp.float32)[None, :] - 2.0 * cross
    d = jnp.where(member, d, jnp.inf)
    kp = min(kp, d.shape[1])
    _, pos = jax.lax.top_k(-d, kp)
    return (pos.astype(jnp.int32),
            jnp.take_along_axis(member, pos, axis=1))


@functools.partial(jax.jit, static_argnames=("kp",))
def pq_oblivious_scan(codes_t, lut, member, kp: int):
    """Scan-oblivious PQ ADC IVF scan: full-bucket LUT accumulation
    masked by per-query pool membership.

    codes_t: (m, n) uint8; lut: (nq, m, 256) f32; member: (nq, n) bool
    -> (ids (nq, kp), valid (nq, kp)).  Same distance values as
    `pq_pool_scan` for member rows, constant access pattern.
    """
    nq = lut.shape[0]
    cc = jnp.broadcast_to(codes_t.astype(jnp.int32)[None],
                          (nq,) + codes_t.shape)
    g = jnp.take_along_axis(lut, cc, axis=2)        # (nq, m, n)
    d = jnp.where(member, g.sum(axis=1), jnp.inf)
    kp = min(kp, d.shape[1])
    _, pos = jax.lax.top_k(-d, kp)
    return (pos.astype(jnp.int32),
            jnp.take_along_axis(member, pos, axis=1))
