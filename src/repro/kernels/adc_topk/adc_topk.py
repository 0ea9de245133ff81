"""Pallas TPU kernels: fused quantized-ADC scan + running top-k.

The filter-phase successor to `l2_topk` for quantized collections
(DESIGN.md §11): distances are computed *from codes* —

  int8 (SQ): cross = q8 . c8 on the MXU's native s8 x s8 -> s32 path,
             surrogate distance  cn - 2*cross  in pure int32;
  pq8  (PQ): per-query LUT (built host-side, resident in VMEM) gathered
             per code via a one-hot MXU matmul — the TPU formulation of
             Faiss-style ADC scanning: a (m*256, bn) one-hot of the code
             tile contracts against the (nq, m*256) flattened LUT, so
             the gather rides the systolic array instead of scatter/
             gather units;

and the per-tile distance block is folded into a *running partial
top-k* kept in the output refs (constant index_map -> the (nq, K)
state lives in VMEM across the whole sequential grid).  Neither the
decoded vectors nor the (nq, chunk) distance block ever round-trips
through HBM — HBM traffic is exactly: codes + the (1, n) row-validity
stream once, plus the final (nq, K) result.

Row validity is *data*, not shape: the `ok` input masks padded bucket
slots and tombstoned rows (serving/runtime mutable stores hand the
kernel sentinel-padded power-of-two buffers), so growing deltas reuse
executables instead of recompiling per row count.

The merge is K rounds of extract-min over the concatenated
[running-K | tile] buffer — pure VPU min/compare/select ops (no
lax.sort / lax.top_k inside the kernel), each round masking the
selected column, so the state stays ascending by construction.

VMEM per grid step (defaults): SQ — q8 (128 x d_p) + c8 tile
(512 x d_p) int8 + int32 state/scratch ~ d_p KiB-scale; PQ — LUT
(128 x m_p*256) f32 = 4 MiB + one-hot (m_p*256 x 128) f32 = 4 MiB.
Both comfortably inside ~16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..common import HIGHEST, LANE, interpret_default, pad_to, padded_size

DEFAULT_BLOCK_N_SQ = 512
DEFAULT_BLOCK_N_PQ = 128
INT8_SUBLANE = 32            # min int8/uint8 tile is (32, 128)
PQ_K = 256                   # centroids per subspace (1-byte codes)

INT_BIG = np.int32(2 ** 30)  # sentinel surrogate distance (int32 path)


def _merge_topk(best_d_ref, best_i_ref, d_blk, i_blk, big):
    """Fold a (bq, bn) distance tile into the (bq, K) running top-k.

    K rounds of extract-min over [running | tile]: per round, the
    row-wise min and its first column are found with VPU reductions,
    written into output column t, and masked out of the buffer.  Ties
    resolve to the first column, i.e. the lowest global id (running
    entries precede the tile, and tile columns are ascending ids) —
    the same tie order as `jax.lax.top_k` over the full distance row.
    Exhausted rounds (min already `big`: fewer than K valid rows seen)
    emit id -1, never a duplicate of an already-extracted id — callers
    treat negative ids as empty slots.
    """
    prev_d = best_d_ref[...]
    prev_i = best_i_ref[...]
    bq, K = prev_d.shape
    cat_d = jnp.concatenate([prev_d, d_blk], axis=1)
    cat_i = jnp.concatenate([prev_i, i_blk], axis=1)
    W = cat_d.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, W), 1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, (bq, K), 1)

    def round_(t, carry):
        cat, out_d, out_i = carry
        m = jnp.min(cat, axis=1, keepdims=True)                 # (bq, 1)
        first = jnp.min(jnp.where(cat == m, cols, W), axis=1,
                        keepdims=True)
        sel = cols == first                                      # one-hot
        mi = jnp.max(jnp.where(sel, cat_i, -1), axis=1, keepdims=True)
        mi = jnp.where(m >= big, -1, mi)         # exhausted: empty slot
        out_d = jnp.where(kcols == t, m, out_d)
        out_i = jnp.where(kcols == t, mi, out_i)
        return jnp.where(sel, big, cat), out_d, out_i

    _, out_d, out_i = jax.lax.fori_loop(
        0, K, round_, (cat_d, jnp.full_like(prev_d, big),
                       jnp.full_like(prev_i, -1)))
    best_d_ref[...] = out_d
    best_i_ref[...] = out_i


def _sq_adc_kernel(q_ref, c_ref, cn_ref, ok_ref, best_d_ref, best_i_ref):
    """One code tile of the int8 scan: s8 MXU dot + top-k merge.

    q_ref: (nq_p, d_p) int8;  c_ref: (bn, d_p) int8;
    cn_ref/ok_ref: (1, bn) int32;  best_*_ref: (nq_p, K) int32 state.
    """
    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        best_d_ref[...] = jnp.full(best_d_ref.shape, INT_BIG, jnp.int32)
        best_i_ref[...] = jnp.full(best_i_ref.shape, -1, jnp.int32)

    cross = jax.lax.dot_general(
        q_ref[...], c_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    d_blk = jnp.where(ok_ref[...] > 0, cn_ref[...] - 2 * cross, INT_BIG)
    bn = d_blk.shape[1]
    gcol = pi * bn + jax.lax.broadcasted_iota(jnp.int32, d_blk.shape, 1)
    _merge_topk(best_d_ref, best_i_ref, d_blk, gcol, INT_BIG)


def _pq_adc_kernel(lut_ref, codes_ref, ok_ref, best_d_ref, best_i_ref):
    """One code tile of the PQ scan: one-hot MXU LUT gather + merge.

    lut_ref: (nq_p, m_p*256) f32 flattened per-query tables (padded
    subspaces hold zeros, so their gathered term vanishes);
    codes_ref: (m_p, bn) uint8 transposed code tile; ok_ref: (1, bn)
    int32 row validity.
    """
    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        best_d_ref[...] = jnp.full(best_d_ref.shape, jnp.inf, jnp.float32)
        best_i_ref[...] = jnp.full(best_i_ref.shape, -1, jnp.int32)

    codes = codes_ref[...].astype(jnp.int32)          # (m_p, bn)
    m_p, bn = codes.shape
    rem = jax.lax.broadcasted_iota(jnp.int32, (m_p, PQ_K, bn), 1)
    onehot = (codes[:, None, :] == rem).astype(jnp.float32)
    onehot = onehot.reshape(m_p * PQ_K, bn)
    d_blk = jax.lax.dot_general(
        lut_ref[...], onehot,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )                                                  # (nq_p, bn)
    d_blk = jnp.where(ok_ref[...] > 0, d_blk, jnp.inf)
    gcol = pi * bn + jax.lax.broadcasted_iota(jnp.int32, d_blk.shape, 1)
    _merge_topk(best_d_ref, best_i_ref, d_blk, gcol, jnp.float32(jnp.inf))


def _pad_ok(ok: jnp.ndarray, n: int, block_n: int) -> jnp.ndarray:
    """(n,) validity -> (1, n_p) int32 with padded slots invalid."""
    row = ok.astype(jnp.int32)[None, :]
    return pad_to(row, 1, block_n, value=0)


@functools.partial(
    jax.jit, static_argnames=("kp", "block_n", "interpret"))
def sq_adc_topk(
    q8: jnp.ndarray,
    c8: jnp.ndarray,
    cn: jnp.ndarray,
    ok: jnp.ndarray,
    kp: int,
    *,
    block_n: int = DEFAULT_BLOCK_N_SQ,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused int8 ADC scan + top-kp.

    q8: (nq, d) int8; c8: (n, d) int8; cn: (n,) int32; ok: (n,) row
    validity -> (dists (nq, kp) int32 ascending, idx (nq, kp) int32).
    Slots beyond the valid-row count come back as id -1 / dist INT_BIG.
    """
    if interpret is None:
        interpret = interpret_default()
    nq, _ = q8.shape
    n = c8.shape[0]
    kp = min(kp, n)
    K = padded_size(max(kp, 1), LANE)

    block_n = max(LANE, min(block_n, padded_size(n, LANE)))
    Qp = pad_to(pad_to(q8, 0, INT8_SUBLANE), 1, LANE)
    Cp = pad_to(pad_to(c8, 0, block_n), 1, LANE)
    cnp = pad_to(cn[None, :].astype(jnp.int32), 1, block_n)
    okp = _pad_ok(ok, n, block_n)
    nq_p, d_p = Qp.shape
    n_p = Cp.shape[0]

    grid = (n_p // block_n,)
    best_d, best_i = pl.pallas_call(
        _sq_adc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nq_p, d_p), lambda i: (0, 0)),
            pl.BlockSpec((block_n, d_p), lambda i: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((nq_p, K), lambda i: (0, 0)),
            pl.BlockSpec((nq_p, K), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq_p, K), jnp.int32),
            jax.ShapeDtypeStruct((nq_p, K), jnp.int32),
        ],
        interpret=interpret,
    )(Qp, Cp, cnp, okp)
    return best_d[:nq, :kp], best_i[:nq, :kp]


@functools.partial(
    jax.jit, static_argnames=("kp", "block_n", "interpret"))
def pq_adc_topk(
    lut: jnp.ndarray,
    codes_t: jnp.ndarray,
    ok: jnp.ndarray,
    kp: int,
    *,
    block_n: int = DEFAULT_BLOCK_N_PQ,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused PQ ADC scan + top-kp.

    lut: (nq, m, 256) f32 per-query tables; codes_t: (m, n) uint8
    transposed codes; ok: (n,) row validity
    -> (dists (nq, kp) f32 ascending, idx (nq, kp) int32).
    """
    if interpret is None:
        interpret = interpret_default()
    nq, m, pqk = lut.shape
    assert pqk == PQ_K
    n = codes_t.shape[1]
    kp = min(kp, n)
    K = padded_size(max(kp, 1), LANE)

    block_n = max(LANE, min(block_n, padded_size(n, LANE)))
    # pad subspaces: zero LUT rows + code 0 -> padded term gathers 0.0
    lut_p = pad_to(pad_to(lut.astype(jnp.float32), 1, INT8_SUBLANE), 0, 8)
    nq_p, m_p, _ = lut_p.shape
    lut_flat = lut_p.reshape(nq_p, m_p * PQ_K)
    Cp = pad_to(pad_to(codes_t, 0, INT8_SUBLANE), 1, block_n)
    okp = _pad_ok(ok, n, block_n)
    n_p = Cp.shape[1]

    grid = (n_p // block_n,)
    best_d, best_i = pl.pallas_call(
        _pq_adc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nq_p, m_p * PQ_K), lambda i: (0, 0)),
            pl.BlockSpec((m_p, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((nq_p, K), lambda i: (0, 0)),
            pl.BlockSpec((nq_p, K), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq_p, K), jnp.float32),
            jax.ShapeDtypeStruct((nq_p, K), jnp.int32),
        ],
        interpret=interpret,
    )(lut_flat, Cp, okp)
    return best_d[:nq, :kp], best_i[:nq, :kp]
