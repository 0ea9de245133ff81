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

The merge is a threshold-gated insertion: only tile entries strictly
below the row's worst kept distance are candidates, and a data-
dependent loop inserts them one per round, smallest first, at their
rank in the ascending state (a one-lane shift right, the worst entry
dropping off).  Pure VPU min/compare/select ops and one lane rotate
(no lax.sort / lax.top_k inside the kernel).  A tile none of whose
entries beats its row's threshold costs one compare and one row
reduction; over a corpus in no particular order the rounds per tile
fall off as ~K/t, so the whole merge runs about 1% of the K rounds
per tile a fixed extract-min would.  The kernel counts the rounds it
ran (`merge_rounds`): a third output, summed over the grid.

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
from jax.experimental.pallas import tpu as pltpu

from ..common import HIGHEST, LANE, interpret_default, pad_to, padded_size

DEFAULT_BLOCK_N_SQ = 512
DEFAULT_BLOCK_N_PQ = 128
INT8_SUBLANE = 32            # min int8/uint8 tile is (32, 128)
PQ_K = 256                   # centroids per subspace (1-byte codes)

INT_BIG = np.int32(2 ** 30)  # sentinel surrogate distance (int32 path)


def _merge_topk(best_d_ref, best_i_ref, rounds_ref, d_blk, base, big, nq):
    """Fold a (bq, bn) distance tile into the (bq, K) running top-k.

    The state is ascending by (distance, id) and holds the K smallest
    pairs seen so far; empty slots are (big, -1).  Tile entries below
    the row's worst kept distance are the candidates.  Each round takes
    every row's smallest candidate `m` and its first column (ties go to
    the lowest id, as in `jax.lax.top_k` over the full distance row)
    and, where `m` is still below the row's worst, inserts it after the
    kept entries <= m: kept ids are lower (earlier tiles, or earlier
    columns of this one), so (distance, id) order holds and an entry
    equal to the worst never enters.  A row whose candidate does not
    enter is done with the tile.  The loop runs until no row has a
    candidate left: at most min(bn, K) + 1 rounds, none when the tile
    holds nothing that can enter.  Tile columns are global ids
    base + col; rows from nq on pad the query block and merge nothing;
    the rounds run are added to `rounds_ref`.
    """
    state_d = best_d_ref[...]
    state_i = best_i_ref[...]
    bq, K = state_d.shape
    bn = d_blk.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, (bq, K), 1)
    live = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0) < nq
    cand = jnp.where(live & (d_blk < state_d[:, K - 1:]), d_blk, big)
    m0 = jnp.min(cand, axis=1, keepdims=True)

    def more(carry):
        return jnp.min(carry[3]) < big

    def round_(carry):
        cand, sd, si, m, n = carry
        first = jnp.min(jnp.where(cand == m, cols, bn), axis=1,
                        keepdims=True)
        # rank among the kept entries; K where m is not below the worst
        pos = jnp.sum((sd <= m).astype(jnp.int32), axis=1, keepdims=True)
        keep = kcols < pos
        sd = jnp.where(keep, sd, jnp.where(kcols == pos, m,
                                           pltpu.roll(sd, 1, 1)))
        si = jnp.where(keep, si, jnp.where(kcols == pos, base + first,
                                           pltpu.roll(si, 1, 1)))
        cand = jnp.where((pos < K) & (cols != first), cand, big)
        return (cand, sd, si, jnp.min(cand, axis=1, keepdims=True), n + 1)

    _, state_d, state_i, _, n = jax.lax.while_loop(
        more, round_, (cand, state_d, state_i, m0, jnp.int32(0)))
    best_d_ref[...] = state_d
    best_i_ref[...] = state_i
    rounds_ref[...] += n


def _init_state(best_d_ref, best_i_ref, rounds_ref, big):
    best_d_ref[...] = jnp.full(best_d_ref.shape, big, best_d_ref.dtype)
    best_i_ref[...] = jnp.full(best_i_ref.shape, -1, jnp.int32)
    rounds_ref[...] = jnp.zeros(rounds_ref.shape, jnp.int32)


def _sq_adc_kernel(q_ref, c_ref, cn_ref, ok_ref, best_d_ref, best_i_ref,
                   rounds_ref, *, nq):
    """One code tile of the int8 scan: s8 MXU dot + top-k merge.

    q_ref: (nq_p, d_p) int8;  c_ref: (bn, d_p) int8;
    cn_ref/ok_ref: (1, bn) int32;  best_*_ref: (nq_p, K) int32 state;
    rounds_ref: (1, LANE) int32 merge-round count.
    """
    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        _init_state(best_d_ref, best_i_ref, rounds_ref, INT_BIG)

    cross = jax.lax.dot_general(
        q_ref[...], c_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    d_blk = jnp.where(ok_ref[...] > 0, cn_ref[...] - 2 * cross, INT_BIG)
    _merge_topk(best_d_ref, best_i_ref, rounds_ref, d_blk,
                pi * d_blk.shape[1], INT_BIG, nq)


def _pq_adc_kernel(lut_ref, codes_ref, ok_ref, best_d_ref, best_i_ref,
                   rounds_ref, *, nq):
    """One code tile of the PQ scan: one-hot MXU LUT gather + merge.

    lut_ref: (nq_p, m_p*256) f32 flattened per-query tables (padded
    subspaces hold zeros, so their gathered term vanishes);
    codes_ref: (m_p, bn) uint8 transposed code tile; ok_ref: (1, bn)
    int32 row validity.
    """
    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        _init_state(best_d_ref, best_i_ref, rounds_ref, jnp.inf)

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
    _merge_topk(best_d_ref, best_i_ref, rounds_ref, d_blk, pi * bn,
                jnp.float32(jnp.inf), nq)


def _pad_ok(ok: jnp.ndarray, n: int, block_n: int) -> jnp.ndarray:
    """(n,) validity -> (1, n_p) int32 with padded slots invalid."""
    row = ok.astype(jnp.int32)[None, :]
    return pad_to(row, 1, block_n, value=0)


def _plan(n: int, kp: int, block_n: int) -> tuple[int, int, int]:
    """(kp clamped to n, state width K, tile rows) of a scan over n rows."""
    kp = min(kp, n)
    return (kp, padded_size(max(kp, 1), LANE),
            max(LANE, min(block_n, padded_size(n, LANE))))


def merge_rounds_full(n: int, kp: int, block_n: int) -> int:
    """Merge rounds of a fixed K-per-tile merge over n rows: the count a
    kernel's `merge_rounds` is read against (K x tiles)."""
    _, K, block_n = _plan(n, kp, block_n)
    return K * (padded_size(n, block_n) // block_n)


def _out_specs(nq_p: int, K: int, dist_dtype):
    state = pl.BlockSpec((nq_p, K), lambda i: (0, 0))
    count = pl.BlockSpec((1, LANE), lambda i: (0, 0))
    return ([state, state, count],
            [jax.ShapeDtypeStruct((nq_p, K), dist_dtype),
             jax.ShapeDtypeStruct((nq_p, K), jnp.int32),
             jax.ShapeDtypeStruct((1, LANE), jnp.int32)])


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
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused int8 ADC scan + top-kp.

    q8: (nq, d) int8; c8: (n, d) int8; cn: (n,) int32; ok: (n,) row
    validity -> (dists (nq, kp) int32 ascending, idx (nq, kp) int32,
    merge_rounds () int32).  Slots beyond the valid-row count come back
    as id -1 / dist INT_BIG.
    """
    if interpret is None:
        interpret = interpret_default()
    n = c8.shape[0]
    kp, K, block_n = _plan(n, kp, block_n)
    nq = q8.shape[0]
    Qp = pad_to(pad_to(q8, 0, INT8_SUBLANE), 1, LANE)
    Cp = pad_to(pad_to(c8, 0, block_n), 1, LANE)
    cnp = pad_to(cn[None, :].astype(jnp.int32), 1, block_n)
    okp = _pad_ok(ok, n, block_n)
    nq_p, d_p = Qp.shape
    n_p = Cp.shape[0]

    out_specs, out_shape = _out_specs(nq_p, K, jnp.int32)
    best_d, best_i, rounds = pl.pallas_call(
        functools.partial(_sq_adc_kernel, nq=nq),
        grid=(n_p // block_n,),
        in_specs=[
            pl.BlockSpec((nq_p, d_p), lambda i: (0, 0)),
            pl.BlockSpec((block_n, d_p), lambda i: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(Qp, Cp, cnp, okp)
    return best_d[:nq, :kp], best_i[:nq, :kp], rounds[0, 0]


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
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused PQ ADC scan + top-kp.

    lut: (nq, m, 256) f32 per-query tables; codes_t: (m, n) uint8
    transposed codes; ok: (n,) row validity
    -> (dists (nq, kp) f32 ascending, idx (nq, kp) int32,
    merge_rounds () int32).
    """
    if interpret is None:
        interpret = interpret_default()
    nq, m, pqk = lut.shape
    assert pqk == PQ_K
    n = codes_t.shape[1]
    kp, K, block_n = _plan(n, kp, block_n)
    # pad subspaces: zero LUT rows + code 0 -> padded term gathers 0.0
    lut_p = pad_to(pad_to(lut.astype(jnp.float32), 1, INT8_SUBLANE), 0, 8)
    nq_p, m_p, _ = lut_p.shape
    lut_flat = lut_p.reshape(nq_p, m_p * PQ_K)
    Cp = pad_to(pad_to(codes_t, 0, INT8_SUBLANE), 1, block_n)
    okp = _pad_ok(ok, n, block_n)
    n_p = Cp.shape[1]

    out_specs, out_shape = _out_specs(nq_p, K, jnp.float32)
    best_d, best_i, rounds = pl.pallas_call(
        functools.partial(_pq_adc_kernel, nq=nq),
        grid=(n_p // block_n,),
        in_specs=[
            pl.BlockSpec((nq_p, m_p * PQ_K), lambda i: (0, 0)),
            pl.BlockSpec((m_p, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(lut_flat, Cp, okp)
    return best_d[:nq, :kp], best_i[:nq, :kp], rounds[0, 0]
