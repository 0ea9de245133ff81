"""Pallas TPU kernel: batched DCE DistanceComp tiles (refine-phase hot-spot).

The paper's refine phase walks a max-heap doing one O(d) DistanceComp at a
time.  TPU adaptation (DESIGN.md §3): we compute the *pairwise Z matrix*
of a candidate set in MXU tiles,

    Z[i, j] = (C_i1 ∘ t) . C_j3  -  (C_i2 ∘ t) . C_j4 ,

then rank candidates by win counts — an exact total order because DCE
comparisons are exact (Theorem 3).  Two fused element-wise-scaled matmuls
per tile; the trapdoor scaling (C1 * t) is fused into the kernel rather
than materialized in HBM.

VMEM per grid step (block 128, D = 2d+16 padded to lane multiple; d=960 →
D=2048): 4 operand tiles * 128*2048*4B = 4 MiB + t (8 KiB) + out (64 KiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import HIGHEST, LANE, interpret_default, pad_to, padded_size

DEFAULT_BLOCK = 128


def _z_tile_kernel(c1_ref, c2_ref, c3_ref, c4_ref, t_ref, out_ref):
    """One (block_i, block_j) tile of the Z matrix."""
    t = t_ref[...]                       # (1, D)
    left1 = c1_ref[...] * t              # fused trapdoor scaling
    left2 = c2_ref[...] * t
    term1 = jax.lax.dot_general(
        left1, c3_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    term2 = jax.lax.dot_general(
        left2, c4_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    out_ref[...] = term1 - term2


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def z_matrix(
    C: jnp.ndarray,
    t: jnp.ndarray,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """All-pairs DCE Z-scores via Pallas tiles.  C: (n, 4, D), t: (D,)."""
    if interpret is None:
        interpret = interpret_default()
    n, four, D = C.shape
    assert four == 4
    Cf = C.astype(jnp.float32)
    tf = t.astype(jnp.float32)[None, :]          # (1, D)

    Cp = pad_to(pad_to(Cf, 0, block), 2, LANE)
    tp = pad_to(tf, 1, LANE)
    n_p, _, D_p = Cp.shape
    comps = [Cp[:, i, :] for i in range(4)]      # (n_p, D_p) each

    grid = (n_p // block, n_p // block)
    out = pl.pallas_call(
        _z_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, D_p), lambda i, j: (i, 0)),   # C1 rows
            pl.BlockSpec((block, D_p), lambda i, j: (i, 0)),   # C2 rows
            pl.BlockSpec((block, D_p), lambda i, j: (j, 0)),   # C3 cols
            pl.BlockSpec((block, D_p), lambda i, j: (j, 0)),   # C4 cols
            pl.BlockSpec((1, D_p), lambda i, j: (0, 0)),       # trapdoor
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_p, n_p), jnp.float32),
        interpret=interpret,
    )(comps[0], comps[1], comps[2], comps[3], tp)
    return out[:n, :n]


def _z_tile_kernel_batched(c1_ref, c2_ref, c3_ref, c4_ref, t_ref, out_ref):
    """One (1, block_i, block_j) tile of the batched Z tensor.

    Identical math to `_z_tile_kernel`, with a leading batch grid dim
    selecting which query's candidate set and trapdoor are resident.
    """
    t = t_ref[0]                         # (1, D)
    left1 = c1_ref[0] * t                # fused trapdoor scaling
    left2 = c2_ref[0] * t
    term1 = jax.lax.dot_general(
        left1, c3_ref[0], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    term2 = jax.lax.dot_general(
        left2, c4_ref[0], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    out_ref[0] = term1 - term2


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def batched_z_matrix(
    C: jnp.ndarray,
    T: jnp.ndarray,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-query all-pairs Z tensors for a batch of candidate sets.

    C: (B, n, 4, D) candidate ciphertexts, T: (B, D) trapdoors
    -> (B, n, n) float32.  One pallas_call with grid (B, n/block, n/block);
    each grid step touches one query's tiles, so VMEM per step matches the
    unbatched kernel (refine candidate sets are small: n = k' ~ O(100)).
    """
    if interpret is None:
        interpret = interpret_default()
    B, n, four, D = C.shape
    assert four == 4
    Cf = C.astype(jnp.float32)
    Tf = T.astype(jnp.float32)

    blk = min(block, max(LANE, padded_size(n, LANE)))
    Cp = pad_to(pad_to(Cf, 1, blk), 3, LANE)
    # trapdoors as (B, 1, D_p): a (1, 1, D_p) block keeps the last two
    # block dims equal to the array's, the TPU tiling rule a (1, D_p)
    # block over a (B, D_p) array breaks for every B > 1
    Tp = pad_to(Tf, 1, LANE)[:, None, :]
    _, n_p, _, D_p = Cp.shape
    comps = [Cp[:, :, i, :] for i in range(4)]   # (B, n_p, D_p) each

    grid = (B, n_p // blk, n_p // blk)
    out = pl.pallas_call(
        _z_tile_kernel_batched,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk, D_p), lambda b, i, j: (b, i, 0)),  # C1 rows
            pl.BlockSpec((1, blk, D_p), lambda b, i, j: (b, i, 0)),  # C2 rows
            pl.BlockSpec((1, blk, D_p), lambda b, i, j: (b, j, 0)),  # C3 cols
            pl.BlockSpec((1, blk, D_p), lambda b, i, j: (b, j, 0)),  # C4 cols
            pl.BlockSpec((1, 1, D_p), lambda b, i, j: (b, 0, 0)),    # trapdoor
        ],
        out_specs=pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, n_p, n_p), jnp.float32),
        interpret=interpret,
    )(comps[0], comps[1], comps[2], comps[3], Tp)
    return out[:, :n, :n]
