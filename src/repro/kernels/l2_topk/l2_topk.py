"""Pallas TPU kernel: fused batched squared-L2 distance tiles.

This is the *filter-phase* hot-spot of the paper's scheme (and the
brute-force / IVF scan): distances between encrypted queries and DCPE
ciphertexts.  TPU adaptation: the one-at-a-time C++ distance loop becomes
``||q||^2 - 2 q.x + ||x||^2`` where the cross term is an MXU matmul over
(block_q x d) x (d x block_n) VMEM tiles; norms are rank-1 broadcast adds
fused into the same kernel.

VMEM budget per grid step (block_q = block_n = 128, d <= 4096 padded to a
lane multiple): 2 * 128*4096*4B = 4 MiB of operand tiles + 64 KiB out —
comfortably inside the ~16 MiB v5e VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import HIGHEST, LANE, interpret_default, pad_to, padded_size

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_N = 128


def _l2_tile_kernel(q_ref, x_ref, qn_ref, xn_ref, out_ref):
    """One (block_q, block_n) distance tile.

    q_ref: (bq, d) query tile;      x_ref: (bn, d) database tile
    qn_ref: (bq, 1) query norms;    xn_ref: (1, bn) database norms
    out_ref: (bq, bn) squared distances
    """
    cross = jax.lax.dot_general(
        q_ref[...], x_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )
    out_ref[...] = qn_ref[...] - 2.0 * cross + xn_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "interpret"))
def pairwise_sq_dists(
    Q: jnp.ndarray,
    X: jnp.ndarray,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """All-pairs ||q - x||^2 via the Pallas tile kernel.

    Q: (nq, d), X: (n, d)  ->  (nq, n) float32.
    """
    if interpret is None:
        interpret = interpret_default()
    nq, d = Q.shape
    n = X.shape[0]
    Qf = Q.astype(jnp.float32)
    Xf = X.astype(jnp.float32)
    qn = (Qf * Qf).sum(-1, keepdims=True)            # (nq, 1)
    xn = (Xf * Xf).sum(-1)[None, :]                  # (1, n)

    # Hardware-aligned padding: zero-padding rows adds zero-norm phantom
    # vectors whose distances land in sliced-away rows/cols.
    Qp = pad_to(pad_to(Qf, 0, block_q), 1, LANE)
    Xp = pad_to(pad_to(Xf, 0, block_n), 1, LANE)
    qnp_ = pad_to(qn, 0, block_q)
    xnp_ = pad_to(xn, 1, block_n)
    nq_p, d_p = Qp.shape
    n_p = Xp.shape[0]

    grid = (nq_p // block_q, n_p // block_n)
    out = pl.pallas_call(
        _l2_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d_p), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d_p), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq_p, n_p), jnp.float32),
        interpret=interpret,
    )(Qp, Xp, qnp_, xnp_)
    return out[:nq, :n]
