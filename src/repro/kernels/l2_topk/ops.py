"""Jitted public wrappers around the l2_topk Pallas kernel.

`knn` streams the database through the distance kernel tile-by-tile and
keeps a running top-k (the HBM-resident database never materializes an
(nq, n) distance matrix) — the TPU analogue of the paper's linear scan with
a max-heap, restructured as a chunked merge so it is O(n/chunk) sequential
steps instead of O(n).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import running_topk_scan
from . import l2_topk as _kernel
from . import ref as _ref

pairwise_sq_dists = _kernel.pairwise_sq_dists


@functools.partial(
    jax.jit, static_argnames=("k", "chunk", "interpret", "use_kernel"))
def knn(
    Q: jnp.ndarray,
    X: jnp.ndarray,
    k: int,
    *,
    chunk: int = 4096,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact k-NN of each query against X.

    Q: (nq, d), X: (n, d)  ->  (dists (nq, k) ascending, idx (nq, k)).
    Scans X in `chunk`-row tiles; per tile the Pallas kernel produces the
    distance block and a top-k merge folds it into the running state.
    """
    nq, _ = Q.shape
    n = X.shape[0]
    k = min(k, n)
    chunk = min(chunk, n)
    n_chunks = -(-n // chunk)
    n_pad = n_chunks * chunk
    Xp = jnp.pad(X, ((0, n_pad - n), (0, 0)))

    kernel_fn = pairwise_sq_dists if use_kernel else _ref.pairwise_sq_dists

    # Hoisted loop invariants: the in-chunk column offsets (the mask is
    # one add+compare against them per step, never a fresh arange).
    # The running-top-k merge itself — including the pos<k id mapping
    # that avoids materializing an (nq, chunk) id block — is the shared
    # `running_topk_scan` (kernels/common.py), one copy for this scan
    # and the adc_topk fallbacks.
    col = jnp.arange(chunk, dtype=jnp.int32)[None, :]

    def dist_fn(start):
        xs = jax.lax.dynamic_slice_in_dim(Xp, start, chunk, axis=0)
        if use_kernel:
            d_blk = kernel_fn(Q, xs, interpret=interpret)
        else:
            d_blk = kernel_fn(Q, xs)
        return jnp.where(start + col < n, d_blk, jnp.inf)

    best_d, best_i = running_topk_scan(dist_fn, n_pad, nq, k, chunk)
    return best_d, best_i.astype(jnp.int32)
