"""Jitted public wrappers for the dce_comp kernel: the tournament refine."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dce_comp as _kernel
from . import ref as _ref

z_matrix = _kernel.z_matrix
batched_z_matrix = _kernel.batched_z_matrix


@functools.partial(
    jax.jit, static_argnames=("k", "block", "interpret", "use_kernel"))
def top_k_by_wins(
    C: jnp.ndarray,
    t: jnp.ndarray,
    k: int,
    *,
    block: int = _kernel.DEFAULT_BLOCK,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """Exact top-k of a DCE-encrypted candidate set (refine phase).

    Ranks the n candidates by pairwise-comparison win counts computed from
    the Pallas Z-matrix kernel.  Exactness: DCE comparisons reflect true
    distances (Theorem 3), so win counts sort identically to distances
    (ties in wins <=> exact distance ties).
    """
    if use_kernel:
        Z = z_matrix(C, t, block=block, interpret=interpret)
    else:
        Z = _ref.z_matrix(C, t)
    # Exclude the diagonal: Z_ii is mathematically 0 but floats to +-eps.
    offdiag = ~jnp.eye(Z.shape[0], dtype=bool)
    wins = ((Z < 0) & offdiag).sum(axis=1)
    k = min(k, C.shape[0])
    _, idx = jax.lax.top_k(wins, k)
    return idx.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("k", "block", "interpret", "use_kernel"))
def batched_top_k_by_wins(
    C: jnp.ndarray,
    T: jnp.ndarray,
    k: int,
    *,
    valid: jnp.ndarray | None = None,
    block: int = _kernel.DEFAULT_BLOCK,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """Batched refine: per-query exact top-k of DCE candidate sets.

    C: (B, n, 4, D) candidate ciphertexts, T: (B, D) trapdoors,
    valid: optional (B, n) bool mask for padded candidate slots (backends
    with ragged candidate lists pad to a rectangle) -> (B, k) int32 local
    indices, descending win count (== ascending true distance, Theorem 3).

    Per batch row this computes exactly what `top_k_by_wins` computes, so
    the per-query and batched engine paths return identical ids.  With
    use_kernel=False the Z tensor comes from the einsum oracle — the
    GSPMD-safe path for mesh-sharded serving.
    """
    if use_kernel:
        Z = batched_z_matrix(C, T, block=block, interpret=interpret)
    else:
        Z = _ref.batched_z_matrix(C, T)
    n = C.shape[1]
    # Exclude the diagonal: Z_ii is mathematically 0 but floats to +-eps.
    offdiag = ~jnp.eye(n, dtype=bool)[None]
    win_mask = (Z < 0) & offdiag
    if valid is not None:
        win_mask = win_mask & valid[:, None, :]   # wins vs real rivals only
    wins = win_mask.sum(axis=-1)
    if valid is not None:
        wins = jnp.where(valid, wins, -1)         # padded slots rank last
    k = min(k, n)
    _, idx = jax.lax.top_k(wins, k)
    return idx.astype(jnp.int32)
