"""Pure-jnp oracle for the dce_comp kernel."""

from __future__ import annotations

import jax.numpy as jnp

from ..common import HIGHEST


def z_matrix(C: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """All-pairs DCE Z-scores.  C: (n, 4, D), t: (D,) -> (n, n).

    Z[i, j] = DistanceComp(C_i, C_j, t) = 2 r_i r_j r_q (d_i - d_j);
    Z[i, j] < 0  iff  dist(i, q) < dist(j, q).
    """
    C = C.astype(jnp.float32)
    t = t.astype(jnp.float32)
    term1 = jnp.matmul(C[:, 0, :] * t, C[:, 2, :].T, precision=HIGHEST)
    term2 = jnp.matmul(C[:, 1, :] * t, C[:, 3, :].T, precision=HIGHEST)
    return term1 - term2


def win_counts(C: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """wins[i] = #{j != i : dist(i,q) < dist(j,q)} — ranking by wins is an
    exact total order because DCE comparisons are exact (Theorem 3).  The
    diagonal is excluded: Z_ii is mathematically 0 but floats to ±eps."""
    Z = z_matrix(C, t)
    n = Z.shape[0]
    offdiag = ~jnp.eye(n, dtype=bool)
    return ((Z < 0) & offdiag).sum(axis=1).astype(jnp.int32)


def top_k_by_wins(C: jnp.ndarray, t: jnp.ndarray, k: int) -> jnp.ndarray:
    """Indices of the k closest candidates (descending win count)."""
    wins = win_counts(C, t)
    return jnp.argsort(-wins)[:k]


def batched_z_matrix(C: jnp.ndarray, T: jnp.ndarray) -> jnp.ndarray:
    """Per-query all-pairs Z tensors.  C: (B, n, 4, D), T: (B, D) ->
    (B, n, n).  Pure-einsum formulation — also the GSPMD-friendly refine
    used under mesh sharding (DESIGN.md §3), where a Pallas call over
    gathered candidates would fight the partitioner."""
    C = C.astype(jnp.float32)
    T = T.astype(jnp.float32)
    left1 = C[:, :, 0, :] * T[:, None, :]
    left2 = C[:, :, 1, :] * T[:, None, :]
    z1 = jnp.einsum("bkd,bjd->bkj", left1, C[:, :, 2, :], precision=HIGHEST)
    z2 = jnp.einsum("bkd,bjd->bkj", left2, C[:, :, 3, :], precision=HIGHEST)
    return z1 - z2
