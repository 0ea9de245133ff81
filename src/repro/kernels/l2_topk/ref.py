"""Pure-jnp oracle for the l2_topk kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import HIGHEST


def pairwise_sq_dists(Q: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """||q - x||^2 for all pairs; Q: (nq, d), X: (n, d) -> (nq, n)."""
    Q = Q.astype(jnp.float32)
    X = X.astype(jnp.float32)
    qn = (Q * Q).sum(-1, keepdims=True)
    xn = (X * X).sum(-1)[None, :]
    return qn - 2.0 * jnp.matmul(Q, X.T, precision=HIGHEST) + xn


def knn(Q: jnp.ndarray, X: jnp.ndarray, k: int):
    """Exact k-NN: returns (dists (nq, k), idx (nq, k)) ascending."""
    d = pairwise_sq_dists(Q, X)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx
