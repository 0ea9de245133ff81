"""The plain reference: exact top-k by plaintext L2, in blocks on the device.

Written against `jax.numpy` alone and independent of the code under
test.  The distances are ||q||^2 - 2 q.x + ||x||^2 with the cross term
at `Precision.HIGHEST` (full float32 on a TPU, whose default for float32
is one bfloat16 pass).  `precision="bfloat16"` computes the same thing
from bfloat16-rounded vectors with one bfloat16 pass: the control, the
step below the float32 that the configurations state.
"""

from __future__ import annotations

import functools

import numpy as np

BLOCK_Q = 1024
BLOCK_N = 65536


@functools.cache
def _topk_fn(n: int, d: int, bq: int, k: int, block_n: int,
             precision: str):
    import jax
    import jax.numpy as jnp

    n_blocks = -(-n // block_n)
    n_pad = n_blocks * block_n

    def dists(q, xs):
        if precision == "bfloat16":
            q = q.astype(jnp.bfloat16)
            xs = xs.astype(jnp.bfloat16)
            cross = jnp.matmul(q, xs.T, preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.DEFAULT)
            q = q.astype(jnp.float32)
            xs = xs.astype(jnp.float32)
        else:
            cross = jnp.matmul(q, xs.T, precision=jax.lax.Precision.HIGHEST)
        qn = (q * q).sum(-1)[:, None]
        xn = (xs * xs).sum(-1)[None, :]
        return qn - 2.0 * cross + xn

    @jax.jit
    def topk(q, base):
        base = jnp.pad(base, ((0, n_pad - n), (0, 0)))
        col = jnp.arange(block_n)[None, :]

        def body(carry, b):
            best_d, best_i = carry
            start = b * block_n
            xs = jax.lax.dynamic_slice_in_dim(base, start, block_n, axis=0)
            dist = jnp.where(start + col < n, dists(q, xs), jnp.inf)
            neg, pos = jax.lax.top_k(-dist, k)
            all_d = jnp.concatenate([best_d, -neg], axis=1)
            all_i = jnp.concatenate([best_i, start + pos], axis=1)
            neg, pos = jax.lax.top_k(-all_d, k)
            return (-neg, jnp.take_along_axis(all_i, pos, axis=1)), None

        init = (jnp.full((q.shape[0], k), jnp.inf, jnp.float32),
                jnp.full((q.shape[0], k), -1, jnp.int32))
        (_, best_i), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
        return best_i

    return topk


def exact_topk(base, queries, k: int, *, precision: str = "highest",
               block_q: int = BLOCK_Q, block_n: int = BLOCK_N) -> np.ndarray:
    """(nq, k) int64 ids of each query's k nearest rows of `base`,
    nearest first.  `base` and `queries` may be host or device arrays."""
    import jax.numpy as jnp

    if precision not in ("highest", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    base = jnp.asarray(base, jnp.float32)
    queries = np.asarray(queries, np.float32)
    n, d = base.shape
    nq = queries.shape[0]
    block_n = min(block_n, n)
    bq = min(block_q, max(nq, 1))
    fn = _topk_fn(n, d, bq, k, block_n, precision)
    out = []
    for i in range(0, nq, bq):
        part = queries[i: i + bq]
        m = part.shape[0]
        if m < bq:
            part = np.concatenate([part, np.zeros((bq - m, d), np.float32)])
        out.append(np.asarray(fn(jnp.asarray(part), base))[:m])
    return (np.concatenate(out) if out
            else np.zeros((0, k), np.int32)).astype(np.int64)
