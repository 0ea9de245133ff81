"""Shared helpers for the Pallas TPU kernels.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU with interpret=True, per the repo conventions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# MXU/VPU-aligned tile sizes.
LANE = 128
SUBLANE = 8

# f32 matmul precision for every ciphertext product.  The TPU's default
# for f32 operands is a single bf16 pass, which rounds DCPE distances and
# DCE Z-scores far past the gaps the filter ranks and the refine's exact
# comparisons resolve; HIGHEST keeps full f32 (CPU computes f32 anyway).
HIGHEST = jax.lax.Precision.HIGHEST


def interpret_default() -> bool:
    """Run pallas in interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def next_bucket(n: int, minimum: int = 1, maximum: int | None = None) -> int:
    """Smallest power-of-two bucket >= max(n, minimum), optionally capped.

    Jitted executables are cached per input shape, so callers that see
    ragged sizes (micro-batched query counts, ingestion delta buffers,
    owner-side encryption batches — DESIGN.md §8) pad to bucketed shapes
    and reuse a handful of executables instead of recompiling per size.
    """
    if n < 0:
        raise ValueError(f"negative size {n}")
    b = max(minimum, 1)
    while b < n:
        b <<= 1
    if maximum is not None and b > maximum:
        if n > maximum:
            raise ValueError(f"size {n} exceeds bucket cap {maximum}")
        b = maximum
    return b


def running_topk_scan(dist_fn, n: int, nq: int, k: int, chunk: int):
    """Streaming top-k merge shared by `l2_topk.ops.knn` and the
    adc_topk XLA fallbacks: fold `chunk`-row distance blocks into a
    running (nq, k) ascending state.

    `dist_fn(start)` returns the (nq, chunk) distance block for rows
    [start, start+chunk) of the (padded) database, with invalid rows
    already pushed to +inf/sentinel.  The id mapping avoids ever
    materializing an (nq, chunk) id block: merge positions < k select
    from the running ids, the rest are `start + (pos - k)`.  Returns
    (dists (nq, k) ascending, ids (nq, k) int32; unfilled slots -1).
    """
    n_chunks = -(-n // chunk)

    def body(carry, ci):
        best_d, best_i = carry
        start = ci * chunk
        d_blk = dist_fn(start)
        cat_d = jnp.concatenate([best_d, d_blk], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        from_best = jnp.take_along_axis(best_i, jnp.minimum(pos, k - 1),
                                        axis=1)
        best_i = jnp.where(pos < k, from_best,
                           start + (pos - k).astype(jnp.int32))
        return (-neg, best_i), None

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return best_d, best_i


def pad_to(x: jnp.ndarray, axis: int, multiple: int,
           value: float = 0.0) -> jnp.ndarray:
    """Right-pad `axis` of x up to a multiple (hardware-aligned shapes)."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def padded_size(n: int, multiple: int) -> int:
    return n + ((-n) % multiple)
