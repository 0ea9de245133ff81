"""Pure numpy/jnp oracles for the adc_topk kernel family.

Distances here are the *ranking surrogates* the kernels compute, not
squared L2 itself:

  int8 (SQ):  d_i = cn_i - 2 * (q8 . c8_i)   — int32-exact; adding the
              per-query constant ||q8||^2 would give the true symmetric
              quantized distance, but constants do not change top-k.
  pq8  (PQ):  d_i = sum_m LUT[m, codes_t[m, i]] — the classic ADC LUT
              gather-accumulate (f32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sq_dists(q8: np.ndarray, c8: np.ndarray, cn: np.ndarray) -> np.ndarray:
    """Symmetric int8 ADC surrogate distances, int32-exact.

    q8: (nq, d) int8 quantized queries; c8: (n, d) int8 codes;
    cn: (n,) int32 code norms  ->  (nq, n) int32.
    """
    cross = q8.astype(np.int32) @ c8.astype(np.int32).T
    return cn[None, :].astype(np.int32) - 2 * cross


def pq_dists(lut: np.ndarray, codes_t: np.ndarray) -> np.ndarray:
    """PQ ADC distances from per-query LUTs.

    lut: (nq, m, 256) f32; codes_t: (m, n) uint8  ->  (nq, n) f32.
    """
    m, n = codes_t.shape
    out = np.zeros((lut.shape[0], n), np.float32)
    for j in range(m):
        out += lut[:, j, codes_t[j].astype(np.int64)]
    return out


def _topk_ascending(d, k: int, ok=None, big=None):
    """Top-k ascending; rows with ok == 0 rank last at `big`, and slots
    holding `big` come back as id -1 (the kernels' empty-slot rule)."""
    d = jnp.asarray(d)
    if ok is not None:
        d = jnp.where(jnp.asarray(ok)[None, :] > 0, d, big)
    # k clamps to the row count, as the kernels' kp = min(kp, n) does
    neg, idx = jax.lax.top_k(-d, min(k, d.shape[-1]))
    idx = idx.astype(jnp.int32)
    if ok is not None:
        idx = jnp.where(-neg >= big, -1, idx)
    return -neg, idx


def sq_knn(q8, c8, cn, k: int, ok=None):
    """Exact top-k (ascending surrogate distance) of the SQ oracle;
    optional (n,) row validity `ok`."""
    return _topk_ascending(sq_dists(np.asarray(q8), np.asarray(c8),
                                    np.asarray(cn)), k, ok,
                           np.int32(2 ** 30))


def pq_knn(lut, codes_t, k: int, ok=None):
    """Exact top-k (ascending surrogate distance) of the PQ oracle;
    optional (n,) row validity `ok`."""
    return _topk_ascending(pq_dists(np.asarray(lut), np.asarray(codes_t)),
                           k, ok, np.float32(np.inf))
