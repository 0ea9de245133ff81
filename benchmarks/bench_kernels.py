"""Kernel microbenchmarks: Pallas wrappers vs jnp reference — the
per-call cost table for the two hot-spot kernels.  The kernels compile
for the chip on a TPU and run in interpret mode elsewhere
(`kernels.common.interpret_default`); row names say which.  On CPU the
interpret path is slower than jnp: the table then documents call
overhead and wiring, not kernel speed."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import dce
from repro.kernels.common import interpret_default
from repro.kernels.dce_comp import ops as dce_ops, ref as dce_ref
from repro.kernels.l2_topk import ops as l2_ops, ref as l2_ref

from .common import row, timeit


def run() -> list[str]:
    rng = np.random.default_rng(0)
    rows = []
    mode = "interp" if interpret_default() else "tpu"
    Q = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((4096, 128)), jnp.float32)

    t, _ = timeit(lambda: l2_ref.pairwise_sq_dists(Q, X).block_until_ready())
    rows.append(row("kern/l2_ref_jnp", 1e6 * t, "64x4096xd128"))
    t, _ = timeit(lambda: l2_ops.pairwise_sq_dists(
        Q, X).block_until_ready())
    rows.append(row(f"kern/l2_pallas_{mode}", 1e6 * t, "64x4096xd128"))
    t, _ = timeit(lambda: l2_ops.knn(Q, X, 10)[0].block_until_ready())
    rows.append(row("kern/knn_streaming", 1e6 * t, "k=10 chunk=4096"))

    key = dce.keygen(128, seed=0)
    P = rng.standard_normal((512, 128))
    C = jnp.asarray(dce.encrypt(P, key, seed=1))
    T = jnp.asarray(dce.trapgen(P[:1], key, seed=2)[0])
    t, _ = timeit(lambda: dce_ref.z_matrix(C, T).block_until_ready())
    rows.append(row("kern/dce_z_ref_jnp", 1e6 * t, "512x512 pairs d=128"))
    t, _ = timeit(lambda: dce_ops.z_matrix(C, T).block_until_ready())
    rows.append(row(f"kern/dce_z_pallas_{mode}", 1e6 * t, "512x512 pairs"))
    t, _ = timeit(lambda: dce_ops.top_k_by_wins(
        C, T, 10, use_kernel=False).block_until_ready())
    rows.append(row("kern/dce_tournament_topk", 1e6 * t, "512 cands k=10"))
    return rows
