"""The unified engine's search, distribution-native: the sharded
secure-scan dry-run cell (DESIGN.md §3, §4).

This is the dry-run cell that represents the paper's technique at
production scale: the encrypted database (DCPE filter ciphertexts + DCE
refine ciphertexts) is sharded row-wise over EVERY mesh device; a batch of
encrypted queries runs

  filter:  per-shard L2 distance tiles (MXU) -> per-shard top-k'
           -> all-gather(k' candidates/shard) -> global top-k'   [shard_map]
  refine:  gather candidates' DCE ciphertexts -> the engine's shared
           batched tournament (kernels.dce_comp.batched_top_k_by_wins,
           einsum formulation) -> exact top-k                    [GSPMD]

The refine math is the same code path the live engine
(serving.search_engine) and the mesh server (serving.ann_server) run —
this module only adds the explicit-collective filter formulation:
per-device work is O(n/devices) and the only communication is k' rows
per shard, which is what makes the paper's single-server design scale
linearly in devices (EXPERIMENTS.md §Perf discusses the alternative
GSPMD-auto formulation, which all-gathers the (B, n) distance matrix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..kernels.common import HIGHEST
from ..kernels.dce_comp import ops as dce_ops

__all__ = ["build_secure_scan_step", "secure_scan_input_specs"]


def secure_scan_input_specs(n: int, d: int, batch: int, *, dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    Dd = 2 * d + 16
    return {
        "C_sap": jax.ShapeDtypeStruct((n, d), dtype),
        "C_dce": jax.ShapeDtypeStruct((n, 4, Dd), dtype),
        "Q_sap": jax.ShapeDtypeStruct((batch, d), dtype),
        "T_q": jax.ShapeDtypeStruct((batch, Dd), dtype),
    }


def secure_scan_pspecs(mesh: Mesh):
    axes = tuple(mesh.axis_names)
    return {
        "C_sap": P(axes, None),
        "C_dce": P(axes, None, None),
        "Q_sap": P(),            # queries replicated (tiny)
        "T_q": P(),
    }


def build_secure_scan_step_gspmd(mesh: Mesh, *, k: int, k_prime: int):
    """Negative control for §Perf: the GSPMD-auto formulation.  The global
    (B, n) distance matrix and its top-k are left to the partitioner,
    which must materialize/gather across the sharded n dimension — the
    collective/memory blowup the shard_map version avoids."""

    def step(C_sap, C_dce, Q_sap, T_q):
        qn = (Q_sap * Q_sap).sum(-1, keepdims=True)
        xn = (C_sap * C_sap).sum(-1)[None, :]
        dist = qn - 2.0 * jnp.matmul(Q_sap, C_sap.T, precision=HIGHEST) + xn
        _, cand = jax.lax.top_k(-dist, k_prime)
        Cc = jnp.take(C_dce, cand, axis=0)
        top = dce_ops.batched_top_k_by_wins(Cc, T_q, k, use_kernel=False)
        return jnp.take_along_axis(cand, top, axis=1)

    return step


def build_secure_scan_step(mesh: Mesh, *, k: int, k_prime: int):
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))

    def _shard_index():
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False)
    def filter_local(C_sap_loc, Q):
        """Per-shard filter + global candidate merge."""
        n_loc = C_sap_loc.shape[0]
        qn = (Q * Q).sum(-1, keepdims=True)
        xn = (C_sap_loc * C_sap_loc).sum(-1)[None, :]
        dist = qn - 2.0 * jnp.matmul(Q, C_sap_loc.T, precision=HIGHEST) + xn
        kp = min(k_prime, n_loc)
        neg, idx = jax.lax.top_k(-dist, kp)               # local top-k'
        gidx = idx + _shard_index() * n_loc
        # every shard contributes k' candidates -> (B, shards * k')
        vals = jax.lax.all_gather(-neg, axes, axis=1, tiled=True)
        gids = jax.lax.all_gather(gidx, axes, axis=1, tiled=True)
        neg2, pos = jax.lax.top_k(-vals, min(k_prime, vals.shape[1]))
        cand = jnp.take_along_axis(gids, pos, axis=1)
        return -neg2, cand                                # (B, k')

    def step(C_sap, C_dce, Q_sap, T_q):
        _, cand = filter_local(C_sap, Q_sap)              # (B, k')
        # refine: the engine's shared batched tournament (GSPMD gather)
        Cc = jnp.take(C_dce, cand, axis=0)                # (B, k', 4, Dd)
        top = dce_ops.batched_top_k_by_wins(Cc, T_q, k, use_kernel=False)
        return jnp.take_along_axis(cand, top, axis=1)     # (B, k)

    return step
