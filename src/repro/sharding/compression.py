"""Gradient compression: int8 ring all-reduce with f32 accumulation.

A genuine wire-level 4x: the ring is written manually in shard_map with
jax.lax.ppermute, and every hop's payload is an int8-quantized partial
(per-chunk f32 scales ride along, amortized).  Accumulation happens in
f32 locally, so quantization error is one rounding per hop (error feed
-back is left as a knob).

Use for the DP gradient sync of the pure-DP / small-model tier, where the
grad all-reduce is the only collective (EXPERIMENTS.md §Perf): wraps as

    sync = make_int8_allreduce(mesh, axis="data")
    grads = jax.tree.map(sync, grads)        # inside shard_map context
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

__all__ = ["quantize_int8", "dequantize_int8", "int8_ring_allreduce",
           "make_int8_allreduce"]


def quantize_int8(x):
    """Symmetric per-tensor int8; returns (q int8, scale f32)."""
    amax = jnp.max(jnp.abs(x)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def int8_ring_allreduce(x, axis_name: str):
    """Ring all-reduce whose wire payloads are int8 (+1 f32 scale).

    reduce-scatter phase: n-1 hops, each sending an int8-quantized chunk
    to the next rank and accumulating in f32; all-gather phase: n-1 hops
    circulating the reduced int8 chunks.  Payload per hop = bytes/4 of the
    f32 equivalent.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    orig_shape = x.shape
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)                       # chunk c per rank
    perm = [(i, (i + 1) % n) for i in range(n)]

    # ---- reduce-scatter: rank r starts with its copy of chunk (r+1) and
    # at hop s receives the partial for chunk (r-s+1), adding its own copy;
    # after n-1 hops it holds the full sum of chunk (r+2-n) mod n.
    acc = chunks[(idx + 1) % n]                        # start: own copy
    for step in range(1, n):
        q, s = quantize_int8(acc)
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        recv = dequantize_int8(q, s)
        mine = jnp.take(chunks, (idx - step + 1) % n, axis=0)
        acc = recv + mine

    # ---- all-gather: circulate the reduced chunks n-1 hops (int8 wire)
    out = jnp.zeros_like(chunks)
    cur_id = (idx + 2 - n) % n                         # chunk we now own
    q, s = quantize_int8(acc)
    out = out.at[cur_id].set(dequantize_int8(q, s))
    for _ in range(n - 1):
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        cur_id = (cur_id - 1) % n
        out = out.at[cur_id].set(dequantize_int8(q, s))
    flat_out = out.reshape(-1)
    if pad:
        flat_out = flat_out[:-pad]
    return flat_out.reshape(orig_shape).astype(x.dtype)


def make_int8_allreduce(mesh: Mesh, axis: str = "data"):
    """shard_map-wrapped tree all-reduce over `axis` with int8 wire."""

    def sync_tree(tree):
        def one(x):
            fn = shard_map(
                functools.partial(int8_ring_allreduce, axis_name=axis),
                mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
            return fn(x)
        return jax.tree.map(one, tree)

    return sync_tree
