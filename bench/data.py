"""Corpus and query pool of a deployment, made on the device from the seed.

The 64-cluster Gaussian mixture of `repro/data/synth.py`, copied here so
that a change to the program cannot change the data: centers
N(0, I) * center_scale, each row a uniformly drawn center plus
cluster_std * N(0, I).  The queries of the pool come from the same
mixture.  Everything is drawn in one jitted call from a key made of the
seed, so the same seed gives the same corpus and pool on every machine.
"""

from __future__ import annotations

import functools

import numpy as np


def seed_words(seed: int, n: int) -> list[int]:
    """`n` independent 31-bit integers derived from any whole-number seed
    (seeds above 2**31 are fine): the sub-seeds handed to the program's
    key generation and encryption, which take 31-bit ints."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(w) & 0x7FFFFFFF for w in state]


def jax_key(seed: int, stream: int):
    import jax
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


@functools.cache
def _mixture_fn(n: int, n_queries: int, d: int, n_clusters: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key, center_scale, cluster_std):
        kc, ka, kb, kqa, kqb = jax.random.split(key, 5)
        centers = jax.random.normal(kc, (n_clusters, d), jnp.float32)
        centers = centers * center_scale
        assign = jax.random.randint(ka, (n,), 0, n_clusters)
        base = centers[assign] + cluster_std * jax.random.normal(
            kb, (n, d), jnp.float32)
        qassign = jax.random.randint(kqa, (n_queries,), 0, n_clusters)
        queries = centers[qassign] + cluster_std * jax.random.normal(
            kqb, (n_queries, d), jnp.float32)
        return base, queries

    return draw


def make_corpus(config: dict, seed: int):
    """(base (n, d), queries (n_queries, d)) as device float32 arrays."""
    mix = config["data"]
    draw = _mixture_fn(int(config["n"]), int(config["n_queries"]),
                       int(config["d"]), int(mix["n_clusters"]))
    return draw(jax_key(seed, 0), np.float32(mix["center_scale"]),
                np.float32(mix["cluster_std"]))
