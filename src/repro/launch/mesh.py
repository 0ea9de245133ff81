"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (jax locks the device count on first backend
init, and only launch/dryrun.py sets the 512-device XLA flag)."""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with explicit Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis.  Batch shards over ('pod','data'): only DP gradient all-reduce
    (and ZeRO all-gathers for fsdp archs) crosses the slow inter-pod links;
    TP stays intra-pod on 'model'."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
