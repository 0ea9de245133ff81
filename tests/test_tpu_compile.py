"""The serving kernels compile for a TPU v5e chip at paper widths.

Each test lowers a kernel with `interpret=False` against a *described*
v5e chip (`jax.experimental.topologies`) and compiles it with the TPU
compiler that ships with jaxlib: Mosaic's tiling, VMEM and lowering
refusals surface here, on the CPU, before any chip time is spent.
Nothing runs — a compile that passes says nothing about results or
speed.

The topology is described inside a module-scoped fixture, never while
the module is imported: only one process at a time may load the TPU
library, and a worker that decides at import time whether its tests
exist would hand the xdist workers different test lists.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dce
from repro.kernels.adc_topk import ops as adc_ops
from repro.kernels.dce_comp import ops as dce_ops
from repro.kernels.l2_topk import ops as l2_ops
from repro.serving.search_engine import refine_candidates

NQ = 32                 # the micro-batcher's largest bucket (max_batch)
N = 2 ** 20             # a 1M-row collection's capacity bucket


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def test_l2_knn_compiles(one_chip):
    """The flat f32 filter: streamed distance tiles + running top-k'."""
    fn = functools.partial(l2_ops.knn.__wrapped__, k=80, chunk=4096,
                           interpret=False)
    _compiled_text(fn, _spec(one_chip, (NQ, 128), jnp.float32),
                   _spec(one_chip, (N, 128), jnp.float32))


@pytest.mark.parametrize("B", [1, 32])
@pytest.mark.parametrize("d,kp", [(128, 80), (960, 320)])
def test_batched_z_matrix_compiles(one_chip, B, d, kp):
    """The refine's Z tiles at every micro-batch size: the trapdoor
    block must tile for B > 1, not only for a batch of one."""
    D = dce.ciphertext_dim(d)
    fn = functools.partial(dce_ops.batched_z_matrix, interpret=False)
    _compiled_text(fn, _spec(one_chip, (B, kp, 4, D), jnp.float32),
                   _spec(one_chip, (B, D), jnp.float32))


def test_refine_program_compiles(one_chip, monkeypatch):
    """The whole refine program the engine runs: candidate gather from
    a 1M-row DCE array + kernel tournament.  The engine picks interpret
    mode from the default backend, which is the CPU here; steer it to
    the chip's choice."""
    from repro.kernels.dce_comp import dce_comp
    monkeypatch.setattr(dce_comp, "interpret_default", lambda: False)
    D = dce.ciphertext_dim(128)
    fn = functools.partial(refine_candidates, k=10, use_kernel=True)
    _compiled_text(fn, _spec(one_chip, (N, 4, D), jnp.float32),
                   _spec(one_chip, (NQ, 80), jnp.int32),
                   _spec(one_chip, (NQ, D), jnp.float32),
                   _spec(one_chip, (NQ, 80), jnp.bool_))


def test_sq_adc_topk_compiles(one_chip):
    fn = functools.partial(adc_ops.sq_knn.__wrapped__, k=160,
                           interpret=False, use_kernel=True)
    _compiled_text(fn, _spec(one_chip, (NQ, 128), jnp.int8),
                   _spec(one_chip, (N, 128), jnp.int8),
                   _spec(one_chip, (N,), jnp.int32))


def test_pq_adc_topk_compiles(one_chip):
    fn = functools.partial(adc_ops.pq_knn.__wrapped__, k=320,
                           interpret=False, use_kernel=True)
    _compiled_text(fn, _spec(one_chip, (NQ, 16, 256), jnp.float32),
                   _spec(one_chip, (16, N), jnp.uint8))
