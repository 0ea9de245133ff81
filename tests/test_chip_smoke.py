"""chip_smoke.py, the on-chip bring-up run, rehearsed on the CPU.

The script itself refuses any backend but a TPU; these tests pin that
refusal and drive its phase machinery at a small size, so a change that
breaks the served path's entry points fails here before a chip call.
"""

import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402


def test_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_phases_pass_their_checks_at_small_scale():
    phases = tuple(p for p in chip_smoke.PHASES
                   if p.name in ("flat-f32", "flat-int8", "ivf-f32"))
    out = chip_smoke.run_one_chip(600, seed=1, phases=phases,
                                  check_kernels=False)
    assert [r["phase"] for r in out] == [p.name for p in phases]
    for r in out:
        assert r["n"] == 600
        assert r["recall_at_10"] >= chip_smoke.RECALL_FLOOR
        assert r["ids_batch_invariant"]
        assert r["collection_device_bytes"] > 0


@pytest.mark.parametrize("phase", chip_smoke.PHASES,
                         ids=lambda p: p.name)
def test_phase_cuts_are_stated(phase):
    """A phase that runs below the corpus size says why."""
    assert (phase.n is None) == (phase.cut == "")


def test_kernel_check_lowers_the_served_programs():
    """`_has_kernel` lowers the ops' own jitted programs: on the CPU it
    finds no TPU custom call, and raises nothing."""
    import jax.numpy as jnp

    from repro.kernels.adc_topk import ops as adc_ops
    from repro.kernels.l2_topk import ops as l2_ops

    n, d, m, k = 256, 16, 4, 8
    ok = jnp.ones((n,), jnp.int32)
    assert not chip_smoke._has_kernel(
        l2_ops.knn, jnp.zeros((2, d), jnp.float32),
        jnp.zeros((n, d), jnp.float32), k, chunk=128, use_kernel=False)
    assert not chip_smoke._has_kernel(
        adc_ops.sq_knn, jnp.zeros((2, d), jnp.int8),
        jnp.zeros((n, d), jnp.int8), jnp.zeros((n,), jnp.int32), k,
        ok=ok, use_kernel=False)
    assert not chip_smoke._has_kernel(
        adc_ops.pq_knn, jnp.zeros((2, m, 256), jnp.float32),
        jnp.zeros((m, n), jnp.uint8), k, ok=ok, use_kernel=False)
