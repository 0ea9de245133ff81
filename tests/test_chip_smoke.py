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
