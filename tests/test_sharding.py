"""Distribution tests: rule resolution units + a real lower/compile of
dry-run cells on a small multi-device mesh (subprocess: jax pins the
device count at first init, so the 4-device world must be isolated)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.sharding.rules import (AxisRules, PURE_DP_TRAIN_RULES,
                                  TRAIN_RULES, resolve_spec)


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def P(*args):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*args)


def test_resolve_divisibility_strict():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # 40 heads don't divide 16 -> replicated under strict
    spec = resolve_spec(mesh, TRAIN_RULES, ("embed_fsdp", "heads"),
                        (5120, 40), strict=True)
    assert spec == P(None, None) or spec[1] is None
    # fused head dim 5120 divides -> sharded
    spec = resolve_spec(mesh, TRAIN_RULES, (None, "heads"),
                        (5120, 5120), strict=True)
    assert spec == P(None, "model")


def test_resolve_suffix_fallback():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    # batch 256 < 512 -> falls back to ('data','model') = 256
    spec = resolve_spec(mesh, PURE_DP_TRAIN_RULES, ("act_batch", None),
                        (256, 64), strict=True)
    assert spec == P(("data", "model"), None)
    # batch 512 uses the full tuple
    spec = resolve_spec(mesh, PURE_DP_TRAIN_RULES, ("act_batch", None),
                        (512, 64), strict=True)
    assert spec == P(("pod", "data", "model"), None)


def test_resolve_no_axis_reuse():
    mesh = _FakeMesh({"data": 4, "model": 4})
    rules = AxisRules({"a": ("model",), "b": ("model",)})
    spec = resolve_spec(mesh, rules, ("a", "b"), (16, 16), strict=True)
    assert spec == P("model", None)        # model used once only


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax
    from repro.launch.dryrun import (cost_analysis_dict, lower_cell,
                                     parse_collectives)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    lowered, aux = lower_cell(sys.argv[1], sys.argv[2], mesh)
    compiled = lowered.compile()
    colls = parse_collectives(compiled.as_text())
    print("RESULT:" + json.dumps({
        "ok": True,
        "kinds": sorted(colls),
        "flops": cost_analysis_dict(compiled).get("flops", -1),
    }))
""")


@pytest.mark.parametrize("arch,shape", [
    ("qwen3-1.7b", "decode_32k"),
    ("mamba2-370m", "long_500k"),
])
def test_lower_compile_on_small_mesh(arch, shape):
    """End-to-end SPMD check: real config, 4 fake devices, collectives
    present in the partitioned module."""
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC, arch, shape],
        capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, out.stdout[-1000:]
    res = json.loads(line[0][len("RESULT:"):])
    assert res["ok"]
    assert res["flops"] > 0


def test_int8_ring_allreduce_subprocess():
    """int8-wire ring all-reduce matches psum within quantization error."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.sharding.compression import int8_ring_allreduce
        import functools
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        x = jnp.arange(4 * 103, dtype=jnp.float32).reshape(4, 103) / 7.0

        ring = shard_map(functools.partial(
            int8_ring_allreduce, axis_name="data"), mesh=mesh,
            in_specs=P("data", None), out_specs=P("data", None),
            check_vma=False)
        got = np.asarray(ring(x))
        want = np.asarray(x).sum(0, keepdims=True).repeat(4, 0)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert err < 0.02, err
        print("RESULT:ok", err)
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RESULT:ok" in out.stdout


def test_dryrun_artifacts_complete():
    """Every runnable (arch x shape) cell has a green artifact for BOTH
    meshes — the multi-pod dry-run deliverable."""
    res_dir = os.path.join(os.path.dirname(__file__), "..",
                           "results", "dryrun")
    if not os.path.isdir(res_dir):
        pytest.skip("dry-run artifacts not generated yet")
    from repro.launch.dryrun import all_cells
    missing, failed = [], []
    for arch, shape in all_cells():
        for mesh in ("1pod_256", "2pod_512"):
            fn = os.path.join(res_dir, f"{arch}__{shape}__{mesh}.json")
            if not os.path.exists(fn):
                missing.append((arch, shape, mesh))
                continue
            with open(fn) as f:
                if not json.load(f).get("ok"):
                    failed.append((arch, shape, mesh))
    assert not missing, f"missing cells: {missing[:10]}"
    assert not failed, f"failed cells: {failed[:10]}"
