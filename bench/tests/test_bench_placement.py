"""Placement named by a configuration: a cell on four chips builds a
sharded collection through `SecureAnnService.create_collection(...,
placement=...)`, answers what single placement answers, and reports its
memory per chip; a cell whose chips and placement disagree stops before
set-up; a configuration without `placement` is placed on one chip.

The four-chip run goes to a child process with four virtual CPU devices
(`--xla_force_host_platform_device_count=4`), which have to be asked for
before JAX starts."""

import contextlib
import io
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import deployment, harness  # noqa: E402

TINY = {"config": {"n": 2048, "n_queries": 128, "ratio_k": 2},
        "traffic": {"max_rate_qps": 200, "clients": 8}}
SEED = 2**31 + 16016
SHARDED4 = "sift128-flat-f32-sharded4"


def make_root(root: pathlib.Path) -> pathlib.Path:
    """A checkout whose benchmark also has the f32 configuration sharded
    four ways, a four-chip cell on it, and two cells whose chips and
    placement disagree."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads(
        (ROOT / "bench/configs/sift128-flat-f32.json").read_text())
    config["placement"] = {"kind": "sharded", "n_shards": 4}
    (root / f"bench/configs/{SHARDED4}.json").write_text(json.dumps(config))
    bench["configs"].append({
        "name": SHARDED4, "source": "test", "reduced": [], "why": "test",
        "file": f"bench/configs/{SHARDED4}.json"})
    for name, cfg, chips in ((f"{SHARDED4}.single64", SHARDED4, 4),
                             (f"{SHARDED4}.one-chip", SHARDED4, 1),
                             ("sift128-flat-f32.four-chips",
                              "sift128-flat-f32", 4)):
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": "single64", "chips": chips,
                                   "why": "test"})
    qps = harness.find(bench["end_to_end"], "qps", "metric")
    qps["workloads"].append(f"{SHARDED4}.single64")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def sharded_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def sharded_and_single(root: str, seed: int) -> dict:
    """Run the four-chip sharded cell and the one-chip cell of the same
    configuration at one seed; return both results, their `run` lines,
    the placement each collection was created with, and how many answers
    of the requests both runs answered agree.  Run with four devices."""
    from bench import traffic

    drive, build = traffic.drive, deployment.build
    seen = {}

    def recording_drive(plan, *a, **kw):
        seen["plan"], seen["log"] = plan, drive(plan, *a, **kw)
        return seen["log"]

    def recording_build(*a, **kw):
        dep = build(*a, **kw)
        seen["placement"] = dep.svc.placement(dep.spec.tenant,
                                              dep.spec.name).to_dict()
        return dep

    traffic.drive, deployment.build = recording_drive, recording_build
    out, logs = {}, {}
    for kind, workload in (("sharded", f"{SHARDED4}.single64"),
                           ("single", "sift128-flat-f32.single64")):
        seen.clear()
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            res = harness.run_cell(workload, seed, 1.5, False,
                                   overrides=TINY, root=pathlib.Path(root))
        facts = [json.loads(line[4:]) for line in buf.getvalue().splitlines()
                 if line.startswith("run ")][-1]
        out[kind] = {"result": res, "run": facts,
                     "placement": seen["placement"]}
        logs[kind] = (seen["plan"], seen["log"])
    (p_sh, l_sh), (p_si, l_si) = logs["sharded"], logs["single"]
    both = np.flatnonzero((l_sh.status == traffic.OK)
                          & (l_si.status == traffic.OK))
    out["same_plan"] = bool(np.array_equal(p_sh.qrows, p_si.qrows))
    out["answered_by_both"] = int(both.size)
    out["ids_equal"] = int(sum(np.array_equal(l_sh.ids[i], l_si.ids[i])
                               for i in both))
    return out


def test_a_sharded_four_chip_cell_answers_as_single_placement(sharded_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, sys; sys.path.insert(0, sys.argv[1]); "
            "import test_bench_placement as t; "
            "print('RESULT ' + json.dumps(t.sharded_and_single("
            "sys.argv[2], int(sys.argv[3]))))")
    p = subprocess.run(
        [sys.executable, "-c", code, str(pathlib.Path(__file__).parent),
         str(sharded_root), str(SEED)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads([line for line in p.stdout.splitlines()
                      if line.startswith("RESULT ")][-1][7:])
    sharded, single = out["sharded"], out["single"]

    assert sharded["placement"]["kind"] == "sharded"
    assert sharded["placement"]["n_shards"] == 4
    assert single["placement"]["kind"] == "single"
    assert sharded["result"]["correct"], sharded["result"]["checks"]
    assert single["result"]["correct"], single["result"]["checks"]
    assert out["same_plan"] and out["answered_by_both"] > 0
    assert out["ids_equal"] == out["answered_by_both"]

    dev = sharded["result"]["device"]
    assert dev["count"] == 4
    assert len(dev["memory_peak_bytes_by_device"]) == 4
    assert dev["memory_peak_bytes"] == max(dev["memory_peak_bytes_by_device"])
    run = sharded["run"]
    col = run["collection_device_bytes_by_device"]
    assert len(col) == 4 and min(col) > 0
    assert max(col) <= 1.25 * min(col), col          # about a quarter each
    assert run["collection_device_bytes"] == sum(col)
    assert run["host_rss_peak_bytes"] > 0
    assert len(single["result"]["device"]["memory_peak_bytes_by_device"]) \
        == 1
    assert len(single["run"]["collection_device_bytes_by_device"]) == 1


@pytest.mark.parametrize("workload,chips,shards", [
    (f"{SHARDED4}.one-chip", 1, 4),
    ("sift128-flat-f32.four-chips", 4, 1),
])
def test_chips_and_placement_that_disagree_stop_before_setup(
        workload, chips, shards, sharded_root, monkeypatch):
    def no_setup(*a, **kw):
        raise AssertionError("set-up ran")

    monkeypatch.setattr(harness, "make_corpus", no_setup)
    with pytest.raises(ValueError) as err:
        harness.run_cell(workload, SEED, 1.0, False, overrides=TINY,
                         root=sharded_root)
    msg = str(err.value)
    assert f"{chips} chip(s)" in msg and f"over {shards}" in msg


@pytest.mark.parametrize("placement", [None,
                                       {"kind": "sharded", "n_shards": 1}])
def test_build_passes_the_configured_placement_and_none_without_it(
        placement, monkeypatch):
    """`build` hands `create_collection` the configuration's placement;
    without the key, the service's default single placement, the one
    `create_collection(spec)` makes."""
    from repro.api import PlacementSpec, SecureAnnService

    from bench.data import make_corpus

    calls = []
    create = SecureAnnService.create_collection

    def recording(self, *a, **kw):
        calls.append((a, kw))
        return create(self, *a, **kw)

    monkeypatch.setattr(SecureAnnService, "create_collection", recording)
    config = harness.merged(
        harness.load_config(harness.load_benchmark(), "sift128-flat-f32"),
        TINY["config"])
    if placement is not None:
        config["placement"] = placement
    base = np.asarray(make_corpus(config, SEED)[0])
    dep = deployment.build(config, base, SEED, log=lambda msg: None)
    try:
        placed = dep.svc.placement(dep.spec.tenant, dep.spec.name)
    finally:
        dep.svc.close()
    (args, kw), = calls
    assert len(args) == 1 and args[0].name == config["name"]
    assert kw == {"placement": PlacementSpec(**(placement or {}))}
    if placement is None:
        assert placed == PlacementSpec()
        assert placed.kind == "single" and placed.n_shards is None
    else:
        assert placed.kind == "sharded" and placed.n_shards == 1


def test_sharded_programs_compile_at_a_shape_for_four_described_chips():
    """`bench/sharded_compile.py` compiles the sharded refine and filter
    for a described v5e 2x2 and reports each one's memory per device:
    the refine's argument holds a quarter of the DCE rows."""
    n, d = 8192, 960
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/sharded_compile.py"),
         "--n", str(n), "--d", str(d)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    if p.returncode == 3:
        pytest.skip(p.stderr.strip().splitlines()[-1])
    assert p.returncode == 0, p.stderr[-4000:]
    arg = {line.split(":")[0]: int(line.split("argument ")[1].split()[0])
           for line in p.stdout.splitlines() if "per device argument" in line}
    assert set(arg) == {"_sharded_refine", "_sharded_flat_topk"}
    assert arg["_sharded_refine"] >= n // 4 * 4 * (2 * d + 16) * 4
    assert arg["_sharded_flat_topk"] >= n // 4 * d * 4
