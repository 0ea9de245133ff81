"""Everything a cell needs is found by name: a new configuration, mix or
metric is usable once its file exists, with no edit to any other file."""

import hashlib
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.fixture()
def checkout(tmp_path):
    """A copy of the benchmark's files, as a checkout would hold them."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


def add_cell(root: pathlib.Path):
    """What a later PR adds: a configuration file, a mix file, a metric
    reader, and their entries in BENCHMARK.json."""
    cfg = json.loads((root / "bench/configs/sift128-flat-f32.json")
                     .read_text())
    cfg.update(name="sift128-flat-f32-wide", ratio_k=32)
    (root / "bench/configs/sift128-flat-f32-wide.json").write_text(
        json.dumps(cfg))
    (root / "bench/traffic/single8.json").write_text(json.dumps(
        {"kind": "closed", "clients": 8, "queries_per_request": 1,
         "max_rate_qps": 100}))
    (root / "bench/metrics/flushes.closed.py").write_text(
        "def read(ctx):\n"
        "    n = sum(1 for s in ctx.spans\n"
        "            if s['name'] == 'flush' and 'bucket' in s['attrs'])\n"
        "    return float(n) if n else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "sift128-flat-f32-wide", "source": "test",
        "file": "bench/configs/sift128-flat-f32-wide.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "sift128-flat-f32-wide.single8",
        "config": "sift128-flat-f32-wide", "traffic": "single8",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("sift128-flat-f32-wide.single8")
    bench["per_layer"].append({
        "name": "flushes.closed", "unit": "flushes", "better": "higher",
        "source": "program_span", "layer": "scheduler", "moves": "qps",
        "workloads": ["sift128-flat-f32-wide.single8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_files_are_found_by_name_without_editing_the_old(checkout):
    before = digest(checkout / "bench")
    add_cell(checkout)
    after = digest(checkout / "bench")
    assert {k: v for k, v in after.items() if k in before} == before

    bench = harness.load_benchmark(checkout)
    cell = harness.find(bench["workloads"], "sift128-flat-f32-wide.single8",
                        "workload")
    cfg = harness.load_config(bench, cell["config"], checkout)
    assert cfg["ratio_k"] == 32
    mix = harness.load_traffic("single8", checkout / "bench")
    assert mix["clients"] == 8
    reader = harness.load_metric("flushes.closed", checkout / "bench")
    assert reader.read(type("C", (), {"spans": []})) is None
    names = [m["name"] for m in harness.cell_metrics(
        bench, "per_layer", "sift128-flat-f32-wide.single8")]
    assert names == ["flushes.closed"]
    e2e = [m["name"] for m in harness.cell_metrics(
        bench, "end_to_end", "sift128-flat-f32-wide.single8")]
    assert e2e == ["qps", "recall_at_10", "setup_s"]


def test_a_name_that_is_not_found_is_an_error(checkout):
    bench = harness.load_benchmark(checkout)
    with pytest.raises(KeyError):
        harness.find(bench["workloads"], "no-such-cell", "workload")
    with pytest.raises(KeyError):
        harness.load_config(bench, "no-such-config", checkout)
    with pytest.raises(FileNotFoundError):
        harness.load_traffic("no-such-mix", checkout / "bench")
    with pytest.raises(FileNotFoundError):
        harness.load_metric("no_such_metric", checkout / "bench")
    (checkout / "bench/traffic/bad.json").write_text('{"kind": "bursty"}')
    with pytest.raises(ValueError):
        harness.load_traffic("bad", checkout / "bench")


def test_every_cell_and_metric_of_the_benchmark_resolves():
    bench = harness.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        cfg = harness.load_config(bench, cell["config"], ROOT)
        assert cfg["name"] == cell["config"]
        harness.load_traffic(cell["traffic"])
        assert harness.cell_metrics(bench, "per_layer", cell["name"])
    for m in bench["per_layer"]:
        harness.load_metric(m["name"])


def test_a_new_cell_runs_with_its_new_metric(checkout):
    add_cell(checkout)
    res = harness.run_cell(
        "sift128-flat-f32-wide.single8", 3, 1.0, True, root=checkout,
        overrides={"config": {"n": 1024, "n_queries": 64, "ratio_k": 2}})
    assert res["correct"], res["checks"]
    assert res["metrics"]["flushes.closed"]["value"] >= 1
