"""A run of each traffic kind at a tiny size on the CPU, through the
harness's internal entry (the kernels in interpret mode); the entry
script's refusal of a machine without a TPU; and faults planted under
the timed path, which the check has to catch."""

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

from bench import harness  # noqa: E402

TINY = {"config": {"n": 2048, "n_queries": 128, "ratio_k": 2},
        "traffic": {"max_rate_qps": 200, "clients": 8}}
SEED = 2**31 + 12345        # seeds run past 32 signed bits
# each traffic kind: the cell it runs on, its traffic at a tiny size, and
# the end-to-end metrics it reports
KINDS = {
    "single": ("sift128-flat-f32.single64", {},
               {"qps", "recall_at_10", "setup_s"}),
    # requests of many rows go straight to the engine, past the batcher
    "batch": ("sift128-flat-int8.single64",
              {"clients": 2, "queries_per_request": 16},
              {"qps", "recall_at_10", "setup_s"}),
    "open": ("sift128-flat-f32.poisson", {"rate_per_s": 40, "senders": 8},
             {"p50_ms", "p99_ms", "recall_at_10", "setup_s"}),
}


@pytest.fixture(scope="module")
def open_loop_root(tmp_path_factory):
    """A checkout whose benchmark also has an open-loop cell on the
    `poisson` mix, as the later cell listed in PERF.md would add it."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "sift128-flat-f32.poisson", "config": "sift128-flat-f32",
        "traffic": "poisson", "chips": 1, "why": "test"})
    for name in ("p50_ms", "p99_ms"):
        bench["end_to_end"].append({
            "name": name, "unit": "ms", "better": "lower", "bound": 0.1,
            "source": "host_clock",
            "workloads": ["sift128-flat-f32.poisson"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(workload, capsys, seconds=1.5, trace=False, traffic=None,
        root=ROOT):
    overrides = {"config": TINY["config"],
                 "traffic": {**TINY["traffic"], **(traffic or {})}}
    res = harness.run_cell(workload, SEED, seconds, trace,
                           overrides=overrides, root=root)
    facts = [json.loads(line[4:]) for line in
             capsys.readouterr().out.splitlines() if line.startswith("run ")]
    return res, facts[-1]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_each_traffic_kind_runs_correct_with_no_compile_in_the_window(
        kind, capsys, request):
    workload, traffic, e2e = KINDS[kind]
    root = request.getfixturevalue("open_loop_root") if kind == "open" \
        else ROOT
    res, facts = run(workload, capsys, traffic=traffic, root=root)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == e2e
    assert res["attempted"] > 0 and res["failed"] == 0
    assert facts["jit_cache_size"][0] == facts["jit_cache_size"][1]
    assert set(facts["compiles_in_window"].values()) == {0}
    assert list(res)[-1] == "checks"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_a_traced_run_reports_per_layer_metrics_from_spans(capsys):
    res, _ = run("sift128-flat-f32.single64", capsys, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert {"batch_rows.closed", "filter_ms.closed",
            "refine_ms.closed"} <= set(m)
    assert 1 <= m["batch_rows.closed"]["value"] <= 32
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter(monkeypatch, how):
    """Alter every answer where the engine produces it."""
    from repro.serving.search_engine import SecureSearchEngine

    orig = SecureSearchEngine.search_batch

    def altered(self, *a, **kw):
        ids, stats = orig(self, *a, **kw)
        if how == "reversed":
            ids = ids[:, ::-1].copy()
        elif how == "other_rows":
            ids = (ids + 1) % self.n
        elif how == "rotated":          # each answer to another request
            ids = np.roll(ids, 1, axis=0)
        elif how == "half_dropped":     # half of the batch left out
            ids = ids.copy()
            ids[ids.shape[0] // 2:] = -1
        return ids, stats

    monkeypatch.setattr(SecureSearchEngine, "search_batch", altered)


@pytest.mark.parametrize("how", ["reversed", "other_rows", "half_dropped"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        how, monkeypatch, capsys):
    _alter(monkeypatch, how)
    res, _ = run("sift128-flat-f32.single64", capsys, seconds=1.0)
    assert not res["correct"], res["checks"]


def test_answers_handed_to_other_requests_are_not_correct(monkeypatch,
                                                          capsys):
    _alter(monkeypatch, "rotated")
    workload, traffic, _ = KINDS["batch"]
    res, _ = run(workload, capsys, seconds=1.0, traffic=traffic)
    assert not res["correct"], res["checks"]


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "sift128-flat-f32.single64", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_entry_refuses_a_machine_without_a_tpu():
    p = _entry(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _entry(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
