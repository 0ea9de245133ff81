"""The kernels layer's `sq_merge_share.closed` reader on hand-built
spans, and a traced run at a tiny size on the CPU of each cell that
lists it, with the int8 kernel in interpret mode."""

import importlib.util
import json
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

NAME = "sq_merge_share.closed"
SEED = 2**31 + 1515
TINY = {"n": 2048, "n_queries": 128, "ratio_k": 2}


def reader():
    spec = importlib.util.spec_from_file_location(
        "_m_sq_merge_share", ROOT / "bench" / "metrics" / f"{NAME}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, **attrs):
    return {"name": name, "trace_id": "c:b0", "span_id": 1,
            "parent_id": None, "t_start": 0.0, "t_end": 0.01,
            "attrs": attrs}


def ctx(spans):
    return types.SimpleNamespace(spans=spans)


def test_share_is_the_rounds_run_over_the_rounds_a_fixed_merge_runs():
    share = reader().read
    a = span("filter", dist_evals=32, merge_rounds=300,
             merge_rounds_full=786432)
    b = span("filter", dist_evals=32, merge_rounds=9000,
             merge_rounds_full=786432)
    assert share(ctx([a])) == pytest.approx(100 * 300 / 786432)
    # summed over the window, not a mean of per-call shares
    assert share(ctx([a, b, span("refine", comparisons=5)])) == \
        pytest.approx(100 * 9300 / (2 * 786432))


def test_share_reads_nothing_without_the_count():
    """The parent's filter spans carry no merge rounds, nor do those of
    the XLA path: the reader finds nothing and does not raise."""
    share = reader().read
    assert share(ctx([span("filter", dist_evals=32, bytes_scanned=9)])) \
        is None
    assert share(ctx([])) is None
    assert share(ctx([span("refine", merge_rounds=3)])) is None


CELLS = {
    "sift128-flat-int8.single64": {"clients": 8, "max_rate_qps": 200},
    "sift128-flat-int8.batch256": {"queries_per_request": 16,
                                   "max_rate_qps": 200},
}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_traced_run_reports_the_merge_share(workload, monkeypatch,
                                              capsys):
    from repro.serving.runtime.ingest import DeltaAwareBackend

    bench = harness.load_benchmark(ROOT)
    assert workload in harness.find(bench["per_layer"], NAME,
                                    "metric")["workloads"]
    # the chip's path: the Pallas kernel, here in interpret mode
    monkeypatch.setattr(DeltaAwareBackend, "_use_pallas", lambda self: True)
    res = harness.run_cell(workload, SEED, 1.5, True,
                           overrides={"config": TINY,
                                      "traffic": CELLS[workload]})
    facts = [json.loads(line[4:]) for line in
             capsys.readouterr().out.splitlines() if line.startswith("run ")]
    assert res["correct"], res["checks"]
    m = res["metrics"][NAME]
    assert m["unit"] == "%" and 0 < m["value"] <= 100 * (1 + 1 / 128)
    assert res["metrics"]["host_syncs.closed"]["value"] == 2.0
    assert set(facts[-1]["compiles_in_window"].values()) == {0}
