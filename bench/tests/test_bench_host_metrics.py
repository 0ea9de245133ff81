"""The host layer's readers: `host_ms.closed` and `host_syncs.closed` on
hand-built spans, and a traced run at a tiny size on the CPU of each
cell that lists them."""

import importlib.util
import itertools
import json
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

METRICS = ROOT / "bench" / "metrics"
SEED = 2**31 + 777
TINY = {"n": 2048, "n_queries": 128, "ratio_k": 2}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"_h_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_IDS = itertools.count(1)


def trace(tid, *rows):
    """The spans of one trace, from rows (name, t_start, t_end, index of
    the parent row or None, attrs)."""
    out = []
    for name, t0, t1, parent, attrs in rows:
        out.append({"name": name, "trace_id": tid, "span_id": next(_IDS),
                    "parent_id": None if parent is None
                    else out[parent]["span_id"],
                    "t_start": t0, "t_end": t1, "attrs": attrs})
    return out


def ctx(spans):
    return types.SimpleNamespace(spans=spans)


# one flush of the micro-batcher: 2 ms collecting, 1 ms assembly, a
# 20 ms engine call of which 16 ms wait in two syncs, 1 ms delivery;
# and one direct batch request of 30 ms with one 25 ms sync
FLUSH = trace(
    "c:b0",
    ("batch", 0.000, 0.024, None, {"n_real": 32}),
    ("collect", 0.000, 0.002, 0, {}),
    ("assemble", 0.002, 0.003, 0, {}),
    ("flush", 0.003, 0.023, 0, {"n_real": 32, "bucket": 32}),
    ("filter", 0.003, 0.015, 3, {}),
    ("sync", 0.004, 0.014, 4, {"what": "candidates"}),
    ("refine", 0.015, 0.023, 3, {}),
    ("sync", 0.016, 0.022, 6, {"what": "ids"}),
    ("deliver", 0.023, 0.024, 0, {}))
DIRECT = trace(
    "c:d0",
    ("batch", 0.100, 0.130, None, {"n_real": 256}),
    ("filter", 0.101, 0.128, 0, {}),
    ("sync", 0.102, 0.127, 1, {"what": "candidates"}))
# the request spans of the same flush, stamped after the fact: the
# reader must not take their `flush` child or anything else for host
# work of an engine call
REQUEST = trace(
    "c:r0",
    ("request", -0.001, 0.024, None, {"k": 10}),
    ("queue", -0.001, 0.003, 0, {}),
    ("flush", 0.003, 0.023, 0, {"batch": "c:b0"}))


def test_host_ms_is_the_batch_root_less_collect_and_syncs():
    host_ms = reader("host_ms.closed")
    # 24 ms root - 2 ms collect - 10 ms and 6 ms of syncs = 6 ms
    assert host_ms.read(ctx(FLUSH + REQUEST)) == pytest.approx(6.0)
    # 30 ms - 25 ms of sync = 5 ms; the mean over both roots
    assert host_ms.read(ctx(DIRECT)) == pytest.approx(5.0)
    assert host_ms.read(ctx(FLUSH + DIRECT + REQUEST)) == \
        pytest.approx(5.5)


def test_host_syncs_counts_the_sync_spans_of_each_batch_trace():
    syncs = reader("host_syncs.closed")
    assert syncs.read(ctx(FLUSH + REQUEST)) == 2.0
    assert syncs.read(ctx(FLUSH + DIRECT)) == pytest.approx(1.5)


@pytest.mark.parametrize("name", ["host_ms.closed", "host_syncs.closed"])
def test_host_readers_find_nothing_without_batch_roots(name):
    """A program that opens no `batch` spans (as before they existed)
    reads as nothing, and a trace whose batch was discarded before any
    request rode it (`n_real` 0) does not count."""
    r = reader(name)
    old = trace("c:b0",
                ("flush", 0.0, 0.02, None, {"n_real": 32, "bucket": 32}),
                ("filter", 0.0, 0.01, 0, {}))
    assert r.read(ctx(old + REQUEST)) is None
    empty = trace("c:b1", ("batch", 0.0, 0.01, None, {"n_real": 0}),
                  ("collect", 0.0, 0.01, 0, {}))
    assert r.read(ctx(empty)) is None
    assert r.read(ctx(empty + FLUSH)) == r.read(ctx(FLUSH))


# each cell that lists the host metrics: its traffic at a tiny size and
# the device->host reads per engine call its filter makes (f32: the
# candidates and their distances, then the refined ids; int8: the
# candidates, then the ids)
CELLS = {
    "sift128-flat-f32.single64": ({"clients": 8, "max_rate_qps": 200},
                                  3.0),
    "sift128-flat-int8.single64": ({"clients": 8, "max_rate_qps": 200},
                                   2.0),
    "sift128-flat-int8.batch256": ({"queries_per_request": 16,
                                    "max_rate_qps": 200}, 2.0),
}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_traced_run_reports_the_host_metrics(workload, capsys):
    traffic, syncs = CELLS[workload]
    bench = harness.load_benchmark(ROOT)
    for name in ("host_ms.closed", "host_syncs.closed"):
        listed = harness.find(bench["per_layer"], name, "metric")
        assert workload in listed["workloads"]
    res = harness.run_cell(workload, SEED, 1.5, True,
                           overrides={"config": TINY, "traffic": traffic})
    facts = [json.loads(line[4:]) for line in
             capsys.readouterr().out.splitlines() if line.startswith("run ")]
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["host_syncs.closed"] == {"value": syncs, "unit": "syncs"}
    assert m["host_ms.closed"]["unit"] == "ms"
    assert m["host_ms.closed"]["value"] > 0
    assert set(facts[-1]["compiles_in_window"].values()) == {0}
