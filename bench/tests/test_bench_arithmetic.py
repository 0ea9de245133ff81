"""Metric arithmetic, the correctness numbers, work counts and peaks."""

import importlib.util
import math
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, roofline, stats  # noqa: E402

METRICS = ROOT / "bench" / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"_t_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- rates, latencies, tails ------------------------------------------------

def test_rate_counts_only_work_done_inside_the_window_over_its_length():
    done = np.array([9.9, 10.0, 10.5, 12.0, 13.0, np.nan])
    units = np.array([1, 2, 4, 8, 16, 32])
    # window [10, 13]: 2 + 4 + 8 + 16 rows over 3 s; the one before the
    # window, and the one never answered, do not count
    assert stats.rate(done, units, 10.0, 3.0) == pytest.approx(30 / 3)


def test_closed_rate_is_all_answered_work_over_the_time_to_the_last():
    done = np.array([10.5, 12.0, 13.25, np.nan])
    units = np.array([4, 8, 16, 32])
    # the window opened at 10; the last answer came at 13.25, after the
    # close: 4 + 8 + 16 rows over 3.25 s, the unanswered one adds nothing
    assert stats.closed_rate(done, units, 10.0) == pytest.approx(28 / 3.25)
    assert stats.closed_rate(np.array([np.nan]), np.array([1]), 0.0) == 0.0


def test_latency_runs_from_the_due_time_and_a_missing_answer_is_infinite():
    due = np.array([0.0, 1.0, 2.0])
    done = np.array([0.010, 1.5, np.nan])
    lat = stats.latencies_ms(due, done)
    assert lat[0] == pytest.approx(10.0)
    assert lat[1] == pytest.approx(500.0)     # sent late: still counted
    assert math.isinf(lat[2])


def test_tail_is_taken_over_every_request():
    lat = np.arange(1, 101, dtype=float)        # 1..100 ms
    assert stats.percentile(lat, 50) == 50.0
    assert stats.percentile(lat, 99) == 99.0
    lat[-2:] = np.inf                           # two requests failed
    assert math.isinf(stats.percentile(lat, 99))
    assert stats.percentile(np.array([7.0]), 99) == 7.0
    with pytest.raises(ValueError):
        stats.percentile(np.array([]), 50)


# -- the correctness numbers -------------------------------------------------

def test_malformed_rows_need_k_distinct_ids_of_stored_rows():
    ids = np.array([[0, 1, 2], [3, 3, 4], [5, 6, 10], [-1, 1, 2]])
    assert check.malformed_rows(ids, 10).tolist() == [False, True, True,
                                                      True]


def test_miss_rate_is_one_minus_recall():
    ref = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    ids = np.array([[4, 3, 2, 9], [5, 6, 7, 8]])
    assert check.miss_rate(ids, ref) == pytest.approx(1 / 8)


def test_order_violations_count_descents_beyond_the_gap():
    base = np.array([[0.0], [1.0], [2.0], [3.0], [3.0001]], np.float32)
    queries = np.zeros((1, 1), np.float32)
    good = np.array([[0, 1, 2, 3]])
    swapped = np.array([[1, 0, 2, 3]])          # dist 1 > 0
    near_tie = np.array([[0, 1, 2, 4, 3]])      # 9.0006 > 9 by 7e-5
    q = np.array([0])
    assert check.order_violations(good, q, base, queries) == [0.0]
    assert check.order_violations(swapped, q, base, queries) == [1 / 3]
    assert check.order_violations(near_tie, q, base, queries,
                                  gaps=(0.0, 1e-3)) == [1 / 4, 0.0]


def test_judge_holds_every_number_to_its_limit():
    limits = {"lost": 0, "malformed": 0, "miss_rate": 0.1,
              "order_violations": 0.01}
    nums = {"lost": 0, "malformed": 0, "miss_rate": 0.05,
            "order_violations": 0.0}
    ok, lines = check.judge(nums, limits)
    assert ok and len(lines) == 4 and lines[2].startswith("miss_rate 0.05")
    assert not check.judge(dict(nums, lost=1), limits)[0]
    assert not check.judge(dict(nums, order_violations=0.02), limits)[0]
    with pytest.raises(KeyError):
        check.judge(nums, {"lost": 0})


# -- peaks and work counts ---------------------------------------------------

def test_peaks_table_has_the_v5e_and_refuses_an_unknown_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_least_time_names_the_bound_that_applies():
    assert roofline.least_time(1e12, 1.0, 1e12, 1.0) == (1.0, "compute")
    assert roofline.least_time(1.0, 2e9, 1e12, 1e9) == (2.0, "memory")


def _ctx(**kw):
    base = dict(n_rows=1_000_000, d=128, k=10, kp=160, rows_per_call=32.0,
                device_kind="TPU v5 lite", trace=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_filter_work_counts():
    ops, nbytes, peak = reader("l2_topk_roofline").work(_ctx())
    assert ops == 2 * 32 * 1_000_000 * 128
    assert nbytes == 4 * 1_000_000 * 128 and peak == "bf16_flops_per_s"
    ops, nbytes, peak = reader("sq_adc_topk_roofline").work(_ctx())
    assert ops == 2 * 32 * 1_000_000 * 128
    assert nbytes == 1_000_000 * 128 and peak == "int8_ops_per_s"


def test_refine_work_counts_the_fewest_comparisons_of_a_selection():
    ops, nbytes, peak = reader("dce_comp_roofline").work(_ctx(kp=160))
    width = 2 * 128 + 16
    cmp = 159 + 9 * 8                           # ceil(log2 160) = 8
    assert ops == 32 * cmp * 4 * width
    assert nbytes == 32 * 160 * 4 * width * 4
    assert peak == "bf16_flops_per_s"


def _trace(module, calls, busy_ns_each):
    ev, t = [], 0.0
    for i in range(calls):
        ev.append({"plane": "/device:TPU:0", "line": "XLA Modules",
                   "name": f"{module}({i})", "start_ns": t,
                   "dur_ns": busy_ns_each})
        ev.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                   "name": "fusion", "start_ns": t, "dur_ns": busy_ns_each,
                   "module": module})
        t += 2 * busy_ns_each
    return ev


def test_roofline_share_is_calls_times_least_time_over_device_time():
    # 4 filter calls, each busy 6.25 ms; least time 0.625 ms (memory)
    ctx = _ctx(trace=_trace("jit_knn", 4, 6.25e6))
    share = reader("l2_topk_roofline").read(ctx)
    least = 4 * 1_000_000 * 128 / 819e9
    assert share == pytest.approx(100 * least / 6.25e-3)
    # nothing of the program in the trace: no reading, never 0
    assert reader("sq_adc_topk_roofline").read(ctx) is None
    assert reader("l2_topk_roofline").read(_ctx()) is None


def test_span_readers():
    spans = [
        {"name": "flush", "t_start": 0.0, "t_end": 0.010,
         "attrs": {"n_real": 30, "bucket": 32}},
        {"name": "flush", "t_start": 0.0, "t_end": 0.010,
         "attrs": {"n_real": 30, "batch": "b0"}},   # per-request child
        {"name": "flush", "t_start": 0.02, "t_end": 0.03,
         "attrs": {"n_real": 32, "bucket": 32}},
        {"name": "filter", "t_start": 0.0, "t_end": 0.008, "attrs": {}},
        {"name": "filter", "t_start": 0.02, "t_end": 0.026, "attrs": {}},
        {"name": "refine", "t_start": 0.008, "t_end": 0.010, "attrs": {}},
        {"name": "queue", "t_start": 0.0, "t_end": 0.004, "attrs": {}},
    ]
    ctx = types.SimpleNamespace(spans=spans)
    assert reader("batch_rows.closed").read(ctx) == 31
    assert reader("filter_ms.closed").read(ctx) == pytest.approx(7.0)
    assert reader("refine_ms.closed").read(ctx) == pytest.approx(2.0)
    assert reader("queue_ms.open").read(ctx) == pytest.approx(4.0)
    empty = types.SimpleNamespace(spans=[])
    assert reader("batch_rows.closed").read(empty) is None
