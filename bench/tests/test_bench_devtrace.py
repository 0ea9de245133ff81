"""The trace reducer on a small trace recorded on a TPU v5 lite: the end of
one filter (`jit_knn`) execution, the refine (`jit_refine_candidates`)
after it, and the start of the next filter."""

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace  # noqa: E402

DATA = json.loads((pathlib.Path(__file__).parent / "data"
                   / "chip_trace_small.json").read_text())


@pytest.fixture()
def trace():
    events = devtrace.attach_modules([dict(e) for e in DATA["events"]])
    lo, hi = DATA["window_ns"]
    return devtrace.clip(events, lo, hi), (lo, hi)


def busy_by_grid(events, lo, hi, module=None):
    """Busy time counted on a 100 ns grid: an independent check of the
    interval union."""
    grid = np.zeros(int((hi - lo) / 100) + 1, bool)
    for e in events:
        if e["line"] != devtrace.OPS_LINE:
            continue
        if module is not None and e.get("module") != module:
            continue
        a = int((e["start_ns"] - lo) / 100)
        b = int((e["start_ns"] + e["dur_ns"] - lo) / 100)
        grid[a:b] = True
    return grid.sum() * 100.0


def test_every_op_gets_the_program_it_ran_in(trace):
    events, _ = trace
    mods = {e.get("module") for e in events
            if e["line"] == devtrace.OPS_LINE}
    assert mods == {"jit_knn", "jit_refine_candidates"}
    assert devtrace.module_name("jit_knn(17599287626563774906)") == "jit_knn"


def test_busy_union_and_idle_share(trace):
    events, (lo, hi) = trace
    s = devtrace.summary(events, (lo, hi))
    expect = busy_by_grid(events, lo, hi)
    assert s["busy_s"] * 1e9 == pytest.approx(expect, rel=2e-3)
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert s["idle"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
    # two host gaps (after the filter and after the refine) lie inside
    assert 0.2 < s["idle"] < 0.5


def test_the_window_is_cut_to_the_device_ops_recorded(trace):
    events, (lo, hi) = trace
    ops = [e for e in events if e["line"] == devtrace.OPS_LINE]
    first = min(e["start_ns"] for e in ops)
    last = max(e["start_ns"] + e["dur_ns"] for e in ops)
    # a second before the first op and after the last: the profiler saw
    # nothing there, which need not be idle
    assert devtrace.observed(events, (lo - 1e9, hi + 1e9)) == (first, last)
    assert devtrace.observed(events, (first + 10, last - 10)) == \
        (first + 10, last - 10)
    assert devtrace.observed([], (lo, hi)) == (lo, hi)


def test_per_program_device_time_and_calls(trace):
    events, (lo, hi) = trace
    ref = devtrace.program(events, "jit_refine_candidates")
    assert ref["calls"] == 1
    assert ref["busy_s"] * 1e9 == pytest.approx(
        busy_by_grid(events, lo, hi, "jit_refine_candidates"), rel=2e-3)
    # the refine ran about 18.8 ms on this chip
    assert 0.015 < ref["busy_s"] < 0.02
    knn = devtrace.program(events, "jit_knn")
    assert knn["calls"] == 2                # cut at both ends
    assert devtrace.program(events, "jit_sq_knn") == {"busy_s": 0.0,
                                                      "calls": 0}


def test_breakdown_lists_top_ops_and_longest_gaps(trace):
    events, window = trace
    ops = devtrace.top_ops(events)
    assert 1 <= len(ops) <= 10
    secs = [s for _, s in ops]
    assert secs == sorted(secs, reverse=True)
    assert ops[0][0] == "jit_refine_candidates/%copy.4 copy"
    gaps = devtrace.idle_gaps(events, window, lambda t: "x")
    assert [label for label, _ in gaps] == ["x"] * len(gaps)
    lengths = [g for _, g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    # the host gap after the refine (5.6 ms) and after the filter (3.4)
    assert lengths[0] == pytest.approx(5.55e-3, rel=0.05)
    assert lengths[1] == pytest.approx(3.40e-3, rel=0.05)


def test_gaps_are_labelled_from_spans_on_the_trace_clock(trace):
    events, window = trace
    offset = devtrace.sync_offset_ns(events + [
        e for e in DATA["events"] if e["name"] == devtrace.SYNC_EVENT],
        DATA["t_sync_s"])
    sync = [e for e in DATA["events"] if e["name"] == devtrace.SYNC_EVENT]
    assert offset == sync[0]["start_ns"] - DATA["t_sync_s"] * 1e9
    gaps = devtrace.idle_gaps(events, window, lambda t: t)
    mid = gaps[1][0]                         # the gap after the filter
    t = (mid - offset) / 1e9
    spans = [{"name": "flush", "t_start": t - 0.05, "t_end": t + 0.05},
             {"name": "refine", "t_start": t - 0.001, "t_end": t + 0.02}]
    label = devtrace.span_label(spans, offset)
    assert label(mid) == "host: refine"
    assert label(mid + 0.06e9) == "host: no batch open"
    assert devtrace.span_label(spans, None)(mid) == "unattributed"


def test_op_names_are_cut_to_name_and_opcode():
    assert devtrace.op_name(
        "%sort.7 = (f32[32,4256]{1,0:T(8,128)S(1)}, s32[32,4256]{1,0}) "
        "sort(f32[32,4256]{1,0} %x), dimensions={1}") == "%sort.7 sort"
    assert devtrace.op_name(
        "%p.7 = f32[128,4096]{1,0:T(8,128)S(1)} custom-call(f32[128,128] "
        "%a)") == "%p.7 custom-call"
    assert devtrace.op_name("plain") == "plain"


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                (5, 8)]
