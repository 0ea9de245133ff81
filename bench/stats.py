"""Arithmetic of the end-to-end metrics.

A closed loop's rate is all the work its clients sent in the window,
every piece of it answered, over the time from the window's start to the
last answer: the clients stop sending when the window closes and wait
for what they have in flight, so no request is cut at the close and the
rate moves by less than one request's worth from run to run.  A latency runs from the time a request was due, not from when
it was sent, so a late generator shows as latency.  A tail is taken over
every request due in the window; one that failed or never came counts
as infinitely late.
"""

from __future__ import annotations

import math

import numpy as np


def rate(done_times: np.ndarray, units: np.ndarray, t0: float,
         seconds: float) -> float:
    """Units of work (query rows) completed in [t0, t0 + seconds], per
    second of the window (the knee sweep's keep-up test)."""
    done_times = np.asarray(done_times, np.float64)
    inside = (done_times >= t0) & (done_times <= t0 + seconds)
    return float(np.asarray(units)[inside].sum() / seconds)


def closed_rate(done_times: np.ndarray, units: np.ndarray,
                t0: float) -> float:
    """Units of work (query rows) answered, over the seconds from the
    window's start `t0` to the last answer.  NaN times (never answered)
    add no work."""
    done_times = np.asarray(done_times, np.float64)
    answered = ~np.isnan(done_times)
    if not answered.any():
        return 0.0
    elapsed = float(done_times[answered].max()) - t0
    return float(np.asarray(units)[answered].sum() / elapsed)


def latencies_ms(t_due: np.ndarray, t_done: np.ndarray) -> np.ndarray:
    """Due-to-answer latency in ms; NaN answers (failed, never came)
    become +inf."""
    lat = (np.asarray(t_done, np.float64)
           - np.asarray(t_due, np.float64)) * 1e3
    return np.where(np.isnan(lat), np.inf, lat)


def percentile(values: np.ndarray, p: float) -> float:
    """Nearest-rank percentile (0 < p <= 100) of all values: the
    smallest value with at least p% of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(p / 100.0 * v.size))
    return float(v[rank - 1])
