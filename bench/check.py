"""The comparison that decides `correct`.

Every answer the window produced is held against the plain reference
(`reference.py`) of its own plaintext query:

  lost              requests that never got an answer (refusals under
                    admission control are counted as failed, not lost)
  malformed         answers without k distinct ids of stored rows
  miss_rate         1 - recall@k against the exact top-k: the filter's
                    candidate sets, and an answer that went to another
                    request, show here
  order_violations  share of neighbouring ids in an answer whose
                    plaintext distances (float64, on the host) descend
                    by more than the relative gap `order_gap` that the
                    configuration's guarantee allows: the DCE refine's
                    ordering shows here

Each number has its limit in the configuration's file, under `limits`;
a run is correct when no number is above its limit.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("lost", "malformed", "miss_rate", "order_violations")
CHUNK = 8192
# relative gaps at which the order violations are also recorded
GAPS = (0.0, 1e-5, 1e-4, 1e-3, 1e-2)


def malformed_rows(ids: np.ndarray, n: int) -> np.ndarray:
    """Rows without k distinct ids in [0, n)."""
    ids = np.asarray(ids)
    in_range = ((ids >= 0) & (ids < n)).all(axis=1)
    s = np.sort(ids, axis=1)
    distinct = (np.diff(s, axis=1) != 0).all(axis=1)
    return ~(in_range & distinct)


def miss_rate(ids: np.ndarray, ref: np.ndarray) -> float:
    """1 - recall@k of `ids` (m, k) against `ref` (m, k)."""
    ids = np.asarray(ids)
    ref = np.asarray(ref)
    if ids.shape[0] == 0:
        return 0.0
    hits = (ids[:, :, None] == ref[:, None, :]).any(axis=2).sum()
    return float(1.0 - hits / ref.size)


def order_violations(ids: np.ndarray, qrows: np.ndarray, base: np.ndarray,
                     queries: np.ndarray, gaps=(0.0,)) -> list[float]:
    """For each relative gap g of `gaps`: the share of adjacent pairs
    (j, j+1) of each answer with dist(ids[j]) > dist(ids[j+1]) * (1 + g),
    in float64 plaintext distance."""
    ids = np.asarray(ids)
    m, k = ids.shape
    if m == 0 or k < 2:
        return [0.0] * len(gaps)
    bad = np.zeros(len(gaps), np.int64)
    for i in range(0, m, CHUNK):
        rows = base[np.clip(ids[i: i + CHUNK], 0, base.shape[0] - 1)]
        q = queries[qrows[i: i + CHUNK]]
        diff = rows.astype(np.float64) - q[:, None, :].astype(np.float64)
        dist = np.einsum("mkd,mkd->mk", diff, diff)
        for j, g in enumerate(gaps):
            bad[j] += int((dist[:, :-1] > dist[:, 1:] * (1.0 + g)).sum())
    return [int(b) / (m * (k - 1)) for b in bad]


def numbers(ids: np.ndarray, qrows: np.ndarray, ref: np.ndarray,
            base: np.ndarray, queries: np.ndarray, *, lost: int,
            order_gap: float) -> tuple[dict, dict]:
    """The compared numbers for answers `ids` (m, k) to pool queries
    `qrows` (m,), whose exact top-k is `ref` (m, k); and, for the record,
    the order violations at each gap of `GAPS`."""
    bad = malformed_rows(ids, base.shape[0])
    good = ~bad
    gaps = (order_gap,) + GAPS
    viol = order_violations(ids[good], qrows[good], base, queries, gaps)
    nums = {"lost": int(lost), "malformed": int(bad.sum()),
            "miss_rate": miss_rate(ids[good], ref[good]),
            "order_violations": viol[0]}
    return nums, {f"{g:g}": v for g, v in zip(GAPS, viol[1:])}


def judge(nums: dict, limits: dict) -> tuple[bool, list[str]]:
    """(correct, one 'name number limit' line per compared number)."""
    missing = set(NUMBERS) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    lines = [f"{name} {nums[name]!r} limit {limits[name]!r}"
             for name in NUMBERS]
    return all(nums[name] <= limits[name] for name in NUMBERS), lines
