"""Stage profiling suite (EXPERIMENTS.md §Perf, DESIGN.md §13).

Per backend cell (flat f32, int8, pq8) and corpus size, the engine runs
under a `TraceRecorder` ambient span, so the engine's own `filter` /
`refine` child spans time the two stages and carry the measured
`bytes_scanned` / `comparisons` attributes.  Writes `BENCH_profile.json`
at the repo root (the profiling trajectory record) plus the harness's
results-dir copy.  Device time per kernel comes from a `jax.profiler`
capture, in which the same spans appear on the host threads (DESIGN.md
§13).

  PYTHONPATH=src python -m benchmarks.bench_profile --smoke

exits non-zero if serving throughput with full observability attached
(tracer + metrics) drops more than OVERHEAD_GATE (5%) below the
obs-disabled baseline, best-of-3 rounds each — the `obs-smoke` CI gate
for the "near-free" contract (DESIGN.md §13).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

from repro.core import dcpe, ppanns
from repro.data import synth
from repro.obs import Observability, TraceRecorder
from repro.serving.runtime import Collection
from repro.serving.search_engine import SecureSearchEngine

from .common import row

K = 10
RATIO_K = 8.0
NQ = 16
QUANTS = (None, "int8", "pq8")
OVERHEAD_GATE = 0.05

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _setup(n: int, d: int, nq: int, seed: int = 0):
    ds = synth.make_dataset("sift1m", n=n, n_queries=nq, d=d, k_gt=K,
                            seed=seed)
    beta = dcpe.suggest_beta(ds.base, fraction=0.01)
    owner = ppanns.DataOwner(d=d, sap_beta=beta, sap_s=1024.0, seed=seed)
    C_sap, C_dce = owner.encrypt_vectors(ds.base)
    user = ppanns.User(owner.share_keys(), seed=seed + 1)
    enc = [user.encrypt_query(q) for q in ds.queries]
    Q = np.stack([c for c, _ in enc])
    T = np.stack([t for _, t in enc])
    return ds, C_sap, C_dce, Q, T


def _profile_cell(C_sap, C_dce, Q, T, *, quantization: str | None,
                  seed: int, repeats: int):
    """One (backend, n) cell: span-level filter/refine seconds + bytes,
    averaged over `repeats` calls."""
    kw = dict(seed=seed) if quantization is not None else {}
    if quantization == "pq8":
        kw.update(pq_m=32, refine_ratio=8.0)
    eng = SecureSearchEngine(C_sap, C_dce, backend="flat",
                             quantization=quantization, **kw)
    eng.search_batch(Q, T, K, ratio_k=RATIO_K)       # warmup/compile
    rec = TraceRecorder()
    for i in range(repeats):
        with rec.span("profile", f"cell:{i}"):
            eng.search_batch(Q, T, K, ratio_k=RATIO_K)
    stages = {"filter": [], "refine": []}
    attrs = {}
    for sp in rec.spans():
        if sp.name in stages:
            stages[sp.name].append(sp.duration)
            attrs[sp.name] = sp.attrs
    return {
        "filter_s": sum(stages["filter"]) / repeats,
        "refine_s": sum(stages["refine"]) / repeats,
        "filter_bytes": int(attrs["filter"].get("bytes_scanned", 0)),
        "refine_comparisons": int(attrs["refine"].get("comparisons", 0)),
    }


def run(sizes=(10_000, 100_000), d: int = 128, nq: int = NQ,
        repeats: int = 3, seed: int = 0,
        write_root_json: bool = True) -> list[str]:
    rows = []
    for n in sizes:
        ds, C_sap, C_dce, Q, T = _setup(n, d, nq, seed)
        for quant in QUANTS:
            label = quant or "f32"
            c = _profile_cell(C_sap, C_dce, Q, T, quantization=quant,
                              seed=seed, repeats=repeats)
            rows.append(row(
                f"profile/n={n}/flat/{label}/filter",
                1e6 * c["filter_s"] / nq,
                f"bytes_scanned={c['filter_bytes']}"))
            rows.append(row(
                f"profile/n={n}/flat/{label}/refine",
                1e6 * c["refine_s"] / nq,
                f"comparisons={c['refine_comparisons']}"))
    if write_root_json:
        _write_root_json(rows, sizes, d, nq)
    return rows


def _write_root_json(rows: list[str], sizes, d: int, nq: int):
    """The repo-root BENCH_profile.json: the profiling trajectory record
    sessions diff against (the harness also writes its own copy under
    results/bench)."""
    from .run import provenance
    payload = {
        "suite": "profile",
        "unix_time": time.time(),
        "config": {"sizes": list(sizes), "d": d, "nq": nq, "k": K,
                   "ratio_k": RATIO_K},
        "provenance": provenance(),
        "rows": [{"name": r.split(",", 2)[0],
                  "us_per_call": float(r.split(",", 2)[1]),
                  "derived": r.split(",", 2)[2]} for r in rows],
    }
    (_ROOT / "BENCH_profile.json").write_text(
        json.dumps(payload, indent=2) + "\n")


# --------------------------------------------------- obs overhead smoke


def _serve_round(col, enc) -> float:
    """One closed-loop round: every query submitted (asynchronously)
    through the scheduler; returns queries/second."""
    t0 = time.perf_counter()
    futs = [col.submit(c, t, K) for c, t in enc]
    for f in futs:
        f.result(timeout=120)
    return len(enc) / (time.perf_counter() - t0)


def _overhead_qps(ds, obs_on: bool, *, seed: int, n_req: int,
                  rounds: int) -> float:
    """Best-of-`rounds` serving throughput with observability on/off.
    Same seed both ways: identical keys, corpus, and queries."""
    kw = {}
    if obs_on:
        obs = Observability()
        kw = dict(tracer=obs.recorder, metrics=obs.metrics)
    beta = dcpe.suggest_beta(ds.base, fraction=0.01)
    col = Collection("bench", f"ov-{int(obs_on)}", ds.base.shape[1],
                     sap_beta=beta, seed=seed, max_batch=8,
                     max_wait_ms=0.5, max_queue=4 * n_req, **kw)
    try:
        col.insert(ds.base)
        user = col.new_user()
        enc = [user.encrypt_query(ds.queries[i % len(ds.queries)])
               for i in range(n_req)]
        col.warmup(K)
        _serve_round(col, enc)                       # warm the path
        return max(_serve_round(col, enc) for _ in range(rounds))
    finally:
        col.close()


def _smoke(n: int = 20_000, d: int = 64, n_req: int = 128,
           rounds: int = 3, seed: int = 0) -> int:
    """CI gate: full observability (tracer + metrics) must cost <= 5%
    of obs-disabled serving throughput, best-of-3 rounds each side."""
    ds = synth.make_dataset("sift1m", n=n, n_queries=NQ, d=d, k_gt=K,
                            seed=seed)
    qps_off = _overhead_qps(ds, False, seed=seed, n_req=n_req,
                            rounds=rounds)
    qps_on = _overhead_qps(ds, True, seed=seed, n_req=n_req,
                           rounds=rounds)
    overhead = 1.0 - qps_on / qps_off
    print(row(f"profile-smoke/overhead/n={n}", 0.0,
              f"qps_off={qps_off:.1f} qps_on={qps_on:.1f} "
              f"overhead={100 * overhead:.2f}%"), flush=True)
    if overhead > OVERHEAD_GATE:
        print(f"# SMOKE FAIL: observability overhead "
              f"{100 * overhead:.2f}% > {100 * OVERHEAD_GATE:.0f}%")
        return 1
    print(f"# smoke OK: observability overhead {100 * overhead:.2f}% "
          f"<= {100 * OVERHEAD_GATE:.0f}% gate")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: obs-enabled serving within 5% of "
                         "obs-disabled throughput")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        sys.exit(_smoke())
    for r in run(sizes=(10_000, 100_000) if not args.full
                 else (10_000, 100_000, 200_000)):
        print(r)


if __name__ == "__main__":
    main()
