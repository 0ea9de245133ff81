"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig6] [--full]
      [--out-dir results/bench]

Prints `name,us_per_call,derived` CSV rows (scaffold convention) and
writes one machine-readable `BENCH_<suite>.json` per completed suite to
`--out-dir` — the perf-trajectory record that later sessions diff
against (EXPERIMENTS.md §Perf).
Default sizes are CPU-feasible; --full enlarges toward paper scale.
"""

from __future__ import annotations

import argparse
import datetime
import json
import pathlib
import subprocess
import sys
import time


def provenance() -> dict:
    """Attribution stamp for every BENCH_<suite>.json: which commit,
    when, and on what software/hardware the numbers were taken — without
    it the perf trajectory (history.jsonl) cannot be diffed meaningfully
    across sessions."""
    info: dict = {
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }
    try:
        info["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=pathlib.Path(__file__).resolve().parent.parent,
            capture_output=True, text=True, timeout=10,
            check=True).stdout.strip()
    except Exception:                               # noqa: BLE001
        info["git_sha"] = None
    import numpy as np
    info["numpy_version"] = np.__version__
    try:
        import jax
        dev = jax.devices()[0]
        info["jax_version"] = jax.__version__
        info["device"] = (f"{dev.platform}:"
                          f"{getattr(dev, 'device_kind', 'unknown')}")
        info["n_devices"] = jax.device_count()
    except Exception:                               # noqa: BLE001
        info["jax_version"] = info["device"] = None
    return info


def _parse_row(r: str) -> dict:
    """'name,us,derived...' -> dict (derived may itself contain commas)."""
    name, us, derived = r.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    return {"name": name, "us_per_call": us_val, "derived": derived}


def write_suite_json(out_dir: pathlib.Path, suite: str, rows: list[str],
                     wall_s: float, full: bool) -> pathlib.Path:
    """BENCH_<suite>.json holds the latest run; history.jsonl accumulates
    every run (one JSON object per line) — that append-only log is the
    perf trajectory later sessions diff against."""
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"BENCH_{suite}.json"
    payload = {
        "suite": suite,
        "unix_time": time.time(),
        "wall_s": round(wall_s, 3),
        "full": full,
        "provenance": provenance(),
        "rows": [_parse_row(r) for r in rows],
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")
    with (out_dir / "history.jsonl").open("a") as fh:
        fh.write(json.dumps(payload) + "\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out-dir", default="results/bench",
                    help="directory for BENCH_<suite>.json records")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (bench_attacks, bench_baselines, bench_batched,
                   bench_beta, bench_encrypt, bench_filter, bench_graph,
                   bench_kernels, bench_profile, bench_ratio_k,
                   bench_refine, bench_resilience, bench_roofline,
                   bench_runtime, bench_scalability)

    suites = {
        "fig4_beta": lambda: bench_beta.run(
            n=20000 if args.full else 6000),
        "fig5_ratio_k": lambda: bench_ratio_k.run(
            n=20000 if args.full else 8000),
        "fig6_refine": lambda: bench_refine.run(
            n=20000 if args.full else 6000),
        "fig7_9_baselines": lambda: bench_baselines.run(
            n=20000 if args.full else 6000),
        "fig8_encrypt": lambda: bench_encrypt.run(),
        "fig10_scalability": lambda: bench_scalability.run(
            sizes=(10000, 20000, 40000, 80000) if args.full
            else (5000, 10000, 20000, 40000)),
        # mesh-sharded placement (DESIGN.md §10): in this process over
        # the real accelerators, or over 8 simulated CPU devices in a
        # child process on a CPU run
        "sharded": lambda: bench_scalability.run_sharded(
            n=16000 if args.full else 6000),
        # quantized ADC filter path: f32 vs int8 vs pq8 (DESIGN.md §11);
        # also writes the repo-root BENCH_filter.json trajectory record
        "filter": lambda: bench_filter.run(
            sizes=(10_000, 100_000, 200_000) if args.full
            else (10_000, 100_000)),
        # batched CSR graph traversal vs the per-query host walk over
        # one identical owner-built HNSW (DESIGN.md §15); also writes
        # the repo-root BENCH_graph.json trajectory record.  The hard
        # gate (batched > host-walk QPS + id parity) lives in
        # `python -m benchmarks.bench_graph --smoke` (CI)
        # (no --full enlargement: the owner-side host build is pure
        # Python and 200k would dominate the whole harness's wall time)
        "graph": lambda: bench_graph.run(sizes=(10_000, 100_000)),
        # span-level filter/refine stage timing + kernel-level op timing
        # per backend (DESIGN.md §13); also writes the repo-root
        # BENCH_profile.json trajectory record.  The hard gate (obs
        # overhead <= 5%) lives in
        # `python -m benchmarks.bench_profile --smoke` (CI)
        "profile": lambda: bench_profile.run(
            sizes=(10_000, 100_000, 200_000) if args.full
            else (10_000, 100_000)),
        "batched_engine": lambda: bench_batched.run(
            n=20000 if args.full else 6000),
        # measurement only — the hard smoke gate (occupancy/recompiles)
        # lives in `python -m benchmarks.bench_runtime --smoke` (CI)
        "runtime": lambda: bench_runtime.run(
            n=20000 if args.full else 6000, smoke=False),
        # flush vs continuous slot-table scheduler under Poisson arrivals
        # (DESIGN.md §12); also writes the repo-root BENCH_runtime.json
        # trajectory record.  The hard gate lives in
        # `python -m benchmarks.bench_runtime --sweep --smoke` (CI)
        "runtime_sweep": lambda: bench_runtime.run_sweep(
            n=20000 if args.full else 6000, smoke=False),
        # normalized ASPE KPA rows + the security-profile
        # leakage-vs-QPS frontier (DESIGN.md §14); also writes the
        # repo-root BENCH_attacks.json trajectory record.  The hard
        # gate (hardened at-chance, balanced <= 25% QPS cost) lives
        # in `python -m benchmarks.bench_attacks --smoke` (CI)
        "attacks": lambda: bench_attacks.run(
            n=32_768 if args.full else 16_384),
        # recovery-time vs WAL length, checkpoint-interval vs replay
        # cost, failover QPS healthy vs dead-replica (DESIGN.md §16);
        # also writes the repo-root BENCH_resilience.json trajectory
        # record.  The hard gate (digest-identical recovery, invisible
        # replica failover) lives in
        # `python -m benchmarks.bench_resilience --smoke` (CI)
        "resilience": lambda: bench_resilience.run(
            n_records=(100, 400, 1600) if args.full
            else (50, 200, 800)),
        "kernels": lambda: bench_kernels.run(),
        "roofline": lambda: bench_roofline.run(),
    }

    out_dir = pathlib.Path(args.out_dir)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows = list(fn())
            for r in rows:
                print(r, flush=True)
            wall = time.time() - t0
            path = write_suite_json(out_dir, name, rows, wall, args.full)
            print(f"# {name} done in {wall:.1f}s -> {path}", flush=True)
        except Exception as e:                      # noqa: BLE001
            failed.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
