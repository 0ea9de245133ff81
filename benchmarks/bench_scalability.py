"""Fig. 10 — scalability: latency vs database size at fixed recall,
plus the `sharded` suite: the same filter-and-refine pipeline
row-sharded over 1/2/8 simulated devices (DESIGN.md §10).

The paper sweeps 25M..100M; CPU-scaled here to 5k..40k with the same
sublinearity check (HNSW latency ~ O(log n)).  Alongside the paper's
per-query walk we time the unified engine's batched path (DESIGN.md §2):
same HNSW filter, one jitted refine for the whole batch.

The sharded suite needs more than one XLA device.  On accelerators it
runs in this process over the real chips: a chip belongs to one process,
so a child could not reach it while this one holds it.  When JAX's
backend is the CPU, the devices must be forced *before* jax
initializes, so `run_sharded()` re-executes this module in a CPU-only
child with `XLA_FLAGS=--xla_force_host_platform_device_count=8` and
collects its rows (`python -m benchmarks.bench_scalability --sharded`
runs the measurement directly)."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from repro.core import ppanns
from repro.data import synth

from .common import row, timeit


def run(sizes=(5000, 10000, 20000, 40000), nq: int = 15) -> list[str]:
    rows = []
    lat = {}
    for n in sizes:
        ds = synth.make_dataset("sift1m", n=n, n_queries=nq, k_gt=20, seed=2)
        owner, user, server = ppanns.build_system(
            ds.base, beta_fraction=0.03, M=16, ef_construction=100, seed=2)
        enc = [user.encrypt_query(q) for q in ds.queries]

        def search_all():
            return np.stack([server.search(cs, tq, 10, ratio_k=8,
                                           ef_search=128)[0]
                             for cs, tq in enc])
        t, found = timeit(search_all, repeats=1)
        rec = synth.recall_at_k(found, ds.gt, 10)
        lat[n] = t / nq
        rows.append(row(f"fig10/n={n}", 1e6 * t / nq,
                        f"recall={rec:.3f} qps={nq / t:.1f}"))

        Q = np.stack([c for c, _ in enc])
        T = np.stack([tq for _, tq in enc])
        tb, (found_b, _) = timeit(server.search_batch, Q, T, 10,
                                  ratio_k=8, ef_search=128, repeats=1)
        np.testing.assert_array_equal(found_b, found)   # engine parity
        rows.append(row(f"fig10/batched/n={n}", 1e6 * tb / nq,
                        f"qps={nq / tb:.1f} speedup_x{t / tb:.2f}"))
    # sublinearity: latency growth should be far below linear in n
    n0, n1 = sizes[0], sizes[-1]
    growth = lat[n1] / lat[n0]
    rows.append(row("fig10/sublinearity", 0.0,
                    f"nx{n1 // n0} latency x{growth:.2f} (linear would be "
                    f"x{n1 // n0})"))
    return rows


# ---------------------------------------------------------------------------
# sharded suite — one service surface, deployment as a parameter.
# ---------------------------------------------------------------------------

def _run_sharded_inproc(n: int, nq: int, shards=(1, 2, 8)) -> list[str]:
    """Batched submit() latency per (backend, shard count) + exact-id
    parity against the single-device placement.  Requires enough XLA
    devices; see `run_sharded` for the subprocess wrapper."""
    import dataclasses

    import jax

    from repro.api import (DataOwnerClient, IndexSpec, PlacementSpec,
                           SearchParams, SearchRequest, SecureAnnService,
                           suggest_beta)

    ds = synth.make_dataset("sift1m", n=n, n_queries=nq, d=64, k_gt=10,
                            seed=3)
    base = IndexSpec(tenant="bench", name="base", d=64,
                     sap_beta=suggest_beta(ds.base, fraction=0.03), seed=3)
    owner = DataOwnerClient(base)
    C_sap, C_dce = owner.encrypt_vectors(ds.base, seed=11)
    query = owner.query_client().encrypt_queries(ds.queries)
    params = SearchParams(k=10, ratio_k=8.0)

    rows = []
    for backend in ("flat", "ivf"):
        extra = dict(n_partitions=64, nprobe=8) if backend == "ivf" else {}
        spec = dataclasses.replace(base, backend=backend,
                                   name=backend, **extra)
        req = SearchRequest(tenant="bench", collection=spec.name,
                            query=query, params=params, coalesce=False)
        # the single-device placement is the parity reference AND the
        # baseline row every sharded cell is compared against
        with SecureAnnService() as svc:
            svc.create_collection(spec)
            svc.insert("bench", spec.name, C_sap, C_dce)
            svc.submit(req)                             # build + compile
            t, res = timeit(svc.submit, req, repeats=3)
            ref_ids = res.ids
            rec = synth.recall_at_k(ref_ids, ds.gt, 10)
            rows.append(row(f"sharded/{backend}/single", 1e6 * t / nq,
                            f"recall={rec:.3f} qps={nq / t:.1f} n={n}"))
        for n_shards in shards:
            if n_shards > jax.device_count():
                rows.append(row(f"sharded/{backend}/shards={n_shards}",
                                0.0, "SKIPPED: not enough devices"))
                continue
            with SecureAnnService() as svc:
                svc.create_collection(spec, placement=PlacementSpec(
                    kind="sharded", n_shards=n_shards))
                svc.insert("bench", spec.name, C_sap, C_dce)
                svc.submit(req)                         # build + compile
                t, res = timeit(svc.submit, req, repeats=3)
                # bit-identical to the single-device placement
                np.testing.assert_array_equal(res.ids, ref_ids)
                rows.append(row(
                    f"sharded/{backend}/shards={n_shards}", 1e6 * t / nq,
                    f"qps={nq / t:.1f} n={n} parity=exact-vs-single"))
    return rows


def run_sharded(n: int = 6000, nq: int = 16) -> list[str]:
    """The sharded suite rows: in-process over the real devices, or —
    on a CPU run — from a CPU-only child with 8 forced host devices
    (jax pins its device count at first init, so the flag cannot be set
    in-process once any other suite has run)."""
    import jax
    if jax.default_backend() != "cpu":
        n_dev = jax.device_count()
        shards = tuple(s for s in (1, 2, 4, 8) if s <= n_dev)
        return _run_sharded_inproc(n, nq, shards=shards)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scalability", "--sharded",
         "--n", str(n), "--nq", str(nq)],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded subprocess failed:\n"
                           f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return [l for l in proc.stdout.splitlines()
            if l.startswith("sharded/")]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--n", type=int, default=6000)
    ap.add_argument("--nq", type=int, default=16)
    args = ap.parse_args()
    for r in (_run_sharded_inproc(args.n, args.nq) if args.sharded
              else run()):
        print(r, flush=True)
