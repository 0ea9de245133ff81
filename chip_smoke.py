#!/usr/bin/env python3
"""Bring-up smoke run of the encrypted search service on a TPU.

Drives the served path end to end through the public roles of
`repro.api` — owner encryption, `SecureAnnService` ingest, filter, DCE
refine, ids — at the scale of the paper's SIFT1M configuration
(d=128, n=1,000,000; `repro.configs.ppanns_datasets`), on data generated
from `--seed`, and checks every answer against the plaintext exact k-NN.

  python3 chip_smoke.py                # one chip: five index phases
  python3 chip_smoke.py --four-chips   # row-sharded placement on 4 chips
                                       # vs the single placement

One process drives every chip it uses.  Without a TPU the script exits
non-zero before doing any work; it never falls back to the CPU.  Every
phase either passes its checks or raises, so exit status 0 means all
of them passed.  The last line of standard output is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

K = 10
N = 1_000_000               # SIFT1M rows (configs/ppanns_datasets.py)
D = 128
N_QUERIES = 64
RECALL_FLOOR = 0.85         # examples/secure_ann_search.py asserts it too
# Owner's DCPE noise at 1% of the legal beta range (as benchmarks/
# bench_filter.py sets it at 100k rows), and a filter k' of 16k.  At
# 1M rows the 64 clusters hold ~15.6k rows each, far denser than the
# 20k-row default the configs' 3% was tuned on, and the DCPE noise
# swamps the neighbour gaps: at 3% and k'=8k a numpy model of filter +
# exact refine gives recall@10 0.46; at 1% and k'=8k, 0.89.
BETA_FRACTION = 0.01
RATIO_K = 16.0
# Phases whose owner- or server-side host work is pure Python/numpy and
# cannot reach 1M rows inside the run's time limit; measured on one
# host CPU core: the HNSW build takes ~40 rows/s (1M rows: ~7 h), PQ
# codebook training + encoding ~150 s per 262,144 rows.
GRAPH_N = 4096
PQ8_N = 65_536
# A dropped collection must hand back its device bytes (GBs at 1M rows);
# what may stay is compiled code and small key/query operands.
DROP_SLACK_BYTES = 256 << 20


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    backend: str
    quantization: str | None = None
    n: int | None = None          # None: the full corpus
    cut: str = ""                 # why n was cut, when it was
    index: tuple = ()             # extra IndexSpec fields


PHASES = (
    Phase("flat-f32", "flat"),
    Phase("flat-int8", "flat", "int8"),
    # the large-n PQ configuration of benchmarks/bench_filter.py: the
    # default 16 subspaces cannot tell a cluster's rows apart
    Phase("flat-pq8", "flat", "pq8", n=PQ8_N,
          cut="PQ codebook training + encoding is host numpy "
              "(~150 s per 262,144 rows at 16 subspaces on one core)",
          index=(("pq_m", 32), ("refine_ratio", 8.0))),
    Phase("ivf-f32", "ivf"),
    Phase("graph-f32", "graph", n=GRAPH_N,
          cut="the owner-side HNSW build is pure Python "
              "(~40 rows/s on one core)"),
)

# Placements compared on four chips (same 1M corpus, same process).
SHARDED_PHASES = (
    Phase("flat-f32", "flat"),
    Phase("flat-int8", "flat", "int8"),
    Phase("ivf-f32", "ivf"),
)


def log(msg: str):
    print(msg, flush=True)


def collection_bytes(col, devices) -> list[int]:
    """Per-device bytes of the arrays a collection's backend and engine
    hold (the corpus, codes and index state it keeps on the chips)."""
    import jax
    arrays = {id(a): a for obj in (col._backend, col._engine)
              for a in vars(obj).values() if isinstance(a, jax.Array)}
    return [sum(s.data.nbytes for a in arrays.values()
                for s in a.addressable_shards if s.device == d)
            for d in devices]


def memory_stats(device) -> dict:
    return device.memory_stats() or {}


# ---------------------------------------------------------------------------
# Data, keys, requests.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Corpus:
    base: np.ndarray
    queries: np.ndarray
    gt: np.ndarray
    spec: object                  # IndexSpec all phases derive from
    owner: object                 # DataOwnerClient
    C_sap: np.ndarray
    C_dce: np.ndarray


def make_corpus(n: int, seed: int) -> Corpus:
    from repro.api import DataOwnerClient, IndexSpec, suggest_beta
    from repro.data import synth

    t0 = time.perf_counter()
    ds = synth.make_dataset("sift1m", n=n, d=D, n_queries=N_QUERIES,
                            k_gt=K, seed=seed)
    spec = IndexSpec(tenant="smoke", name="base", d=D,
                     sap_beta=suggest_beta(ds.base, fraction=BETA_FRACTION),
                     seed=seed)
    owner = DataOwnerClient(spec)
    t1 = time.perf_counter()
    C_sap, C_dce = owner.encrypt_vectors(ds.base, seed=seed + 1)
    t2 = time.perf_counter()
    log(f"corpus n={n} d={D} queries={N_QUERIES} seed={seed} "
        f"beta={spec.sap_beta:.4f} data_s={t1 - t0:.3f} "
        f"encrypt_s={t2 - t1:.3f} dce_bytes={C_dce.nbytes}")
    return Corpus(ds.base, ds.queries, ds.gt, spec, owner, C_sap, C_dce)


def load_collection(svc, corpus: Corpus, phase: Phase, placement=None):
    """Create the phase's collection and fill it the way its users do:
    owner-encrypted rows through `insert` + `compact`, or, for the
    graph index, an owner-built corpus upload."""
    from repro.api import DataOwnerClient

    n = phase.n or corpus.base.shape[0]
    spec = dataclasses.replace(corpus.spec, name=phase.name,
                               backend=phase.backend,
                               quantization=phase.quantization,
                               **dict(phase.index))
    if phase.backend == "graph":
        # same seed, same keys: the query client stays valid
        upload = DataOwnerClient(spec).encrypt_corpus(corpus.base[:n])
        svc.create_collection(spec, upload, placement=placement)
    else:
        svc.create_collection(spec, placement=placement)
        svc.insert(spec.tenant, spec.name, corpus.C_sap[:n],
                   corpus.C_dce[:n])
        svc.compact(spec.tenant, spec.name)
    return spec, n


def ground_truth(corpus: Corpus, n: int) -> np.ndarray:
    from repro.data import synth
    if n == corpus.base.shape[0]:
        return corpus.gt
    return synth.ground_truth(corpus.base[:n], corpus.queries, K)


# ---------------------------------------------------------------------------
# Checks that the Pallas kernels ran compiled.
# ---------------------------------------------------------------------------

def _has_kernel(jitted, *args, **kw) -> bool:
    text = jitted.lower(*args, **kw).compile().as_text()
    return "tpu_custom_call" in text


def kernel_report(col, k: int = K, nq: int = 32) -> dict:
    """Which kernel each stage of `col`'s programs runs, read from the
    compiled TPU programs at the collection's own shapes."""
    import jax.numpy as jnp

    from repro.kernels.adc_topk import ops as adc_ops
    from repro.kernels.common import interpret_default
    from repro.kernels.l2_topk import ops as l2_ops
    from repro.serving import search_engine as se

    if interpret_default():
        raise SystemExit("Pallas would run in interpret mode")
    b = col._backend
    eng = col._engine
    if not (b.use_kernel and eng.use_kernel):
        raise SystemExit(f"{col.name}: kernels switched off")
    kp = b.oversampled(round(RATIO_K * k))
    Q = jnp.zeros((nq, col.d), jnp.float32)
    report = {}
    if b.kind == "flat" and b.quantization is None:
        ok = _has_kernel(l2_ops.knn.__wrapped__, Q, b._C_main, kp,
                         chunk=min(4096, b._C_main.shape[0]),
                         use_kernel=True)
        report["filter"] = "l2_topk" if ok else "xla"
    elif b.kind == "flat":
        if not b._use_pallas():
            raise SystemExit(f"{col.name}: ADC filter takes the XLA path")
        if b.quantization == "int8":
            ok = _has_kernel(adc_ops.sq_knn.__wrapped__,
                             jnp.zeros((nq, col.d), jnp.int8), b._adc_c8,
                             b._adc_cn, kp, ok=b._adc_ok, use_kernel=True)
            report["filter"] = "sq_adc_topk" if ok else "xla"
        else:
            m = b.adc_codebook.m
            ok = _has_kernel(adc_ops.pq_knn.__wrapped__,
                             jnp.zeros((nq, m, 256), jnp.float32),
                             b._adc_codes_t, kp, ok=b._adc_ok,
                             use_kernel=True)
            report["filter"] = "pq_adc_topk" if ok else "xla"
    else:                         # ivf pooled scan, graph walk: XLA only
        report["filter"] = "xla"
    T = jnp.zeros((nq, eng._C_dce_dev.shape[-1]), jnp.float32)
    ok = _has_kernel(se.refine_candidates, eng._C_dce_dev,
                     jnp.zeros((nq, kp), jnp.int32), T,
                     jnp.ones((nq, kp), bool), k=k, use_kernel=True)
    report["refine"] = "dce_comp" if ok else "xla"
    return report


# ---------------------------------------------------------------------------
# One phase on one chip.
# ---------------------------------------------------------------------------

def run_phase(svc, corpus: Corpus, user, phase: Phase, device, *,
              check_kernels: bool = True) -> dict:
    from repro.api import EncryptedQuery, SearchParams
    from repro.data import synth

    mem0 = memory_stats(device).get("bytes_in_use")
    t0 = time.perf_counter()
    spec, n = load_collection(svc, corpus, phase)
    t_ingest = time.perf_counter() - t0
    if phase.cut:
        log(f"cut {phase.name}: n={n} instead of {corpus.base.shape[0]} "
            f"because {phase.cut}")

    t0 = time.perf_counter()
    # attach + every batch shape
    svc.warmup(spec.tenant, spec.name, k=K, ratio_k=RATIO_K)
    t_cold = time.perf_counter() - t0

    # every query is encrypted once (fresh DCPE noise per encryption),
    # then sent alone, in one 32-query batch, and concurrently
    params = SearchParams(k=K, ratio_k=RATIO_K)
    reqs = [user.request(spec.tenant, spec.name, q, params)
            for q in corpus.queries]
    single = svc.submit(reqs[0])
    batch = svc.submit(dataclasses.replace(reqs[0], query=EncryptedQuery(
        C_sap=np.concatenate([r.query.C_sap for r in reqs[:32]]),
        T=np.concatenate([r.query.T for r in reqs[:32]]))))
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(reqs)) as pool:
        results = list(pool.map(svc.submit, reqs))
    t_concurrent = time.perf_counter() - t0
    ids = np.concatenate([r.ids for r in results])

    recall = synth.recall_at_k(ids, ground_truth(corpus, n), K)
    # one engine contract on every path: a query's ids do not depend on
    # the batch it rode in
    parity = (np.array_equal(single.ids[0], ids[0])
              and np.array_equal(batch.ids, ids[:32]))
    col = svc.collection(spec.tenant, spec.name)
    kernels = kernel_report(col) if check_kernels else {}
    held = collection_bytes(col, [device])[0]
    stats = memory_stats(device)
    alive = weakref.ref(col)
    col = None
    svc.drop_collection(spec.tenant, spec.name)
    gc.collect()
    after = memory_stats(device)
    kept = (None if mem0 is None
            else after.get("bytes_in_use") - mem0)

    out = {
        "phase": phase.name, "n": n, "d": D, "backend": phase.backend,
        "quantization": phase.quantization or "f32",
        "recall_at_10": recall, "ids_batch_invariant": parity,
        "ingest_s": t_ingest, "cold_compile_first_call_s": t_cold,
        "concurrent_64_s": t_concurrent, "kernels": kernels,
        "collection_device_bytes": held,
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use_after_drop": after.get("bytes_in_use"),
    }
    log("phase " + json.dumps(out))
    failures = []
    if recall < RECALL_FLOOR:
        failures.append(f"recall@10 {recall:.4f} < {RECALL_FLOOR}")
    if not parity:
        failures.append("ids differ between single, batch and concurrent")
    if alive() is not None:
        failures.append("the dropped collection is still referenced")
    if kept is not None and kept > DROP_SLACK_BYTES:
        failures.append(f"{kept} device bytes not returned by the drop")
    if check_kernels and kernels.get("refine") != "dce_comp":
        failures.append("the refine did not run the dce_comp kernel")
    if check_kernels and phase.backend == "flat" \
            and kernels.get("filter") == "xla":
        failures.append("the flat filter did not run its Pallas kernel")
    if failures:
        raise SystemExit(f"phase {phase.name} failed: "
                         + "; ".join(failures))
    return out


def run_one_chip(n: int, seed: int, phases=PHASES, *,
                 check_kernels: bool = True) -> list[dict]:
    import jax

    from repro.api import SecureAnnService

    device = jax.devices()[0]
    corpus = make_corpus(n, seed)
    user = corpus.owner.query_client(seed=seed + 2)
    out = []
    with SecureAnnService() as svc:
        for phase in phases:
            if phase.n is not None and phase.n >= n:
                phase = dataclasses.replace(phase, n=None, cut="")
            out.append(run_phase(svc, corpus, user, phase, device,
                                 check_kernels=check_kernels))
    return out


# ---------------------------------------------------------------------------
# Four chips: sharded placement vs the single placement.
# ---------------------------------------------------------------------------

def run_sharded(n: int, seed: int, n_shards: int,
                phases=SHARDED_PHASES) -> list[dict]:
    import jax

    from repro.api import PlacementSpec, SearchParams, SecureAnnService
    from repro.data import synth

    devices = jax.devices()[:n_shards]
    corpus = make_corpus(n, seed)
    user = corpus.owner.query_client(seed=seed + 2)
    params = SearchParams(k=K, ratio_k=RATIO_K)
    out = []
    with SecureAnnService() as svc:
        for phase in phases:
            # one encryption of the queries serves both placements
            req = user.request(corpus.spec.tenant, phase.name,
                               corpus.queries, params)
            ids, per_device = {}, {}
            for kind in ("single", "sharded"):
                placement = (PlacementSpec(kind="sharded", n_shards=n_shards)
                             if kind == "sharded" else None)
                t0 = time.perf_counter()
                spec, _ = load_collection(svc, corpus, phase, placement)
                ids[kind] = svc.submit(req).ids
                dt = time.perf_counter() - t0
                owned = collection_bytes(
                    svc.collection(spec.tenant, spec.name), devices)
                held = [memory_stats(d).get("bytes_in_use")
                        for d in devices]
                svc.drop_collection(spec.tenant, spec.name)
                gc.collect()
                # what the drop hands back is what the collection held
                # (the arrays' own bytes where the backend keeps no
                # allocator statistics)
                per_device[kind] = [
                    o if h is None else h - memory_stats(d)["bytes_in_use"]
                    for o, h, d in zip(owned, held, devices)]
                log(f"placement {phase.name}/{kind}: load+first search "
                    f"{dt:.3f} s, array bytes per device {owned}, "
                    f"bytes freed by the drop {per_device[kind]}")
            held = per_device["sharded"]
            whole = sum(per_device["single"])
            quarter = [h / whole for h in held]
            res = {
                "phase": phase.name, "n": n, "n_shards": n_shards,
                "ids_identical": bool(np.array_equal(ids["single"],
                                                     ids["sharded"])),
                "recall_at_10": synth.recall_at_k(ids["sharded"],
                                                  corpus.gt, K),
                "share_per_device": quarter,
            }
            log("sharded " + json.dumps(res))
            share_ok = all(abs(q - 1 / n_shards) < 0.5 / n_shards
                           for q in quarter)
            if not (res["ids_identical"] and share_ok
                    and res["recall_at_10"] >= RECALL_FLOOR):
                raise SystemExit(f"sharded {phase.name} failed: {res}")
            out.append(res)
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way sharded placement and the "
                         "single placement it must match")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); "
              "refusing to run on it", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, jax found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    cache = pathlib.Path(use_compile_cache())

    def cache_entries() -> int:
        return sum(1 for _ in cache.glob("*")) if cache.is_dir() else 0

    log(f"compile cache: {cache} ({cache_entries()} entries at start)")
    log(f"jax {jax.__version__} on {len(devices)} x "
        f"{devices[0].device_kind}")

    t0 = time.perf_counter()
    if args.four_chips:
        run_sharded(N, args.seed, n_shards=4)
    else:
        run_one_chip(N, args.seed)
    log(f"total_s={time.perf_counter() - t0:.3f} "
        f"compile_cache_entries_at_end={cache_entries()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
