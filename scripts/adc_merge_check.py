#!/usr/bin/env python3
"""Old against new int8 ADC kernel on the benchmark's data: parity and time.

Builds the `sift128-flat-int8` deployment's filter inputs from `--seed`
(the benchmark's corpus and query pool, DCPE-encrypted with the
deployment's keys, SQ-encoded, padded to the power-of-two bucket the
served backend uses), then runs the int8 filter as the engine calls it
(`adc_topk.ops.sq_knn`, k' = 320) against `sq_adc_topk` of another
checkout (`--old`, the root of e.g. `git archive` of an earlier commit)
on the same arrays, for each query-batch size and each row order:

  random       the corpus as drawn (clusters i.i.d. per row)
  clustered    rows grouped by the nearest of 64 sampled rows, groups
               in random order: a corpus ingested cluster by cluster
  far_to_near  rows by descending surrogate distance to the batch's
               first query: every tile of that query enters the top-k,
               the gated merge's worst case (K + 1 rounds a tile)

It prints one `result` line per (order, rows) with whether dists and ids
are bit-identical, the best of three timed calls of each kernel
(old, new, new, old, each once warmed), and the merge rounds against
K x tiles; the last line is all of them as one JSON object.  Exit status
1 when any pair differs.

  python3 scripts/adc_merge_check.py --old <parent checkout> --seed 7
  # CPU rehearsal (interpret mode), small corpus:
  JAX_PLATFORMS=cpu python3 scripts/adc_merge_check.py --old <dir> --n 3000
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import data, deployment, harness  # noqa: E402
from repro.api import DataOwnerClient  # noqa: E402
from repro.core import adc, dcpe  # noqa: E402
from repro.kernels.adc_topk import ops  # noqa: E402
from repro.kernels.common import next_bucket  # noqa: E402

KP = 320          # the benchmark's k': round(ratio_k * k) * refine_ratio
ORDERS = ("random", "clustered", "far_to_near")


def load_old_kernel(old_root: pathlib.Path):
    """`adc_topk.adc_topk` of another checkout, under a package name of
    its own so that it sits beside this tree's."""
    kernels = old_root / "src" / "repro" / "kernels"
    for name, path in (("old_kernels", kernels),
                       ("old_kernels.adc_topk", kernels / "adc_topk")):
        pkg = types.ModuleType(name)
        pkg.__path__ = [str(path)]
        sys.modules[name] = pkg
    return importlib.import_module("old_kernels.adc_topk.adc_topk")


def filter_inputs(seed: int, n: int | None):
    """(c8 (n, d) int8, cn (n,) int32, q8 (256, d) int8) of the
    deployment, as the int8 backend encodes them."""
    config = harness.load_config(harness.load_benchmark(ROOT),
                                 "sift128-flat-int8")
    if n is not None:
        config = dict(config, n=n)
    base, queries = data.make_corpus(config, seed)
    spec, seeds = deployment.index_spec(config, seed)
    keys = DataOwnerClient(spec).share_keys()
    C_sap = dcpe.encrypt(np.asarray(base), keys.sap_key, seed=seeds[1])
    Q_sap = dcpe.encrypt(np.asarray(queries)[:256], keys.sap_key,
                         seed=seeds[2])
    cb = adc.SQCodebook.train(C_sap)
    c8, cn = cb.encode(C_sap)
    return c8, cn, cb.encode_query(Q_sap)


def row_order(order: str, c8, cn, q0, seed: int) -> np.ndarray:
    n = c8.shape[0]
    rng = np.random.default_rng(seed)
    if order == "random":
        return np.arange(n)
    c8d = jnp.asarray(c8)
    if order == "far_to_near":
        cross = jnp.dot(c8d, jnp.asarray(q0),
                        preferred_element_type=jnp.int32)
        dist = np.asarray(jnp.asarray(cn) - 2 * cross)
        return np.argsort(-dist.astype(np.int64), kind="stable")
    centers = jnp.asarray(c8[rng.choice(n, 64, replace=False)])
    cross = jnp.dot(c8d, centers.T, preferred_element_type=jnp.int32)
    cc = (centers.astype(jnp.int32) ** 2).sum(1)
    label = np.asarray(jnp.argmin(cc[None, :] - 2 * cross, axis=1))
    return np.argsort(rng.permutation(64)[label], kind="stable")


def padded(c8, cn, perm):
    n = c8.shape[0]
    bucket = next_bucket(n, minimum=256)
    c8p = np.zeros((bucket, c8.shape[1]), np.int8)
    cnp = np.zeros(bucket, np.int32)
    ok = np.zeros(bucket, np.int32)
    c8p[:n], cnp[:n], ok[:n] = c8[perm], cn[perm], 1
    return jnp.asarray(c8p), jnp.asarray(cnp), jnp.asarray(ok)


def best_of_three(fn):
    out = jax.block_until_ready(fn())
    times = []
    for _ in range(3):
        t = time.perf_counter()
        out = jax.block_until_ready(fn())
        times.append(time.perf_counter() - t)
    return [np.asarray(a) for a in out[:2]], min(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--old", type=pathlib.Path, required=True,
                    help="root of the checkout whose kernel is compared")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=None,
                    help="corpus rows (default: the configuration's)")
    ap.add_argument("--rows", type=int, nargs="+", default=[32, 256])
    ap.add_argument("--orders", nargs="+", choices=ORDERS,
                    default=list(ORDERS))
    args = ap.parse_args(argv)

    old = load_old_kernel(args.old.resolve())
    print("device", jax.devices()[0].device_kind, flush=True)
    c8, cn, q8 = filter_inputs(args.seed, args.n)
    results = {}
    for order in args.orders:
        perm = row_order(order, c8, cn, q8[0], args.seed)
        c8d, cnd, okd = padded(c8, cn, perm)
        for nq in args.rows:
            qd = jnp.asarray(q8[:nq])
            new_fn = lambda: ops.sq_knn(qd, c8d, cnd, KP, ok=okd)  # noqa: E731
            old_fn = lambda: old.sq_adc_topk(qd, c8d, cnd, okd, KP)  # noqa: E731
            (d_old, i_old), t_old = best_of_three(old_fn)
            (d_new, i_new), t_new = best_of_three(new_fn)
            t_new = min(t_new, best_of_three(new_fn)[1])
            t_old = min(t_old, best_of_three(old_fn)[1])
            i_new, rounds = ops.split_merge_rounds(i_new)
            full = ops.merge_rounds_full(c8d.shape[0], KP,
                                         ops.DEFAULT_BLOCK_N_SQ)
            res = dict(bit_identical=bool((d_new == d_old).all()
                                          and (i_new == i_old).all()),
                       old_ms=1e3 * t_old, new_ms=1e3 * t_new,
                       rounds=rounds, rounds_full=full,
                       share=None if rounds is None else 100.0 * rounds / full)
            results[f"{order}.{nq}"] = res
            print("result", order, nq, json.dumps(res), flush=True)
    print(json.dumps(results), flush=True)
    return 0 if all(r["bit_identical"] for r in results.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
