#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell on the chip, once, to find
its knee: the highest rate at which no request is refused, the tail
does not grow across the window, and the answers keep up with the
arrivals.

  python3 bench/knee.py --workload sift128-flat-f32.poisson --seed 7 \\
      --seconds 10 --rates 500,1000,1500,2000

One process: the deployment is set up once, then each rate runs for
`--seconds` with requests encrypted afresh.  For each rate it prints the
p50 and p99 of all requests, the p99 of the first and the second half of
the window (by due time), the refusals and how late the generator ran,
and last a JSON object with every row and the knee.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
# p99 of the second half may exceed the first half's by this much before
# the tail counts as growing
GROWTH = 1.5
# share of the offered requests that must be answered inside the window
KEEP_UP = 0.97


def sweep_row(rate, plan, run_log, t0, seconds):
    import numpy as np

    from bench import stats, traffic

    lat = stats.latencies_ms(run_log.t_due, run_log.t_done)
    first = plan.offsets < seconds / 2
    sent = run_log.sent()
    late = (run_log.t_send - run_log.t_due)[sent]
    row = {
        "rate_per_s": rate, "requests": int(plan.n_requests),
        "p50_ms": stats.percentile(lat, 50),
        "p99_ms": stats.percentile(lat, 99),
        "p99_first_half_ms": stats.percentile(lat[first], 99),
        "p99_second_half_ms": stats.percentile(lat[~first], 99),
        "refused": int((run_log.status == traffic.REFUSED).sum()),
        "late_ms_max": float(late.max() * 1e3) if late.size else 0.0,
        "done_in_window_per_s": stats.rate(
            run_log.t_done, np.ones(plan.n_requests), t0, seconds),
    }
    row["sustained"] = bool(
        row["refused"] == 0 and np.isfinite(row["p99_ms"])
        and row["p99_second_half_ms"] <= GROWTH * row["p99_first_half_ms"]
        and row["done_in_window_per_s"] >= KEEP_UP * rate)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU; refusing to run", file=sys.stderr)
        return 2
    from repro.api import SearchParams
    from repro.launch.compile_cache import use_compile_cache

    from bench import deployment, harness, traffic
    from bench.data import make_corpus
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    bench = harness.load_benchmark(ROOT)
    cell = harness.find(bench["workloads"], args.workload, "workload")
    config = harness.load_config(bench, cell["config"], ROOT)
    mix = harness.load_traffic(cell["traffic"])
    if mix["kind"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    base, queries = (np.asarray(a) for a in make_corpus(config, args.seed))
    dep = deployment.build(config, base, args.seed, log=harness.log)
    k, nq = int(config["k"]), int(mix["queries_per_request"])
    params = SearchParams(k=k, ratio_k=float(config["ratio_k"]))
    wq, wt = deployment.encrypt_queries(dep.keys, queries[:nq],
                                        seed=dep.seeds[3])
    deployment.warm_up(dep, config, mix, wq, wt)
    harness.log(f"setup_s={time.monotonic() - T_START:.3f}")

    def submit(req):
        return np.asarray(dep.svc.submit(req).ids)[:, :k]

    rows = []
    for j, rate in enumerate(rates):
        plan = traffic.make_plan(dict(mix, rate_per_s=rate),
                                 queries.shape[0], args.seed + j,
                                 args.seconds)
        make = deployment.request_maker(dep, queries, plan, params,
                                        dep.seeds[4] + 4099 * j)
        t0 = time.monotonic() + 0.25
        run_log = traffic.drive(plan, submit, make, t0, args.seconds)
        row = sweep_row(rate, plan, run_log, t0, args.seconds)
        rows.append(row)
        print("rate " + json.dumps(row), flush=True)
    dep.svc.close()
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"workload": args.workload, "rows": rows,
                      "knee_per_s": max(ok) if ok else None,
                      "device_kind": jax.devices()[0].device_kind}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
