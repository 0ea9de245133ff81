#!/usr/bin/env python3
"""The controls of a cell's check, which have to come out not correct.

  --mode program    the cell's own timed path with every ciphertext
                    matmul of the program one precision step below what
                    the configuration states: one bfloat16 pass
                    (`Precision.DEFAULT`) for float32 at HIGHEST (the
                    Pallas kernels accept no three-pass `HIGH`).  This is
                    the step that would tempt a later PR.
  --mode reference  the plain reference computed in bfloat16 and put in
                    the program's place, on the queries a run sends.

  python3 bench/control.py --mode program --workload \\
      sift128-flat-f32.single64 --seeds 11,12,13

For each seed it prints the compared numbers, their limits and the
verdict, and last one JSON object with every reading.  The benchmark's
own runs never run this.
"""

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def lower_program_precision():
    """Rebind the program's matmul precision to one bfloat16 pass.  Has
    to run before any module of the program that reads it is imported."""
    import jax
    kernels = [m for m in sys.modules if m.startswith("repro.kernels.")
               and m != "repro.kernels.common"]
    if kernels:
        raise RuntimeError(f"too late: {kernels} already imported")
    import repro.kernels.common as common
    common.HIGHEST = jax.lax.Precision.DEFAULT


def program_readings(workload: str, seed: int, seconds: float) -> dict:
    from bench import harness
    res = harness.run_cell(workload, seed, seconds, False)
    return {"seed": seed, "correct": res["correct"],
            "numbers": {k: v["value"] for k, v in res["checks"].items()},
            "metrics": res["metrics"]}


def reference_readings(config: dict, mix: dict, seed: int, seconds: float,
                       answers: int) -> dict:
    import numpy as np

    from bench import check, traffic
    from bench.data import make_corpus
    from bench.reference import exact_topk

    base, queries = (np.asarray(a) for a in make_corpus(config, seed))
    plan = traffic.make_plan(mix, queries.shape[0], seed, seconds)
    qrows = plan.qrows.ravel()[:answers]
    used, inverse = np.unique(qrows, return_inverse=True)
    k = int(config["k"])
    ref = exact_topk(base, queries[used], k)[inverse]
    ctl = exact_topk(base, queries[used], k, precision="bfloat16")[inverse]
    nums, by_gap = check.numbers(ctl, qrows, ref, base, queries, lost=0,
                                 order_gap=float(config["order_gap"]))
    correct, _ = check.judge(nums, config["limits"])
    return {"seed": seed, "answers": int(qrows.size), "numbers": nums,
            "order_violations_by_gap": by_gap, "correct": correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mode", choices=("program", "reference"),
                    required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", type=int, default=12000,
                    help="reference mode: query rows compared")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; refusing to run", file=sys.stderr)
        return 2
    if args.mode == "program":
        lower_program_precision()
    from bench import harness
    bench = harness.load_benchmark(ROOT)
    cell = harness.find(bench["workloads"], args.workload, "workload")
    config = harness.load_config(bench, cell["config"], ROOT)
    mix = harness.load_traffic(cell["traffic"])
    seconds = float(bench["run_seconds"])
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.mode == "program":
            r = program_readings(args.workload, seed, seconds)
        else:
            r = reference_readings(config, mix, seed, seconds, args.answers)
        print(f"control {args.mode} seed={seed} correct={r['correct']} "
              f"numbers={json.dumps(r['numbers'])}", flush=True)
        out.append(r)
    print(json.dumps({"workload": args.workload, "mode": args.mode,
                      "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
