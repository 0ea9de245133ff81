#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this machine holds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's deployment, warms up its shapes, measures for
`--seconds`, checks every answer against the plain reference, and prints
one JSON object as the last line of standard output (the numbers it
compared, beside their limits, are the last lines of standard error).
Without a TPU, or with fewer chips than the cell asks for, it exits 2
before any work and prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.find(harness.load_benchmark(ROOT)["workloads"],
                        args.workload, "workload")
    # the compile cache lives at a fixed path inside the checkout, set
    # before JAX reads its configuration
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # every program, however quick to compile, is kept: a repeat run
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
