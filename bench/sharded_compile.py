#!/usr/bin/env python3
"""Compile the sharded placement's two programs at a deployment's shape
for a described TPU topology, without a chip, and report their memory.

  python3 bench/sharded_compile.py [--n 1048576] [--d 960] [--nq 32]
                                   [--kp 160] [--topology v5e:2x2]

`serving/sharded._sharded_refine` (the DCE gather, the `psum` of k'
rows a query and the tournament) and `_sharded_flat_topk` (the DCPE
filter) are lowered on abstract arrays row-sharded over the topology's
devices, as a `"placement": {"kind": "sharded"}` configuration builds
them, and compiled.  Printed per program: the compiler's memory
analysis per device, and each HLO instruction that copies, gathers or
reduces across devices, so that a whole-shard layout copy shows with
its shape.  `--n` is the store's bucket (a power of two at or above the
corpus's rows).  Nothing is written to disk.  Exits 3 where this
installation cannot describe the topology (no TPU compiler).
"""

import argparse
import os
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
# HLO instructions worth showing, on arrays (not scalars): copies,
# gathers and cross-device reductions
SHOWN = re.compile(
    r"= \w+\[\d[\d,]*\]\S* (copy|gather|all-reduce|all-gather)\(")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--d", type=int, default=960)
    ap.add_argument("--nq", type=int, default=32)
    ap.add_argument("--kp", type=int, default=160)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.serving import sharded

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=args.topology)
    except Exception as exc:        # noqa: BLE001 — no TPU compiler here
        print(f"cannot describe {args.topology}: {exc}", file=sys.stderr)
        return 3
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without the chip: keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    mesh = Mesh(np.array(topo.devices), ("data",))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data"))
    dim = 2 * args.d + 16                  # a DCE ciphertext's width
    f32 = jnp.float32

    def arg(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    programs = {
        "_sharded_refine": sharded._sharded_refine.lower(
            arg((args.n, 4, dim), f32, rows),
            arg((args.nq, args.kp), jnp.int32, rep),
            arg((args.nq, dim), f32, rep),
            arg((args.nq, args.kp), jnp.bool_, rep),
            mesh=mesh, axis="data", k=args.k),
        "_sharded_flat_topk": sharded._sharded_flat_topk.lower(
            arg((args.n, args.d), f32, rows),
            arg((args.nq, args.d), f32, rep),
            mesh=mesh, axis="data", kp=args.kp),
    }
    print(f"topology {args.topology} ({len(topo.devices)} devices), "
          f"n {args.n}, d {args.d}, nq {args.nq}, k' {args.kp}")
    for name, lowered in programs.items():
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        print(f"\n{name}: per device argument "
              f"{mem.argument_size_in_bytes} B, output "
              f"{mem.output_size_in_bytes} B, temp "
              f"{mem.temp_size_in_bytes} B")
        for line in compiled.as_text().splitlines():
            if SHOWN.search(line):
                print("  " + line.strip()[:240])
    return 0


if __name__ == "__main__":
    sys.exit(main())
