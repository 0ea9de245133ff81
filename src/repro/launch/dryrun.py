import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, single-pod (16x16=256) and multi-pod (2x16x16=512),
with ShapeDtypeStruct stand-ins (no allocation).

Per cell this records to JSON:
  * memory_analysis  — per-device argument/temp/output bytes (fits-check)
  * cost_analysis    — per-device HLO flops / bytes accessed
  * collective bytes — parsed from the partitioned HLO per collective kind
  * analytic MODEL_FLOPS (6·N·D train / 2·N·D inference, N_active for MoE)

CLI:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all            # every runnable cell
  python -m repro.launch.dryrun --all --multi-pod
  (add --out DIR to change the results directory; default results/dryrun)

The two XLA_FLAGS lines above MUST stay the first statements: jax locks
the device count on first init, and only the dry-run wants 512 host
devices (smoke tests and benchmarks see 1).
"""

import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import Model
from repro.models.config import SHAPES
from repro.models.model import (abstract_batch, batch_pspecs, cache_pspecs)
from repro.sharding.rules import (LONG_DECODE_RULES, PURE_DP_TRAIN_RULES,
                                  SERVE_RULES, TRAIN_RULES)
from repro.training import OptConfig, abstract_train_state, build_train_step
from repro.training.train_loop import train_state_pspecs

RESULTS_DIR = "results/dryrun"

# Per-arch training knobs (optimizer family / state dtype / accumulation):
# chosen so optimizer state + gradient buffers fit the v5e HBM budget —
# rationale in EXPERIMENTS.md §Dry-run.
TRAIN_SETTINGS = {
    "nemotron-4-340b": dict(opt="adafactor", state_dtype="float32",
                            n_micro=8, accum="float32"),
    # n_micro trades ZeRO-3 gather volume (up) for activation memory
    # (down); 16 was tried in §Perf and reverted — see EXPERIMENTS.md
    "kimi-k2-1t-a32b": dict(opt="adafactor", state_dtype="float32",
                            n_micro=8, accum="bfloat16"),
    "grok-1-314b": dict(opt="adamw", state_dtype="bfloat16",
                        n_micro=8, accum="float32"),
    "qwen2.5-14b": dict(opt="adamw", state_dtype="float32",
                        n_micro=8, accum="float32"),
    # bf16 moments + 8 microbatches: f32 states/4-micro put the train
    # cell at 19-21 GB/dev (§Dry-run note)
    "chatglm3-6b": dict(opt="adamw", state_dtype="bfloat16",
                        n_micro=8, accum="float32"),
    # ZeRO-1 optimizer-state sharding for the 1-10B TP tier
    "qwen3-1.7b": dict(opt="adamw", state_dtype="float32",
                       n_micro=4, accum="float32", zero1=True),
    "zamba2-1.2b": dict(opt="adamw", state_dtype="float32",
                        n_micro=4, accum="float32", zero1=True),
    "paligemma-3b": dict(opt="adamw", state_dtype="float32",
                         n_micro=4, accum="float32", zero1=True),
    # pure-DP hillclimb (see sharding.rules.PURE_DP_TRAIN_RULES).
    # n_micro must be 1: global_batch 256 == chip count, so any microbatch
    # split would leave mesh axes without batch rows to shard.
    "mamba2-370m": dict(opt="adamw", state_dtype="float32",
                        n_micro=1, accum="float32", pure_dp=True),
    "whisper-small": dict(opt="adamw", state_dtype="float32",
                          n_micro=1, accum="float32", pure_dp=True),
}
# activation memory scales 1/n_micro (layer-scan stores one carry per
# layer per microbatch); 4 keeps small-model cells well under HBM.
DEFAULT_TRAIN = dict(opt="adamw", state_dtype="float32", n_micro=4,
                     accum="float32")

# The paper-technique cell: distributed secure scan (see
# repro/serving/secure_scan.py).  16M encrypted vectors, SIFT dims.
# Suffixed variants are the §Perf hillclimb iterations.
PPANNS_CELLS = {
    "scan_16m": dict(n=16_777_216, d=128, batch=1024, k=10, k_prime=128),
    # hillclimb: bf16 filter ciphertexts (DCPE is approximate by design;
    # refine stays f32 for exact DCE signs)
    "scan_16m_bf16": dict(n=16_777_216, d=128, batch=1024, k=10,
                          k_prime=128, dtype="bfloat16"),
    # hillclimb: amortize the DB read over a 4x query batch
    "scan_16m_bf16_b4096": dict(n=16_777_216, d=128, batch=4096, k=10,
                                k_prime=128, dtype="bfloat16"),
    # negative control: GSPMD-auto formulation (no shard_map)
    "scan_16m_gspmd": dict(n=16_777_216, d=128, batch=1024, k=10,
                           k_prime=128, gspmd=True),
}

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"= (\w+)\[([\d,]*)\][^ ]* (all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def cost_analysis_dict(compiled) -> dict:
    """compiled.cost_analysis() as a dict ({} when XLA reports none)."""
    return compiled.cost_analysis() or {}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device bytes moved per collective kind.

    Model: all-gather/all-to-all/collective-permute move ~result bytes per
    device; all-reduce moves ~2x (reduce-scatter + all-gather phases);
    reduce-scatter moves ~result x group_size (its operand)."""
    out: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        numel = int(np.prod([int(x) for x in dims.split(",") if x])) \
            if dims else 1
        size = numel * nbytes
        g = _GROUPS_RE.search(line)
        gsz = int(g.group(2)) if g else 1
        if kind == "all-reduce":
            moved = 2 * size * max(gsz - 1, 0) / max(gsz, 1)
        elif kind == "reduce-scatter":
            moved = size * max(gsz - 1, 0)
        elif kind == "all-gather":
            moved = size * max(gsz - 1, 0) / max(gsz, 1)
        else:   # all-to-all / collective-permute
            moved = size
        d = out.setdefault(kind, {"count": 0, "bytes": 0.0})
        d["count"] += 1
        d["bytes"] += float(moved)
    return out


def model_flops(cfg, sc) -> float:
    """Analytic 6·N·D (train) / 2·N·D (inference); N_active for MoE."""
    n = Model(cfg).n_active_params()
    if sc.kind == "train":
        tokens = sc.global_batch * sc.seq_len
        return 6.0 * n * tokens
    if sc.kind == "prefill":
        return 2.0 * n * sc.global_batch * sc.seq_len
    return 2.0 * n * sc.global_batch          # decode: 1 token / sequence


def rules_for(shape_name: str, arch: str = ""):
    if shape_name == "train_4k":
        ts = TRAIN_SETTINGS.get(arch, DEFAULT_TRAIN)
        return PURE_DP_TRAIN_RULES if ts.get("pure_dp") else TRAIN_RULES
    if shape_name == "long_500k":
        return LONG_DECODE_RULES
    return SERVE_RULES


def runnable(arch: str, shape_name: str) -> bool:
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.subquadratic:
        return False          # full-attention archs skip (DESIGN.md §4)
    return True


def lower_cell(arch: str, shape_name: str, mesh):
    """Build and lower one cell; returns (lowered, aux)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if arch == "ppanns-scan":
        import jax.numpy as jnp
        from repro.api import (build_secure_scan_step,
                               build_secure_scan_step_gspmd,
                               secure_scan_input_specs,
                               secure_scan_pspecs)
        cell = PPANNS_CELLS[shape_name]
        builder = (build_secure_scan_step_gspmd if cell.get("gspmd")
                   else build_secure_scan_step)
        step = builder(mesh, k=cell["k"], k_prime=cell["k_prime"])
        specs = secure_scan_input_specs(
            cell["n"], cell["d"], cell["batch"],
            dtype=jnp.dtype(cell.get("dtype", "float32")))
        pspecs = secure_scan_pspecs(mesh)
        shardings = {k: NamedSharding(mesh, v) for k, v in pspecs.items()}
        jitted = jax.jit(
            step,
            in_shardings=(shardings["C_sap"], shardings["C_dce"],
                          shardings["Q_sap"], shardings["T_q"]))
        lowered = jitted.lower(specs["C_sap"], specs["C_dce"],
                               specs["Q_sap"], specs["T_q"])
        return lowered, {"model_flops": 2.0 * cell["n"] * cell["d"]
                         * cell["batch"], "n_params": 0}

    cfg = get_config(arch)
    sc = SHAPES[shape_name]
    model = Model(cfg)
    rules = rules_for(shape_name, arch)
    ns = lambda spec: NamedSharding(mesh, spec)
    tree_ns = lambda specs: jax.tree.map(
        ns, specs, is_leaf=lambda s: isinstance(s, P))
    aux = {"model_flops": model_flops(cfg, sc),
           "n_params": model.n_params(),
           "n_active_params": model.n_active_params()}

    if sc.kind == "train":
        ts = TRAIN_SETTINGS.get(arch, DEFAULT_TRAIN)
        opt_cfg = OptConfig(kind=ts["opt"], state_dtype=ts["state_dtype"])
        step = build_train_step(model, opt_cfg, mesh, rules,
                                n_microbatches=ts["n_micro"],
                                accum_dtype=ts["accum"])
        state_abs = abstract_train_state(model, opt_cfg)
        state_specs = train_state_pspecs(model, opt_cfg, mesh, rules,
                                         zero1=bool(ts.get("zero1")))
        batch_abs = abstract_batch(cfg, sc)
        b_specs = batch_pspecs(cfg, sc, mesh, rules)
        jitted = jax.jit(step,
                         in_shardings=(tree_ns(state_specs), tree_ns(b_specs)),
                         out_shardings=(tree_ns(state_specs), None),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_abs, batch_abs)
        aux["train_settings"] = ts
        return lowered, aux

    params_abs = model.abstract_params()
    p_specs = model.param_specs(mesh, rules)
    B, T = sc.global_batch, sc.seq_len
    cache_abs = model.init_cache(B, T, abstract=True)
    c_specs = cache_pspecs(cfg, B, T, mesh, rules)

    if sc.kind == "prefill":
        batch_abs = abstract_batch(cfg, sc)
        b_specs = batch_pspecs(cfg, sc, mesh, rules)
        fn = lambda p, b, c: model.prefill(p, b, c, mesh, rules)
        jitted = jax.jit(fn,
                         in_shardings=(tree_ns(p_specs), tree_ns(b_specs),
                                       tree_ns(c_specs)),
                         out_shardings=(None, tree_ns(c_specs)),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_abs, batch_abs, cache_abs)
        return lowered, aux

    # decode: one new token against a T-long cache
    token_abs = abstract_batch(cfg, sc)["tokens"]
    tok_spec = batch_pspecs(cfg, sc, mesh, rules)["tokens"]
    fn = lambda p, t, c: model.decode_step(p, t, c, mesh, rules)
    jitted = jax.jit(fn,
                     in_shardings=(tree_ns(p_specs), ns(tok_spec),
                                   tree_ns(c_specs)),
                     out_shardings=(None, tree_ns(c_specs)),
                     donate_argnums=(2,))
    lowered = jitted.lower(params_abs, token_abs, cache_abs)
    return lowered, aux


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, verbose: bool = True) -> dict:
    mesh_name = "2pod_512" if multi_pod else "1pod_256"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, aux = lower_cell(arch, shape_name, mesh)
        rec.update(aux)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        rec["lower_s"] = round(t1 - t0, 1)

        ma = compiled.memory_analysis()
        if ma is not None:
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
            }
            print("memory_analysis:", ma)          # proves it fits
        ca = cost_analysis_dict(compiled)
        rec["cost"] = {"flops": float(ca.get("flops", -1)),
                       "bytes_accessed": float(ca.get("bytes accessed", -1))}
        print("cost_analysis:", {k: ca.get(k) for k in
                                 ("flops", "bytes accessed")})
        rec["collectives"] = parse_collectives(compiled.as_text())
        rec["ok"] = True
    except Exception as e:                          # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        if verbose:
            import traceback
            traceback.print_exc()
    rec["total_s"] = round(time.time() - t0, 1)

    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir,
                      f"{arch}__{shape_name}__{mesh_name}.json")
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '')[:120]})"
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: {status} "
              f"({rec['total_s']}s)")
    return rec


def all_cells():
    cells = []
    for arch in ARCHS:
        for shape_name in SHAPES:
            if runnable(arch, shape_name):
                cells.append((arch, shape_name))
    for cell_name in PPANNS_CELLS:
        cells.append(("ppanns-scan", cell_name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        # subprocess per cell: isolates device-count env and XLA state
        for arch, shape_name in all_cells():
            for mp in ([False, True] if args.both_meshes
                       else [args.multi_pod]):
                mesh_name = "2pod_512" if mp else "1pod_256"
                fn = os.path.join(
                    args.out, f"{arch}__{shape_name}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(fn):
                    print(f"[dryrun] skip existing {fn}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out", args.out]
                if mp:
                    cmd.append("--multi-pod")
                subprocess.run(cmd, check=False)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out)
    sys.exit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
