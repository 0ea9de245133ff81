"""One run of one cell: set-up, a measured window, the check, the result.

Everything a cell needs is found by name: the cell in `BENCHMARK.json`,
its configuration's file, its traffic mix under `traffic/<mix>.json`,
and each per-layer metric's reader under `metrics/<metric>.py`.  A name
that is not found is an error, never a default.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import logging
import pathlib
import shutil
import sys
import tempfile
import threading
import time
import types

import numpy as np

from . import check, deployment, devtrace, stats
from . import traffic as traffic_mod
from .data import make_corpus

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# spans kept by a traced run: enough for every request of a window
TRACE_CAPACITY = 4_000_000
# seconds of the window the profiler records, and where it starts
TRACE_SECONDS = 3.0
TRACE_START = 0.4


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Discovery by name.
# ---------------------------------------------------------------------------

def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def load_config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    entry = find(bench["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def load_traffic(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    path = bench_dir / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = json.loads(path.read_text())
    traffic_mod.validate(mix)
    return mix


def load_metric(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The reader module of a per-layer metric (`read(ctx)`)."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise AttributeError(f"metric reader {path} has no read(ctx)")
    return mod


def cell_metrics(bench: dict, section: str, workload: str) -> list[dict]:
    """The metrics of `section` ('end_to_end' or 'per_layer') that this
    cell reports: those listing it, and those that list no cells."""
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def merged(base: dict, override: dict | None) -> dict:
    out = json.loads(json.dumps(base))
    for k, v in (override or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# Compile counting.
# ---------------------------------------------------------------------------

class CacheMisses(logging.Handler):
    """Names of the programs that missed JAX's persistent compilation
    cache (and so compiled), read from the compiler's log records."""

    PREFIX = "PERSISTENT COMPILATION CACHE MISS for "

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: list[str] = []
        self._logger = logging.getLogger("jax._src.compiler")
        self._logger.addHandler(self)
        self._logger.setLevel(logging.DEBUG)
        self._logger.propagate = False

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith(self.PREFIX):
            self.names.append(msg[len(self.PREFIX):].split("'")[1])
        elif record.levelno >= logging.WARNING:
            log(msg)


class CompileCounter:
    """Counts JAX's compile events (traces and backend compiles)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.counts = dict.fromkeys(self.EVENTS, 0)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.counts:
            with self._lock:
                self.counts[event] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.counts)


# ---------------------------------------------------------------------------
# Tracing.
# ---------------------------------------------------------------------------

class Profile:
    """A `jax.profiler` trace of part of the window, started and stopped
    from a thread of its own while the load runs."""

    def __init__(self, start_at: float, seconds: float):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.start_at, self.seconds = start_at, seconds
        self.t_sync = self.t_stop = None
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-profile")
        self._thread.start()

    def _run(self):
        import jax
        try:
            wait = self.start_at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(devtrace.SYNC_EVENT):
                self.t_sync = time.monotonic()
            time.sleep(max(0.0, self.start_at + self.seconds
                           - time.monotonic()))
            self.t_stop = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as exc:          # noqa: BLE001 — reported below
            self.error = exc

    def events(self) -> tuple[list, tuple, float]:
        """(events, (lo_ns, hi_ns) of the traced window, offset_ns)."""
        self._thread.join(timeout=120)
        if self.error is not None:
            raise RuntimeError(f"profiler failed: {self.error!r}")
        files = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        events = devtrace.load_events(str(files[-1]))
        shutil.rmtree(self.dir, ignore_errors=True)
        offset = devtrace.sync_offset_ns(events, self.t_sync)
        if offset is None:
            raise RuntimeError("the trace lacks its clock-sync annotation")
        lo = self.t_sync * 1e9 + offset
        hi = self.t_stop * 1e9 + offset
        return devtrace.clip(events, lo, hi), (lo, hi), offset


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, root: pathlib.Path = ROOT,
             overrides: dict | None = None) -> dict:
    """Run `workload` once and return the result object.  `overrides`
    ({'config': {...}, 'traffic': {...}}) resize a cell for tests."""
    import jax

    from repro.api import SearchParams
    from repro.serving.runtime.telemetry import jit_cache_size

    t_start = time.monotonic() if t_start is None else t_start
    overrides = overrides or {}
    bench = load_benchmark(root)
    cell = find(bench["workloads"], workload, "workload")
    config = merged(load_config(bench, cell["config"], root),
                    overrides.get("config"))
    mix = merged(load_traffic(cell["traffic"], root / "bench"),
                 overrides.get("traffic"))
    e2e = cell_metrics(bench, "end_to_end", workload)
    layer = cell_metrics(bench, "per_layer", workload)
    readers = {m["name"]: load_metric(m["name"], root / "bench")
               for m in layer}
    devices = jax.devices()
    device = devices[0]
    counter = CompileCounter()
    misses = CacheMisses()
    k = int(config["k"])
    nq = int(mix["queries_per_request"])

    # -- set-up: corpus, owner encryption, ingest, requests, warm-up ----
    base_dev, queries_dev = make_corpus(config, seed)
    base = np.asarray(base_dev)
    queries = np.asarray(queries_dev)
    del base_dev, queries_dev
    obs = None
    if trace:
        from repro.obs import Observability
        obs = Observability(trace_capacity=TRACE_CAPACITY)
    dep = deployment.build(config, base, seed, obs=obs, log=log)
    plan = traffic_mod.make_plan(mix, queries.shape[0], seed, seconds)
    t_enc = time.monotonic()
    params = SearchParams(k=k, ratio_k=float(config["ratio_k"]))
    make_request = deployment.request_maker(dep, queries, plan, params,
                                            dep.seeds[2])
    wq, wt = deployment.encrypt_queries(dep.keys, queries[:nq],
                                        seed=dep.seeds[3])
    t_warm = time.monotonic()
    deployment.warm_up(dep, config, mix, wq, wt)
    t_ready = time.monotonic()
    log(f"setup queries_s={t_warm - t_enc:.3f} "
        f"warmup_s={t_ready - t_warm:.3f} requests={plan.n_requests}")
    setup_s = t_ready - t_start

    # -- the window ---------------------------------------------------
    def submit(req):
        return np.asarray(dep.svc.submit(req).ids)[:, :k]

    # what set-up left alive is never garbage again: keep the collector
    # from walking it in the window
    gc.collect()
    gc.freeze()
    jit0, comp0 = jit_cache_size(), counter.snapshot()
    n_setup_misses = len(misses.names)
    t0 = time.monotonic() + 0.25
    prof = (Profile(t0 + TRACE_START * seconds,
                    min(TRACE_SECONDS, 0.5 * seconds))
            if trace else None)
    run_log = traffic_mod.drive(plan, submit, make_request, t0, seconds)
    t_closed = time.monotonic()
    gc.unfreeze()
    jit1, comp1 = jit_cache_size(), counter.snapshot()
    mem = device.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    spans = ([s.to_dict() for s in obs.recorder.spans()
              if t0 <= s.t_start and s.t_end <= t_closed]
             if obs is not None else [])
    rows_per_call = _rows_per_call(spans, nq)
    trace_events = trace_window = offset = None
    if prof is not None:
        trace_events, trace_window, offset = prof.events()
        trace_window = devtrace.observed(trace_events, trace_window)
        trace_events = devtrace.clip(trace_events, *trace_window)
    col_bytes = sum(a.nbytes for a in _device_arrays(dep.collection))
    dep.svc.drop_collection(dep.spec.tenant, dep.spec.name)
    dep.svc.close()
    del dep, obs
    gc.collect()

    # -- what the window did -------------------------------------------
    sent = run_log.sent()
    ok = run_log.status == traffic_mod.OK
    refused = int((run_log.status == traffic_mod.REFUSED).sum())
    lost = int((sent & (run_log.status == traffic_mod.ERROR)).sum())
    if plan.kind == "open":
        attempted = plan.n_requests
        lost += int((~sent).sum())
    else:
        attempted = int(sent.sum())
    late = (run_log.t_send - run_log.t_due)[sent]
    facts = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "attempted": attempted, "refused": refused, "lost": lost,
        "exhausted": bool(run_log.exhausted),
        "compiles_in_window": {k_.rsplit("/", 1)[-1]: comp1[k_] - comp0[k_]
                               for k_ in comp1},
        "jit_cache_size": [jit0, jit1],
        "compile_cache_misses": {
            "setup": misses.names[:n_setup_misses],
            "window": misses.names[n_setup_misses:]},
        "generator_late_ms": {"mean": float(late.mean() * 1e3)
                              if late.size else 0.0,
                              "max": float(late.max() * 1e3)
                              if late.size else 0.0},
        "memory_peak_bytes": memory_peak,
        "collection_device_bytes": col_bytes,
        "rows_per_call": rows_per_call,
        "setup_s": setup_s,
        # answers per whole second of the window, and of the drain after
        "answers_per_s": np.bincount(
            ((run_log.t_done[ok] - t0) // 1.0).astype(int).clip(0)
        ).tolist() if ok.any() else [],
    }
    print("run " + json.dumps(facts), flush=True)

    # -- the check, against the plain reference -------------------------
    t_ref = time.monotonic()
    idx = np.flatnonzero(ok)
    ids = (np.concatenate([run_log.ids[i] for i in idx])
           if idx.size else np.zeros((0, k), np.int64))
    qrows = plan.qrows[idx].ravel()
    used, inverse = np.unique(qrows, return_inverse=True)
    from .reference import exact_topk
    ref = exact_topk(base, queries[used], k)[inverse]
    nums, by_gap = check.numbers(ids, qrows, ref, base, queries, lost=lost,
                                 order_gap=float(config["order_gap"]))
    correct, lines = check.judge(nums, config["limits"])
    log(f"check reference_s={time.monotonic() - t_ref:.3f} "
        f"answers={ids.shape[0]} order_violations_by_gap="
        f"{json.dumps(by_gap)}")

    # -- metrics -------------------------------------------------------
    values = {
        "setup_s": setup_s,
        "recall_at_10": 1.0 - nums["miss_rate"],
    }
    units = np.full(plan.n_requests, nq)
    if plan.kind == "closed":
        answered = np.where(ok, run_log.t_done, np.nan)
        values["qps"] = stats.closed_rate(answered, units, t0)
    else:
        lat = stats.latencies_ms(run_log.t_due, run_log.t_done)
        values["p50_ms"] = stats.percentile(lat, 50)
        values["p99_ms"] = stats.percentile(lat, 99)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": refused + lost}
    if trace:
        summary = devtrace.summary(trace_events, trace_window)
        ctx = types.SimpleNamespace(
            spans=spans, trace=trace_events, trace_summary=summary,
            device_kind=device.device_kind,
            n_rows=int(config["n"]), d=int(config["d"]), k=k,
            kp=_kp(config), rows_per_call=rows_per_call,
            config=config, traffic=mix)
        metrics = {}
        for m in layer:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["metrics"] = metrics
        result["device"] = dev
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(trace_events),
            "idle_gaps": devtrace.idle_gaps(
                trace_events, trace_window,
                devtrace.span_label(spans, offset)),
        }
    else:
        metrics = {}
        for m in e2e:
            if m["name"] not in values:
                raise KeyError(f"{workload} lists {m['name']!r}, which a "
                               f"{plan.kind} loop does not measure")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dev
    result["checks"] = {name: {"value": nums[name],
                               "limit": config["limits"][name]}
                        for name in check.NUMBERS}
    for line in lines:
        log(line)
    return result


def _kp(config: dict) -> int:
    kp = round(float(config["ratio_k"]) * int(config["k"]))
    ratio = config["index"].get("refine_ratio")
    if config["index"].get("quantization") is not None and ratio:
        kp = int(np.ceil(kp * float(ratio)))
    return kp


def _rows_per_call(spans: list, nq: int) -> float:
    """Mean real query rows per engine call: the flush spans' n_real
    under the micro-batcher, else the request's own rows."""
    rows = [s["attrs"]["n_real"] for s in spans
            if s["name"] == "flush" and "bucket" in s["attrs"]]
    return sum(rows) / len(rows) if rows else float(nq)


def _device_arrays(col):
    import jax
    seen = {}
    for obj in (col._backend, col._engine):
        for a in vars(obj).values():
            if isinstance(a, jax.Array):
                seen[id(a)] = a
    return seen.values()
