#!/usr/bin/env python3
"""Public-surface check (DESIGN.md §9): `repro.api` is the one entry
point for secure-ANN functionality.

Two gates:
  1. every name in `repro.api.__all__` actually resolves (the lazy
     export table cannot rot);
  2. no example and no serve launcher imports a legacy secure-ANN
     constructor directly — `examples/*.py` and
     `src/repro/launch/serve.py` must reach the system through
     `repro.api` only.  (Tests and benchmarks may still reach inside;
     they exercise internals on purpose.)

Run from the repo root:  PYTHONPATH=src python scripts/check_api.py
Exit code 0 = surface intact.
"""

from __future__ import annotations

import ast
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

# Files that must speak only repro.api for secure-ANN functionality.
GUARDED = sorted((ROOT / "examples").glob("*.py")) + \
    [ROOT / "src" / "repro" / "launch" / "serve.py"]

# Legacy secure-ANN modules: any import of these (or a submodule) from a
# guarded file is a surface violation.
BANNED_MODULES = (
    "repro.core.ppanns",
    "repro.serving.search_engine",
    "repro.serving.runtime",
    "repro.serving.ann_server",
    "repro.serving.secure_scan",
)

# Legacy constructors re-exported by `repro.serving` / `repro.core`:
# importing them by name from an umbrella module is the same violation.
BANNED_NAMES = {
    "ppanns", "SecureSearchEngine", "SearchStats", "FlatScanFilter",
    "IVFScanFilter", "HNSWGraphFilter", "CollectionManager", "Collection",
    "MicroBatcher", "SlotLoop", "Scheduler",
    "MutableEncryptedStore", "DeltaAwareBackend",
    "DistributedSecureANN", "ShardedBackend", "QueueFullError",
    "TenantIsolationError", "build_secure_scan_step", "secure_scan",
}

# Names that MUST stay exported by repro.api — the placement-aware
# surface contract (DESIGN.md §10) on top of the resolve check.
REQUIRED_EXPORTS = {
    "PlacementSpec", "IndexSpec", "SearchParams", "SearchRequest",
    "SearchResult", "SecureAnnService", "DataOwnerClient", "QueryClient",
}

# serving.ann_server is a deprecated shim (DESIGN.md §10): nothing in
# the src tree may import it except the shim modules themselves.  Tests
# may (they parity-test the shim).
ANN_SERVER_SHIMS = {
    pathlib.Path("src/repro/serving/ann_server.py"),
    pathlib.Path("src/repro/api/mesh.py"),
    pathlib.Path("src/repro/serving/__init__.py"),
}


def _banned_module(mod: str) -> bool:
    return any(mod == b or mod.startswith(b + ".") for b in BANNED_MODULES)


def check_imports(path: pathlib.Path) -> list[str]:
    errors = []
    rel = path.relative_to(ROOT)
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if _banned_module(alias.name):
                    errors.append(f"{rel}:{node.lineno}: imports legacy "
                                  f"module {alias.name} (use repro.api)")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level:                 # relative import — not repro.*
                continue
            if _banned_module(mod):
                errors.append(f"{rel}:{node.lineno}: imports from legacy "
                              f"module {mod} (use repro.api)")
            elif mod in ("repro.core", "repro.serving"):
                bad = sorted({a.name for a in node.names} & BANNED_NAMES)
                if bad:
                    errors.append(
                        f"{rel}:{node.lineno}: imports legacy "
                        f"constructor(s) {', '.join(bad)} from {mod} "
                        f"(use repro.api)")
    return errors


def check_api_exports() -> list[str]:
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.api as api
    except Exception as e:                          # noqa: BLE001
        return [f"import repro.api failed: {type(e).__name__}: {e}"]
    errors = []
    for name in api.__all__:
        try:
            getattr(api, name)
        except Exception as e:                      # noqa: BLE001
            errors.append(f"repro.api.{name} does not resolve: "
                          f"{type(e).__name__}: {e}")
    for name in sorted(REQUIRED_EXPORTS - set(api.__all__)):
        errors.append(f"repro.api must export {name} (placement-aware "
                      f"surface contract, DESIGN.md §10)")
    errors.extend(check_quantization_surface(api))
    errors.extend(check_obs_surface(api))
    errors.extend(check_sec_surface(api))
    errors.extend(check_graph_surface(api))
    errors.extend(check_resilience_surface(api))
    return errors


# Names that MUST stay exported by repro.graph — the batched graph-index
# surface contract (DESIGN.md §15).
REQUIRED_GRAPH_EXPORTS = {
    "CSRGraph", "GraphFilter", "beam_plan", "graph_topk", "traverse",
}


def check_graph_surface(api) -> list[str]:
    """The batched graph-index surface contract (DESIGN.md §15):
    repro.graph exports the CSR mirror + batched filter, and IndexSpec
    admits backend='graph' with quantization AND the hardened tier —
    the combinations the legacy per-query 'hnsw' backend rejects."""
    errors = []
    try:
        import repro.graph as graph
    except Exception as e:                          # noqa: BLE001
        return [f"import repro.graph failed: {type(e).__name__}: {e}"]
    for name in sorted(REQUIRED_GRAPH_EXPORTS):
        if not hasattr(graph, name):
            errors.append(f"repro.graph must export {name} (graph "
                          f"surface contract, DESIGN.md §15)")
    for kw in ({"quantization": "int8"},
               {"security_profile": "hardened"}):
        try:
            spec = api.IndexSpec(tenant="_gate", name="_gate", d=8,
                                 backend="graph", **kw)
            if api.IndexSpec.from_bytes(spec.to_bytes()) != spec:
                errors.append(f"IndexSpec(backend='graph', **{kw}) does "
                              f"not survive a wire round-trip")
        except Exception as e:                      # noqa: BLE001
            errors.append(f"IndexSpec must admit backend='graph' with "
                          f"{kw} (DESIGN.md §15): {type(e).__name__}: {e}")
    return errors


def check_quantization_surface(api) -> list[str]:
    """The quantized-ADC surface contract (DESIGN.md §11): IndexSpec
    carries the quantization knobs, rejects bad values, and round-trips
    them over the wire."""
    import dataclasses
    errors = []
    fields = {f.name for f in dataclasses.fields(api.IndexSpec)}
    for name in ("quantization", "refine_ratio", "pq_m"):
        if name not in fields:
            errors.append(f"IndexSpec must carry {name} (quantized ADC "
                          f"surface, DESIGN.md §11)")
    if errors:
        return errors
    try:
        spec = api.IndexSpec(tenant="_gate", name="_gate", d=8,
                             quantization="int8")
        spec2 = api.IndexSpec.from_bytes(spec.to_bytes())
        if spec2.quantization != "int8":
            errors.append("IndexSpec.quantization does not survive a "
                          "wire round-trip")
    except Exception as e:                          # noqa: BLE001
        errors.append(f"IndexSpec(quantization='int8') must construct "
                      f"and round-trip: {type(e).__name__}: {e}")
    for bad in ({"quantization": "int4"},
                {"quantization": "int8", "backend": "hnsw"}):
        try:
            api.IndexSpec(tenant="_gate", name="_gate", d=8, **bad)
            errors.append(f"IndexSpec must reject {bad}")
        except ValueError:
            pass
    return errors


# Names that MUST stay exported by repro.obs — the observability
# surface contract (DESIGN.md §13).
REQUIRED_OBS_EXPORTS = {
    "Observability", "TraceRecorder", "NullRecorder", "NULL_RECORDER",
    "Span", "child_span", "child_complete", "current", "to_host",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "start_metrics_server",
}


def check_obs_surface(api) -> list[str]:
    """The observability surface contract (DESIGN.md §13): repro.obs
    exports the tracing/metrics entry points, the service
    exposes the export methods, and SearchRequest carries trace_id over
    the wire."""
    import dataclasses
    errors = []
    try:
        import repro.obs as obs
    except Exception as e:                          # noqa: BLE001
        return [f"import repro.obs failed: {type(e).__name__}: {e}"]
    for name in sorted(REQUIRED_OBS_EXPORTS):
        if not hasattr(obs, name):
            errors.append(f"repro.obs must export {name} (observability "
                          f"surface contract, DESIGN.md §13)")
    for meth in ("metrics_text", "trace_events", "export_chrome_trace"):
        if not callable(getattr(api.SecureAnnService, meth, None)):
            errors.append(f"SecureAnnService must expose {meth}() "
                          f"(DESIGN.md §13)")
    fields = {f.name for f in dataclasses.fields(api.SearchRequest)}
    if "trace_id" not in fields:
        errors.append("SearchRequest must carry trace_id "
                      "(client-propagated correlation id, DESIGN.md §13)")
    return errors


# Names that MUST stay exported by repro.sec — the security-profile +
# leakage-harness surface contract (DESIGN.md §14).
REQUIRED_SEC_EXPORTS = {
    "SecurityProfile", "PROFILES", "SECURITY_PROFILE_NAMES",
    "DEFAULT_PROFILE", "get_profile",
    "AttackResult", "ServerView", "capture_server_view",
    "aspe_kpa_attack", "dce_kpa_attack", "adc_code_attack",
    "access_pattern_attack", "evaluate_profile",
}


def check_sec_surface(api) -> list[str]:
    """The security-profile surface contract (DESIGN.md §14): repro.sec
    exports the profile registry + leakage harness, and IndexSpec
    carries (validates, round-trips) `security_profile`."""
    import dataclasses
    errors = []
    try:
        import repro.sec as sec
    except Exception as e:                          # noqa: BLE001
        return [f"import repro.sec failed: {type(e).__name__}: {e}"]
    for name in sorted(REQUIRED_SEC_EXPORTS):
        if not hasattr(sec, name):
            errors.append(f"repro.sec must export {name} (security "
                          f"surface contract, DESIGN.md §14)")
    fields = {f.name for f in dataclasses.fields(api.IndexSpec)}
    if "security_profile" not in fields:
        return errors + ["IndexSpec must carry security_profile "
                         "(DESIGN.md §14)"]
    try:
        spec = api.IndexSpec(tenant="_gate", name="_gate", d=8,
                             security_profile="hardened")
        if api.IndexSpec.from_bytes(spec.to_bytes()) != spec:
            errors.append("IndexSpec.security_profile does not survive "
                          "a wire round-trip")
    except Exception as e:                          # noqa: BLE001
        errors.append(f"IndexSpec(security_profile='hardened') must "
                      f"construct and round-trip: {type(e).__name__}: {e}")
    for bad in ({"security_profile": "bogus"},
                {"security_profile": "hardened", "backend": "hnsw"}):
        try:
            api.IndexSpec(tenant="_gate", name="_gate", d=8, **bad)
            errors.append(f"IndexSpec must reject {bad}")
        except ValueError:
            pass
    return errors


# Names that MUST stay exported by repro.resilience — the fault-tolerant
# serving surface contract (DESIGN.md §16).
REQUIRED_RESILIENCE_EXPORTS = {
    "WriteAheadLog", "WalRecord", "WalCorruptionError",
    "AsyncCheckpointer", "recover", "RecoveryReport", "attach_wal",
    "ShardHealthRegistry", "FaultPlan", "InjectedFault", "SimulatedCrash",
    "EngineRetryPolicy", "RetryPolicy", "ResilientRunner",
    "StragglerWatchdog",
}


def check_resilience_surface(api) -> list[str]:
    """The fault-tolerance surface contract (DESIGN.md §16):
    repro.resilience exports the WAL/checkpoint/recovery/failover entry
    points, and the failover wire fields stay ADDITIVE — old payloads
    without them must keep decoding as healthy answers, and
    PlacementSpec.n_replicas must validate and round-trip."""
    import dataclasses

    import numpy as np
    errors = []
    try:
        import repro.resilience as resilience
    except Exception as e:                          # noqa: BLE001
        return [f"import repro.resilience failed: "
                f"{type(e).__name__}: {e}"]
    for name in sorted(REQUIRED_RESILIENCE_EXPORTS):
        if not hasattr(resilience, name):
            errors.append(f"repro.resilience must export {name} "
                          f"(resilience surface contract, DESIGN.md §16)")
    # additive wire fields: a stats dict WITHOUT the failover keys (a
    # pre-§16 peer's payload) must decode as a healthy answer
    from repro.serving.search_engine import SearchStats
    stats_fields = {f.name for f in dataclasses.fields(SearchStats)}
    for name in ("degraded", "n_shards_down"):
        if name not in stats_fields:
            errors.append(f"SearchStats must carry {name} "
                          f"(failover accounting, DESIGN.md §16)")
    if not errors:
        try:
            from repro.api.protocol import PROTOCOL_VERSION
            from repro.core.wireformat import pack
            old_stats = dataclasses.asdict(SearchStats(
                latency_s=0.0, filter_dist_evals=0, refine_comparisons=0,
                bytes_up=0, bytes_down=0, n_queries=1, backend="flat"))
            old_stats.pop("degraded")
            old_stats.pop("n_shards_down")
            res = api.SearchResult.from_bytes(pack(
                "search-result", PROTOCOL_VERSION,
                arrays={"ids": np.zeros((1, 1), np.int64)},
                meta={"stats": old_stats}))
            if res.degraded or res.stats.n_shards_down:
                errors.append("pre-resilience search-result payloads "
                              "must decode as healthy (additive wire "
                              "contract, DESIGN.md §16)")
        except Exception as e:                      # noqa: BLE001
            errors.append(f"pre-resilience search-result payload must "
                          f"decode: {type(e).__name__}: {e}")
    # PlacementSpec.n_replicas: validated, wire round-tripped, additive
    try:
        p = api.PlacementSpec(kind="sharded", n_shards=2, n_replicas=3)
        if api.PlacementSpec.from_bytes(p.to_bytes()) != p:
            errors.append("PlacementSpec.n_replicas does not survive a "
                          "wire round-trip")
        d = p.to_dict()
        d.pop("n_replicas")
        if api.PlacementSpec.from_dict(d).n_replicas != 1:
            errors.append("PlacementSpec.from_dict must default missing "
                          "n_replicas to 1 (additive wire contract)")
    except Exception as e:                          # noqa: BLE001
        errors.append(f"PlacementSpec(n_replicas=3) must construct and "
                      f"round-trip (DESIGN.md §16): "
                      f"{type(e).__name__}: {e}")
    for bad in ({"kind": "sharded", "n_shards": 2, "n_replicas": 0},
                {"kind": "single", "n_replicas": 2}):
        try:
            api.PlacementSpec(**bad)
            errors.append(f"PlacementSpec must reject {bad}")
        except ValueError:
            pass
    return errors


def check_ann_server_ban() -> list[str]:
    """No src module outside the shims may import the deprecated
    `serving.ann_server` path (absolute or relative)."""
    errors = []
    for path in sorted((ROOT / "src").rglob("*.py")):
        rel = path.relative_to(ROOT)
        if rel in ANN_SERVER_SHIMS:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                # `from pkg.serving import ann_server` names the module
                # as an alias, not in node.module — check both
                mods = [node.module or ""] \
                    + [a.name for a in node.names]
            for mod in mods:
                if mod == "ann_server" or mod.endswith(".ann_server"):
                    errors.append(
                        f"{rel}:{node.lineno}: imports deprecated "
                        f"ann_server (only the shims may; use "
                        f"serving.sharded / placement=)")
                    break
    return errors


def main() -> int:
    errors = check_api_exports()
    errors.extend(check_ann_server_ban())
    for f in GUARDED:
        errors.extend(check_imports(f))
    if errors:
        print("api surface check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    import repro.api as api
    print(f"api surface check OK: {len(api.__all__)} public names "
          f"resolve; {len(GUARDED)} guarded files import only repro.api")
    return 0


if __name__ == "__main__":
    sys.exit(main())
