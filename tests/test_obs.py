"""End-to-end observability: exact span trees on VirtualClock for both
schedulers and both placements, the batch trace of direct requests,
device->host reads under `sync` spans, the spans' mirror in a
`jax.profiler` capture, Prometheus exposition, Chrome-trace export, and
disabled-mode no-op guarantees (DESIGN.md §13).

Every tree test is a SCRIPTED interleaving on the injected
`VirtualClock`: the recorder runs on the same clock instance as the
scheduler, so structure, attributes, AND virtual timestamps are
asserted exactly — no sleeps, no tolerance windows.
"""

import dataclasses
import json
import re
import threading
import urllib.request

import jax
import numpy as np
import pytest

from repro.api import (DataOwnerClient, IndexSpec, PlacementSpec,
                       SearchParams, SearchRequest, SecureAnnService,
                       suggest_beta)
from repro.core import dcpe
from repro.data import synth
from repro.obs import (NULL_RECORDER, MetricsRegistry, Observability,
                       TraceRecorder, child_span, current,
                       start_metrics_server, to_host)
from repro.serving.runtime import (Collection, SlotLoop, VirtualClock)
from repro.serving.search_engine import SearchStats

D = 24
K = 5


@pytest.fixture(scope="module")
def ds():
    return synth.make_dataset("sift1m", n=200, n_queries=4, d=D,
                              k_gt=K, seed=0)


def _shape(node):
    """Span tree -> (name, [child shapes]) for exact assertions."""
    return (node["name"], [_shape(c) for c in node["children"]])


def _collection(ds, name, vc, rec, **kw):
    col = Collection("t", name, D,
                     sap_beta=dcpe.suggest_beta(ds.base, fraction=0.05),
                     seed=1, clock=vc, tracer=rec, **kw)
    col.insert(ds.base[:64])
    return col


# ------------------------------------------------- flush scheduler tree


def test_flush_two_request_interleaving_exact_tree(ds):
    """Scripted interleaving: r0 parks on the (never-reached) deadline,
    r1 arrives 1 virtual ms later and completes the size-2 bucket — one
    flush serves both.  The full span forest is asserted exactly."""
    vc = VirtualClock()
    rec = TraceRecorder(clock=vc)
    col = _collection(ds, "c", vc, rec, max_batch=2,
                      max_wait_ms=10_000.0)
    try:
        user = col.new_user()
        enc = [user.encrypt_query(q) for q in ds.queries[:2]]
        f0 = col.submit(*enc[0], K)
        vc.wait_for_waiters(1)             # worker parked on deadline
        vc.advance(0.001)
        f1 = col.submit(*enc[1], K)        # fills the bucket: size flush
        r0, r1 = f0.result(timeout=30), f1.result(timeout=30)
        assert r0.shape == (K,) and r1.shape == (K,)
    finally:
        col.close()

    assert sorted(rec.trace_ids()) == ["t/c:b0", "t/c:i0", "t/c:r0",
                                       "t/c:r1"]
    # ingest trace: one root insert span, no compaction at 64 rows
    (ins,) = rec.tree("t/c:i0")
    assert _shape(ins) == ("insert", [])
    assert ins["attrs"]["n_rows"] == 64
    assert ins["attrs"]["compacted"] is False

    # batch trace: the worker saw r0 at 0 and collected until r1 filled
    # the bucket at 0.001; assembly, the engine call and delivery take
    # no virtual time.  The engine's filter and refine end at their
    # device->host reads (64 rows, all in the delta buffer: its
    # candidates and distances, then the refined ids)
    (root,) = rec.tree("t/c:b0")
    assert _shape(root) == ("batch", [
        ("collect", []), ("assemble", []),
        ("flush", [("filter", [("sync", []), ("sync", [])]),
                   ("refine", [("sync", [])])]),
        ("deliver", [])])
    assert root["attrs"] == {"collection": "t/c", "n_real": 2}
    assert (root["t_start"], root["t_end"]) == (0.0, 0.001)
    collect, assemble, flush, deliver = root["children"]
    assert (collect["t_start"], collect["t_end"]) == (0.0, 0.001)
    for node in (assemble, flush, deliver):
        assert (node["t_start"], node["t_end"]) == (0.001, 0.001)
    assert _shape(flush) == ("flush", [
        ("filter", [("sync", []), ("sync", [])]),
        ("refine", [("sync", [])])])
    assert [s["attrs"]["what"] for c in flush["children"]
            for s in c["children"]] == ["candidates", "distances", "ids"]
    assert flush["attrs"]["n_real"] == 2
    assert flush["attrs"]["bucket"] == 2
    assert flush["attrs"]["backend"] == "flat"
    assert flush["attrs"]["n_queries"] == 2
    assert flush["attrs"]["filter_dist_evals"] > 0
    assert flush["attrs"]["filter_bytes_scanned"] > 0
    filt, ref = flush["children"]
    assert filt["attrs"]["nq"] == 2
    assert filt["attrs"]["dist_evals"] == \
        flush["attrs"]["filter_dist_evals"]
    assert ref["attrs"]["comparisons"] == \
        flush["attrs"]["refine_comparisons"]

    # request traces: admission -> queue -> flush -> emit, exact times
    (req0,) = rec.tree("t/c:r0")
    assert _shape(req0) == ("request",
                            [("queue", []), ("flush", []), ("emit", [])])
    assert req0["attrs"]["scheduler"] == "microbatcher"
    assert req0["attrs"]["k"] == K
    assert req0["attrs"]["backend"] == "flat"      # closed with stats
    q0, fl0, em0 = req0["children"]
    assert (q0["t_start"], q0["t_end"]) == (0.0, 0.001)
    assert (fl0["t_start"], fl0["t_end"]) == (0.001, 0.001)
    assert (em0["t_start"], em0["t_end"]) == (0.001, 0.001)
    assert fl0["attrs"]["batch"] == "t/c:b0"       # request -> batch link
    assert (req0["t_start"], req0["t_end"]) == (0.0, 0.001)

    (req1,) = rec.tree("t/c:r1")
    q1 = req1["children"][0]
    assert (q1["t_start"], q1["t_end"]) == (0.001, 0.001)
    assert req1["children"][1]["attrs"]["batch"] == "t/c:b0"


# -------------------------------------------- continuous scheduler tree


def test_continuous_scheduler_exact_tree(ds):
    """Two sequential requests through the slot loop: each gets its own
    step trace; the request tree swaps `flush` for `slot` (occupancy)."""
    vc = VirtualClock()
    rec = TraceRecorder(clock=vc)
    col = _collection(ds, "s", vc, rec, scheduler="continuous",
                      max_batch=2)
    try:
        user = col.new_user()
        enc = [user.encrypt_query(q) for q in ds.queries[:2]]
        assert col.submit(*enc[0], K).result(timeout=30).shape == (K,)
        assert col.submit(*enc[1], K).result(timeout=30).shape == (K,)
    finally:
        col.close()

    for i in range(2):
        (req,) = rec.tree(f"t/s:r{i}")
        assert _shape(req) == ("request", [("queue", []), ("slot", []),
                                           ("emit", [])])
        assert req["attrs"]["scheduler"] == "slotloop"
        slot = req["children"][1]
        assert slot["attrs"]["batch"] == f"t/s:s{i}"
        (step,) = rec.tree(f"t/s:s{i}")
        assert _shape(step) == ("step", [
            ("filter", [("sync", []), ("sync", [])]),
            ("refine", [("sync", [])])])
        assert step["attrs"]["n_active"] == 1
        assert step["attrs"]["capacity"] == 2


def test_slot_loop_shared_step_interleaving():
    """Scripted interleaving on the bare slot loop: A stalls in step s0;
    B and C are admitted while s0 is in flight and ride step s1
    TOGETHER — the slot spans name the shared step trace."""
    entered, gate = threading.Event(), threading.Event()
    calls = []

    def eng(Q, T, k, ratio_k=8.0, ef_search=96):
        entered.set()
        gate.wait(timeout=10.0)
        Q = np.atleast_2d(Q)
        calls.append(Q.shape)
        ids = np.round(Q[:, 0]).astype(np.int64)[:, None] + np.arange(k)
        return ids, SearchStats(latency_s=0.0, filter_dist_evals=0,
                                refine_comparisons=0, bytes_up=0,
                                bytes_down=0, n_queries=Q.shape[0],
                                backend="fake")

    def req(i):
        return np.full(D, float(i), np.float32), np.zeros(2 * D + 16,
                                                          np.float32)

    vc = VirtualClock()
    rec = TraceRecorder(clock=vc)
    with SlotLoop(eng, max_batch=4, d=D, cdim=2 * D + 16, clock=vc,
                  name="nm", tracer=rec) as sl:
        fa = sl.submit(*req(1), K)
        assert entered.wait(timeout=10.0)  # A's step s0 is in flight
        entered.clear()
        fb = sl.submit(*req(2), K)         # queued during s0
        fc = sl.submit(*req(3), K)         # queued during s0
        gate.set()
        for i, f in zip((1, 2, 3), (fa, fb, fc)):
            np.testing.assert_array_equal(f.result(timeout=10),
                                          i + np.arange(K))

    def batch_of(tid):
        (tree,) = rec.tree(tid)
        assert _shape(tree) == ("request", [("queue", []), ("slot", []),
                                            ("emit", [])])
        return tree["children"][1]["attrs"]["batch"]

    assert batch_of("nm:r0") == "nm:s0"
    assert batch_of("nm:r1") == "nm:s1"    # B and C share one step
    assert batch_of("nm:r2") == "nm:s1"
    (s1,) = rec.tree("nm:s1")
    assert s1["attrs"]["n_active"] == 2


# ------------------------------------------------- sharded placement


def test_sharded_placement_emits_per_shard_spans(ds):
    """Sharded placement: the filter span carries one retroactive child
    per shard with that shard's row range and live count."""
    n_shards = min(2, jax.device_count())
    vc = VirtualClock()
    rec = TraceRecorder(clock=vc)
    col = _collection(ds, "sh", vc, rec, max_batch=2, max_wait_ms=5.0,
                      placement=PlacementSpec(kind="sharded",
                                              n_shards=n_shards))
    try:
        user = col.new_user()
        fut = col.submit(*user.encrypt_query(ds.queries[0]), K)
        vc.wait_for_waiters(1)
        vc.advance(0.01)                   # past the 5 ms deadline
        assert fut.result(timeout=60).shape == (K,)
    finally:
        col.close()

    (root,) = rec.tree("t/sh:b0")
    flush = root["children"][2]
    expect_shards = [(f"shard{i}", []) for i in range(n_shards)]
    assert _shape(flush) == ("flush", [
        ("filter", [("sync", [])] + expect_shards),
        ("refine", [("sync", [])])])
    shards = flush["children"][0]["children"][1:]
    assert [s["attrs"]["shard"] for s in shards] == list(range(n_shards))
    assert sum(s["attrs"]["n_alive"] for s in shards) == 64
    assert shards[-1]["attrs"]["row_stop"] >= 64


# ------------------------------------------ service surface + exports


def test_service_obs_surface_and_exports(ds, tmp_path):
    spec = IndexSpec(tenant="t", name="svc", d=D,
                     sap_beta=suggest_beta(ds.base, fraction=0.05),
                     max_wait_ms=4.0, seed=3)
    owner = DataOwnerClient(spec)
    C_sap, C_dce = owner.encrypt_vectors(ds.base)
    query = owner.query_client().encrypt_queries(ds.queries)
    with SecureAnnService(obs=True) as svc:
        assert isinstance(svc.obs, Observability)
        svc.create_collection(spec)
        svc.insert("t", "svc", C_sap, C_dce)
        res = svc.submit(SearchRequest(
            tenant="t", collection="svc", query=query,
            params=SearchParams(k=K), coalesce=False))
        assert res.ids.shape == (len(ds.queries), K)
        # client-propagated correlation id names the request trace
        one = dataclasses.replace(
            query, C_sap=query.C_sap[0], T=query.T[0])
        svc.submit(SearchRequest(tenant="t", collection="svc", query=one,
                                 params=SearchParams(k=K),
                                 trace_id="corr-42"))
        assert "corr-42" in svc.obs.recorder.trace_ids()
        (req,) = svc.obs.recorder.tree("corr-42")
        assert req["name"] == "request"

        text = svc.metrics_text()
        out = tmp_path / "trace.json"
        svc.export_chrome_trace(str(out))
        events = svc.trace_events()

    # prometheus exposition parses line-by-line
    sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
                        r'(\{[^{}]*\})? (\+Inf|[-+0-9.eE]+)$')
    for line in text.strip().splitlines():
        assert line.startswith("#") or sample.match(line), line
    assert "ann_requests_total" in text
    assert 'ann_request_latency_seconds_bucket' in text
    # histogram buckets are cumulative and end at +Inf == _count
    buckets = re.findall(
        r'ann_request_latency_seconds_bucket\{[^}]*collection="svc"'
        r'[^}]*le="([^"]+)"\} (\d+)', text)
    counts = [int(c) for _, c in buckets]
    assert counts == sorted(counts) and buckets[-1][0] == "+Inf"
    (count,) = re.findall(
        r'ann_request_latency_seconds_count\{[^}]*collection="svc"'
        r'[^}]*\} (\d+)', text)
    assert int(count) == counts[-1]

    # chrome trace loads as JSON with well-formed events
    data = json.loads(out.read_text())
    assert data["traceEvents"]
    for ev in data["traceEvents"]:
        assert ev["ph"] in ("X", "M", "i")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and "ts" in ev
    names = {e["args"]["name"] for e in data["traceEvents"]
             if e["ph"] == "M"}
    assert "corr-42" in names              # traces become named threads
    assert any(e["kind"] == "span" for e in events)


def test_service_obs_disabled_is_inert(ds):
    with SecureAnnService() as svc:
        assert svc.obs is None
        assert svc.metrics_text().startswith("# observability disabled")
        assert svc.trace_events() == []
        with pytest.raises(RuntimeError):
            svc.export_chrome_trace("/tmp/nope.json")


def test_trace_id_wire_roundtrip(ds):
    spec = IndexSpec(tenant="t", name="w", d=D, sap_beta=1.0, seed=0)
    query = DataOwnerClient(spec).query_client().encrypt_queries(
        ds.queries[:1])
    req = SearchRequest(tenant="t", collection="w", query=query,
                        params=SearchParams(k=3), trace_id="abc")
    assert SearchRequest.from_bytes(req.to_bytes()).trace_id == "abc"
    bare = dataclasses.replace(req, trace_id=None)
    assert SearchRequest.from_bytes(bare.to_bytes()).trace_id is None


def test_start_metrics_server_scrape():
    class Source:
        def metrics_text(self):
            return "demo_metric 1\n"

    server = start_metrics_server(Source(), 0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read()
        assert body == b"demo_metric 1\n"
    finally:
        server.shutdown()


# --------------------------------------------- batch trace, direct path


def test_batch_request_opens_a_batch_root_with_engine_spans(ds):
    """A request of several queries skips the micro-batcher: the
    collection's direct call opens the `batch` root itself, so the
    engine's filter/refine and their syncs have a trace to attach to."""
    spec = IndexSpec(tenant="t", name="direct", d=D,
                     sap_beta=suggest_beta(ds.base, fraction=0.05), seed=3)
    owner = DataOwnerClient(spec)
    C_sap, C_dce = owner.encrypt_vectors(ds.base)
    query = owner.query_client().encrypt_queries(ds.queries)
    with SecureAnnService(obs=True) as svc:
        svc.create_collection(spec)
        svc.insert("t", "direct", C_sap, C_dce)
        res = svc.submit(SearchRequest(tenant="t", collection="direct",
                                       query=query,
                                       params=SearchParams(k=K)))
        assert res.ids.shape == (len(ds.queries), K)
        rec = svc.obs.recorder
        (root,) = rec.tree("t/direct:d0")
    assert _shape(root) == ("batch", [
        ("filter", [("sync", []), ("sync", [])]),
        ("refine", [("sync", [])])])
    assert root["attrs"] == {"collection": "t/direct",
                             "n_real": len(ds.queries)}
    filt, ref = root["children"]
    assert filt["attrs"]["nq"] == len(ds.queries)
    assert root["t_start"] <= filt["t_start"] <= filt["t_end"] \
        <= ref["t_start"] <= ref["t_end"] <= root["t_end"]


# ------------------------------------------- device->host reads: sync


@pytest.mark.parametrize("backend,quantization", [
    ("flat", None), ("flat", "int8"), ("ivf", None)])
def test_every_device_read_in_a_search_is_a_sync_span(
        ds, monkeypatch, backend, quantization):
    """Every host read of a device array during `search_batch` happens
    inside a `sync` span, one read per span.  Results are committed to a
    one-device mesh so that every read goes through `ArrayImpl.__array__`
    (on the CPU, numpy otherwise takes single-device arrays through the
    buffer protocol, which no hook sees); `int()`/`.item()` reads pass
    through `_value` as `__array__` does, so `_value` is what counts."""
    from jax._src.array import ArrayImpl

    reads = []
    orig = ArrayImpl._value

    def value(self):
        ctx = current()
        reads.append(None if ctx is None else ctx[1].name)
        return orig.fget(self)

    rec = TraceRecorder()
    col = Collection("t", f"rd-{backend}-{quantization}", D,
                     sap_beta=dcpe.suggest_beta(ds.base, fraction=0.05),
                     seed=1, tracer=rec, backend=backend,
                     quantization=quantization)
    try:
        col.insert(ds.base[:128])
        col.compact()
        col.insert(ds.base[128:160])          # main and delta rows
        user = col.new_user()
        enc = [user.encrypt_query(q) for q in ds.queries]
        Q = np.stack([c for c, _ in enc])
        T = np.stack([t for _, t in enc])
        col.search_batch(Q, T, K)             # attach and compile
        rec.clear()
        mesh = jax.make_mesh((1,), ("x",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        monkeypatch.setattr(ArrayImpl, "_value", property(value))
        with jax.set_mesh(mesh):
            ids, _ = col.search_batch(Q, T, K)
        monkeypatch.undo()
    finally:
        col.close()
    assert ids.shape == (len(ds.queries), K)
    syncs = [s for s in rec.spans() if s.name == "sync"]
    assert reads and set(reads) == {"sync"}
    assert len(reads) == len(syncs)
    assert {s.trace_id for s in syncs} == {col.tracer.trace_ids()[0]}


@pytest.mark.parametrize("quantization", ["int8", "pq8"])
def test_merge_rounds_ride_the_candidate_read(ds, monkeypatch,
                                              quantization):
    """The flat ADC kernel's merge-round count reaches the `filter` span
    (`merge_rounds`, beside the host's `merge_rounds_full`) inside the
    candidate read the filter makes anyway: each batch trace holds as many `sync` spans as
    the XLA path's, which counts no rounds.  The kernel runs in
    interpret mode here, as the chip's choice of path is steered."""
    from repro.serving.runtime.ingest import DeltaAwareBackend

    rec = TraceRecorder()
    col = Collection("t", f"mr-{quantization}", D,
                     sap_beta=dcpe.suggest_beta(ds.base, fraction=0.05),
                     seed=1, tracer=rec, quantization=quantization)
    user = col.new_user()
    enc = [user.encrypt_query(q) for q in ds.queries]
    Q = np.stack([c for c, _ in enc])
    T = np.stack([t for _, t in enc])
    syncs, filters = {}, {}
    try:
        col.insert(ds.base)
        for kernel in (False, True):
            monkeypatch.setattr(DeltaAwareBackend, "_use_pallas",
                                lambda self, on=kernel: on)
            rec.clear()
            col.search_batch(Q, T, K)
            (root,) = [s for s in rec.spans()
                       if s.name == "batch" and s.parent_id is None]
            syncs[kernel] = sum(1 for s in rec.spans()
                                if s.name == "sync"
                                and s.trace_id == root.trace_id)
            (filters[kernel],) = [s.attrs for s in rec.spans()
                                  if s.name == "filter"]
    finally:
        col.close()
    assert syncs[True] == syncs[False] == 2
    assert "merge_rounds" not in filters[False]
    attrs = filters[True]
    assert attrs["merge_rounds"] > 0
    assert attrs["merge_rounds_full"] > 0
    assert attrs["merge_rounds_full"] % 128 == 0       # K x tiles


def test_to_host_without_a_trace_is_a_plain_read():
    import jax.numpy as jnp

    assert current() is None
    out = to_host(jnp.arange(4), "ids", np.int64)
    assert isinstance(out, np.ndarray) and out.dtype == np.int64
    assert out.tolist() == [0, 1, 2, 3]


# ---------------------------------------- the profiler's own clock


def test_profiler_capture_holds_the_program_spans(ds, tmp_path):
    """A `jax.profiler` capture around a traced search holds the live
    spans as host events of the same names, on the capture's clock."""
    from jax.profiler import ProfileData

    rec = TraceRecorder()
    col = Collection("t", "prof", D, sap_beta=1.0, seed=1, max_batch=2,
                     max_wait_ms=1.0, tracer=rec)
    try:
        col.insert(ds.base[:64])
        user = col.new_user()
        enc = user.encrypt_query(ds.queries[0])
        col.search(*enc, K)                   # compile outside the capture
        jax.profiler.start_trace(str(tmp_path))
        try:
            col.search(*enc, K)
        finally:
            jax.profiler.stop_trace()
    finally:
        col.close()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert {"batch", "collect", "assemble", "flush", "filter", "sync",
            "refine", "deliver"} <= names
    # retroactive per-request spans are not mirrored
    assert not {"request", "queue", "emit"} & names


# ------------------------------------------------------- disabled mode


def test_disabled_mode_is_noop(ds):
    """No tracer attached: child_span hands out the one shared no-op
    span, no ambient context exists, and nothing records."""
    assert current() is None
    sp = child_span("anything", x=1)
    assert sp is child_span("other")       # the same shared instance
    with sp as s:
        s.set(y=2)
    with NULL_RECORDER.span("op", "tid") as s:
        s.set(z=3)                         # ingest-path fallback CM
    assert NULL_RECORDER.spans() == []
    assert NULL_RECORDER.tree("tid") == []

    col = Collection("t", "off", D, sap_beta=1.0, seed=1, max_batch=2,
                     max_wait_ms=1.0)
    try:
        col.insert(ds.base[:32])
        user = col.new_user()
        ids = col.search(*user.encrypt_query(ds.queries[0]), K)
        assert ids.shape == (K,)           # untraced path serves fine
        assert current() is None
    finally:
        col.close()
