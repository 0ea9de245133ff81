"""repro.graph: the device-resident batched CSR graph index (DESIGN.md §15).

Pins the subsystem's contracts:

  * the CSR mirror round-trips the owner-built HNSW bit-identically,
    deletes and incremental row refreshes included;
  * the batched lockstep traversal returns ids identical to the
    per-query host walk at fixed ef — the host walk stays as the
    parity oracle the batched filter is measured against;
  * the ADC-quantized variant keeps recall; the oblivious variant is
    bit-identical to the perf variant with CONSTANT hop/edge counts;
  * mutations through the delta store: tombstones never surface, new
    rows are reachable before compaction, and the steady state is
    recompile-free on both schedulers;
  * sharded collections serve per-shard subgraphs with exact
    batched-vs-looped parity and snapshot persistence;
  * the spec/wire surface: `backend="graph"` is admitted where the
    legacy per-query "hnsw" backend stays rejected, and the new
    SearchStats fields are additive (old payloads decode to 0).
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.api import IndexSpec, PlacementSpec
from repro.core import dcpe, ppanns
from repro.core.hnsw import HNSW
from repro.data import synth
from repro.graph import CSRGraph, GraphFilter
from repro.serving.runtime import Collection
from repro.serving.runtime.telemetry import jit_cache_size
from repro.serving.search_engine import (HNSWGraphFilter, SearchStats,
                                         SecureSearchEngine)

K = 10


@pytest.fixture(scope="module")
def setup():
    ds = synth.make_dataset("deep1m", n=800, n_queries=8, k_gt=30, seed=21,
                            d=32)
    owner, user, server = ppanns.build_system(
        ds.base, beta_fraction=0.03, M=12, ef_construction=100, seed=21)
    qs, ts = zip(*(user.encrypt_query(q) for q in ds.queries))
    return ds, server, np.stack(qs), np.stack(ts)


# ---------------------------------------------------------------------------
# CSR mirror: bit-identical round trip with the host HNSW.
# ---------------------------------------------------------------------------

def _assert_arrays_equal(a: dict, b: dict):
    assert set(a) == set(b)
    for k in a:
        assert np.asarray(a[k]).dtype == np.asarray(b[k]).dtype, k
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), k)


def test_csr_round_trip_bit_identical_with_deletes():
    rng = np.random.default_rng(3)
    h = HNSW(16, M=8, ef_construction=60, seed=3)
    h.build(rng.standard_normal((200, 16)).astype(np.float32))
    h.delete(5)
    h.delete(17)
    g = CSRGraph.from_hnsw(h)
    _assert_arrays_equal(g.to_arrays(), h.to_arrays())
    # arrays → HNSW → arrays is the identity too (persistence path)
    h2 = HNSW.from_arrays(g.to_arrays())
    _assert_arrays_equal(h2.to_arrays(), h.to_arrays())


def test_csr_incremental_refresh_matches_full_rebuild():
    rng = np.random.default_rng(4)
    h = HNSW(16, M=8, ef_construction=60, seed=4)
    h.build(rng.standard_normal((150, 16)).astype(np.float32))
    g = CSRGraph.from_hnsw(h, R=256)
    assert g.fits(h)
    # one insert dirties the new node and every node it linked to (their
    # lists changed, possibly pruned) — the ingest layer's changed-row rule
    node = h.insert(rng.standard_normal(16).astype(np.float32))
    dirty = {node}
    for lev in range(h.levels[node] + 1):
        dirty.update(np.asarray(h.links[lev][node]).tolist())
    # one delete dirties the row and the repaired in-neighbors
    dirty.add(30)
    dirty.update(h.delete(30))
    g.refresh_rows(h, sorted(dirty))
    g.refresh_meta(h)
    fresh = CSRGraph.from_hnsw(h, R=g.R, LU=g.LU)
    np.testing.assert_array_equal(g.neigh0, fresh.neigh0)
    np.testing.assert_array_equal(g.neigh_up, fresh.neigh_up)
    np.testing.assert_array_equal(g.levels, fresh.levels)
    np.testing.assert_array_equal(g.X, fresh.X)
    assert g.entry == fresh.entry and g.n == fresh.n


# ---------------------------------------------------------------------------
# Batched filter vs the host-walk parity oracle.
# ---------------------------------------------------------------------------

def test_batched_filter_matches_host_walk_oracle(setup):
    """The acceptance property: GraphFilter ids == per-query host walk
    ids at fixed ef, exactly (the equivalence argument in graph.traverse)."""
    ds, server, Q, T = setup
    C_sap, C_dce = server.db.C_sap, server.db.C_dce
    eng_g = SecureSearchEngine(
        C_sap, C_dce, backend=GraphFilter(server.db.index))
    eng_h = SecureSearchEngine(
        C_sap, C_dce, backend=HNSWGraphFilter(server.db.index))
    with pytest.warns(DeprecationWarning, match="parity oracle"):
        host, _ = eng_h.search_batch(Q, T, K, ratio_k=8, ef_search=128)
    batched, st = eng_g.search_batch(Q, T, K, ratio_k=8, ef_search=128)
    np.testing.assert_array_equal(batched, host)
    assert st.backend == "graph"
    assert st.n_hops > 0 and st.n_edges_scanned > 0
    assert synth.recall_at_k(batched, ds.gt, K) >= 0.9


def test_batched_matches_per_query(setup):
    ds, server, Q, T = setup
    eng = SecureSearchEngine(
        server.db.C_sap, server.db.C_dce,
        backend=GraphFilter(server.db.index))
    whole, _ = eng.search_batch(Q, T, K, ratio_k=8, ef_search=128)
    for i in range(len(Q)):
        one, _ = eng.search_batch(Q[i:i + 1], T[i:i + 1], K, ratio_k=8,
                                  ef_search=128)
        np.testing.assert_array_equal(whole[i], one[0])


def test_int8_quantized_graph_recall(setup):
    ds, server, Q, T = setup
    gf = GraphFilter(server.db.index, quantization="int8")
    eng = SecureSearchEngine(server.db.C_sap, server.db.C_dce, backend=gf)
    ids, st = eng.search_batch(Q, T, K, ratio_k=8, ef_search=128)
    assert st.backend == "adc-graph-int8"
    assert synth.recall_at_k(ids, ds.gt, K) >= 0.8
    # surrogate scoring reads code bytes, not f32 rows
    assert 0 < gf.last_filter_bytes < gf.last_n_edges_scanned * ds.d * 4


def test_oblivious_bit_identical_with_constant_accounting(setup):
    ds, server, Q, T = setup
    perf = GraphFilter(server.db.index)
    obl = GraphFilter(server.db.index, oblivious=True)
    perf.attach(server.db.C_sap)
    obl.attach(server.db.C_sap)

    def ids(gf, Qb):
        c, v, _ = gf.candidates(Qb, 32, 128)
        return np.where(v, c, -1)

    np.testing.assert_array_equal(ids(obl, Q[:4]), ids(perf, Q[:4]))
    h1, e1 = obl.last_n_hops, obl.last_n_edges_scanned
    ids(obl, Q[4:8])                       # different queries, same shape
    assert (obl.last_n_hops, obl.last_n_edges_scanned) == (h1, e1)
    assert h1 >= perf.last_n_hops          # bounded-hop pads, never trims
    # the residual leak is the ADDRESS stream: the visited bitmap stays
    # data-dependent (sec.leakage scores it; the intermediate tier)
    tr = obl.last_scan_trace
    assert tr.dtype == np.bool_ and tr.shape[0] == 4
    assert 0 < tr.sum() < tr.size


# ---------------------------------------------------------------------------
# Spec / engine admission surface.
# ---------------------------------------------------------------------------

def _spec(**kw):
    return IndexSpec(tenant="t", name="g", d=16, sap_beta=1.0, seed=0, **kw)


def test_spec_admits_graph_where_hnsw_is_rejected():
    # graph takes quantization and the hardened tier; the legacy
    # per-query host walk still rejects both
    _spec(backend="graph", quantization="int8")
    _spec(backend="graph", security_profile="hardened")
    with pytest.raises(ValueError, match="quantization"):
        _spec(backend="hnsw", quantization="int8")
    with pytest.raises(ValueError, match="graph"):
        _spec(backend="hnsw", security_profile="hardened")


def test_engine_rejects_graph_as_string(setup):
    ds, server, Q, T = setup
    with pytest.raises(ValueError, match="GraphFilter"):
        SecureSearchEngine(server.db.C_sap, server.db.C_dce,
                           backend="graph")


def test_search_stats_new_fields_are_additive():
    """Old wire payloads carry no n_hops/n_edges_scanned: decoding them
    into the new dataclass must default both to 0, not fail."""
    flds = {f.name: f for f in dataclasses.fields(SearchStats)}
    assert flds["n_hops"].default == 0
    assert flds["n_edges_scanned"].default == 0


# ---------------------------------------------------------------------------
# Mutations through the delta store, on both schedulers.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheduler", ["flush", "continuous"])
def test_graph_delta_lifecycle(scheduler):
    ds = synth.make_dataset("deep1m", n=400, n_queries=6, k_gt=10, seed=7,
                            d=16)
    beta = dcpe.suggest_beta(ds.base, fraction=0.03)
    col = Collection("t0", f"g-{scheduler}", ds.d, backend="graph",
                     sap_beta=beta, seed=7, scheduler=scheduler,
                     compact_every=10_000, hnsw_M=8,
                     hnsw_ef_construction=60)
    try:
        col.insert(ds.base)
        user = col.new_user()
        enc = [user.encrypt_query(q) for q in ds.queries]
        Q = np.stack([c for c, _ in enc])
        T = np.stack([t for _, t in enc])
        dead = []

        def cycle(i):
            new = int(col.insert(ds.queries[i][None])[0])
            ids, st = col.search_batch(Q, T, K, ratio_k=8, ef_search=96)
            # the delta row is reachable BEFORE any compaction
            assert new in ids[i]
            assert st.n_hops > 0 and st.n_edges_scanned > 0
            # scheduler-path parity with the direct engine call
            fut = col.submit(*enc[i], K, ef_search=96)
            one, _ = col.search_batch(Q[i:i + 1], T[i:i + 1], K,
                                      ef_search=96)
            np.testing.assert_array_equal(fut.result(timeout=30), one[0])
            victim = int(ds.gt[i, 0])
            col.delete([new, victim])
            dead.extend([new, victim])
            ids2, _ = col.search_batch(Q, T, K, ratio_k=8, ef_search=96)
            # tombstones never surface, with or without compaction
            assert not np.isin(ids2, dead).any()
            return ids2

        cycle(0)
        warm = jit_cache_size()             # one warmup cycle compiles all
        for i in (1, 2):
            cycle(i)
        assert jit_cache_size() == warm     # steady state: zero recompiles
        col.compact()
        ids3 = cycle(3)
        assert synth.recall_at_k(ids3, ds.gt, K) >= 0.5
        snap = col.stats()
        assert snap["n_hops"] > 0 and snap["n_edges_scanned"] > 0
    finally:
        col.close()


# ---------------------------------------------------------------------------
# Sharded: per-shard subgraphs, exact parity, persistence.
# ---------------------------------------------------------------------------

def test_sharded_graph_parity_and_snapshot():
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices (run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    ds = synth.make_dataset("deep1m", n=500, n_queries=6, k_gt=10, seed=11,
                            d=16)
    beta = dcpe.suggest_beta(ds.base, fraction=0.03)
    pl = PlacementSpec(kind="sharded", n_shards=2).resolve(
        jax.device_count())

    def make(**kw):
        return Collection("t0", "shg", ds.d, backend="graph",
                          sap_beta=beta, seed=11, placement=pl,
                          compact_every=10_000, hnsw_M=8,
                          hnsw_ef_construction=60, **kw)

    col = make()
    try:
        col.insert(ds.base)
        user = col.new_user()
        qs, ts = zip(*(user.encrypt_query(q) for q in ds.queries))
        Q, T = np.stack(qs), np.stack(ts)
        ids, st = col.search_batch(Q, T, K, ratio_k=8, ef_search=96)
        assert st.backend == "sharded-graph"
        assert st.n_hops > 0
        assert synth.recall_at_k(ids, ds.gt, K) >= 0.6
        for i in range(len(Q)):                       # batched == looped
            one, _ = col.search_batch(Q[i:i + 1], T[i:i + 1], K,
                                      ratio_k=8, ef_search=96)
            np.testing.assert_array_equal(ids[i], one[0])
        arrays, book = col.snapshot()
        col2 = make()
        try:
            col2.load_snapshot(
                arrays["C_sap"], arrays["C_dce"], alive=arrays["alive"],
                n_main=book["n_main"], main_gen=book["main_gen"],
                graph_arrays={k[len("graph__"):]: v
                              for k, v in arrays.items()
                              if k.startswith("graph__")})
            ids2, _ = col2.search_batch(Q, T, K, ratio_k=8, ef_search=96)
            np.testing.assert_array_equal(ids, ids2)
        finally:
            col2.close()
    finally:
        col.close()
