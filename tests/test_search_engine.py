"""Unified search engine: batched/per-query parity across every filter
backend, uniform SearchStats, and cross-entry-point agreement.

The acceptance property (ISSUE 1): `Server.search` looped over queries
and the batched engine return *identical* ids on a fixed-seed synthetic
dataset for flat, IVF, and HNSW backends — the refine path is the same
jitted batched tournament either way (batch-of-one vs batch-of-nq).
"""

import numpy as np
import pytest

from repro.core import dce, dcpe, ppanns
from repro.data import synth
from repro.serving.search_engine import (FlatScanFilter, HNSWGraphFilter,
                                         IVFScanFilter, SearchStats,
                                         SecureSearchEngine)

K = 10


@pytest.fixture(scope="module")
def setup():
    ds = synth.make_dataset("deep1m", n=1200, n_queries=8, k_gt=30, seed=21)
    owner, user, server = ppanns.build_system(
        ds.base, beta_fraction=0.03, M=12, ef_construction=100, seed=21)
    qs, ts = zip(*(user.encrypt_query(q) for q in ds.queries))
    return ds, server, np.stack(qs), np.stack(ts)


def _engines(server):
    C_sap, C_dce = server.db.C_sap, server.db.C_dce
    return {
        "flat": SecureSearchEngine(C_sap, C_dce, backend="flat"),
        "ivf": SecureSearchEngine(C_sap, C_dce, backend="ivf",
                                  n_partitions=16, nprobe=6),
        "hnsw": SecureSearchEngine(
            C_sap, C_dce, backend=HNSWGraphFilter(server.db.index)),
    }


@pytest.mark.parametrize("backend", ["flat", "ivf", "hnsw"])
def test_batched_matches_per_query(setup, backend):
    """Engine batch == engine looped batch-of-one, exactly, per backend."""
    ds, server, Q, T = setup
    eng = _engines(server)[backend]
    batched, stats = eng.search_batch(Q, T, K, ratio_k=6)
    for qi in range(Q.shape[0]):
        single, sstats = eng.search(Q[qi], T[qi], K, ratio_k=6)
        np.testing.assert_array_equal(batched[qi], single)
        assert sstats.backend == stats.backend == backend


def test_server_search_loop_matches_batched(setup):
    """The acceptance check: looped Server.search (per-query wrapper) ==
    Server.search_batch == the engine's batched path."""
    ds, server, Q, T = setup
    batched, _ = server.search_batch(Q, T, K, ratio_k=6)
    looped = np.stack([server.search(Q[qi], T[qi], K, ratio_k=6)[0]
                       for qi in range(Q.shape[0])])
    np.testing.assert_array_equal(batched, looped)


def test_flat_and_hnsw_agree_on_final_ids(setup):
    """Different filters, same refine: on an easy ratio_k both candidate
    supersets contain the true top-k, so final ids coincide as sets."""
    ds, server, Q, T = setup
    engs = _engines(server)
    flat, _ = engs["flat"].search_batch(Q, T, K, ratio_k=8)
    hnsw, _ = engs["hnsw"].search_batch(Q, T, K, ratio_k=8, ef_search=128)
    agree = np.mean([len(set(a) & set(b)) / K
                     for a, b in zip(flat.tolist(), hnsw.tolist())])
    assert agree >= 0.9, agree


@pytest.mark.parametrize("backend", ["flat", "ivf", "hnsw"])
def test_recall(setup, backend):
    ds, server, Q, T = setup
    eng = _engines(server)[backend]
    ids, _ = eng.search_batch(Q, T, K, ratio_k=8, ef_search=128)
    rec = synth.recall_at_k(ids, ds.gt, K)
    assert rec >= 0.85, (backend, rec)


@pytest.mark.parametrize("backend", ["flat", "ivf", "hnsw"])
def test_stats_populated_and_consistent(setup, backend):
    ds, server, Q, T = setup
    eng = _engines(server)[backend]
    nq = Q.shape[0]
    ids, stats = eng.search_batch(Q, T, K, ratio_k=6)
    assert isinstance(stats, SearchStats)
    assert stats.n_queries == nq and stats.backend == backend
    assert stats.latency_s > 0
    assert stats.filter_dist_evals > 0
    assert stats.refine_comparisons > 0
    assert stats.bytes_up == Q.nbytes + T.nbytes + 4 * nq
    assert stats.bytes_down == ids.nbytes == 8 * ids.size   # int64 ids
    # single-query stats carry the paper's §V-C communication shape
    _, s1 = eng.search(Q[0], T[0], K, ratio_k=6)
    assert s1.bytes_up == 4 * ds.d + 4 * (2 * ds.d + 16) + 4
    assert s1.bytes_down == 8 * K


def test_heap_refine_selects_same_set(setup):
    """Paper heap refine and batched tournament pick the same k ids from
    the same candidates (both exact; order may differ — heap is unordered)."""
    ds, server, Q, T = setup
    for qi in range(3):
        a, _ = server.search(Q[qi], T[qi], K, ratio_k=6, refine="heap")
        b, _ = server.search(Q[qi], T[qi], K, ratio_k=6, refine="tournament")
        assert len(set(a.tolist()) & set(b.tolist())) >= K - 1


def test_filter_only_mode_batched(setup):
    ds, server, Q, T = setup
    eng = _engines(server)["flat"]
    ids, stats = eng.search_batch(Q, T, K, ratio_k=6, refine="none")
    assert ids.shape == (Q.shape[0], K)
    assert stats.refine_comparisons == 0
    # flat filter-only == exact NN on *DCPE ciphertexts*: high recall
    assert synth.recall_at_k(ids, ds.gt, K) >= 0.5


def test_engine_matches_distributed_scan(setup):
    """The engine's flat path and the mesh server compute the same answer
    (same filter math, same shared refine)."""
    from repro.serving.ann_server import DistributedSecureANN
    ds, server, Q, T = setup
    eng = _engines(server)["flat"]
    ids_e, _ = eng.search_batch(Q, T, K, ratio_k=6)
    dist = DistributedSecureANN(np.asarray(server.db.C_sap),
                                np.asarray(server.db.C_dce))
    ids_d = dist.query_batch(Q, T, K, ratio_k=6)
    for a, b in zip(ids_e.tolist(), ids_d.tolist()):
        assert set(a) == set(b)


def test_update_database_after_insert(setup):
    """Engine state refresh mirrors §V-D maintenance: shrinking the
    database re-attaches the backend and the batched path never returns
    ids outside the new database."""
    ds, server, Q, T = setup
    C_sap, C_dce = np.asarray(server.db.C_sap), np.asarray(server.db.C_dce)
    eng = SecureSearchEngine(C_sap, C_dce, backend="flat")
    eng.update_database(C_sap[: ds.n - 1], C_dce[: ds.n - 1])
    ids1, _ = eng.search_batch(Q[:1], T[:1], K)
    assert eng.n == ds.n - 1
    assert (ids1 < ds.n - 1).all()


def test_underfilled_candidates_use_sentinel_not_id_zero():
    """A query with fewer than k real candidates gets -1 fill, never a
    fabricated id 0 (regression: zero-padded cand slots used to leak)."""
    rng = np.random.default_rng(3)
    P = rng.standard_normal((6, 16)).astype(np.float32)   # tiny database
    owner, user, server = ppanns.build_system(P, beta_fraction=0.05, seed=3)
    cq, tq = user.encrypt_query(P[4])
    k = 10                                                # k > n
    ids, _ = server.search(cq, tq, k)
    real = ids[ids >= 0]
    assert len(set(real.tolist())) == len(real) == 6      # all 6, no dupes
    assert (ids[6:] == -1).all()
    ids_f, _ = server.search(cq, tq, k, refine="none")
    assert (ids_f[ids_f >= 0] < 6).all() and (ids_f[6:] == -1).all()
    # same (nq, k) contract for the flat backend and the mesh server
    from repro.serving.ann_server import DistributedSecureANN
    C_sap, C_dce = server.db.C_sap, server.db.C_dce
    flat = SecureSearchEngine(C_sap, C_dce, backend="flat")
    ids2, _ = flat.search(cq, tq, k)
    assert ids2.shape == (k,) and (ids2[6:] == -1).all()
    np.testing.assert_array_equal(ids2[:6], ids[:6])
    dist = DistributedSecureANN(np.asarray(C_sap), np.asarray(C_dce))
    ids3 = dist.query_batch(cq[None], tq[None], k)
    assert ids3.shape == (1, k) and (ids3[0, 6:] == -1).all()
    np.testing.assert_array_equal(ids3[0, :6], ids[:6])


def test_pool_scan_steps_bound_the_gather(monkeypatch):
    """The IVF pool scan gathers a few queries' rows per step when a
    whole batch's gather would pass POOL_GATHER_BYTES; the distances
    and the top-k' they select are those of the one-step scan."""
    import jax.numpy as jnp

    from repro.serving import search_engine as se

    rng = np.random.default_rng(3)
    C = jnp.asarray(rng.standard_normal((500, 16)), jnp.float32)
    Q = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    cand = jnp.asarray(rng.integers(0, 500, (8, 128)), jnp.int32)
    valid = jnp.asarray(rng.random((8, 128)) < 0.9)
    whole = np.asarray(se.pool_dists(C, Q, cand, valid))
    # room for two queries' rows per step: four steps over the batch
    monkeypatch.setattr(se, "POOL_GATHER_BYTES", 2 * 128 * 16 * 4)
    stepped = np.asarray(se.pool_dists(C, Q, cand, valid))
    np.testing.assert_allclose(stepped, whole, rtol=1e-6)
    assert np.array_equal(np.isinf(stepped), ~np.asarray(valid))
    np.testing.assert_array_equal(np.argsort(stepped, axis=1)[:, :20],
                                  np.argsort(whole, axis=1)[:, :20])
