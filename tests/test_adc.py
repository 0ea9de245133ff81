"""Quantized ADC filter path (DESIGN.md §11): codebooks, kernel parity,
engine recall/oversampling, runtime mutation semantics, sharded + ppcol
round trips, and the filter_bytes_scanned accounting."""

import dataclasses
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import adc, dcpe, ppanns
from repro.data import synth
from repro.kernels.adc_topk import adc_topk as adc_kernel
from repro.kernels.adc_topk import ops as adc_ops
from repro.kernels.adc_topk import ref as adc_ref
from repro.kernels.l2_topk import ops as l2_ops
from repro.serving.search_engine import ADCFilter, SecureSearchEngine


def _clustered(n=1200, d=32, n_clusters=8, seed=0, spread=0.15):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, d)) * 3.0
    X = (centers[rng.integers(0, n_clusters, n)]
         + rng.standard_normal((n, d)) * spread)
    return X.astype(np.float32)


@pytest.fixture(scope="module")
def system():
    """Clustered corpus + encrypted system + batch of queries."""
    d, nq = 32, 8
    base = _clustered(n=1500, d=d, seed=0)
    queries = _clustered(n=nq, d=d, seed=1)
    beta = dcpe.suggest_beta(base, fraction=0.03)
    owner = ppanns.DataOwner(d=d, sap_beta=beta, sap_s=1024.0, seed=2)
    C_sap, C_dce = owner.encrypt_vectors(base)
    user = ppanns.User(owner.share_keys(), seed=3)
    enc = [user.encrypt_query(q) for q in queries]
    Q = np.stack([c for c, _ in enc])
    T = np.stack([t for _, t in enc])
    gt = np.asarray(l2_ops.knn(jnp.asarray(queries), jnp.asarray(base),
                               10, use_kernel=False)[1])
    return dict(base=base, C_sap=C_sap, C_dce=C_dce, Q=Q, T=T, gt=gt,
                owner=owner)


# ---------------------------------------------------------------------------
# Codebooks.
# ---------------------------------------------------------------------------

class TestCodebooks:
    def test_sq_roundtrip_error_bounded(self):
        C = _clustered()
        cb = adc.SQCodebook.train(C)
        codes, cn = cb.encode(C)
        assert codes.dtype == np.int8 and cn.dtype == np.int32
        # reconstruction error bounded by half a quantization step
        assert np.abs(cb.decode(codes) - C).max() <= cb.scale * 0.51
        np.testing.assert_array_equal(
            cn, (codes.astype(np.int64) ** 2).sum(1))

    def test_sq_arrays_roundtrip_bit_identical(self):
        cb = adc.SQCodebook.train(_clustered())
        cb2 = adc.SQCodebook.from_arrays(cb.to_arrays())
        np.testing.assert_array_equal(cb.offset, cb2.offset)
        assert cb.scale == cb2.scale and cb.trained_n == cb2.trained_n

    def test_pq_roundtrip_and_arrays(self):
        C = _clustered(d=32)
        cb = adc.PQCodebook.train(C, m=8, seed=0)
        codes = cb.encode(C)
        assert codes.shape == (C.shape[0], 8) and codes.dtype == np.uint8
        # PQ reconstruction is lossy but must beat a null model
        err = ((cb.decode(codes) - C) ** 2).sum(1).mean()
        null = ((C - C.mean(0)) ** 2).sum(1).mean()
        assert err < 0.5 * null
        cb2 = adc.PQCodebook.from_arrays(cb.to_arrays())
        np.testing.assert_array_equal(cb.centroids, cb2.centroids)
        np.testing.assert_array_equal(cb2.encode(C), codes)

    def test_pq_subspaces_divides(self):
        assert adc.pq_subspaces(128, 16) == 16
        assert adc.pq_subspaces(30, 16) == 15
        assert adc.pq_subspaces(7, 16) == 7

    def test_train_codebook_rejects_unknown(self):
        with pytest.raises(ValueError):
            adc.train_codebook(_clustered(), "int4")

    def test_default_refine_ratio(self):
        assert adc.default_refine_ratio(None) == 1.0
        assert adc.default_refine_ratio("pq8") > \
            adc.default_refine_ratio("int8") > 1.0


# ---------------------------------------------------------------------------
# Kernel parity (interpret mode vs oracle).
# ---------------------------------------------------------------------------

class TestKernelParity:
    @pytest.mark.parametrize("n,d,nq,kp", [(300, 24, 3, 20),
                                           (1000, 48, 9, 130)])
    def test_sq_kernel_exact_vs_oracle(self, n, d, nq, kp):
        rng = np.random.default_rng(n)
        C = rng.standard_normal((n, d)).astype(np.float32)
        Q = rng.standard_normal((nq, d)).astype(np.float32)
        cb = adc.SQCodebook.train(C)
        c8, cn = cb.encode(C)
        q8 = cb.encode_query(Q)
        dk, ik = adc_ops.sq_knn(jnp.asarray(q8), jnp.asarray(c8),
                                jnp.asarray(cn), kp, interpret=True,
                                use_kernel=True)
        ik, _ = adc_ops.split_merge_rounds(np.asarray(ik))
        dr, ir = adc_ref.sq_knn(q8, c8, cn, kp)
        # int32 math: the fused kernel is bit-exact against the oracle
        np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
        np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
        # and the XLA fallback ranks identically (small d: the f32
        # surrogate stays integer-exact, kernels/adc_topk/ops.py)
        _, i_f = adc_ops.sq_knn(jnp.asarray(q8), jnp.asarray(c8),
                                jnp.asarray(cn), kp, use_kernel=False)
        i_f, _ = adc_ops.split_merge_rounds(np.asarray(i_f))
        np.testing.assert_array_equal(i_f, np.asarray(ir))

    @pytest.mark.parametrize("n,d,m,kp", [(300, 24, 8, 20),
                                          (700, 32, 16, 130)])
    def test_pq_kernel_vs_oracle(self, n, d, m, kp):
        rng = np.random.default_rng(n)
        C = rng.standard_normal((n, d)).astype(np.float32)
        Q = rng.standard_normal((4, d)).astype(np.float32)
        cb = adc.PQCodebook.train(C, m=m, seed=0)
        codes_t = np.ascontiguousarray(cb.encode(C).T)
        lut = cb.lut(Q)
        dk, ik = adc_ops.pq_knn(jnp.asarray(lut), jnp.asarray(codes_t),
                                kp, interpret=True, use_kernel=True)
        ik, _ = adc_ops.split_merge_rounds(np.asarray(ik))
        dr, ir = adc_ref.pq_knn(lut, codes_t, kp)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
        _, i_f = adc_ops.pq_knn(jnp.asarray(lut), jnp.asarray(codes_t),
                                kp, use_kernel=False)
        i_f, _ = adc_ops.split_merge_rounds(np.asarray(i_f))
        np.testing.assert_array_equal(i_f, np.asarray(ir))

    def test_ok_mask_excludes_rows(self):
        """Invalid rows (tombstones / bucket padding) never rank ahead
        of valid ones — kernel and fallback agree."""
        rng = np.random.default_rng(7)
        C = rng.standard_normal((400, 16)).astype(np.float32)
        Q = rng.standard_normal((3, 16)).astype(np.float32)
        cb = adc.SQCodebook.train(C)
        c8, cn = cb.encode(C)
        q8 = cb.encode_query(Q)
        ok = np.ones(400, np.int32)
        ok[:150] = 0
        for use_kernel in (True, False):
            _, ids = adc_ops.sq_knn(
                jnp.asarray(q8), jnp.asarray(c8), jnp.asarray(cn), 30,
                ok=jnp.asarray(ok), interpret=True, use_kernel=use_kernel)
            ids, _ = adc_ops.split_merge_rounds(np.asarray(ids))
            assert (ids >= 150).all()
        pq = adc.PQCodebook.train(C, m=8, seed=0)
        codes_t = np.ascontiguousarray(pq.encode(C).T)
        for use_kernel in (True, False):
            _, ids = adc_ops.pq_knn(
                jnp.asarray(pq.lut(Q)), jnp.asarray(codes_t), 30,
                ok=jnp.asarray(ok), interpret=True, use_kernel=use_kernel)
            ids, _ = adc_ops.split_merge_rounds(np.asarray(ids))
            assert (ids >= 150).all()

    def test_exhausted_merge_emits_empty_slots_not_duplicates(self):
        """kp' beyond the valid-row count must yield -1 slots, never a
        duplicated alive id (kernel and fallback agree) — the refine
        would otherwise see one row in many candidate slots."""
        rng = np.random.default_rng(21)
        C = rng.standard_normal((40, 16)).astype(np.float32)
        Q = rng.standard_normal((2, 16)).astype(np.float32)
        cb = adc.SQCodebook.train(C)
        c8, cn = cb.encode(C)
        q8 = cb.encode_query(Q)
        ok = np.zeros(40, np.int32)
        ok[:12] = 1                         # only 12 valid rows, kp=30
        for use_kernel in (True, False):
            _, ids = adc_ops.sq_knn(
                jnp.asarray(q8), jnp.asarray(c8), jnp.asarray(cn), 30,
                ok=jnp.asarray(ok), interpret=True, use_kernel=use_kernel)
            ids, _ = adc_ops.split_merge_rounds(np.asarray(ids))
            assert (ids[:, :12] < 12).all() and (ids[:, :12] >= 0).all()
            assert (ids[:, 12:] == -1).all(), use_kernel
            for row in ids:                 # no duplicates among real ids
                real = row[row >= 0]
                assert len(set(real.tolist())) == real.size

    def test_pool_scans_match_dense_ranking(self):
        """Pool-scan results equal a dense oracle restricted to the
        pool."""
        rng = np.random.default_rng(9)
        C = rng.standard_normal((500, 16)).astype(np.float32)
        Q = rng.standard_normal((2, 16)).astype(np.float32)
        cb = adc.SQCodebook.train(C)
        c8, cn = cb.encode(C)
        q8 = cb.encode_query(Q)
        from repro.serving.search_engine import layout_pools
        pools = [rng.choice(500, size=200, replace=False) for _ in range(2)]
        cand, valid = layout_pools(2, pools, 15)
        ids, vout = adc_ops.sq_pool_scan(
            jnp.asarray(c8), jnp.asarray(cn), jnp.asarray(q8),
            jnp.asarray(cand), jnp.asarray(valid), 15)
        ids = np.asarray(ids)
        d_all = adc_ref.sq_dists(q8, c8, cn)
        for qi in range(2):
            pool_d = d_all[qi][pools[qi]]
            expect = pools[qi][np.argsort(pool_d, kind="stable")[:15]]
            np.testing.assert_array_equal(
                np.sort(d_all[qi][ids[qi]]), np.sort(d_all[qi][expect]))


# ---------------------------------------------------------------------------
# Threshold-gated merge: bit parity where ties, arrival order, empty
# slots and tombstoned tiles stress it, and its round counter.
# ---------------------------------------------------------------------------

SQ_BN = adc_kernel.DEFAULT_BLOCK_N_SQ
PQ_BN = adc_kernel.DEFAULT_BLOCK_N_PQ


def _ordered_rows(rng, q, n, d, descending):
    """n int8 rows of pairwise distinct distance to query q, ordered
    far to near (descending) or near to far."""
    pool = rng.integers(-20, 21, (8 * n, d)).astype(np.int8)
    cn = (pool.astype(np.int32) ** 2).sum(1)
    dist = cn - 2 * (pool.astype(np.int32) @ q.astype(np.int32))
    _, first = np.unique(dist, return_index=True)
    assert first.size >= n
    rows = rng.choice(first, n, replace=False)
    rows = rows[np.argsort(dist[rows])]
    return pool[rows[::-1] if descending else rows]


def _sq_case(case, nq=3, seed=0):
    """(q8, c8, cn, ok, kp) of one named stress case over 4 tiles."""
    rng = np.random.default_rng(seed)
    n, d, kp = 4 * SQ_BN, 16, 100
    lo, hi = (-1, 2) if case == "ties" else (-20, 21)
    q8 = rng.integers(lo, hi, (nq, d)).astype(np.int8)
    c8 = rng.integers(lo, hi, (n, d)).astype(np.int8)
    ok = np.ones(n, np.int32)
    if case in ("far_to_near", "near_to_far"):
        c8 = _ordered_rows(rng, q8[0], n, d, case == "far_to_near")
    elif case == "n_below_kp":          # -1 slots past the 100 rows
        c8, ok, kp = c8[:100], ok[:100], 160
    elif case == "tombstone_tiles":     # whole dead tiles between live
        ok[SQ_BN: 3 * SQ_BN] = 0
        ok[rng.random(n) < 0.1] = 0
    elif case == "kp_200":
        kp = 200
    cn = (c8.astype(np.int32) ** 2).sum(1).astype(np.int32)
    return q8, c8, cn, ok, kp


def _sq_gated(q8, c8, cn, ok, kp):
    """Kernel (interpret mode) -> (dists, ids, rounds, full, tiles)."""
    dk, ik = adc_ops.sq_knn(jnp.asarray(q8), jnp.asarray(c8),
                            jnp.asarray(cn), kp, ok=jnp.asarray(ok),
                            interpret=True, use_kernel=True)
    ids, rounds = adc_ops.split_merge_rounds(np.asarray(ik))
    n = c8.shape[0]
    tiles = -(-n // SQ_BN)
    full = adc_ops.merge_rounds_full(n, kp, SQ_BN)
    assert full == 128 * -(-min(kp, n) // 128) * tiles      # K x tiles
    return np.asarray(dk), ids, rounds, full, tiles


class TestGatedMerge:
    @pytest.mark.parametrize("case", [
        "random", "ties", "far_to_near", "near_to_far", "n_below_kp",
        "tombstone_tiles", "kp_200"])
    def test_sq_bit_exact_vs_oracle(self, case):
        q8, c8, cn, ok, kp = _sq_case(case)
        dk, ik, rounds, full, tiles = _sq_gated(q8, c8, cn, ok, kp)
        dr, ir = adc_ref.sq_knn(q8, c8, cn, kp, ok=ok)
        np.testing.assert_array_equal(dk, np.asarray(dr))
        np.testing.assert_array_equal(ik, np.asarray(ir))
        # never more than K + 1 rounds a tile (full = K x tiles)
        assert 0 < rounds <= full + tiles

    @pytest.mark.parametrize("nq", [1, 32, 256])
    def test_sq_bit_exact_at_batch(self, nq):
        q8, c8, cn, ok, kp = _sq_case("random", nq=nq, seed=nq)
        dk, ik, rounds, full, tiles = _sq_gated(q8, c8, cn, ok, kp)
        dr, ir = adc_ref.sq_knn(q8, c8, cn, kp, ok=ok)
        np.testing.assert_array_equal(dk, np.asarray(dr))
        np.testing.assert_array_equal(ik, np.asarray(ir))
        assert 0 < rounds <= full + tiles

    def test_sq_empty_slots_are_minus_one(self):
        q8, c8, cn, ok, kp = _sq_case("n_below_kp")
        dk, ik, _, _, _ = _sq_gated(q8, c8, cn, ok, kp)
        assert (ik[:, 100:] == -1).all()
        assert (dk[:, 100:] == adc_ops.INT_BIG).all()
        assert (np.sort(ik[:, :100], axis=1) == np.arange(100)).all()

    @pytest.mark.parametrize("nq", [1, 5])
    def test_pq_bit_exact_on_tied_sums(self, nq):
        """Small-integer LUT entries: every sum is exact in f32 in any
        order, and thousands of rows tie."""
        rng = np.random.default_rng(nq)
        m, n, kp = 8, 700, 130
        lut = rng.integers(0, 3, (nq, m, 256)).astype(np.float32)
        codes_t = rng.integers(0, 256, (m, n)).astype(np.uint8)
        ok = (rng.random(n) > 0.1).astype(np.int32)
        dk, ik = adc_ops.pq_knn(jnp.asarray(lut), jnp.asarray(codes_t), kp,
                                ok=jnp.asarray(ok), interpret=True,
                                use_kernel=True)
        ids, rounds = adc_ops.split_merge_rounds(np.asarray(ik))
        full = adc_ops.merge_rounds_full(n, kp, PQ_BN)
        dr, ir = adc_ref.pq_knn(lut, codes_t, kp, ok=ok)
        np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
        np.testing.assert_array_equal(ids, np.asarray(ir))
        assert 0 < rounds <= full + -(-n // PQ_BN)

    def test_far_to_near_inserts_k_entries_every_tile(self):
        q8, c8, cn, ok, kp = _sq_case("far_to_near", nq=1)
        _, _, rounds, full, tiles = _sq_gated(q8, c8, cn, ok, kp)
        assert full <= rounds <= full + tiles

    def test_near_to_far_merges_nothing_after_the_first_tile(self):
        q8, c8, cn, ok, kp = _sq_case("near_to_far", nq=1)
        _, _, rounds, full, tiles = _sq_gated(q8, c8, cn, ok, kp)
        assert rounds <= full // tiles + tiles

    @pytest.mark.parametrize("quant", ["int8", "pq8"])
    def test_xla_path_counts_no_rounds(self, quant):
        q8, c8, cn, ok, kp = _sq_case("random")
        if quant == "int8":
            _, ik = adc_ops.sq_knn(jnp.asarray(q8), jnp.asarray(c8),
                                   jnp.asarray(cn), kp, use_kernel=False)
        else:
            lut = np.ones((3, 4, 256), np.float32)
            codes_t = np.zeros((4, c8.shape[0]), np.uint8)
            _, ik = adc_ops.pq_knn(jnp.asarray(lut), jnp.asarray(codes_t),
                                   kp, use_kernel=False)
        ids, rounds = adc_ops.split_merge_rounds(np.asarray(ik))
        assert ids.shape == (3, kp) and rounds is None


# ---------------------------------------------------------------------------
# Engine: ADCFilter + refine.
# ---------------------------------------------------------------------------

class TestEngineADC:
    @pytest.mark.parametrize("quant", ["int8", "pq8"])
    @pytest.mark.parametrize("backend", ["flat", "ivf"])
    def test_recall_after_refine(self, system, quant, backend):
        """The acceptance recall model: ADC filter + exact refine holds
        recall@10 >= 0.95 on clustered data at the default
        refine_ratio."""
        eng = SecureSearchEngine(system["C_sap"], system["C_dce"],
                                 backend=backend, quantization=quant,
                                 seed=4)
        ids, stats = eng.search_batch(system["Q"], system["T"], 10,
                                      ratio_k=8.0)
        rec = synth.recall_at_k(np.asarray(ids), system["gt"], 10)
        assert rec >= 0.95, (quant, backend, rec)
        assert stats.backend == f"adc-{backend}-{quant}"

    def test_quantization_none_is_bit_identical(self, system):
        """quantization=None must leave the PR 4 path untouched."""
        a = SecureSearchEngine(system["C_sap"], system["C_dce"],
                               backend="flat")
        b = SecureSearchEngine(system["C_sap"], system["C_dce"],
                               backend="flat", quantization=None)
        ia, _ = a.search_batch(system["Q"], system["T"], 10)
        ib, _ = b.search_batch(system["Q"], system["T"], 10)
        np.testing.assert_array_equal(ia, ib)

    def test_bytes_scanned_shows_bandwidth_win(self, system):
        n, d = system["C_sap"].shape
        f32 = SecureSearchEngine(system["C_sap"], system["C_dce"],
                                 backend="flat")
        _, s0 = f32.search_batch(system["Q"], system["T"], 10)
        assert s0.filter_bytes_scanned == n * d * 4
        sq = SecureSearchEngine(system["C_sap"], system["C_dce"],
                                backend="flat", quantization="int8")
        _, s1 = sq.search_batch(system["Q"], system["T"], 10)
        assert s1.filter_bytes_scanned == n * (d + 4)
        pq = SecureSearchEngine(system["C_sap"], system["C_dce"],
                                backend="flat", quantization="pq8",
                                pq_m=16)
        _, s2 = pq.search_batch(system["Q"], system["T"], 10)
        assert s2.filter_bytes_scanned == n * 16
        assert s2.filter_bytes_scanned < s1.filter_bytes_scanned \
            < s0.filter_bytes_scanned

    def test_oversampling_ratio(self):
        f = ADCFilter("pq8")
        assert f.oversampled(80) == int(np.ceil(
            80 * adc.DEFAULT_REFINE_RATIO["pq8"]))
        g = ADCFilter("int8", refine_ratio=3.0)
        assert g.oversampled(10) == 30

    def test_engine_rejects_bad_combos(self, system):
        with pytest.raises(ValueError):
            SecureSearchEngine(system["C_sap"], system["C_dce"],
                               backend="hnsw", quantization="int8")
        with pytest.raises(ValueError):
            ADCFilter("int4")
        with pytest.raises(ValueError):
            ADCFilter("int8", kind="hnsw")


# ---------------------------------------------------------------------------
# Satellite: l2_topk merge rework stays exact + recompile-free.
# ---------------------------------------------------------------------------

class TestL2TopkMerge:
    def test_chunked_merge_matches_oracle(self):
        from repro.kernels.l2_topk import ref as l2_ref
        rng = np.random.default_rng(11)
        for n, chunk, k in [(999, 256, 17), (256, 256, 10), (40, 64, 50)]:
            X = rng.standard_normal((n, 24)).astype(np.float32)
            Q = rng.standard_normal((5, 24)).astype(np.float32)
            d1, i1 = l2_ops.knn(jnp.asarray(Q), jnp.asarray(X), k,
                                chunk=chunk, use_kernel=False)
            d2, i2 = l2_ref.knn(jnp.asarray(Q), jnp.asarray(X),
                                min(k, n))
            np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                       rtol=1e-5, atol=1e-4)
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_no_recompile_across_repeat_calls(self):
        """jit_cache_size audit: repeated same-shape scans reuse the one
        executable (the scan-body rework must not leak recompiles)."""
        from repro.serving.runtime.telemetry import jit_cache_size
        rng = np.random.default_rng(12)
        X = jnp.asarray(rng.standard_normal((1000, 24)).astype(np.float32))
        Q = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
        l2_ops.knn(Q, X, 17, chunk=256)             # warm
        c0 = jit_cache_size()
        for _ in range(3):
            l2_ops.knn(Q, X, 17, chunk=256)
        assert jit_cache_size() == c0


# ---------------------------------------------------------------------------
# Runtime: mutation + compaction retrain + persistence.
# ---------------------------------------------------------------------------

def _service_system(n=900, d=24, nq=5, seed=0):
    from repro.api import (DataOwnerClient, IndexSpec, SearchParams,
                           SearchRequest, suggest_beta)
    base = _clustered(n=n, d=d, seed=seed)
    queries = _clustered(n=nq, d=d, seed=seed + 1)
    spec = IndexSpec(tenant="t", name="c", d=d,
                     sap_beta=suggest_beta(base, fraction=0.03),
                     seed=seed + 2)
    owner = DataOwnerClient(spec)
    C_sap, C_dce = owner.encrypt_vectors(base, seed=seed + 3)
    query = owner.query_client(seed=seed + 4).encrypt_queries(queries)
    req = lambda name: SearchRequest(                      # noqa: E731
        tenant="t", collection=name, query=query,
        params=SearchParams(k=10, ratio_k=8.0), coalesce=False)
    return spec, owner, C_sap, C_dce, req


class TestRuntimeADC:
    @pytest.mark.parametrize("quant,backend",
                             [("int8", "flat"), ("pq8", "ivf")])
    def test_mutation_semantics(self, quant, backend):
        from repro.api import SecureAnnService
        spec, owner, C_sap, C_dce, req = _service_system()
        spec = dataclasses.replace(spec, backend=backend,
                                   quantization=quant)
        with SecureAnnService() as svc:
            svc.create_collection(spec)
            svc.insert("t", "c", C_sap, C_dce)
            r0 = svc.submit(req("c"))
            # insert: a near-duplicate of the current best must become
            # visible to the very next search
            best = int(r0.ids[0][0])
            dup_sap, dup_dce = owner.encrypt_vectors(
                np.atleast_2d(np.zeros(spec.d, np.float32)), seed=99)
            rows = svc.insert("t", "c", dup_sap, dup_dce)
            # delete: a returned id must never come back
            svc.delete("t", "c", [best])
            r1 = svc.submit(req("c"))
            assert not any(best in set(row) for row in r1.ids)
            # compact and re-check
            svc.compact("t", "c")
            r2 = svc.submit(req("c"))
            assert not any(best in set(row) for row in r2.ids)

    def test_compaction_retrains_after_doubling(self):
        from repro.api import SecureAnnService
        spec, owner, C_sap, C_dce, req = _service_system(n=300)
        spec = dataclasses.replace(spec, quantization="int8",
                                   compact_every=10 ** 9)
        with SecureAnnService() as svc:
            svc.create_collection(spec)
            svc.insert("t", "c", C_sap, C_dce)
            svc.submit(req("c"))                   # first attach: train
            col = svc.collection("t", "c")
            cb0 = col._backend.adc_codebook
            assert cb0 is not None and cb0.trained_n == 300
            # small growth + compact: reuse (alive count < 2x)
            more = _clustered(n=30, d=spec.d, seed=9) * 5.0
            svc.insert("t", "c", *owner.encrypt_vectors(more, seed=5))
            svc.compact("t", "c")
            svc.submit(req("c"))
            assert col._backend.adc_codebook is cb0
            # double the corpus + compact: retrain (grid must follow
            # the drifted distribution)
            big = _clustered(n=600, d=spec.d, seed=10) * 5.0
            svc.insert("t", "c", *owner.encrypt_vectors(big, seed=6))
            svc.compact("t", "c")
            svc.submit(req("c"))
            cb1 = col._backend.adc_codebook
            assert cb1 is not cb0 and cb1.trained_n > cb0.trained_n

    def test_placeholder_codebook_retrains_on_first_real_rows(self):
        """Searching a fully-tombstoned quantized collection trains a
        degenerate placeholder codebook; the next attach with real rows
        must retrain it (not reuse the zero-spread grid) so recall
        recovers without waiting for a compaction."""
        from repro.api import SecureAnnService
        spec, owner, C_sap, C_dce, req = _service_system(n=200)
        spec = dataclasses.replace(spec, quantization="int8",
                                   compact_every=10 ** 9)
        with SecureAnnService() as svc:
            svc.create_collection(spec)
            first = svc.insert("t", "c", C_sap[:4], C_dce[:4])
            svc.delete("t", "c", first)
            svc.submit(req("c"))            # attach over zero alive rows
            col = svc.collection("t", "c")
            assert col._backend.adc_codebook.trained_n == 0
            svc.insert("t", "c", C_sap[4:], C_dce[4:])
            r = svc.submit(req("c"))        # same main_gen: must retrain
            assert col._backend.adc_codebook.trained_n > 0
            exact = SecureSearchEngine(
                col.store.sap_view, col.store.dce_padded_view,
                backend="flat")
            ids0, _ = exact.search_batch(req("c").query.C_sap,
                                         req("c").query.T, 10)
            overlap = np.mean([
                len(set(a[a >= 0]) & set(b[b >= 0])) / 10
                for a, b in zip(np.asarray(ids0), r.ids)])
            assert overlap >= 0.9, overlap

    @pytest.mark.parametrize("quant,backend",
                             [("int8", "flat"), ("int8", "ivf"),
                              ("pq8", "flat"), ("pq8", "ivf")])
    def test_ppcol_roundtrip_bit_identical(self, quant, backend):
        """save/load: ids bit-identical, codebook and re-derived codes
        bit-identical (the .ppcol contract, DESIGN.md §11)."""
        from repro.api import SecureAnnService
        spec, owner, C_sap, C_dce, req = _service_system()
        spec = dataclasses.replace(spec, backend=backend,
                                   quantization=quant)
        with SecureAnnService() as svc:
            svc.create_collection(spec)
            svc.insert("t", "c", C_sap, C_dce)
            svc.delete("t", "c", [3, 4])
            r0 = svc.submit(req("c"))
            with tempfile.TemporaryDirectory() as td:
                svc.save(td)
                svc2 = SecureAnnService.load(td)
            r1 = svc2.submit(req("c"))
            np.testing.assert_array_equal(r0.ids, r1.ids)
            b0 = svc.collection("t", "c")._backend
            b1 = svc2.collection("t", "c")._backend
            a0, a1 = b0.adc_codebook.to_arrays(), \
                b1.adc_codebook.to_arrays()
            assert set(a0) == set(a1)
            for k in a0:
                np.testing.assert_array_equal(np.asarray(a0[k]),
                                              np.asarray(a1[k]))
            if quant == "int8":
                np.testing.assert_array_equal(np.asarray(b0._adc_c8),
                                              np.asarray(b1._adc_c8))
            else:
                np.testing.assert_array_equal(
                    np.asarray(b0._adc_codes_t),
                    np.asarray(b1._adc_codes_t))
            svc2.close()

    def test_indexspec_quantization_validation(self):
        from repro.api import IndexSpec, WireFormatError  # noqa: F401
        with pytest.raises(ValueError):
            IndexSpec(tenant="t", name="c", d=8, quantization="int4")
        with pytest.raises(ValueError):
            IndexSpec(tenant="t", name="c", d=8, backend="hnsw",
                      quantization="int8")
        with pytest.raises(ValueError):
            IndexSpec(tenant="t", name="c", d=8, refine_ratio=2.0)
        spec = IndexSpec(tenant="t", name="c", d=8, quantization="pq8",
                         refine_ratio=4.0, pq_m=4)
        spec2 = spec.from_bytes(spec.to_bytes())
        assert spec2.quantization == "pq8" and spec2.refine_ratio == 4.0

    def test_searchstats_wire_carries_filter_bytes(self):
        from repro.api import SearchResult, SearchStats
        stats = SearchStats(latency_s=0.1, filter_dist_evals=10,
                            refine_comparisons=2, bytes_up=1,
                            bytes_down=2, filter_bytes_scanned=12345)
        res = SearchResult(ids=np.arange(4)[None], stats=stats)
        back = SearchResult.from_bytes(res.to_bytes())
        assert back.stats.filter_bytes_scanned == 12345
