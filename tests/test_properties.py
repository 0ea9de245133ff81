"""Hypothesis property tests (DCE Theorem 3, DCPE Def. 3, Pallas kernels).

All hypothesis-driven sweeps live in this one module, guarded by
`pytest.importorskip`, so the deterministic tests in test_dce.py /
test_dcpe.py / test_kernels.py still run when `hypothesis` is absent
(it is a dev-only dependency; see requirements-dev.txt).
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import (example, given, settings,  # noqa: E402
                        strategies as st)

import jax.numpy as jnp  # noqa: E402

from repro.core import dce, dcpe  # noqa: E402
from repro.kernels.dce_comp import ops as dce_ops  # noqa: E402
from repro.kernels.dce_comp import ref as dce_ref  # noqa: E402
from repro.kernels.l2_topk import ops as l2_ops  # noqa: E402
from repro.kernels.l2_topk import ref as l2_ref  # noqa: E402


def _exact_sq_dists(P, q):
    return ((P - q) ** 2).sum(-1)


@settings(max_examples=30, deadline=None)
@given(
    d=st.integers(min_value=2, max_value=24),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    scale=st.floats(min_value=0.01, max_value=100.0),
)
def test_dce_property_random_dims_and_scales(d, seed, scale):
    """Hypothesis sweep: arbitrary dims/scales/seeds preserve Theorem 3."""
    rng = np.random.default_rng(seed)
    key = dce.keygen(d, seed=seed)
    P = rng.standard_normal((12, d)) * scale
    q = rng.standard_normal((1, d)) * scale
    C = dce.encrypt(P, key, seed=seed + 1, dtype=np.float64)
    T = dce.trapgen(q, key, seed=seed + 2, dtype=np.float64)
    dist = _exact_sq_dists(P, q[0])
    Z = dce.pairwise_z_matrix(C, T[0])
    true = dist[:, None] - dist[None, :]
    rel = np.abs(true) / (np.abs(dist[:, None]) + np.abs(dist[None, :]) + 1e-30)
    ok = (np.sign(Z) == np.sign(true)) | (rel < 1e-9)
    assert ok.all()


@settings(max_examples=25, deadline=None)
@given(
    d=st.integers(min_value=2, max_value=64),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    beta=st.floats(min_value=0.1, max_value=8.0),
)
def test_dcpe_beta_dcp_property(d, seed, beta):
    """Def. 3: dist(o,q) < dist(p,q) - beta  =>  encrypted comparison agrees
    (metric distances; the +-s*beta/2 sandwich makes this deterministic)."""
    rng = np.random.default_rng(seed)
    key = dcpe.keygen(s=64.0, beta=beta)
    O = rng.standard_normal((30, d)) * 3
    P = rng.standard_normal((30, d)) * 3
    q = rng.standard_normal((1, d)) * 3
    C_O = dcpe.encrypt(O, key, seed=1).astype(np.float64)
    C_P = dcpe.encrypt(P, key, seed=2).astype(np.float64)
    C_q = dcpe.encrypt(q, key, seed=3).astype(np.float64)[0]
    d_o = np.linalg.norm(O - q, axis=1)
    d_p = np.linalg.norm(P - q, axis=1)
    e_o = np.linalg.norm(C_O - C_q, axis=1)
    e_p = np.linalg.norm(C_P - C_q, axis=1)
    sep = d_o < d_p - beta                      # beta-separated pairs
    assert (e_o[sep] < e_p[sep]).all()


@settings(max_examples=15, deadline=None)
@given(
    nq=st.integers(1, 40), n=st.integers(1, 200), d=st.integers(1, 80),
    seed=st.integers(0, 2**31 - 1),
)
def test_l2_kernel_property(nq, n, d, seed):
    rng = np.random.default_rng(seed)
    Q = jnp.asarray(rng.standard_normal((nq, d)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    got = l2_ops.pairwise_sq_dists(Q, X, interpret=True)
    want = l2_ref.pairwise_sq_dists(Q, X)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def _make_cipher(n, d, seed):
    rng = np.random.default_rng(seed)
    key = dce.keygen(d, seed=seed)
    P = rng.standard_normal((n, d))
    q = rng.standard_normal((1, d))
    C = dce.encrypt(P, key, seed=seed + 1)
    T = dce.trapgen(q, key, seed=seed + 2)[0]
    return jnp.asarray(C), jnp.asarray(T)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 80), d=st.integers(2, 48),
       seed=st.integers(0, 2**31 - 1))
def test_z_matrix_property(n, d, seed):
    C, T = _make_cipher(n, d, seed=seed)
    got = dce_ops.z_matrix(C, T, interpret=True)
    want = dce_ref.z_matrix(C, T)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-3 * float(np.abs(want).max() + 1))


# ---------------------------------------------------------------------------
# Quantized ADC filter (DESIGN.md §11).
# ---------------------------------------------------------------------------

from repro.core import adc  # noqa: E402
from repro.kernels.adc_topk import ops as adc_ops  # noqa: E402
from repro.kernels.adc_topk import ref as adc_ref  # noqa: E402


@settings(max_examples=15, deadline=None)
@given(n=st.integers(20, 400), d=st.integers(4, 48),
       kp=st.integers(1, 64), seed=st.integers(0, 2**31 - 1))
@example(n=20, d=8, kp=21, seed=0)          # kp > n: both sides clamp
def test_sq_adc_kernel_property(n, d, kp, seed):
    """Hypothesis sweep: the fused int8 scan is bit-exact against the
    int32 oracle for arbitrary shapes/seeds."""
    rng = np.random.default_rng(seed)
    C = rng.standard_normal((n, d)).astype(np.float32) * 2.0
    Q = rng.standard_normal((3, d)).astype(np.float32) * 2.0
    cb = adc.SQCodebook.train(C)
    c8, cn = cb.encode(C)
    q8 = cb.encode_query(Q)
    dk, ik = adc_ops.sq_knn(jnp.asarray(q8), jnp.asarray(c8),
                            jnp.asarray(cn), kp, interpret=True,
                            use_kernel=True)
    dr, ir = adc_ref.sq_knn(q8, c8, cn, kp)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))


@settings(max_examples=8, deadline=None)
@given(n_clusters=st.integers(4, 12), seed=st.integers(0, 2**31 - 1),
       quant=st.sampled_from(["int8", "pq8"]))
@example(n_clusters=4, seed=0, quant="int8")
def test_adc_filter_recall_property(n_clusters, seed, quant):
    """ADCFilter + exact refine holds recall@k >= 0.95 vs the exact
    engine on synthetic clustered data at the default refine_ratio
    (the ADC recall-oversampling model, core.adc).

    The reference engine refines the same candidate budget the ADC
    engine does (k' * refine_ratio), so the recall gap measures the
    quantization alone.  At k' without the oversampling, few large
    clusters put true neighbours past the f32 filter's k' under DCPE
    noise, and the reference itself misses them."""
    from repro.core import dcpe as dcpe_mod, ppanns
    from repro.serving.search_engine import SecureSearchEngine

    d, n, nq, k = 24, 800, 6, 10
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, d)) * 3.0
    base = (centers[rng.integers(0, n_clusters, n)]
            + rng.standard_normal((n, d)) * 0.2).astype(np.float32)
    queries = (centers[rng.integers(0, n_clusters, nq)]
               + rng.standard_normal((nq, d)) * 0.2).astype(np.float32)
    owner = ppanns.DataOwner(
        d=d, sap_beta=dcpe_mod.suggest_beta(base, fraction=0.03),
        sap_s=1024.0, seed=seed % 1000)
    C_sap, C_dce = owner.encrypt_vectors(base)
    user = ppanns.User(owner.share_keys(), seed=seed % 997)
    enc = [user.encrypt_query(q) for q in queries]
    Q = np.stack([c for c, _ in enc])
    T = np.stack([t for _, t in enc])
    eng = SecureSearchEngine(C_sap, C_dce, backend="flat",
                             quantization=quant, seed=1)
    ids, _ = eng.search_batch(Q, T, k, ratio_k=8.0)
    exact = SecureSearchEngine(C_sap, C_dce, backend="flat")
    ids0, _ = exact.search_batch(
        Q, T, k, ratio_k=8.0 * eng.backend.refine_ratio)
    recall = np.mean([len(set(ids0[i][ids0[i] >= 0])
                          & set(ids[i][ids[i] >= 0])) / k
                      for i in range(nq)])
    assert recall >= 0.95, (quant, recall)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(10, 300), nq=st.integers(1, 6),
       kp=st.integers(1, 40), seed=st.integers(0, 2**31 - 1))
def test_adc_exact_id_parity_when_unquantized(n, nq, kp, seed):
    """quantization=None must stay on the PR 4 f32 path bit-for-bit."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((n, 16)), jnp.float32)
    Q = jnp.asarray(rng.standard_normal((nq, 16)), jnp.float32)
    k = min(kp, n)
    d1, i1 = l2_ops.knn(Q, X, k, chunk=128, use_kernel=False)
    d2, i2 = l2_ref.knn(Q, X, k)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
