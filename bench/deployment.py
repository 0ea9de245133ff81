"""Set-up of one deployment through the program's public roles.

The data owner encrypts the corpus with `DataOwnerClient.encrypt_vectors`
and the service ingests it through `insert` and `compact`, as users do.
Queries are encrypted on the client side under the user's keys, each
request with fresh randomness, before the window opens.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .data import seed_words

TENANT = "bench"
# Rows per call of the client-side query encryption (see
# `encrypt_queries`).
QUERY_CHUNK = 1024


@dataclasses.dataclass
class Deployment:
    svc: object                   # SecureAnnService
    spec: object                  # IndexSpec of the collection
    keys: object                  # the user's keys (owner's key handoff)
    seeds: list                   # derived 31-bit sub-seeds

    @property
    def collection(self):
        return self.svc.collection(self.spec.tenant, self.spec.name)


def index_spec(config: dict, seed: int):
    from repro.api import IndexSpec

    seeds = seed_words(seed, 8)
    spec = IndexSpec(tenant=TENANT, name=config["name"], d=int(config["d"]),
                     sap_beta=float(config["sap_beta"]),
                     seed=seeds[0], **config["index"])
    return spec, seeds


def build(config: dict, base: np.ndarray, seed: int, *, obs=None,
          log=print) -> Deployment:
    """Encrypt `base` as its owner, then create and fill the collection."""
    import time

    from repro.api import DataOwnerClient, SecureAnnService

    spec, seeds = index_spec(config, seed)
    owner = DataOwnerClient(spec)
    t0 = time.perf_counter()
    C_sap, C_dce = owner.encrypt_vectors(base, seed=seeds[1])
    t1 = time.perf_counter()
    svc = SecureAnnService(obs=obs)
    svc.create_collection(spec)
    svc.insert(spec.tenant, spec.name, C_sap, C_dce)
    svc.compact(spec.tenant, spec.name)
    t2 = time.perf_counter()
    log(f"setup encrypt_s={t1 - t0:.3f} ingest_s={t2 - t1:.3f} "
        f"rows={C_sap.shape[0]} dce_bytes={C_dce.nbytes} "
        f"beta={spec.sap_beta:.6f}")
    return Deployment(svc, spec, owner.share_keys(), seeds)


def encrypt_queries(keys, Q: np.ndarray, seed: int):
    """Client-side encryption of many queries: (C_sap (m, d), T (m, D)).

    The same DCPE and DCE trapdoor algorithms the query client runs
    (`repro.core.dcpe.encrypt`, `repro.core.dce.trapgen`), called on
    `QUERY_CHUNK` rows at a time: every row draws its own noise, so no
    two rows share a ciphertext, at a few microseconds a row instead of
    the per-query loop's quarter millisecond."""
    from repro.core import dce, dcpe

    Q = np.asarray(Q, np.float32)
    cs, ts = [], []
    for i in range(0, Q.shape[0], QUERY_CHUNK):
        part = Q[i: i + QUERY_CHUNK]
        s = seed + 2 * (i // QUERY_CHUNK)
        cs.append(dcpe.encrypt(part, keys.sap_key, seed=s))
        ts.append(dce.trapgen(part, keys.dce_key, seed=s + 1))
    if not cs:
        d = Q.shape[1]
        return (np.zeros((0, d), np.float32),
                np.zeros((0, dce.ciphertext_dim(d)), np.float32))
    return np.concatenate(cs), np.concatenate(ts)


def request_maker(dep: Deployment, queries: np.ndarray, plan, params,
                  seed: int):
    """Encrypt every query row of `plan` now; returns make(i), the
    i-th request of the plan."""
    from repro.api import EncryptedQuery, SearchRequest

    nq = plan.nq
    Cq, Tq = encrypt_queries(dep.keys, queries[plan.qrows.ravel()], seed)

    def make(i):
        rows = slice(i * nq, (i + 1) * nq)
        return SearchRequest(
            tenant=dep.spec.tenant, collection=dep.spec.name,
            query=EncryptedQuery(C_sap=Cq[rows], T=Tq[rows]), params=params)

    if plan.kind == "open":           # built ahead: not on the due path
        built = [make(i) for i in range(plan.n_requests)]
        return built.__getitem__
    return make


def warm_up(dep: Deployment, config: dict, traffic: dict, warm_q, warm_t):
    """Compile (or load from the cache) the shapes this cell's traffic
    uses, and no others: every micro-batcher bucket for single-query
    traffic, the one batch shape for batch requests."""
    from repro.api import EncryptedQuery, SearchParams, SearchRequest

    k = int(config["k"])
    ratio_k = float(config["ratio_k"])
    nq = int(traffic["queries_per_request"])
    if nq == 1:
        dep.svc.warmup(dep.spec.tenant, dep.spec.name, k=k, ratio_k=ratio_k)
    req = SearchRequest(tenant=dep.spec.tenant, collection=dep.spec.name,
                        query=EncryptedQuery(C_sap=warm_q[:nq],
                                             T=warm_t[:nq]),
                        params=SearchParams(k=k, ratio_k=ratio_k))
    dep.svc.submit(req)
