"""Batched lockstep HNSW traversal over the CSR mirror (DESIGN.md §15).

One jitted call expands *all* queries' beams together: upper layers run
a lockstep greedy descent, layer 0 a lockstep best-first beam search —
each hop selects every query's closest unexpanded beam entry, gathers
its fixed-degree neighbor row, scores the edges, and merges into the
beam with one argsort.  Every shape is a function of static buckets
only (row capacity R, beam capacity ef_cap, padded layer count LU), so
bucket growth, tombstones, and varying `ef` never recompile:

  * invalid neighbor slots (`-1` padding) and tombstoned rows ride the
    `ok` validity stream as data — masked to +inf, never a shape;
  * the *effective* ef is a traced scalar: beam slots >= ef are
    re-invalidated after every merge, so results are a pure function of
    `ef` and identical across beam-capacity buckets (which is also what
    makes per-shard traversals mergeable bit-identically);
  * edge scoring is a static `quant` mode: "f32" exact ciphertext
    distances, "int8"/"pq8" the ADC surrogate distances of the existing
    `core.adc` codebooks (rank-equivalent integer forms, DESIGN.md §11).

Equivalence with the host walk (`core.hnsw.HNSW.search`): the host's
candidate heap can only ever expand a node that is within the current
best-ef results (a popped candidate worse than the ef-th best
terminates the layer), so discarding beam entries beyond slot ef loses
nothing; both sides expand the globally closest unexpanded node next,
giving identical expansion order and identical result sets up to
floating-point ties.  tests/test_graph.py pins this parity.

`oblivious=True` is the bounded-hop fixed-fanout variant behind the
`hardened` security profile (DESIGN.md §14/§15): the loop always runs
its static trip count and every step gathers and scores a full
fixed-degree row for every query (post-compute masking), so hop count,
edges scored, and wall-clock are constants of the bucket shapes.
Per-query termination still *latches* identically in both modes — a
finished query's state is frozen, never rewritten — so returned ids
are bit-identical between the perf and oblivious variants (the
cross-profile id-parity contract).  What remains data-dependent is
*which* rows the gathers touch; sec.leakage measures exactly that
residual (the documented intermediate tier — constant volume, not
constant addresses).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["graph_topk", "traverse", "upper_entry", "beam_layer0",
           "beam_plan", "GREEDY_BOUND"]

_INF = jnp.float32(jnp.inf)

# Static trip-count ceiling of each upper layer's greedy descent.  The
# climb strictly improves per step, so real path lengths are O(log n);
# the bound only exists so the oblivious variant has a constant trip
# count (and the while_loop a termination guarantee).
GREEDY_BOUND = 64


def _score(quant: str, db, qd, ids):
    """Edge scores of `ids` (any (nq, W) int32, pre-clamped safe) for
    each query.  f32 uses the host walk's exact formulation
    (sum((x-q)^2)) so the parity suite compares like to like; int8/pq8
    are the ADC surrogates (rank-equivalent, not metric)."""
    if quant == "f32":
        (C,) = db
        rows = jnp.take(C, ids, axis=0)                  # (nq, W, d)
        diff = rows - qd[:, None, :]
        return (diff * diff).sum(-1)
    if quant == "int8":
        c8, cn = db
        rows = jnp.take(c8, ids, axis=0).astype(jnp.float32)
        cross = jnp.einsum("qwd,qd->qw", rows, qd.astype(jnp.float32))
        return jnp.take(cn, ids).astype(jnp.float32) - 2.0 * cross
    if quant == "pq8":
        (codes_t,) = db                                  # (m, R) uint8
        cc = jnp.take(codes_t, ids, axis=1)              # (m, nq, W)
        cc = jnp.transpose(cc, (1, 0, 2)).astype(jnp.int32)
        g = jnp.take_along_axis(qd, cc, axis=2)          # (nq, m, W)
        return g.sum(axis=1)
    raise ValueError(f"unknown edge-scoring mode {quant!r}")


def _climb(rows, ok, db, qd, cur, cur_d, quant: str, oblivious: bool,
           hops, edges):
    """Lockstep greedy descent over one upper layer's (R, M) rows.
    Matches HNSW._greedy: move to the argmin neighbor while it strictly
    improves.  Updates latch per query (frozen once done), so the
    early-exit and fixed-trip variants reach the same state."""
    M = rows.shape[1]

    def step(state):
        t, cur, cur_d, done, hops, edges = state
        nbrs = jnp.take(rows, cur, axis=0)               # (nq, M)
        valid = nbrs >= 0
        safe = jnp.where(valid, nbrs, 0)
        valid = valid & jnp.take(ok, safe)
        d = jnp.where(valid, _score(quant, db, qd, safe), _INF)
        j = jnp.argmin(d, axis=1)
        best = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
        sel = jnp.take_along_axis(safe, j[:, None], axis=1)[:, 0]
        better = (best < cur_d) & ~done
        cur = jnp.where(better, sel, cur)
        cur_d = jnp.where(better, best, cur_d)
        if oblivious:            # constant accounting: every query, full row
            hops = hops + 1
            edges = edges + M
        else:
            hops = hops + (~done).astype(jnp.int32)
            edges = edges + jnp.where(done, 0, valid.sum(axis=1))
        done = done | ~better
        return t + 1, cur, cur_d, done, hops, edges

    nq = cur.shape[0]
    done0 = cur < 0
    state = (jnp.int32(0), jnp.where(done0, 0, cur), cur_d, done0,
             hops, edges)
    if oblivious:
        state = jax.lax.fori_loop(0, GREEDY_BOUND,
                                  lambda _, s: step(s), state)
    else:
        state = jax.lax.while_loop(
            lambda s: (s[0] < GREEDY_BOUND) & jnp.any(~s[3]), step, state)
    _, cur, cur_d, _, hops, edges = state
    return jnp.where(done0, -1, cur), cur_d, hops, edges


def beam_plan(kp: int, ef: int, minimum: int = 32):
    """Static shape plan of one traversal call: (ef_eff, ef_cap,
    max_hops).  ef_cap is the power-of-two beam capacity (results stay
    a pure function of the traced effective ef, so bucket crossings
    change shapes, never ids); max_hops bounds the layer-0 expansion
    count — the host walk expands ~ef nodes, so 4x is generous slack
    (parity tests would catch a premature freeze)."""
    from ..kernels.common import next_bucket
    ef_eff = int(max(kp, ef))
    ef_cap = next_bucket(ef_eff, minimum=minimum)
    return ef_eff, ef_cap, 4 * ef_cap


def upper_entry(neigh_up, ok, db, qd, entry, *, quant: str = "f32",
                oblivious: bool = False):
    """Phase 1: greedy-descend the upper layers, top first, all queries
    in lockstep.  Layers above max_level hold only -1 rows
    (delete-with-repair empties them), so running every padded layer is
    inert, never wrong.  Returns (ep (nq,) int32 layer-0 entry per
    query (-1 if the graph is empty), ep_d (nq,) f32, hops, edges)."""
    nq = qd.shape[0]
    hops = jnp.zeros(nq, jnp.int32)
    edges = jnp.zeros(nq, jnp.int32)
    entry_ok = entry >= 0
    cur = jnp.where(entry_ok, entry, 0) * jnp.ones(nq, jnp.int32)
    cur = jnp.where(entry_ok, cur, -1)
    cur_d = jnp.where(
        entry_ok & jnp.take(ok, jnp.maximum(cur, 0)),
        _score(quant, db, qd, jnp.maximum(cur, 0)[:, None])[:, 0], _INF)
    cur = jnp.where(cur_d < _INF, cur, -1)
    for li in reversed(range(neigh_up.shape[0])):
        cur, cur_d, hops, edges = _climb(
            neigh_up[li], ok, db, qd, cur, cur_d, quant, oblivious,
            hops, edges)
    return cur, cur_d, hops, edges


def beam_layer0(neigh0, ok, db, qd, ep, ep_d, ef, *, kp: int,
                ef_cap: int, max_hops: int, quant: str = "f32",
                oblivious: bool = False, hops=None, edges=None):
    """Phase 2: lockstep best-first beam search over the layer-0 rows,
    starting each query at its descent endpoint ep/ep_d.

    Returns (cand (nq, kp) int32 with -1 fill, cand_d (nq, kp) f32
    (+inf fill), visited (nq, R) bool scan trace, hops, edges).
    """
    if not 1 <= kp <= ef_cap:
        raise ValueError(f"kp={kp} outside [1, ef_cap={ef_cap}]")
    nq = qd.shape[0]
    R = neigh0.shape[0]
    M0 = neigh0.shape[1]
    if hops is None:
        hops = jnp.zeros(nq, jnp.int32)
    if edges is None:
        edges = jnp.zeros(nq, jnp.int32)
    cur, cur_d = ep, ep_d
    ep_ok = cur >= 0
    ep = jnp.where(ep_ok, cur, 0)
    iota_ef = jax.lax.broadcasted_iota(jnp.int32, (nq, ef_cap), 1)
    bd = jnp.where((iota_ef == 0) & ep_ok[:, None], cur_d[:, None], _INF)
    bi = jnp.where((iota_ef == 0) & ep_ok[:, None], ep[:, None], -1)
    bx = ~((iota_ef == 0) & ep_ok[:, None])      # True = expanded/inert
    visited = jnp.zeros((nq, R), bool)
    visited = visited.at[jnp.arange(nq), ep].max(ep_ok)
    done = ~ep_ok
    rows_q = jnp.arange(nq)[:, None]

    def beam_step(state):
        t, bd, bi, bx, visited, done, hops, edges = state
        du = jnp.where(bx, _INF, bd)
        j = jnp.argmin(du, axis=1)
        sel_d = jnp.take_along_axis(du, j[:, None], axis=1)[:, 0]
        sel_i = jnp.take_along_axis(bi, j[:, None], axis=1)[:, 0]
        worst = jnp.take_along_axis(
            bd, jnp.broadcast_to(ef - 1, (nq, 1)), axis=1)[:, 0]
        # host break rule: min unexpanded worse than the ef-th best (or
        # nothing left to expand).  worst==inf while the beam is not
        # full, so the len(result)>=ef clause is implied.
        qdone = jnp.isinf(sel_d) | (sel_d > worst)
        active = ~done & ~qdone

        sel_safe = jnp.where(sel_i >= 0, sel_i, 0)
        nbrs = jnp.take(neigh0, sel_safe, axis=0)        # (nq, M0)
        valid = nbrs >= 0
        safe = jnp.where(valid, nbrs, 0)
        valid = valid & jnp.take(ok, safe)
        seen = jnp.take_along_axis(visited, safe, axis=1)
        fresh = valid & ~seen
        d = jnp.where(fresh, _score(quant, db, qd, safe), _INF)
        visited = visited.at[rows_q, safe].max(fresh & active[:, None])

        bx_sel = bx | (iota_ef == j[:, None])            # mark expanded
        cat_d = jnp.concatenate([bd, d], axis=1)
        cat_i = jnp.concatenate([bi, jnp.where(fresh, safe, -1)], axis=1)
        cat_x = jnp.concatenate([bx_sel, ~fresh], axis=1)
        # partial selection, not a full stable sort: lax.top_k breaks
        # equal keys toward the lower index, which on the negated
        # distances is exactly stable-ascending order — same permutation
        # the host heap induces, ~1.5x cheaper per hop on CPU
        perm = jax.lax.top_k(-cat_d, ef_cap)[1]
        nbd = jnp.take_along_axis(cat_d, perm, axis=1)
        nbi = jnp.take_along_axis(cat_i, perm, axis=1)
        nbx = jnp.take_along_axis(cat_x, perm, axis=1)
        over = iota_ef >= ef          # effective-ef truncation (traced)
        nbd = jnp.where(over, _INF, nbd)
        nbi = jnp.where(over, -1, nbi)
        nbx = nbx | over

        am = active[:, None]
        bd = jnp.where(am, nbd, bd)
        bi = jnp.where(am, nbi, bi)
        bx = jnp.where(am, nbx, bx)
        if oblivious:
            hops = hops + 1
            edges = edges + M0
        else:
            hops = hops + active.astype(jnp.int32)
            edges = edges + jnp.where(active, fresh.sum(axis=1), 0)
        done = done | qdone
        return t + 1, bd, bi, bx, visited, done, hops, edges

    state = (jnp.int32(0), bd, bi, bx, visited, done, hops, edges)
    if oblivious:
        state = jax.lax.fori_loop(0, max_hops,
                                  lambda _, s: beam_step(s), state)
    else:
        state = jax.lax.while_loop(
            lambda s: (s[0] < max_hops) & jnp.any(~s[5]), beam_step, state)
    _, bd, bi, bx, visited, done, hops, edges = state

    cand = bi[:, :kp]
    cand_d = bd[:, :kp]
    return cand, cand_d, visited, hops, edges


def traverse(neigh0, neigh_up, ok, db, qd, entry, ef, *, kp: int,
             ef_cap: int, max_hops: int, quant: str = "f32",
             oblivious: bool = False):
    """The full batched walk (pure function; `graph_topk` is its jitted
    module-level entry point, and the sharded backend calls this per
    shard under shard_map).

    neigh0 (R, M0) / neigh_up (LU, R, M) int32, `-1` padded; ok (R,)
    bool row validity; db the quant-mode scan arrays — ("f32": (C,),
    "int8": (c8, cn), "pq8": (codes_t,)); qd the matching per-query
    operand (Q | q8 | lut); entry/ef traced int32 scalars.

    Returns (cand (nq, kp) int32 with -1 fill, cand_d (nq, kp) f32
    (+inf fill), visited (nq, R) bool scan trace, hops (nq,) int32,
    edges (nq,) int32).
    """
    ep, ep_d, hops, edges = upper_entry(
        neigh_up, ok, db, qd, entry, quant=quant, oblivious=oblivious)
    return beam_layer0(
        neigh0, ok, db, qd, ep, ep_d, ef, kp=kp, ef_cap=ef_cap,
        max_hops=max_hops, quant=quant, oblivious=oblivious,
        hops=hops, edges=edges)


graph_topk = jax.jit(
    traverse,
    static_argnames=("kp", "ef_cap", "max_hops", "quant", "oblivious"))

# Opt-in kernel profiling (repro.obs, DESIGN.md §13): strict
# passthrough unless a KernelProfiler is active; `_cache_size` is
# preserved for the recompile audit.
from ..obs.profiler import instrument as _instrument  # noqa: E402

graph_topk = _instrument("graph.graph_topk", graph_topk)
