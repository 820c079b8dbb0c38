"""Staged executor for compiled query plans.

Each physical stage maps onto the existing jitted primitives — the IVF
probe (`ivf.search` via `delta.search_with_delta`), typed masked traversal
(`traversal.multi_hop_batch`), candidate-sparse fusion
(`index._fuse_candidates` / `fusion.fuse_topk_sparse`) — and threads one
fixed-shape (Q, C) candidate-set state ``(scores, ids)`` between stages:
scores descending, −inf on empty slots, ids −1 there. Stage widths are
static per compiled plan, so chains jit once per plan shape.

This module is also the one execution path behind the facade:
``HMGIIndex.search`` and ``hybrid_search`` compile the equivalent plan and
run it here (``run_seed`` is the former ``search`` body verbatim — probe
assignment, workload recording, predicate pushdown vs the widening
oversample loop)."""
from __future__ import annotations

import functools
import itertools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import delta as delta_mod
from repro.core import graph_store as graph_mod
from repro.core import ivf as ivf_mod
from repro.core import nsw as nsw_mod
from repro.core import traversal as trav_mod
from repro.core.fusion import (FusionWeights, adaptive_weights,
                               fuse_topk_sparse, scatter_sim)
from repro.core.index import _fuse_candidates
from repro.core.partitioner import assign_topk
from repro.common.shapes import pow2_round
from repro.kernels.ivf_topk.ref import pad_topk
from repro.query.planner import (PhysicalPlan, PRescore, PSeed, PSetOp,
                                 PTraverse)

State = Tuple[jax.Array, jax.Array]      # (scores (Q, C), ids (Q, C))

# the ``call`` argument of each ``query.execute`` span: in a profiler
# trace, the spans nested inside it on its thread belong to that call
_CALL_IDS = itertools.count()


def _topk_state(sv: jax.Array, si: jax.Array, k: int) -> State:
    """The one spelling of the candidate-state sort/truncate contract:
    top-k scores descending, ids gathered along, −1 wherever the score is
    −inf (empty slots must never leak a masked id)."""
    vals, pos = jax.lax.top_k(sv, k)
    ids = jnp.take_along_axis(si, pos, axis=1)
    return vals, jnp.where(jnp.isfinite(vals), ids, -1)


# ------------------------------------------------------------------ seed scan
def search_raw(index, m, q: jax.Array, probes, n_probe: int, k: int,
               node_pass=None, impl: str = "auto", sharded=None) -> State:
    """One stable+delta scan round (centroids pre-scored in ``probes``),
    with the optional NSW refine lane (MVCC-visibility- and
    predicate-masked). ``sharded`` (an ivf.shard_index replica) routes the
    stable scan through the row-sharded path — same masks, same probes,
    same merged results, the flops spread over the mesh's db axes."""
    if sharded is not None:
        scores, ids = delta_mod.search_with_delta_sharded(
            sharded, m.delta, q, index.mesh, n_probe=n_probe, k=k,
            rescore_margin=index.cfg.delta_rescore_margin, probes=probes,
            node_pass=node_pass, impl=impl, mvcc_filter=m.has_dead)
    else:
        scores, ids = delta_mod.search_with_delta(
            m.ivf, m.delta, q, n_probe=n_probe, k=k,
            rescore_margin=index.cfg.delta_rescore_margin, probes=probes,
            node_pass=node_pass, impl=impl, mvcc_filter=m.has_dead)
    if index.cfg.use_nsw_refine and m.nsw is not None:
        ns, ni = nsw_mod.search(m.nsw, q, ef=index.cfg.nsw_ef, k=k)
        ni = jnp.where(ni >= 0, m.ids[jnp.clip(ni, 0, m.ids.shape[0] - 1)], -1)
        # the NSW layer indexes ingest-time rows: apply the same MVCC
        # visibility rules as the stable scan (deletes and superseded
        # versions must not resurface through the refine lane) plus the
        # predicate mask
        dead = jnp.logical_or(m.delta.tombstones, m.delta.superseded)
        ok = jnp.logical_and(
            ni >= 0, ~dead[jnp.clip(ni, 0, dead.shape[0] - 1)])
        if node_pass is not None:
            ok = jnp.logical_and(ok, graph_mod.mask_pass(node_pass, ni))
        ns = jnp.where(ok, ns, -jnp.inf)
        ni = jnp.where(ok, ni, -1)
        scores, ids = ivf_mod.dedup_merge_topk(scores, ids, ns, ni, k)
        ids = jnp.where(jnp.isfinite(scores), ids, -1)
    return scores, ids


def run_seed(index, s: PSeed, node_pass) -> State:
    """ANNS seed stage. Unfiltered, or per the compiled filter plan:
    *pushdown* folds the predicate into the scan validity masks pre-top-k;
    *oversample* scans unfiltered at k_scan and widens (doubling, pow2
    jit-stable) until every query has k qualifying survivors — exact at
    full probe either way (the unfiltered top-k_scan is descending, so once
    k rows pass they are the filtered top-k over everything probed)."""
    m = index.modalities[s.modality]
    q = s.query
    n_probe = min(s.n_probe, m.ivf.n_partitions)
    k = s.k
    # the planner's device-layout choice: resolve the row-sharded replica
    # once per seed stage (built lazily, cached until the stable changes)
    sharded = (index._ensure_sharded(s.modality, s.layout.n_shards)
               if s.layout.layout == "sharded" else None)
    # centroids are scored once per batch: the same assignment feeds the
    # workload tracker and (as precomputed probes) every shard's IVF scan
    probes, _ = assign_topk(q, m.ivf.centroids, n_probe)
    if m.workload is not None:
        m.workload.record(obs.to_host(probes, "workload"))
    if node_pass is None:
        return search_raw(index, m, q, probes, n_probe, k, impl=s.impl,
                          sharded=sharded)
    index._metrics["filter_selectivity"] = s.filter_plan.selectivity
    index._metrics["filter_mode"] = s.filter_plan.mode
    if s.filter_plan.mode == "prefilter":
        return search_raw(index, m, q, probes, n_probe, k,
                          node_pass=node_pass, impl=s.impl, sharded=sharded)
    k_max = min(int(m.ids.shape[0]),
                n_probe * m.ivf.capacity + m.delta.ids.shape[0])
    # pow2-round: k_scan is a static jit arg, so raw selectivity-derived
    # widths would recompile the scan pipeline per distinct batch
    k_scan = min(max(k, pow2_round(s.filter_plan.k_scan)), k_max)
    while True:
        sv, si = search_raw(index, m, q, probes, n_probe, k_scan, impl=s.impl,
                            sharded=sharded)
        ok = graph_mod.mask_pass(node_pass, si)
        sv = jnp.where(ok, sv, -jnp.inf)
        if k_scan >= k_max:
            break
        least = jnp.min(jnp.sum(ok, axis=1))
        if int(obs.to_host(least, "oversample")) >= k:
            break
        k_scan = min(2 * k_scan, k_max)
    vals, ids = _topk_state(sv, si, min(k, sv.shape[1]))
    return pad_topk(vals, ids, k)


# ------------------------------------------------------------- traverse+fuse
def run_traverse(index, t: PTraverse, sv: jax.Array, si: jax.Array,
                 node_pass) -> State:
    """h-hop traversal seeded by the current candidate set, fused back into
    the scores (Eq. 3) via the compiled representation: candidate-sparse
    (seeds ∪ frontier) or dense (all N). hops=0 passes the set through."""
    if t.n_hops == 0:
        return sv, si
    cfg = index.cfg
    g = index.graph
    if index.boosted_weights is not None:
        g = g._replace(edge_weight=index.boosted_weights)
    with obs.span("query.traversal") as sp:
        graph_scores = sp.fence(trav_mod.multi_hop_batch(
            g, si, sv, n_hops=t.n_hops, edge_type_mask=t.edge_type_mask,
            node_mask=node_pass, damping=t.damping))                # (Q, N)
    with obs.span("query.fusion") as sp:
        w = (adaptive_weights(sv, base_wv=cfg.w_vector, base_wg=cfg.w_graph)
             if cfg.adaptive_weights else
             FusionWeights(jnp.full((sv.shape[0],), cfg.w_vector),
                           jnp.full((sv.shape[0],), cfg.w_graph)))
        if t.repr == "sparse":
            out = _fuse_candidates(sv, si, graph_scores, w.w_vector,
                                   w.w_graph, k_fuse=t.k_fuse,
                                   frontier=t.frontier, node_pass=node_pass)
        else:
            out = _fuse_dense(sv, si, graph_scores, w.w_vector, w.w_graph,
                              k_fuse=t.k_fuse, node_pass=node_pass)
        return sp.fence(out)


@functools.partial(jax.jit, static_argnames=("k_fuse",))
def _fuse_dense(sv, si, graph_scores, wv, wg, *, k_fuse: int, node_pass=None):
    """Dense fusion representation: one scatter of the candidate sims over
    all N nodes (positions are ids), then Eq. 3 + top-k_fuse. Chosen by the
    planner when the sparse frontier would cover the corpus anyway."""
    sim_full = scatter_sim(graph_scores.shape[1], si, sv)
    valid = (None if node_pass is None else
             jnp.broadcast_to(node_pass[None, :], graph_scores.shape))
    vals, pos = fuse_topk_sparse(sim_full, graph_scores,
                                 FusionWeights(wv, wg), k_fuse, valid=valid)
    return vals, jnp.where(jnp.isfinite(vals), pos, -1)


# --------------------------------------------------------------- cross-modal
def run_rescore(index, r: PRescore, sv: jax.Array, si: jax.Array) -> State:
    m = index.modalities[r.modality]
    # the id->row map only changes when the modality gains new ids — cache
    # it (an O(n_nodes) scatter per query would dwarf the re-score einsum).
    # The build is double-checked under the index's cache lock: concurrent
    # search threads share one published map instead of racing the build.
    rows = index._modality_id_rows(r.modality)
    return _rescore(r.query, m.vectors, rows, m.delta.tombstones,
                    sv, si, jnp.float32(r.weight))


@functools.partial(jax.jit, static_argnames=("n_nodes",))
def _modality_rows(ids: jax.Array, n_nodes: int) -> jax.Array:
    """(n_nodes,) global-id -> row map for one modality (-1 = no embedding)."""
    rows = jnp.full((n_nodes,), -1, jnp.int32)
    return rows.at[jnp.clip(ids, 0, n_nodes - 1)].set(
        jnp.arange(ids.shape[0], dtype=jnp.int32))


@jax.jit
def _rescore(q2, vectors, rows, tombstones, sv, si, weight):
    """new = (1-w)·current + w·sim2 over the fp32 master rows of the second
    modality (latest versions — updates rewrite them in place); candidates
    without an embedding there — never ingested, or deleted (tombstoned
    ids must not contribute their dead vector) — read sim2 = 0.
    Width-preserving, re-sorted descending."""
    rr = rows[jnp.clip(si, 0, rows.shape[0] - 1)]
    present = jnp.logical_and(si >= 0, rr >= 0)
    present = jnp.logical_and(
        present, ~tombstones[jnp.clip(si, 0, tombstones.shape[0] - 1)])
    vecs = vectors[jnp.clip(rr, 0, vectors.shape[0] - 1)]       # (Q, C, d2)
    sim2 = jnp.einsum("qd,qcd->qc", q2, vecs,
                      precision=jax.lax.Precision.HIGHEST)
    sim2 = jnp.where(present, sim2, 0.0)
    new = jnp.where(jnp.isfinite(sv),
                    (1.0 - weight) * sv + weight * sim2, -jnp.inf)
    return _topk_state(new, si, new.shape[1])


# ------------------------------------------------------------------- set ops
def run_setop(index, op: PSetOp) -> State:
    la, li = execute(index, op.left)
    ra, ri = execute(index, op.right)
    return (_union if op.kind == "union" else _intersect)(la, li, ra, ri)


@jax.jit
def _union(sa, ia, sb, ib):
    """ids from either side; duplicate ids keep their higher score."""
    vals, ids = ivf_mod.dedup_merge_topk(sa, ia, sb, ib,
                                         sa.shape[1] + sb.shape[1])
    return vals, jnp.where(jnp.isfinite(vals), ids, -1)


@jax.jit
def _intersect(sa, ia, sb, ib):
    """ids live on both sides; score = mean of the two sides' scores."""
    match = jnp.logical_and(ia[:, :, None] == ib[:, None, :],
                            ia[:, :, None] >= 0)
    match = jnp.logical_and(match, jnp.isfinite(sb)[:, None, :])
    sb_at = jnp.max(jnp.where(match, sb[:, None, :], -jnp.inf), axis=-1)
    both = jnp.logical_and(jnp.isfinite(sa), jnp.isfinite(sb_at))
    s = jnp.where(both, 0.5 * (sa + sb_at), -jnp.inf)
    return _topk_state(s, ia, s.shape[1])


@jax.jit
def _post_filter(sv, si, node_pass):
    """Outer Where over a set-op source: branches fixed their candidate
    sets already, so the merged set is post-filtered (and later stages
    still carry the mask)."""
    ok = graph_mod.mask_pass(node_pass, si)
    return _topk_state(jnp.where(ok, sv, -jnp.inf), si, sv.shape[1])


# ------------------------------------------------------- serving micro-batch
def search_bucketed(index, queries, modality: str, *, k: int,
                    n_probe: Optional[int] = None, where=None,
                    n_hops: int = 0, impl: str = "auto",
                    floor: int = 2) -> Tuple[np.ndarray, np.ndarray]:
    """The cross-request retrieval entry: one ``(B, k)`` jitted call over
    the pow2 bucket ``B = pow2_round(Q, lo=floor)``, rows sliced back to Q.

    Padding replicates row 0 — every per-row computation in the pipeline
    (probe assignment, scan, top-k, traversal, fusion, rescore) is
    row-separable at fixed shape, so pad-row *content* cannot influence a
    real row's result, and bucketing keeps the set of compiled shapes
    O(log max_batch) (HMG102/HMG103 budgets stay flat).

    The floor of 2 is load-bearing for bit-exactness: XLA:CPU specialises
    the Q=1 contraction differently from Q>=2 (last-bit float divergence in
    the fp32 rescore), while every B>=2 bucket computes rows identically.
    With the floor, a request retrieved solo and the same request
    co-batched with 63 others return byte-identical results — the oracle
    contract tests/test_serving_batch.py pins.

    Shared probe work is amortised structurally: ``run_seed`` scores the
    centroids once per batch (one ``assign_topk`` feeds every co-batched
    query's IVF scan), so Q requests pay one probe-assignment pass."""
    q = np.asarray(queries, np.float32)
    if q.ndim == 1:
        q = q[None]
    n_q = q.shape[0]
    bucket = pow2_round(n_q, lo=max(int(floor), 1))
    if bucket != n_q:
        q = np.concatenate(
            [q, np.broadcast_to(q[:1], (bucket - n_q,) + q.shape[1:])])
    if n_hops > 0:
        sv, si = index.hybrid_search(q, modality, k=k, n_hops=n_hops,
                                     n_probe=n_probe, where=where)
    else:
        sv, si = index.search(q, modality, k=k, n_probe=n_probe,
                              where=where, impl=impl)
    sv, si = obs.to_host((sv, si), "result")
    return sv[:n_q], si[:n_q]


# ----------------------------------------------------------------- execution
def run_topk(sv: jax.Array, si: jax.Array, k: int) -> State:
    """Terminal truncation to k (padded with (−inf, −1) past the width)."""
    vals, ids = _topk_state(sv, si, min(k, sv.shape[1]))
    return pad_topk(vals, ids, k)


def execute(index, phys: PhysicalPlan, *, truncate: bool = True) -> State:
    """Runs a compiled plan. truncate=False returns the last stage's full
    candidate set (the facade's rerank lane re-scores it before cutting)."""
    with obs.span("query.execute", call=next(_CALL_IDS)) as root:
        if isinstance(phys.source, PSetOp):
            with obs.span("query.setop") as sp:
                sv, si = sp.fence(run_setop(index, phys.source))
                if phys.node_pass is not None:
                    sv, si = sp.fence(
                        _post_filter(sv, si, phys.node_pass))
        else:
            with obs.span("query.seed_scan") as sp:
                sv, si = sp.fence(
                    run_seed(index, phys.source, phys.node_pass))
        for st in phys.stages:
            if isinstance(st, PTraverse):
                sv, si = run_traverse(index, st, sv, si, phys.node_pass)
            else:
                with obs.span("query.cross_modal") as sp:
                    sv, si = sp.fence(run_rescore(index, st, sv, si))
        if truncate:
            sv, si = run_topk(sv, si, phys.k)
        return root.fence((sv, si))
