"""HMGIIndex — the unified facade (paper Fig. 1): modality-aware partitioned
vector indexes + knowledge-graph store + MVCC delta + hybrid fusion engine +
learned optimisation, behind one ingest/search/update API.

Host-side orchestration (builds, compaction scheduling, plan selection) wraps
jitted device kernels (assignment, IVF scan, traversal, fusion). Ids are
global graph-node ids across all modalities, so vector hits seed traversals
directly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import HMGIConfig
from repro.core import delta as delta_mod
from repro.core import ivf as ivf_mod
from repro.core import nsw as nsw_mod
from repro.core import community as comm_mod
from repro.core import rerank as rerank_mod
from repro.core.cost_model import (CostModel, DeviceLayoutPlan,
                                   plan_device_layout, select_plan)
from repro.core.fusion import FusionWeights, fuse_topk_sparse
from repro.core import graph_store as graph_mod
from repro.core.graph_store import (GraphStore, NodeAttributes,
                                    from_edges as graph_from_edges)
from repro.core.cost_model import plan_maintenance
from repro.core.partitioner import WorkloadStats, assign_with_distance
from repro.core.quantization import AdaptiveQuantPolicy
from repro.maintenance import MaintenanceReport, PartitionStats

# NOTE: repro.query (the declarative engine this facade compiles onto) is
# imported lazily inside methods — repro.query.planner/executor import core
# submodules at module scope, so a top-level import here would cycle.
# repro.maintenance.executor is imported lazily for the same hygiene.


@functools.partial(jax.jit, static_argnames=("k_fuse", "frontier"))
def _fuse_candidates(vs, vi, graph_scores, wv, wg, *, k_fuse: int,
                     frontier: int, node_pass=None):
    """Candidate-sparse fusion stage (Eq. 3): fuse over the union of the
    ANNS seeds ``vi`` and the ``frontier`` strongest traversal nodes instead
    of scattering into a dense (Q, n_nodes) similarity array.

    Exactness: a node outside the union that dense fusion would rank in its
    top-k_fuse has no vector term, so its fused score is monotone in its
    graph mass — but ≥ k_fuse non-seed nodes inside the frontier carry at
    least as much mass (frontier = k_fuse + k_seed ≥ k_fuse + #seeds), so it
    can never displace the union's top-k_fuse. The graph normaliser is the
    frontier's top-1 = the global max. Peak memory is O(Q·C), C = k_seed +
    frontier — independent of n_nodes.

    node_pass: optional (N,) bool predicate mask — excluded nodes are struck
    from both the seed and frontier candidate lanes (the traversal already
    routes no mass through them, but a zero-mass node could otherwise still
    fill a trailing top-k_fuse slot)."""
    # barrier: XLA:CPU otherwise re-materialises the frontier sort inside
    # every consumer fusion of its outputs (~40x fusion-stage slowdown)
    g_vals, g_ids = jax.lax.optimization_barrier(
        jax.lax.top_k(graph_scores, frontier))                    # (Q, F)
    n_nodes = graph_scores.shape[1]
    # drop repeated seed ids (NSW-refine merges can re-surface an IVF hit):
    # keep the first = highest-scored occurrence (the dense scatter's
    # duplicate-write order was unspecified; highest-score is the one
    # deterministic choice that never understates a seed)
    ks = vi.shape[1]
    earlier = jnp.tril(jnp.ones((ks, ks), bool), k=-1)
    seed_dup = jnp.any((vi[:, :, None] == vi[:, None, :]) & earlier[None],
                       axis=-1)                                   # (Q, ks)
    seed_valid = jnp.logical_and(vi >= 0, ~seed_dup)
    front_valid = jnp.ones(g_ids.shape, bool)
    if node_pass is not None:
        seed_valid = jnp.logical_and(seed_valid,
                                     graph_mod.mask_pass(node_pass, vi))
        front_valid = graph_mod.mask_pass(node_pass, g_ids)
    g_at_vi = jnp.take_along_axis(
        graph_scores, jnp.clip(vi, 0, n_nodes - 1).astype(jnp.int32), axis=1)
    # frontier entries already present as seeds fuse through the seed copy
    dup = jnp.any(g_ids[:, :, None] == jnp.where(seed_valid, vi, -2)[:, None, :],
                  axis=-1)                                        # (Q, F)
    cand_ids = jnp.concatenate([jnp.where(seed_valid, vi, -1), g_ids], axis=1)
    cand_sim = jnp.concatenate(
        [jnp.where(seed_valid, vs, -jnp.inf),
         jnp.full_like(g_vals, -jnp.inf)], axis=1)
    cand_graph = jnp.concatenate(
        [jnp.where(seed_valid, g_at_vi, 0.0),
         jnp.where(dup, 0.0, g_vals)], axis=1)
    cand_valid = jnp.concatenate(
        [seed_valid, jnp.logical_and(~dup, front_valid)], axis=1)
    w = FusionWeights(wv, wg)
    fvals, fpos = fuse_topk_sparse(cand_sim, cand_graph, w, k_fuse,
                                   graph_max=g_vals[:, :1], valid=cand_valid)
    fids = jnp.take_along_axis(cand_ids, fpos, axis=1)
    return fvals, fids


@dataclasses.dataclass
class ModalityIndex:
    ivf: ivf_mod.IVFIndex
    delta: delta_mod.DeltaStore
    vectors: jax.Array          # fp32 master copy (compaction + NSW refine)
    ids: jax.Array              # (N,) global node ids
    nsw: Optional[nsw_mod.NSWGraph] = None
    workload: Optional[WorkloadStats] = None
    # write-time per-partition maintenance statistics (heat lives in
    # ``workload``; this adds delta pressure, tombstone ratio, drift) —
    # consumed by cost_model.plan_maintenance via HMGIIndex.maintain
    stats: Optional[PartitionStats] = None
    # True once any delete/update touched this modality: gates the MVCC
    # visibility pushdown in the scan (never reset — conservative; False
    # guarantees no dead row can be visible, so scans skip the mask)
    has_dead: bool = False
    # (n_nodes,) global-id -> row cache for cross-modal re-scoring; rebuilt
    # lazily by the executor, invalidated when ``ids`` gains new entries
    id_rows: Optional[jax.Array] = None
    # row-sharded replica of ``ivf`` (ivf.shard_index layout, leaves placed
    # over the mesh's db axes); built lazily when the device-layout plan
    # says "sharded", dropped whenever the stable store is rebuilt
    ivf_sharded: Optional[ivf_mod.IVFIndex] = None


class HMGIIndex:
    """The Hybrid Multimodal Graph Index.

    Thread-safety contract (docs/DESIGN.md §9): searches are safe from any
    number of threads, concurrently with at most one mutating caller.
    ``_write_lock`` (reentrant) serialises every mutation — insert, delete,
    compact, maintain, repartition, ingest, restore — plus the state_tree
    snapshot, so writers and snapshotters see a consistent index.
    ``_cache_lock`` guards the two lazily-built read-path caches
    (``ModalityIndex.ivf_sharded`` and ``.id_rows``) with double-checked
    locking: searchers never touch ``_write_lock``, and the hot path is
    lock-free once a cache is published. Lock order is
    ``_write_lock -> _cache_lock -> leaf locks`` (obs, WorkloadStats) —
    enforced statically as HMG201-204 and dynamically by tools/racecheck.
    """

    def __init__(self, cfg: HMGIConfig, mesh=None, seed: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.key = jax.random.PRNGKey(seed)
        self._write_lock = threading.RLock()   # serialises mutations
        self._cache_lock = threading.Lock()    # guards lazy read caches
        self.modalities: Dict[str, ModalityIndex] = {}
        self.graph: Optional[GraphStore] = None
        self.attributes: Optional[NodeAttributes] = None
        self.communities: Optional[np.ndarray] = None
        self.boosted_weights: Optional[jax.Array] = None
        self.sparse_docs: Optional[rerank_mod.SparseVectors] = None
        self.cost_model = CostModel(cfg.cost_alpha, cfg.cost_beta, cfg.cost_gamma)
        self.quant_policy = AdaptiveQuantPolicy(cfg.memory_budget_bytes)
        self.n_nodes = 0
        self._metrics: Dict[str, float] = {}
        # monotone mutation stamp: bumped by every change that can alter a
        # search result (insert/delete/compact/applied maintenance/
        # repartition/ingest/restore/attribute or sparse-doc swap). Serving
        # caches key results on it — a stale entry can never be served
        # because its stamp no longer matches. A *no-op* maintenance pass
        # does not bump (the MaintenanceDriver ticks constantly; ticking
        # must not flush hot caches).
        self._version = 0

    @property
    def version(self) -> int:
        """The mutation stamp (see ``__init__``). Read lock-free: a small
        int is published atomically under the GIL, and a reader that sees
        the pre-mutation value merely caches a result that the very next
        stamp check discards — the same conservative direction as missing."""
        return self._version

    def _bump_version(self) -> None:
        self._version += 1

    # ------------------------------------------------------------------ build
    def _split(self):
        self.key, k = jax.random.split(self.key)
        return k

    def ingest(self, embeddings: Dict[str, Tuple[np.ndarray, np.ndarray]],
               n_nodes: int, edges: Optional[Tuple] = None,
               build_nsw: bool = False,
               node_attrs: Optional[Dict[str, np.ndarray]] = None):
        """Builds the index over a multimodal corpus.

        embeddings: modality -> (node_ids (N_m,) int, vectors (N_m, d_m));
        vectors are L2-normalised here (all similarity is dot-product over
        unit vectors). edges: (src, dst[, edge_type[, edge_weight]]) arrays
        over global node ids. node_attrs: column name -> (n_nodes,) int
        values (the WHERE-clause side). Build overflow (rows beyond a
        partition's capacity) is routed to the delta store — grown if
        needed, never dropped — and per-partition maintenance statistics
        are baselined from the build's own assignment."""
        with self._write_lock:
            self._ingest_locked(embeddings, n_nodes, edges, build_nsw,
                                node_attrs)

    def _ingest_locked(self, embeddings, n_nodes, edges, build_nsw,
                       node_attrs):
        self.n_nodes = n_nodes
        for mod, (ids, vecs) in embeddings.items():
            vecs = jnp.asarray(vecs, jnp.float32)
            vecs = vecs / jnp.maximum(
                jnp.linalg.norm(vecs, axis=-1, keepdims=True), 1e-12)
            ids = jnp.asarray(ids, jnp.int32)
            bits = self.quant_policy.choose_bits(
                int(vecs.size * 4), default_bits=self.cfg.quant_bits)
            k = min(self.cfg.n_partitions, vecs.shape[0])
            index, overflow = ivf_mod.build(
                self._split(), vecs, ids, n_partitions=k, bits=bits,
                kmeans_iters=self.cfg.kmeans_iters)
            dstore = delta_mod.init(self.cfg.delta_capacity, vecs.shape[1],
                                    max_ids=max(n_nodes, 1))
            # overflow rows go to the delta store (capacity-bounded build) —
            # grown if needed: build overflow must never be dropped
            n_over = int(jnp.sum(overflow))
            if n_over:
                ov = jnp.where(overflow)[0]
                dstore = delta_mod.insert_grow(dstore, vecs[ov], ids[ov])
            m = ModalityIndex(ivf=index, delta=dstore, vectors=vecs, ids=ids,
                              workload=WorkloadStats(k),
                              stats=PartitionStats.from_build(
                                  vecs, ids, index, max_ids=max(n_nodes, 1)))
            if build_nsw or self.cfg.use_nsw_refine:
                m.nsw = nsw_mod.build(self._split(), vecs,
                                      degree=min(self.cfg.nsw_degree, vecs.shape[0] - 1))
            self.modalities[mod] = m
        if edges is not None:
            src, dst = edges[0], edges[1]
            et = edges[2] if len(edges) > 2 else None
            ew = edges[3] if len(edges) > 3 else None
            self.graph = graph_from_edges(n_nodes, src, dst, et, ew)
            with obs.span("index.communities"):
                self.communities = comm_mod.louvain_one_level(
                    n_nodes, np.asarray(src), np.asarray(dst),
                    np.ones(len(src)) if ew is None else np.asarray(ew))
            self.boosted_weights = comm_mod.community_edge_boost(
                self.graph, self.communities)
        if node_attrs is not None:
            self.set_attributes(node_attrs)
        self._bump_version()

    def set_attributes(self, node_attrs: Dict[str, np.ndarray]):
        """Attach/replace the relational attribute columns (global node id
        keyed; see graph_store.NodeAttributes). Swapping columns changes
        every filtered result, so it bumps the version stamp."""
        with self._write_lock:
            self.attributes = NodeAttributes.from_columns(self.n_nodes,
                                                          node_attrs)
            self._bump_version()

    def set_sparse_docs(self, docs: rerank_mod.SparseVectors):
        with self._write_lock:
            self.sparse_docs = docs
            self._bump_version()

    # ----------------------------------------------------------------- search
    def _norm_queries(self, queries) -> jax.Array:
        q = jnp.asarray(queries, jnp.float32)
        return q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)

    def _node_pass(self, where) -> Optional[jax.Array]:
        """Compiles a where clause against the attribute store -> (N,) bool."""
        if where is None:
            return None
        if self.attributes is None:
            raise ValueError("filtered search needs attributes: call "
                             "set_attributes() or ingest(node_attrs=...)")
        return self.attributes.node_pass(where)

    def device_layout(self, modality: str) -> DeviceLayoutPlan:
        """Where this modality's stable scan runs: row-sharded over the
        mesh's db axes when the quantized slab exceeds
        cfg.shard_device_budget_bytes (cfg.shard_layout forces either way),
        single-device otherwise. No mesh ⇒ always single."""
        from repro.sharding.rules import db_shards
        m = self.modalities[modality]
        force = None if self.cfg.shard_layout == "auto" else self.cfg.shard_layout
        return plan_device_layout(
            int(np.prod(m.ivf.data.shape[:2])), int(m.ivf.data.shape[-1]),
            n_shards=db_shards(self.mesh),
            budget_bytes=self.cfg.shard_device_budget_bytes,
            bytes_per_elem=int(m.ivf.data.dtype.itemsize), force=force)

    def _ensure_sharded(self, modality: str, n_shards: int) -> ivf_mod.IVFIndex:
        """The row-sharded stable replica (built lazily, leaves placed over
        the mesh's db axes; invalidated whenever the stable store changes).

        Double-checked: concurrent searchers must neither observe a
        half-built replica nor build it twice — the build happens once
        under ``_cache_lock`` and is published as a single reference
        assignment; the replica itself is immutable once published."""
        m = self.modalities[modality]
        # staticcheck: disable=HMG201 (double-checked fast path: a published replica is immutable and assigned atomically; a stale None just falls through to the locked build)
        sh = m.ivf_sharded
        if sh is not None and sh.ids.shape[0] == n_shards:
            return sh
        with self._cache_lock:
            sh = m.ivf_sharded
            if sh is None or sh.ids.shape[0] != n_shards:
                sh = ivf_mod.shard_index(m.ivf, n_shards)
                if self.mesh is not None:
                    sh = jax.tree_util.tree_map(
                        ivf_mod.shard_placement(self.mesh), sh)
                m.ivf_sharded = sh
            return sh

    def _modality_id_rows(self, modality: str) -> jax.Array:
        """The (n_nodes,) global-id -> row scatter map for cross-modal
        re-scoring, built lazily once per (modality, corpus-size) and
        shared by every search thread. Same double-checked publication
        protocol as ``_ensure_sharded``; invalidated (under
        ``_cache_lock``) when an insert adds new ids."""
        m = self.modalities[modality]
        # staticcheck: disable=HMG201 (double-checked fast path: a published rows array is immutable and assigned atomically; a stale None just falls through to the locked build)
        rows = m.id_rows
        if rows is not None and rows.shape[0] == self.n_nodes:
            return rows
        with self._cache_lock:
            rows = m.id_rows
            if rows is None or rows.shape[0] != self.n_nodes:
                from repro.query.executor import _modality_rows
                rows = _modality_rows(m.ids, self.n_nodes)
                m.id_rows = rows
            return rows

    def query(self, plan, *, trace: bool = False):
        """Runs a declarative plan (see ``repro.query.Q``): compiles it
        cost-wise against this index (predicate pushdown vs post-filter,
        probe widths, sparse vs dense fusion) and executes it as staged
        jitted primitives. Returns (scores (Q, k), ids (Q, k)); with
        ``trace=True``, (scores, ids, trace) where ``trace.render()`` is
        the per-stage span tree."""
        from repro.query.executor import execute
        from repro.query.planner import compile_plan
        obs.set_sync_spans(self.cfg.obs_sync_spans)
        with self._maybe_trace(trace) as t:
            out = execute(self, compile_plan(self, plan))
        return out + (t,) if trace else out

    @staticmethod
    def _maybe_trace(trace: bool):
        """``obs.trace()`` collector when tracing, else a null context —
        untraced queries skip span-tree assembly entirely (spans still
        feed the registry histograms)."""
        return obs.trace() if trace else contextlib.nullcontext()

    def explain(self, plan) -> str:
        """The compiled physical plan for ``plan``, as a one-line string
        (stage order, widths, filter mode, fusion representation)."""
        from repro.query.planner import compile_plan
        return compile_plan(self, plan).describe()

    def search(self, queries, modality: str, k: Optional[int] = None,
               n_probe: Optional[int] = None, where=None, impl: str = "auto",
               *, trace: bool = False, _node_pass=None):
        """Pure vector search (ANNS on stable index + delta), tombstone-aware.

        A thin wrapper over the query engine: builds the one-stage plan
        ``Q.vector(modality, queries).where(where).topk(k)`` and executes it.

        where: optional relational predicate — a (column, op, value) tuple or
        a list of them (AND), evaluated against the attribute store. The
        selectivity estimator picks the execution strategy per batch:
        *pushdown* (predicate folded into the scan validity masks, pre-top-k)
        when few rows qualify, *oversample-then-post-filter* when most do —
        the post-filter pass doubles its scan width until every query has k
        qualifying candidates (or the probed slabs are exhausted), so at full
        probe both strategies return the brute-force-with-predicate top-k.

        trace: when True, returns (scores, ids, trace) — ``trace.render()``
        prints the per-stage span tree (plan, seed-scan, traversal, ...)."""
        from repro.query.ast import Q
        from repro.query.executor import execute
        from repro.query.planner import compile_plan
        obs.set_sync_spans(self.cfg.obs_sync_spans)
        plan = Q.vector(modality, queries, n_probe=n_probe,
                        impl=impl).where(where)
        with self._maybe_trace(trace) as t:
            phys = compile_plan(self, plan, k=k or self.cfg.top_k,
                                node_pass=_node_pass)
            out = execute(self, phys)
        return out + (t,) if trace else out

    def hybrid_search(self, queries, modality: str, k: Optional[int] = None,
                      n_hops: Optional[int] = None,
                      n_probe: Optional[int] = None,
                      edge_type_mask=None,
                      where=None,
                      min_recall: Optional[float] = None,
                      use_rerank: bool = False,
                      q_terms=None, q_term_weights=None, *,
                      trace: bool = False):
        """The paper's hybrid query (Eq. 3): ANNS seeds -> h-hop traversal ->
        adaptive fusion -> (optional sparse-dense rerank). Returns (scores, ids).

        A thin wrapper over the query engine — it builds and executes
        ``Q.vector(...).where(where).traverse(n_hops, edge_types=...)``
        (fusion representation pinned to the candidate-sparse path), then
        applies the optional rerank lane to the untruncated candidate set.

        where: optional relational predicate (see ``search``). It is enforced
        at every stage: seed search (pushdown or planned oversampling),
        traversal (excluded nodes route no mass — ``frontier_expand``'s node
        mask), and fusion (excluded frontier nodes can't take candidate
        slots) — "nearest neighbors of q WHERE node.attr = v within h hops"
        as one query."""
        from repro.query.ast import Q
        from repro.query.executor import execute
        from repro.query.planner import compile_plan
        assert self.graph is not None, "hybrid_search needs a graph"
        obs.set_sync_spans(self.cfg.obs_sync_spans)
        cfg = self.cfg
        k = k or cfg.top_k
        if min_recall is not None:
            plan = select_plan(self.cost_model,
                               n=int(self.modalities[modality].ids.shape[0]),
                               d=int(self.modalities[modality].vectors.shape[1]),
                               min_recall=min_recall)
            n_probe = plan.n_probe
            n_hops = plan.n_hops
            use_rerank = use_rerank or plan.use_rerank
        n_hops = cfg.max_hops if n_hops is None else n_hops
        q = self._norm_queries(queries)

        with self._maybe_trace(trace) as t:
            plan = (Q.vector(modality, q, n_probe=n_probe)
                    .where(where)
                    .traverse(n_hops, edge_types=edge_type_mask))
            phys = compile_plan(self, plan, k=k, fusion_repr="sparse")
            fvals, fids = execute(self, phys, truncate=False)

            if (n_hops > 0 and use_rerank and self.sparse_docs is not None
                    and q_terms is not None):
                # optional sparse-dense rerank over the full fused set
                with obs.span("query.rescore") as span:
                    ss = rerank_mod.sparse_overlap_scores(
                        self.sparse_docs, q_terms, q_term_weights, fids)
                    fvals, fids = span.fence(
                        rerank_mod.rrf_rerank(fvals, ss, fids, k=k))
                out = (fvals, fids)
            else:
                out = (fvals[:, :k], fids[:, :k])
        return out + (t,) if trace else out

    # ----------------------------------------------------------------- update
    def _record_dead(self, m: ModalityIndex, ids_np: np.ndarray):
        """Maintenance stats: ids whose stable row just became invisible
        (tombstoned or superseded). Counts only freshly dead ids — an id
        already hidden must not inflate the partition's dead counter."""
        if m.stats is None or not ids_np.size:
            return
        tomb = np.asarray(m.delta.tombstones)
        sup = np.asarray(m.delta.superseded)
        c = np.clip(ids_np, 0, tomb.shape[0] - 1)
        m.stats.record_dead(ids_np[~(tomb[c] | sup[c])], m.ivf)

    def insert(self, modality: str, ids, vectors):
        """Insert-or-update a batch.

        ids: (B,) global node ids; vectors: (B, d_m) — L2-normalised here.
        Existing ids are superseded (MVCC update path): the stable row is
        hidden, the fp32 master row is rewritten in place, and the new
        version lands in the delta. When the delta lacks room (or crosses
        the compaction threshold), ``cfg.maint_auto`` routes the work
        through ``maintain`` — bounded incremental drains instead of a
        stop-the-world ``compact`` — growing the delta only if maintenance
        could not free enough slots. Writes are never dropped."""
        with obs.span("index.insert"), self._write_lock:
            self._insert_locked(modality, ids, vectors)

    def _insert_locked(self, modality: str, ids, vectors):
        m = self.modalities[modality]
        v = self._norm_queries(vectors)
        # free delta room BEFORE any visibility change: a forced drain here
        # still sees consistent MVCC state. Draining after supersede() would
        # move the id's *old* delta version into stable and clear its
        # superseded bit — then appending the new version would leave two
        # visible copies (the stale one served from stable).
        if delta_mod.free_slots(m.delta) < v.shape[0]:
            if self.cfg.maint_auto:
                self.maintain(modality,
                              need_rows=v.shape[0] - delta_mod.free_slots(m.delta))
            else:
                self.compact(modality)
        ids32 = jnp.asarray(ids, jnp.int32)
        ids_np = np.asarray(ids32)
        existing_np = np.asarray(m.ids)
        # vectorized id -> row lookup (no host loop over the corpus)
        order = np.argsort(existing_np, kind="stable")
        sorted_ids = existing_np[order]
        pos = np.searchsorted(sorted_ids, ids_np)
        pos_c = np.minimum(pos, max(existing_np.size - 1, 0))
        upd_mask = (sorted_ids[pos_c] == ids_np) if existing_np.size \
            else np.zeros(ids_np.shape, bool)
        if upd_mask.any():
            m.has_dead = True
            self._record_dead(m, ids_np[upd_mask])
            m.delta = delta_mod.supersede(m.delta, ids32[jnp.asarray(upd_mask)])
            rows = order[pos_c[upd_mask]]
            m.vectors = m.vectors.at[jnp.asarray(rows)].set(v[jnp.asarray(upd_mask)])
        if (~upd_mask).any():
            sel = jnp.asarray(~upd_mask)
            m.vectors = jnp.concatenate([m.vectors, v[sel]], axis=0)
            m.ids = jnp.concatenate([m.ids, ids32[sel]])
            with self._cache_lock:
                m.id_rows = None    # new ids -> the row cache is stale
        # never drop writes: insert_grow widens the store if the (already
        # drained, above) delta still lacks room for the batch
        m.delta = delta_mod.insert_grow(m.delta, v, ids32)
        if m.stats is not None:
            a, d2 = assign_with_distance(v, m.ivf.centroids)
            m.stats.record_writes(np.asarray(a), np.asarray(d2))
        if delta_mod.should_compact(m.delta, self.cfg.compact_threshold):
            if self.cfg.maint_auto:
                self.maintain(modality)
            else:
                self.compact(modality)
        self._bump_version()

    def delete(self, modality: str, ids):
        """Tombstones the ids in ``modality`` (O(B) mask writes; the rows
        vanish from every scan path immediately and are physically purged by
        maintenance/compaction). Auto-triggers a maintenance pass so
        hollowed-out partitions eventually merge away."""
        with obs.span("index.delete"), self._write_lock:
            m = self.modalities[modality]
            ids_np = np.asarray(jnp.asarray(ids, jnp.int32))
            self._record_dead(m, ids_np)
            m.has_dead = True
            m.delta = delta_mod.delete(m.delta, jnp.asarray(ids, jnp.int32))
            self._bump_version()
            if self.cfg.maint_auto:
                self.maintain(modality)

    def compact(self, modality: str):
        """Full compaction: merge the whole delta into the stable store in
        one synchronous rebuild (async-vacuum analogue; see core/delta.py).
        The adaptive path (``maintain`` / ``cfg.maint_auto``) drains the
        delta in bounded chunks instead — this remains the one-shot fallback
        and the reference the incremental drain must match."""
        with self._write_lock:
            self._compact_locked(modality)

    def _compact_locked(self, modality: str):
        m = self.modalities[modality]
        m.ivf, m.delta = delta_mod.compact(self._split(), m.ivf, m.delta,
                                           m.vectors, m.ids)
        with self._cache_lock:
            m.ivf_sharded = None  # stable rebuilt -> sharded replica stale
        if m.stats is not None:
            # the rebuild dropped every dead stable row and re-packed slots
            m.stats.dead[:] = 0
            m.stats.invalidate_slab()
        if m.nsw is not None:
            # compaction clears the superseded mask, which is what hid
            # updated rows from the NSW lane — refresh it over the latest
            # vectors or it would serve pre-update similarities again
            m.nsw = nsw_mod.build(
                self._split(), m.vectors,
                degree=min(self.cfg.nsw_degree, m.vectors.shape[0] - 1))
        self._bump_version()

    def maybe_repartition(self, modality: str):
        """Workload-aware online adjustment (paper §3.2), as bounded work.

        When the probe-heat tracker reports imbalance, the hottest
        partition is split in place by the maintenance executor: a local
        K=2 fit over that partition's stored rows, moved byte-identically
        between the hot slab and a freed partition (merging the coldest
        away first when none is parked). Only the hot partition's rows move
        — no full rebuild, and survivors that don't fit anywhere are routed
        to the delta, never dropped. Returns True if a split was applied."""
        from repro.maintenance import executor as maint_exec
        with self._write_lock:
            m = self.modalities[modality]
            if m.workload is None or not m.workload.should_repartition():
                return False
            # a parked partition's pre-merge hits must not win the argmax
            # (its heat is never reset on merge) and suppress the real hot
            # split
            hits = m.workload.hits_snapshot()
            if m.stats is not None:
                hits = np.where(m.stats.parked, -1, hits)
            hot = int(np.argmax(hits))
            res = maint_exec.split_hot(m, self.cfg, self._split(), m.stats,
                                       hot)
            with self._cache_lock:
                m.ivf_sharded = None  # slots moved -> sharded replica stale
            m.workload.reset()
            self._bump_version()
            return bool(res.get("moved", 0))

    def maintain(self, modality: Optional[str] = None,
                 budget: Optional[int] = None, *, need_rows: int = 0):
        """One adaptive-maintenance pass (docs/DESIGN.md §3.4): plan
        cost-worthy actions from the write-time partition statistics and
        apply them as bounded-work steps.

        budget: row budget for this pass (default ``cfg.maint_budget_rows``)
        — the planner picks the best benefit/row actions that fit.
        need_rows: caller must free at least this many delta slots (the
        insert path's never-drop-a-write hook); forces drain chunks ahead
        of the budget.

        Returns the ``MaintenanceReport`` for ``modality`` (or a dict of
        reports over all modalities when ``modality`` is None). The applied
        decision trail is also surfaced in ``metrics()['maintenance']``.

        Obs: the pass's wall time lands in the ``index.maintain`` histogram
        (write-path stall, since maintenance runs inline with mutations);
        each applied action bumps ``maintenance.actions.<kind>`` and its
        moved/drained/reclaimed rows accumulate in
        ``maintenance.rows_moved``."""
        with obs.span("index.maintain"), self._write_lock:
            return self._maintain_locked(modality, budget,
                                         need_rows=need_rows)

    def _maintain_locked(self, modality: Optional[str] = None,
                         budget: Optional[int] = None, *,
                         need_rows: int = 0):
        from repro.maintenance import executor as maint_exec
        cfg = self.cfg
        budget = cfg.maint_budget_rows if budget is None else int(budget)
        if budget <= 0 and need_rows <= 0:
            # an explicit zero budget is "no optional work", not "default"
            return ({m: MaintenanceReport(m) for m in self.modalities}
                    if modality is None else MaintenanceReport(modality))
        reports: Dict[str, MaintenanceReport] = {}
        for mod in ([modality] if modality else list(self.modalities)):
            m = self.modalities[mod]
            if m.stats is None:
                m.stats = PartitionStats.from_build(
                    m.vectors, m.ids, m.ivf,
                    max_ids=int(m.delta.tombstones.shape[0]))
            heat = None if m.workload is None else m.workload.hits_snapshot()
            actions = plan_maintenance(
                m.stats.summarize(m, heat),
                budget_rows=budget,
                chunk=cfg.maint_chunk, need_rows=need_rows,
                delta_pressure=cfg.maint_delta_pressure,
                heat_imbalance=cfg.maint_heat_imbalance,
                split_min_fill=cfg.maint_split_min_fill,
                merge_max_fill=cfg.maint_merge_max_fill,
                drift_threshold=cfg.maint_drift_threshold)
            report = MaintenanceReport(mod)
            cleared = 0
            skip_chunks = False
            for act in actions:
                if act.kind == "compact_chunk" and skip_chunks:
                    continue
                res = maint_exec.apply(m, cfg, self._split(), m.stats, act)
                report.actions.append((act, res))
                obs.counter(f"maintenance.actions.{act.kind}").inc()
                obs.counter("maintenance.rows_moved").inc(
                    res.get("drained", 0) + res.get("moved", 0)
                    + res.get("reclaimed", 0))
                cleared += res.get("cleared_superseded", 0)
                if act.kind == "compact_chunk" and not (
                        res.get("drained", 0) or res.get("reclaimed", 0)):
                    # every target partition is full (or the delta emptied):
                    # further chunks this pass would spin without progress
                    skip_chunks = True
                if res.get("ivf_changed", False):
                    with self._cache_lock:
                        m.ivf_sharded = None  # slots/centroids moved
                    if act.kind == "split_hot" and m.workload is not None:
                        m.workload.reset()
            if cleared and m.nsw is not None:
                # drained updates cleared superseded bits — exactly like a
                # full compaction, the NSW layer must refresh over the
                # latest master rows or it would serve pre-update scores
                m.nsw = nsw_mod.build(
                    self._split(), m.vectors,
                    degree=min(cfg.nsw_degree, m.vectors.shape[0] - 1))
            reports[mod] = report
        trail = "; ".join(r.describe() for r in reports.values()
                          if not r.is_noop)
        if trail:
            # the latest *applied* decision trail (a no-op pass leaves the
            # last real decision visible — that is the interesting one)
            self._metrics["maintenance"] = trail
            # only an *applied* pass can change results: a no-op plan must
            # not invalidate serving caches (the driver ticks constantly)
            self._bump_version()
        return reports[modality] if modality else reports

    # ------------------------------------------------------- durability state
    # The complete durable state, as a flat {key: array} dict + JSON-able
    # structural metadata. This is THE definition of "what must survive a
    # crash" — anything that influences a search result or a future
    # mutation's outcome is here (quantized slabs byte-identical, centroids
    # incl. parked sentinels, delta + staleness bits, graph CSR, attributes,
    # MVCC tombstone/superseded bits, partition stats, workload heat, PRNG
    # key). Derived caches (id_rows, ivf_sharded, _part_of) are excluded:
    # they rebuild lazily and deterministically from this state. Consumed by
    # repro.persistence.snapshot; keep the two restore paths in sync when
    # adding fields.

    def state_tree(self) -> Tuple[Dict[str, object], Dict[str, object]]:
        """Returns ``(tree, meta)``: every durable array keyed by a flat
        path, plus the structural metadata needed to rebuild the facade.
        Host-side numpy leaves (stats, heat) keep their exact dtypes —
        they must round-trip bit-identically, not through jnp's 32-bit
        coercion."""
        with self._write_lock:
            return self._state_tree_locked()

    def _state_tree_locked(self):
        tree: Dict[str, object] = {"key": self.key}
        meta: Dict[str, object] = {
            "n_nodes": int(self.n_nodes),
            "modalities": {},
            "graph": self.graph is not None,
            "communities": self.communities is not None,
            "boosted_weights": self.boosted_weights is not None,
            "attr_columns": None,
            "sparse_docs": self.sparse_docs is not None,
        }
        for mod, m in self.modalities.items():
            p = f"m/{mod}"
            for f in ("centroids", "data", "vmin", "scale", "ids", "counts"):
                tree[f"{p}/ivf/{f}"] = getattr(m.ivf, f)
            for f in delta_mod.DeltaStore._fields:
                tree[f"{p}/delta/{f}"] = getattr(m.delta, f)
            tree[f"{p}/vectors"] = m.vectors
            tree[f"{p}/ids"] = m.ids
            if m.nsw is not None:
                for f in ("vectors", "neighbors", "entry"):
                    tree[f"{p}/nsw/{f}"] = getattr(m.nsw, f)
            if m.workload is not None:
                tree[f"{p}/workload_hits"] = m.workload.hits_snapshot()
            if m.stats is not None:
                st = m.stats
                for f in ("baseline", "drift_sum", "drift_cnt", "dead",
                          "parked"):
                    tree[f"{p}/stats/{f}"] = np.asarray(getattr(st, f))
            meta["modalities"][mod] = {
                "bits": int(m.ivf.bits),
                "has_dead": bool(m.has_dead),
                "nsw": m.nsw is not None,
                "workload": m.workload is not None,
                "stats": m.stats is not None,
                "stats_max_ids": (int(m.stats.max_ids)
                                  if m.stats is not None else 0),
            }
        if self.graph is not None:
            for f in GraphStore._fields:
                tree[f"graph/{f}"] = getattr(self.graph, f)
        if self.communities is not None:
            tree["communities"] = np.asarray(self.communities)
        if self.boosted_weights is not None:
            tree["boosted_weights"] = self.boosted_weights
        if self.attributes is not None:
            tree["attributes/values"] = self.attributes.values
            cols = sorted(self.attributes.columns, key=self.attributes.columns.get)
            meta["attr_columns"] = cols
        if self.sparse_docs is not None:
            tree["sparse/term_ids"] = self.sparse_docs.term_ids
            tree["sparse/term_weights"] = self.sparse_docs.term_weights
        return tree, meta

    def restore_state(self, tree: Dict[str, object],
                      meta: Dict[str, object]) -> None:
        """Rebuilds this (freshly constructed) index from ``state_tree``
        output. Device arrays re-enter via jnp; host-side stat arrays stay
        numpy with their stored dtypes. The result is bit-identical to the
        snapshotted index for every search path."""
        with self._write_lock:
            self._restore_state_locked(tree, meta)

    def _restore_state_locked(self, tree, meta) -> None:
        self.n_nodes = int(meta["n_nodes"])
        self.key = jnp.asarray(np.asarray(tree["key"]))
        self.modalities = {}
        for mod, mm in meta["modalities"].items():
            p = f"m/{mod}"
            ivf = ivf_mod.IVFIndex(
                **{f: jnp.asarray(np.asarray(tree[f"{p}/ivf/{f}"]))
                   for f in ("centroids", "data", "vmin", "scale", "ids",
                             "counts")},
                bits=int(mm["bits"]))
            dstore = delta_mod.DeltaStore(
                **{f: jnp.asarray(np.asarray(tree[f"{p}/delta/{f}"]))
                   for f in delta_mod.DeltaStore._fields})
            m = ModalityIndex(
                ivf=ivf, delta=dstore,
                vectors=jnp.asarray(np.asarray(tree[f"{p}/vectors"])),
                ids=jnp.asarray(np.asarray(tree[f"{p}/ids"])),
                has_dead=bool(mm["has_dead"]))
            if mm["nsw"]:
                m.nsw = nsw_mod.NSWGraph(
                    vectors=jnp.asarray(np.asarray(tree[f"{p}/nsw/vectors"])),
                    neighbors=jnp.asarray(np.asarray(tree[f"{p}/nsw/neighbors"])),
                    entry=jnp.asarray(np.asarray(tree[f"{p}/nsw/entry"])))
            k = ivf.n_partitions
            if mm["workload"]:
                m.workload = WorkloadStats(k)
                m.workload.load_hits(np.asarray(tree[f"{p}/workload_hits"]))
            if mm["stats"]:
                st = PartitionStats(k, int(mm["stats_max_ids"]))
                for f in ("baseline", "drift_sum", "drift_cnt", "dead",
                          "parked"):
                    setattr(st, f, np.asarray(tree[f"{p}/stats/{f}"]).copy())
                m.stats = st
            self.modalities[mod] = m
        self.graph = (GraphStore(
            **{f: jnp.asarray(np.asarray(tree[f"graph/{f}"]))
               for f in GraphStore._fields})
            if meta["graph"] else None)
        self.communities = (np.asarray(tree["communities"]).copy()
                            if meta["communities"] else None)
        self.boosted_weights = (
            jnp.asarray(np.asarray(tree["boosted_weights"]))
            if meta["boosted_weights"] else None)
        if meta["attr_columns"] is not None:
            self.attributes = NodeAttributes(
                {n: i for i, n in enumerate(meta["attr_columns"])},
                jnp.asarray(np.asarray(tree["attributes/values"])))
        else:
            self.attributes = None
        if meta["sparse_docs"]:
            self.sparse_docs = rerank_mod.SparseVectors(
                term_ids=jnp.asarray(np.asarray(tree["sparse/term_ids"])),
                term_weights=jnp.asarray(np.asarray(tree["sparse/term_weights"])))
        else:
            self.sparse_docs = None
        self._bump_version()

    # ------------------------------------------------------------------ stats
    def metrics(self) -> Dict[str, object]:
        """Execution-side observability: filter selectivity/mode recorded by
        the last filtered seed scan, the latest maintenance decision trail
        under ``"maintenance"`` (one line per modality acted on), and the
        process-global obs registry snapshot under ``"obs"`` (counters,
        gauges, histogram summaries with exact p50/p90/p99 — see
        ``repro.obs``)."""
        out = dict(self._metrics)
        out["obs"] = obs.snapshot()
        return out

    def memory_usage(self) -> Dict[str, int]:
        """Bytes per component: one entry per modality's stable slab, one
        per delta store (fp32 master + int8 mirror + dequant terms), the
        graph, and a "total" sum."""
        out = {}
        for mod, m in self.modalities.items():
            out[mod] = m.ivf.nbytes
            out[f"{mod}_delta"] = int(m.delta.vectors.size * 4
                                      + m.delta.qdata.size
                                      + (m.delta.qvmin.size
                                         + m.delta.qscale.size) * 4)
        if self.graph is not None:
            out["graph"] = self.graph.nbytes
        out["total"] = sum(out.values())
        return out
