"""Partitioned ANNS — the TPU-native realisation of the paper's search layer.

Two-level search (docs/DESIGN.md §2.1): centroid scoring (small matmul) selects
``n_probe`` partitions per query; probed partitions are scored over their
*quantized* rows; exact top-k over the probed candidates. Cost ∝
n_probe·N/K + K instead of N — the paper's sub-linear claim, with every FLOP
on the MXU.

Storage is fixed-shape: (K, cap, d) quantized buckets + (K, cap) ids with -1
sentinels, so search jits once per (K, cap, n_probe, k) and shards cleanly.

Slab layout & the fused kernel. The buckets are one (K, cap, d) int8 slab
with per-row vmin/scale and -1 ids on empty slots (``IVFIndex.slab_view``
flattens it to (K·cap, d) rows: partition ``p`` is [p·cap, (p+1)·cap)).
The probe path never copies the probed partitions: it sorts the call's
(query, probe) pairs by partition and hands the slab itself to the fused
Pallas kernel (``kernels/ivf_topk``), which reads each probed partition's
row blocks in place, once per call, and scores them against every query
that probes it; the affine dequant folds into the scan matmul and each
chunk reduces to one survivor, and an exact rescore of each query's top-k
chunks recovers the exact top-k. ``impl`` selects the path: "kernel" (int8
indexes), "einsum" (the legacy fp32 dequant-then-einsum, kept for 4/16-bit
storage and as the test oracle), or "auto" (kernel whenever bits == 8).
Off-TPU the kernel runs under ``interpret=True``, probed once on the first
kernel call (see ``kernels/ivf_topk/ops._interpret_mode``).

Sharded execution path. ``shard_index`` re-lays the stable slab out as S
per-shard replicas with a leading shard dim: partition ``p``'s capacity slots
are dealt round-robin across shards (slot j -> shard j % S, local slot
j // S), the quantized rows move untouched (same int8 bytes, same per-row
vmin/scale), and the centroids are replicated. Every shard therefore holds
the same K partitions over a 1/S row slice, so a query's probe list —
scored against identical centroids — selects exactly the single-device
candidate set, split S ways. ``search_sharded`` runs the per-shard scan
(kernel or einsum, with the same validity ∧ predicate mask pushdown as
``search``) under ``shard_map`` over the ("pod","data") mesh axes, then
all-gathers the S local top-k lists and merges — bit-identical scores to the
single-device scan at any ``n_probe`` (k ≪ N ⇒ collective-light; ids may
permute only where scores tie exactly).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.shapes import pad_to_chunk
from repro.core import partitioner
from repro.core.graph_store import mask_pass
from repro.core.quantization import QuantizedVectors, quantize
from repro.kernels.ivf_topk.ops import scan_topk_probed
from repro.kernels.ivf_topk.ref import pad_topk


# Partition capacities are whole multiples of the int8 memory tile's rows.
# On TPU a (K, cap, d) int8 slab with cap off that tile is laid out
# slot-major (that layout needs no padding), so no partition's rows would
# be contiguous, and the probe kernel's in-place block reads would cost a
# relayout copy of the whole slab on every call.
SLOT_TILE = 32


def default_capacity(n: int, n_partitions: int) -> int:
    """Slots per partition for n rows: twice the mean fill, plus one, to
    a whole ``SLOT_TILE``."""
    return pad_to_chunk(max(int(2 * n / n_partitions) + 1, 8), SLOT_TILE)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["centroids", "data", "vmin", "scale", "ids", "counts"],
    meta_fields=["bits"],
)
@dataclasses.dataclass
class IVFIndex:
    centroids: jax.Array     # (K, d) fp32
    data: jax.Array          # (K, cap, d) int8 | (K, cap, d//2) int4-packed | bf16
    vmin: jax.Array          # (K, cap) fp32
    scale: jax.Array         # (K, cap) fp32
    ids: jax.Array           # (K, cap) int32, -1 = empty slot
    counts: jax.Array        # (K,) int32
    bits: int = 8

    @property
    def n_partitions(self) -> int:
        return self.centroids.shape[0]

    @property
    def capacity(self) -> int:
        return self.ids.shape[1]

    @property
    def nbytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (self.centroids, self.data, self.vmin, self.scale, self.ids))

    def slab_view(self):
        """Flattened row-major view: (K·cap, d') data, (K·cap,) vmin/scale/ids.

        Partition p occupies the contiguous rows [p·cap, (p+1)·cap), so a
        probe list maps to row blocks the fused kernel consumes directly.
        Reshape-only — no copy, no dequantization."""
        k, cap = self.ids.shape
        return (self.data.reshape(k * cap, -1), self.vmin.reshape(-1),
                self.scale.reshape(-1), self.ids.reshape(-1))

    def _replace(self, **kw) -> "IVFIndex":
        return dataclasses.replace(self, **kw)


def build(key, vectors: jax.Array, ids: jax.Array, *, n_partitions: int,
          capacity: Optional[int] = None, bits: int = 8, kmeans_iters: int = 16,
          centroids: Optional[jax.Array] = None) -> Tuple[IVFIndex, jax.Array]:
    """Builds an IVF index. Returns (index, overflow_mask) — True rows did not
    fit their partition's capacity and belong in the delta store."""
    n, d = vectors.shape
    k = n_partitions
    cap = capacity or default_capacity(n, k)
    if centroids is None:
        st = partitioner.fit(key, vectors, k, kmeans_iters)
        centroids = st.centroids
    a = partitioner.assign(vectors, centroids)                    # (N,)

    onehot = jax.nn.one_hot(a, k, dtype=jnp.int32)                # (N, K)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    keep = pos < cap
    slot = jnp.where(keep, a * cap + pos, k * cap)

    qv = quantize(vectors, bits)
    dstore = jnp.zeros((k * cap + 1,) + qv.data.shape[1:], qv.data.dtype)
    dstore = dstore.at[slot].set(jnp.where(keep[:, None], qv.data, 0))
    vmin = jnp.zeros((k * cap + 1,), jnp.float32).at[slot].set(qv.vmin[:, 0])
    scale = jnp.ones((k * cap + 1,), jnp.float32).at[slot].set(qv.scale[:, 0])
    id_store = jnp.full((k * cap + 1,), -1, jnp.int32)
    id_store = id_store.at[slot].set(jnp.where(keep, ids.astype(jnp.int32), -1))
    counts = jax.ops.segment_sum(keep.astype(jnp.int32), a, num_segments=k)

    idx = IVFIndex(
        centroids=centroids,
        data=dstore[:-1].reshape(k, cap, -1),
        vmin=vmin[:-1].reshape(k, cap),
        scale=scale[:-1].reshape(k, cap),
        ids=id_store[:-1].reshape(k, cap),
        counts=counts,
        bits=bits,
    )
    return idx, ~keep


# ---------------------------------------------------------------------------
# slot-level slab surgery (the maintenance executor's primitives)
# ---------------------------------------------------------------------------
# Maintenance actions (incremental compaction, merge-cold, split-hot — see
# repro/maintenance/executor.py) rewrite bounded sets of slab slots in place
# instead of rebuilding the (K, cap, d) store. Rows always move as their
# stored bytes: identical int8 data + per-row vmin/scale ⇒ identical
# dequantized scores, exactly like ``shard_index``'s re-layout. ``rows`` are
# flat slab indices (partition p's slots are [p·cap, (p+1)·cap), matching
# ``slab_view``). Host-side orchestration — dynamic shapes are fine here.

def _part_slot(index: IVFIndex, rows):
    """Flat slab rows -> (partition, slot) index pairs. The stores are
    indexed as (K, cap, …): on TPU, flattening (K, cap) with cap not a
    multiple of the memory tile is a relayout copy of the whole slab, and
    XLA fuses it into every gather or scatter that reads the flat view."""
    rows = jnp.asarray(rows, jnp.int32)
    return rows // index.capacity, rows % index.capacity


def set_slots(index: IVFIndex, rows, data, vmin, scale, ids) -> IVFIndex:
    """Writes quantized rows (byte-identical) into the given flat slab slots
    and refreshes the per-partition counts."""
    p, c = _part_slot(index, rows)
    new_ids = index.ids.at[p, c].set(jnp.asarray(ids, jnp.int32))
    return index._replace(
        data=index.data.at[p, c].set(data),
        vmin=index.vmin.at[p, c].set(vmin),
        scale=index.scale.at[p, c].set(scale),
        ids=new_ids,
        counts=jnp.sum(new_ids >= 0, axis=1, dtype=jnp.int32))


def clear_slots(index: IVFIndex, rows) -> IVFIndex:
    """Empties the given flat slab slots (-1 id, zero data, unit scale)."""
    rows = jnp.asarray(rows, jnp.int32)
    n = rows.shape[0]
    return set_slots(
        index, rows,
        jnp.zeros((n,) + index.data.shape[2:], index.data.dtype),
        jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        jnp.full((n,), -1, jnp.int32))


def gather_slots(index: IVFIndex, rows):
    """(data, vmin, scale, ids) of the given flat slab slots — the stored
    bytes, ready to be ``set_slots`` elsewhere byte-identically."""
    p, c = _part_slot(index, rows)
    return index.data[p, c], index.vmin[p, c], index.scale[p, c], index.ids[p, c]


def _dequant_rows(index: IVFIndex, rows_data, rows_vmin, rows_scale):
    """rows_data: (..., d') quantized — returns (..., d) fp32."""
    if index.bits == 16:
        return rows_data.astype(jnp.float32)
    if index.bits == 8:
        q = rows_data.astype(jnp.float32) + 128.0
    else:  # 4-bit packed
        u = rows_data.astype(jnp.uint8)
        lo = (u & 0xF).astype(jnp.float32)
        hi = (u >> 4).astype(jnp.float32)
        q = jnp.stack([lo, hi], axis=-1).reshape(*u.shape[:-1], -1)
    return q * rows_scale[..., None] + rows_vmin[..., None]


def _resolve_impl(index: IVFIndex, impl: str) -> str:
    if impl == "auto":
        return "kernel" if index.bits == 8 else "einsum"
    if impl == "kernel" and index.bits != 8:
        raise ValueError(f"kernel probe path needs int8 storage, bits={index.bits}")
    return impl


@functools.partial(jax.jit, static_argnames=("n_probe", "k", "query_block", "impl"))
def search(index: IVFIndex, queries: jax.Array, *, n_probe: int, k: int,
           query_block: int = 64, impl: str = "auto",
           probes: Optional[jax.Array] = None,
           node_pass: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Returns (scores (Q, k), ids (Q, k)) — dot-product similarity, descending.

    impl="kernel" (default for int8) scans the probed partitions in place
    with the fused Pallas kernel, partition-major over the whole call
    (``ops.scan_topk_probed``): int8 rows all the way into the scoring
    matmul, no copy of the probed rows and no fp32 dequant in HBM.
    impl="einsum" is the legacy gather-dequant-einsum path (4/16-bit
    storage, the test oracle); ``query_block`` bounds its per-block
    (qb, P, cap, d) dequant and plays no part in the kernel path.

    probes: optional precomputed (Q, n_probe) partition assignment (the
    facade records workload stats from the same ``assign_topk`` — passing it
    here scores centroids once per query batch instead of twice).

    node_pass: optional (max_id+1,) bool predicate mask over global node
    ids — predicate *pushdown*: excluded rows are folded into the scan's
    validity mask (kernel bias / einsum -inf) before the top-k, so the k
    results all satisfy the predicate with no post-filter recall loss."""
    impl = _resolve_impl(index, impl)
    q = queries.astype(jnp.float32)
    nq = q.shape[0]
    n_probe = min(n_probe, index.n_partitions)
    if probes is None:
        probe, _ = partitioner.assign_topk(q, index.centroids, n_probe)  # (Q, P)
    else:
        probe = probes[:, :n_probe].astype(jnp.int32)

    def _row_valid(bids):
        """Slot occupancy ∧ predicate pushdown (pre-top-k filtering)."""
        if node_pass is not None:
            return mask_pass(node_pass, bids)
        return bids >= 0

    if impl == "kernel":
        # the whole call at once, partition-major: the slab is read in
        # place, each probed partition once per row block
        vals, part, slot = scan_topk_probed(
            q, index.data, index.vmin, index.scale, _row_valid(index.ids),
            probe, k=k)
        ids = jnp.where(slot >= 0, index.ids[part, jnp.maximum(slot, 0)], -1)
        return vals, ids

    qb = min(query_block, nq)
    pad = (-nq) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0)))
    pp = jnp.pad(probe, ((0, pad), (0, 0)))
    nblocks = qp.shape[0] // qb

    def block_einsum(carry, i):
        qs = jax.lax.dynamic_slice_in_dim(qp, i * qb, qb, axis=0)      # (qb, d)
        ps = jax.lax.dynamic_slice_in_dim(pp, i * qb, qb, axis=0)      # (qb, P)
        bdata = index.data[ps]                                          # (qb,P,cap,d')
        bmin = index.vmin[ps]
        bscale = index.scale[ps]
        bids = index.ids[ps]                                            # (qb,P,cap)
        vecs = _dequant_rows(index, bdata, bmin, bscale)                # (qb,P,cap,d)
        scores = jnp.einsum("qd,qpcd->qpc", qs, vecs,
                            precision=jax.lax.Precision.HIGHEST)
        scores = jnp.where(_row_valid(bids), scores, -jnp.inf)
        flat = scores.reshape(qb, -1)
        fids = bids.reshape(qb, -1)
        vals, pos = jax.lax.top_k(flat, min(k, flat.shape[1]))
        ids = jnp.where(jnp.isfinite(vals),
                        jnp.take_along_axis(fids, pos, axis=1), -1)
        return carry, pad_topk(vals, ids, k)

    _, (vals, ids) = jax.lax.scan(block_einsum, None, jnp.arange(nblocks))
    return vals.reshape(-1, k)[:nq], ids.reshape(-1, k)[:nq]


@functools.partial(jax.jit, static_argnames=("k",))
def brute_force(vectors: jax.Array, valid: jax.Array, ids: jax.Array,
                queries: jax.Array, *, k: int):
    """Monolithic-baseline / delta-store scoring: exact matmul + top-k."""
    scores = jnp.matmul(queries.astype(jnp.float32),
                        vectors.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    vals, pos = jax.lax.top_k(scores, min(k, vectors.shape[0]))
    return vals, ids[pos]


def merge_topk(scores_a, ids_a, scores_b, ids_b, k: int):
    """Exact merge of two descending top-k lists (associative — distributed
    tournament merges use this pairwise). Assumes disjoint id sets."""
    s = jnp.concatenate([scores_a, scores_b], axis=-1)
    i = jnp.concatenate([ids_a, ids_b], axis=-1)
    vals, pos = jax.lax.top_k(s, k)
    return vals, jnp.take_along_axis(i, pos, axis=-1)


def dedup_merge_topk(scores_a, ids_a, scores_b, ids_b, k: int):
    """Merge of possibly-overlapping top-k lists: keeps one entry per id
    (progressive rounds re-probe earlier partitions)."""
    s = jnp.concatenate([scores_a, scores_b], axis=-1)
    i = jnp.concatenate([ids_a, ids_b], axis=-1)
    order = jnp.argsort(-s, axis=-1)
    s = jnp.take_along_axis(s, order, axis=-1)
    i = jnp.take_along_axis(i, order, axis=-1)
    # mask entries whose id appeared at any earlier (higher-score) position
    dup = (i[..., :, None] == i[..., None, :])
    earlier = jnp.tril(jnp.ones((s.shape[-1], s.shape[-1]), bool), k=-1)
    is_dup = jnp.any(jnp.logical_and(dup, earlier[None, :, :]), axis=-1)
    s = jnp.where(jnp.logical_or(is_dup, i < 0), -jnp.inf, s)
    vals, pos = jax.lax.top_k(s, k)
    return vals, jnp.take_along_axis(i, pos, axis=-1)


def shard_index(index: IVFIndex, n_shards: int) -> IVFIndex:
    """Re-lays the stable store out for ``n_shards``-way row-parallel search.

    Returns an ``IVFIndex`` whose every leaf carries a leading shard dim
    (S, ...): partition ``p``'s capacity slots are dealt round-robin (slot j
    -> shard j % S, local slot j // S — builds pack live rows into the low
    slots, so live rows spread evenly), the quantized rows are moved without
    re-quantization (identical int8 bytes + per-row vmin/scale ⇒ identical
    dequantized scores), and the centroids are replicated. A probe list
    computed against the (identical) centroids therefore selects exactly the
    single-device candidate set, split S ways — ``search_sharded`` over this
    layout is score-bit-identical to ``search`` at any ``n_probe``.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    k, cap = index.ids.shape
    cap_l = pad_to_chunk(-(-cap // n_shards), SLOT_TILE)
    pad = n_shards * cap_l - cap

    def deal(a, fill):
        if pad:
            widths = [(0, 0)] * a.ndim
            widths[1] = (0, pad)
            a = jnp.pad(a, widths, constant_values=fill)
        # (K, cap_l·S, ...) -> (K, cap_l, S, ...) -> (S, K, cap_l, ...):
        # local slot l of shard s is global slot l·S + s
        a = a.reshape((k, cap_l, n_shards) + a.shape[2:])
        return jnp.moveaxis(a, 2, 0)

    ids = deal(index.ids, -1)
    return IVFIndex(
        centroids=jnp.broadcast_to(index.centroids,
                                   (n_shards,) + index.centroids.shape),
        data=deal(index.data, 0),
        vmin=deal(index.vmin, 0.0),
        scale=deal(index.scale, 1.0),
        ids=ids,
        counts=jnp.sum((ids >= 0).astype(jnp.int32), axis=2),
        bits=index.bits,
    )


def shard_placement(mesh):
    """NamedSharding placing shard_index leaves: leading shard dim over the
    mesh's db axes (sharding/rules.py), everything else replicated."""
    from jax.sharding import NamedSharding
    from repro.sharding.rules import db_axes
    axes = db_axes(mesh)
    spec = axes if len(axes) > 1 else (axes[0] if axes else None)

    def place(a):
        return jax.device_put(
            a, NamedSharding(mesh, P(*((spec,) + (None,) * (a.ndim - 1)))))
    return place


def search_sharded(index: IVFIndex, queries: jax.Array, mesh, *, n_probe: int,
                   k: int, query_block: int = 64, impl: str = "auto",
                   probes: Optional[jax.Array] = None,
                   node_pass: Optional[jax.Array] = None):
    """Distributed search: index leaves carry a leading shard dim (S, ...)
    row-sharded over ("pod","data") (see ``shard_index``); queries (and the
    optional precomputed ``probes`` / ``node_pass`` predicate-or-visibility
    mask) replicated; per-shard local top-k, then all-gather(k) + merge.
    Local ids must already be globally unique (they are global node ids).
    The local scan is ``search`` itself — same kernel/einsum selection, same
    pre-top-k mask pushdown, same -inf/-1 padding semantics — so the merged
    result carries the single-device scores exactly."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bits = index.bits

    have_probes = probes is not None
    have_pass = node_pass is not None

    def local(cent, data, vmin, scale, ids, counts, q, *rest):
        rest = iter(rest)
        pr = next(rest) if have_probes else None
        npass = next(rest) if have_pass else None
        loc = IVFIndex(cent[0], data[0], vmin[0], scale[0], ids[0], counts[0],
                       bits)
        vals, lids = search(loc, q, n_probe=n_probe, k=k,
                            query_block=query_block, impl=impl,
                            probes=pr, node_pass=npass)
        allv = jax.lax.all_gather(vals, data_axes, axis=0, tiled=False)   # (S,Q,k)
        alli = jax.lax.all_gather(lids, data_axes, axis=0, tiled=False)
        ns = allv.shape[0]
        allv = jnp.moveaxis(allv, 0, 1).reshape(q.shape[0], ns * k)
        alli = jnp.moveaxis(alli, 0, 1).reshape(q.shape[0], ns * k)
        mv, pos = jax.lax.top_k(allv, k)
        mi = jnp.take_along_axis(alli, pos, axis=1)
        # shards pad ragged tails with (-inf, -1): never let a pad slot of
        # one shard surface another's id through the merge
        return mv, jnp.where(jnp.isfinite(mv), mi, -1)

    shard_spec = P(data_axes if len(data_axes) > 1 else data_axes[0])
    # shard_map pytrees can't hold None leaves: absent optionals are dropped
    # from the arg list and re-inserted as None inside ``local``
    in_specs = [shard_spec] * 6 + [P(None, None)]
    args = [index.centroids, index.data, index.vmin, index.scale, index.ids,
            index.counts, queries]
    if have_probes:
        in_specs.append(P(None, None))
        args.append(probes)
    if have_pass:
        in_specs.append(P(None))
        args.append(node_pass)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return fn(*args)
