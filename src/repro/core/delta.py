"""MVCC delta store (paper §3.5): insertions/updates/deletions land in a
fixed-capacity buffer; queries hybridise ANNS-on-stable with a scan-on-delta;
asynchronous compaction merges the delta into the IVF partitions without a
full rebuild.

Versioning: every write bumps ``version`` and stamps the rows it writes with
that counter (``row_version``). Visibility rules per read:
  stable row visible  iff  not tombstoned and not superseded
  delta  row visible  iff  not tombstoned and no newer delta version of the
                           same id exists (latest-version-wins)
``superseded`` marks ids whose latest version lives in the delta (an update =
supersede(old) + insert(new)); the latest-version mask covers the
delta-vs-delta case (insert-then-update before compaction), where a stale
row would otherwise outrank the update purely on score. Compaction folds the
latest versions back into the stable index and clears both — either the full
synchronous ``compact`` or, on the adaptive path, fixed-size incremental
drains (``live_slots`` + ``rebuild_keep``, driven by repro/maintenance).
Readers are wait-free: search takes a consistent (stable, delta) snapshot
pair.

Scan path: rows are quantized to int8 at insert time (mirroring the stable
slab layout), so the delta scan runs through the same fused Pallas kernel as
the IVF probe path — int8 HBM traffic, affine dequant folded into the matmul.
The top (k + margin) quantized survivors are then rescored exactly against
the fp32 master rows (a tiny gather), so results stay brute-force-exact
whenever the margin covers the quantization noise — and always when the
delta holds ≤ k + margin rows.

Predicate pushdown: ``_scan_delta``/``search_with_delta`` take an optional
``node_pass`` (max_ids,) bool mask (see core/graph_store.NodeAttributes) that
is folded into the scan validity mask — filtered queries never spend top-k
slots on excluded rows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ivf as ivf_mod
from repro.core.graph_store import mask_pass
from repro.core.ivf import IVFIndex
from repro.core.quantization import quantize
from repro.kernels.ivf_topk.ops import scan_topk_quantized
from repro.kernels.ivf_topk.ref import pad_topk

# default extra quantized survivors rescored in fp32 before the final top-k
# (HMGIConfig.delta_rescore_margin overrides per index)
_RESCORE_MARGIN = 16


class DeltaStore(NamedTuple):
    vectors: jax.Array      # (cap, d) fp32 — master rows (compaction, rescore)
    qdata: jax.Array        # (cap, d) int8 — kernel-scan mirror (centered)
    qvmin: jax.Array        # (cap,) fp32 — per-row affine dequant terms
    qscale: jax.Array       # (cap,) fp32
    ids: jax.Array          # (cap,) int32, -1 empty
    row_version: jax.Array  # (cap,) int32 — MVCC audit stamp of the writing
                            # insert (visibility itself reads ``stale``)
    stale: jax.Array        # (cap,) bool — a newer delta version of this id
                            # exists (maintained at write time: O(1) to read)
    count: jax.Array        # () int32
    version: jax.Array      # () int32 — MVCC write counter
    tombstones: jax.Array   # (max_ids,) bool — user deletes
    superseded: jax.Array   # (max_ids,) bool — stale stable rows (updates)


def init(capacity: int, dim: int, max_ids: int) -> DeltaStore:
    return DeltaStore(
        vectors=jnp.zeros((capacity, dim), jnp.float32),
        qdata=jnp.zeros((capacity, dim), jnp.int8),
        qvmin=jnp.zeros((capacity,), jnp.float32),
        qscale=jnp.ones((capacity,), jnp.float32),
        ids=jnp.full((capacity,), -1, jnp.int32),
        row_version=jnp.full((capacity,), -1, jnp.int32),
        stale=jnp.zeros((capacity,), bool),
        count=jnp.zeros((), jnp.int32),
        version=jnp.zeros((), jnp.int32),
        tombstones=jnp.zeros((max_ids,), bool),
        superseded=jnp.zeros((max_ids,), bool),
    )


def _clip_ids(delta: DeltaStore, ids):
    return jnp.clip(ids, 0, delta.tombstones.shape[0] - 1)


@jax.jit
def insert(delta: DeltaStore, vecs: jax.Array, new_ids: jax.Array) -> DeltaStore:
    """Appends a batch (drops silently if full — callers grow/compact first,
    see ``free_slots``/``grow``). Rows are quantized here so reads never touch
    fp32 for the scan, and stamped with the current write version so readers
    can mask all but the latest version of an id. Clears tombstones for
    re-inserted ids."""
    cap = delta.vectors.shape[0]
    n = vecs.shape[0]
    base = delta.count
    slots = jnp.clip(base + jnp.arange(n), 0, cap - 1)
    fits = (base + jnp.arange(n)) < cap
    v32 = vecs.astype(jnp.float32)
    qv = quantize(v32, 8)
    vectors = delta.vectors.at[slots].set(
        jnp.where(fits[:, None], v32, delta.vectors[slots]))
    qdata = delta.qdata.at[slots].set(
        jnp.where(fits[:, None], qv.data, delta.qdata[slots]))
    qvmin = delta.qvmin.at[slots].set(
        jnp.where(fits, qv.vmin[:, 0], delta.qvmin[slots]))
    qscale = delta.qscale.at[slots].set(
        jnp.where(fits, qv.scale[:, 0], delta.qscale[slots]))
    ids = delta.ids.at[slots].set(jnp.where(fits, new_ids.astype(jnp.int32),
                                            delta.ids[slots]))
    rv = delta.row_version.at[slots].set(
        jnp.where(fits, delta.version, delta.row_version[slots]))
    # latest-version-wins, maintained at write time (reads pay nothing):
    # existing rows sharing an id with an *actually written* batch row go
    # stale, as does any batch row with a later same-id row in the batch.
    # Sort-based — O((cap+n)·log n), no (cap, n) or (n, n) intermediates
    # (bulk overflow batches can be large).
    ids_eff = jnp.where(fits, new_ids.astype(jnp.int32), -2)
    sb = jnp.sort(ids_eff)
    pos = jnp.clip(jnp.searchsorted(sb, delta.ids), 0, n - 1)
    hit_old = jnp.logical_and(sb[pos] == delta.ids, delta.ids >= 0)
    stale = jnp.logical_or(delta.stale, hit_old)
    # stable argsort keeps batch order within equal ids: a sorted element
    # followed by its own id is not the last (newest) version
    order = jnp.argsort(ids_eff, stable=True)
    not_last = jnp.concatenate(
        [ids_eff[order][:-1] == ids_eff[order][1:], jnp.zeros((1,), bool)])
    batch_stale = jnp.zeros((n,), bool).at[order].set(not_last)
    stale = stale.at[slots].set(jnp.where(fits, batch_stale, stale[slots]))
    ts = delta.tombstones.at[_clip_ids(delta, new_ids)].set(False)
    return DeltaStore(vectors, qdata, qvmin, qscale, ids, rv, stale,
                      base + jnp.sum(fits.astype(jnp.int32)),
                      delta.version + 1, ts, delta.superseded)


@jax.jit
def supersede(delta: DeltaStore, old_ids: jax.Array) -> DeltaStore:
    """Marks stable rows stale (the update path: supersede + insert)."""
    sp = delta.superseded.at[_clip_ids(delta, old_ids)].set(True)
    return delta._replace(superseded=sp, version=delta.version + 1)


@jax.jit
def delete(delta: DeltaStore, dead_ids: jax.Array) -> DeltaStore:
    ts = delta.tombstones.at[_clip_ids(delta, dead_ids)].set(True)
    return delta._replace(tombstones=ts, version=delta.version + 1)


def free_slots(delta: DeltaStore) -> int:
    return int(delta.vectors.shape[0] - delta.count)


def insert_grow(delta: DeltaStore, vecs: jax.Array,
                new_ids: jax.Array) -> DeltaStore:
    """Host-side insert that never drops rows: grows the store first when
    the batch exceeds the free slots (2x headroom so the result isn't born
    at the compaction threshold). The one spelling of the overflow-routing
    idiom shared by ingest, compaction, repartitioning, and facade inserts."""
    n = int(vecs.shape[0])
    if free_slots(delta) < n:
        delta = grow(delta, int(delta.count) + 2 * n + 1)
    return insert(delta, vecs, new_ids)


def grow(delta: DeltaStore, min_capacity: int) -> DeltaStore:
    """Host-side capacity growth (copy into a larger store). Used when an
    overflow batch (compaction / repartition) exceeds the remaining slots —
    rows must never be dropped silently. Doubles to amortise re-jits."""
    cap = delta.vectors.shape[0]
    if min_capacity <= cap:
        return delta
    new_cap = cap
    while new_cap < min_capacity:
        new_cap *= 2
    pad = new_cap - cap
    return delta._replace(
        vectors=jnp.pad(delta.vectors, ((0, pad), (0, 0))),
        qdata=jnp.pad(delta.qdata, ((0, pad), (0, 0))),
        qvmin=jnp.pad(delta.qvmin, (0, pad)),
        qscale=jnp.pad(delta.qscale, (0, pad), constant_values=1.0),
        ids=jnp.pad(delta.ids, (0, pad), constant_values=-1),
        row_version=jnp.pad(delta.row_version, (0, pad), constant_values=-1),
        stale=jnp.pad(delta.stale, (0, pad)),
    )


def _latest_version_mask(delta: DeltaStore) -> jax.Array:
    """(cap,) bool: True where the row is the newest delta version of its id.

    The delta can hold several live versions of one id (insert-then-update
    before compaction); score-based dedup would happily return the stale
    vector. ``insert`` maintains the staleness bit at write time (slots are
    append-only, so it marks prior same-id rows — and earlier same-id rows
    of its own batch — as superseded), which keeps this read-side mask O(cap)
    regardless of corpus size."""
    return jnp.logical_and(delta.ids >= 0, ~delta.stale)


@functools.partial(jax.jit, static_argnames=("k", "margin"))
def _scan_delta(delta: DeltaStore, queries: jax.Array, *, k: int,
                margin: int = _RESCORE_MARGIN,
                node_pass: Optional[jax.Array] = None):
    """Kernel scan over the quantized delta rows + exact fp32 rescore of the
    top (k + margin) survivors. chunk=1 makes the survivor ordering exact
    over quantized scores (the delta is small; its scan output is tiny).
    Results match brute force exactly whenever the delta holds ≤ k + margin
    live rows, and up to int8 ordering error at the survivor boundary
    otherwise — raise ``margin`` (cfg.delta_rescore_margin) toward
    delta_capacity to trade scan output size for exactness.

    Visibility: tombstones out, stale versions out (see
    ``_latest_version_mask``), and rows failing ``node_pass`` out — predicate
    pushdown happens before the top-k, mirroring the stable probe path."""
    cap = delta.ids.shape[0]
    valid = jnp.logical_and(
        _latest_version_mask(delta),
        ~delta.tombstones[_clip_ids(delta, delta.ids)])
    if node_pass is not None:
        valid = jnp.logical_and(valid, mask_pass(node_pass, delta.ids))
    k_scan = min(cap, k + margin)
    qvals, qrows = scan_topk_quantized(
        queries, delta.qdata, delta.qvmin, delta.qscale, valid, k=k_scan,
        chunk=1)
    rows = jnp.clip(qrows, 0, cap - 1)
    vecs = delta.vectors[rows]                                # (Q, k_scan, d)
    exact = jnp.einsum("qd,qrd->qr", queries.astype(jnp.float32), vecs,
                       precision=jax.lax.Precision.HIGHEST)
    exact = jnp.where(jnp.logical_and(qrows >= 0, jnp.isfinite(qvals)),
                      exact, -jnp.inf)
    kk = min(k, exact.shape[1])
    vals, pos = jax.lax.top_k(exact, kk)
    di = jnp.take_along_axis(delta.ids[rows], pos, axis=1)
    di = jnp.where(jnp.isfinite(vals), di, -1)
    return pad_topk(vals, di, k)


def _stable_visibility(delta: DeltaStore, node_pass: Optional[jax.Array],
                       mvcc_filter: bool) -> Optional[jax.Array]:
    """The stable scan's pre-top-k validity mask: MVCC visibility
    (tombstones | superseded out) ∧ the optional predicate. The one spelling
    shared by the single-device and sharded paths — their results must stay
    bit-identical, so their visibility semantics must not be able to drift.
    mvcc_filter=False is the caller-asserted never-mutated fast path (no
    (N,) mask built when there is no predicate either)."""
    if not mvcc_filter:
        return node_pass
    dead = jnp.logical_or(delta.tombstones, delta.superseded)
    return ~dead if node_pass is None else jnp.logical_and(~dead, node_pass)


def search_with_delta(index: IVFIndex, delta: DeltaStore, queries: jax.Array, *,
                      n_probe: int, k: int,
                      rescore_margin: int = _RESCORE_MARGIN,
                      probes: Optional[jax.Array] = None,
                      node_pass: Optional[jax.Array] = None,
                      impl: str = "auto",
                      mvcc_filter: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Stable-ANNS ∪ delta-kernel-scan, visibility-filtered, dedup-merged.

    probes: optional precomputed partition assignment (see ivf.search).
    node_pass: optional predicate mask pushed into both scans.

    MVCC visibility (tombstones, superseded ids) is pushed into the stable
    scan's validity mask exactly like the predicate — *pre* top-k. Masking
    after the scan would let dead rows waste top-k slots (an update whose
    old vector scores well would push a live k-th result out), so a scan at
    full probe would no longer match brute force over the visible corpus.

    mvcc_filter=False is the caller-asserted fast path for indexes that
    have never seen a delete or update (the facade tracks this per
    modality): it skips building the (N,) visibility mask and keeps the
    unfiltered scan off the masked-gather lane."""
    visible = _stable_visibility(delta, node_pass, mvcc_filter)
    sv, si = ivf_mod.search(index, queries, n_probe=n_probe, k=k,
                            probes=probes, node_pass=visible, impl=impl)
    dv, di = _scan_delta(delta, queries, k=k, margin=rescore_margin,
                         node_pass=node_pass)
    # delta may hold multiple versions of an id (insert-after-insert): stale
    # versions are masked in _scan_delta; dedup covers stable-vs-delta overlap
    mv, mi = ivf_mod.dedup_merge_topk(sv, si, dv, di, k)
    # -inf slots are "no result": don't leak a masked (e.g. tombstoned) id
    return mv, jnp.where(jnp.isfinite(mv), mi, -1)


def search_with_delta_sharded(sharded: IVFIndex, delta: DeltaStore,
                              queries: jax.Array, mesh, *, n_probe: int, k: int,
                              rescore_margin: int = _RESCORE_MARGIN,
                              probes: Optional[jax.Array] = None,
                              node_pass: Optional[jax.Array] = None,
                              impl: str = "auto",
                              mvcc_filter: bool = True) -> Tuple[jax.Array, jax.Array]:
    """``search_with_delta`` over a row-sharded stable store (the sharded
    execution path): per-shard masked probes + cross-shard merge via
    ``ivf.search_sharded``, one replicated delta scan, dedup-merge.

    ``sharded`` is an ``ivf.shard_index`` layout (leading shard dim per
    leaf). The MVCC visibility mask and the predicate mask are built exactly
    as in the single-device path and broadcast (replicated) into every
    shard's scan — pre-top-k, so per-shard top-k lists only ever hold
    visible, qualifying rows. The delta is replicated state: scanning it once
    outside the shard_map and merging host-side is both cheaper than S
    redundant scans and keeps the two paths' results identical."""
    visible = _stable_visibility(delta, node_pass, mvcc_filter)
    with obs.span("sharded.scan") as sp:
        sv, si = sp.fence(ivf_mod.search_sharded(
            sharded, queries, mesh, n_probe=n_probe, k=k, probes=probes,
            node_pass=visible, impl=impl))
    # everything after the per-shard scans is the sharded path's extra cost
    # over single-device execution — surfaced as the "sharded.merge" span
    with obs.span("sharded.merge") as sp:
        # the distributed section ends at the cross-shard merge: the (Q, k)
        # candidate state is tiny, and every downstream stage (delta merge,
        # traversal, fusion) is a single-device computation — pulling the
        # replicated result onto the default device here keeps those stages
        # compiling exactly as in the single-device path
        sv, si = jax.device_put((sv, si), jax.devices()[0])
        dv, di = _scan_delta(delta, queries, k=k, margin=rescore_margin,
                             node_pass=node_pass)
        mv, mi = ivf_mod.dedup_merge_topk(sv, si, dv, di, k)
        return sp.fence((mv, jnp.where(jnp.isfinite(mv), mi, -1)))


def should_compact(delta: DeltaStore, threshold: float = 0.5) -> bool:
    """True when the delta holds ≥ threshold·capacity rows (counting stale
    and drained slots: ``count`` is the append watermark, the quantity that
    actually exhausts capacity)."""
    return int(delta.count) >= int(threshold * delta.vectors.shape[0])


# ---------------------------------------------------------------------------
# incremental drain (bounded-work compaction steps; maintenance/executor.py)
# ---------------------------------------------------------------------------

def live_slots(delta: DeltaStore):
    """Host: slot indices (ascending — oldest write first) of rows visible
    to the delta scan: latest version per id, not tombstoned. The incremental
    compactor drains a bounded prefix of this list per step."""
    ids = np.asarray(delta.ids)
    tomb = np.asarray(delta.tombstones)
    ok = np.asarray(_latest_version_mask(delta)) \
        & ~tomb[np.clip(ids, 0, tomb.shape[0] - 1)]
    return np.where(ok)[0]


def rebuild_keep(delta: DeltaStore, keep_slots, clear_superseded_ids=None
                 ) -> DeltaStore:
    """Fresh store holding only ``keep_slots``'s rows — the drain step's
    tail: drained / stale / tombstone-shadowed slots vanish and the kept
    rows re-pack from slot 0 as one fixed-(cap,)-shape gather (their stored
    bytes move untouched — and the shape never depends on how many rows
    survive, so repeated drain steps hit the same compiled executables).
    Tombstones carry over; the version stays monotone.
    ``clear_superseded_ids`` marks ids whose latest version just moved into
    the stable store — their stable row is live again, so the superseded
    bit must drop with the delta row."""
    sp = delta.superseded
    if clear_superseded_ids is not None and len(clear_superseded_ids):
        sp = sp.at[_clip_ids(delta, jnp.asarray(
            np.asarray(clear_superseded_ids, np.int32)))].set(False)
    cap = delta.vectors.shape[0]
    keep_slots = np.asarray(keep_slots, np.int64)
    n = int(keep_slots.size)
    # (cap,) gather map: kept rows to the front, slot 0 as a harmless
    # source for the (masked-out) tail
    src = np.zeros(cap, np.int64)
    src[:n] = keep_slots
    gs = jnp.asarray(src)
    valid = jnp.arange(cap) < n
    return DeltaStore(
        vectors=jnp.where(valid[:, None], delta.vectors[gs], 0.0),
        qdata=jnp.where(valid[:, None], delta.qdata[gs], 0),
        qvmin=jnp.where(valid, delta.qvmin[gs], 0.0),
        qscale=jnp.where(valid, delta.qscale[gs], 1.0),
        ids=jnp.where(valid, delta.ids[gs], -1),
        row_version=jnp.where(valid, delta.row_version[gs], -1),
        stale=jnp.zeros((cap,), bool),      # kept rows are one-per-id live
        count=jnp.asarray(n, jnp.int32),
        version=delta.version + 1,
        tombstones=delta.tombstones,
        superseded=sp,
    )


def compact(key, index: IVFIndex, delta: DeltaStore,
            all_vectors: jax.Array, all_ids: jax.Array) -> Tuple[IVFIndex, DeltaStore]:
    """Full synchronous compaction: merge live delta rows into the stable
    index by re-running the (cheap) assignment against *existing* centroids —
    no K-means refit (paper: "incremental merges into snapshots"). This is
    the one-shot fallback; the bounded-work path drains chunks instead
    (``live_slots``/``rebuild_keep`` + repro/maintenance, docs/DESIGN.md
    §3.4). Centroid drift is handled there too (recluster/split actions).

    all_vectors/all_ids: the full live corpus with one latest row per id
    (facade-provided); returns (new_index, fresh_delta). Overflow rows that
    don't fit their partition are re-queued in the fresh delta — growing it
    when they exceed its capacity, never truncating (rows must stay
    searchable until the next repartition widens the slabs)."""
    live = ~delta.tombstones[_clip_ids(delta, all_ids)]
    vecs = jnp.where(live[:, None], all_vectors, 0.0)
    ids = jnp.where(live, all_ids, -1)
    new_index, overflow = ivf_mod.build(key, vecs, ids,
                                        n_partitions=index.n_partitions,
                                        capacity=index.capacity, bits=index.bits,
                                        centroids=index.centroids)
    # rows that didn't fit their partition stay queryable via the fresh delta
    over = jnp.logical_and(overflow, live)
    n_over = int(jnp.sum(over))
    fresh = init(delta.vectors.shape[0], delta.vectors.shape[1],
                 delta.tombstones.shape[0])
    fresh = fresh._replace(version=delta.version + 1, tombstones=delta.tombstones)
    if n_over:
        sel = jnp.where(over)[0]
        fresh = insert_grow(fresh, all_vectors[sel], all_ids[sel])
    return new_index, fresh
