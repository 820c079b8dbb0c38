"""Plain reference for what the served path returns, in float64 on the host.

It calls nothing of the program's search, traversal or fusion code. It
reads the index as built (the partition layout: centroids, the stored
int8 rows with their per-row affine terms, the delta store, the community
labels) after the window, checks that layout against the corpus that the
benchmark regenerates from the seed (``check_layout``) and the community
labels against the graph (``modularity``), and then computes from it,
per query, what the configuration's semantics say the answer is:

- vector search: the ``n_probe`` partitions whose centroids score highest
  (q·c − |c|²/2), every live stored row in them scored by its dequantized
  value, plus the delta store's top ``k + margin`` live rows by
  dequantized score rescored against the fp32 rows; the top k of the union.
  A ``where`` predicate is pushed into both scans when its selectivity is
  at most ``prefilter_max_sel``; above it both scans run unfiltered at the
  oversampled width and the union is filtered afterwards.
- hybrid search: that top ``seed_width`` as seeds; their scores, shifted
  to be positive and normalised, as seed mass; ``n_hops`` pushes of
  ``damping`` times the out-degree-normalised mass along every edge, edge
  weights ``community_boost`` within a community and 1 across, with
  nodes failing the predicate neither receiving nor passing mass; graph
  relevance the mean over hops; and Eq. 3 fusion with the adaptive
  weights, top k over every node that passes the predicate.

``precision="bf16x3"`` computes the same answers with every product in
three bfloat16 passes and the traversal and fusion held to bfloat16: the
control that the check must reject.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import exact

NEG = -np.inf


# ------------------------------------------------------------- precision
def bf16(x: np.ndarray) -> np.ndarray:
    """Rounds float32 values to the nearest bfloat16 (ties to even)."""
    x = np.ascontiguousarray(x, np.float32)
    u = x.view(np.uint32)
    u = (u + (np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def matmul(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """a @ b in float64, or as three bfloat16 passes (hi·hi + hi·lo +
    lo·hi) accumulated in float32."""
    if precision == "f64":
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ah, bh = bf16(a32), bf16(b32)
    al, bl = bf16(a32 - ah), bf16(b32 - bh)
    return (ah @ bh + ah @ bl + al @ bh).astype(np.float64)


def _dequant_scores(qn, q8, vmin, scale, precision):
    """(Q, R) scores of queries against stored rows, each row's value
    (q8 + 128)·scale + vmin. In float64 the affine terms factor out of the
    sum exactly enough (the integer part is exact); the control
    dequantizes each row to float32 first, as a kernel would."""
    if precision == "f64":
        ints = q8.astype(np.float64) + 128.0
        return ((qn @ ints.T) * scale[None, :].astype(np.float64)
                + np.sum(qn, axis=1, keepdims=True)
                * vmin[None, :].astype(np.float64))
    deq = ((q8.astype(np.float32) + np.float32(128.0)) * scale[:, None]
           + vmin[:, None])
    return matmul(qn, deq.T, precision)


def hold(x: np.ndarray, precision: str) -> np.ndarray:
    return x if precision == "f64" else bf16(x).astype(np.float64)


# ----------------------------------------------------------------- layout
@dataclasses.dataclass
class Layout:
    """The built index as stored, copied to the host."""
    centroids: np.ndarray      # (K, d)
    data: np.ndarray           # (K, cap, d) int8, centred by -128
    vmin: np.ndarray           # (K, cap)
    scale: np.ndarray          # (K, cap)
    ids: np.ndarray            # (K, cap) int32, -1 empty
    d_ids: np.ndarray          # (c,) delta ids, -1 empty
    d_data: np.ndarray         # (c, d) int8
    d_vmin: np.ndarray         # (c,)
    d_scale: np.ndarray        # (c,)
    d_live: np.ndarray         # (c,) bool: newest version, not deleted
    dead: np.ndarray           # (n_ids,) bool: deleted or superseded in stable
    communities: Optional[np.ndarray] = None

    @classmethod
    def of(cls, index, modality: str) -> "Layout":
        m = index.modalities[modality]
        iv, dl = m.ivf, m.delta
        tomb = np.asarray(dl.tombstones)
        d_ids = np.asarray(dl.ids)
        d_live = (d_ids >= 0) & ~np.asarray(dl.stale) \
            & ~tomb[np.clip(d_ids, 0, tomb.size - 1)]
        return cls(np.asarray(iv.centroids), np.asarray(iv.data),
                   np.asarray(iv.vmin), np.asarray(iv.scale),
                   np.asarray(iv.ids), d_ids, np.asarray(dl.qdata),
                   np.asarray(dl.qvmin), np.asarray(dl.qscale), d_live,
                   tomb | np.asarray(dl.superseded),
                   None if index.communities is None
                   else np.asarray(index.communities))

    def stable_live(self) -> np.ndarray:
        ids = self.ids
        return (ids >= 0) & ~self.dead[np.clip(ids, 0, self.dead.size - 1)]


def unit64(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def check_layout(lay: Layout, vectors, bits: int) -> Dict[str, float]:
    """The stored index against the corpus as given (``vectors``: the raw
    rows on the device, row i = id i), by ``exact.layout_gaps``. Each id
    must be stored exactly once among the live stable rows and live delta
    rows; every stored row must carry Eq. 2's affine terms of its own
    normalised vector at the configuration's ``bits`` (``layout_affine``,
    float32 rounding), and int8 values that are their floor
    (``layout_gap``: at most 1/2, up to float32 rounding of v); every
    stable row must sit in its nearest centroid's partition (Eq. 1:
    ``partition_gap``, float32 rounding of the centroid score)."""
    n = int(vectors.shape[0])
    live = lay.stable_live()
    ids = np.concatenate([lay.ids[live], lay.d_ids[lay.d_live]])
    q8 = np.concatenate([lay.data[live], lay.d_data[lay.d_live]])
    vmin = np.concatenate([lay.vmin[live], lay.d_vmin[lay.d_live]])
    scale = np.concatenate([lay.scale[live], lay.d_scale[lay.d_live]])
    part = np.concatenate([np.nonzero(live)[0].astype(np.int32),
                           np.full(int(lay.d_live.sum()), -1, np.int32)])
    inside = (ids >= 0) & (ids < n)
    seen = np.bincount(ids[inside], minlength=n)
    gaps = (0.0, 0.0, 0.0)
    if inside.any():
        gaps = exact.layout_gaps(vectors, ids[inside], q8[inside],
                                 vmin[inside], scale[inside], part[inside],
                                 np.asarray(lay.centroids, np.float32), bits)
    if not inside.all():
        gaps = (np.inf,) * 3
    return {"layout_gap": float(gaps[0]), "layout_affine": float(gaps[1]),
            "partition_gap": float(gaps[2]),
            "layout_missing": int(np.sum(seen != 1))}


def modularity(edges, n_nodes: int, labels) -> float:
    """Newman modularity of ``labels`` on the edges as given, taken as
    undirected with unit weights: the share of edge ends inside a
    community less what a random graph of the same degrees would put
    there. A labelling that is not one int per node reads −1 (no
    labelling reads under −1/2)."""
    if labels is None:
        return -1.0
    lab = np.asarray(labels)
    if lab.shape != (n_nodes,) or lab.dtype.kind not in "iu" \
            or (lab.size and lab.min() < 0):
        return -1.0
    src = np.asarray(edges[0], np.int64)
    dst = np.asarray(edges[1], np.int64)
    two_m = 2.0 * src.size
    if two_m == 0:
        return 0.0
    inside = float(np.sum(lab[src] == lab[dst])) * 2.0
    deg = np.bincount(src, minlength=n_nodes) + np.bincount(
        dst, minlength=n_nodes)
    tot = np.bincount(lab, weights=deg.astype(np.float64))
    return inside / two_m - float(np.sum((tot / two_m) ** 2))


# -------------------------------------------------------------- semantics
@dataclasses.dataclass(frozen=True)
class Semantics:
    """The configuration's statement of what a served answer is."""
    n_probe: int
    k: int
    margin: int
    prefilter_max_sel: float
    oversample: float
    seed_width: int = 0
    n_hops: int = 0
    damping: float = 0.85
    community_boost: float = 1.0
    w_vector: float = 0.6
    w_graph: float = 0.4
    confidence_slope: float = 4.0
    confidence_margin: float = 0.05

    @classmethod
    def of(cls, cfg: dict, n_hops: int) -> "Semantics":
        ix, se = cfg["index"], cfg["semantics"]
        return cls(n_probe=ix["n_probe"], k=ix["top_k"],
                   margin=ix["delta_rescore_margin"],
                   prefilter_max_sel=ix["filter_prefilter_max_sel"],
                   oversample=ix["filter_oversample"],
                   seed_width=se.get("seed_width", 0), n_hops=n_hops,
                   damping=se.get("damping", 0.85),
                   community_boost=se.get("community_boost", 1.0),
                   w_vector=ix.get("w_vector", 0.6),
                   w_graph=ix.get("w_graph", 0.4),
                   confidence_slope=se.get("confidence_slope", 4.0),
                   confidence_margin=se.get("confidence_margin", 0.05))


def scan_width(sem: Semantics, k: int, sel: Optional[float],
               n_rows: int) -> Tuple[int, bool]:
    """(width, pushdown) of the seed scans for a predicate of selectivity
    ``sel`` (None: no predicate): the bare k with the predicate pushed
    down, or ``k·oversample/sel`` rounded up to a power of two, unfiltered."""
    if sel is None or sel <= sem.prefilter_max_sel:
        return k, sel is not None
    w = min(n_rows, max(k + 1, int(np.ceil(k * sem.oversample / sel))))
    return min(max(k, 1 << (w - 1).bit_length()), n_rows), False


@dataclasses.dataclass
class Answer:
    scores: np.ndarray           # (Q, k) descending, -inf padded
    ids: np.ndarray              # (Q, k), -1 padded
    score_of: List[Dict[int, float]]
    ambiguous: np.ndarray        # (Q,) bool: a near-tie the answer hinges on
    seeds: Optional["Answer"] = None   # hybrid: the seeds it was built on


class Reference:
    """Answers of the stated semantics over one built layout."""

    def __init__(self, lay: Layout, vectors: np.ndarray, sem: Semantics,
                 *, edges=None, n_nodes: int = 0, tie: float = 1e-6):
        self.lay, self.sem, self.tie = lay, sem, tie
        self.vectors = vectors            # raw corpus rows, id = row
        self.cent = np.asarray(lay.centroids, np.float64)
        self.live = lay.stable_live()
        self.n_rows = int(self.live.sum() + lay.d_live.sum())
        self._graph = None
        if edges is not None:
            self._graph = _Graph(edges, n_nodes, lay.communities,
                                 sem.community_boost)

    # --------------------------------------------------------- seed scan
    def search(self, q: np.ndarray, k: int, node_pass: Optional[np.ndarray],
               precision: str = "f64") -> Answer:
        lay, sem = self.lay, self.sem
        qn = unit64(q)
        nq = qn.shape[0]
        sel = None if node_pass is None else float(np.mean(node_pass))
        width, pushdown = scan_width(sem, k, sel, self.n_rows)
        # probes: the stated centroid score in float64 (the control's too:
        # probe selection is not where it departs)
        cs = qn @ self.cent.T - 0.5 * np.sum(self.cent ** 2, axis=1)[None]
        order = np.argsort(-cs, axis=1, kind="stable")
        probes = order[:, :sem.n_probe]
        ambiguous = np.zeros(nq, bool)
        if cs.shape[1] > sem.n_probe:
            r = np.arange(nq)
            ambiguous |= (cs[r, order[:, sem.n_probe - 1]]
                          - cs[r, order[:, sem.n_probe]]) < self.tie
        mask_rows = node_pass if pushdown else None
        cand_s: List[List[np.ndarray]] = [[] for _ in range(nq)]
        cand_i: List[List[np.ndarray]] = [[] for _ in range(nq)]
        for p in np.unique(probes):
            qs = np.nonzero(np.any(probes == p, axis=1))[0]
            ok = self.live[p].copy()
            ids_p = lay.ids[p]
            if mask_rows is not None:
                ok &= mask_rows[np.clip(ids_p, 0, mask_rows.size - 1)]
            rows = np.nonzero(ok)[0]
            if rows.size == 0:
                continue
            s = _dequant_scores(qn[qs], lay.data[p, rows], lay.vmin[p, rows],
                                lay.scale[p, rows], precision)
            top = min(width + 1, rows.size)
            part = np.argpartition(-s, top - 1, axis=1)[:, :top]
            for j, qi in enumerate(qs):
                cand_s[qi].append(s[j, part[j]])
                cand_i[qi].append(ids_p[rows[part[j]]])
        ds, di = self._delta(qn, width, mask_rows, precision)
        out_s = np.full((nq, k), NEG)
        out_i = np.full((nq, k), -1, np.int64)
        score_of: List[Dict[int, float]] = []
        for qi in range(nq):
            s = np.concatenate(cand_s[qi] + [ds[qi]])
            i = np.concatenate(cand_i[qi] + [di[qi]]).astype(np.int64)
            o = np.argsort(-s, kind="stable")
            s, i = s[o], i[o]
            keep = np.ones(s.size, bool)
            if not pushdown and node_pass is not None:
                # the union at the oversampled width, filtered afterwards
                keep[width:] = False
                keep &= node_pass[np.clip(i, 0, node_pass.size - 1)]
            s, i = s[keep], i[keep]
            m = min(k, s.size)
            out_s[qi, :m], out_i[qi, :m] = s[:m], i[:m]
            if s.size > m and m == k and s[k - 1] - s[k] < self.tie:
                ambiguous[qi] = True
            score_of.append(dict(zip(i.tolist(), s.tolist())))
        return Answer(out_s, out_i, score_of, ambiguous)

    def _delta(self, qn, width, node_pass, precision):
        lay, sem = self.lay, self.sem
        ok = lay.d_live.copy()
        if node_pass is not None:
            ok &= node_pass[np.clip(lay.d_ids, 0, node_pass.size - 1)]
        rows = np.nonzero(ok)[0]
        nq = qn.shape[0]
        if rows.size == 0:
            return [np.zeros(0)] * nq, [np.zeros(0, np.int64)] * nq
        approx = _dequant_scores(qn, lay.d_data[rows], lay.d_vmin[rows],
                                 lay.d_scale[rows], precision)
        exact = matmul(qn, unit64(self.vectors[lay.d_ids[rows]]).T, precision)
        n_keep = min(rows.size, width + sem.margin)
        out_s, out_i = [], []
        for qi in range(nq):
            c = np.argsort(-approx[qi], kind="stable")[:n_keep]
            e = exact[qi, c]
            o = np.argsort(-e, kind="stable")[:width]
            out_s.append(e[o])
            out_i.append(lay.d_ids[rows[c[o]]].astype(np.int64))
        return out_s, out_i

    # ------------------------------------------------------------ hybrid
    def hybrid(self, q: np.ndarray, node_pass: Optional[np.ndarray],
               precision: str = "f64",
               seeds: Optional[Answer] = None) -> Answer:
        """Hybrid answers; ``seeds`` replaces the probed seed scan (the
        recall check seeds from brute force)."""
        sem = self.sem
        if seeds is None:
            seeds = self.search(q, sem.seed_width, node_pass, precision)
        g = self._graph.mass(seeds.ids, seeds.scores, node_pass, sem,
                             precision)                         # (N, Q)
        nq = q.shape[0]
        out_s = np.full((nq, sem.k), NEG)
        out_i = np.full((nq, sem.k), -1, np.int64)
        score_of = []
        ambiguous = seeds.ambiguous.copy()
        wv, wg = _weights(seeds.scores, sem)
        for qi in range(nq):
            gq = g[:, qi]
            gmax = np.max(gq)
            fused = hold(wg[qi] * (gq / max(gmax, 1e-12)), precision)
            sid, ss = seeds.ids[qi], seeds.scores[qi]
            ok = sid >= 0
            sid, ss = sid[ok], ss[ok]
            fused[sid] = hold(wv[qi] * (1.0 - 0.5 * (1.0 - ss))
                              + wg[qi] * (gq[sid] / max(gmax, 1e-12)),
                              precision)
            if node_pass is not None:
                fused = np.where(node_pass, fused, NEG)
            top = np.argpartition(-fused, sem.k)[:sem.k + 1]
            top = top[np.argsort(-fused[top], kind="stable")]
            out_s[qi] = fused[top[:sem.k]]
            out_i[qi] = top[:sem.k]
            if fused[top[sem.k - 1]] - fused[top[sem.k]] < self.tie:
                ambiguous[qi] = True
            score_of.append(_LazyScores(fused))
        return Answer(out_s, out_i, score_of, ambiguous, seeds)


class _LazyScores:
    """A dense score vector read like the ``score_of`` dicts."""

    def __init__(self, fused: np.ndarray):
        self.fused = fused

    def get(self, i: int):
        if 0 <= i < self.fused.size and np.isfinite(self.fused[i]):
            return float(self.fused[i])
        return None


def _weights(seed_scores: np.ndarray, sem: Semantics):
    s = seed_scores
    margin = s[:, 0] - (s[:, 1] if s.shape[1] > 1 else s[:, 0])
    margin = np.nan_to_num(margin, nan=0.0, posinf=1.0, neginf=0.0)
    conf = 1.0 / (1.0 + np.exp(-sem.confidence_slope
                               * (margin - sem.confidence_margin)))
    wv = sem.w_vector * (0.5 + conf)
    wg = sem.w_graph * (1.5 - conf)
    return wv / (wv + wg), wg / (wv + wg)


class _Graph:
    """Row-normalised push operator over the edges as given."""

    def __init__(self, edges, n_nodes: int, communities, boost: float):
        from scipy.sparse import csr_matrix
        src = np.asarray(edges[0], np.int64)
        dst = np.asarray(edges[1], np.int64)
        w = np.ones(src.size)
        if communities is not None:
            w = np.where(communities[src] == communities[dst], boost, 1.0)
        out = np.bincount(src, weights=w, minlength=n_nodes)
        self.inv_deg = np.where(out > 0, 1.0 / np.maximum(out, 1e-12), 0.0)
        self.push = csr_matrix((w, (dst, src)), shape=(n_nodes, n_nodes))
        self.n = n_nodes

    def mass(self, ids, scores, node_pass, sem: Semantics, precision: str):
        nq = ids.shape[0]
        x = np.zeros((self.n, nq))
        for qi in range(nq):
            ok = ids[qi] >= 0
            if not ok.any():
                continue
            s = scores[qi, ok]
            w = s - np.min(s) + 1e-6
            np.add.at(x[:, qi], ids[qi, ok], w / max(np.sum(w), 1e-12))
        nm = None if node_pass is None else node_pass.astype(np.float64)
        if nm is not None:
            x *= nm[:, None]
        x = hold(x, precision)
        total = np.zeros_like(x)
        for _ in range(sem.n_hops):
            x = sem.damping * (self.push @ (self.inv_deg[:, None] * x))
            if nm is not None:
                x *= nm[:, None]
            x = hold(x, precision)
            total += x
        return hold(total / max(sem.n_hops, 1), precision)


# ------------------------------------------------------------- comparison
def compare(served_s: np.ndarray, served_i: np.ndarray, ref: Answer,
            tol: float, node_pass: Optional[Sequence] = None
            ) -> Dict[str, float]:
    """Served (Q, k) answers against the reference's. ``score_gap``: the
    widest |served − reference| score at the same rank; ``id_miss``: ranks
    whose served id is neither the reference's id nor an id the reference
    scores within ``tol`` of its own score at that rank, or repeats an id;
    ``predicate_miss``: served ids that fail the query's predicate.
    Queries the reference marks ambiguous are left out and counted."""
    use = ~ref.ambiguous
    gap, miss, pmiss = 0.0, 0, 0
    per_query = np.zeros(len(served_s))
    for qi in np.nonzero(use)[0]:
        s, i = served_s[qi], served_i[qi]
        rs, ri = ref.scores[qi], ref.ids[qi]
        fin = np.isfinite(s) & np.isfinite(rs)
        if np.any(np.isfinite(s) != np.isfinite(rs)):
            gap = np.inf
        if fin.any():
            per_query[qi] = float(np.max(np.abs(s[fin] - rs[fin])))
            gap = max(gap, per_query[qi])
        seen = set()
        for r in range(len(i)):
            sid = int(i[r])
            if sid in seen and sid >= 0:
                miss += 1
            seen.add(sid)
            if sid != int(ri[r]):
                got = ref.score_of[qi].get(sid) if sid >= 0 else None
                if got is None or not abs(got - rs[r]) <= tol:
                    miss += 1
        if node_pass is not None and node_pass[qi] is not None:
            live = i[i >= 0]
            pmiss += int(np.sum(~node_pass[qi][live]))
    return {"score_gap": gap, "id_miss": miss, "predicate_miss": pmiss,
            "compared": int(use.sum()), "ambiguous": int((~use).sum()),
            "per_query": per_query}


def recall(served_i: np.ndarray, exact_i: np.ndarray) -> float:
    """Mean share of each exact top-k id list found in the served one."""
    hits = [len(set(a[a >= 0].tolist()) & set(b[b >= 0].tolist()))
            / max(int(np.sum(b >= 0)), 1) for a, b in zip(served_i, exact_i)]
    return float(np.mean(hits))
