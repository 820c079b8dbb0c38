#!/usr/bin/env python3
"""Chip smoke test: the HMGI retrieval path on a TPU at the serve_1m deployment.

Drives the main path once through the entry points a user calls, in one
process, with all data made from ``--seed``:

  build   make_corpus at full width (1,048,576 × 384 text vectors, a graph
          of mean degree ~16, one int attribute column) -> HMGIIndex.ingest
  search  vector-only top-10 through RetrievalService.search_many at the
          serving batch of 256; the compiled search step must hold the
          Mosaic kernels (tpu_custom_call) and the stable scan must have
          resolved to the kernel path
  check   served results vs a plain numpy reference over the dequantized
          rows of the same probed partitions (+ the delta), and recall@10
          against the fp32 brute force
  hybrid  filtered vector search and 2-hop hybrid_search with a where= at
          selectivity 0.01 and 0.5; every answer satisfies the predicate
  writes  inserts, updates and deletes of a few hundred rows, maintain(),
          then read-back: inserted and updated vectors are found by
          querying with themselves, deleted ids never come back

Earlier lines report each phase's wall time (set-up, not a metric), the
device's peak bytes in use, and the device. The last line is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.

Usage:
  python chip_smoke.py                  # one TPU chip, serve_1m
  python chip_smoke.py --chips 4        # the sharded stable scan on a
                                        # 4-chip ("data",) mesh vs the
                                        # single-device scan, nothing else
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                        # tiny sizes, Pallas interpret mode;
                                        # never reports ok
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      python chip_smoke.py --rehearse --chips 4

Without ``--rehearse`` any backend other than the TPU is a failure: the
script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

SERVE_1M = dict(n_vectors=1_048_576, dim=384, batch=256)
REHEARSE = dict(n_vectors=8192, dim=384, batch=64)
MEAN_DEGREE = 16            # undirected: 2·E / N
N_HELD_OUT = 256            # graph nodes whose vectors arrive as inserts
N_UPDATE = 128
N_DELETE = 256
N_CHECK = 8                 # queries held to the numpy reference
SCORE_TOL = 1e-5            # |served - reference| on unit-vector scores
SELECTIVITIES = {0.01: ("sel", "<", 1), 0.5: ("sel", "<", 50)}


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


class Phases:
    """Per-phase wall time (set-up, not a metric) and peak device bytes."""

    def __init__(self, dev):
        self.dev = dev

    def peak(self):
        stats = self.dev.memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def run(self, name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        wall = time.perf_counter() - t0
        log(f"[phase] {name}: wall_s={wall:.3f} peak_bytes_in_use={self.peak()}")
        return out


# ------------------------------------------------------------------- data
def make_data(n, d, seed, *, with_graph):
    """The serve_1m corpus: planted-cluster unit vectors at full width, plus
    N_HELD_OUT extra graph nodes whose vectors are inserted later. The
    graph keeps make_corpus's intra/inter-cluster ratio with both edge
    probabilities scaled by 1/N, for a mean undirected degree of ~16."""
    import numpy as np
    from repro.data.synthetic import make_corpus
    n_nodes = n + N_HELD_OUT
    base_intra, base_inter, n_clusters = 0.015, 0.0005, 16
    c = MEAN_DEGREE / 2 / (base_intra / n_clusters + base_inter)
    corpus = make_corpus(n_nodes=n_nodes, modality_dims={"text": d},
                         n_clusters=n_clusters,
                         intra_p=base_intra * c / n_nodes if with_graph else 0,
                         inter_p=base_inter * c / n_nodes if with_graph else 0,
                         seed=seed)
    rng = np.random.default_rng(seed + 1)
    attrs = {"sel": rng.integers(0, 100, n_nodes).astype(np.int32)}
    return corpus, attrs


def make_queries(corpus, batch, seed):
    import numpy as np
    rng = np.random.default_rng(seed + 2)
    v = corpus.vectors["text"]
    sel = rng.integers(0, v.shape[0] - N_HELD_OUT, batch)
    q = v[sel] + 0.05 * rng.normal(size=(batch, v.shape[1])).astype(np.float32)
    return q.astype(np.float32)


def unit(x):
    import numpy as np
    x = np.asarray(x, np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


# ---------------------------------------------------------------- checks
def assert_kernel_path(index, q, rehearse):
    """The stable scan resolved to the Pallas kernel, the kernels are not
    interpreted, and the compiled search steps hold Mosaic custom calls
    (a rehearsal off-TPU interprets them, and only reports)."""
    import jax
    from repro.core import delta as delta_mod
    from repro.core import ivf as ivf_mod
    from repro.kernels.ivf_topk.ops import _interpret_mode
    m = index.modalities["text"]
    cfg = index.cfg
    impl = ivf_mod._resolve_impl(m.ivf, "auto")
    check(impl == "kernel", f"stable scan resolved to {impl!r}, not 'kernel'")
    qn = index._norm_queries(q)
    step = ivf_mod.search.lower(m.ivf, qn, n_probe=cfg.n_probe,
                                k=cfg.top_k).compile().as_text()
    dstep = delta_mod._scan_delta.lower(
        m.delta, qn, k=cfg.top_k,
        margin=cfg.delta_rescore_margin).compile().as_text()
    if not rehearse:
        check(not _interpret_mode(), "Pallas kernels run in interpret mode")
        check("tpu_custom_call" in step,
              "compiled ivf.search holds no tpu_custom_call")
        check("tpu_custom_call" in dstep,
              "compiled delta scan holds no tpu_custom_call")
    log(f"kernel path: impl={impl} interpret={_interpret_mode()} "
        f"mosaic_calls(search)={step.count('tpu_custom_call')} "
        f"mosaic_calls(delta)={dstep.count('tpu_custom_call')} "
        f"backend={jax.default_backend()}")


def reference_topk(index, q_unit, k):
    """Plain numpy top-k, in float64, over the dequantized rows of the
    probed partitions plus the delta as the delta store defines its scan:
    the top (k + margin) live rows by dequantized score, rescored against
    their fp32 master rows. Probes are the system's own, checked against
    float64 centroid scores."""
    import numpy as np
    from repro.core.partitioner import assign_topk
    m = index.modalities["text"]
    n_probe = index.cfg.n_probe
    probes = np.asarray(assign_topk(index._norm_queries(q_unit),
                                    m.ivf.centroids, n_probe)[0])
    cent = np.asarray(m.ivf.centroids, np.float64)
    cs = q_unit @ cent.T - 0.5 * np.sum(cent * cent, axis=1)[None]
    for i in range(q_unit.shape[0]):
        chosen = np.sort(cs[i, probes[i]])
        best = np.sort(cs[i])[::-1][:n_probe][::-1]
        check(np.all(np.abs(chosen - best) <= SCORE_TOL),
              f"query {i}: probed partitions {sorted(probes[i])} are not "
              "the float64 nearest centroids")
    data = np.asarray(m.ivf.data)
    vmin = np.asarray(m.ivf.vmin, np.float64)
    scale = np.asarray(m.ivf.scale, np.float64)
    sids = np.asarray(m.ivf.ids)
    dl = m.delta
    d_ids = np.asarray(dl.ids)
    tomb = np.asarray(dl.tombstones) | np.asarray(dl.superseded)
    d_live = (d_ids >= 0) & ~np.asarray(dl.stale) \
        & ~np.asarray(dl.tombstones)[np.clip(d_ids, 0, tomb.size - 1)]
    d_ids = d_ids[d_live]
    d_vecs = np.asarray(dl.vectors, np.float64)[d_live]
    d_deq = ((np.asarray(dl.qdata)[d_live].astype(np.float64) + 128.0)
             * np.asarray(dl.qscale, np.float64)[d_live][:, None]
             + np.asarray(dl.qvmin, np.float64)[d_live][:, None])
    k_scan = k + index.cfg.delta_rescore_margin
    out_s, out_i, score_of = [], [], []
    for i in range(q_unit.shape[0]):
        p = probes[i]
        rows = (data[p].astype(np.float64) + 128.0) * scale[p][..., None] \
            + vmin[p][..., None]
        s = (rows @ q_unit[i]).reshape(-1)
        ids = sids[p].reshape(-1)
        ok = (ids >= 0) & ~tomb[np.clip(ids, 0, tomb.size - 1)]
        cand = np.argsort(-(d_deq @ q_unit[i]), kind="stable")[:k_scan]
        s_all = np.concatenate([s[ok], d_vecs[cand] @ q_unit[i]])
        i_all = np.concatenate([ids[ok], d_ids[cand]])
        order = np.argsort(-s_all, kind="stable")[:k]
        out_s.append(s_all[order])
        out_i.append(i_all[order])
        score_of.append(dict(zip(i_all.tolist(), s_all.tolist())))
    return np.stack(out_s), np.stack(out_i), score_of


def check_against_reference(index, q, sv, si, k):
    import numpy as np
    q_unit = unit(q[:N_CHECK])
    rs, ri, score_of = reference_topk(index, q_unit, k)
    sv, si = np.asarray(sv[:N_CHECK], np.float64), np.asarray(si[:N_CHECK])
    err = float(np.max(np.abs(sv - rs)))
    check(err <= SCORE_TOL, f"served scores differ from the reference by "
          f"{err:.3e} > {SCORE_TOL}")
    swaps = 0
    for i in range(N_CHECK):
        for pos in np.nonzero(si[i] != ri[i])[0]:
            # a differing id is admitted only as a tie within the tolerance
            got = score_of[i].get(int(si[i, pos]))
            check(got is not None and abs(got - rs[i, pos]) <= SCORE_TOL,
                  f"query {i} rank {pos}: served id {si[i, pos]} vs "
                  f"reference id {ri[i, pos]} (not a tie)")
            swaps += 1
    log(f"reference check: {N_CHECK} queries, max |score diff|={err:.3e} "
        f"(tol {SCORE_TOL}), tie swaps={swaps}")


def recall_vs_brute_force(index, q, si, k):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ivf as ivf_mod
    from repro.data.synthetic import recall_at_k
    m = index.modalities["text"]
    _, bi = ivf_mod.brute_force(m.vectors, jnp.ones(m.ids.shape, bool),
                                m.ids, index._norm_queries(q), k=k)
    r = recall_at_k(np.asarray(si), np.asarray(bi))
    log(f"recall@{k} vs fp32 brute force over {q.shape[0]} queries: {r:.4f}")
    return r


def check_predicate(ids, attrs, where, label):
    import numpy as np
    col, op, val = where
    assert op == "<"
    ids = np.asarray(ids)
    live = ids[ids >= 0]
    check(live.size > 0, f"{label}: no results")
    check(bool(np.all(attrs[col][live] < val)),
          f"{label}: a result fails {where}")
    return live.size


def hybrid_batch(index, batch):
    """Largest power-of-two query batch (≤ batch) whose filtered 2-hop
    traversal needs at most half of the device's free memory, per the
    compiler's memory_analysis: measured at 8 queries, scaled linearly,
    then confirmed at the chosen batch (halving while it does not fit)."""
    import jax
    import jax.numpy as jnp
    from repro.core import traversal as trav_mod
    stats = jax.devices()[0].memory_stats() or {}
    free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
    g = index.graph._replace(edge_weight=index.boosted_weights)
    fn = jax.jit(functools.partial(trav_mod.multi_hop_batch,
                                   n_hops=index.cfg.max_hops))
    k = index.cfg.top_k

    def need(b):
        ma = fn.lower(g, jax.ShapeDtypeStruct((b, k), jnp.int32),
                      jax.ShapeDtypeStruct((b, k), jnp.float32),
                      node_mask=jax.ShapeDtypeStruct((g.n_nodes,), bool)
                      ).compile().memory_analysis()
        return ma.temp_size_in_bytes + ma.output_size_in_bytes

    if free <= 0:
        log(f"hybrid batch: {batch} (backend reports no memory limit)")
        return batch
    b, per8 = batch, need(8)
    while b > 8 and 2 * per8 * b // 8 > free:
        b //= 2
    nb = need(b)
    while b > 8 and 2 * nb > free:
        b //= 2
        nb = need(b)
    log(f"hybrid batch: {b} of {batch} (traversal temp+out at {b}: {nb} B, "
        f"device free: {free} B)")
    return b


# ------------------------------------------------------------------ phases
def build(index_cls, cfg, corpus, attrs, n, *, graph, mesh=None):
    ids = corpus.node_ids["text"][:n]
    vecs = corpus.vectors["text"][:n]
    index = index_cls(cfg, mesh=mesh, seed=0)
    edges = (corpus.src, corpus.dst, corpus.edge_type) if graph else None
    index.ingest({"text": (ids, vecs)}, n_nodes=corpus.n_nodes, edges=edges,
                 node_attrs=attrs)
    import jax
    jax.block_until_ready(index.modalities["text"].ivf.data)
    m = index.modalities["text"]
    log(f"built: vectors={int(m.ids.shape[0])} dim={vecs.shape[1]} "
        f"K={m.ivf.n_partitions} cap={m.ivf.capacity} "
        f"delta_rows={int(m.delta.count)} "
        f"edges={0 if index.graph is None else index.graph.n_edges} "
        f"nodes={corpus.n_nodes} memory_usage={index.memory_usage()}")
    return index


def serve(service, plan, q):
    import jax
    out = service.search_many(plan, q)
    check(out is not None, "admission rejected the batch")
    jax.block_until_ready(out)
    return out


def search_phase(service, plan, q):
    t0 = time.perf_counter()
    sv, si = serve(service, plan, q)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    sv2, si2 = serve(service, plan, q)
    warm = time.perf_counter() - t0
    import numpy as np
    check(np.array_equal(sv, sv2) and np.array_equal(si, si2),
          "repeated search_many returned different results")
    check(np.all(np.isfinite(sv)) and np.all(si >= 0),
          "vector search returned empty slots")
    log(f"search_many: batch={q.shape[0]} first_call_s={cold:.3f} "
        f"second_call_s={warm:.3f}")
    return sv, si


def hybrid_phase(index, service, q, attrs, batch):
    import numpy as np
    from repro.serving.retrieval import RetrievalPlan, freeze_where
    k = index.cfg.top_k
    for sel, where in SELECTIVITIES.items():
        plan = RetrievalPlan("text", k, where=freeze_where(where))
        _, fi = serve(service, plan, q)
        n_f = check_predicate(fi, attrs, where, f"filtered sel={sel}")
        hv, hi = index.hybrid_search(q[:batch], "text", k=k,
                                     n_hops=index.cfg.max_hops, where=where)
        hv, hi = np.asarray(hv), np.asarray(hi)
        check(np.all(np.isfinite(hv[hi >= 0])), "hybrid: non-finite scores")
        n_h = check_predicate(hi, attrs, where, f"hybrid sel={sel}")
        log(f"where {where} (selectivity {sel}): filtered results={n_f} "
            f"hybrid results={n_h}, all satisfy the predicate")


def writes_phase(index, service, corpus, n, seed):
    import numpy as np
    from repro.serving.retrieval import RetrievalPlan
    rng = np.random.default_rng(seed + 3)
    k = index.cfg.top_k
    plan = RetrievalPlan("text", k)
    d = corpus.vectors["text"].shape[1]
    new_ids = corpus.node_ids["text"][n:n + N_HELD_OUT]
    new_vecs = corpus.vectors["text"][n:n + N_HELD_OUT]
    pick = rng.choice(n, N_UPDATE + N_DELETE, replace=False).astype(np.int32)
    upd_ids, del_ids = pick[:N_UPDATE], pick[N_UPDATE:]
    upd_vecs = rng.normal(size=(N_UPDATE, d)).astype(np.float32)
    old_upd = corpus.vectors["text"][upd_ids]
    del_vecs = corpus.vectors["text"][del_ids]
    index.insert("text", new_ids, new_vecs)
    index.insert("text", upd_ids, upd_vecs)
    index.delete("text", del_ids)
    report = index.maintain("text")
    log(f"writes: inserted={N_HELD_OUT} updated={N_UPDATE} "
        f"deleted={N_DELETE} maintain: {report.describe()}")

    _, si = serve(service, plan, new_vecs)
    check(np.array_equal(si[:, 0], new_ids),
          "an inserted vector is not its own top-1")
    sv, si = serve(service, plan, upd_vecs)
    check(np.array_equal(si[:, 0], upd_ids),
          "an updated vector is not its own top-1")
    sv, si = serve(service, plan, old_upd)
    stale = (si == upd_ids[:, None]) & (sv > 0.99)
    check(not stale.any(), "a superseded version was served")
    _, si = serve(service, plan, del_vecs)
    check(not np.isin(si, del_ids).any(), "a deleted id was returned")
    log("read-back: inserts and updates found as their own top-1, "
        "superseded versions and deleted ids never returned")
    return del_ids


# ------------------------------------------------------------------- modes
def run_one_chip(args, sizes, dev):
    import numpy as np
    from repro.configs.hmgi import CONFIG
    from repro.core import HMGIIndex
    from repro.serving.retrieval import RetrievalPlan, RetrievalService
    n, d, batch = sizes["n_vectors"], sizes["dim"], sizes["batch"]
    ph = Phases(dev)
    cfg = CONFIG.replace(modalities=("text",), modality_dims={"text": d})
    corpus, attrs = ph.run("data", make_data, n, d, args.seed,
                           with_graph=True)
    q = make_queries(corpus, batch, args.seed)
    index = ph.run("build", build, HMGIIndex, cfg, corpus, attrs, n,
                   graph=True)
    from repro import obs
    log(f"louvain (host, inside build): "
        f"wall_s={obs.histogram('index.communities').summary()['max'] / 1e3:.3f}")
    service = RetrievalService(index, batching=False)
    plan = RetrievalPlan("text", cfg.top_k)
    sv, si = ph.run("search", search_phase, service, plan, q)
    ph.run("kernel_path", assert_kernel_path, index, q, args.rehearse)
    ph.run("check", check_against_reference, index, q, sv, si, cfg.top_k)
    ph.run("recall", recall_vs_brute_force, index, q, si, cfg.top_k)
    hb = hybrid_batch(index, batch)
    ph.run("hybrid", hybrid_phase, index, service, q, attrs, hb)
    del_ids = ph.run("writes", writes_phase, index, service, corpus, n,
                     args.seed)
    _, si = serve(service, plan, q)
    check(not np.isin(si, del_ids).any(), "a deleted id was returned")


def run_sharded(args, sizes, dev):
    """--chips 4: the sharded stable scan (planner-chosen layout on a
    ("data",) mesh) against the single-device scan of the same index, in
    this process. Bit-identical scores; ids may permute only across exact
    ties. Vectors only: the graph plays no part in the stable scan."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs.hmgi import CONFIG
    from repro.core import HMGIIndex
    from repro.core import ivf as ivf_mod
    n, d, batch = sizes["n_vectors"], sizes["dim"], sizes["batch"]
    devs = jax.devices()
    check(len(devs) >= args.chips, f"--chips {args.chips} but JAX sees "
          f"{len(devs)} devices")
    mesh = Mesh(np.array(devs[:args.chips]), ("data",))
    ph = Phases(dev)
    cfg = CONFIG.replace(modalities=("text",), modality_dims={"text": d})
    if args.rehearse:   # tiny slab: force the layout the full size gets
        cfg = cfg.replace(shard_device_budget_bytes=1 << 16)
    corpus, attrs = ph.run("data", make_data, n, d, args.seed,
                           with_graph=False)
    q = make_queries(corpus, batch, args.seed)
    index = ph.run("build", build, HMGIIndex, cfg, corpus, attrs, n,
                   graph=False, mesh=mesh)
    lay = index.device_layout("text")
    check(lay.layout == "sharded" and lay.n_shards == args.chips,
          f"planner chose {lay} for the serve_1m slab on {args.chips} chips")
    log(f"device layout: {lay}")

    def both(label, where=None):
        index.cfg = cfg
        sv, si = index.search(q, "text", where=where)
        index.cfg = cfg.replace(shard_layout="single")
        rv, ri = index.search(q, "text", where=where)
        index.cfg = cfg
        sv, si, rv, ri = map(np.asarray, (sv, si, rv, ri))
        check(np.array_equal(sv, rv),
              f"{label}: sharded scores are not bit-identical")
        for i in range(q.shape[0]):
            for s in np.unique(sv[i]):
                at = sv[i] == s
                if s == sv[i, -1]:
                    continue          # the cut may split an exact tie
                check(set(si[i, at]) == set(ri[i, at]),
                      f"{label}: query {i} ids differ beyond exact ties")
        log(f"{label}: sharded == single-device over {q.shape[0]} queries "
            f"(scores bit-identical)")

    ph.run("sharded_search", both, "vector search")
    m = index.modalities["text"]
    sh = index._ensure_sharded("text", args.chips)
    txt = jax.jit(functools.partial(
        ivf_mod.search_sharded, mesh=mesh, n_probe=cfg.n_probe,
        k=cfg.top_k)).lower(sh, index._norm_queries(q)).compile().as_text()
    if not args.rehearse:
        check("tpu_custom_call" in txt, "sharded scan holds no Mosaic kernel")
    log(f"sharded step: mosaic_calls={txt.count('tpu_custom_call')} "
        f"all_gather={'all-gather' in txt}")
    rng = np.random.default_rng(args.seed + 4)
    index.delete("text", rng.choice(n, N_DELETE, replace=False)
                 .astype(np.int32))
    ph.run("sharded_search_mvcc", both, "after deletes",
           where=SELECTIVITIES[0.5])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend (Pallas interpret mode "
                         "off-TPU); never reports ok")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: the repository's sources are not at {SRC}")
    sys.path.insert(0, str(SRC))

    import jax
    from repro.common.compile_cache import enable_compile_cache
    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: JAX found no TPU (backend {backend!r}); "
                 "the smoke runs on the chip only (--rehearse for a tiny "
                 "CPU rehearsal)")
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    sizes = REHEARSE if args.rehearse else SERVE_1M
    log(f"device: {info} backend={backend} compile_cache={cache} "
        f"sizes={sizes} seed={args.seed}")
    if args.chips == 4:
        run_sharded(args, sizes, dev)
    else:
        run_one_chip(args, sizes, dev)
    if args.rehearse:
        log("rehearsal passed (sizes cut; not a chip result)")
        return
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
