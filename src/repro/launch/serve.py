"""Serving launcher: builds an HMGI index over a synthetic multimodal corpus
and serves batched hybrid queries, then an ingest-while-search phase
(streaming inserts/deletes interleaved with queries, adaptive maintenance
draining the delta in bounded steps between batches) and optional RAG
generation with maintenance paced between decode steps.

``python -m repro.launch.serve --n-nodes 2000 --queries 64 [--rag]``

Durability: ``--data-dir DIR`` makes the index durable (write-ahead op log +
periodic snapshots under DIR); ``--recover`` restarts from DIR's latest
valid snapshot plus log-tail replay instead of rebuilding — search results
are bit-identical to the pre-crash index.

Observability: all phase timings come from the ``repro.obs`` registry
(spans feed named histograms; see docs/ARCHITECTURE.md). ``--metrics-out
FILE`` dumps the full registry snapshot as JSON at exit.
"""
from __future__ import annotations

import argparse
import json

import numpy as np
import jax

from repro import obs
from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_config
from repro.core import HMGIIndex
from repro.data.synthetic import ground_truth_topk, make_corpus, recall_at_k


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-nodes", type=int, default=2000)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--hops", type=int, default=2)
    ap.add_argument("--rag", action="store_true")
    ap.add_argument("--ingest-steps", type=int, default=4,
                    help="ingest-while-search streaming steps (0 = skip)")
    ap.add_argument("--data-dir", type=str, default=None,
                    help="durable mode: op-log + snapshot under this dir")
    ap.add_argument("--recover", action="store_true",
                    help="recover from --data-dir instead of rebuilding")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the obs registry snapshot (JSON) here at exit")
    args = ap.parse_args()
    if args.recover and not args.data_dir:
        ap.error("--recover requires --data-dir")
    enable_compile_cache()

    cfg = get_config("hmgi").replace(n_partitions=32, n_probe=8,
                                     kmeans_iters=8, top_k=args.k)
    corpus = make_corpus(n_nodes=args.n_nodes,
                         modality_dims={"text": 64, "image": 96})
    hist = lambda name: obs.histogram(name).summary()
    if args.recover:
        from repro.persistence import recover
        with obs.span("serve.recover"):
            index = recover(cfg, args.data_dir, seed=0)
        print(f"recover: {hist('serve.recover')['max']/1e3:.2f}s  "
              f"[{index.metrics()['recovery']}]")
    else:
        if args.data_dir:
            from repro.persistence import DurableHMGIIndex
            index = DurableHMGIIndex(cfg, args.data_dir, seed=0)
        else:
            index = HMGIIndex(cfg, seed=0)
        with obs.span("serve.ingest_build"):
            index.ingest({m: (corpus.node_ids[m], corpus.vectors[m])
                          for m in corpus.vectors}, n_nodes=corpus.n_nodes,
                         edges=(corpus.src, corpus.dst, corpus.edge_type))
        print(f"ingest+build: {hist('serve.ingest_build')['max']/1e3:.2f}s  "
              f"memory: {index.memory_usage()['total']/2**20:.1f} MiB")

    rng = np.random.default_rng(1)
    sel = rng.integers(0, len(corpus.vectors["text"]), args.queries)
    q = corpus.vectors["text"][sel] + 0.05 * rng.normal(
        size=(args.queries, 64)).astype(np.float32)

    with obs.span("serve.vector_batch") as sp:
        sv, si = index.search(q, "text", k=args.k)
        jax.block_until_ready(sv)
        sp.fence(sv)
    truth = ground_truth_topk(corpus.vectors["text"], corpus.node_ids["text"],
                              q, args.k)
    print(f"vector search: "
          f"{hist('serve.vector_batch')['max']/args.queries:.3f} ms/q  "
          f"recall@{args.k}={recall_at_k(np.asarray(si), truth):.3f}")

    with obs.span("serve.hybrid_batch") as sp:
        hv, hi = index.hybrid_search(q, "text", k=args.k, n_hops=args.hops)
        jax.block_until_ready(hv)
        sp.fence(hv)
    print(f"hybrid search ({args.hops} hops): "
          f"{hist('serve.hybrid_batch')['max']/args.queries:.3f} ms/q")

    # ingest-while-search: streaming writes interleaved with queries; the
    # adaptive maintenance hooks (insert/delete auto-trigger) drain the
    # delta in bounded steps instead of stop-the-world compactions. Worst
    # write stall = the max of the per-step "serve.ingest_step" histogram.
    if args.ingest_steps > 0:
        batch = max(args.n_nodes // 20, 8)
        for step in range(args.ingest_steps):
            wid = rng.integers(0, args.n_nodes, batch).astype(np.int32)
            wv = rng.normal(size=(batch, 64)).astype(np.float32)
            with obs.span("serve.ingest_step"):
                index.insert("text", wid, wv)
                index.delete("text", wid[:batch // 8])
            sv2, _ = index.search(q[:8], "text", k=args.k)
            jax.block_until_ready(sv2)
        m = index.modalities["text"]
        print(f"ingest-while-search: {args.ingest_steps} steps x {batch} "
              f"writes, worst write stall "
              f"{hist('serve.ingest_step')['max']:.1f} ms, "
              f"delta={int(m.delta.count)}  "
              f"maintenance: {index.metrics().get('maintenance', 'n/a')}")

    if args.data_dir:
        with obs.span("serve.snapshot"):
            path = index.snapshot()
        print(f"snapshot: {hist('serve.snapshot')['max']/1e3:.2f}s -> {path}  "
              f"(last_seq={index.last_seq})")

    if args.rag:
        from repro.models import lm
        from repro.serving.engine import EngineConfig, RAGEngine
        lcfg = smoke_config("phi4-mini-3.8b")
        params, _ = lm.init_lm(lcfg, jax.random.PRNGKey(0))
        eng = RAGEngine(lcfg, params, index,
                        EngineConfig(n_slots=4, max_seq=64, retrieve_k=4,
                                     snapshot_interval=32))
        rids = eng.retrieve(q[:4])
        for i in range(4):
            eng.submit(i, rng.integers(0, lcfg.vocab_size, 8), rids[i], 8)
        gen = eng.run_to_completion()
        print(f"RAG generated: { {k: len(v) for k, v in gen.items()} } "
              f"stats={eng.stats}")

    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(obs.snapshot(), f, indent=2)
        print(f"metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
