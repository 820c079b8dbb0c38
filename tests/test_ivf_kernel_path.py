"""Kernel-backed IVF probe path + candidate-sparse fusion.

Parity: the fused-Pallas probe path must agree with the legacy fp32
gather-dequant einsum path (both score the same quantized rows, so scores
match to fp rounding and ids match wherever scores are distinct), and both
must hit brute-force recall at full probe. Fusion: the sparse candidate
formulation must reproduce dense fusion exactly, and its jaxpr must contain
no intermediate sized by n_nodes (the memory claim, checked structurally).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import delta as delta_mod
from repro.core import index as index_mod
from repro.core import ivf as ivf_mod
from repro.core.fusion import FusionWeights, fuse_topk, fuse_topk_sparse
from repro.core.quantization import quantize
from repro.kernels.ivf_topk import ops as ivf_ops
from repro.kernels.ivf_topk.ref import scan_probed_ref


def _corpus(rng, n, d):
    v = rng.normal(size=(n, d)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v


class TestKernelProbeParity:
    @pytest.mark.parametrize("n,d,k_parts,n_probe", [(1500, 48, 12, 3),
                                                     (3000, 96, 16, 16)])
    def test_kernel_matches_einsum(self, n, d, k_parts, n_probe, rng):
        v = _corpus(rng, n, d)
        idx, _ = ivf_mod.build(jax.random.PRNGKey(0), jnp.asarray(v),
                               jnp.arange(n), n_partitions=k_parts, bits=8)
        q = jnp.asarray(v[:24] + 0.02 * rng.normal(size=(24, d)).astype(np.float32))
        se, ie = ivf_mod.search(idx, q, n_probe=n_probe, k=10, impl="einsum")
        sk, ik = ivf_mod.search(idx, q, n_probe=n_probe, k=10, impl="kernel")
        np.testing.assert_allclose(np.asarray(sk), np.asarray(se),
                                   rtol=2e-5, atol=2e-5)
        assert np.mean(np.asarray(ik) == np.asarray(ie)) > 0.99

    def test_full_probe_matches_brute_force_recall(self, rng):
        n, d = 1500, 48
        v = _corpus(rng, n, d)
        idx, over = ivf_mod.build(jax.random.PRNGKey(0), jnp.asarray(v),
                                  jnp.arange(n), n_partitions=12, bits=8)
        q = jnp.asarray(v[:32] + 0.02 * rng.normal(size=(32, d)).astype(np.float32))
        bv, bi = ivf_mod.brute_force(jnp.asarray(v), ~over, jnp.arange(n), q, k=10)
        _, ik = ivf_mod.search(idx, q, n_probe=12, k=10, impl="kernel")
        hits = np.mean([len(set(map(int, a)) & set(map(int, b))) / 10
                        for a, b in zip(np.asarray(ik), np.asarray(bi))])
        assert hits > 0.9   # limited only by int8 quantization

    def test_empty_slots_and_overflow_k(self, rng):
        """Mostly-empty slab: no empty slot ever surfaces, tail pads -1."""
        n, d = 50, 32
        v = _corpus(rng, n, d)
        idx, over = ivf_mod.build(jax.random.PRNGKey(1), jnp.asarray(v),
                                  jnp.arange(n), n_partitions=8, bits=8)
        stored = int(np.sum(~np.asarray(over)))
        q = jnp.asarray(v[:4])
        sk, ik = ivf_mod.search(idx, q, n_probe=8, k=60, impl="kernel")
        ik, sk = np.asarray(ik), np.asarray(sk)
        for row_i, row_s in zip(ik, sk):
            live = row_i[row_i >= 0]
            assert len(live) == stored
            assert len(set(live.tolist())) == len(live)     # no dupes
            assert np.all(np.isneginf(row_s[row_i < 0]))    # dead ⇒ -inf

    @pytest.mark.parametrize("qn,k_parts,cap,n_probe,block_n,skew", [
        (6, 4, 512, 2, 128, False),      # whole blocks
        (7, 8, 300, 3, 128, False),      # partial last block (44 of 128)
        (5, 3, 260, 2, 128, False),      # last block's rows short of a slab
        (40, 5, 96, 1, 96, True),        # one partition, 5 groups of 8
        (3, 6, 64, 2, 64, False),        # most partitions unprobed
    ])
    def test_probed_kernel_matches_ref(self, rng, qn, k_parts, cap, n_probe,
                                       block_n, skew):
        """Stage 1 of the probe path — pairs grouped partition-major, the
        slab read in place, survivors put back per (query, probe) — gives
        the oracle's chunk maxima for every pair; dead and past-cap rows
        read NEG in both."""
        d, chunk = 64, 16
        v = rng.normal(size=(k_parts * cap, d)).astype(np.float32)
        qv = quantize(jnp.asarray(v), 8)
        data = qv.data.reshape(k_parts, cap, d)
        vmin = qv.vmin[:, 0].reshape(k_parts, cap)
        scale = qv.scale[:, 0].reshape(k_parts, cap)
        valid = jnp.asarray(rng.random((k_parts, cap)) < 0.7)
        queries = jnp.asarray(rng.normal(size=(qn, d)).astype(np.float32))
        if skew:
            probes = jnp.zeros((qn, n_probe), jnp.int32)
        else:
            probes = jnp.asarray(np.stack([
                rng.choice(min(k_parts, 2 * n_probe), n_probe, replace=False)
                for _ in range(qn)]).astype(np.int32))
        got = ivf_ops.probe_chunk_max(queries, data, vmin, scale, valid,
                                      probes, chunk=chunk, block_n=block_n,
                                      interpret=True)
        want = scan_probed_ref(queries, data, vmin, scale, valid, probes,
                               chunk=chunk, block_n=block_n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_delta_scan_matches_brute_force(self, rng):
        """Quantized delta scan + fp32 rescore == exact brute force (the
        delta is smaller than k + margin, so rescore covers every row)."""
        d = 32
        v = _corpus(rng, 20, d)
        store = delta_mod.init(32, d, max_ids=64)
        store = delta_mod.insert(store, jnp.asarray(v), jnp.arange(20))
        q = jnp.asarray(_corpus(rng, 5, d))
        dv, di = delta_mod._scan_delta(store, q, k=5)
        bv, bi = ivf_mod.brute_force(jnp.asarray(v), jnp.ones(20, bool),
                                     jnp.arange(20), q, k=5)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(bv),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(di), np.asarray(bi))

    def test_search_sharded_single_device(self, rng):
        """1-shard mesh: sharded search (kernel path inside shard_map) must
        reproduce the local result bit-for-bit."""
        from jax.sharding import Mesh
        n, d = 512, 32
        v = _corpus(rng, n, d)
        idx, _ = ivf_mod.build(jax.random.PRNGKey(2), jnp.asarray(v),
                               jnp.arange(n), n_partitions=8, bits=8)
        leaves = jax.tree_util.tree_map(lambda a: a[None], idx)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        q = jnp.asarray(v[:8])
        sv, si = ivf_mod.search_sharded(leaves, q, mesh, n_probe=8, k=5)
        se, ie = ivf_mod.search(idx, q, n_probe=8, k=5)
        np.testing.assert_allclose(np.asarray(sv), np.asarray(se))
        np.testing.assert_array_equal(np.asarray(si), np.asarray(ie))

    def test_delta_tombstones_respected(self, rng):
        d = 16
        v = _corpus(rng, 8, d)
        store = delta_mod.init(16, d, max_ids=32)
        store = delta_mod.insert(store, jnp.asarray(v), jnp.arange(8))
        store = delta_mod.delete(store, jnp.asarray([0, 3]))
        _, di = delta_mod._scan_delta(store, jnp.asarray(v), k=8)
        assert not np.any(np.isin(np.asarray(di), [0, 3]))


def _quantized_oracle(idx, q, probes, k, visible=None):
    """Exact top-k over the quantized rows each query probes (float64,
    host): (scores (Q, k) -inf padded, per-query {id: score} of every
    candidate)."""
    ids = np.asarray(idx.ids)
    e = ((np.asarray(idx.data, np.float64) + 128.0)
         * np.asarray(idx.scale, np.float64)[..., None]
         + np.asarray(idx.vmin, np.float64)[..., None])
    ok = ids >= 0
    if visible is not None:
        ok &= np.asarray(visible)[np.maximum(ids, 0)]
    top, cands = [], []
    for qi, pr in zip(np.asarray(q, np.float64), np.asarray(probes)):
        sc = np.einsum("pcd,d->pc", e[pr], qi)[ok[pr]]
        cands.append(dict(zip(ids[pr][ok[pr]].tolist(), sc.tolist())))
        row = np.full(k, -np.inf)
        best = np.sort(sc)[::-1][:k]
        row[:len(best)] = best
        top.append(row)
    return np.stack(top), cands


def _assert_exact(scores, ids, oracle, tol=2e-5):
    """Served (scores, ids) are the oracle's top-k: the same scores, and
    every id a distinct candidate whose exact score is the one served."""
    top, cands = oracle
    scores, ids = np.asarray(scores), np.asarray(ids)
    np.testing.assert_allclose(scores, top, rtol=tol, atol=tol)
    for row_s, row_i, cand in zip(scores, ids, cands):
        live = np.isfinite(row_s)
        assert np.all(row_i[~live] == -1)
        assert len(set(row_i[live].tolist())) == int(live.sum())
        for sv, iv in zip(row_s[live], row_i[live]):
            assert iv in cand and abs(cand[iv] - sv) <= tol * (1 + abs(sv))


class TestProbedScanExact:
    """The partition-major probe path equals the einsum path, and the exact
    top-k over the quantized rows each query probes (the brute-force
    oracle at full probe), across the grouping's edge cases."""

    @staticmethod
    def _index(rng, n, d, k_parts, capacity=None, seed=0):
        v = _corpus(rng, n, d)
        idx, _ = ivf_mod.build(jax.random.PRNGKey(seed), jnp.asarray(v),
                               jnp.arange(n), n_partitions=k_parts, bits=8,
                               capacity=capacity)
        return v, idx

    @pytest.mark.parametrize("qn", [1, 7, 64, 200])
    def test_query_counts(self, rng, qn):
        v, idx = self._index(rng, 1500, 32, 12)
        q = jnp.asarray(v[rng.integers(0, 1500, qn)]
                        + 0.05 * rng.normal(size=(qn, 32)).astype(np.float32))
        probes, _ = ivf_mod.partitioner.assign_topk(q, idx.centroids, 3)
        sk, ik = ivf_mod.search(idx, q, n_probe=3, k=10, impl="kernel")
        se, ie = ivf_mod.search(idx, q, n_probe=3, k=10, impl="einsum")
        np.testing.assert_allclose(np.asarray(sk), np.asarray(se),
                                   rtol=2e-5, atol=2e-5)
        _assert_exact(sk, ik, _quantized_oracle(idx, q, probes, 10))
        _assert_exact(se, ie, _quantized_oracle(idx, q, probes, 10))

    @pytest.mark.parametrize("qn", [7, 64])
    def test_full_probe_is_brute_force(self, rng, qn):
        v, idx = self._index(rng, 800, 32, 8)
        q = jnp.asarray(_corpus(rng, qn, 32))
        probes = jnp.tile(jnp.arange(8, dtype=jnp.int32), (qn, 1))
        sk, ik = ivf_mod.search(idx, q, n_probe=8, k=10, impl="kernel")
        _assert_exact(sk, ik, _quantized_oracle(idx, q, probes, 10))

    @pytest.mark.parametrize("case", ["skewed", "unprobed"])
    def test_probe_lists(self, rng, case):
        """skewed: every query probes one partition, whose pairs span
        several groups; unprobed: most partitions are probed by no query."""
        v, idx = self._index(rng, 1500, 32, 12)
        qn = 40 if case == "skewed" else 3
        q = jnp.asarray(_corpus(rng, qn, 32))
        if case == "skewed":
            probes = jnp.full((qn, 1), 5, jnp.int32)
        else:
            probes = jnp.asarray([[1, 3], [3, 5], [5, 1]], jnp.int32)
        n_probe = probes.shape[1]
        sk, ik = ivf_mod.search(idx, q, n_probe=n_probe, k=10, impl="kernel",
                                probes=probes)
        se, ie = ivf_mod.search(idx, q, n_probe=n_probe, k=10, impl="einsum",
                                probes=probes)
        np.testing.assert_allclose(np.asarray(sk), np.asarray(se),
                                   rtol=2e-5, atol=2e-5)
        _assert_exact(sk, ik, _quantized_oracle(idx, q, probes, 10))

    @pytest.mark.parametrize("block_n", [None, 32])
    def test_partial_last_block_with_live_last_slot(self, rng, block_n):
        """cap 50 is a multiple of no block; every partition is full, so
        its last slot is live, and must be found."""
        v, idx = self._index(rng, 600, 32, 4, capacity=50)
        assert bool(jnp.all(idx.ids[:, -1] >= 0))
        q = jnp.asarray(np.asarray(idx.data[:, -1], np.float32) + 128.0)
        probes = jnp.asarray([[0, 1], [1, 2], [2, 3], [3, 0]], jnp.int32)
        sv, part, slot = ivf_ops.scan_topk_probed(
            q, idx.data, idx.vmin, idx.scale, idx.ids >= 0, probes, k=5,
            block_n=block_n)
        ids = jnp.where(slot >= 0,
                        idx.ids[part, jnp.maximum(slot, 0)], -1)
        _assert_exact(sv, ids, _quantized_oracle(idx, q, probes, 5))
        assert set(np.asarray(slot).ravel().tolist()) >= {49}

    @pytest.mark.parametrize("case", ["node_pass", "mvcc_dead"])
    def test_validity_folds_into_the_scan(self, rng, case):
        """node_pass pushdown and MVCC-dead rows are left out before the
        top-k, never after it."""
        n, d = 1500, 32
        v, idx = self._index(rng, n, d, 12)
        q = jnp.asarray(v[:16] + 0.02 * rng.normal(size=(16, d)).astype(
            np.float32))
        probes, _ = ivf_mod.partitioner.assign_topk(q, idx.centroids, 4)
        if case == "node_pass":
            visible = jnp.asarray(rng.random(n) < 0.3)
            sk, ik = ivf_mod.search(idx, q, n_probe=4, k=10, impl="kernel",
                                    node_pass=visible)
            se, ie = ivf_mod.search(idx, q, n_probe=4, k=10, impl="einsum",
                                    node_pass=visible)
        else:
            store = delta_mod.init(64, d, max_ids=n)
            dead = jnp.asarray(rng.choice(n, 300, replace=False))
            store = delta_mod.delete(store, dead)
            visible = ~store.tombstones
            run = functools.partial(delta_mod.search_with_delta, idx, store,
                                    q, n_probe=4, k=10, probes=probes)
            sk, ik = run(impl="kernel")
            se, ie = run(impl="einsum")
            assert not np.any(np.isin(np.asarray(ik), np.asarray(dead)))
        np.testing.assert_allclose(np.asarray(sk), np.asarray(se),
                                   rtol=2e-5, atol=2e-5)
        _assert_exact(sk, ik, _quantized_oracle(idx, q, probes, 10, visible))

    @pytest.mark.parametrize("case", ["k_over_fill", "k_chunks_over_rows"])
    def test_k_beyond_the_rows(self, rng, case):
        """k larger than a partition's fill, and k·chunk larger than the
        rows probed: every live row comes back once, the rest pads."""
        n, k = (50, 30) if case == "k_over_fill" else (200, 40)
        v, idx = self._index(rng, n, 16, 8, seed=1)
        q = jnp.asarray(v[:4])
        probes, _ = ivf_mod.partitioner.assign_topk(q, idx.centroids, 2)
        rows = int(jnp.max(jnp.sum(idx.ids[probes] >= 0, axis=(1, 2))))
        assert rows < k * 16 and int(jnp.min(idx.counts)) < k
        sk, ik = ivf_mod.search(idx, q, n_probe=2, k=k, impl="kernel")
        se, ie = ivf_mod.search(idx, q, n_probe=2, k=k, impl="einsum")
        np.testing.assert_allclose(np.asarray(sk), np.asarray(se),
                                   rtol=2e-5, atol=2e-5)
        _assert_exact(sk, ik, _quantized_oracle(idx, q, probes, k))


class TestSparseFusion:
    def _dense_reference(self, vs, vi, graph_scores, wv, wg, n_nodes, k_fuse):
        """The pre-refactor dense formulation, verbatim."""
        sim_full = jnp.full((vs.shape[0], n_nodes), -jnp.inf)
        rows = jnp.arange(vs.shape[0])[:, None]
        sim_full = sim_full.at[rows, jnp.clip(vi, 0, n_nodes - 1)].set(
            jnp.where(vi >= 0, vs, -jnp.inf))
        w = FusionWeights(wv, wg)
        return fuse_topk(sim_full, graph_scores, w, k_fuse)

    def test_sparse_equals_dense_fuse_topk(self, rng):
        q_n, n = 6, 400
        sim = jnp.asarray(rng.normal(size=(q_n, n)).astype(np.float32))
        sim = jnp.where(jnp.asarray(rng.random((q_n, n)) < 0.9), -jnp.inf, sim)
        g = jnp.asarray(np.abs(rng.normal(size=(q_n, n))).astype(np.float32))
        w = FusionWeights(jnp.full((q_n,), 0.6), jnp.full((q_n,), 0.4))
        dv, dp = fuse_topk(sim, g, w, 10)
        sv, sp = fuse_topk_sparse(sim, g, w, 10,
                                  graph_max=jnp.max(g, axis=1, keepdims=True),
                                  valid=jnp.ones((q_n, n), bool))
        np.testing.assert_allclose(np.asarray(sv), np.asarray(dv), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(sp), np.asarray(dp))

    def test_candidate_union_equals_dense(self, rng):
        q_n, n, k_seed, k = 5, 500, 12, 6
        vs = jnp.sort(jnp.asarray(
            rng.random((q_n, k_seed)).astype(np.float32)), axis=1)[:, ::-1]
        vi = jnp.asarray(
            np.stack([rng.choice(n, k_seed, replace=False)
                      for _ in range(q_n)]).astype(np.int32))
        g = jnp.asarray(np.abs(rng.normal(size=(q_n, n))).astype(np.float32))
        wv = jnp.full((q_n,), 0.55)
        wg = jnp.full((q_n,), 0.45)
        k_fuse = 4 * k
        dv, di = self._dense_reference(vs, vi, g, wv, wg, n, k_fuse)
        sv, si = index_mod._fuse_candidates(vs, vi, g, wv, wg, k_fuse=k_fuse,
                                            frontier=k_fuse + k_seed)
        np.testing.assert_allclose(np.asarray(sv), np.asarray(dv),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(di))

    def test_candidate_union_with_invalid_seeds(self, rng):
        q_n, n, k_seed = 3, 300, 8
        vs = jnp.asarray(rng.random((q_n, k_seed)).astype(np.float32))
        vi = jnp.asarray(
            np.stack([rng.choice(n, k_seed, replace=False)
                      for _ in range(q_n)]).astype(np.int32))
        vi = vi.at[:, -2:].set(-1)                           # padded seeds
        g = jnp.asarray(np.abs(rng.normal(size=(q_n, n))).astype(np.float32))
        sv, si = index_mod._fuse_candidates(
            vs, vi, g, jnp.full((q_n,), 0.6), jnp.full((q_n,), 0.4),
            k_fuse=10, frontier=40)
        assert bool(jnp.all(jnp.isfinite(sv)))
        assert bool(jnp.all(si >= 0))
        for row in np.asarray(si):
            assert len(set(row.tolist())) == len(row)        # no dupes

    def test_duplicate_seed_ids_collapse(self, rng):
        """NSW-refine merges can surface the same id twice in the seed list;
        fusion must keep one copy (as the dense scatter did), not let the
        duplicate displace the k-th result."""
        q_n, n, k_seed = 2, 100, 4
        vi = jnp.asarray([[7, 7, 3, 1], [5, 2, 5, 2]], jnp.int32)
        vs = jnp.asarray([[.9, .8, .7, .6], [.9, .8, .7, .6]], jnp.float32)
        g = jnp.asarray(np.abs(rng.normal(size=(q_n, n))).astype(np.float32))
        sv, si = index_mod._fuse_candidates(
            vs, vi, g, jnp.full((q_n,), 0.6), jnp.full((q_n,), 0.4),
            k_fuse=4, frontier=8)
        for row in np.asarray(si):
            assert len(set(row.tolist())) == len(row), row

    def test_fusion_stage_memory_independent_of_n_nodes(self):
        """Structural check of the memory claim: no intermediate in the
        fusion jaxpr is sized by n_nodes (only the graph_scores *input* is
        dense; every equation output is candidate-width)."""
        q_n, n_nodes, k_seed = 4, 3331, 12   # distinctive corpus width
        k_fuse, frontier = 20, 32
        fn = functools.partial(index_mod._fuse_candidates,
                               k_fuse=k_fuse, frontier=frontier)
        jaxpr = jax.make_jaxpr(fn)(
            jnp.ones((q_n, k_seed)), jnp.ones((q_n, k_seed), jnp.int32),
            jnp.ones((q_n, n_nodes)), jnp.ones((q_n,)), jnp.ones((q_n,)))

        def walk(jx):
            for eqn in jx.eqns:
                for ov in eqn.outvars:
                    shape = getattr(ov.aval, "shape", ())
                    assert n_nodes not in shape, (eqn.primitive, shape)
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        core = getattr(sub, "jaxpr", None)
                        if hasattr(sub, "eqns"):
                            walk(sub)
                        elif core is not None and hasattr(core, "eqns"):
                            walk(core)

        walk(jaxpr.jaxpr)

    def test_hybrid_search_end_to_end_sparse(self, rng):
        """hybrid_search through the facade stays consistent with a dense
        reference fusion of its own stage outputs."""
        from repro.configs import get_config
        from repro.core import HMGIIndex
        from repro.core import traversal as trav_mod
        from repro.core.fusion import adaptive_weights
        from repro.data.synthetic import make_corpus

        corpus = make_corpus(n_nodes=400, modality_dims={"text": 32}, seed=3)
        cfg = get_config("hmgi").replace(n_partitions=8, n_probe=8, top_k=5,
                                         kmeans_iters=4, delta_capacity=64)
        idx = HMGIIndex(cfg, seed=0)
        idx.ingest({"text": (corpus.node_ids["text"], corpus.vectors["text"])},
                   n_nodes=corpus.n_nodes,
                   edges=(corpus.src, corpus.dst, corpus.edge_type))
        q = corpus.vectors["text"][:6]
        k = 5
        hv, hi = idx.hybrid_search(q, "text", k=k, n_hops=2)

        # dense reference over the same stage-1/2 outputs
        qn = idx._norm_queries(q)
        k_seed = max(2 * k, k + 8)
        vs, vi = idx.search(qn, "text", k=k_seed, n_probe=cfg.n_probe)
        g = idx.graph._replace(edge_weight=idx.boosted_weights) \
            if idx.boosted_weights is not None else idx.graph
        gs = trav_mod.multi_hop_batch(g, vi, vs, n_hops=2)
        w = adaptive_weights(vs, base_wv=cfg.w_vector, base_wg=cfg.w_graph)
        k_fuse = max(k, min(4 * k, corpus.n_nodes))
        ref = TestSparseFusion()._dense_reference(
            vs, vi, gs, w.w_vector, w.w_graph, corpus.n_nodes, k_fuse)
        np.testing.assert_allclose(np.asarray(hv), np.asarray(ref[0][:, :k]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(hi), np.asarray(ref[1][:, :k]))
