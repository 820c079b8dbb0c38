"""Observability layer: registry math, span semantics, exporters, wiring.

Covers the obs package contract: histogram bucket/quantile math against a
numpy oracle, nested span parenting and exception safety, registry reset
isolation, the zero-sync guarantee when ``obs_sync_spans`` is off, the
Prometheus exposition round-trip, and the integration points (facade
``trace=``, ``metrics()["obs"]``, WAL histograms, staticcheck cleanliness
of the instrumented tree).
"""
import json
import subprocess
import sys
import os

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import obs
from repro.obs import metrics as obs_metrics


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts from an empty global registry with sync off."""
    obs.reset()
    obs.set_enabled(True)
    obs.set_sync_spans(False)
    yield
    obs.reset()
    obs.set_enabled(True)
    obs.set_sync_spans(False)


# ---------------------------------------------------------------- histograms
def test_histogram_buckets_match_manual_count(rng):
    h = obs.histogram("t.lat")
    xs = rng.gamma(2.0, 5.0, size=500)          # ms-ish latencies
    for x in xs:
        h.observe(x)
    cum = h.cumulative_buckets()
    for le, got in cum:
        assert got == int(np.sum(xs <= le)), f"bucket le={le}"
    assert cum[-1][1] == len(xs)                 # +inf holds everything
    assert h.count == len(xs)
    assert h.total == pytest.approx(float(np.sum(xs)))
    assert h.vmax == pytest.approx(float(np.max(xs)))


def test_histogram_quantiles_match_numpy_oracle(rng):
    h = obs.histogram("t.q")
    xs = rng.normal(50.0, 10.0, size=1000)
    for x in xs:
        h.observe(x)
    for p in (50, 90, 99, 0, 100, 37.5):
        assert h.percentile(p) == pytest.approx(float(np.percentile(xs, p)))


def test_histogram_window_keeps_newest(rng):
    """Past the ring window, quantiles are over the newest `window`
    observations — old samples age out."""
    h = obs.histogram("t.w")
    n = obs_metrics.DEFAULT_WINDOW
    for _ in range(n):
        h.observe(1.0)
    for _ in range(n):
        h.observe(100.0)
    assert h.percentile(50) == pytest.approx(100.0)   # old 1.0s aged out
    assert h.count == 2 * n                           # totals never age
    assert h.cumulative_buckets()[-1][1] == 2 * n


def test_histogram_empty_and_bad_buckets():
    h = obs.histogram("t.e")
    assert np.isnan(h.percentile(50))
    assert np.isnan(h.summary()["p99"])
    with pytest.raises(ValueError):
        obs_metrics.Histogram("bad", buckets=(5.0, 1.0, float("inf")))
    with pytest.raises(ValueError):
        obs_metrics.Histogram("bad", buckets=(1.0, 5.0))   # no +inf


def test_counter_gauge_and_disable():
    obs.counter("t.c").inc()
    obs.counter("t.c").inc(3)
    obs.gauge("t.g").set(7)
    assert obs.registry().counter("t.c").value == 4
    assert obs.registry().gauge("t.g").value == 7
    obs.set_enabled(False)
    obs.counter("t.c").inc(100)
    obs.gauge("t.g").set(0)
    obs.histogram("t.h").observe(1.0)
    obs.set_enabled(True)
    snap = obs.snapshot()
    assert snap["counters"]["t.c"] == 4          # disabled writes dropped
    assert snap["gauges"]["t.g"] == 7
    assert snap["histograms"]["t.h"]["count"] == 0


def test_registry_reset_between_tests_part1():
    obs.counter("leak.check").inc()


def test_registry_reset_between_tests_part2():
    # runs after part1; the autouse fixture must have wiped its counter
    assert "leak.check" not in obs.snapshot()["counters"]


# --------------------------------------------------------------------- spans
def test_span_records_duration_histogram():
    with obs.span("t.span"):
        pass
    h = obs.registry().histogram("t.span")
    assert h.count == 1
    assert h.vmax >= 0.0


def test_nested_span_parenting():
    with obs.trace() as t:
        with obs.span("outer"):
            with obs.span("inner.a"):
                pass
            with obs.span("inner.b"):
                with obs.span("leaf"):
                    pass
    root = t.root
    assert root.name == "outer"
    assert [c.name for c in root.children] == ["inner.a", "inner.b"]
    assert [c.name for c in root.children[1].children] == ["leaf"]
    assert t.find("leaf") is not None
    # every node carries a recorded duration
    assert all(np.isfinite(n.duration_ms)
               for n in [root, *root.children, root.children[1].children[0]])
    # the render is one line per span, indented by depth
    lines = t.render().splitlines()
    assert len(lines) == 4 and lines[0].startswith("outer")
    assert lines[1].startswith("  inner.a")


def test_span_closed_and_recorded_on_raise():
    with pytest.raises(RuntimeError):
        with obs.trace() as t:
            with obs.span("boom"):
                raise RuntimeError("x")
    h = obs.registry().histogram("boom")
    assert h.count == 1                          # duration still recorded
    node = t.find("boom")
    assert node.error == "RuntimeError"
    # the per-thread stack unwound: a fresh span is a root again
    with obs.trace() as t2:
        with obs.span("after"):
            pass
    assert t2.root.name == "after"


def test_spans_without_trace_still_feed_registry():
    with obs.span("untraced"):
        pass
    assert obs.registry().histogram("untraced").count == 1


def test_fence_noop_when_sync_off(monkeypatch):
    """obs_sync_spans off: span exit must never call block_until_ready —
    the zero-overhead contract for always-on instrumentation."""
    import jax
    calls = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or x)
    with obs.span("t.f") as sp:
        assert sp.fence("value") == "value"      # passthrough either way
    assert calls == []
    obs.set_sync_spans(True)
    with obs.span("t.f") as sp:
        sp.fence("value")
    assert calls == [1]


# ----------------------------------------------------------------- exporters
def test_prometheus_roundtrip(rng):
    obs.counter("q.count").inc(5)
    obs.gauge("q.depth").set(3)
    h = obs.histogram("q.lat")
    xs = rng.gamma(2.0, 5.0, size=200)
    for x in xs:
        h.observe(x)
    text = obs.render_prometheus()
    back = obs.parse_prometheus(text)
    assert back["counters"]["hmgi_q_count"] == 5
    assert back["gauges"]["hmgi_q_depth"] == 3
    hb = back["histograms"]["hmgi_q_lat"]
    assert hb["count"] == 200
    assert hb["sum"] == pytest.approx(float(np.sum(xs)), rel=1e-6)
    assert hb["buckets"] == h.cumulative_buckets()
    # exposition shape: cumulative, ends at +Inf == count
    les = [le for le, _ in hb["buckets"]]
    assert les == sorted(les) and les[-1] == float("inf")
    assert hb["buckets"][-1][1] == hb["count"]


def test_snapshot_is_json_serialisable():
    obs.counter("j.c").inc()
    obs.histogram("j.h").observe(1.5)
    out = json.loads(json.dumps(obs.snapshot()))
    assert out["histograms"]["j.h"]["count"] == 1


# ------------------------------------------------------------- facade wiring
@pytest.fixture(scope="module")
def small_index():
    from repro.configs import get_config
    from repro.core import HMGIIndex
    rng = np.random.default_rng(7)
    cfg = get_config("hmgi").replace(
        modalities=("text",), n_partitions=4, n_probe=4, kmeans_iters=4,
        top_k=5, delta_capacity=64)
    idx = HMGIIndex(cfg, seed=0)
    vecs = rng.normal(size=(128, cfg.dim)).astype(np.float32)
    edges = (np.arange(128), (np.arange(128) + 1) % 128)
    idx.ingest({"text": (np.arange(128), vecs)}, n_nodes=128, edges=edges)
    return idx, vecs


def test_search_trace_option(small_index):
    idx, vecs = small_index
    q = vecs[:2]
    sv, si = idx.search(q, "text", k=5)           # default: 2-tuple compat
    sv2, si2, t = idx.search(q, "text", k=5, trace=True)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(si2))
    names = {n.name for n in t.roots}
    assert names == {"query.plan", "query.execute"}
    assert t.find("query.seed_scan") is not None
    assert "query.execute" in t.render()


def test_hybrid_search_trace_spans(small_index):
    idx, vecs = small_index
    _, _, t = idx.hybrid_search(vecs[:2], "text", k=5, n_hops=1, trace=True)
    for name in ("query.plan", "query.execute", "query.seed_scan",
                 "query.traversal", "query.fusion"):
        assert t.find(name) is not None, name


def test_metrics_obs_section_and_registry_population(small_index):
    idx, vecs = small_index
    idx.search(vecs[:2], "text", k=5)
    m = idx.metrics()
    hs = m["obs"]["histograms"]
    assert hs["query.execute"]["count"] >= 1
    assert np.isfinite(hs["query.execute"]["p50"])
    assert "query.seed_scan" in hs


def test_progressive_rounds_counter(small_index):
    from repro.core.progressive import progressive_search
    idx, vecs = small_index
    m = idx.modalities["text"]
    results = list(progressive_search(m.ivf, vecs[:2], k=5,
                                      probe_schedule=(1, 2, 4)))
    assert len(results) == 3
    assert obs.registry().counter("progressive.rounds").value == 3
    assert obs.registry().histogram("progressive.round").count == 3
    # elapsed is accumulated *work* time: monotone across rounds
    els = [r.elapsed_s for r in results]
    assert els == sorted(els) and els[0] > 0


def test_wal_histograms_populate(tmp_path):
    from repro.persistence.oplog import OpLog
    log = OpLog(str(tmp_path), sync_every=2)
    for i in range(4):
        log.append("op", {"i": i}, {"a": np.arange(3, dtype=np.int32)})
    log.close()
    reg = obs.registry()
    assert reg.histogram("wal.append").count == 4
    assert reg.histogram("wal.fsync").count == 2      # group commit of 2
    assert reg.histogram("wal.sync_batch").percentile(50) == 2.0


def test_staticcheck_all_stays_clean():
    """The instrumented tree (obs/ is in the HMG001 hot-path set) passes
    the full lint+trace+budget gate."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run([sys.executable, "-m", "tools.staticcheck", "--all"],
                       cwd=repo, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, f"staticcheck --all failed:\n{r.stdout}\n{r.stderr}"


# ------------------------------------------- profiler clock, syncs, compiles
def _host_spans(trace_dir):
    """(name, start_ns, end_ns, args, thread) of every program span on the
    host plane of the profiler trace written under ``trace_dir``; spans of
    one thread share ``thread`` (the plane and line they sit on)."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("query.", "serving.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (plane.name, line.name)))
    return out


@pytest.fixture(scope="module")
def filtered_index():
    """A small index with a ``year`` column: ``year >= 2026`` admits few
    rows (pushdown), ``year >= 2001`` nearly all (oversample)."""
    from repro.configs import get_config
    from repro.core import HMGIIndex
    rng = np.random.default_rng(11)
    cfg = get_config("hmgi").replace(
        modalities=("text",), n_partitions=4, n_probe=4, kmeans_iters=4,
        top_k=5, delta_capacity=64)
    idx = HMGIIndex(cfg, seed=0)
    vecs = rng.normal(size=(128, cfg.dim)).astype(np.float32)
    edges = (np.arange(128), (np.arange(128) + 1) % 128)
    year = rng.integers(2000, 2030, 128).astype(np.int32)
    idx.ingest({"text": (np.arange(128), vecs)}, n_nodes=128, edges=edges,
               node_attrs={"year": year})
    return idx, vecs


def test_spans_sit_on_the_profiler_host_plane_with_one_call_id(
        filtered_index, tmp_path):
    import jax
    idx, vecs = filtered_index
    idx.hybrid_search(vecs[:2], "text", k=5, n_hops=1)          # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        idx.hybrid_search(vecs[:2], "text", k=5, n_hops=1)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    by_name = {}
    for name, a, b, args, thread in spans:
        by_name.setdefault(name, []).append((a, b, args, thread))
    execute = by_name["query.execute"]
    assert len(execute) == 1
    lo, hi, args, thread = execute[0]
    assert isinstance(args["call"], int)
    # the call's spans are those nested inside its ``query.execute`` on its
    # thread, and carry no id of their own
    for name in ("query.seed_scan", "query.traversal", "query.fusion"):
        (a, b, inner, where), = by_name[name]
        assert lo <= a and b <= hi and where == thread, name
        assert "call" not in inner, name
    # the planner runs before the call is numbered
    (a, _, plan_args, where), = by_name["query.plan"]
    assert a < lo and where == thread and "call" not in plan_args


def test_span_arguments_reach_its_annotation_alone(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("query.execute", call=41):
            with obs.span("query.seed_scan"):
                pass
        with obs.span("serving.batch", batch=7, size=3):
            pass
    finally:
        jax.profiler.stop_trace()
    got = sorted((name, {k: args[k] for k in ("call", "batch", "size")
                         if k in args})
                 for name, _, _, args, _ in _host_spans(tmp_path))
    assert got == [("query.execute", {"call": 41}), ("query.seed_scan", {}),
                   ("serving.batch", {"batch": 7, "size": 3})]
    # the annotation is opened and closed with the span's histogram
    assert obs.histogram("serving.batch").count == 1


def test_compile_counter_counts_jit_cache_misses():
    import jax
    import jax.numpy as jnp
    x = jnp.ones((3,))

    def fresh():
        return jax.jit(lambda v: v * 2 + 1)

    f = fresh()
    f(x)
    before = obs.counter("executor.compiles").value
    f(x)                                          # cached: no compile
    assert obs.counter("executor.compiles").value == before
    fresh()(x)                                    # a new closure compiles
    assert obs.counter("executor.compiles").value == before + 1


def test_compile_counter_works_with_the_persistent_cache_off():
    import jax
    import jax.numpy as jnp
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        x = jnp.ones((5,))
        before = obs.counter("executor.compiles").value
        jax.jit(lambda v: v - 3)(x)
        assert obs.counter("executor.compiles").value == before + 1
    finally:
        jax.config.update("jax_enable_compilation_cache", on)


def test_to_host_counts_and_spans_each_read():
    import jax.numpy as jnp
    a, b = obs.to_host((jnp.arange(3), jnp.ones((2,))), "result")
    assert isinstance(a, np.ndarray) and a.tolist() == [0, 1, 2]
    assert b.tolist() == [1.0, 1.0]
    assert int(obs.to_host(jnp.int32(7), "oversample")) == 7
    assert obs.counter("executor.syncs").value == 2
    assert obs.histogram("query.to_host").count == 2


# syncs one bucketed search makes: the result read, the workload tracker's
# probe read, and with a ``where`` the planner's selectivity read; an
# oversampled scan adds one read per widening round (one here: nearly every
# row passes, so the first width holds k survivors)
@pytest.mark.parametrize("where,mode,syncs", [
    (None, None, 2),
    (("year", ">=", 2026), "prefilter", 3),
    (("year", ">=", 2001), "oversample", 4),
])
@pytest.mark.parametrize("n_hops", [0, 1])
def test_syncs_per_call_follow_the_plan(filtered_index, where, mode, syncs,
                                        n_hops):
    from repro.query.executor import search_bucketed
    idx, vecs = filtered_index
    search_bucketed(idx, vecs[:3], "text", k=5, where=where, n_hops=n_hops)
    obs.reset()
    search_bucketed(idx, vecs[:3], "text", k=5, where=where, n_hops=n_hops)
    assert idx.metrics().get("filter_mode") == mode or where is None
    assert obs.counter("executor.syncs").value == syncs
    assert obs.histogram("query.to_host").count == syncs


def test_sync_guard_rehearsal_counts_each_plan_class(capsys):
    """``tools/sync_guard.py`` off the TPU: every class served, with the
    planner's mode and the syncs its plan implies (the guard itself only
    fires on a device backend)."""
    from tools import sync_guard
    assert sync_guard.main(["--rehearse", "--rows", "512"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] is False                     # a rehearsal never is
    got = {k: (v["result"], v["mode"], v["syncs"])
           for k, v in out["calls"].items()}
    assert got == {"pushdown": ("ok", "prefilter", 3.0),
                   "oversample": ("ok", "oversample", 4.0),
                   "vector": ("ok", None, 2.0)}
