"""The reduction by program spans on two chip traces recorded on a TPU
v5e by ``record_span_trace.py``: the same work under the program's
spans, the traversal's hops eager in one trace and one jitted function in
the other, two calls each inside one ``bench.window``, with a
``serving.window`` host sleep inside each call and a quiet spell between
them."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import span_reduce, trace_reduce  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
VARIANTS = {"eager": "jit_matmul", "jit": "jit_hops_eager"}
WINDOW_S, GAP_S, CALLS = 0.02, 0.05, 2      # as record_span_trace.py


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def variant(request):
    path = str(DATA / f"spans_{request.param}.xplane.pb")
    sel = {"seed": {"modules": ["jit_seed"]},
           "traversal": {"modules": [VARIANTS[request.param]]},
           "fusion": {"modules": ["jit_fuse"]}}
    return (request.param, span_reduce.reduce(path),
            trace_reduce.reduce(path, sel))


@pytest.fixture(scope="module")
def both():
    return {v: span_reduce.reduce(str(DATA / f"spans_{v}.xplane.pb"))
            for v in VARIANTS}


def test_every_module_links_to_its_dispatch(variant):
    _, spans, _ = variant
    assert spans.unlinked == 0
    assert spans.linked == CALLS * (6 if variant[0] == "eager" else 3)


def test_device_time_falls_to_the_span_that_launched_it(variant):
    _, spans, reduced = variant
    sel = reduced.selected_s
    for span, module in (("query.seed_scan", "seed"),
                         ("query.traversal", "traversal"),
                         ("query.fusion", "fusion")):
        assert spans.device_s([span]) == pytest.approx(sel[module],
                                                       rel=1e-9), span
    # every module ran inside the call's ``query.execute``
    assert spans.device_s(["query.execute"]) == pytest.approx(
        sum(sel.values()), rel=1e-9)
    assert spans.device_s(["serving.window", "query.to_host:result"]) == 0.0
    # a sync is named by the site it reads
    assert "query.to_host:result" in spans.names


def test_traversal_reads_alike_eager_and_jitted(both):
    eager, jit = (both[v].device_s(["query.traversal"]) for v in
                  ("eager", "jit"))
    # the eager hops also copy their operand once per module
    assert jit == pytest.approx(eager, rel=0.1)
    assert span_reduce.span_ms_per_q(both["eager"], CALLS) == \
        pytest.approx(span_reduce.span_ms_per_q(both["jit"], CALLS),
                      rel=0.1)


def test_the_sleep_idles_in_its_span(variant):
    _, spans, reduced = variant
    # the split covers trace_reduce's in-flight idle, no more, no less
    assert sum(spans.idle_s.values()) == pytest.approx(
        reduced.idle_inflight_s)
    assert CALLS * WINDOW_S <= spans.idle_s["serving.window"] \
        < CALLS * WINDOW_S * 1.2
    assert spans.unattributed_s < 0.05 * spans.idle_inflight_s
    assert span_reduce.host_ms_per_q(spans, CALLS) == pytest.approx(
        1e3 * spans.idle_under("query.") / CALLS)


def test_idle_gaps_name_the_call_and_the_span(variant):
    _, spans, reduced = variant
    labels = [lab for lab, _ in spans.idle_gaps]
    assert labels[0] == "host: no call in flight"      # between the calls
    assert GAP_S * 0.9 < spans.idle_gaps[0][1] < GAP_S * 1.6
    assert labels[1] == "bench.search (1 open) / serving.window"
    assert WINDOW_S <= spans.idle_gaps[1][1] < WINDOW_S * 1.2
    # the same gaps as trace_reduce's, each label extended
    assert [g for _, g in spans.idle_gaps] == [g for _, g in
                                               reduced.idle_gaps]
    for ours, theirs in zip(labels, (lab for lab, _ in reduced.idle_gaps)):
        assert ours.startswith(theirs)


def test_readers_read_nothing_without_their_spans():
    spans = span_reduce.reduce(str(DATA / "small.xplane.pb"))
    assert spans.names == frozenset()
    # no thread holds a program span: no dispatch to link the modules to
    assert spans.linked == 0 and spans.unlinked == 4
    assert span_reduce.host_ms_per_q(spans, 2) is None
    assert span_reduce.span_ms_per_q(spans, 2) is None
    assert spans.idle_s == {span_reduce.UNATTRIBUTED: pytest.approx(
        spans.idle_inflight_s)}
    assert all(" / no program span" in lab or lab.startswith("host:")
               for lab, _ in spans.idle_gaps)


def test_readers_read_nothing_without_requests(both):
    assert span_reduce.host_ms_per_q(both["jit"], 0) is None
    assert span_reduce.span_ms_per_q(both["jit"], 0) is None


def test_innermost_names_each_instant_by_the_shortest_open_span():
    spans = [(0.0, 100.0, "query.execute"), (10.0, 40.0, "query.seed_scan"),
             (20.0, 30.0, "query.to_host"),                 # nested
             (35.0, 90.0, "serving.window")]                # other thread
    assert span_reduce._innermost(spans) == [
        (0.0, 10.0, "query.execute"), (10.0, 20.0, "query.seed_scan"),
        (20.0, 30.0, "query.to_host"), (30.0, 40.0, "query.seed_scan"),
        (40.0, 90.0, "serving.window"), (90.0, 100.0, "query.execute")]
    idle = [(5.0, 25.0), (95.0, 120.0)]
    split = span_reduce._split(idle, span_reduce._innermost(spans))
    assert split == {"query.execute": 10.0, "query.seed_scan": 10.0,
                     "query.to_host": 5.0, "unattributed": 20.0}


def test_open_sets_list_every_span_open_on_the_thread():
    spans = [(0.0, 100.0, "query.execute"), (10.0, 40.0, "query.traversal"),
             (20.0, 30.0, "query.to_host"), (50.0, 60.0, "query.to_host")]
    starts, ends, sets = span_reduce._open_sets(spans)
    assert list(zip(starts, ends)) == [(0.0, 10.0), (10.0, 20.0),
                                       (20.0, 30.0), (30.0, 40.0),
                                       (40.0, 50.0), (50.0, 60.0),
                                       (60.0, 100.0)]
    ex, tv, th = "query.execute", "query.traversal", "query.to_host"
    assert sets == [{ex}, {ex, tv}, {ex, tv, th}, {ex, tv}, {ex},
                    {ex, th}, {ex}]


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"m_{name.replace('.', '_')}",
        ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_syncs_reader_reads_the_counter_over_the_calls():
    import types
    from repro import obs
    mod = _reader("executor.syncs_per_call")
    obs.reset()
    for _ in range(7):
        obs.counter("executor.syncs").inc()
    batch_q = {"count": 2, "sum": 2.0}
    assert mod.read(types.SimpleNamespace(batch_q=batch_q)) == 3.5
    # no calls in the window: nothing to read
    assert mod.read(types.SimpleNamespace(batch_q={"count": 0})) is None
    obs.reset()


def test_syncs_reader_reads_nothing_without_the_counter():
    """A program without ``executor.syncs`` (one older than the counter)
    leaves the metric out of the line."""
    import types
    from repro import obs
    obs.reset()
    obs.histogram("serving.batch_q").observe(1)
    run = types.SimpleNamespace(
        batch_q=obs.histogram("serving.batch_q").summary())
    assert _reader("executor.syncs_per_call").read(run) is None


@pytest.mark.parametrize("where,per_call", [
    (("year", ">=", 2026), 3.0),        # pushdown
    (("year", ">=", 2001), 4.0),        # oversample, one widening round
])
def test_syncs_reader_on_the_micro_batched_service(where, per_call):
    """The reader over a window of micro-batched hybrid calls, as the open
    cell makes them: result, probes and selectivity reads, plus one
    widening round where the planner oversamples."""
    import types
    import numpy as np
    from repro import obs
    from repro.configs import get_config
    from repro.core import HMGIIndex
    from repro.serving.retrieval import RetrievalPlan, RetrievalService
    rng = np.random.default_rng(11)
    cfg = get_config("hmgi").replace(
        modalities=("text",), n_partitions=4, n_probe=4, kmeans_iters=4,
        top_k=5, delta_capacity=64)
    idx = HMGIIndex(cfg, seed=0)
    vecs = rng.normal(size=(128, cfg.dim)).astype(np.float32)
    idx.ingest({"text": (np.arange(128), vecs)}, n_nodes=128,
               edges=(np.arange(128), (np.arange(128) + 1) % 128),
               node_attrs={"year": rng.integers(2000, 2030, 128)
                           .astype(np.int32)})
    svc = RetrievalService(idx, batching=True, window_s=0.0)
    plan = RetrievalPlan("text", k=5, n_hops=1, where=where)
    svc.search(plan, vecs[0])                               # compile
    obs.reset()
    for i in range(3):
        svc.search(plan, vecs[i + 1])
    run = types.SimpleNamespace(
        batch_q=obs.histogram("serving.batch_q").summary())
    assert run.batch_q["count"] == 3
    assert _reader("executor.syncs_per_call").read(run) == per_call
    obs.reset()


def test_a_reused_flow_id_links_to_the_latest_earlier_producer():
    """A long trace reuses flow ids (a 51 s hybrid trace on a v5e: 11,453
    of 140,548 producer ids recur, seconds apart): a link takes the latest
    producer that starts before its consumer, give or take the skew of
    the device and host clocks."""
    producers = {7: [(100.0, 0, 1), (9e9, 1, 2)]}
    assert span_reduce._producer(producers, 7, 200.0) == (0, 1)
    # a device event may start just before the host event that enqueued it
    assert span_reduce._producer(producers, 7, 50.0) == (0, 1)
    assert span_reduce._producer(producers, 7, 1e10) == (1, 2)
    assert span_reduce._producer(producers, 7, -1e8) is None
    assert span_reduce._producer(producers, 8, 200.0) is None
