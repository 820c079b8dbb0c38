"""The trace reduction on a small trace recorded on a TPU v5e by
``record_trace.py``: two calls of a jitted ``search`` then ``merge``
inside one ``bench.window``, with a 50 ms host sleep between them."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

TRACE = ROOT / "bench" / "tests" / "data" / "small.xplane.pb"
SEL = {"search": {"modules": ["jit_search"]},
       "merge": {"modules": ["jit_merge"]},
       "sorts": {"ops": ["sort*"]},
       "with_absent": {"modules": ["jit_search", "jit_absent"]}}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(TRACE), SEL)


def test_window_is_the_harness_annotation(reduced):
    assert reduced.n_devices == 1
    assert 0.050 < reduced.window_s < 0.060      # the sleep and two calls
    assert 0 < reduced.busy_s < reduced.window_s
    assert reduced.idle_share > 0.8


def test_selectors_split_the_busy_time(reduced):
    s = reduced.selected_s
    assert s["search"] > 0 and s["merge"] > 0
    assert s["search"] + s["merge"] <= reduced.busy_s * 1.0001 + 1e-6
    assert s["search"] > 10 * s["merge"]         # the matmul dominates
    assert 0 < s["sorts"] <= s["merge"]


def test_breakdown(reduced):
    ops = reduced.device_ops
    assert 0 < len(ops) <= 10
    assert ops[0][0].startswith("jit_search/")
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    label, seconds = reduced.idle_gaps[0]
    assert label == "host: no call in flight"    # the sleep between calls
    assert 0.045 < seconds < 0.055
    assert any(lab.startswith("bench.search_many")
               for lab, _ in reduced.idle_gaps)


def test_each_pattern_of_a_selector_is_timed_alone(reduced):
    parts = reduced.selected_parts
    assert parts["search"] == {"jit_search": reduced.selected_s["search"]}
    assert parts["with_absent"]["jit_absent"] == 0.0
    assert parts["with_absent"]["jit_search"] == reduced.selected_s["search"]


def test_idle_in_flight_leaves_out_the_quiet_spell(reduced):
    # the 50 ms sleep has no call open: only the calls' own idle counts
    assert 0 < reduced.idle_inflight_s < 0.005
    share = reduced.idle_share_inflight
    assert share == pytest.approx(reduced.idle_inflight_s / (
        reduced.busy_s + reduced.idle_inflight_s))
    assert 0 < share < reduced.idle_share


def test_intersect():
    a = [(0.0, 10.0), (20.0, 30.0)]
    b = [(5.0, 25.0), (28.0, 40.0)]
    assert trace_reduce.intersect(a, b) == [(5.0, 10.0), (20.0, 25.0),
                                            (28.0, 30.0)]
    assert trace_reduce.intersect(a, []) == []


def test_union_merges_and_clips():
    u = trace_reduce.union(np.array([0.0, 5, 6, 20]),
                           np.array([10.0, 8, 12, 30]), 2.0, 25.0)
    assert u == [(2.0, 12.0), (20.0, 25.0)]


def test_op_and_module_names():
    assert trace_reduce.op_name("%fusion.12 = f32[8]{0} fusion(...)") \
        == "fusion.12"
    assert trace_reduce.module_name("jit_search(1234567)") == "jit_search"


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"m_{name.replace('.', '_')}", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("absent", [None, "jit__mean"])
def test_traversal_reader_needs_every_module(absent):
    import types
    mod = _reader("traversal.ms_per_q")
    parts = {m: (0.0 if m == absent else 1.0) for m in mod.SELECT["modules"]}
    trace = types.SimpleNamespace(
        selected_parts={"traversal.ms_per_q": parts},
        selected_s={"traversal.ms_per_q": sum(parts.values())})
    got = mod.read(types.SimpleNamespace(trace=trace, answered=100))
    if absent:
        assert got is None
    else:
        assert got == pytest.approx(1e3 * 4.0 / 100)


def test_hybrid_idle_reader_reads_the_in_flight_share(reduced):
    import types
    got = _reader("device.idle.hybrid").read(types.SimpleNamespace(
        trace=reduced))
    assert got == pytest.approx(100 * reduced.idle_share_inflight)
