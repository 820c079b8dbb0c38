"""Reduces a JAX profiler trace by the program's own spans.

The program's spans (``repro.obs.span``) are also profiler annotations,
so a ``--trace 1`` run's trace holds them on its host plane, on the clock
of the device's operations. On top of ``trace_reduce.reduce``'s reading
(``Spans.base``), over its window (the ``bench.window`` annotation) and
its calls in flight (the union of the other ``bench.*`` annotations),
this reads:

- idle by span: the device's idle time with a call in flight
  (``trace_reduce``'s ``idle_inflight_s``), split by
  the innermost program span open at each instant, on any thread (the
  shortest one open, as ``trace_reduce`` names a gap by its innermost
  call); idle time with a call in flight and no program span open is
  left ``unattributed``;
- device time by span: each XLA module execution counts towards every
  program span open around the host dispatch that launched it, on the
  dispatching thread, whatever the module is named, so the number reads
  the same whether the work runs as many eager operations or as one
  jitted function;
- ``trace_reduce``'s longest idle gaps, each label followed by the
  innermost program span open at the gap's middle:
  ``bench.search (5 open) / query.traversal``.

Program spans are the host events whose names start with one of
``PREFIXES``; a ``query.to_host`` span is named by its ``site`` argument
too (``query.to_host:oversample``), so the syncs split by what they read.
Module executions are tied to their dispatch by the trace's own flow
links, read off a TPU v5e trace: an ``XLA Modules`` event's
``_c`` flow id is the ``_p`` of the host event that enqueued it
(``DoEnqueueProgram``, which also carries the module's ``run_id``); from
there each host event's innermost enclosing event with a ``_c`` (itself
included) names the host event that produced it
(``tpu::System::Execute=>IssueSequencedEvent`` from
``tpu::System::Execute``; ``PJRT_LoadedExecutable_Execute`` from
``PJRT_LoadedExecutable_Execute linkage``), until the chain reaches a
thread that holds program spans: the start of that event is the
dispatch. A long trace reuses flow ids, so each link takes the latest
producer that starts before its consumer. A module whose chain breaks is
counted in ``unlinked`` and falls to no span.

    python -m bench.span_reduce <trace.xplane.pb>
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import sys
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace_reduce as tr

PREFIXES = ("serving.", "query.")
TO_HOST = "query.to_host"
UNATTRIBUTED = "unattributed"
# flow links followed from a module execution, and events looked back
# over for the one that encloses a producer
_MAX_HOPS = 16
# how much later than its consumer a producer may start: a device
# operation can show up to about a millisecond before the host event that
# enqueued it (``trace_reduce``)
_SKEW_NS = 5e6


@dataclasses.dataclass
class Spans:
    base: tr.Reduced                  # trace_reduce's reading of the trace
    idle_s: Dict[str, float]          # innermost span -> in-flight idle
    launched: List[Tuple[np.ndarray, np.ndarray, List[FrozenSet[str]]]]
    linked: int                       # module executions tied to a dispatch
    unlinked: int
    idle_gaps: List[list]             # [[label, seconds]], longest first
    names: FrozenSet[str] = frozenset()   # program spans in the trace
    lo: float = 0.0
    hi: float = 0.0

    @property
    def window_s(self) -> float:
        return self.base.window_s

    @property
    def idle_inflight_s(self) -> float:
        """trace_reduce's device idle with a call in flight, which
        ``idle_s`` splits."""
        return self.base.idle_inflight_s

    @property
    def unattributed_s(self) -> float:
        return self.idle_s.get(UNATTRIBUTED, 0.0)

    def idle_under(self, prefix: str) -> float:
        """In-flight idle seconds whose innermost span starts with
        ``prefix``."""
        return sum(v for k, v in self.idle_s.items() if k.startswith(prefix))

    def device_s(self, names: Sequence[str]) -> float:
        """Device seconds of the module executions launched inside any
        span named in ``names`` (a union, clipped to the window), averaged
        over the devices."""
        want = set(names)
        total = 0.0
        for start, end, open_ in self.launched:
            hit = np.array([bool(want & o) for o in open_], bool)
            total += tr._length(tr.union(start[hit], end[hit], self.lo,
                                         self.hi))
        return total / max(len(self.launched), 1) * 1e-9


def _is_span(name: str) -> bool:
    return name.startswith(PREFIXES)


@dataclasses.dataclass
class _Line:
    """One host thread's events, in start order."""
    start: np.ndarray
    end: np.ndarray
    consumer: Dict[int, int]          # event index -> its ``_c`` flow id
    spans: List[Tuple[float, float, str]]


def _host_lines(pd):
    """Every host line's events, and the flow producers: ``_p`` id ->
    [(start, line, event index)] in start order (an id can recur)."""
    lines: List[_Line] = []
    producers: Dict[int, List[Tuple[float, int, int]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            continue
        for ln in plane.lines:
            evs = sorted(ln.events, key=lambda e: e.start_ns)
            li = len(lines)
            line = _Line(np.array([float(e.start_ns) for e in evs]),
                         np.array([float(e.start_ns + e.duration_ns)
                                   for e in evs]), {}, [])
            for i, ev in enumerate(evs):
                st = tr._stats(ev)
                if _is_span(ev.name):
                    name = ev.name
                    if name == TO_HOST and "site" in st:
                        name = f"{name}:{st['site']}"
                    line.spans.append((line.start[i], line.end[i], name))
                    continue
                if "_p" in st:
                    producers.setdefault(int(st["_p"]), []).append(
                        (float(line.start[i]), li, i))
                if "_c" in st:
                    line.consumer[i] = int(st["_c"])
            lines.append(line)
    for found in producers.values():
        found.sort()
    return lines, producers


def _producer(producers, fid: int, t: float) -> Optional[Tuple[int, int]]:
    """(line, event index) of the producer of flow ``fid`` for a consumer
    that starts at ``t``: the latest one that starts no later than ``t``
    (give or take the clocks' skew), since a long trace reuses flow ids."""
    found = producers.get(fid)
    if not found:
        return None
    j = bisect.bisect_right(found, (t + _SKEW_NS, np.inf, np.inf)) - 1
    return found[j][1:] if j >= 0 else None


def _enclosing_consumer(line: _Line, i: int, starts: List[float],
                        idx: List[int]) -> Optional[Tuple[int, float]]:
    """The ``_c`` and start of the innermost event on ``line`` that
    contains event ``i`` (``i`` itself first)."""
    if i in line.consumer:
        return line.consumer[i], float(line.start[i])
    s, e = line.start[i], line.end[i]
    j = bisect.bisect_right(starts, s) - 1
    for k in reversed(idx[max(j - _MAX_HOPS, -1) + 1:j + 1]):
        if line.end[k] >= e:
            return line.consumer[k], float(line.start[k])
    return None


def _dispatches(lines, producers, flows) -> List[Optional[Tuple[int,
                                                               float]]]:
    """For each module execution's flow, (``_c`` id, start) or None: the
    (line, time) of its dispatch on a thread that holds program spans, or
    None."""
    consumers = []
    for line in lines:
        idx = sorted(line.consumer, key=lambda i: line.start[i])
        consumers.append(([line.start[i] for i in idx], idx))
    out = []
    for flow in flows:
        hit = None
        for _ in range(_MAX_HOPS):
            at = _producer(producers, *flow) if flow is not None else None
            if at is None:
                break
            li, i = at
            if lines[li].spans:
                hit = (li, float(lines[li].start[i]))
                break
            flow = _enclosing_consumer(lines[li], i, *consumers[li])
        out.append(hit)
    return out


def _open_sets(spans: Sequence[Tuple[float, float, str]]):
    """Elementary segments of one thread's spans: (starts, ends, the set
    of span names open in each)."""
    edges = sorted([(a, 1, n) for a, _, n in spans]
                   + [(b, -1, n) for _, b, n in spans])
    open_: Dict[str, int] = collections.Counter()
    starts, ends, sets = [], [], []
    for i, (t, step, name) in enumerate(edges):
        open_[name] += step
        if i + 1 < len(edges) and edges[i + 1][0] > t:
            names = frozenset(k for k, v in open_.items() if v > 0)
            if names:
                starts.append(t)
                ends.append(edges[i + 1][0])
                sets.append(names)
    return starts, ends, sets


def _innermost(spans: Sequence[Tuple[float, float, str]]) -> List[
        Tuple[float, float, str]]:
    """Disjoint segments, each named by the shortest span open in it (over
    every thread), adjacent segments of one name merged."""
    order = sorted(spans)
    points = sorted({p for a, b, _ in spans for p in (a, b)})
    open_: List[Tuple[float, float, str]] = []
    out: List[List] = []
    k = 0
    for a, b in zip(points, points[1:]):
        while k < len(order) and order[k][0] <= a:
            open_.append(order[k])
            k += 1
        open_ = [x for x in open_ if x[1] > a]
        if not open_:
            continue
        name = min(open_, key=lambda x: x[1] - x[0])[2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return [(a, b, n) for a, b, n in out]


def _split(idle: Sequence[Tuple[float, float]],
           named: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of ``idle`` under each name of ``named`` (both sorted and
    disjoint), the rest under ``UNATTRIBUTED``."""
    out: Dict[str, float] = collections.Counter()
    total = tr._length(idle)
    i = j = 0
    while i < len(idle) and j < len(named):
        lo = max(idle[i][0], named[j][0])
        hi = min(idle[i][1], named[j][1])
        if hi > lo:
            out[named[j][2]] += hi - lo
        if idle[i][1] < named[j][1]:
            i += 1
        else:
            j += 1
    out[UNATTRIBUTED] = total - sum(out.values())
    return dict(out)


def _at(named, t: float) -> Optional[str]:
    j = bisect.bisect_right([a for a, _, _ in named], t) - 1
    if j >= 0 and named[j][1] > t:
        return named[j][2]
    return None


def reduce(path: str, top: int = 10) -> Spans:
    base = tr.reduce(path, top=top)
    pd = tr.load(path)
    devs = tr.device_ops(pd)
    notes = tr.host_annotations(pd)
    win = [(a, b) for a, b, n in notes if n == tr.WINDOW]
    if win:
        lo, hi = win[0]
    else:
        lo = min(float(d.start.min()) for d in devs if d.start.size)
        hi = max(float(d.end.max()) for d in devs if d.end.size)
    calls = [(a, b) for a, b, n in notes if n != tr.WINDOW]
    inflight = tr.union(np.array([a for a, _ in calls]),
                        np.array([b for _, b in calls]), lo, hi)

    lines, producers = _host_lines(pd)
    spans = [s for line in lines for s in line.spans]
    named = _innermost(spans)
    idle: Dict[str, float] = collections.Counter()
    gaps = []
    for d in devs:
        gaps.append(tr._gaps(tr.union(d.start, d.end, lo, hi), lo, hi))
        for k, v in _split(tr.intersect(gaps[-1], inflight), named).items():
            idle[k] += v

    segments = [_open_sets(line.spans) for line in lines]
    launched = []
    linked = unlinked = 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        mods = {ln.name: ln for ln in plane.lines}.get(tr.MODULES_LINE)
        if mods is None:
            continue
        evs = sorted(mods.events, key=lambda e: e.start_ns)
        flows = []
        for ev in evs:
            c = tr._stats(ev).get("_c")
            flows.append(None if c is None else (int(c), float(ev.start_ns)))
        opened = []
        for hit in _dispatches(lines, producers, flows):
            if hit is None:
                unlinked += 1
                opened.append(frozenset())
                continue
            linked += 1
            li, t = hit
            s, e, sets = segments[li]
            j = bisect.bisect_right(s, t) - 1
            opened.append(sets[j] if j >= 0 and e[j] > t else frozenset())
        launched.append((np.array([float(e.start_ns) for e in evs]),
                         np.array([float(e.start_ns + e.duration_ns)
                                   for e in evs]), opened))

    # trace_reduce's gap labels, in its order: the first device's gaps,
    # longest first
    first = sorted(gaps[0], key=lambda g: -(g[1] - g[0]))
    labelled = []
    for (label, sec), (a, b) in zip(base.idle_gaps, first):
        if not label.startswith("host:"):
            label += " / " + (_at(named, (a + b) / 2) or "no program span")
        labelled.append([label, sec])
    n = len(devs)
    return Spans(base=base, idle_s={k: v / n * 1e-9 for k, v in idle.items()},
                 launched=launched, linked=linked, unlinked=unlinked,
                 idle_gaps=labelled,
                 names=frozenset(name for _, _, name in spans),
                 lo=lo, hi=hi)


# Readers of the per-layer quantities these spans give, per request
# answered. Each returns None when the trace lacks what it reads. Program
# changes that would rename their sources: renaming the ``query.*`` spans
# of ``query/executor.py`` and ``query/planner.py``, or running the
# traversal or fusion outside ``run_traverse``'s two spans.

def host_ms_per_q(s: Spans, answered: int) -> Optional[float]:
    """The executor's host time that keeps the device idle: in-flight
    device idle whose innermost program span is a ``query.*`` span, in
    milliseconds per request answered."""
    if answered <= 0 or not any(n.startswith("query.") for n in s.names):
        return None
    return 1e3 * s.idle_under("query.") / answered


def span_ms_per_q(s: Spans, answered: int) -> Optional[float]:
    """Device time of the module executions launched inside
    ``query.traversal`` or ``query.fusion``, in milliseconds per request
    answered, however the work is split into jitted functions."""
    device = s.device_s(["query.traversal", "query.fusion"])
    if answered <= 0 or device <= 0:
        return None
    return 1e3 * device / answered


def summary(s: Spans) -> dict:
    """The numbers a reader needs, JSON-able."""
    names = sorted({n for _, _, open_ in s.launched for o in open_
                    for n in o})
    return {"window_s": s.window_s, "idle_inflight_s": s.idle_inflight_s,
            "idle_s": dict(sorted(s.idle_s.items(), key=lambda kv: -kv[1])),
            "device_s": {n: s.device_s([n]) for n in names},
            "linked": s.linked, "unlinked": s.unlinked,
            "idle_gaps": s.idle_gaps}


if __name__ == "__main__":
    print(json.dumps(summary(reduce(sys.argv[1])), indent=1))
