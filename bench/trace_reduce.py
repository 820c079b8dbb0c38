"""Reduces a JAX profiler trace (``.xplane.pb``) to device numbers.

- busy and idle: the union of the intervals in which an operation ran on
  a device, inside the traced window, averaged over the devices;
- idle while a call is in flight: the device's idle time inside the
  union of the harness's ``bench.*`` call annotations (the window's own
  left out), so that the quiet spells of an open loop's arrivals do not
  count; its share is taken over busy time plus that idle time, because
  the device and host clocks of a trace disagree by about a millisecond
  (a device operation can show before the host call that launched it),
  and busy time clipped to host intervals would lose that much per call;
- device time per selector: the union of the executions of the XLA
  modules (``modules``) or operations (``ops``) whose names match one of
  the selector's ``fnmatch`` patterns (module names drop their
  ``(<program id>)`` suffix), averaged over the devices; and the same for
  each pattern alone, so that a reader can see a pattern match nothing;
- a breakdown: the leaf operations that took the most device time, and
  the longest idle gaps of the first device, each named by the innermost
  ``bench.*`` host annotation open at its middle.

Busy time reads the ``XLA Ops`` line only: an asynchronous copy in flight
while no operation runs does not make the device busy.

The traced window is the host annotation ``bench.window``, which the
harness opens around its measured window; a trace without one uses the
span of its device operations. Run as a script to read a trace by hand:

    python bench/trace_reduce.py <trace.xplane.pb>
"""
from __future__ import annotations

import collections
import dataclasses
import fnmatch
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class DeviceOps:
    device: str
    start: np.ndarray          # ns, operations
    end: np.ndarray
    name: List[str]
    module: List[str]          # each operation's XLA module
    leaf: np.ndarray           # bool: contains no other operation
    mod_start: np.ndarray      # ns, module executions
    mod_end: np.ndarray
    mod_name: List[str]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float              # averaged over devices
    n_devices: int
    selected_s: Dict[str, float]
    selected_parts: Dict[str, Dict[str, float]]   # selector -> pattern -> s
    device_ops: List[list]     # [[module/op, seconds]], most time first
    idle_gaps: List[list]      # [[host activity, seconds]], longest first
    idle_inflight_s: float = 0.0   # device idle, some call in flight

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def idle_share_inflight(self) -> Optional[float]:
        total = self.busy_s + self.idle_inflight_s
        return self.idle_inflight_s / total if total > 0 else None


def module_name(raw: str) -> str:
    return _SUFFIX.sub("", raw.strip())


def op_name(raw: str) -> str:
    """An operation's name without the HLO text the TPU trace appends:
    ``%fusion.12 = f32[...] fusion(...)`` reads ``fusion.12``."""
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _intervals(line):
    s, e, n = [], [], []
    for ev in line.events:
        s.append(float(ev.start_ns))
        e.append(float(ev.start_ns) + float(ev.duration_ns))
        n.append(ev.name)
    s, e = np.array(s), np.array(e)
    order = np.argsort(s, kind="stable")
    return s[order], e[order], [n[i] for i in order]


def device_ops(pd) -> List[DeviceOps]:
    """The operations and module executions of every device plane
    (``/device:<kind>:<n>``, not the host's). An operation belongs to the
    module execution that contains its start; it is a leaf when the next
    operation on its line starts after it ends (a loop contains its body's
    operations on the same line)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        s, e, n = _intervals(lines[OPS_LINE])
        if MODULES_LINE in lines:
            ms, me, mn = _intervals(lines[MODULES_LINE])
        else:
            ms, me, mn = np.zeros(0), np.zeros(0), []
        mn = [module_name(x) for x in mn]
        modules = []
        for st in s:
            j = int(np.searchsorted(ms, st, side="right")) - 1
            modules.append(mn[j] if j >= 0 and me[j] >= st else "?")
        leaf = np.ones(s.size, bool)
        if s.size > 1:
            leaf[:-1] = s[1:] >= e[:-1]
        out.append(DeviceOps(plane.name, s, e, [op_name(x) for x in n],
                             modules, leaf, ms, me, mn))
    return out


def host_annotations(pd, prefix: str = "bench.") -> List[Tuple[float, float,
                                                               str]]:
    """(start, end, name) of every host event whose name starts with
    ``prefix``."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(prefix):
                    out.append((float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns), ev.name))
    return out


def union(start: np.ndarray, end: np.ndarray, lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged intervals of [start, end) clipped to [lo, hi)."""
    s = np.clip(start, lo, hi)
    e = np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    merged: List[List[float]] = []
    for a, b in zip(s[order], e[order]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def intersect(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The overlap of two lists of disjoint sorted intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def _selected(d: DeviceOps, sel: dict, lo: float, hi: float) -> float:
    """Device time inside the executions of the selected modules or the
    selected operations (a union: nested operations count once)."""
    def hit(names, pats):
        return np.array([any(fnmatch.fnmatchcase(x, p) for p in pats)
                         for x in names], bool).reshape(-1)
    starts, ends = [], []
    if sel.get("modules"):
        m = hit(d.mod_name, sel["modules"])
        starts.append(d.mod_start[m])
        ends.append(d.mod_end[m])
    if sel.get("ops"):
        m = hit(d.name, sel["ops"])
        starts.append(d.start[m])
        ends.append(d.end[m])
    if not starts:
        return 0.0
    return _length(union(np.concatenate(starts), np.concatenate(ends),
                         lo, hi))


def _parts(devs, sel: dict, lo: float, hi: float) -> Dict[str, float]:
    """Seconds of each of a selector's patterns alone, over the devices."""
    return {pat: sum(_selected(d, {kind: [pat]}, lo, hi) for d in devs)
            / len(devs) * 1e-9
            for kind in ("modules", "ops") for pat in sel.get(kind, [])}


def reduce(path: str, selectors: Optional[Dict[str, dict]] = None,
           top: int = 10) -> Reduced:
    pd = load(path)
    devs = device_ops(pd)
    if not devs or not any(d.start.size for d in devs):
        raise ValueError(f"{path}: no device operations in the trace")
    notes = host_annotations(pd)
    win = [(a, b) for a, b, n in notes if n == WINDOW]
    if win:
        lo, hi = win[0]
    else:
        lo = min(float(d.start.min()) for d in devs if d.start.size)
        hi = max(float(d.end.max()) for d in devs if d.end.size)
    busy = [_length(union(d.start, d.end, lo, hi)) for d in devs]
    calls = [(a, b) for a, b, n in notes if n != WINDOW]
    inflight = union(np.array([a for a, _ in calls]),
                     np.array([b for _, b in calls]), lo, hi)
    idle_inflight = [_length(intersect(
        _gaps(union(d.start, d.end, lo, hi), lo, hi), inflight))
        for d in devs]
    selectors = selectors or {}
    selected = {name: sum(_selected(d, sel, lo, hi) for d in devs)
                / len(devs) * 1e-9
                for name, sel in selectors.items()}
    parts = {name: _parts(devs, sel, lo, hi)
             for name, sel in selectors.items()}
    per_op: Dict[str, float] = collections.Counter()
    for d in devs:
        dur = np.clip(d.end, lo, hi) - np.clip(d.start, lo, hi)
        for mod, nm, t, lf in zip(d.module, d.name, dur, d.leaf):
            if t > 0 and lf:
                per_op[f"{mod}/{nm}"] += float(t)
    ops_top = [[k, v / len(devs) * 1e-9]
               for k, v in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]
    gaps = _gaps(union(devs[0].start, devs[0].end, lo, hi), lo, hi)
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    idle = [[_activity(notes, (a + b) / 2), float(b - a) * 1e-9]
            for a, b in gaps[:top]]
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=float(np.mean(busy)) * 1e-9, n_devices=len(devs),
                   selected_s=selected, selected_parts=parts,
                   device_ops=ops_top, idle_gaps=idle,
                   idle_inflight_s=float(np.mean(idle_inflight)) * 1e-9)


def _gaps(busy: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _activity(notes, t: float) -> str:
    """The innermost ``bench.*`` annotation open at time ``t`` (other than
    the window itself), with how many such calls were open."""
    open_ = [(b - a, n) for a, b, n in notes
             if a <= t < b and n != WINDOW]
    if not open_:
        return "host: no call in flight"
    name = min(open_)[1]
    return f"{name} ({len(open_)} open)"


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):     # a stat the reader cannot give
        return {}


def describe(path: str, per_line: int = 12) -> str:
    """Planes, lines, event counts and the commonest event names with the
    stats of one example each: what to read before writing selectors."""
    pd = load(path)
    out = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for ln in lines:
            evs = list(ln.events)
            out.append(f"  LINE {ln.name!r}: {len(evs)} events")
            cnt = collections.Counter(e.name for e in evs)
            dur: Dict[str, float] = collections.Counter()
            ex = {}
            for e in evs:
                dur[e.name] += e.duration_ns
                ex.setdefault(e.name, e)
            for nm, _ in sorted(dur.items(), key=lambda kv: -kv[1])[:per_line]:
                e = ex[nm]
                out.append(f"    {nm!r} x{cnt[nm]} total_ms={dur[nm] / 1e6:.3f}"
                           f" first_start_ns={e.start_ns:.0f}"
                           f" stats={_stats(e)}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
