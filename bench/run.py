#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once, on the chip, and prints its
result as the last line of standard output.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run makes its data on the device from ``--seed``, builds the index
through ``HMGIIndex.ingest``, warms up the cell's own shapes, drives the
cell's traffic mix for ``--seconds``, then checks what the timed path
answered against the plain reference in ``reference.py`` and prints
``{"correct", "attempted", "failed", "metrics", "device", ..., "checks"}``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, each read by ``metrics/<name>.py``.

Without a TPU it exits nonzero and prints no result. Other options, which
the benchmark's own runs never pass:

- ``--rehearse``: tiny sizes on any backend (Pallas interpret mode off
  the chip); prints the result line with no metrics;
- ``--control``: the control takes the served answers' place in the
  check (the reference in the program's place, in lower precision), so
  the run must read ``correct: false``; the served answers' numbers are
  logged beside it;
- ``--sweep R1,R2,..``: for an open-loop cell, one set-up, then each rate
  for ``--seconds`` with latency and throughput per rate and no check.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refusal(Exception):
    """The run cannot be made here: exit nonzero, print no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict                 # BENCHMARK.json
    entry: dict                # its entry in "workloads"
    cfg: dict                  # configs/<config>.json
    mix: dict                  # traffic/<traffic>.json
    limits: dict               # limits/<cell>.json

    def metric_names(self, trace: bool) -> List[str]:
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m["name"] for m in group
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str) -> Cell:
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        raise Refusal(f"no {spec_path.name} at {ROOT}")
    spec = json.loads(spec_path.read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refusal(f"no workload {name!r} in {spec_path.name}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return Cell(name, int(entry["chips"]), spec, entry,
                json.loads((ROOT / conf["file"]).read_text()),
                json.loads((BENCH / "traffic" / f"{entry['traffic']}.json")
                           .read_text()),
                json.loads((BENCH / "limits" / f"{name}.json").read_text()))


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class WindowWatch:
    """While ``on``: compile requests that reach XLA (a jit cache miss),
    the persistent-cache misses among them (compiled anew), and the
    interpreter's garbage-collection pauses."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax
        self.on = False
        self.counts = {"requests": 0, "misses": 0}
        self.gc_pauses: List[float] = []
        self._gc_t0 = 0.0
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, event, **_):
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.on:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)

    def summary(self) -> dict:
        p = self.gc_pauses
        return {"compile_requests": self.counts["requests"],
                "compile_cache_misses": self.counts["misses"],
                "gc_pauses": len(p), "gc_s": float(sum(p)),
                "gc_max_s": float(max(p, default=0.0))}


@dataclasses.dataclass
class Run:
    """What a run knows when its per-layer metrics are read."""
    cell: Cell
    device_kind: str
    trace: Optional[object] = None          # trace_reduce.Reduced
    ingest_s: float = math.nan
    communities_s: Optional[float] = None
    batch_q: Optional[dict] = None          # serving.batch_q summary
    answered: int = 0
    scan_work: List[tuple] = dataclasses.field(default_factory=list)


# ------------------------------------------------------------------- set-up
def build_index(cell: Cell, corpus):
    from repro.configs.hmgi import CONFIG
    from repro.core import HMGIIndex
    ix = cell.cfg["index"]
    mod = cell.cfg["modality"]
    d = int(corpus.vectors.shape[1])
    cfg = CONFIG.replace(modalities=(mod,), modality_dims={mod: d},
                         dim=d, **ix)
    index = HMGIIndex(cfg, seed=0)
    index.ingest({mod: (corpus.ids, corpus.vectors)}, n_nodes=corpus.n_nodes,
                 edges=corpus.edges, node_attrs=corpus.attrs or None)
    import jax
    jax.block_until_ready(index.modalities[mod].ivf.data)
    return index


def plans_of(cell: Cell):
    from repro.serving.retrieval import RetrievalPlan, freeze_where
    k = int(cell.cfg["index"]["top_k"])
    return [RetrievalPlan(cell.cfg["modality"], k,
                          n_hops=int(c.get("n_hops", 0)),
                          where=freeze_where(tuple(c["where"]))
                          if c.get("where") else None)
            for c in cell.mix["classes"]]


def node_pass_of(cls: dict, attrs: dict):
    if not cls.get("where"):
        return None
    col, op, val = cls["where"]
    v = attrs[col]
    return {"<": v < val, "<=": v <= val, ">": v > val, ">=": v >= val,
            "==": v == val, "!=": v != val}[op]


def warm_shapes(cell: Cell) -> List[int]:
    """The batch sizes the window can send: the closed loop's batch, or
    every power-of-two bucket the micro-batcher can fill."""
    mix = cell.mix
    if mix["loop"] == "closed":
        return [int(mix["batch"])]
    out, b = [], 2
    while b <= int(mix["service"]["max_batch"]):
        out.append(b)
        b *= 2
    return out


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Refusal as e:
        log(f"bench: {e}")
        return 2


def run(args) -> int:
    cell = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise Refusal(f"the program's sources are not at {ROOT / 'src'}")
    if not args.rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    backend = jax.default_backend()
    if not args.rehearse and backend != "tpu":
        raise Refusal(f"JAX found no TPU (backend {backend!r}); the "
                      "benchmark runs on the chip only")
    devices = jax.devices()
    if len(devices) < cell.chips:
        raise Refusal(f"{cell.name} needs {cell.chips} chips, JAX sees "
                      f"{len(devices)}")
    if args.rehearse:
        cell.cfg = merge(cell.cfg, cell.cfg.get("rehearse", {}))
        cell.mix = merge(cell.mix, cell.mix.get("rehearse", {}))
    else:
        # every program, however quick to compile, is kept: the second run
        # of a cell in a checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.common.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache() if not args.rehearse else None
    dev = devices[0]
    log(f"bench: cell={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={dev.device_kind} x{len(devices)} "
        f"compile_cache={cache_dir}")
    return Harness(cell, args, dev).go()


class Harness:
    def __init__(self, cell: Cell, args, dev):
        import jax
        self.cell, self.args, self.dev = cell, args, dev
        self.jax = jax
        self.watch = WindowWatch()
        self.phases: Dict[str, float] = {}

    def phase(self, name: str, t0: float) -> None:
        self.phases[name] = time.perf_counter() - t0
        log(f"[phase] {name}: {self.phases[name]:.3f} s")

    # ----------------------------------------------------------------- run
    def go(self) -> int:
        import numpy as np
        from repro import obs
        from bench import data as data_mod
        cell, args = self.cell, self.args
        mix = cell.mix
        t = time.perf_counter()
        corpus = data_mod.make(cell.cfg["data"])
        self.jax.block_until_ready(corpus.vectors)
        self.phase("data", t)
        user_bytes = corpus.user_bytes()
        attrs = dict(corpus.attrs)
        edges = corpus.edges
        n_nodes = corpus.n_nodes
        dim = int(corpus.vectors.shape[1])

        sweep = [float(r) for r in args.sweep.split(",") if r]
        if sweep and mix["loop"] != "open":
            raise Refusal(f"{cell.name} is a closed loop: no rate to sweep")
        if mix["loop"] == "open":
            rates = sweep or [float(mix["rate_qps"])]
            scheds = {r: traffic().open_schedule(mix, args.seconds, rate=r)
                      for r in rates}
            n_queries = max(len(s) for s in scheds.values())
        else:
            n_queries = int(mix["query_pool"])
        t = time.perf_counter()
        pool = data_mod.queries(corpus, args.seed, n_queries,
                                float(mix["query_noise"]))
        self.phase("queries", t)

        obs.reset()
        t = time.perf_counter()
        index = build_index(cell, corpus)
        ingest_s = time.perf_counter() - t
        comm = obs.histogram("index.communities").summary()
        communities_s = comm["sum"] / 1e3 if comm["count"] else None
        self.phase("ingest", t)
        m = index.modalities[cell.cfg["modality"]]
        log(f"ingest: communities_s={communities_s} "
            f"delta_rows={int(m.delta.count)} "
            f"delta_capacity={int(m.delta.ids.shape[0])} "
            f"memory_usage={index.memory_usage()}")
        del m
        del corpus
        gc.collect()

        from repro.serving.retrieval import RetrievalService
        svc = mix.get("service", {})
        service = RetrievalService(
            index, batching=bool(svc.get("batching", False)),
            window_s=float(svc.get("window_s", 0.001)),
            max_batch=int(svc.get("max_batch", 64)))
        plans = plans_of(cell)

        if mix["loop"] == "closed":
            def call(cls, q0, n):
                return service.search_many(plans[cls], pool[q0:q0 + n])
        else:
            def call(cls, q0, n):
                return service.search(plans[cls], pool[q0])

        t = time.perf_counter()
        for plan in plans:
            for b in warm_shapes(cell):
                self.jax.block_until_ready(service.search_many(plan, pool[:b]))
        self.phase("warmup", t)
        gc.collect()
        stats = self.dev.memory_stats() or {}
        in_use = stats.get("bytes_in_use")
        setup_s = time.perf_counter() - T_START
        log(f"set-up: {setup_s:.3f} s; bytes_in_use={in_use} "
            f"bytes_limit={stats.get('bytes_limit')} user_bytes={user_bytes}")

        # what set-up built stays for the process: full collections in the
        # window need not walk it
        gc.freeze()
        if sweep:
            return self.sweep(call, scheds)

        tracedir = None
        annotate = contextlib.nullcontext
        if args.trace and args.rehearse:
            log("bench: no trace in a rehearsal (no device to trace)")
            args.trace = 0
        if args.trace:
            annotate = self.jax.profiler.TraceAnnotation
            tracedir = tempfile.mkdtemp(prefix="hmgi-bench-trace-")
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.jax.profiler.start_trace(tracedir, profiler_options=opts)
        obs.reset()
        self.watch.on = True
        with annotate("bench.window") if args.trace else \
                contextlib.nullcontext():
            if mix["loop"] == "closed":
                win = traffic().run_closed(
                    call, mix, args.seconds,
                    annotate if args.trace else _null)
            else:
                win = traffic().run_open(
                    call, scheds[float(mix["rate_qps"])],
                    int(mix["workers"]), args.seconds,
                    annotate if args.trace else _null)
        self.watch.on = False
        batch_q = obs.histogram("serving.batch_q").summary()
        if args.trace:
            self.jax.profiler.stop_trace()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.jax.devices()[:cell.chips])
        n_done = win.queries_answered()
        lat = win.latencies_ms()
        late = win.lateness_ms()
        failed = sum(1 for r in win.requests if r.ids is None)
        log(f"window: requests={len(win.requests)} answered_queries={n_done} "
            f"elapsed_s={win.elapsed_s:.3f} failed={failed} "
            f"{self.watch.summary()} "
            f"lateness_ms p50={_pct(late, 50)} p99={_pct(late, 99)} "
            f"max={float(np.max(late)) if late.size else None}")

        # ------------------------------------------------------ the check
        from bench import reference as ref_mod
        layout = ref_mod.Layout.of(index, cell.cfg["modality"])
        del call, service, index, plans
        gc.collect()
        t = time.perf_counter()
        checks, recall, work = self.check(layout, win, pool, attrs, edges,
                                          n_nodes, dim)
        self.phase("check", t)

        e2e = {
            "setup_s": (setup_s, "s"),
            "qps": (n_done / win.elapsed_s if win.elapsed_s > 0 else 0.0,
                    "q/s"),
            "p95_ms": (_nearest(lat, 95), "ms"),
            "recall_at_10": (recall, "ratio"),
            "device_bytes_ratio": ((in_use or 0) / user_bytes, "B/B"),
        }
        result_metrics = {}
        if args.trace:
            reduced = self.reduce_trace(tracedir)
            run_ = Run(cell, self.dev.device_kind, reduced, ingest_s,
                       communities_s, batch_q, n_done, work)
            for name in cell.metric_names(trace=True):
                mod = load_metric(name)
                v = mod.read(run_)
                if v is None:
                    log(f"bench: per-layer metric {name} found nothing to "
                        f"read in this trace; selected device time: "
                        f"{reduced.selected_parts.get(name)}")
                    continue
                result_metrics[name] = {"value": float(v), "unit": mod.UNIT}
        else:
            for name in cell.metric_names(trace=False):
                v, unit = e2e[name]
                result_metrics[name] = {"value": float(v), "unit": unit}

        correct = all(_holds(c) for c in checks.values())
        for name, c in checks.items():
            side = "at least" if c.get("at_least") else "limit"
            log(f"check {name} = {c['value']!r} ({side} {c['limit']!r}) "
                f"{'ok' if _holds(c) else 'FAIL'}")
        device = {"platform": self.dev.platform,
                  "kind": self.dev.device_kind,
                  "count": len(self.jax.devices()),
                  "memory_peak_bytes": int(peak)}
        out = {"correct": bool(correct),
               "attempted": int(sum(r.n for r in win.requests)),
               "failed": int(sum(r.n for r in win.requests
                                 if r.ids is None)),
               "metrics": {} if args.rehearse else result_metrics,
               "device": device}
        if args.trace:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            out["breakdown"] = {"device_ops": reduced.device_ops,
                                "idle_gaps": reduced.idle_gaps}
        out["window"] = {
            "elapsed_s": win.elapsed_s, "requests": len(win.requests),
            **self.watch.summary(),
            "lateness_ms_p99": _pct(late, 99),
            "lateness_ms_max": float(np.max(late)) if late.size else None,
            "phases_s": self.phases, "setup_s": setup_s,
            "batch_q_mean": (batch_q["sum"] / batch_q["count"]
                             if batch_q["count"] else None)}
        if args.rehearse:
            out["rehearsal"] = True
        out["checks"] = checks
        print(json.dumps(_finite(out)), flush=True)
        return 0

    # --------------------------------------------------------------- sweep
    def sweep(self, call, scheds) -> int:
        import numpy as np
        from repro import obs
        rows = []
        for rate, reqs in scheds.items():
            obs.reset()
            win = traffic().run_open(call, reqs, int(self.cell.mix["workers"]),
                                     self.args.seconds, _null)
            lat = win.latencies_ms()
            late = win.lateness_ms()
            bq = obs.histogram("serving.batch_q").summary()
            row = {"rate_qps": rate, "requests": len(reqs),
                   "answered": win.queries_answered(),
                   "throughput_qps": win.queries_answered() / win.elapsed_s,
                   "elapsed_s": win.elapsed_s,
                   "p50_ms": _nearest(lat, 50), "p95_ms": _nearest(lat, 95),
                   "p99_ms": _nearest(lat, 99),
                   "lateness_ms_p99": _pct(late, 99),
                   "batch_q_mean": bq["sum"] / max(bq["count"], 1)}
            log(f"sweep: {json.dumps(_finite(row))}")
            rows.append(row)
        print(json.dumps(_finite({"sweep": rows})), flush=True)
        return 0

    # --------------------------------------------------------------- trace
    def reduce_trace(self, tracedir: str):
        from bench import trace_reduce
        try:
            files = sorted(pathlib.Path(tracedir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            selectors = {}
            for name in self.cell.metric_names(trace=True):
                sel = getattr(load_metric(name), "SELECT", None)
                if sel:
                    selectors[name] = sel
            return trace_reduce.reduce(str(files[-1]), selectors)
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)

    # --------------------------------------------------------------- check
    def check(self, layout, win, pool, attrs, edges, n_nodes, dim):
        """Regenerates the corpus, checks the stored layout against it,
        and compares a seeded sample of the window's answers with the
        reference. Returns (checks, recall, per-call scan work)."""
        import numpy as np
        from bench import data as data_mod
        from bench import reference as ref_mod
        from bench import work as work_mod
        cell, args = self.cell, self.args
        mix = cell.mix
        corpus = data_mod.make(cell.cfg["data"])
        vec_dev = corpus.vectors
        lay_checks = ref_mod.check_layout(
            layout, vec_dev, int(cell.cfg["index"]["quant_bits"]))
        if edges is not None:
            lay_checks["community_modularity"] = ref_mod.modularity(
                edges, n_nodes, layout.communities)
        vectors = np.asarray(vec_dev)
        classes = mix["classes"]
        hops = {int(c.get("n_hops", 0)) for c in classes}
        sem = ref_mod.Semantics.of(cell.cfg, max(hops))
        tie = float(cell.limits["score_gap"])
        ref = ref_mod.Reference(layout, vectors, sem, edges=edges,
                                n_nodes=n_nodes, tie=tie)
        passes = [node_pass_of(c, attrs) for c in classes]

        # the served queries, one row each: (class, pool row, scores, ids)
        rows = []
        for r in win.answered:
            for j in range(r.n):
                rows.append((r.cls, r.q0 + j, r.scores[j], r.ids[j]))
        rng = np.random.default_rng(args.seed)
        order = rng.permutation(len(rows))
        n_check = min(int(mix["check_sample"]), len(rows))
        n_recall = min(int(mix["recall_sample"]), len(rows))
        checked = order[:n_check]

        totals = {"score_gap": 0.0, "id_miss": 0, "predicate_miss": 0,
                  "compared": 0, "ambiguous": 0}
        control = {"score_gap": 0.0, "id_miss": 0, "predicate_miss": 0}
        precisions = ["f64"] + (["bf16x3"] if args.control else [])
        diag = []
        for ci, cls in enumerate(classes):
            sel = [i for i in checked if rows[i][0] == ci]
            for lo in range(0, len(sel), 128):
                part = sel[lo:lo + 128]
                q = pool[[rows[i][1] for i in part]]
                ss = np.stack([rows[i][2] for i in part])
                si = np.stack([rows[i][3] for i in part])
                answers = {}
                for prec in precisions:
                    if cls.get("n_hops", 0):
                        answers[prec] = ref.hybrid(q, passes[ci], prec)
                    else:
                        answers[prec] = ref.search(q, sem.k, passes[ci], prec)
                npass = [passes[ci]] * len(part)
                got = ref_mod.compare(ss, si, answers["f64"], tie, npass)
                _fold(totals, got)
                seeds = answers["f64"].seeds
                for j, g in enumerate(got["per_query"]):
                    spread = math.nan
                    if seeds is not None:
                        v = seeds.scores[j][np.isfinite(seeds.scores[j])]
                        spread = float(v.max() - v.min()) if v.size else 0.0
                    diag.append((float(g), spread, ci))
                if args.control:
                    low = answers["bf16x3"]
                    got = ref_mod.compare(low.scores, low.ids, answers["f64"],
                                          tie, npass)
                    _fold(control, got)
        log(f"reference: compared={totals['compared']} "
            f"ambiguous={totals['ambiguous']} of {n_check} sampled")
        if diag:
            gaps = np.array([d[0] for d in diag])
            worst = sorted(diag, reverse=True)[:5]
            log(f"score gap per query: median={float(np.median(gaps))!r} "
                f"p90={float(np.percentile(gaps, 90))!r} worst (gap, seed score "
                f"spread, class)={worst}")
        if args.control:
            log(f"served answers (the control takes their place below): "
                f"score_gap={float(totals['score_gap'])!r} "
                f"id_miss={totals['id_miss']} "
                f"predicate_miss={totals['predicate_miss']}")
            totals.update(control)

        recall = self.recall(vec_dev, ref, rows, order[:n_recall], pool,
                             classes, passes, sem)
        del vec_dev, corpus
        work = []
        if mix["loop"] == "closed":
            live = layout.stable_live().sum(axis=1)
            cent = np.asarray(layout.centroids, np.float64)
            for r in win.answered:
                qn = ref_mod.unit64(pool[r.q0:r.q0 + r.n])
                cs = qn @ cent.T - 0.5 * np.sum(cent ** 2, axis=1)[None]
                probes = np.argsort(-cs, axis=1)[:, :sem.n_probe]
                work.append(work_mod.seed_scan_work(probes, live, dim))
        checks = {}
        values = dict(lay_checks)
        values.update(score_gap=totals["score_gap"],
                      id_miss=totals["id_miss"],
                      predicate_miss=totals["predicate_miss"],
                      unanswered=sum(1 for r in win.requests
                                     if r.ids is None))
        for name, v in values.items():
            checks[name] = {"value": float(v),
                            "limit": float(cell.limits[name])}
            if name in AT_LEAST:
                checks[name]["at_least"] = True
        return checks, recall, work

    def recall(self, vec_dev, ref, rows, sample, pool, classes, passes, sem):
        """Overlap of served top-k with the exact answer: float32 brute
        force at HIGHEST over every row for vector search; the hybrid
        semantics from brute-force seeds for hybrid search."""
        import jax.numpy as jnp
        import numpy as np
        from bench import exact
        from bench import reference as ref_mod
        vn = exact.normalise(vec_dev)
        hits = []
        for ci, cls in enumerate(classes):
            sel = [i for i in sample if rows[i][0] == ci]
            hop = int(cls.get("n_hops", 0))
            width = sem.seed_width if hop else sem.k
            npass = None if passes[ci] is None else jnp.asarray(passes[ci])
            for lo in range(0, len(sel), 128):
                part = sel[lo:lo + 128]
                q = pool[[rows[i][1] for i in part]]
                bs, bi = exact.top_k(vn, q, width, npass)
                if hop:
                    seeds = ref_mod.Answer(bs, bi, [], np.zeros(len(part),
                                                                bool))
                    truth = ref.hybrid(q, passes[ci], "f64", seeds=seeds).ids
                else:
                    truth = bi
                served = np.stack([rows[i][3] for i in part])
                hits.append((ref_mod.recall(served, truth), len(part)))
        n = sum(c for _, c in hits)
        return sum(h * c for h, c in hits) / max(n, 1)


# numbers compared whose limit is a floor: the rest may not exceed theirs
AT_LEAST = {"community_modularity"}


def _holds(c: dict) -> bool:
    if c.get("at_least"):
        return c["value"] >= c["limit"]
    return c["value"] <= c["limit"]


def _finite(x):
    """JSON has no infinity: a value that is not finite (an answer that
    never came, a score gap against a missing answer) prints as 1e300."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def _fold(acc: dict, got: dict) -> None:
    for k, v in got.items():
        if k == "score_gap":
            acc[k] = max(acc[k], v)
        elif k in acc:
            acc[k] += v


def _null(_name):
    return contextlib.nullcontext()


def _pct(values, p):
    import numpy as np
    return float(np.percentile(values, p)) if len(values) else None


def _nearest(values, p):
    from bench.traffic import nearest_rank
    return nearest_rank(values, p)


def traffic():
    from bench import traffic as traffic_mod
    return traffic_mod


if __name__ == "__main__":
    sys.exit(main())
