"""Declarative registry behind both analysis layers.

Pure-data sections (imported by the AST layer, no jax needed):

- ``HOT_PATH_MODULES`` — modules whose traced functions must stay free of
  host-sync ops (HMG001)
- ``STATIC_INT_PARAMS`` — jitted entry points and the (static) shape-like
  parameters whose call sites HMG002 audits, with positional indexes so
  positional spellings are caught too
- ``SANCTIONED_SHAPE_HELPERS`` — the blessed padding/rounding spellings a
  data-dependent shape must route through
- ``MVCC_ENTRY_POINTS`` — scan entry points that must thread visibility
  kwargs (HMG003), with the kwargs that satisfy the rule and whether the
  callee's default is provably None (enables the --fix kwarg insertion)

Trace-level sections (functions — importing them pulls in jax + the repo):

- ``trace_entries()`` — hot jitted entry points with canonical shapes,
  traced to jaxprs for HMG101/HMG102
- ``budget_entries()`` / ``entry_cache_sizes()`` — the compile-count
  accounting surface for HMG103 and the benchmarks' ``n_compiles`` column
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# --------------------------------------------------------------------- HMG001
# repo-relative path fragments; a file is hot iff one of these is a suffix
# of its posix path
HOT_PATH_MODULES = (
    "src/repro/core/ivf.py",
    "src/repro/core/delta.py",
    "src/repro/core/fusion.py",
    "src/repro/core/traversal.py",
    "src/repro/query/executor.py",
)
HOT_PATH_DIRS = ("src/repro/kernels/", "src/repro/obs/")

# --------------------------------------------------------------------- HMG002
# callee name -> {param name: positional index or None (kw-only)}.
# Positional indexes count every positional slot, 0-based.
STATIC_INT_PARAMS: Dict[str, Dict[str, Optional[int]]] = {
    "search": {"n_probe": None, "k": None, "query_block": None,
               "ef": None, "max_steps": None},
    "search_sharded": {"n_probe": None, "k": None, "query_block": None},
    "search_with_delta": {"n_probe": None, "k": None,
                          "rescore_margin": None},
    "search_with_delta_sharded": {"n_probe": None, "k": None,
                                  "rescore_margin": None},
    "search_raw": {"n_probe": 4, "k": 5},
    "_scan_delta": {"k": None, "margin": None},
    "scan_topk_quantized": {"k": None, "chunk": None, "block_n": None},
    "scan_topk_probed": {"k": None, "chunk": None, "block_n": None},
    "brute_force": {"k": None},
    "multi_hop_batch": {"n_hops": None, "top_m": None},
    "frontier_expand": {"n_hops": None, "top_m": None},
    "fuse_topk_sparse": {"k": 3},
    "fuse_topk": {"k": 3},
    "_fuse_candidates": {"k_fuse": None, "frontier": None},
}

# a data-dependent int expression is sanctioned when it routes through one
# of these helpers (repro/common/shapes.py) or the inline bit_length idiom
SANCTIONED_SHAPE_HELPERS = ("pow2_round", "pad_to_chunk", "bit_length")

# calls that *produce* data-dependent Python ints (the hazard markers)
HAZARD_CALLS = ("int", "len")

# --------------------------------------------------------------------- HMG003
# callee name -> (receivers or None for any, satisfying kwargs).
# The call must spell at least one of the kwargs explicitly (None counts:
# an explicit node_pass=None documents a conscious opt-out).
MVCC_ENTRY_POINTS: Dict[str, Tuple[Optional[Tuple[str, ...]],
                                   Tuple[str, ...]]] = {
    "search": (("ivf", "ivf_mod"), ("node_pass",)),
    "search_sharded": (None, ("node_pass",)),
    "search_with_delta": (None, ("node_pass", "mvcc_filter")),
    "search_with_delta_sharded": (None, ("node_pass", "mvcc_filter")),
    "_scan_delta": (None, ("node_pass",)),
}
# kwargs whose callee default is None in this repo — --fix may insert
# `<kwarg>=None` (provably behaviour-preserving)
MVCC_DEFAULT_NONE_KWARG = "node_pass"

# --------------------------------------------------------------------- HMG004
PERSISTENCE_DIRS = ("src/repro/persistence/", "src/repro/checkpoint/")
FSYNC_CALLS = ("fsync", "fsync_file", "fsync_dir", "_sync", "sync")
RENAME_CALLS = ("rename", "replace")      # as os.<name> attributes


# ------------------------------------------------------------- HMG201-HMG204
# Guarded-by registry: the shared mutable attributes of the repo's
# concurrent classes and the lock that guards each set. HMG201 enforces the
# discipline lexically (every read/write outside __init__ must sit inside a
# ``with <recv>.<lock>`` block or a ``*_locked`` method); the dynamic
# lockset checker (tools/racecheck.py) enforces it at runtime, importing
# the classes via ``module``. docs/DESIGN.md §9 renders this table.
@dataclasses.dataclass(frozen=True)
class GuardSpec:
    """One concurrent class and its guarded-by contract.

    ``attrs`` accessed via ``self.<attr>`` inside methods of ``cls`` — or
    via any receiver named in ``receivers`` anywhere in ``files`` — must be
    lexically inside ``with <recv>.<lock>`` (double-checked fast-path reads
    carry a reasoned pragma). ``module`` lets racecheck import the class
    for dynamic instrumentation."""
    cls: str
    module: str
    lock: str
    attrs: Tuple[str, ...]
    files: Tuple[str, ...]
    receivers: Tuple[str, ...] = ()       # non-self receivers to audit


GUARDED_BY: Tuple[GuardSpec, ...] = (
    GuardSpec("Histogram", "repro.obs.metrics", "_lock",
              ("bucket_counts", "count", "total", "vmax", "_window",
               "_wpos"),
              ("src/repro/obs/metrics.py",)),
    GuardSpec("MetricsRegistry", "repro.obs.metrics", "_lock",
              ("_counters", "_gauges", "_histograms"),
              ("src/repro/obs/metrics.py",)),
    GuardSpec("CheckpointManager", "repro.checkpoint.checkpoint", "_lock",
              ("_pending", "_error"),
              ("src/repro/checkpoint/checkpoint.py",)),
    GuardSpec("WorkloadStats", "repro.core.partitioner", "_lock",
              ("hits",),
              ("src/repro/core/partitioner.py", "src/repro/core/index.py",
               "src/repro/query/executor.py")),
    GuardSpec("Prefetcher", "repro.data.pipeline", "_lock",
              ("step", "q", "_stop", "_thread"),
              ("src/repro/data/pipeline.py",)),
    # ModalityIndex's lazily-built caches are owned by HMGIIndex's
    # _cache_lock (the facade builds/invalidates them; readers go through
    # the double-checked helpers) — accesses appear as ``m.<attr>``.
    GuardSpec("ModalityIndex", "repro.core.index", "_cache_lock",
              ("ivf_sharded", "id_rows"),
              ("src/repro/core/index.py", "src/repro/query/executor.py"),
              receivers=("m",)),
    # serving layer (PR 10): the hot-result cache's LRU dict, the admission
    # controller's token buckets, and the micro-batcher's combining-funnel
    # state are each guarded by their own leaf lock
    GuardSpec("HotResultCache", "repro.serving.cache", "_lock",
              ("_entries", "_stores"),
              ("src/repro/serving/cache.py",)),
    GuardSpec("AdmissionController", "repro.serving.scheduler", "_lock",
              ("_buckets",),
              ("src/repro/serving/scheduler.py",)),
    GuardSpec("MicroBatcher", "repro.serving.retrieval", "_lock",
              ("_pending", "_leader"),
              ("src/repro/serving/retrieval.py",)),
)

# Methods whose callers are required (and checked) to hold a lock: the
# ``*_locked`` suffix is the repo convention for "the caller already holds
# it". This maps each such method to the lock its body is considered to
# hold (HMG201 treats the body as guarded; HMG203 uses it for edges; call
# sites outside a ``with``-lock are HMG201 violations).
GUARDED_METHODS: Dict[str, str] = {
    "CheckpointManager._drain_pending_locked": "CheckpointManager._lock",
    "HMGIIndex._insert_locked": "HMGIIndex._write_lock",
    "HMGIIndex._maintain_locked": "HMGIIndex._write_lock",
    "HMGIIndex._ingest_locked": "HMGIIndex._write_lock",
    "HMGIIndex._compact_locked": "HMGIIndex._write_lock",
    "HMGIIndex._state_tree_locked": "HMGIIndex._write_lock",
    "HMGIIndex._restore_state_locked": "HMGIIndex._write_lock",
    "MicroBatcher._take_batch_locked": "MicroBatcher._lock",
}

# HMG202: calls that block (filesystem sync, host sync on device work,
# timed waits, thread/future joins) — none may run while one of the
# audited fine-grained locks is held, or every other thread touching that
# structure stalls behind the I/O. The coarse writer lock
# (HMGIIndex._write_lock) is deliberately NOT audited: it serialises
# mutations, and device work under it is the single-writer design.
BLOCKING_CALLS = ("fsync", "fsync_file", "fsync_dir", "sleep",
                  "block_until_ready", "join", "result", "wait",
                  "device_get")
HMG202_LOCK_ATTRS = ("_lock", "_cache_lock")

# HMG203: calls that acquire a known lock internally — lexical ``with``
# nesting alone would miss ``obs.counter(...).inc()`` under another lock.
# callee name -> lock node it acquires.
LOCK_ACQUIRING_CALLS: Dict[str, str] = {
    "counter": "MetricsRegistry._lock",
    "gauge": "MetricsRegistry._lock",
    "histogram": "MetricsRegistry._lock",
    "observe": "Histogram._lock",
    "observe_ms": "Histogram._lock",
    "inc": "Counter._lock",
    "record": "WorkloadStats._lock",
    "hits_snapshot": "WorkloadStats._lock",
    "load_hits": "WorkloadStats._lock",
    "_ensure_sharded": "HMGIIndex._cache_lock",
    "_modality_id_rows": "HMGIIndex._cache_lock",
    "try_admit": "AdmissionController._lock",
}

# HMG204: markers that a class runs background threads ("publication"
# starts at the first of these) and the constructors that create them.
THREAD_SPAWN_CALLS = ("Thread", "ThreadPoolExecutor", "Timer")
THREAD_START_CALLS = ("start", "submit")


# ===========================================================================
# trace-level registry (jax-importing; everything below is lazy)
# ===========================================================================

@dataclasses.dataclass
class TraceEntry:
    """One hot jitted entry point traced at canonical shapes.

    ``build`` returns (fn, args, kwargs) ready for ``jax.make_jaxpr``.
    ``max_upcast_elems`` — HMG101 threshold: an int8->f32
    ``convert_element_type`` of more elements than this (outside
    ``pallas_call``) is a slab-scale dequant, not the bounded rescore.
    None disables HMG101 for the entry (fp32-native paths)."""
    name: str
    build: Callable[[], Tuple[Callable, tuple, dict]]
    max_upcast_elems: Optional[int] = None


# canonical shapes — shared with tests/query_ref.py-style suites: small
# enough to trace in seconds, large enough that slab-scale and rescore-scale
# converts are an order of magnitude apart
_Q, _D, _K_PARTS, _N, _TOPK = 4, 32, 8, 512, 8


def _canonical_index():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ivf as ivf_mod
    rng = np.random.default_rng(0)
    v = rng.normal(size=(_N, _D)).astype(np.float32)
    idx, _ = ivf_mod.build(jax.random.PRNGKey(0), jnp.asarray(v),
                           jnp.arange(_N), n_partitions=_K_PARTS, bits=8)
    q = jnp.asarray(rng.normal(size=(_Q, _D)).astype(np.float32))
    return idx, q


def _canonical_delta():
    import jax.numpy as jnp
    import numpy as np
    from repro.core import delta as delta_mod
    rng = np.random.default_rng(1)
    d = delta_mod.init(128, _D, _N)
    d = delta_mod.insert(d, jnp.asarray(
        rng.normal(size=(96, _D)).astype(np.float32)),
        jnp.arange(96, dtype=jnp.int32))
    q = jnp.asarray(rng.normal(size=(_Q, _D)).astype(np.float32))
    return d, q


def trace_entries() -> List[TraceEntry]:
    import functools

    def ivf_search_kernel():
        from repro.core import ivf as ivf_mod
        idx, q = _canonical_index()
        fn = functools.partial(ivf_mod.search, n_probe=4, k=_TOPK,
                               impl="kernel")
        return fn, (idx, q), {}

    def delta_scan():
        from repro.core import delta as delta_mod
        d, q = _canonical_delta()
        fn = functools.partial(delta_mod._scan_delta, k=_TOPK)
        return fn, (d, q), {}

    def delta_search():
        from repro.core import delta as delta_mod
        idx, q = _canonical_index()
        d, _ = _canonical_delta()
        fn = functools.partial(delta_mod.search_with_delta, n_probe=4,
                               k=_TOPK)
        return fn, (idx, d, q), {}

    def kernel_probed():
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels.ivf_topk.ops import scan_topk_probed
        rng = np.random.default_rng(2)
        k_parts, cap = 8, 256
        fn = functools.partial(scan_topk_probed, k=_TOPK, chunk=16,
                               block_n=128)
        args = (jnp.asarray(rng.normal(size=(_Q, _D)).astype(np.float32)),
                jnp.asarray(rng.integers(-128, 127, size=(k_parts, cap, _D)
                                         ).astype(np.int8)),
                jnp.zeros((k_parts, cap), jnp.float32),
                jnp.ones((k_parts, cap), jnp.float32),
                jnp.ones((k_parts, cap), bool),
                jnp.asarray(rng.integers(0, k_parts, size=(_Q, 4)),
                            jnp.int32))
        return fn, args, {}

    def traverse():
        import jax.numpy as jnp
        import numpy as np
        from repro.core import traversal as trav_mod
        from repro.core.graph_store import from_edges
        rng = np.random.default_rng(3)
        e = 2048
        g = from_edges(_N, jnp.asarray(rng.integers(0, _N, e), jnp.int32),
                       jnp.asarray(rng.integers(0, _N, e), jnp.int32))
        ids = jnp.asarray(rng.integers(0, _N, size=(_Q, _TOPK)), jnp.int32)
        sc = jnp.asarray(rng.random(size=(_Q, _TOPK)).astype(np.float32))
        fn = functools.partial(trav_mod.multi_hop_batch, n_hops=2)
        return fn, (g, ids, sc), {}

    # HMG101 threshold: 2x the provable rescore gather (Q · k·chunk · d).
    # The smallest slab-scale dequant at canonical shapes is ≥ Q·M·d with
    # M = n_probe·cap, comfortably above it.
    rescore_budget = 2 * _Q * _TOPK * 16 * _D
    return [
        TraceEntry("ivf.search[int8-kernel]", ivf_search_kernel,
                   max_upcast_elems=rescore_budget),
        TraceEntry("delta._scan_delta", delta_scan,
                   max_upcast_elems=rescore_budget),
        TraceEntry("delta.search_with_delta[int8-kernel]", delta_search,
                   max_upcast_elems=rescore_budget),
        TraceEntry("ivf_topk.scan_topk_probed", kernel_probed,
                   max_upcast_elems=rescore_budget),
        TraceEntry("traversal.multi_hop_batch", traverse,
                   max_upcast_elems=None),
    ]


# --------------------------------------------------------------------- HMG103
# (entry name, module path, attribute) — every attribute is a jitted
# function exposing _cache_size(); distinct compiled signatures per entry
# are what budgets.json bounds.
BUDGET_ENTRIES: Sequence[Tuple[str, str, str]] = (
    ("ivf.search", "repro.core.ivf", "search"),
    ("ivf.brute_force", "repro.core.ivf", "brute_force"),
    ("delta.insert", "repro.core.delta", "insert"),
    ("delta.supersede", "repro.core.delta", "supersede"),
    ("delta.delete", "repro.core.delta", "delete"),
    ("delta._scan_delta", "repro.core.delta", "_scan_delta"),
    ("kernels.scan_topk_quantized",
     "repro.kernels.ivf_topk.ops", "scan_topk_quantized"),
    ("kernels.scan_topk_probed",
     "repro.kernels.ivf_topk.ops", "scan_topk_probed"),
    ("index._fuse_candidates", "repro.core.index", "_fuse_candidates"),
    ("executor._fuse_dense", "repro.query.executor", "_fuse_dense"),
    ("executor._rescore", "repro.query.executor", "_rescore"),
    ("partitioner.assign_with_distance",
     "repro.core.partitioner", "assign_with_distance"),
    ("nsw.search", "repro.core.nsw", "search"),
)


def budget_functions() -> Dict[str, object]:
    """entry name -> live jitted function object."""
    import importlib
    out = {}
    for name, mod, attr in BUDGET_ENTRIES:
        out[name] = getattr(importlib.import_module(mod), attr)
    return out


def entry_cache_sizes() -> Dict[str, int]:
    """Distinct compiled signatures currently cached per budget entry."""
    sizes = {}
    for name, fn in budget_functions().items():
        try:
            sizes[name] = int(fn._cache_size())
        except AttributeError:          # not a pjit function on this jax
            sizes[name] = -1
    return sizes


def total_cache_size() -> int:
    """Sum of compiled signatures across all budget entries (the
    benchmarks' ``n_compiles`` accounting surface)."""
    return sum(max(v, 0) for v in entry_cache_sizes().values())
