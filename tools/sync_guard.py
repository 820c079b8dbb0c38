#!/usr/bin/env python3
"""Finds device-to-host reads on the served path that do not go through
``repro.obs.to_host``.

Builds a small index with a ``year`` column and serves one micro-batched
2-hop hybrid call of each plan class the hybrid benchmark cell serves
(``pushdown``: a selective ``where``, the planner pushes the mask into the
scan; ``oversample``: a loose one, it scans wider and post-filters) and a
vector-only call, each first compiled, then repeated under
``jax.transfer_guard_device_to_host("disallow")``. ``to_host`` allows its
own reads, so a call that raises names a read that bypasses it. A control
read with plain ``np.asarray`` must raise, or the guard is not armed.

Prints one line per call, ``<class> {"result", "mode", "syncs",
"compiles"}`` (the planner's filter mode; syncs and compiles counted over
the guarded call), and last a JSON
object with ``"ok"``. Exits nonzero unless every call passed and the
control raised.

Usage:
  python tools/sync_guard.py                      # on a TPU
  JAX_PLATFORMS=cpu python tools/sync_guard.py --rehearse
                                  # the guard does not fire on the CPU
                                  # backend: counts only, never ok
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

CLASSES = {"pushdown": ("year", ">=", 2026),
           "oversample": ("year", ">=", 2001), "vector": None}


def build(n_rows: int, seed: int):
    import numpy as np
    from repro.configs import get_config
    from repro.core import HMGIIndex
    rng = np.random.default_rng(seed)
    cfg = get_config("hmgi").replace(
        modalities=("text",), n_partitions=16, n_probe=4, kmeans_iters=4,
        top_k=10, delta_capacity=256)
    idx = HMGIIndex(cfg, seed=seed)
    vecs = rng.normal(size=(n_rows, cfg.dim)).astype(np.float32)
    src = rng.integers(0, n_rows, 8 * n_rows)
    dst = rng.integers(0, n_rows, 8 * n_rows)
    idx.ingest({"text": (np.arange(n_rows), vecs)}, n_nodes=n_rows,
               edges=(src, dst),
               node_attrs={"year": rng.integers(2000, 2030, n_rows)
                           .astype(np.int32)})
    return idx, vecs


def guarded_call(idx, svc, plan, q) -> dict:
    import jax
    from repro import obs
    svc.search(plan, q)                                   # compile
    obs.reset()
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            svc.search(plan, q)
        result = "ok"
    except Exception as e:                                # noqa: BLE001
        result = f"raised {type(e).__name__}: {str(e)[:300]}"
    counters = obs.registry().counters()
    return {"result": result,
            "mode": idx.metrics().get("filter_mode") if plan.where else None,
            "syncs": counters["executor.syncs"].value
            if "executor.syncs" in counters else None,
            "compiles": counters["executor.compiles"].value
            if "executor.compiles" in counters else None}


def control_raises() -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    x = jnp.arange(3.0) + 1.0
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            np.asarray(x)
    except Exception:                                     # noqa: BLE001
        return True
    return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=4000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the TPU (the guard is not armed there)")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu" and not args.rehearse:
        print(f"sync_guard: backend {jax.default_backend()} is not a TPU",
              file=sys.stderr)
        return 2
    from repro.serving.retrieval import RetrievalPlan, RetrievalService
    idx, vecs = build(args.rows, args.seed)
    svc = RetrievalService(idx, batching=True, window_s=0.001)
    out = {}
    for i, (cls, where) in enumerate(CLASSES.items()):
        plan = RetrievalPlan("text", k=10, n_hops=0 if where is None else 2,
                             where=where)
        out[cls] = guarded_call(idx, svc, plan, vecs[i])
        print(cls, json.dumps(out[cls]), flush=True)
    control = control_raises()
    calls_ok = all(r["result"] == "ok" for r in out.values())
    ok = calls_ok and control and not args.rehearse
    print(json.dumps({"ok": ok, "calls": out, "control_raised": control}),
          flush=True)
    return 0 if ok or (args.rehearse and calls_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
