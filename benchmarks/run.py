"""Benchmark harness — one module per paper table/figure.

  paper_tables  — Tables 4-7: QPS / recall@10 / memory / latency,
                  HMGI vs monolithic vs decoupled baselines
  ablations     — §5.1 partitioning, §5.2 updates+quantization, §5.3 fusion
  scaling       — §4.5 sub-linear query scaling
  kernels_bench — Pallas kernel accounting (incl. kernel-vs-einsum probe path)
  hybrid_bench  — hybrid query: sparse vs dense fusion, end-to-end latency
  filtered_bench — attribute-filtered search: pushdown vs post-filter sweep
  query_bench   — declarative query engine: relationship-heavy canned plans
                  (ms/query + compiled plan choice)
  sharded_bench — sharded execution path: 1/2/4/8-shard probe+merge scaling
  maintenance_bench — adaptive maintenance: ingest stall (incremental drain
                  vs full compact) + post-maintenance query latency
  persistence_bench — durability: snapshot write/restore latency, WAL append
                  overhead on ingest, recovery time vs replay length

Prints ``name,us_per_call,derived,n_compiles,p50_ms,p99_ms`` CSV —
``n_compiles`` is the running count of distinct compiled signatures across
the staticcheck (HMG103) registry entries, so jit respecialisation is
visible per row; ``p50_ms``/``p99_ms`` are the obs registry's
``query.execute`` histogram quantiles accumulated since the previous row
(blank for rows that never enter the query executor).
Usage: PYTHONPATH=src python -m benchmarks.run [--only <module>]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["paper_tables", "ablations", "scaling",
                             "kernels_bench", "hybrid_bench",
                             "filtered_bench", "query_bench",
                             "sharded_bench", "maintenance_bench",
                             "persistence_bench"])
    args = ap.parse_args()

    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []

    from benchmarks.common import total_compiles
    from repro import obs

    def report(name: str, us_per_call: float, derived: str = ""):
        n_compiles = total_compiles()
        # per-query latency quantiles since the previous row, from the obs
        # registry's "query.execute" histogram (facade-path rows only;
        # rows that never enter the query executor print blanks)
        h = obs.registry().histogram("query.execute")
        p50 = f"{h.percentile(50):.3f}" if h.count else ""
        p99 = f"{h.percentile(99):.3f}" if h.count else ""
        obs.reset()
        rows.append((name, us_per_call, derived, n_compiles))
        print(f"{name},{us_per_call:.3f},{derived},{n_compiles},{p50},{p99}",
              flush=True)

    from benchmarks import (ablations, filtered_bench, hybrid_bench,
                            kernels_bench, maintenance_bench, paper_tables,
                            persistence_bench, query_bench, scaling,
                            sharded_bench)
    mods = {"paper_tables": paper_tables, "ablations": ablations,
            "scaling": scaling, "kernels_bench": kernels_bench,
            "hybrid_bench": hybrid_bench, "filtered_bench": filtered_bench,
            "query_bench": query_bench, "sharded_bench": sharded_bench,
            "maintenance_bench": maintenance_bench,
            "persistence_bench": persistence_bench}
    selected = [mods[args.only]] if args.only else list(mods.values())

    print("name,us_per_call,derived,n_compiles,p50_ms,p99_ms")
    failed = 0
    for mod in selected:
        try:
            mod.run(report)
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
    print(f"# done: {len(rows)} rows, {failed} module failures", flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
