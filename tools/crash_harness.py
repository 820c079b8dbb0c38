"""Kill-and-recover fault-injection harness for the durable index lifecycle.

For each registered crash point (``repro.persistence.faultpoints.POINTS``)
the harness:

1. runs a child process applying a fixed, deterministic op script to a
   ``DurableHMGIIndex`` with the fault point armed via ``HMGI_FAULTPOINT``
   — the child dies with ``os._exit(137)`` (SIGKILL semantics: no flush,
   no atexit, no finally) at the durability boundary;
2. runs a second child (``--verify``) that recovers the data dir and reads
   the recovered ``last_seq`` D, builds a *golden* index by applying the
   first D logged ops of the same script (plus the interleaved searches
   that precede them — workload heat must match too) to a fresh in-memory
   ``HMGIIndex``, and asserts ``search`` and ``hybrid_search`` results are
   **bit-identical** between recovered and golden.

The parent process never imports JAX: on an accelerator host a parent that
holds the device would leave the children none.

``recover.*`` points crash the *recovery* instead: the child runs clean,
a second child dies mid-replay, and the harness asserts the next recovery
still matches golden (replay is read-only until the final log truncation,
so a crashed recovery is always re-runnable).

Usage:
    python tools/crash_harness.py --sweep              # every crash point
    python tools/crash_harness.py --point wal.pre_append
    python tools/crash_harness.py --child --data-dir D # (internal)
    python tools/crash_harness.py --verify --point P --data-dir D  # (internal)
"""
from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import HMGIConfig  # noqa: E402


def make_cfg() -> HMGIConfig:
    return HMGIConfig(modalities=("text", "image"), dim=12,
                      n_partitions=4, n_probe=4, kmeans_iters=4,
                      delta_capacity=64, use_nsw_refine=True,
                      snapshot_keep=2)


def queries() -> np.ndarray:
    return np.random.default_rng(99).standard_normal((4, 12)).astype(np.float32)


def scripted_ops():
    """Deterministic op script. ``("op", ...)`` entries are logged (one WAL
    record each, in order); ``search``/``snapshot`` entries are not logged
    but matter — searches move workload heat, snapshots set the recovery
    base. The script covers stable + delta + post-maintenance states and
    leaves a replay tail after the last snapshot."""
    rng = np.random.default_rng(7)
    n, d = 160, 12
    emb = {m: (np.arange(n, dtype=np.int32),
               rng.standard_normal((n, d)).astype(np.float32))
           for m in ("text", "image")}
    edges = (rng.integers(0, n, 400).astype(np.int32),
             rng.integers(0, n, 400).astype(np.int32))
    attrs = {"cat": rng.integers(0, 4, n).astype(np.int32)}
    ins = lambda lo, hi: (np.arange(lo, hi, dtype=np.int32),
                          rng.standard_normal((hi - lo, d)).astype(np.float32))
    return [
        ("ingest", emb, n, edges, attrs),                       # seq 1
        ("search", "text"), ("search", "image"),
        ("insert", "text", *ins(160, 180)),                     # seq 2
        ("search", "text"),
        ("delete", "text", np.arange(3, dtype=np.int32)),       # seq 3
        ("maintain",),                                          # seq 4
        ("snapshot",),
        ("insert", "image", *ins(180, 200)),                    # seq 5
        ("compact", "text"),                                    # seq 6
        ("search", "image"),
        ("insert", "text", *ins(200, 212)),                     # seq 7
        ("snapshot",),
        ("insert", "text", *ins(212, 224)),                     # seq 8
        ("delete", "image", np.arange(8, dtype=np.int32)),      # seq 9
        ("maintain",),                                          # seq 10
    ]


def apply_ops(index, ops, until=None):
    """Applies script entries to ``index`` in order, stopping once ``until``
    logged ops have been applied (searches past that point are skipped too —
    the recovered index's heat is the stamp of the last replayed record)."""
    q = queries()
    done = 0
    for entry in ops:
        kind = entry[0]
        if kind == "search":
            index.search(q, entry[1], k=5)
            continue
        if kind == "snapshot":
            if hasattr(index, "snapshot"):
                index.snapshot()
            continue
        if until is not None and done >= until:
            break
        if kind == "ingest":
            _, emb, n, edges, attrs = entry
            index.ingest(emb, n, edges=edges, build_nsw=True,
                         node_attrs=attrs)
        elif kind == "insert":
            index.insert(entry[1], entry[2], entry[3])
        elif kind == "delete":
            index.delete(entry[1], entry[2])
        elif kind == "maintain":
            index.maintain()
        elif kind == "compact":
            index.compact(entry[1])
        else:
            raise ValueError(kind)
        done += 1
    return done


def total_logged(ops) -> int:
    return sum(e[0] not in ("search", "snapshot") for e in ops)


def golden_index(cfg, d: int):
    """Fresh in-memory index after the first ``d`` logged ops."""
    from repro.core.index import HMGIIndex
    idx = HMGIIndex(cfg, seed=0)
    apply_ops(idx, scripted_ops(), until=d)
    return idx


def assert_bit_identical(recovered, golden, label: str):
    q = queries()
    for mod in ("text", "image"):
        rs, ri = recovered.search(q, mod, k=8)
        gs, gi = golden.search(q, mod, k=8)
        if not (np.array_equal(np.asarray(ri), np.asarray(gi))
                and np.array_equal(np.asarray(rs), np.asarray(gs))):
            raise AssertionError(f"{label}: search({mod}) diverged:\n"
                                 f"  recovered ids {np.asarray(ri)[0]}\n"
                                 f"  golden    ids {np.asarray(gi)[0]}")
        rs, ri = recovered.hybrid_search(q, mod, k=8)
        gs, gi = golden.hybrid_search(q, mod, k=8)
        if not (np.array_equal(np.asarray(ri), np.asarray(gi))
                and np.array_equal(np.asarray(rs), np.asarray(gs))):
            raise AssertionError(f"{label}: hybrid_search({mod}) diverged")


# hits chosen so every point fires after meaningful state exists (e.g.
# wal.pre_rotate hit 1 is the constructor's first segment open; hit 2 is
# the first snapshot's rotation)
DEFAULT_HITS = {
    "wal.pre_append": 5,
    "wal.post_append": 5,
    "wal.pre_rotate": 2,
    "wal.pre_gc": 1,
    "wal.post_gc": 1,
    "snapshot.mid_write": 3,
    "snapshot.pre_rename": 1,
    "snapshot.post_rename": 1,
    "recover.mid_replay": 2,
}


def run_child(data_dir: str, *flags: str, env_point: str | None = None):
    env = dict(os.environ)
    env.pop("HMGI_FAULTPOINT", None)
    if env_point:
        env["HMGI_FAULTPOINT"] = env_point
    cmd = [sys.executable, os.path.abspath(__file__), *flags,
           "--data-dir", data_dir]
    return subprocess.run(cmd, env=env, capture_output=True, text=True)


def check_point(point: str, data_dir: str, hits: int | None = None) -> str:
    """One kill-and-recover cycle for ``point``. Returns a summary line;
    raises on any mismatch. Runs in the parent, which only starts
    children and reads their exit codes."""
    hits = DEFAULT_HITS[point] if hits is None else hits
    shutil.rmtree(data_dir, ignore_errors=True)
    if point.startswith("recover."):
        clean = run_child(data_dir, "--child")
        if clean.returncode != 0:
            raise AssertionError(f"clean child failed:\n{clean.stderr[-2000:]}")
        crashed = run_child(data_dir, "--child", "--recover-only",
                            env_point=f"{point}:{hits}")
    else:
        crashed = run_child(data_dir, "--child", env_point=f"{point}:{hits}")
    if crashed.returncode != 137:
        raise AssertionError(
            f"{point}: child exited {crashed.returncode}, expected 137 "
            f"(fault never fired?)\n{crashed.stderr[-2000:]}")
    verified = run_child(data_dir, "--verify", "--point", point)
    if verified.returncode != 0:
        raise AssertionError(f"{point}: {verified.stderr[-2000:]}")
    summary = verified.stdout.strip().splitlines()[-1]
    return f"{point}: killed at hit {hits}, {summary}"


def verify_recovery(point: str, data_dir: str) -> str:
    """The ``--verify`` child: recover ``data_dir`` and hold it
    bit-identical to the golden index at the recovered op count."""
    from repro.persistence import recover
    cfg = make_cfg()
    idx = recover(cfg, data_dir, seed=0)
    d = idx.last_seq
    idx.close()
    # recover() is also what a restarted server runs — compare a *fresh*
    # recovery (the one above validated re-runnability after the crash)
    idx = recover(cfg, data_dir, seed=0)
    golden = golden_index(cfg, d)
    assert_bit_identical(idx, golden, point)
    trail = idx.metrics().get("recovery", "")
    idx.close()
    return f"recovered {d} ops — OK [{trail}]"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--recover-only", action="store_true")
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--data-dir", default="/tmp/hmgi_crash_harness")
    ap.add_argument("--point")
    ap.add_argument("--hits", type=int, default=None)
    ap.add_argument("--sweep", action="store_true")
    args = ap.parse_args()

    if args.child:
        from repro.persistence import DurableHMGIIndex, recover
        cfg = make_cfg()
        if args.recover_only:
            idx = recover(cfg, args.data_dir, seed=0)
        else:
            idx = DurableHMGIIndex(cfg, args.data_dir, seed=0)
            apply_ops(idx, scripted_ops())
        idx.close()
        return
    if args.verify:
        try:
            print(verify_recovery(args.point, args.data_dir))
        except AssertionError as e:
            sys.exit(str(e))
        return

    from repro.persistence.faultpoints import POINTS
    points = list(POINTS) if args.sweep else [args.point]
    if not points[0]:
        ap.error("--point or --sweep required")
    failures = []
    for p in points:
        try:
            print(check_point(p, args.data_dir, args.hits), flush=True)
        except AssertionError as e:
            failures.append(p)
            print(f"FAIL {p}: {e}", flush=True)
    if failures:
        sys.exit(f"{len(failures)} crash point(s) failed: {failures}")
    print(f"all {len(points)} crash point(s): clean recovery, "
          "bit-identical results")


if __name__ == "__main__":
    main()
