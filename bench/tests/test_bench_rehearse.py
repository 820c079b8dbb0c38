"""End-to-end rehearsals of every cell at tiny sizes on the CPU, in
subprocesses run side by side: each prints the contract's last line with
``correct`` true; answers in lower precision, half a batch left out, an
altered answer, an index quantized at 4 bits, rows in the wrong
partition or a degenerate community labelling each make ``correct``
false, and so does the control (``--control``); a rate sweep of the
open-loop cell reports its rate; off the chip without ``--rehearse``, or
without the program's sources, the run exits nonzero and prints no
result."""
import concurrent.futures
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
RUN = ROOT / "bench" / "run.py"
FAULTS = ROOT / "bench" / "tests" / "faults.py"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
OPEN = [w["name"] for w in SPEC["workloads"] if json.loads(
    (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())[
        "loop"] == "open"]
CLOSED = [c for c in CELLS if c not in OPEN]
GRAPH = [w["name"] for w in SPEC["workloads"] if "community_modularity"
         in json.loads((ROOT / "bench" / "limits" / f"{w['name']}.json")
                       .read_text())]
FAULTS_OF = {c: ["precision", "half", "answer", "quant4", "partition"]
             + (["communities"] if c in GRAPH else []) for c in CELLS}
SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _args(cell, *extra):
    return ["--workload", cell, "--seed", str(SEED), "--seconds", "2",
            "--trace", "0", *extra]


def _scenarios():
    out = {}
    for cell in CELLS:
        out[("clean", cell)] = [sys.executable, str(RUN),
                                *_args(cell, "--rehearse")]
        for fault in FAULTS_OF[cell]:
            out[(fault, cell)] = [sys.executable, str(FAULTS), fault,
                                  *_args(cell, "--rehearse")]
        out[("control", cell)] = [sys.executable, str(RUN),
                                  *_args(cell, "--rehearse", "--control")]
    for cell in OPEN:
        out[("sweep", cell)] = [sys.executable, str(RUN),
                                *_args(cell, "--rehearse", "--sweep", "30")]
    for cell in CLOSED:
        out[("sweep", cell)] = [sys.executable, str(RUN),
                                *_args(cell, "--rehearse", "--sweep", "30")]
    out[("no_chip", CELLS[0])] = [sys.executable, str(RUN), *_args(CELLS[0])]
    return out


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    bare = tmp_path_factory.mktemp("bare")
    shutil.copy(ROOT / "BENCHMARK.json", bare)
    shutil.copytree(ROOT / "bench", bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    jobs = _scenarios()
    jobs[("bare", CELLS[0])] = [sys.executable, str(bare / "bench" / "run.py"),
                                *_args(CELLS[0], "--rehearse")]

    def one(cmd):
        p = subprocess.run(cmd, cwd=ROOT, env=ENV, capture_output=True,
                           text=True, timeout=600)
        return p.returncode, p.stdout, p.stderr

    with concurrent.futures.ThreadPoolExecutor(6) as pool:
        futs = {k: pool.submit(one, cmd) for k, cmd in jobs.items()}
        return {k: f.result() for k, f in futs.items()}


def _last_line(res):
    rc, out, err = res
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct(results, cell):
    line = _last_line(results[("clean", cell)])
    assert line["correct"] is True, line["checks"]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "checks"
    assert line["metrics"] == {}          # no device metrics off the chip
    assert line["attempted"] > 0 and line["failed"] == 0
    for c in line["checks"].values():
        if c.get("at_least"):
            assert c["value"] >= c["limit"]
        else:
            assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS
                                        for f in FAULTS_OF[c]])
def test_broken_timed_path_is_not_correct(results, cell, fault):
    line = _last_line(results[(fault, cell)])
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(results, cell):
    line = _last_line(results[("control", cell)])
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["score_gap"]["value"] > \
        line["checks"]["score_gap"]["limit"]


@pytest.mark.parametrize("cell", OPEN)
def test_sweep_reports_each_rate(results, cell):
    line = _last_line(results[("sweep", cell)])
    (row,) = line["sweep"]
    assert row["rate_qps"] == 30.0
    assert row["answered"] > 0 and row["throughput_qps"] > 0
    assert row["p50_ms"] <= row["p95_ms"] < 1e300


@pytest.mark.parametrize("cell", CLOSED)
def test_sweep_refused_in_a_closed_loop(results, cell):
    rc, out, _ = results[("sweep", cell)]
    assert rc != 0
    assert out.strip() == ""


@pytest.mark.parametrize("case", ["no_chip", "bare"])
def test_refuses_without_chip_or_sources(results, case):
    rc, out, _ = results[(case, CELLS[0])]
    assert rc != 0
    assert out.strip() == ""
