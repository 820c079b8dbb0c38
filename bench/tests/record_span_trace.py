"""Records the chip traces that ``test_bench_spans.py`` reads.

    python bench/tests/record_span_trace.py bench/tests/data

Run on a TPU. Writes ``spans_eager.xplane.pb`` and ``spans_jit.xplane.pb``:
the same device work under the program's own spans (``repro.obs``), with
the traversal's hops dispatched one eager operation at a time in the
first and under one ``jax.jit`` in the second. Each trace holds one
``bench.window`` annotation around two calls, each made on a worker
thread under a ``bench.search`` annotation, as the harness's open loop
makes them, with the main thread asleep ``GAP_S`` between them (no call
in flight). A call is, as the served path nests them:

- ``serving.window``: a host sleep of ``WINDOW_S``, the device idle;
- ``query.execute`` (argument ``call``) around ``query.seed_scan`` (the
  jitted ``seed``), ``query.traversal`` (``HOPS`` matrix products),
  ``query.fusion`` (the jitted ``fuse``) and ``query.to_host`` (the
  result read back through ``obs.to_host``).
"""
import glob
import pathlib
import shutil
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

from repro import obs  # noqa: E402

GAP_S = 0.05
WINDOW_S = 0.02
HOPS = 4
N = 8192


@jax.jit
def seed(x):
    return jnp.tanh(x)


def hops_eager(x, w):
    for _ in range(HOPS):
        x = x @ w
    return x


hops_jit = jax.jit(hops_eager)


@jax.jit
def fuse(x, y):
    return jax.lax.top_k(x[:256] + y[:256], 16)


def one_call(call: int, hops, x, w) -> None:
    with jax.profiler.TraceAnnotation("bench.search"):
        with obs.span("serving.window"):
            time.sleep(WINDOW_S)
        with obs.span("query.execute", call=call):
            with obs.span("query.seed_scan"):
                s = seed(x)
            with obs.span("query.traversal"):
                g = hops(s, w)
            with obs.span("query.fusion"):
                out = fuse(s, g)
            obs.to_host(out, "result")


def record(out: pathlib.Path, hops, x, w) -> None:
    one_call(-1, hops, x, w)                         # compile first
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(2):
            t = threading.Thread(target=one_call, args=(i, hops, x, w))
            t.start()
            t.join()
            if i == 0:
                time.sleep(GAP_S)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[-1], out)
    shutil.rmtree(tmp, ignore_errors=True)


def main(out_dir: str) -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("record_span_trace: run on a TPU")
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    x = jnp.full((N, N), 0.01, jnp.float32)
    w = jnp.eye(N, dtype=jnp.float32) * 0.5
    record(out / "spans_eager.xplane.pb", hops_eager, x, w)
    record(out / "spans_jit.xplane.pb", hops_jit, x, w)


if __name__ == "__main__":
    main(sys.argv[1])
