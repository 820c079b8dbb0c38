"""Records the small chip trace that ``test_bench_trace.py`` reads.

    python bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Run on a TPU. Inside one ``bench.window`` annotation it makes two calls,
each under a ``bench.search_many`` annotation, to a jitted function named
``search`` (XLA module ``jit_search``) followed by one named ``merge``,
and sleeps ``GAP_S`` on the host between the calls with nothing in flight.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

GAP_S = 0.05


@jax.jit
def search(x, y):
    return jax.lax.top_k(x @ y, 16)


@jax.jit
def merge(v, i):
    return jnp.sort(v, axis=1), jnp.sort(i, axis=1)


def main(out: str) -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("record_trace: run on a TPU")
    x = jnp.ones((256, 384), jnp.float32)
    y = jnp.ones((384, 8192), jnp.float32)
    jax.block_until_ready(merge(*search(x, y)))          # compile first
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(2):
            with jax.profiler.TraceAnnotation("bench.search_many"):
                jax.block_until_ready(merge(*search(x, y)))
            if i == 0:
                time.sleep(GAP_S)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[-1], out)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
