"""Runs ``bench/run.py`` with the timed path broken underneath, for the
tests that must see ``correct`` come out false.

    python bench/tests/faults.py <fault> <run.py arguments...>

Faults in the answers, each planted where the program produces them
(``repro.serving.retrieval.run_plan``, which both service entries call):

- ``precision``: every score rounded to bfloat16, as a scan or a
  traversal in lower precision would leave it;
- ``half``: only the first half of each batch is computed, the rest get
  the first query's answer;
- ``answer``: one id of every answer altered.

Faults in the index the answers are read from, planted in the build:

- ``quant4``: the stable rows quantized at 4 bits (16 levels), kept one
  value per int8 as the 8-bit scan reads them, each with its own affine
  terms: a lower-precision index that the scan still serves;
- ``partition``: the rows laid out by the centroids of the Lloyd step
  before the last, while the index keeps the last step's (a build that
  skips the final assignment);
- ``communities``: every node labelled one community, so the traversal's
  community boost covers every edge.
"""
import pathlib
import runpy
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ANSWER_FAULTS = ("precision", "half", "answer")
BUILD_FAULTS = ("quant4", "partition", "communities")


def _break_answers(fault: str) -> None:
    import numpy as np
    from bench.reference import bf16
    from repro.serving import retrieval

    clean = retrieval.run_plan

    def broken(index, plan, q):
        if fault == "half":
            keep = max((q.shape[0] + 1) // 2, 1)
            sv, si = clean(index, plan, q[:keep])
            fill = np.zeros(q.shape[0], int)
            fill[:keep] = np.arange(keep)
            return sv[fill], si[fill]
        sv, si = clean(index, plan, q)
        sv, si = np.array(sv), np.array(si)
        if fault == "precision":
            sv = np.where(np.isfinite(sv), bf16(sv), sv)
        else:
            si[:, 0] = si[:, 0] + 1
        return sv, si

    retrieval.run_plan = broken


def _break_build(fault: str) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core import community, ivf, partitioner
    from repro.core.quantization import QuantizedVectors

    if fault == "quant4":
        def quantize(e, bits=8):
            ef = e.astype(jnp.float32)
            vmin = jnp.min(ef, axis=-1, keepdims=True)
            scale = jnp.maximum(jnp.max(ef, axis=-1, keepdims=True) - vmin,
                                1e-12) / 15.0
            q = jnp.clip(jnp.floor((ef - vmin) / scale), 0, 15)
            return QuantizedVectors((q - 128).astype(jnp.int8), vmin, scale,
                                    8, e.shape[-1])
        ivf.quantize = quantize
    elif fault == "partition":
        clean_build = ivf.build

        def build(key, vectors, ids, *, n_partitions, kmeans_iters=16,
                  centroids=None, **kw):
            def fit(n):
                return partitioner.fit(key, vectors, n_partitions, n).centroids
            idx, overflow = clean_build(key, vectors, ids,
                                        n_partitions=n_partitions,
                                        kmeans_iters=kmeans_iters,
                                        centroids=fit(kmeans_iters - 1), **kw)
            return idx._replace(centroids=fit(kmeans_iters)), overflow
        ivf.build = build
    else:
        community.louvain_one_level = (
            lambda n_nodes, *a, **kw: np.zeros(n_nodes, np.int32))


def main() -> None:
    fault, args = sys.argv[1], sys.argv[2:]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    if fault in ANSWER_FAULTS:
        _break_answers(fault)
    elif fault in BUILD_FAULTS:
        _break_build(fault)
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    sys.argv = [str(ROOT / "bench" / "run.py")] + args
    runpy.run_path(str(ROOT / "bench" / "run.py"), run_name="__main__")


if __name__ == "__main__":
    main()
