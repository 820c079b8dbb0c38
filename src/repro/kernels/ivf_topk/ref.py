"""Pure-jnp oracle for the ivf_topk kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ivf_topk.ivf_topk import block_rows


def _chunk_reduce_ref(scores, chunk: int):
    """(Q, N) scores -> per-chunk (max, argmax) in the kernel's strided
    chunk layout (see ivf_topk.py) at the wrappers' default row block:
    chunk c of block b holds the rows b·block_n + j·width + c. Rows past N
    (block padding) read -inf."""
    qn, n = scores.shape
    bn = block_rows(n, chunk)
    pad = (-n) % bn
    sc = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    nb, w = (n + pad) // bn, bn // chunk
    sc = sc.reshape(qn, nb, chunk, w)
    smax = jnp.max(sc, axis=2).reshape(qn, nb * w)
    j = jnp.argmax(sc, axis=2).astype(jnp.int32)                 # (Q, nb, w)
    start = (jnp.arange(nb, dtype=jnp.int32)[:, None] * bn
             + jnp.arange(w, dtype=jnp.int32)[None, :])
    return smax, (start[None] + j * w).reshape(qn, nb * w)


def scan_topk_ref(queries, data_i8, vmin, scale, *, chunk: int = 8):
    """Dequantize fully, exact scores, per-chunk (max, argmax)."""
    q = queries.astype(jnp.float32)
    e = (data_i8.astype(jnp.float32) + 128.0) * scale[:, None] + vmin[:, None]
    return _chunk_reduce_ref(q @ e.T, chunk)                   # (Q, N)


def scan_topk_ref_batched(queries, data_i8, vmin, scale, *, chunk: int = 16):
    """Per-query-slab oracle: dequantize fully, exact scores, per-chunk
    (max, argmax). queries (Q, d); data_i8 (Q, M, d); vmin/scale (Q, M)."""
    q = queries.astype(jnp.float32)
    e = ((data_i8.astype(jnp.float32) + 128.0) * scale[..., None]
         + vmin[..., None])                                  # (Q, M, d)
    return _chunk_reduce_ref(jnp.einsum("qd,qmd->qm", q, e), chunk)


def pad_topk(vals, ids, k: int):
    """Pads (Q, kk ≤ k) descending top-k lists to width k with (-inf, -1) —
    the one sentinel convention every scan/merge path shares."""
    kk = vals.shape[-1]
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - kk)), constant_values=-1)
    return vals, ids


def topk_from_chunks(chunk_max, chunk_arg, k: int):
    """Exact top-k over the chunk survivors (second stage, tiny).

    Clamps k to the available chunk count and pads (-inf, -1)."""
    import jax
    kk = min(k, chunk_max.shape[-1])
    vals, pos = jax.lax.top_k(chunk_max, kk)
    ids = jnp.take_along_axis(chunk_arg, pos, axis=-1)
    return pad_topk(vals, ids, k)
