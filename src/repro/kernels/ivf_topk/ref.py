"""Pure-jnp oracle for the ivf_topk kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ivf_topk.ivf_topk import NEG, block_rows


def _chunk_reduce_ref(scores, chunk: int, block_n: int | None = None,
                      fill: float = -jnp.inf):
    """(Q, N) scores -> per-chunk (max, argmax) in the kernel's strided
    chunk layout (see ivf_topk.py) at row block ``block_n`` (default: the
    wrappers' ``block_rows``): chunk c of block b holds the rows
    b·block_n + j·width + c. Rows past N (block padding) read ``fill``."""
    qn, n = scores.shape
    bn = block_rows(n, chunk, block_n)
    pad = (-n) % bn
    sc = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=fill)
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


def scan_probed_ref(queries, data_i8, vmin, scale, valid, probes, *,
                    chunk: int, block_n: int):
    """Oracle of the partition-major probe scan's survivors: dequantize
    the (K, cap, d) slab, exact scores, and each (query, probe) pair's
    chunk maxima over its partition, probe-major: (Q, P·nb·width). Invalid
    rows and rows past cap read ``NEG``, as in the kernel."""
    q = queries.astype(jnp.float32)
    e = ((data_i8.astype(jnp.float32) + 128.0) * scale[..., None]
         + vmin[..., None])                                  # (K, cap, d)
    s = jnp.einsum("qd,kcd->qkc", q, e, precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(valid[None], s, NEG)
    s = jnp.take_along_axis(s, probes[:, :, None], axis=1)   # (Q, P, cap)
    qn, n_probe, cap = s.shape
    smax, _ = _chunk_reduce_ref(s.reshape(qn * n_probe, cap), chunk,
                                block_n=block_n, fill=NEG)
    return smax.reshape(qn, -1)


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
    kk = min(k, chunk_max.shape[-1])
    vals, pos = jax.lax.top_k(chunk_max, kk)
    ids = jnp.take_along_axis(chunk_arg, pos, axis=-1)
    return pad_topk(vals, ids, k)
