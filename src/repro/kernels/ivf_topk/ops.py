"""Public jit'd wrappers for the fused quantized scan.

On a TPU the pallas_call compiles to Mosaic. On any other backend (the CPU
test lanes) the kernel body runs under ``interpret=True`` — the backend is
probed once, lazily on the first kernel call (``_interpret_mode``), so jit
caches never mix modes and app-level JAX setup still runs first. The
wrappers pad N (or M) to the block size and return exact top-k ids/scores.

Exactness: the kernel emits per-chunk (max, argmax) survivors. For the probe
path, ``scan_topk_quantized_batched`` then *rescores every row of the top-k
chunks*: any true top-k row lives in a chunk whose max is ≥ the k-th best
score, and at most k chunks can have such a max, so the k·chunk rescored rows
provably contain the exact (quantized-score) top-k. The rescore touches only
k·chunk rows per query — tiny next to the scan. The rescore matmul runs at
``Precision.HIGHEST``: XLA:TPU would otherwise take the fp32 einsum at bf16
precision, and the result would no longer be the exact quantized score.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ivf_topk.ivf_topk import (block_rows, chunk_rows,
                                             scan_topk_pallas,
                                             scan_topk_pallas_batched)
from repro.kernels.ivf_topk.ref import pad_topk, topk_from_chunks


@functools.lru_cache(maxsize=None)
def _interpret_mode() -> bool:
    """Probed once, lazily (first kernel call): Mosaic needs a TPU; every
    other backend interprets. Deferred past import so app-level JAX setup
    (jax.distributed.initialize, platform selection) runs first."""
    return jax.default_backend() != "tpu"


NEG = jnp.float32(-3e38)   # additive mask bias (sign-safe, unlike -inf)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "block_n", "interpret"))
def scan_topk_quantized(queries, data_i8, vmin, scale, valid, *, k: int,
                        chunk: int = 8, block_n: int | None = None,
                        interpret: bool | None = None):
    """Top-k over a quantized corpus slab shared by all queries.

    queries (Q, d) fp32; data_i8 (N, d) int8; vmin/scale (N,); valid (N,) bool.
    Returns (scores (Q, k), row_ids (Q, k)) — descending, -inf/-1 padded.
    block_n defaults to ``ivf_topk.block_rows`` (one survivor lane tile).
    """
    interp = _interpret_mode() if interpret is None else interpret
    n, d = data_i8.shape
    block_n = block_rows(n, chunk, block_n)
    pad = (-n) % block_n
    if pad:
        data_i8 = jnp.pad(data_i8, ((0, pad), (0, 0)))
        vmin = jnp.pad(vmin, (0, pad))
        scale = jnp.pad(scale, (0, pad), constant_values=1.0)
        valid = jnp.pad(valid, (0, pad))
    # invalid rows get a -3e38 additive bias inside the kernel (sign-safe)
    bias = jnp.where(valid, 0.0, NEG)
    cmax, carg = scan_topk_pallas(queries, data_i8, vmin, scale, bias,
                                  chunk=chunk, block_n=block_n, interpret=interp)
    vals, ids = topk_from_chunks(cmax, carg, min(k, cmax.shape[1]))
    dead = vals <= NEG * 0.5
    vals = jnp.where(dead, -jnp.inf, vals)
    ids = jnp.where(dead, -1, ids)
    return pad_topk(vals, ids, k)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "block_n", "interpret"))
def scan_topk_quantized_batched(queries, data_i8, vmin, scale, valid, *,
                                k: int, chunk: int = 16,
                                block_n: int | None = None,
                                interpret: bool | None = None):
    """Exact top-k over per-query quantized slabs (the IVF probe path).

    queries (Q, d) fp32; data_i8 (Q, M, d) int8 — each query's gathered probe
    rows; vmin/scale (Q, M) fp32; valid (Q, M) bool. Returns
    (scores (Q, k), rows (Q, k)) — descending; ``rows`` index each query's own
    slab axis M; -inf/-1 padded. Exact over the quantized scores (see module
    docstring for the top-k-chunks containment argument).
    """
    interp = _interpret_mode() if interpret is None else interpret
    qn, m, d = data_i8.shape
    block_n = block_rows(m, chunk, block_n)
    pad = (-m) % block_n
    if pad:
        data_i8 = jnp.pad(data_i8, ((0, 0), (0, pad), (0, 0)))
        vmin = jnp.pad(vmin, ((0, 0), (0, pad)))
        scale = jnp.pad(scale, ((0, 0), (0, pad)), constant_values=1.0)
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    bias = jnp.where(valid, 0.0, NEG)
    cmax, _ = scan_topk_pallas_batched(queries, data_i8, vmin, scale, bias,
                                       chunk=chunk, block_n=block_n,
                                       interpret=interp)
    # stage 2: gather every row of the top-k chunks and rescore exactly —
    # ≤ k chunks can hold a true top-k row, so this set contains all of them.
    nchunks = cmax.shape[1]
    kc = min(k, nchunks)
    _, cpos = jax.lax.top_k(cmax, kc)                                 # (Q, kc)
    rows = chunk_rows(cpos, chunk, block_n)                           # (Q, R)
    dsel = jnp.take_along_axis(data_i8, rows[:, :, None], axis=1)     # (Q,R,d)
    vsel = jnp.take_along_axis(vmin, rows, axis=1)
    ssel = jnp.take_along_axis(scale, rows, axis=1)
    bsel = jnp.take_along_axis(bias, rows, axis=1)
    q32 = queries.astype(jnp.float32)
    qsum = jnp.sum(q32, axis=-1, keepdims=True)
    dots = jnp.einsum("qd,qrd->qr", q32, dsel.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    scores = dots * ssel + qsum * (128.0 * ssel + vsel) + bsel
    kk = min(k, scores.shape[1])
    vals, pos = jax.lax.top_k(scores, kk)
    out_rows = jnp.take_along_axis(rows, pos, axis=1)
    dead = vals <= NEG * 0.5
    vals = jnp.where(dead, -jnp.inf, vals)
    out_rows = jnp.where(dead, -1, out_rows)
    return pad_topk(vals, out_rows, k)
