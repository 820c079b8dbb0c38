"""Fused quantized-scan + partial-top-k Pallas kernel (the IVF hot loop).

Computes, for a query block against a quantized corpus slab:

    score[q, n] = scale[n] · (Q[q] · D_int8[n]) + (128·scale[n] + vmin[n]) · Σ_d Q[q,d]

(the affine-dequant identity — int8 rows never materialise as fp32 in HBM),
then reduces each *chunk* of rows to its (max, argmax). The final exact
top-k over the N/chunk survivors happens outside in jnp — survivors are
tiny. This is the TPU-native ANN layout (partial-reduce scan; cf.
"TPU-KNN at Peak FLOP/s"): the FLOPs are MXU matmuls, HBM traffic is int8,
and no sort runs inside the kernel.

Chunk layout (strided). A row block of ``block_n`` rows holds
``width = block_n // chunk`` chunks; chunk ``c`` of block ``b`` is the rows
``b·block_n + j·width + c`` for ``j < chunk``. The kernel scores the block
as ``chunk`` lane-aligned (rows, width) slabs and keeps an elementwise
running (max, argmax) over them, so the survivor outputs are lane-dense
(…, width) tiles and no in-kernel reshape or lane reduction is needed.
``ref.py`` implements the same layout; ``chunk_rows`` maps survivor
positions back to rows.

TPU tiling (Mosaic): the last two dims of every block must be multiples of
(8, 128) — (32, 128) for the int8 data — or equal the array's dims. So
``width`` is a multiple of 128 whenever a scan spans more than one block;
``block_rows`` picks such blocks. VMEM per grid step: the int8 data block
(double-buffered, 2·block_n·d bytes), one fp32 (width, d) slice at a time,
and (rows, width) fp32 scores — 2048 rows × d=384 is ~1.8 MB, inside the
default scoped VMEM at every supported width.

Two entry points share the kernel math:

  scan_topk_pallas         — one corpus slab shared by every query (the
                             delta-store scan, monolithic baselines). Grid
                             (query blocks, row blocks).
  scan_topk_pallas_batched — per-query slabs (Q, M, d): the IVF probe path,
                             where each query gathered its own probed
                             partitions as contiguous row blocks of the
                             flattened (K·cap, d) index slab. Grid
                             (queries, row blocks), one query per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128                        # TPU vreg lane width
_BLOCK_Q = 256                     # query rows per block of the flat kernel
_HIGHEST = jax.lax.Precision.HIGHEST


def block_rows(n: int, chunk: int, block_n: int | None = None) -> int:
    """Row-block size for an n-row scan: ``block_n`` (default
    ``LANES·chunk``, the smallest TPU-legal multi-block size), or a single
    block of n rounded up to the chunk when the scan fits in one."""
    block_n = LANES * chunk if block_n is None else int(block_n)
    assert block_n % chunk == 0, (block_n, chunk)
    return min(block_n, -(-n // chunk) * chunk)


def chunk_rows(cpos, chunk: int, block_n: int):
    """Rows of the strided chunks ``cpos`` (…, kc) -> (…, kc·chunk)."""
    width = block_n // chunk
    start = (cpos // width) * block_n + cpos % width
    rows = start[..., None] + jnp.arange(chunk, dtype=jnp.int32) * width
    return rows.reshape(*cpos.shape[:-1], -1)


def _scan_block(q, qsum, d_ref, t_ref, chunk: int, width: int, base):
    """(rows, width) running (max, argmax) over the block's ``chunk``
    strided slabs. q (rows, d) fp32; qsum (rows, 1); t_ref (3, block_n)
    fp32 = (affine, scale, bias) per row; base = the block's first row."""
    best = arg = None
    for j in range(chunk):
        lo, hi = j * width, (j + 1) * width
        d = d_ref[lo:hi, :].astype(jnp.float32)                        # (W, d)
        dots = jax.lax.dot_general(q, d, (((1,), (1,)), ((), ())),
                                   precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
        aff, scale, bias = (t_ref[i:i + 1, lo:hi] for i in range(3))
        s = dots * scale + qsum * aff + bias                           # (r, W)
        if best is None:
            best, arg = s, jnp.zeros(s.shape, jnp.int32)
        else:
            upd = s > best                 # strict: ties keep the lower row
            best = jnp.where(upd, s, best)
            arg = jnp.where(upd, j, arg)
    lane = jax.lax.broadcasted_iota(jnp.int32, best.shape, 1)
    return best, base + arg * width + lane


def _kernel(q_ref, qsum_ref, d_ref, t_ref, smax_ref, sarg_ref, *,
            chunk: int, block_n: int):
    # q_ref (bq, d) fp32 · qsum_ref (bq, 1) · d_ref (bn, d) int8 ·
    # t_ref (3, bn) fp32 (affine, scale, bias) · outputs (bq, bn/chunk).
    # The batched variant runs it with bq = 1 on that query's own rows.
    base = pl.program_id(1) * block_n
    smax, sarg = _scan_block(q_ref[...], qsum_ref[...], d_ref, t_ref,
                             chunk, block_n // chunk, base)
    smax_ref[...] = smax
    sarg_ref[...] = sarg


def _terms(vmin, scale, bias):
    """Stacks the per-row dequant terms (affine, scale, bias) on axis -2."""
    bias = jnp.zeros_like(scale) if bias is None else bias
    return jnp.stack([128.0 * scale + vmin, scale,
                      bias.astype(jnp.float32)], axis=-2)


def scan_topk_pallas(queries, data_i8, vmin, scale, bias=None, *,
                     chunk: int = 8, block_n: int = 1024,
                     interpret: bool = False):
    """queries (Q, d) fp32; data_i8 (N, d) int8 (centered at -128);
    vmin/scale (N,) fp32; bias (N,) fp32 or None (0 live, -3e38 masked).
    N must be a multiple of block_n. Queries run in blocks of 256 (fewer
    run as one block), which bounds VMEM at the serving batch. Returns
    (chunk_max (Q, N/chunk), chunk_arg) in the strided chunk layout
    (module docstring)."""
    qn, d = queries.shape
    n = data_i8.shape[0]
    assert n % block_n == 0 and block_n % chunk == 0, (n, block_n, chunk)
    width = block_n // chunk
    bq = min(qn, _BLOCK_Q)
    qpad = (-qn) % bq
    q32 = jnp.pad(queries.astype(jnp.float32), ((0, qpad), (0, 0)))
    qsum = jnp.sum(q32, axis=-1, keepdims=True)                       # (Q, 1)
    out_shape = (qn + qpad, n // chunk)
    cmax, carg = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, block_n=block_n),
        grid=((qn + qpad) // bq, n // block_n),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),               # queries
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),               # qsum
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),          # data
            pl.BlockSpec((3, block_n), lambda i, j: (0, j)),          # terms
        ],
        out_specs=(
            pl.BlockSpec((bq, width), lambda i, j: (i, j)),
            pl.BlockSpec((bq, width), lambda i, j: (i, j)),
        ),
        out_shape=(jax.ShapeDtypeStruct(out_shape, jnp.float32),
                   jax.ShapeDtypeStruct(out_shape, jnp.int32)),
        interpret=interpret,
    )(q32, qsum, data_i8, _terms(vmin, scale, bias))
    return cmax[:qn], carg[:qn]


def scan_topk_pallas_batched(queries, data_i8, vmin, scale, bias=None, *,
                             chunk: int = 16, block_n: int = 2048,
                             interpret: bool = False):
    """Per-query-slab variant: queries (Q, d) fp32; data_i8 (Q, M, d) int8
    (centered at -128); vmin/scale/bias (Q, M) fp32; M a multiple of
    block_n. Returns (chunk_max (Q, M/chunk), chunk_arg) — chunk_arg
    indexes rows of each query's own slab, strided chunk layout."""
    qn, d = queries.shape
    m = data_i8.shape[1]
    assert m % block_n == 0 and block_n % chunk == 0, (m, block_n, chunk)
    width = block_n // chunk
    q32 = queries.astype(jnp.float32)
    qsum = jnp.sum(q32, axis=-1, keepdims=True)
    out_shape = (qn, 1, m // chunk)
    sq = pl.Squeezed()
    cmax, carg = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, block_n=block_n),
        grid=(qn, m // block_n),
        in_specs=[
            pl.BlockSpec((sq, 1, d), lambda i, j: (i, 0, 0)),         # query
            pl.BlockSpec((sq, 1, 1), lambda i, j: (i, 0, 0)),         # qsum
            pl.BlockSpec((sq, block_n, d), lambda i, j: (i, j, 0)),   # data
            pl.BlockSpec((sq, 3, block_n), lambda i, j: (i, 0, j)),   # terms
        ],
        out_specs=(
            pl.BlockSpec((sq, 1, width), lambda i, j: (i, 0, j)),
            pl.BlockSpec((sq, 1, width), lambda i, j: (i, 0, j)),
        ),
        out_shape=(jax.ShapeDtypeStruct(out_shape, jnp.float32),
                   jax.ShapeDtypeStruct(out_shape, jnp.int32)),
        interpret=interpret,
    )(q32[:, None, :], qsum[:, None, :], data_i8, _terms(vmin, scale, bias))
    return cmax[:, 0], carg[:, 0]
