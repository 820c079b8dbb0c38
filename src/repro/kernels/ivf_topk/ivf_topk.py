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
  scan_topk_pallas_grouped — the IVF probe path, partition-major: the
                             (K, cap, d) index slab is read in place, and
                             each grid step scores one partition's row
                             block against a group of the queries that
                             probe it (one (G, d)·(d, width) matmul per
                             strided slab). The partition of each group is
                             a scalar prefetch that the data ``index_map``
                             reads, so no copy of the probed partitions is
                             ever made. Grid (row blocks, groups), groups
                             innermost and sorted by partition: consecutive
                             groups of one partition map to the same data
                             block, which is then fetched from HBM once per
                             call, not once per query. ``cap`` need not be
                             a multiple of the row block: rows past it in
                             the partial last block are masked in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128                        # TPU vreg lane width
NEG = -3e38                        # mask score (sign-safe, unlike -inf)
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


def _scan_block(q, qsum, d_ref, t_ref, chunk: int, width: int, base,
                n_rows: int | None = None):
    """(rows, width) running (max, argmax) over the block's ``chunk``
    strided slabs. q (rows, d) fp32; qsum (rows, 1); t_ref (3, block_n)
    fp32 = (affine, scale, bias) per row; base = the block's first row.
    Rows at or past ``n_rows`` (a partial last block: its data and terms
    there are undefined) score ``NEG``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], width), 1)
    best = arg = None
    for j in range(chunk):
        lo, hi = j * width, (j + 1) * width
        d = d_ref[lo:hi, :].astype(jnp.float32)                        # (W, d)
        dots = jax.lax.dot_general(q, d, (((1,), (1,)), ((), ())),
                                   precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
        aff, scale, bias = (t_ref[i:i + 1, lo:hi] for i in range(3))
        s = dots * scale + qsum * aff + bias                           # (r, W)
        if n_rows is not None:
            s = jnp.where(base + lo + lane < n_rows, s, NEG)
        if best is None:
            best, arg = s, jnp.zeros(s.shape, jnp.int32)
        else:
            upd = s > best                 # strict: ties keep the lower row
            best = jnp.where(upd, s, best)
            arg = jnp.where(upd, j, arg)
    return best, base + arg * width + lane


def _kernel(q_ref, qsum_ref, d_ref, t_ref, smax_ref, sarg_ref, *,
            chunk: int, block_n: int):
    # q_ref (bq, d) fp32 · qsum_ref (bq, 1) · d_ref (bn, d) int8 ·
    # t_ref (3, bn) fp32 (affine, scale, bias) · outputs (bq, bn/chunk).
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


def _grouped_kernel(gp_ref, nl_ref, q_ref, qsum_ref, d_ref, t_ref, smax_ref,
                    *, chunk: int, block_n: int, cap: int):
    # gp_ref (n_groups,) partition of each group · nl_ref (1,) live groups
    # (scalar prefetch) · q_ref (G, d) fp32 · qsum_ref (G, 1) · d_ref
    # (bn, d) int8 of the group's partition · t_ref (3, bn) · smax_ref
    # (G, bn/chunk). Groups past the live ones hold no query: skipped.
    base = pl.program_id(0) * block_n

    @pl.when(pl.program_id(1) < nl_ref[0])
    def _():
        smax, _ = _scan_block(q_ref[...], qsum_ref[...], d_ref, t_ref,
                              chunk, block_n // chunk, base, n_rows=cap)
        smax_ref[...] = smax


def scan_topk_pallas_grouped(group_part, n_live, queries, qsum, data_i8,
                             terms, *, chunk: int = 16, block_n: int = 2048,
                             interpret: bool = False):
    """Partition-major probe scan over the index slab, read in place.

    group_part (n_groups,) int32: the partition each query group scans,
    sorted; n_live (1,) int32: groups that hold queries (the rest trail
    and are skipped); queries (n_groups, G, d) fp32, zero rows for
    padding; qsum (n_groups, G, 1) their row sums; data_i8 (K, cap, d)
    int8 (centered at -128); terms (K, 3, cap) fp32 per-row (affine,
    scale, bias) as ``_terms`` stacks them. Returns chunk_max
    (n_groups, G, ceil(cap/block_n)·width) in the strided chunk layout of
    each partition (module docstring); rows of trailing groups are
    undefined."""
    n_groups, g, d = queries.shape
    cap = data_i8.shape[1]
    assert block_n % chunk == 0, (block_n, chunk)
    width = block_n // chunk
    nb = -(-cap // block_n)
    sq = pl.Squeezed()

    def live(i, nl):
        # trailing groups revisit the last live group's blocks: no DMA
        return jnp.minimum(i, nl[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, n_groups),
        in_specs=[
            pl.BlockSpec((sq, g, d), lambda j, i, gp, nl: (live(i, nl), 0, 0)),
            pl.BlockSpec((sq, g, 1), lambda j, i, gp, nl: (live(i, nl), 0, 0)),
            pl.BlockSpec((sq, block_n, d),
                         lambda j, i, gp, nl: (gp[live(i, nl)], j, 0)),
            pl.BlockSpec((sq, 3, block_n),
                         lambda j, i, gp, nl: (gp[live(i, nl)], 0, j)),
        ],
        out_specs=pl.BlockSpec((sq, g, width),
                               lambda j, i, gp, nl: (live(i, nl), 0, j)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, chunk=chunk, block_n=block_n,
                          cap=cap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, g, nb * width),
                                       jnp.float32),
        interpret=interpret,
    )(group_part, n_live, queries, qsum, data_i8, terms)
