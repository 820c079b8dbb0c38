"""Public jit'd wrappers for the fused quantized scan.

On a TPU the pallas_call compiles to Mosaic. On any other backend (the CPU
test lanes) the kernel body runs under ``interpret=True`` — the backend is
probed once, lazily on the first kernel call (``_interpret_mode``), so jit
caches never mix modes and app-level JAX setup still runs first. The
wrappers pad N (or M) to the block size and return exact top-k ids/scores.

Exactness: the kernel emits per-chunk (max, argmax) survivors. For the probe
path, ``scan_topk_probed`` then *rescores every row of the top-k chunks* of
each query: any true top-k row lives in a chunk whose max is ≥ the k-th best
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

from repro.common.shapes import pad_to_chunk, pow2_round
from repro.kernels.ivf_topk.ivf_topk import (LANES, NEG, _terms, block_rows,
                                             chunk_rows, scan_topk_pallas,
                                             scan_topk_pallas_grouped)
from repro.kernels.ivf_topk.ref import pad_topk, topk_from_chunks


@functools.lru_cache(maxsize=None)
def _interpret_mode() -> bool:
    """Probed once, lazily (first kernel call): Mosaic needs a TPU; every
    other backend interprets. Deferred past import so app-level JAX setup
    (jax.distributed.initialize, platform selection) runs first."""
    return jax.default_backend() != "tpu"


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


def group_rows(n_pairs: int, n_parts: int) -> int:
    """Query rows per group of the partition-major scan: the mean number
    of (query, probe) pairs per partition, as a power of two in [8, 128]
    (8: the fp32 sublane tile). The group grows with the sharing the
    batch holds, so one algorithm serves single queries and batches."""
    return pow2_round(-(-n_pairs // n_parts), lo=8, hi=128)


def probe_groups(probes, n_parts: int, g: int):
    """Sorts the (query, probe) pairs by partition and cuts each
    partition's pairs into groups of ``g`` rows.

    probes (Q, P) int32. Returns (group_part (n_groups,) the partition of
    each group, n_live (1,) the groups that hold a pair (they come first),
    row_pair (n_groups·g,) the pair in each group row or -1, pair_row
    (Q·P,) the group row of each pair). n_groups = ceil(Q·P/g) +
    min(K, Q·P) bounds the groups any probe list needs, so it is static."""
    part = probes.reshape(-1)
    n_pairs = part.shape[0]
    n_groups = -(-n_pairs // g) + min(n_parts, n_pairs)
    order = jnp.argsort(part, stable=True)            # sorted -> pair
    sp = part[order]
    count = jnp.bincount(part, length=n_parts)
    first = jnp.cumsum(count) - count                 # first sorted pair
    n_grp = (count + g - 1) // g
    gfirst = jnp.cumsum(n_grp) - n_grp                # first group
    rank = jnp.arange(n_pairs, dtype=jnp.int32) - first[sp]
    row = (gfirst[sp] + rank // g) * g + rank % g     # group row, sorted
    group_part = jnp.zeros((n_groups,), jnp.int32).at[row // g].set(sp)
    row_pair = jnp.full((n_groups * g,), -1, jnp.int32).at[row].set(order)
    pair_row = jnp.zeros((n_pairs,), jnp.int32).at[order].set(row)
    n_live = jnp.sum(n_grp, dtype=jnp.int32)[None]
    return group_part, n_live, row_pair, pair_row


def probe_block_rows(cap: int, chunk: int, interpret: bool) -> int:
    """Row block of the partition-major scan. Mosaic tiles the terms' row
    axis by lanes (128), so blocks are whole lane tiles, at most one
    survivor tile of rows (``LANES·chunk``); the interpreter pays per grid
    step, so it takes each partition as one block."""
    if interpret:
        return pad_to_chunk(cap, chunk)
    return min(LANES * chunk, pad_to_chunk(cap, LANES))


def probe_chunk_max(queries, data_i8, vmin, scale, valid, probes, *,
                    chunk: int, block_n: int, interpret: bool):
    """Stage 1 of ``scan_topk_probed``: each (query, probe) pair's chunk
    maxima over its partition, probe-major, (Q, P·nb·width). The pairs
    are scanned partition-major (``probe_groups``) by
    ``scan_topk_pallas_grouped`` over the slab in place, then put back in
    pair order."""
    qn, n_probe = probes.shape
    n_parts = data_i8.shape[0]
    g = group_rows(qn * n_probe, n_parts)
    group_part, n_live, row_pair, pair_row = probe_groups(probes, n_parts, g)
    q32 = queries.astype(jnp.float32)
    qsum = jnp.sum(q32, axis=-1, keepdims=True)
    rq = jnp.maximum(row_pair, 0) // n_probe
    live = (row_pair >= 0)[:, None]
    qg = jnp.where(live, q32[rq], 0.0).reshape(-1, g, q32.shape[1])
    sg = jnp.where(live, qsum[rq], 0.0).reshape(-1, g, 1)
    bias = jnp.where(valid, 0.0, NEG)
    cmax = scan_topk_pallas_grouped(
        group_part, n_live, qg, sg, data_i8, _terms(vmin, scale, bias),
        chunk=chunk, block_n=block_n, interpret=interpret)
    return cmax.reshape(-1, cmax.shape[2])[pair_row].reshape(qn, -1)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "block_n", "interpret"))
def scan_topk_probed(queries, data_i8, vmin, scale, valid, probes, *,
                     k: int, chunk: int = 16, block_n: int | None = None,
                     interpret: bool | None = None):
    """Exact top-k of each query over its probed partitions (the IVF probe
    path), reading the index slab in place.

    queries (Q, d) fp32; data_i8 (K, cap, d) int8; vmin/scale/valid
    (K, cap); probes (Q, P) int32 partitions; block_n a multiple of
    ``chunk`` (default ``probe_block_rows``). Returns (scores (Q, k),
    part (Q, k), slot (Q, k)): descending, (-inf, -1, -1) padded. Exact
    over the quantized scores (module docstring): stage 1
    (``probe_chunk_max``) scans partition-major; stage 2 rescores every
    row of each query's top-k chunks, read from the slab by (partition,
    slot).
    """
    interp = _interpret_mode() if interpret is None else interpret
    n_probe = probes.shape[1]
    cap = data_i8.shape[1]
    if block_n is None:
        block_n = probe_block_rows(cap, chunk, interp)
    cmax = probe_chunk_max(queries, data_i8, vmin, scale, valid, probes,
                           chunk=chunk, block_n=block_n, interpret=interp)
    per_probe = cmax.shape[1] // n_probe
    # stage 2: every row of each query's top-k chunks, rescored exactly —
    # ≤ k chunks can hold a true top-k row, so this set contains all of them.
    kc = min(k, cmax.shape[1])
    _, cpos = jax.lax.top_k(cmax, kc)                                 # (Q, kc)
    slot = chunk_rows(cpos % per_probe, chunk, block_n)               # (Q, R)
    part = jnp.repeat(jnp.take_along_axis(probes, cpos // per_probe, axis=1),
                      chunk, axis=1)
    s0 = jnp.minimum(slot, cap - 1)
    dsel = data_i8[part, s0]                                          # (Q,R,d)
    vsel = vmin[part, s0]
    ssel = scale[part, s0]
    ok = jnp.logical_and(slot < cap, valid[part, s0])
    q32 = queries.astype(jnp.float32)
    qsum = jnp.sum(q32, axis=-1, keepdims=True)
    dots = jnp.einsum("qd,qrd->qr", q32, dsel.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    scores = (dots * ssel + qsum * (128.0 * ssel + vsel)
              + jnp.where(ok, 0.0, NEG))
    vals, pos = jax.lax.top_k(scores, min(k, scores.shape[1]))
    dead = vals <= NEG * 0.5
    vals = jnp.where(dead, -jnp.inf, vals)
    part = jnp.where(dead, -1, jnp.take_along_axis(part, pos, axis=1))
    slot = jnp.where(dead, -1, jnp.take_along_axis(s0, pos, axis=1))
    _, slot = pad_topk(vals, slot, k)
    vals, part = pad_topk(vals, part, k)
    return vals, part, slot
