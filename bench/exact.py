"""Exact answers on the device, for the output check: the corpus
normalised, float32 brute-force top-k at HIGHEST precision, and the
gaps of the stored rows from Eq. 1 and 2. Each is one compiled shape: inputs go
in fixed blocks, the last one padded."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
ROW_BLOCK = 1 << 17


@jax.jit
def normalise(v):
    return v / jnp.linalg.norm(v, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_block(vn, q, node_pass, *, k):
    qn = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    s = jnp.matmul(qn, vn.T, precision=jax.lax.Precision.HIGHEST)
    if node_pass is not None:
        s = jnp.where(node_pass[None, :], s, -jnp.inf)
    return jax.lax.top_k(s, k)


def top_k(vn, q: np.ndarray, k: int, node_pass=None):
    """(scores, ids) float64/int64 of the exact top-k of each query over
    the normalised rows ``vn`` (row = id), rows failing ``node_pass`` out."""
    n = q.shape[0]
    qp = np.ones((-(-n // QUERY_BLOCK) * QUERY_BLOCK, q.shape[1]), np.float32)
    qp[:n] = q
    vals, ids = [], []
    for lo in range(0, qp.shape[0], QUERY_BLOCK):
        v, i = _top_k_block(vn, qp[lo:lo + QUERY_BLOCK], node_pass, k=k)
        vals.append(np.asarray(v, np.float64))
        ids.append(np.asarray(i, np.int64))
    return np.concatenate(vals)[:n], np.concatenate(ids)[:n]


@functools.partial(jax.jit, static_argnames=("levels",))
def _layout_block(vectors, ids, q8, vmin, scale, part, ok, centroids, *,
                  levels):
    v = vectors[ids]
    v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
    deq = (q8.astype(jnp.float32) + 128.0) * scale[:, None] + vmin[:, None]
    floor = jnp.abs((v - deq) / scale[:, None] - 0.5)
    lo, hi = jnp.min(v, axis=1), jnp.max(v, axis=1)
    span = jnp.maximum(hi - lo, 1e-12)
    affine = jnp.maximum(jnp.abs(vmin - lo), jnp.abs(scale * levels - span))
    cs = (jnp.matmul(v, centroids.T, precision=jax.lax.Precision.HIGHEST)
          - 0.5 * jnp.sum(centroids * centroids, axis=1)[None])
    mine = jnp.take_along_axis(cs, jnp.maximum(part, 0)[:, None], axis=1)
    near = jnp.where(part >= 0, jnp.max(cs, axis=1) - mine[:, 0], 0.0)
    return (jnp.max(jnp.where(ok[:, None], floor, 0.0)),
            jnp.max(jnp.where(ok, affine / span, 0.0)),
            jnp.max(jnp.where(ok, near, 0.0)))


def layout_gaps(vectors, ids, q8, vmin, scale, part, centroids,
                bits: int) -> tuple:
    """Three widest gaps over stored rows, v the normalised raw row of each
    stored id (Eq. 2 and Eq. 1):

    - ``floor``: |(v − deq)/scale − 1/2|, each int8 value the floor of its
      own row's affine map (at most 1/2);
    - ``affine``: the stored vmin and scale against min(v) and
      (max(v) − min(v))/(2^bits − 1), over max(v) − min(v);
    - ``partition``: how far the best centroid score (v·c − |c|²/2)
      lies above that of the row's partition (``part``; −1 for a row of
      the delta store, which is not checked).
    """
    out = np.zeros(3)
    levels = float((1 << bits) - 1)
    for lo in range(0, ids.size, ROW_BLOCK):
        sl = slice(lo, lo + ROW_BLOCK)
        m = ids[sl].size
        pad = ROW_BLOCK - m

        def fill(a, value=0):
            return np.concatenate([a, np.full((pad,) + a.shape[1:], value,
                                              a.dtype)])
        got = _layout_block(vectors, fill(ids[sl]), fill(q8[sl]),
                            fill(vmin[sl]), fill(scale[sl], 1),
                            fill(part[sl], -1), np.arange(ROW_BLOCK) < m,
                            centroids, levels=levels)
        out = np.maximum(out, [float(g) for g in got])
    return tuple(float(g) for g in out)
