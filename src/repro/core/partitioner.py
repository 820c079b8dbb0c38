"""Modality-aware K-means partitioning (paper Eq. 1) + workload-aware repartitioning.

``Cluster Assignment = argmin_c ||e - mu_c||^2``  — fitted per modality, so
each modality gets its own centroid set and per-partition index
(docs/DESIGN.md C2). On TPU the assignment is a single matmul:
argmin_c ||e-mu||² = argmax_c (e·mu - ||mu||²/2), which is how both ``fit``
and ``assign`` are written here.

Parked partitions (docs/DESIGN.md §3.4): a merged-away partition keeps its
slot in the fixed-shape (K, ...) layout but its centroid is replaced with the
``parked_centroid`` sentinel — a vector whose norm is so large that the
assignment score ``e·mu - ||mu||²/2`` is astronomically negative, so neither
``assign`` nor ``assign_topk`` ever routes a vector or a probe there ahead of
a live partition. Parking frees a partition for a later split without
changing any jitted shape.
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class KMeansState(NamedTuple):
    centroids: jax.Array        # (K, d)
    counts: jax.Array           # (K,) assignment counts from the last fit
    inertia: jax.Array          # scalar: mean squared distance


def _dot_t(x: jax.Array, c: jax.Array) -> jax.Array:
    """x @ c.T in full fp32 (XLA:TPU would run the default-precision matmul
    at bf16, and probe selection must not depend on the backend)."""
    return jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)


def assign(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Eq. 1: nearest-centroid ids for x (N, d). One matmul + argmax."""
    half_sq = 0.5 * jnp.sum(centroids * centroids, axis=-1)       # (K,)
    scores = _dot_t(x, centroids) - half_sq[None, :]              # (N, K)
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


def assign_topk(x: jax.Array, centroids: jax.Array, k: int):
    """Top-k nearest centroids (used for n_probe partition selection)."""
    half_sq = 0.5 * jnp.sum(centroids * centroids, axis=-1)
    scores = _dot_t(x, centroids) - half_sq[None, :]
    vals, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), vals


@jax.jit
def assign_with_distance(x: jax.Array, centroids: jax.Array):
    """Eq. 1 assignment plus the squared distance to the winning centroid.

    Returns ``(assignment (N,) int32, dist2 (N,) fp32)``. The distance feeds
    the write-time drift statistics (maintenance/stats.py): the mean assigned
    distance of *new* rows vs. the build-time baseline is the centroid-drift
    signal that triggers a local recluster."""
    a = assign(x, centroids)
    d = x - centroids[a]
    return a, jnp.sum(d * d, axis=-1)


# ---------------------------------------------------------------------------
# parked partitions (merge-cold leaves the slot, retires the centroid)
# ---------------------------------------------------------------------------

# any centroid with norm beyond this is a parked sentinel: its assignment
# score e·mu - ||mu||²/2 ≈ -PARKED_NORM²/2 can never beat a live centroid's
# (unit-norm corpora score in [-1, 1])
PARKED_NORM = 32768.0


def parked_centroid(dim: int) -> np.ndarray:
    """The sentinel centroid of a merged-away partition (see module doc)."""
    c = np.zeros((dim,), np.float32)
    c[0] = PARKED_NORM
    return c


def parked_mask(centroids) -> np.ndarray:
    """(K,) bool — which partitions are parked (centroid is the sentinel)."""
    c = np.asarray(centroids)
    return np.sum(c * c, axis=-1) >= (0.5 * PARKED_NORM) ** 2


def live_partitions(centroids) -> int:
    """Number of partitions that can win an assignment / deserve a probe."""
    return int(np.sum(~parked_mask(centroids)))


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iters"))
def fit(key: jax.Array, x: jax.Array, n_clusters: int, n_iters: int = 16) -> KMeansState:
    """Lloyd's K-means (k-means++-lite seeding: random distinct samples)."""
    n = x.shape[0]
    idx0 = jax.random.choice(key, n, (n_clusters,), replace=n < n_clusters)
    cents = x[idx0]

    def step(cents, _):
        a = assign(x, cents)
        onehot_sum = jax.ops.segment_sum(x, a, num_segments=n_clusters)
        counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), a, num_segments=n_clusters)
        new = onehot_sum / jnp.maximum(counts[:, None], 1.0)
        # empty clusters keep their previous centroid
        new = jnp.where(counts[:, None] > 0, new, cents)
        return new, counts

    cents, counts = jax.lax.scan(step, cents, None, length=n_iters)
    counts = counts[-1]
    a = assign(x, cents)
    d = x - cents[a]
    inertia = jnp.mean(jnp.sum(d * d, axis=-1))
    return KMeansState(centroids=cents, counts=counts, inertia=inertia)


# ---------------------------------------------------------------------------
# workload-aware repartitioning (paper §3.2: online adjustment on imbalance)
# ---------------------------------------------------------------------------

class WorkloadStats:
    """Host-side probe-frequency tracker driving online repartitioning.

    Search threads bump ``record`` concurrently with writer-side
    ``reset``/``should_repartition``, so every touch of ``hits`` goes
    through ``_lock`` (``np.add.at`` is not atomic under concurrent
    mutation of the same buffer). Guarded-by contract enforced as
    staticcheck HMG201; readers take ``hits_snapshot()``."""

    def __init__(self, n_partitions: int, imbalance_threshold: float = 4.0):
        self.hits = np.zeros(n_partitions, np.int64)
        self.threshold = imbalance_threshold
        self._lock = threading.Lock()

    def record(self, probed_partitions: np.ndarray):
        idx = np.asarray(probed_partitions).reshape(-1)
        with self._lock:
            np.add.at(self.hits, idx, 1)

    def hits_snapshot(self) -> np.ndarray:
        """Coherent copy for readers (state_tree, repartition decisions)."""
        with self._lock:
            return self.hits.copy()

    def load_hits(self, hits: np.ndarray) -> None:
        """Restore path: replace the counters wholesale."""
        with self._lock:
            self.hits = np.asarray(hits, np.int64).copy()

    @property
    def imbalance(self) -> float:
        with self._lock:
            hits = self.hits.copy()
        mean = hits.mean() + 1e-9
        return float(hits.max() / mean)

    def should_repartition(self) -> bool:
        with self._lock:
            hits = self.hits.copy()
        mean = hits.mean() + 1e-9
        return hits.sum() > 0 and float(hits.max() / mean) > self.threshold

    def reset(self):
        with self._lock:
            self.hits[:] = 0


def split_two(key, members: jax.Array, n_iters: int = 8):
    """K=2 Lloyd's fit over one partition's members — the local step behind
    an incremental split (maintenance/executor.py). Returns
    ``(centroids (2, d), assignment (n,))``; only the members move, never the
    rest of the corpus."""
    sub = fit(key, members, 2, n_iters)
    return sub.centroids, assign(members, sub.centroids)


def split_hot_partition(key, x, state: KMeansState, hot: int) -> KMeansState:
    """Legacy stop-the-world split: re-fit K=2 on the hot partition's members
    and overwrite (hot, coldest) centroids; the caller then rebuilds the whole
    slab against the new centroid set. Superseded by the bounded-work split in
    ``repro.maintenance.executor`` (which moves only the hot partition's rows,
    byte-identically) — kept as the reference implementation."""
    a = assign(x, state.centroids)
    # host-side path (numpy): membership gather of the hot partition
    xs = np.asarray(x)
    an = np.asarray(a)
    members = xs[an == hot]
    if len(members) < 2:
        return state
    sub = fit(key, jnp.asarray(members), 2, 8)
    cents = np.asarray(state.centroids).copy()
    cold = int(np.asarray(state.counts).argmin())
    cents[hot] = np.asarray(sub.centroids[0])
    cents[cold] = np.asarray(sub.centroids[1])
    new = KMeansState(jnp.asarray(cents), state.counts, state.inertia)
    return new
