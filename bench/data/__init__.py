"""Seeded data for the benchmark's deployments, made on the device.

A configuration's ``data`` section names its generator (``generator``), a
module of this package that ``make`` finds by that name. A generator
exposes ``make(params, structure_key, sample_key) -> Corpus``: the
structure (topics, cluster sizes and members, the graph, the columns)
from the first key, the noise of every row from the second. Both come
from the configuration's fixed ``corpus_seed``: every run builds the same
index, so its size, fill and build do not move from seed to seed (k-means
lands elsewhere with other noise, and the build's overflow into the delta
store, which the store grows to fit, moves by thousands of rows). The
run's seed draws the queries (``queries``). The same key gives
bit-identical arrays on the same device, so the output check regenerates
the corpus after the window instead of holding a copy through it.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Corpus:
    vectors: jax.Array                 # (n, d) float32 on the device, raw
    ids: np.ndarray                    # (n,) int32 global node ids
    n_nodes: int
    edges: Optional[tuple] = None      # (src, dst) int32 on the host, as given
    attrs: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    def user_bytes(self) -> int:
        """What a user hands the index: fp32 vectors, int32 ids, int32
        attribute columns and two int32 per edge given."""
        n, d = self.vectors.shape
        out = n * d * 4 + n * 4 + sum(4 * a.size for a in self.attrs.values())
        if self.edges is not None:
            out += 2 * 4 * int(self.edges[0].size)
        return int(out)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (the run's ``--seed`` may need
    more than 32 bits): the seed's two 32-bit words folded into key 0."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def quantile_profile(n_items: int, total: int, sigma: float) -> np.ndarray:
    """Fixed heavy-tailed sizes: ``n_items`` lognormal quantiles of spread
    ``sigma``, scaled to whole numbers that sum to ``total`` (largest
    remainder). Every seed gets this same multiset, only in another order."""
    from scipy.special import ndtri
    z = ndtri((np.arange(n_items) + 0.5) / n_items)
    w = np.exp(sigma * z)
    raw = total * w / w.sum()
    sizes = np.floor(raw).astype(np.int64)
    short = total - int(sizes.sum())
    sizes[np.argsort(-(raw - sizes), kind="stable")[:short]] += 1
    return sizes


def labels_from_sizes(key: jax.Array, sizes: np.ndarray) -> jax.Array:
    """(sum(sizes),) int32 labels with ``sizes[i]`` copies of label i, in
    an order drawn from ``key``."""
    lab = jnp.repeat(jnp.arange(len(sizes), dtype=jnp.int32),
                     jnp.asarray(sizes), total_repeat_length=int(sizes.sum()))
    return jax.random.permutation(key, lab)


def make(params: dict) -> Corpus:
    """The corpus of a configuration's ``data`` section."""
    gen = importlib.import_module(f"{__name__}.{params['generator']}")
    key = seed_key(int(params["corpus_seed"]))
    return gen.make(params, key, jax.random.fold_in(key, 1))


@functools.partial(jax.jit, static_argnames=("n",))
def _noisy_rows(vectors, key, noise, *, n):
    kp, kn = jax.random.split(key)
    rows = vectors.shape[0]
    pick = jax.random.permutation(kp, rows)
    pick = jnp.tile(pick, -(-n // rows))[:n]
    x = vectors[pick]
    return x + noise * jax.random.normal(kn, x.shape, x.dtype)


def queries(corpus: Corpus, seed: int, n: int, noise: float) -> np.ndarray:
    """``n`` queries, copied to the host: distinct corpus rows drawn from
    the seed (cycling through the corpus only when ``n`` exceeds it), each
    plus N(0, ``noise``) in every dimension."""
    key = jax.random.fold_in(seed_key(seed), 0x51)
    return np.asarray(_noisy_rows(corpus.vectors, key, jnp.float32(noise),
                                  n=int(n)))
