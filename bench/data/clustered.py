"""Clustered embeddings for a vector-only deployment, made on the device.

Sentence embeddings of a real corpus gather around topics: near
neighbours are far closer than random pairs, and topics themselves group.
The generator plants that structure at any width:

- ``n_topics`` unit topic directions;
- ``n_clusters`` cluster centres, each its topic's direction plus
  N(0, ``center_spread``) per dimension, renormalised;
- cluster sizes from ``quantile_profile(n_clusters, n_rows,
  cluster_size_sigma)``, dealt to clusters and rows from the structure
  key;
- each row its centre plus N(0, ``noise``) per dimension (not normalised:
  the index normalises what it is given).

Row ``i`` has id ``i``. No attribute columns and no graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import Corpus, labels_from_sizes, quantile_profile


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("d", "n_topics", "n_clusters"))
def _rows(key, kn, labels, center_spread, noise, *, d, n_topics, n_clusters):
    kt, kc = jax.random.split(key)
    topics = _unit(jax.random.normal(kt, (n_topics, d)))
    centers = _unit(topics[jnp.arange(n_clusters) % n_topics]
                    + center_spread * jax.random.normal(kc, (n_clusters, d)))
    return centers[labels] + noise * jax.random.normal(
        kn, (labels.shape[0], d))


def make(p: dict, key, sample_key) -> Corpus:
    """``key`` fixes topics, centres and cluster members; ``sample_key``
    draws every row's noise."""
    n, d = int(p["n_rows"]), int(p["dim"])
    sizes = quantile_profile(int(p["n_clusters"]), n,
                             float(p["cluster_size_sigma"]))
    kl, kr = jax.random.split(key)
    labels = labels_from_sizes(kl, sizes)
    vec = _rows(kr, sample_key, labels, jnp.float32(p["center_spread"]),
                jnp.float32(p["noise"]), d=d, n_topics=int(p["n_topics"]),
                n_clusters=int(p["n_clusters"]))
    return Corpus(vectors=vec, ids=np.arange(n, dtype=np.int32), n_nodes=n)
