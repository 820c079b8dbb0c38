"""A citation graph with text features and relational columns, made on the
device, at the shape of a published citation dataset.

Nodes are papers in publication order. ``year_groups`` lists
``[first_year, last_year, count]`` blocks in that order; years within a
block rise evenly with the node index. Each paper has one of
``n_subjects`` subjects (sizes from ``quantile_profile``, the same for
every seed) and a feature vector: its subject's direction, spread into
``topics_per_subject`` topics by N(0, ``center_spread``), plus
N(0, ``noise``) per dimension.

Exactly ``n_edges`` distinct citations, each from a paper to an older one:

- citing papers get ``n_edges`` in proportion to a fixed lognormal
  profile (``out_degree_sigma``), ramped up linearly over the first
  ``ramp_nodes`` papers, which have little older work to cite;
- a citation goes to an older paper of the same subject with probability
  ``homophily``, otherwise to any older paper;
- the cited paper is drawn in proportion to its fitness, a fixed
  lognormal profile (``fitness_sigma``): a heavy-tailed in-degree;
- repeated pairs are drawn again until none is left.

Edges are given to the index in both directions. Columns: ``year`` and
``subject``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import Corpus, labels_from_sizes, quantile_profile

MAX_REDRAW_ROUNDS = 64


def years(groups) -> np.ndarray:
    out = []
    for first, last, count in groups:
        span = int(last) - int(first) + 1
        out.append(int(first) + (np.arange(int(count)) * span) // int(count))
    return np.concatenate(out).astype(np.int32)


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("d", "n_subjects", "topics_per_subject"))
def _features(key, kn, subj, center_spread, noise, *, d, n_subjects,
              topics_per_subject):
    ks, kt, ka = jax.random.split(key, 3)
    n_topics = n_subjects * topics_per_subject
    subj_dir = _unit(jax.random.normal(ks, (n_subjects, d)))
    topic = _unit(subj_dir[jnp.arange(n_topics) // topics_per_subject]
                  + center_spread * jax.random.normal(kt, (n_topics, d)))
    tix = subj * topics_per_subject + jax.random.randint(
        ka, subj.shape, 0, topics_per_subject)
    return topic[tix] + noise * jax.random.normal(kn, (subj.shape[0], d))


def _later_duplicates(citing, cited):
    """(m,) bool: True on every repeat of an earlier (citing, cited) pair."""
    m = citing.shape[0]
    c, t, ix = jax.lax.sort((citing, cited, jnp.arange(m, dtype=jnp.int32)),
                            num_keys=2)
    rep = jnp.concatenate([jnp.zeros((1,), bool),
                           (c[1:] == c[:-1]) & (t[1:] == t[:-1])])
    return jnp.zeros((m,), bool).at[ix].set(rep)


@functools.partial(jax.jit, static_argnames=("n_subjects", "m"))
def _edges(key, subj, outw, fit, homophily, *, n_subjects, m):
    n = subj.shape[0]
    k_any, k_same, k_pick, k_redraw = jax.random.split(key, 4)
    cw = jnp.cumsum(outw)
    bounds = jnp.floor(m * (cw / cw[-1])).astype(jnp.int32).at[-1].set(m)
    citing = jnp.searchsorted(bounds, jnp.arange(m, dtype=jnp.int32),
                              side="right").astype(jnp.int32)
    prev = citing - 1

    f_all = jnp.cumsum(fit)

    def any_older(k):
        r = jax.random.uniform(k, (m,)) * f_all[prev]
        return jnp.minimum(jnp.searchsorted(f_all, r, side="right"),
                           prev).astype(jnp.int32)

    # older papers of the citing paper's subject are the positions
    # [lo, hi) of the subject-major order
    order = jnp.argsort(subj * n + jnp.arange(n, dtype=jnp.int32))
    pos = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    f_subj = jnp.cumsum(fit[order])
    start = jnp.searchsorted(subj[order], jnp.arange(n_subjects),
                             side="left").astype(jnp.int32)
    lo = start[subj[citing]]
    hi = pos[citing]
    base = jnp.where(lo > 0, f_subj[jnp.maximum(lo - 1, 0)], 0.0)
    top = f_subj[jnp.maximum(hi - 1, 0)]
    r = base + jax.random.uniform(k_same, (m,)) * (top - base)
    p = jnp.clip(jnp.searchsorted(f_subj, r, side="right"), lo,
                 jnp.maximum(hi - 1, lo))
    same = (hi > lo) & (jax.random.uniform(k_pick, (m,)) < homophily)
    cited = jnp.where(same, order[p], any_older(k_any)).astype(jnp.int32)

    def cond(state):
        _, it, left = state
        return (left > 0) & (it < MAX_REDRAW_ROUNDS)

    def body(state):
        cited, it, _ = state
        rep = _later_duplicates(citing, cited)
        cited = jnp.where(rep, any_older(jax.random.fold_in(k_redraw, it)),
                          cited)
        return cited, it + 1, jnp.sum(rep)

    cited, _, _ = jax.lax.while_loop(cond, body, (cited, 0, jnp.int32(1)))
    return citing, cited, jnp.sum(_later_duplicates(citing, cited))


def make(p: dict, key, sample_key) -> Corpus:
    """``key`` fixes subjects, topics, years and every citation;
    ``sample_key`` draws the noise of every feature vector."""
    n, d, m = int(p["n_nodes"]), int(p["dim"]), int(p["n_edges"])
    year = years(p["year_groups"])
    if year.size != n:
        raise ValueError(f"year_groups count {year.size} nodes, not {n}")
    n_subj = int(p["n_subjects"])
    k_subj, k_feat, k_out, k_fit, k_edge = jax.random.split(key, 5)
    subj = labels_from_sizes(
        k_subj, quantile_profile(n_subj, n, float(p["subject_size_sigma"])))
    ramp = np.minimum(np.arange(n) / float(p["ramp_nodes"]), 1.0)
    prof = quantile_profile(n, 10 ** 9, float(p["out_degree_sigma"]))
    outw = jax.random.permutation(k_out, jnp.asarray(prof, jnp.float32)) \
        * jnp.asarray(ramp, jnp.float32)
    fit = jax.random.permutation(k_fit, jnp.asarray(
        quantile_profile(n, 10 ** 9, float(p["fitness_sigma"])), jnp.float32))
    citing, cited, left = _edges(k_edge, subj, outw, fit,
                                 jnp.float32(p["homophily"]),
                                 n_subjects=n_subj, m=m)
    if int(left):
        raise RuntimeError(f"{int(left)} repeated citations left after "
                           f"{MAX_REDRAW_ROUNDS} redraw rounds")
    vec = _features(k_feat, sample_key, subj, jnp.float32(p["center_spread"]),
                    jnp.float32(p["noise"]), d=d, n_subjects=n_subj,
                    topics_per_subject=int(p["topics_per_subject"]))
    citing, cited = np.asarray(citing), np.asarray(cited)
    edges = (np.concatenate([citing, cited]), np.concatenate([cited, citing]))
    return Corpus(vectors=vec, ids=np.arange(n, dtype=np.int32), n_nodes=n,
                  edges=edges, attrs={"year": year,
                                      "subject": np.asarray(subj, np.int32)})
