"""The seeded generators: the published counts of the citation dataset at
full size, and the clustered recipe at a small size."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import data  # noqa: E402

SEED = 2 ** 33 + 7              # more than 32 bits


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["data"]


@pytest.fixture(scope="module")
def arxiv():
    return data.make(_cfg("arxiv_hybrid"))


def test_arxiv_published_counts(arxiv):
    assert arxiv.n_nodes == 169_343
    assert arxiv.vectors.shape == (169_343, 128)
    src, dst = arxiv.edges
    m = src.size // 2
    assert m == 1_166_243                        # citations, each given twice
    citing, cited = src[:m], dst[:m]
    np.testing.assert_array_equal(src[m:], cited)
    np.testing.assert_array_equal(dst[m:], citing)
    assert np.all(cited < citing)                # newer cites older
    pairs = citing.astype(np.int64) * arxiv.n_nodes + cited
    assert np.unique(pairs).size == m            # no repeated citation
    year = arxiv.attrs["year"]
    assert int(np.sum(year <= 2017)) == 90_941
    assert int(np.sum(year == 2018)) == 29_799
    assert int(np.sum(year >= 2019)) == 48_603
    assert np.all(np.diff(year) >= 0)            # nodes in publication order
    assert np.unique(arxiv.attrs["subject"]).size == 40


def test_arxiv_shape_of_the_graph(arxiv):
    src, dst = arxiv.edges
    m = src.size // 2
    indeg = np.bincount(dst[:m], minlength=arxiv.n_nodes)
    assert indeg.max() > 20 * indeg.mean()       # heavy-tailed in-degree
    subj = arxiv.attrs["subject"]
    same = np.mean(subj[src[:m]] == subj[dst[:m]])
    assert 0.6 < same < 0.7                      # homophily about 0.65


def test_clustered_recipe_small():
    p = dict(_cfg("serve_1m"), n_rows=4096, n_clusters=64, n_topics=8)
    a = data.make(p)
    b = data.make(p)
    c = data.make(dict(p, corpus_seed=SEED))
    va, vb, vc = (np.asarray(x.vectors) for x in (a, b, c))
    assert va.shape == (4096, 384) and va.dtype == np.float32
    np.testing.assert_array_equal(va, vb)        # the seed fixes the data
    assert not np.array_equal(va, vc)
    np.testing.assert_array_equal(a.ids, np.arange(4096))
    assert a.edges is None and not a.attrs
    u = va / np.linalg.norm(va, axis=1, keepdims=True)
    s = u[:256] @ u.T
    s[np.arange(256), np.arange(256)] = -1.0
    top1 = np.median(s.max(axis=1))
    rand = np.median(s)
    assert top1 > 0.4 and abs(rand) < 0.1        # not noise-dominated


def test_cluster_sizes_are_the_same_multiset_for_every_seed():
    sizes = data.quantile_profile(1024, 1_048_576, 0.5)
    assert sizes.sum() == 1_048_576
    np.testing.assert_array_equal(sizes,
                                  data.quantile_profile(1024, 1_048_576, 0.5))
    assert sizes.min() > 0 and sizes.max() < 8 * sizes.mean()


def test_queries_are_noisy_distinct_rows():
    p = dict(_cfg("serve_1m"), n_rows=1024, n_clusters=16, n_topics=4,
             dim=32)
    c = data.make(p)
    v = np.asarray(c.vectors)
    rows = data.queries(c, SEED, 512, 0.0)
    q = data.queries(c, SEED, 512, 0.05)
    assert not np.array_equal(rows, data.queries(c, SEED + 1, 512, 0.0))
    hit = (rows[:, None, :] == v[None]).all(-1)
    assert np.all(hit.sum(1) == 1)               # each query is a row ...
    assert np.unique(hit.argmax(1)).size == 512  # ... and none repeats
    assert np.std(q - rows) == pytest.approx(0.05, rel=0.05)
