"""The IVF lists are the Voronoi cells of their centroids.

`kmeans` assigns every row its nearest returned centroid, and the IVF
index builds, refreshes and appends to its lists by that same rule; the
check is a float64 host scan, with ties within 32 float32 ulps of the
squared norms left out (the rule `bench/remote/ivf.py` verifies on the
chip).  `CHUNK` is patched small so that the assignment runs over several
chunks, the last one padded.  `build_invlists` is pinned against the
per-row loop it replaced."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.ivf import IVFFlatIndex, build_invlists

kmeans_mod = importlib.import_module("repro.index.kmeans")

TIE_ULPS = 32
EPS32 = float(np.finfo(np.float32).eps)


@pytest.fixture
def small_chunks(monkeypatch):
    """CHUNK is read when a function is traced: drop the compiled
    functions before and after, so no other test sees the small chunks."""
    monkeypatch.setattr(kmeans_mod, "CHUNK", 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _not_nearest(data, cents, assign) -> np.ndarray:
    """Rows whose assigned centroid is farther than their nearest one
    beyond a tie, by float64 distances."""
    x = np.asarray(data, np.float64)
    c = np.asarray(cents, np.float64)
    d = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    mine = d[np.arange(x.shape[0]), np.asarray(assign)]
    tol = TIE_ULPS * EPS32 * ((x * x).sum(1) + (c * c).sum(1).max())
    return np.nonzero(mine - d.min(1) > tol)[0]


def _list_of(index) -> np.ndarray:
    """Each slab row's list in the index's table; -1 for a row in none.
    Fails if a row sits in two lists or twice in one."""
    table = np.asarray(index.invlists)
    ids = table[table >= 0]
    assert np.unique(ids).shape == ids.shape
    own = np.full(index.capacity, -1)
    own[ids] = np.nonzero(table >= 0)[0]
    return own


def _data(n, d, seed=0):
    return jnp.asarray(np.random.default_rng(seed).random((n, d),
                                                          np.float32))


@pytest.mark.parametrize("n,d,k", [(1000, 16, 24), (1030, 8, 64),
                                   (129, 4, 7)])
def test_kmeans_assigns_each_row_its_nearest_centroid(small_chunks, n, d, k):
    data = _data(n, d)
    cents, assign = kmeans_mod.kmeans(jax.random.PRNGKey(1), data, k, 4)
    assert assign.shape == (n,)
    assert _not_nearest(data, cents, assign).size == 0


def test_ivf_build_refresh_and_add_keep_voronoi_lists(small_chunks):
    data = _data(1000, 16)
    idx = IVFFlatIndex(data, nlist=20, nprobe=4)
    live = np.arange(1000)
    own = _list_of(idx)
    assert (own[live] >= 0).all()
    assert _not_nearest(data, idx.centroids, own[live]).size == 0

    # refresh: k-means again over the live rows only
    idx.remove(np.arange(0, 1000, 3))
    idx.refresh()
    live = idx.live_rows()
    own = _list_of(idx)
    assert (own[live] >= 0).all() and (own[np.arange(0, 1000, 3)] < 0).all()
    emb = np.asarray(idx.embeddings)
    assert _not_nearest(emb[live], idx.centroids, own[live]).size == 0

    # add: new rows join the list of their nearest existing centroid
    new = np.random.default_rng(5).random((300, 16), np.float32)
    ids = idx.add(new)
    own = _list_of(idx)
    assert (own[ids] >= 0).all()
    assert _not_nearest(new, idx.centroids, own[ids]).size == 0


def test_list_stats_describe_the_table():
    idx = IVFFlatIndex(_data(600, 8), nlist=16, nprobe=4)
    s = idx.list_stats()
    table = np.asarray(idx.invlists)
    sizes = (table >= 0).sum(1)
    assert (s["nlist"], s["cap"]) == table.shape
    assert s["max_list"] == sizes.max() == s["cap"]
    assert s["mean_list"] == pytest.approx(600 / 16)
    assert s["empty_lists"] == int((sizes == 0).sum())
    assert s["padded_share"] == pytest.approx(1 - 600 / table.size)


def _loop_invlists(assign, nlist, cap=None):
    """The per-row build `build_invlists` replaced."""
    counts = np.bincount(assign, minlength=nlist)
    cap = int(counts.max()) if cap is None else cap
    table = np.full((nlist, cap), -1, np.int32)
    cursor = np.zeros(nlist, np.int32)
    for i, a in enumerate(assign):
        c = cursor[a]
        if c < cap:
            table[a, c] = i
            cursor[a] = c + 1
    return table


@pytest.mark.parametrize("n,nlist,cap,empty", [
    (5000, 64, None, 0),
    (5000, 64, 40, 0),        # truncated: each list keeps its lowest ids
    (777, 50, None, 17),      # empty lists
    (777, 50, 5, 17),
    (1, 3, None, 2),
    (300, 8, 0, 0),
])
def test_build_invlists_is_the_loops(n, nlist, cap, empty):
    rng = np.random.default_rng(n + nlist)
    used = rng.permutation(nlist)[:nlist - empty]
    assign = rng.choice(used, size=n).astype(np.int32)
    got = build_invlists(assign, nlist, cap)
    want = _loop_invlists(assign, nlist, cap)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)
