"""The plain reference of `bench/reference.py` and `bench/remote/*.py`
against the program's own oracles and update functions, at a small size:
the scans against `repro.kernels.ref`, the serving and gain equations
against `repro.core.gain`, the projection and rounding against
`repro.core.projection` and `repro.core.rounding`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.registry import Registry
from repro.core import gain, projection, rounding
from repro.index.ivf import IVFFlatIndex
from repro.kernels import ref as kref

N, D, B = 4096, 16, 8


@pytest.fixture(scope="module")
def catalog():
    return jax.random.uniform(jax.random.PRNGKey(0), (N, D))


@pytest.fixture(scope="module")
def queries(catalog):
    return np.asarray(catalog[:B]) + np.float32(0.01)


def test_flat_scan_is_l2_topk_ref(catalog, queries):
    remote = Registry().module("remote", "flat").Remote(catalog, {}, None,
                                                        "float32")
    d, i, tied, _ = remote.topk(jnp.asarray(queries), 20, np.zeros(B))
    want_d, want_i = kref.l2_topk_ref(jnp.asarray(queries), catalog, 20)
    np.testing.assert_allclose(d, np.asarray(want_d), rtol=1e-5, atol=1e-5)
    assert (i == np.asarray(want_i)).mean() > 0.99
    assert not tied.any()


def test_ivf_scan_is_ivf_scan_ref(catalog, queries):
    index = IVFFlatIndex(catalog, nlist=32, nprobe=4)
    remote = Registry().module("remote", "ivf").Remote(
        catalog, {"nprobe": 4}, index, "float32")
    q = jnp.asarray(queries)
    d, i, tied, rows = remote.topk(q, 20, np.zeros(B))
    dc = kref.pairwise_l2_ref(q, index.centroids)
    _, probe = jax.lax.top_k(-dc, 4)
    cand = index.invlists[probe].reshape(B, -1)
    want_d, want_i = kref.ivf_scan_ref(q, catalog, cand, 20)
    np.testing.assert_allclose(d, np.asarray(want_d), rtol=1e-5)
    np.testing.assert_array_equal(i, np.asarray(want_i))
    # the program's own lists pass the check, one moved row does not
    assert remote.verify() == {"ivf_lists_bad": 0}
    bad = np.asarray(index.invlists).copy()
    r, c = np.argwhere(bad >= 0)[0]
    row = bad[r, c]
    bad[r, c] = -1
    other = (r + 1) % bad.shape[0]
    bad[other, np.argmax(bad[other] < 0)] = row
    moved = Registry().module("remote", "ivf").Remote(
        catalog, {"nprobe": 4}, index, "float32")
    moved.invlists = bad
    assert moved.verify()["ivf_lists_bad"] >= 1


def _slab(seed, c=24, k=5):
    rng = np.random.default_rng(seed)
    d = np.sort(rng.random((B, c)).astype(np.float32) * 4, axis=1)
    valid = rng.random((B, c)) > 0.1
    d = np.where(valid, d, reference.BIG).astype(np.float32)
    x = (rng.random((B, c)) < 0.3).astype(np.float32) * valid
    y = np.where(x > 0, rng.uniform(0.5, 1.0, (B, c)),
                 rng.uniform(0.0, 0.2, (B, c))).astype(np.float32) * valid
    return d, valid, x, y, k


@pytest.mark.parametrize("seed", range(4))
def test_serving_and_gain_equations_are_the_programs(seed):
    d, valid, x, y, k = _slab(seed)
    c_f = 1.7
    cost, g_int, g_frac, g, _ = reference.serve_and_gain(d, valid, x, y, k,
                                                         c_f)
    served = gain.serve_batch(jnp.asarray(d), jnp.asarray(x), k, c_f)
    frac, sub = gain.gain_and_subgradient_batch(jnp.asarray(d),
                                                jnp.asarray(y), k, c_f)
    np.testing.assert_allclose(cost, np.asarray(served.cost), rtol=1e-6)
    np.testing.assert_allclose(g_int, np.asarray(served.gain), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(g_frac, np.asarray(frac), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g, np.where(valid, np.asarray(sub), 0),
                               rtol=1e-5, atol=1e-5)


def test_projection_is_the_programs():
    z = np.random.default_rng(3).exponential(1.0, 5000).astype(np.float32)
    z[:7] *= 300.0   # some entries cap at 1
    want = np.asarray(projection.capped_simplex_negentropy(jnp.asarray(z), 100))
    got = reference.project_capped_simplex(z, 100)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    assert got.sum() == pytest.approx(100.0)


def test_coupled_rounding_is_the_programs():
    rng = np.random.default_rng(4)
    y = rng.uniform(0, 1, 3000).astype(np.float32)
    y_new = np.clip(y + rng.normal(0, 0.05, 3000), 0, 1).astype(np.float32)
    x = (rng.random(3000) < y).astype(np.float32)
    key = jax.random.PRNGKey(11)
    want = np.asarray(rounding.coupled_rounding(
        key, jnp.asarray(x), jnp.asarray(y), jnp.asarray(y_new)))
    u = np.asarray(jax.random.uniform(key, (3000,), jnp.float32))
    np.testing.assert_array_equal(reference.coupled_round(u, x, y, y_new),
                                  want)


def test_round_keys_follow_the_programs_chain():
    from repro.core import policy

    state = policy.init_state(16, policy.AcaiConfig(h=4), seed=21)
    keys = reference.round_keys(21, [0, 3])
    k = state.key
    for step in range(4):
        k, k_round = jax.random.split(k)
        if step in keys:
            np.testing.assert_array_equal(np.asarray(keys[step]),
                                          np.asarray(k_round))


def test_candidate_slab_keeps_first_occurrences():
    ids, d, valid = reference.candidate_slab(
        np.array([[3, 1]]), np.array([[0.1, 0.2]]), np.array([[1, 5]]),
        np.array([[0.2, 0.9]]), n=10)
    np.testing.assert_array_equal(valid, [[True, True, False, True]])
    np.testing.assert_array_equal(ids, [[3, 1, 10, 5]])
    assert d[0, 2] == reference.BIG


def test_compare_leaves_ties_out():
    ref = {"cost": np.array([10.0, 10.0]), "gain_int": np.zeros(2),
           "gain_frac": np.zeros(2), "y": np.zeros(4, np.float32),
           "x": np.zeros(4, np.float32), "tied": np.array([False, True]),
           "tied_rows": np.array([False, False, False, True]),
           "round_ties": np.array([False, False, True, False])}
    prog = {"cost": np.array([10.0, 50.0]), "gain_int": np.zeros(2),
            "gain_frac": np.zeros(2), "y": np.array([0, 0, 0, 1.0]),
            "x": np.array([0, 0, 0, 1.0])}
    got = reference.compare(prog, ref, h=2)
    assert got == {"cost_gap": 0.0, "gain_gap": 0.0, "state_gap": 0.0,
                   "tied": 1, "fill_ties": 0}
    prog["x"] = np.array([0, 0, 1.0, 1.0])      # a rounding tie
    assert reference.compare(prog, ref, h=2)["state_gap"] == 0.0
    prog["x"] = np.array([1.0, 0, 1.0, 1.0])
    assert reference.compare(prog, ref, h=2)["state_gap"] == 0.5


def test_rounding_ties_are_the_near_threshold_uniforms():
    y = np.array([0.5, 0.5, 0.2, 0.2], np.float32)
    y_new = np.array([0.4, 0.4, 0.3, 0.3], np.float32)
    x = np.array([1.0, 1.0, 0.0, 0.0], np.float32)
    p = np.array([0.2, 0.2, 0.125, 0.125], np.float32)   # evict, fetch
    u = p + np.array([1e-6, 1e-2, -1e-6, -1e-2], np.float32)
    np.testing.assert_array_equal(reference.rounding_ties(u, x, y, y_new),
                                  [True, False, True, False])


def test_fill_boundary_tie_is_flagged():
    """Weights that sum to k within float32 rounding put the fill's
    boundary in doubt; weights well off k do not."""
    d = np.array([[1.0, 2.0, 3.0], [1.0, 2.0, 3.0]], np.float32)
    valid = np.ones((2, 3), bool)
    x = np.zeros((2, 3), np.float32)
    y = np.array([[1.0, 1.0, 0.0], [0.3, 0.6, 0.2]], np.float32)
    *_, tie = reference.serve_and_gain(d, valid, x, y, 2, 10.0)
    np.testing.assert_array_equal(tie, [True, False])


def test_state_gap_takes_the_projections_common_factor():
    """y off by one common factor within SCALE_TIE reads as no gap; a
    larger factor, or a gap on one row, does not."""
    y_r = np.array([0.2, 0.3, 0.5, 1.0], np.float32)
    ref = {"cost": np.ones(1), "gain_int": np.zeros(1),
           "gain_frac": np.zeros(1), "y": y_r, "x": np.zeros(4, np.float32),
           "tied": np.array([False]), "tied_rows": np.zeros(4, bool),
           "round_ties": np.zeros(4, bool)}
    prog = {"cost": np.ones(1), "gain_int": np.zeros(1),
            "gain_frac": np.zeros(1), "x": np.zeros(4, np.float32)}
    gap = lambda y: reference.compare({**prog, "y": y}, ref, h=2)["state_gap"]
    assert gap(np.where(y_r < 1, y_r * (1 + 3e-5), y_r)) < 1e-7
    assert gap(np.where(y_r < 1, y_r * 1.01, y_r)) > 1e-3
    assert gap(y_r + np.array([0, 0.01, 0, 0], np.float32)) > 1e-3
