"""The benchmark's copies of the program's generators and fetch-cost
calibration, pinned against the originals at a small size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen
from repro.core import costs, trace
from repro.serve import arrivals


def test_catalog_distribution_matches_sift_like():
    """Both draw U[0,1]^d float32: compare the two samples' distributions
    (two-sample Kolmogorov-Smirnov statistic) and ranges."""
    ours = np.asarray(gen.make_catalog(4096, 16, seed=3)).ravel()
    theirs, _, _ = trace.sift_like(n=4096, d=16, t=8, seed=3)
    theirs = theirs.ravel()
    assert ours.dtype == theirs.dtype == np.float32
    assert 0.0 <= ours.min() and ours.max() < 1.0
    grid = np.linspace(0, 1, 201)
    ks = np.abs(np.searchsorted(np.sort(ours), grid) / ours.size
                - np.searchsorted(np.sort(theirs), grid) / theirs.size).max()
    assert ks < 0.01, ks


@pytest.mark.parametrize("n", [50, 1000, 20000])
def test_zipf_beta_is_the_programs(n):
    d = np.sort(np.random.default_rng(n).random(n) * 3)
    assert gen.zipf_calibrate_beta(d, 0.9) == trace._zipf_calibrate_beta(d, 0.9)


def test_popularity_is_the_programs():
    catalog = np.random.default_rng(1).random((5000, 16), dtype=np.float32)
    want = trace._barycentric_popularity(catalog, 0.9)
    got = gen.popularity_from_distances(
        gen.barycentric_distances(jnp.asarray(catalog)), 0.9)
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_request_ids_follow_popularity():
    lam = np.array([0.5, 0.25, 0.125, 0.125])
    ids = gen.request_ids(lam, 40000, seed=5)
    np.testing.assert_allclose(np.bincount(ids, minlength=4) / ids.size, lam,
                               atol=0.01)
    np.testing.assert_array_equal(ids, gen.request_ids(lam, 40000, seed=5))


def test_fetch_cost_is_the_programs():
    x = jax.random.uniform(jax.random.PRNGKey(2), (3000, 16))
    want = float(costs.calibrate_fetch_cost(x, kth=50, sample=256))
    got = gen.fetch_cost(x, kth=50, sample=256, block=64)
    assert got == pytest.approx(want, rel=1e-5)


def test_poisson_gaps_are_the_programs():
    spec = arrivals.ArrivalSpec(kind="poisson", rate_rps=465.0, seed=77)
    want = arrivals.arrival_times(spec, 5000)
    got = np.cumsum(gen.poisson_gaps(465.0, 5000, seed=77))
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_poisson_schedule_is_conditioned_on_its_count():
    due = gen.poisson_schedule(465.0, 30.0, seed=9, multiple=64)
    assert due.size % 64 == 0 and abs(due.size - 465 * 30) < 64
    assert due[-1] == pytest.approx(30.0) and (np.diff(due) >= 0).all()
    # the same draw as the program's stream, rescaled to the window
    raw = np.cumsum(gen.poisson_gaps(465.0, due.size, seed=9)) / 1e3
    np.testing.assert_allclose(due, raw * (30.0 / raw[-1]))


def test_sub_seeds_take_large_seeds():
    a = gen.sub_seeds(2**31 + 5)
    b = gen.sub_seeds(2**40 + 5)
    assert a != b and all(0 <= v < 2**31 for v in a.values())
    assert a == gen.sub_seeds(2**31 + 5)


def test_ordered_schedule_keeps_the_set_of_batches():
    a = gen.poisson_schedule(550.0, 20.0, seed=0, multiple=64, order_seed=1)
    b = gen.poisson_schedule(550.0, 20.0, seed=0, multiple=64, order_seed=2)
    assert a.size == b.size and a[-1] == pytest.approx(b[-1])
    assert not np.array_equal(a, b)
    fill = lambda t: np.sort(np.diff(np.concatenate([[0.0], t]))
                             .reshape(-1, 64).sum(axis=1))
    np.testing.assert_allclose(fill(a), fill(b))
