"""Inputs of a benchmark run, made from seeds: the catalog, its request
popularity, the request pool, the Poisson arrival schedule and the fetch
cost c_f.

These are the benchmark's own copies of the generators the program ships
(`repro.core.trace.sift_like`, `repro.serve.arrivals`,
`repro.core.costs.calibrate_fetch_cost`), so that a change to the program
cannot change the yardstick.  `bench/tests/test_bench_gen.py` pins each copy
against the original at a small size.

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: dissociates the arrival stream from other consumers of the same seed
#: (the value `repro.serve.arrivals` uses)
ARRIVAL_TAG = 0xA221


def sub_seeds(seed: int) -> dict:
    """Independent 31-bit seeds for each random stream of one run, drawn
    from `--seed` (any non-negative integer, also above 2**32)."""
    words = np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)
    names = ("requests", "cache", "arrivals", "check")
    return {n: int(w) & 0x7FFFFFFF for n, w in zip(names, words)}


# -- catalog -----------------------------------------------------------------

def make_catalog(rows: int, dim: int, seed: int):
    """U[0,1]^dim float32 catalog of `rows` rows, made on the device in one
    jitted call (the distribution of `sift_like`)."""

    @jax.jit
    def draw(key):
        return jax.random.uniform(key, (rows, dim), jnp.float32)

    return draw(jax.random.PRNGKey(seed))


def zipf_calibrate_beta(dist_rank: np.ndarray, zipf_a: float = 0.9) -> float:
    """beta such that lambda ∝ d^-beta has a Zipf(a)-like ranked tail: the
    log-log slope gamma of ranked distances against rank, then beta =
    a / gamma (copy of `repro.core.trace._zipf_calibrate_beta`)."""
    n = dist_rank.shape[0]
    if n < 2:
        return float(zipf_a)
    ranks = np.arange(1, n + 1)
    lo, hi = n // 100 + 1, n // 2
    if hi - lo < 8:
        lo, hi = 0, n
    sel = slice(lo, hi)
    with np.errstate(all="ignore"):
        gamma = np.polyfit(np.log(ranks[sel]),
                           np.log(dist_rank[sel] + 1e-12), 1)[0]
    if not np.isfinite(gamma) or gamma <= 0:
        gamma = 1.0
    return float(zipf_a / max(gamma, 1e-3))


def popularity_from_distances(dist: np.ndarray, zipf_a: float) -> np.ndarray:
    """The paper's IRM popularity: lambda_i ∝ d_i^-beta, d_i the distance of
    object i from the catalog barycenter, beta Zipf-calibrated (copy of
    `repro.core.trace._barycentric_popularity` from the distances on)."""
    dist = np.asarray(dist, np.float64)
    beta = zipf_calibrate_beta(np.sort(dist), zipf_a)
    log_lam = -beta * np.log(dist + 1e-9)
    lam = np.exp(log_lam - log_lam.max())
    return lam / lam.sum()


def barycentric_distances(catalog):
    """(N,) float32 distance of each row from the catalog barycenter,
    computed on the device where the catalog lives."""

    @jax.jit
    def dist(x):
        bary = jnp.mean(x, axis=0, keepdims=True)
        return jnp.sqrt(jnp.sum((x - bary) ** 2, axis=1))

    return np.asarray(dist(catalog))


def request_ids(lam: np.ndarray, count: int, seed: int) -> np.ndarray:
    """`count` catalog ids drawn i.i.d. from the popularity `lam`."""
    rng = np.random.default_rng(seed)
    return rng.choice(lam.shape[0], size=count, p=lam)


def gather_rows(catalog, ids: np.ndarray) -> np.ndarray:
    """catalog[ids] as a host array: requests reach the program from the
    network, as host memory."""

    take = jax.jit(lambda x, i: x[i])
    return np.asarray(take(catalog, jnp.asarray(ids, jnp.int32)))


# -- fetch cost ----------------------------------------------------------------

def fetch_cost(catalog, kth: int = 50, sample: int = 512, seed: int = 0,
               block: int = 64) -> float:
    """c_f := mean squared-L2 distance of the `kth` closest neighbour over
    a seeded sample of catalog rows (the paper's Sec. V-C construction,
    copy of `repro.core.costs.calibrate_fetch_cost`), in blocks of `block`
    sample rows so that no (sample, N) matrix is held at once."""

    n = catalog.shape[0]
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def kth_dist(x):
        idx = jax.random.choice(jax.random.PRNGKey(seed), n,
                                shape=(min(sample, n),), replace=False)
        q = x[idx].reshape(-1, block, x.shape[1])
        xn = jnp.sum(x * x, axis=1)

        def one(qb):
            qn = jnp.sum(qb * qb, axis=1, keepdims=True)
            d = jnp.maximum(qn - 2.0 * jnp.matmul(qb, x.T, precision=hi)
                            + xn[None, :], 0.0)
            neg, _ = jax.lax.top_k(-d, kth + 1)
            return -neg[:, kth]

        return jnp.mean(jax.lax.map(one, q))

    assert min(sample, n) % block == 0, (sample, n, block)
    return float(kth_dist(catalog))


# -- arrivals ------------------------------------------------------------------

def poisson_gaps(rate_rps: float, count: int, seed: int) -> np.ndarray:
    """`count` exponential inter-arrival gaps in milliseconds at `rate_rps`
    (the draw of `repro.serve.arrivals`' poisson kind: keyed by
    SeedSequence((seed, ARRIVAL_TAG)))."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, ARRIVAL_TAG)))
    return rng.exponential(1.0, size=count) / (rate_rps / 1e3)


def poisson_schedule(rate_rps: float, seconds: float, seed: int,
                     multiple: int = 1, order_seed: int | None = None
                     ) -> np.ndarray:
    """Due times in seconds of an open-loop Poisson stream over a window of
    `seconds`, conditioned on its count: exactly round(rate * seconds)
    arrivals (rounded to a multiple of `multiple`, so batches formed by
    size are all full), the last one at the window's end.  Given their
    count, Poisson arrivals are uniform order statistics, which is what
    rescaling the cumulative exponential gaps gives.

    With `order_seed`, the gaps drawn from `seed` are cut into runs of
    `multiple` (one batch's worth each) and the runs are put in an order
    drawn from `order_seed`: every order seed then gets the same set of
    arrivals, and of batch fill times, in another order."""
    count = max(multiple, int(round(rate_rps * seconds / multiple)) * multiple)
    gaps = poisson_gaps(rate_rps, count, seed)
    if order_seed is not None:
        runs = gaps.reshape(-1, multiple)
        order = np.random.default_rng(order_seed).permutation(runs.shape[0])
        gaps = runs[order].ravel()
    t = np.cumsum(gaps)
    return t * (seconds / t[-1])

