"""Cost model of the paper (Sec. II / IV-A).

Objects and requests are embeddings in R^d.  The *dissimilarity cost*
c_d(r, o) is a distance in that space (squared Euclidean by default — the
metric used for both SIFT1M and the Amazon trace in Sec. V-C).  Fetching an
object from the remote server adds the *fetching cost* c_f.  All costs are
additive and mutually comparable (paper's main cost assumption).

The *augmented catalog* (Sec. IV-D) gives every object i two copies:
  - the local copy  i      with cost c(r, i)   = c_d(r, i)
  - the remote copy i + N  with cost c(r, i+N) = c_d(r, i) + c_f
with the coupling constraint x_{i+N} = 1 - x_i.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

# Finite stand-in for +inf so that cost differences never produce NaNs.
BIG_COST = jnp.float32(1e9)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Dissimilarity + fetching cost specification."""

    c_f: float
    metric: str = "sqeuclidean"  # 'sqeuclidean' | 'euclidean' | 'ip'

    def dissimilarity(self, queries: jax.Array, points: jax.Array) -> jax.Array:
        return pairwise_dissimilarity(queries, points, self.metric)


def pairwise_dissimilarity(
    queries: jax.Array, points: jax.Array, metric: str = "sqeuclidean"
) -> jax.Array:
    """(Q, d) x (N, d) -> (Q, N) dissimilarity matrix.

    Computed as ||q||^2 - 2 q.x + ||x||^2 so the contraction runs on the MXU.
    A Pallas-kernelised version lives in repro.kernels.ops.pairwise_l2.
    """
    queries = jnp.atleast_2d(queries)
    # f32 products: on the TPU the default is one bf16 MXU pass, whose
    # error reorders near neighbours
    dots = jnp.matmul(queries, points.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return -dots
    qn = jnp.sum(queries * queries, axis=-1, keepdims=True)
    pn = jnp.sum(points * points, axis=-1)[None, :]
    sq = jnp.maximum(qn - 2.0 * dots + pn, 0.0)
    if metric == "euclidean":
        return jnp.sqrt(sq)
    if metric == "sqeuclidean":
        return sq
    raise ValueError(f"unknown metric {metric!r}")


@partial(jax.jit, static_argnames=("kth", "sample"))
def calibrate_fetch_cost(
    catalog: jax.Array, *, kth: int = 50, sample: int = 512, seed: int = 0
) -> jax.Array:
    """c_f := average distance of the `kth` closest neighbour in the catalog.

    Exactly the paper's Sec. V-C construction ("we set c_f equal to the
    average distance of the 50-th closest neighbor in the catalog N"),
    estimated over a random sample of catalog points.
    """
    n = catalog.shape[0]
    key = jax.random.PRNGKey(seed)
    idx = jax.random.choice(key, n, shape=(min(sample, n),), replace=False)
    d = pairwise_dissimilarity(catalog[idx], catalog)
    # kth+1 because the point itself is at distance 0.
    neg_top, _ = jax.lax.top_k(-d, kth + 1)
    return jnp.mean(-neg_top[:, kth])
