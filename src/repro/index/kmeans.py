"""Lloyd k-means in JAX (used to train IVF coarse quantizers + PQ codebooks)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops

# Rows per assignment pass.  The (rows, k) distance and one-hot matrices
# are the build's peak memory: at a 1M-row catalog and k = 1024 lists they
# would take 4 GiB each in one pass, so larger catalogs are assigned in
# chunks of this many rows (256 MiB each at k = 1024).
CHUNK = 65536


def _chunks(data):
    """`data` zero-padded to whole chunks of at most CHUNK rows:
    (nc, chunk, d)."""
    n, dim = data.shape
    chunk = min(n, CHUNK)
    nc = -(-n // chunk)
    return jnp.pad(data, ((0, nc * chunk - n), (0, 0))).reshape(
        nc, chunk, dim)


def nearest_centroid(data, cents):
    """Index of each row's nearest centroid by float32 squared distance,
    the lowest index on a tie.

    `jnp.argmin` is not used here: where only its index is consumed, the
    TPU compiler narrows the running minimum it carries to bfloat16, and
    depending on how it tiles the fused distance product a row then lands
    up to half a bfloat16 ulp of its distance (0.03 at a squared distance
    of 10) away from its nearest centroid: about 1% of a 1M x 128 catalog
    against 1,024 centroids, in chunks under `lax.map`, on a TPU v5e.
    `top_k` compares the float32 distances themselves, and picks the same
    index as `argmin` wherever both are exact."""
    return jax.lax.top_k(-ops.pairwise_l2_xla(data, cents), 1)[1][:, 0]


@jax.jit
def nearest(data, cents):
    """`nearest_centroid` of every row, CHUNK rows at a time."""
    n = data.shape[0]
    idx = jax.lax.map(lambda part: nearest_centroid(part, cents),
                      _chunks(data))
    return idx.reshape(-1)[:n]


def _sum_by_centroid(data, cents, k: int):
    """(per-centroid row sums (k, d), counts (k, 1)) of the rows each
    centroid is nearest to, one chunk at a time."""
    n, dim = data.shape
    chunks = _chunks(data)
    rows = jnp.arange(chunks.shape[0] * chunks.shape[1]).reshape(
        chunks.shape[:2])

    def body(carry, xs):
        sums, counts = carry
        part, row = xs
        assign = nearest_centroid(part, cents)
        onehot = jax.nn.one_hot(jnp.where(row < n, assign, k), k,
                                dtype=data.dtype)              # pad rows: 0
        sums = sums + jnp.matmul(onehot.T, part,
                                 precision=jax.lax.Precision.HIGHEST)
        return (sums, counts + jnp.sum(onehot, axis=0)[:, None]), None

    init = (jnp.zeros((k, dim), data.dtype), jnp.zeros((k, 1), data.dtype))
    (sums, counts), _ = jax.lax.scan(body, init, (chunks, rows))
    return sums, counts


@partial(jax.jit, static_argnames=("k", "iters"))
def train(key: jax.Array, data: jax.Array, k: int, iters: int = 12):
    """Centroids (k, d) after `iters` Lloyd iterations from k distinct
    rows drawn with `key`."""
    n = data.shape[0]
    init_idx = jax.random.choice(key, n, shape=(k,), replace=False)
    cents = data[init_idx]

    def body(_, cents):
        sums, counts = _sum_by_centroid(data, cents, k)
        new = sums / jnp.maximum(counts, 1.0)
        # keep the old centroid for empty clusters
        return jnp.where(counts > 0, new, cents)

    return jax.lax.fori_loop(0, iters, body, cents)


def kmeans(key: jax.Array, data: jax.Array, k: int, iters: int = 12):
    """Returns (centroids (k, d), assignments (n,)): each row is assigned
    its nearest returned centroid (`nearest`)."""
    cents = train(key, data, k, iters)
    return cents, nearest(data, cents)
