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


def _assign(data, cents):
    return jnp.argmin(ops.pairwise_l2_xla(data, cents), axis=1)


def _assign_and_sum(data, cents, k: int):
    """(assignments (n,), per-centroid row sums (k, d), counts (k, 1)),
    one chunk of at most CHUNK rows at a time."""
    n, dim = data.shape
    chunk = min(n, CHUNK)
    nc = -(-n // chunk)
    chunks = jnp.pad(data, ((0, nc * chunk - n), (0, 0))).reshape(
        nc, chunk, dim)
    rows = jnp.arange(nc * chunk).reshape(nc, chunk)

    def body(carry, xs):
        sums, counts = carry
        part, row = xs
        assign = _assign(part, cents)
        onehot = jax.nn.one_hot(jnp.where(row < n, assign, k), k,
                                dtype=data.dtype)              # pad rows: 0
        sums = sums + jnp.matmul(onehot.T, part,
                                 precision=jax.lax.Precision.HIGHEST)
        return (sums, counts + jnp.sum(onehot, axis=0)[:, None]), assign

    init = (jnp.zeros((k, dim), data.dtype), jnp.zeros((k, 1), data.dtype))
    (sums, counts), assign = jax.lax.scan(body, init, (chunks, rows))
    return assign.reshape(-1)[:n], sums, counts


@partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(key: jax.Array, data: jax.Array, k: int, iters: int = 12):
    """Returns (centroids (k, d), assignments (n,))."""
    n = data.shape[0]
    init_idx = jax.random.choice(key, n, shape=(k,), replace=False)
    cents = data[init_idx]

    def body(_, cents):
        _, sums, counts = _assign_and_sum(data, cents, k)
        new = sums / jnp.maximum(counts, 1.0)
        # keep the old centroid for empty clusters
        return jnp.where(counts > 0, new, cents)

    cents = jax.lax.fori_loop(0, iters, body, cents)
    assign, _, _ = _assign_and_sum(data, cents, k)
    return cents, assign
