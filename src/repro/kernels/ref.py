"""Pure-jnp oracles for every Pallas kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_l2_ref(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(Q, D), (N, D) -> (Q, N) squared euclidean distances, float32."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    xn = jnp.sum(x * x, axis=-1)[None, :]
    dots = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn - 2.0 * dots + xn, 0.0)


def pq_adc_ref(lut: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Asymmetric-distance computation.

    lut:   (Q, M, C) per-query, per-subspace distance tables (float32)
    codes: (N, M)    PQ codes, integer in [0, C)
    out:   (Q, N)    dist[q, n] = sum_m lut[q, m, codes[n, m]]
    """
    q, m, c = lut.shape
    onehot = jnp.equal(codes[..., None], jnp.arange(c)[None, None, :])
    return jnp.einsum("qmc,nmc->qn", lut.astype(jnp.float32),
                      onehot.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def l2_topk_ref(q: jnp.ndarray, x: jnp.ndarray, k: int, valid=None):
    """Exact top-k smallest distances: returns (dists (Q,k), ids (Q,k)).

    `valid` (N,) bool marks live catalog rows (mutable-catalog tombstone
    mask, DESIGN.md §10): masked rows never surface, and queries with
    fewer than k live rows underflow as dist = +inf, id = -1.  With
    valid=None the output is exactly the unmasked scan (no -1s possible).
    """
    d = pairwise_l2_ref(q, x)
    if valid is None:
        neg, idx = jax.lax.top_k(-d, k)
        return -neg, idx
    d = jnp.where(valid[None, :], d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, jnp.where(jnp.isfinite(neg), idx, -1)


def ivf_scan_ref(q: jnp.ndarray, x: jnp.ndarray, cand: jnp.ndarray, k: int,
                 valid=None):
    """Gathered-candidate top-k: the oracle for kernels.ivf_scan.

    q (B, d), x (N, d), cand (B, P) int32 with -1 marking invalid slots.
    Returns (dists (B, k), ids (B, k)); ids = -1 (dist = +inf) when a query
    has fewer than k valid candidates — including the structural case
    k > P (a narrower slab than requested underflows rather than crashing,
    matching the Pallas path, whose slab is tile-padded).

    `valid` (N,) bool is the mutable-catalog tombstone mask (DESIGN.md
    §10): candidate ids whose row is tombstoned are treated as -1 slots,
    so a removed object can never surface from a stale inverted list.
    """
    q = q.astype(jnp.float32)
    if valid is not None:
        cand = jnp.where(
            (cand >= 0) & valid[jnp.clip(cand, 0, x.shape[0] - 1)], cand, -1)
    x = x.astype(jnp.float32)
    embs = x[jnp.clip(cand, 0, None)]                  # (B, P, d)
    diff = embs - q[:, None, :]
    d = jnp.sum(diff * diff, axis=-1)
    d = jnp.where(cand >= 0, d, jnp.inf)
    kk = min(k, cand.shape[1])
    neg, pos = jax.lax.top_k(-d, kk)
    ids = jnp.take_along_axis(cand, pos, axis=1)
    ids = jnp.where(jnp.isfinite(neg), ids, -1)
    if kk < k:
        ids = jnp.pad(ids, ((0, 0), (0, k - kk)), constant_values=-1)
        neg = jnp.pad(neg, ((0, 0), (0, k - kk)),
                      constant_values=-jnp.inf)
    return -neg, ids
