"""Reference remote scan of a flat (exact) index: the top-k catalog rows
of each request by squared L2, scanned in blocks of catalog rows so that
no (B, N) matrix is held at once."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import block_rows, sq_dists

BLOCK_ROWS = 1 << 16


@partial(jax.jit, static_argnames=("k", "rows", "precision"))
def _scan(q, catalog, k: int, rows: int, precision: str):
    b = q.shape[0]

    def body(j, carry):
        best_d, best_i = carry
        blk = jax.lax.dynamic_slice_in_dim(catalog, j * rows, rows, 0)
        neg, pos = jax.lax.top_k(-sq_dists(q, blk, precision), k)
        cat_d = jnp.concatenate([best_d, -neg], axis=1)
        cat_i = jnp.concatenate([best_i, pos + j * rows], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return -neg, jnp.take_along_axis(cat_i, pos, axis=1)

    init = (jnp.full((b, k), jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, catalog.shape[0] // rows, body, init)


class Remote:
    """Exact scan over the whole catalog; a flat index has no structure to
    check, and its lists end in a tie only at their k-th row, which the
    caller checks."""

    def __init__(self, catalog, spec: dict, index, precision: str):
        self.catalog, self.precision = catalog, precision
        self.rows = block_rows(int(catalog.shape[0]), BLOCK_ROWS)

    def verify(self) -> dict:
        return {}

    def topk(self, q, k: int, tol: np.ndarray):
        d, i = _scan(q, self.catalog, k, self.rows, self.precision)
        b = q.shape[0]
        return (np.asarray(d), np.asarray(i), np.zeros(b, bool),
                [np.zeros(0, np.int64)] * b)
