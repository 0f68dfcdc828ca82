"""Reference remote scan of an IVF-Flat index: probe the `nprobe` lists
whose centroids are nearest to the request, then the exact top-k of the
rows in those lists.

The centroids are the index's parameters and are taken from the program's
index, like its `nlist`.  Its lists are taken too, but only after
`verify` has checked them against the catalog: every row in exactly one
list, and that list's centroid the nearest one to the row, up to a tie.
Probing and scanning are the reference's own."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import EPS32, TIE_ULPS, block_rows, diff_dists, sq_dists

ASSIGN_ROWS = 1 << 15


@partial(jax.jit, static_argnames=("precision",))
def _nearest(catalog, centroids, own, precision: str):
    """Per row: its nearest centroid, the squared distance to it, and the
    squared distance to the centroid of list `own` of the row."""
    n, dim = catalog.shape
    rows = block_rows(n, ASSIGN_ROWS)
    blocks = catalog.reshape(n // rows, rows, dim)
    owns = own.reshape(n // rows, rows)

    def one(args):
        blk, o = args
        d = sq_dists(blk, centroids, precision)
        mine = jnp.take_along_axis(d, jnp.clip(o, 0, None)[:, None], 1)
        return jnp.argmin(d, axis=1), jnp.min(d, axis=1), mine[:, 0]

    arg, best, mine = jax.lax.map(one, (blocks, owns))
    return arg.reshape(n), best.reshape(n), mine.reshape(n)


@partial(jax.jit, static_argnames=("k", "nprobe", "precision"))
def _scan(q, catalog, centroids, invlists, k: int, nprobe: int,
          precision: str):
    dc = sq_dists(q, centroids, precision)
    negc, probe = jax.lax.top_k(-dc, nprobe + 1)
    cand = invlists[probe[:, :nprobe]].reshape(q.shape[0], -1)

    def one(args):
        qv, ids = args
        d = diff_dists(qv, catalog[jnp.clip(ids, 0, None)], precision)
        d = jnp.where(ids >= 0, d, jnp.inf)
        neg, pos = jax.lax.top_k(-d, k)
        return -neg, jnp.where(jnp.isfinite(neg), ids[pos], -1)

    d, i = jax.lax.map(one, (q, cand))
    return d, i, -negc, probe


class Remote:
    def __init__(self, catalog, spec: dict, index, precision: str):
        self.catalog, self.precision = catalog, precision
        self.nprobe = int(spec["nprobe"])
        self.centroids = jnp.asarray(index.centroids, jnp.float32)
        self.invlists = np.asarray(index.invlists)
        self._lists = jnp.asarray(self.invlists)
        self.cent_norm_max = float(jnp.max(jnp.sum(
            self.centroids * self.centroids, axis=1)))

    def assign(self) -> np.ndarray:
        """The list of each catalog row by this scan's own precision: the
        lists that a program computing in that precision would hold."""
        own = jnp.zeros(self.catalog.shape[0], jnp.int32)
        return np.asarray(_nearest(self.catalog, self.centroids, own,
                                        self.precision)[0])

    def verify(self, own: np.ndarray | None = None) -> dict:
        """`ivf_lists_bad`: catalog rows held by no list or by several, or
        by a list whose centroid is farther than the nearest one beyond a
        tie.  `own` (N,) gives each row's list in place of the program's
        lists (the control's lists)."""
        n = int(self.catalog.shape[0])
        bad = 0
        if own is None:
            ids = self.invlists[self.invlists >= 0]
            count = np.bincount(ids[ids < n], minlength=n)
            bad = int((count != 1).sum()) + int((ids >= n).sum())
            own = np.full(n, -1, np.int32)
            lists = np.nonzero(self.invlists >= 0)[0]
            own[ids[ids < n]] = lists[ids < n]
        _, best, mine = _nearest(self.catalog, self.centroids,
                                      jnp.asarray(own, jnp.int32), "float32")
        best, mine = np.asarray(best, np.float64), np.asarray(mine, np.float64)
        norms = np.asarray(jnp.sum(self.catalog * self.catalog, axis=1))
        tol = TIE_ULPS * EPS32 * (norms + self.cent_norm_max)
        bad += int(((mine - best > tol) & (own >= 0)).sum())
        return {"ivf_lists_bad": bad}

    def topk(self, q, k: int, tol: np.ndarray):
        d, i, dc, probe = _scan(q, self.catalog, self.centroids,
                                     self._lists, k, self.nprobe,
                                     self.precision)
        dc, probe = np.asarray(dc), np.asarray(probe)
        qn = np.asarray((q * q).sum(axis=1), np.float64)
        ctol = TIE_ULPS * EPS32 * (qn + self.cent_norm_max)
        tied = (dc[:, self.nprobe] - dc[:, self.nprobe - 1]) <= ctol
        rows = [self.invlists[probe[b, self.nprobe - 1:]].reshape(-1)
                if tied[b] else np.zeros(0, np.int64)
                for b in range(q.shape[0])]
        return np.asarray(d), np.asarray(i), tied, rows


def probed_rows(reqs: np.ndarray, index, nprobe: int, batch: int) -> float:
    """Mean number of catalog rows in the lists that a batch of `batch`
    requests probes, over the batches of `reqs`: the rows an IVF scan
    reads."""
    sizes = jnp.asarray((np.asarray(index.invlists) >= 0).sum(axis=1))

    @jax.jit
    def rows(q, centroids):
        _, probe = jax.lax.top_k(-sq_dists(q, centroids, "float32"), nprobe)
        return jnp.sum(sizes[probe])

    cents = jnp.asarray(index.centroids, jnp.float32)
    total = 0.0
    for s in range(0, reqs.shape[0], 4096):
        total += float(rows(jnp.asarray(reqs[s:s + 4096]), cents))
    return total / (reqs.shape[0] / batch)
