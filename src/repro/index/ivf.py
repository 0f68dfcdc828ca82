"""IVF-Flat index: coarse k-means partition + exact scan of probed lists.

JAX/TPU adaptation of the FAISS inverted-file layout: inverted lists are a
dense (nlist, cap) id table padded with -1, so probing is a static gather —
no pointer chasing, shapes jit/shard cleanly (the table shards row-wise over
the `model` mesh axis at scale).

Mutable catalog (DESIGN.md §10): `add` assigns new rows to their nearest
*existing* centroid and appends to that inverted list (per-table capacity
doubling when a list fills); `remove` tombstones rows — stale list entries
are folded into the scan's -1 invalid-slot convention at query time via the
validity mask; `refresh` re-trains the coarse quantizer and rebuilds the
lists over the live rows only (row ids stay stable).  The quantizer drifts
between refreshes (new objects are binned by stale centroids), which is
exactly the recall-vs-refresh-cost trade-off the churn bench measures.

Sharded serving (DESIGN.md §15): `ivf_sharded` splits the slab row-wise
over the mesh's `model` axis and each shard scans its own probed lists
inside the fused sharded step.  That sharded *structure* is still
immutable — online mutation on a mesh serves through the exact masked
scan instead (`AcaiCache(mesh=...)` with `index=None`); teaching the
sharded inverted lists to accept owner-routed appends is the remaining
ROADMAP item, not a driver or policy limitation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.base import (MutableRows, _flat_set, arrays_bytes,
                              check_finite_queries, pad_ids, pad_rows,
                              run_device, track_jit)
from repro.index.kmeans import nearest, nearest_centroid, train
from repro.kernels import ops


def build_invlists(assign: np.ndarray, nlist: int, cap: int | None = None):
    """Dense padded inverted lists from an assignment vector: list `a`
    holds the ids assigned to it in ascending order, and a list longer
    than `cap` keeps its `cap` lowest ids."""
    assign = np.asarray(assign)
    counts = np.bincount(assign, minlength=nlist)
    cap = int(counts.max()) if cap is None else cap
    order = np.argsort(assign, kind="stable")    # by list, then by id
    col = np.arange(order.shape[0]) - np.repeat(np.cumsum(counts) - counts,
                                                 counts)
    keep = col < cap
    table = np.full((nlist, cap), -1, np.int32)
    table[assign[order[keep]], col[keep]] = order[keep]
    return table


def invlist_positions(cursor: np.ndarray, assign: np.ndarray) -> np.ndarray:
    """Destination column of each appended id in its inverted list (the
    cursor plus the id's rank among same-list ids earlier in the batch).
    Host-side bookkeeping only — the actual write is a donated device
    scatter.  Advances `cursor` in place."""
    pos = np.empty(assign.shape[0], np.int32)
    for j, a in enumerate(assign):
        pos[j] = cursor[a]
        cursor[a] += 1
    return pos


def invlist_device_append(invlists: jax.Array, cursor: np.ndarray,
                          assign: np.ndarray, ids: np.ndarray) -> jax.Array:
    """Append `ids` to their assigned lists in the device-resident
    (nlist, cols) table: host cursor bookkeeping plus one donated flat
    scatter (padded lanes carry an out-of-range flat index and are
    dropped).  A full list doubles the table column-wise — a rare
    reallocation, warmed away like slab growth.  Returns the new table;
    `cursor` is advanced in place."""
    counts = np.bincount(assign, minlength=cursor.shape[0])
    need = int((cursor + counts).max())
    cols = invlists.shape[1]
    if need > cols:
        cols = max(2 * cols, need)
        invlists = jnp.pad(invlists,
                           ((0, 0), (0, cols - invlists.shape[1])),
                           constant_values=-1)
    pos = invlist_positions(cursor, assign)
    oob = invlists.size
    assert oob < np.iinfo(np.int32).max, "invlist tensor exceeds int32"
    flat = (assign.astype(np.int64) * cols + pos).astype(np.int32)
    return run_device(_flat_set, invlists, pad_ids(flat, oob),
                      pad_ids(ids, -1))


@track_jit("ivf_assign")
@jax.jit
def _assign_lists(vecs: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest existing centroid per (padded) incoming row, by the rule
    the build assigns with (`kmeans.nearest_centroid`)."""
    return nearest_centroid(vecs, centroids)


def ivf_probe(q, centroids, invlists, nprobe: int):
    """(B, d) -> (B, nprobe * cap) candidate ids, -1 in padded slots: the
    table rows of the `nprobe` lists whose centroids are nearest to each
    request, under the `acai.ivf_probe` scope."""
    with jax.named_scope("acai.ivf_probe"):
        dc = ops.pairwise_l2_xla(q, centroids)          # (B, nlist)
        _, probe = jax.lax.top_k(-dc, nprobe)            # (B, nprobe)
        return invlists[probe].reshape(q.shape[0], -1)


@track_jit("ivf_query")
@partial(jax.jit, static_argnames=("k", "nprobe", "masked"))
def _ivf_query(q, emb, centroids, invlists, valid, k: int, nprobe: int,
               masked: bool):
    """(B, d) -> (dists (B, k), ids (B, k)); ids = -1 on underflow.

    The probed lists go through the fused gather+L2+top-k scan
    (repro.kernels.ivf_scan on TPU, its XLA oracle elsewhere), so the
    (B, P, d) gathered embeddings never materialise in HBM.  `masked`
    threads the tombstone mask through the scan (fresh builds skip it and
    stay bitwise identical to the static-catalog path)."""
    q = jnp.atleast_2d(q)
    cand = ivf_probe(q, centroids, invlists, nprobe)
    return ops.ivf_scan_auto(q, emb, cand, k, valid if masked else None)


class IVFFlatIndex(MutableRows):
    exact_distances = True  # probed lists are scanned with exact L2

    def __init__(
        self,
        embeddings,
        nlist: int = 64,
        nprobe: int = 8,
        train_iters: int = 12,
        seed: int = 0,
    ):
        self._init_rows(embeddings)
        self.nlist, self.nprobe = nlist, nprobe
        self.train_iters, self.seed = train_iters, seed
        self._build_structures()

    # -- structure (re)build ------------------------------------------------

    def _compute_structures(self):
        """(Re-)train the coarse quantizer and lists over the live rows.

        Row ids are stable: the k-means/table build runs over the live
        rows in slab order and the resulting local ids are remapped back
        to slab ids, so a refreshed index answers exactly like a fresh
        build on the live rows (modulo that id remap).  Pure — the live
        structures keep serving until `_install_structures` swaps the new
        bundle in (the double-buffered refresh of DESIGN.md §14)."""
        live = self.live_rows()
        n_live = len(live)
        emb_live = (self.embeddings if n_live == self.capacity
                    else self.embeddings[jnp.asarray(live)])
        nlist = min(self.nlist, max(n_live, 1))
        key = jax.random.PRNGKey(self.seed)
        with jax.profiler.TraceAnnotation("ivf.kmeans"):
            centroids = jax.block_until_ready(
                train(key, emb_live, nlist, self.train_iters))
        with jax.profiler.TraceAnnotation("ivf.assign"):
            assign = np.asarray(nearest(emb_live, centroids))
        with jax.profiler.TraceAnnotation("ivf.lists"):
            table = build_invlists(assign, nlist)
        if n_live != self.capacity:  # remap local ids -> slab row ids
            table = np.where(table >= 0, live[np.clip(table, 0, None)], -1)
        cursor = (table >= 0).sum(axis=1).astype(np.int32)
        return (centroids, jnp.asarray(table, jnp.int32), cursor)

    def _install_structures(self, structures) -> None:
        self.centroids, self.invlists, self._cursor = structures

    # -- mutation -----------------------------------------------------------

    def add(self, vectors) -> np.ndarray:
        """Append rows and bin them by the *current* (possibly stale)
        coarse quantizer — FAISS's add-time behaviour.

        Device-resident fast path: assignment runs as a tracked jit on the
        width-padded incoming batch, destination columns are host cursor
        bookkeeping, and the ids land in the (nlist, cols) table via one
        donated flat scatter — no numpy table master, no full re-upload.
        A full list still doubles the table column-wise (a rare
        reallocation, warmed away like slab growth)."""
        vec_np = np.asarray(vectors, np.float32)
        ids = self._append_rows(vec_np)
        b = ids.shape[0]
        assign = np.asarray(run_device(
            _assign_lists, pad_rows(vec_np), self.centroids))[:b]
        self.invlists = invlist_device_append(self.invlists, self._cursor,
                                              assign, ids)
        return ids

    # -- queries ------------------------------------------------------------

    def memory_bytes(self) -> int:
        return arrays_bytes(self.embeddings, self.centroids, self.invlists,
                            self.valid)

    def list_stats(self) -> dict:
        """The inverted-list table's shape, from host bookkeeping (no
        device read): `nlist`, the table width `cap`, the mean and largest
        list, the empty lists, and `padded_share`, the share of the table's
        slots that hold no id (1 - rows / (nlist * cap)); `ivf_scan` walks
        a padded slot but issues no DMA for it."""
        sizes = self._cursor
        nlist, cap = self.invlists.shape
        return {"nlist": int(nlist), "cap": int(cap),
                "mean_list": float(sizes.mean()),
                "max_list": int(sizes.max()),
                "empty_lists": int((sizes == 0).sum()),
                "padded_share": 1.0 - float(sizes.sum()) / (nlist * cap)}

    def query(self, q: jax.Array, k: int):
        check_finite_queries(q, "IVFFlatIndex.query")
        # candidates come from the id tables (never from unused slab rows),
        # so the mask is only needed once a row has been tombstoned
        return _ivf_query(q, self.embeddings, self.centroids, self.invlists,
                          self.valid, k,
                          min(self.nprobe, self.centroids.shape[0]),
                          masked=self._live != self._n_slots)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other
