"""NSW graph index — the HNSW-style local-catalog index, TPU-adapted.

HNSW's pointer-chasing search is hostile to jit; we keep the navigable-
small-world *semantics* (greedy beam search over a neighbour graph, entry
point = medoid) but store the graph as a dense (N, degree) table and run a
fixed-width, fixed-step beam with masked gathers (DESIGN.md §3).  Recall is
controlled by (degree, beam, steps, expand) just like HNSW's (M, efSearch).

Batched-first (DESIGN.md §6): `query` carries the whole mini-batch's beams
as (B, beam) arrays through one fori_loop — per step it expands the
`expand` best unexpanded beam entries of every query at once (top-k,
gather, dedup and re-top-k all along axis 1), so the MXU sees (B, E·deg)
tiles instead of a per-query scalar loop.

Build: exact kNN graph + long-range shortcuts (random far edges), the
classic NSW construction, done once in numpy at setup.  (Reverse-edge
symmetrization of the shortcut slots was measured and REFUTED: replacing
the random far edges with incoming-kNN edges drops amazon-trace recall
0.84 -> 0.75 — the shortcuts are what lets the beam cross clusters.)

Mutable catalog (DESIGN.md §10): `add` is the classic incremental NSW
insertion — the new node's out-edges are its current beam-search kNN plus
random shortcut edges, and a couple of its neighbours each give one edge
slot back to the new node so it becomes reachable; `remove` tombstones
(dead nodes stay routable until refresh, the standard mark-deleted HNSW
semantics, but can never surface as answers); `refresh` rebuilds the graph
and the entry points over the live rows.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.base import (MutableRows, _flat_set, _rows_write,
                              arrays_bytes, check_finite_queries, pad_ids,
                              pad_rows, run_device, track_jit)
from repro.kernels import ops


def build_nsw_graph(emb: np.ndarray, degree: int = 16, shortcuts: int = 2,
                    seed: int = 0, chunk: int = 1024) -> np.ndarray:
    n = emb.shape[0]
    rng = np.random.default_rng(seed)
    knn = min(degree - shortcuts, n - 1)
    graph = np.empty((n, degree), np.int32)
    cn = (emb ** 2).sum(1)
    for s in range(0, n, chunk):
        q = emb[s:s + chunk]
        d = (q ** 2).sum(1)[:, None] - 2 * q @ emb.T + cn[None]
        np.fill_diagonal(d[:, s:s + q.shape[0]], np.inf)
        part = np.argpartition(d, knn, axis=1)[:, :knn]
        graph[s:s + chunk, :knn] = part
    graph[:, knn:] = rng.integers(0, n, (n, degree - knn))
    return graph


@track_jit("nsw_query")
@partial(jax.jit, static_argnames=("k", "beam", "steps", "expand", "masked"))
def _nsw_query(q, emb, graph, entry_points, valid, k: int, beam: int,
               steps: int, expand: int, masked: bool):
    """(B, d) -> (dists (B, k), ids (B, k)); ids = -1 on underflow.

    `masked` threads the tombstone mask: dead nodes keep routing the beam
    (their edges are intact) but carry dist = +inf, so they are expanded
    last and can never surface as answers."""
    q = jnp.atleast_2d(q)
    b = q.shape[0]
    deg = graph.shape[1]
    e = expand
    rows = jnp.arange(b)[:, None]

    seeds = jnp.resize(entry_points, (beam,))            # (beam,)
    beam_ids = jnp.broadcast_to(seeds[None, :], (b, beam))
    beam_d = jnp.sum(
        (emb[seeds][None, :, :] - q[:, None, :]) ** 2, -1)
    if masked:
        beam_d = jnp.where(valid[seeds][None, :], beam_d, jnp.inf)
    # mark duplicate seeds so they are not re-expanded
    nentry = entry_points.shape[0]
    dup0 = jnp.concatenate(
        [jnp.zeros((nentry,), bool), jnp.ones((beam - nentry,), bool)]
    ) if beam > nentry else jnp.zeros((beam,), bool)
    beam_d = jnp.where(dup0[None, :], jnp.inf, beam_d)
    expanded = jnp.broadcast_to(dup0[None, :], (b, beam))

    def step(_, carry):
        ids, dist, exp = carry                          # all (b, beam)
        # expand the e best unexpanded beam entries of every query
        cand_d = jnp.where(exp, jnp.inf, dist)
        _, sel = jax.lax.top_k(-cand_d, e)                    # (b, e)
        exp = exp.at[rows, sel].set(True)
        sel_ids = jnp.take_along_axis(ids, sel, axis=1)
        nbrs = graph[sel_ids].reshape(b, e * deg)
        nd = jnp.sum(
            (emb[nbrs] - q[:, None, :]) ** 2, axis=-1)
        if masked:
            nd = jnp.where(valid[nbrs], nd, jnp.inf)
        all_ids = jnp.concatenate([ids, nbrs], axis=1)
        all_d = jnp.concatenate([dist, nd], axis=1)
        all_exp = jnp.concatenate(
            [exp, jnp.zeros((b, e * deg), bool)], axis=1)
        # dedup: keep the first occurrence of each id (sorted by id,
        # mark repeats with +inf) then take the best `beam`
        order = jnp.argsort(all_ids, axis=1)
        sid = jnp.take_along_axis(all_ids, order, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((b, 1), bool), sid[:, 1:] == sid[:, :-1]], axis=1)
        dupmask = jnp.zeros_like(dup).at[rows, order].set(dup)
        all_d = jnp.where(dupmask, jnp.inf, all_d)
        neg, pos = jax.lax.top_k(-all_d, beam)
        return (jnp.take_along_axis(all_ids, pos, axis=1), -neg,
                jnp.take_along_axis(all_exp, pos, axis=1))

    ids, dist, _ = jax.lax.fori_loop(
        0, steps, step, (beam_ids, beam_d, expanded))
    # the beam can only ever hold `beam` candidates: k beyond it is
    # structural underflow — pad with the protocol's -1 / +inf slots
    # (so e.g. cfg.c_remote > beam degrades instead of crashing)
    kk = min(k, beam)
    neg, pos = jax.lax.top_k(-dist, kk)
    out_ids = jnp.take_along_axis(ids, pos, axis=1)
    out_d = -neg
    out_ids = jnp.where(jnp.isfinite(neg), out_ids, -1)
    if kk < k:
        out_d = jnp.pad(out_d, ((0, 0), (0, k - kk)),
                        constant_values=jnp.inf)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)),
                          constant_values=-1)
    return out_d, out_ids


class NSWIndex(MutableRows):
    exact_distances = True  # candidates scored with exact L2
    # answer-cache capability flags (repro.serve.answer_cache): insertion
    # rewires existing nodes' adjacency (reverse links) and the first
    # tombstone flips beam masking, so mutations can change answers far
    # from the mutated rows — the cache must flush, not radius-check.
    answer_unstable_add = True
    answer_unstable_remove = True

    # how many of a new node's neighbours donate one edge slot back to it
    # (the incremental insertion's bidirectional-link half)
    _REV_LINKS = 2

    def __init__(self, embeddings, degree: int = 16, beam: int = 32,
                 steps: int = 12, expand: int = 2, seed: int = 0):
        self._init_rows(embeddings)
        self.beam, self.steps, self.degree = beam, steps, degree
        self.expand = max(1, min(expand, beam))
        self.seed = seed
        self._rng = np.random.default_rng(seed + 1)  # insertion randomness
        self._build_structures()

    def _compute_structures(self):
        """Rebuild graph + entry points over the live rows (restores the
        build-quality kNN graph after incremental drift / deletions).
        Pure — serving keeps the stale graph until `_install_structures`."""
        live = self.live_rows()
        emb_np = np.asarray(self.embeddings)[live]
        graph_live = build_nsw_graph(emb_np, self.degree, seed=self.seed)
        graph = np.zeros((self.capacity, self.degree), np.int32)
        graph[live] = live[graph_live]               # remap to slab row ids
        # entry points = catalog points nearest to k-means centroids: the
        # static-shape stand-in for HNSW's upper navigation layers — ensures
        # every density mode seeds the beam (DESIGN.md §3).
        from repro.index.kmeans import train

        nentry = min(self.beam, len(live))
        emb_live = (self.embeddings if len(live) == self.capacity
                    else self.embeddings[jnp.asarray(live)])
        cents = train(jax.random.PRNGKey(self.seed), emb_live, nentry)
        d2 = ops.pairwise_l2_xla(cents, emb_live)
        near = np.asarray(jnp.argmin(d2, axis=1))
        return (jnp.asarray(graph), jnp.asarray(live[near], jnp.int32))

    def _install_structures(self, structures) -> None:
        self.graph, self.entry_points = structures

    # -- mutation -----------------------------------------------------------

    def add(self, vectors) -> np.ndarray:
        """Incremental NSW insertion: out-edges = beam-search kNN over the
        pre-insert graph + random shortcut edges; `_REV_LINKS` neighbours
        each donate one edge slot back so the new nodes become reachable.

        Device-resident fast path: the pre-insert kNN query runs on the
        width-padded batch (fixed shapes, no per-batch-size retrace), the
        new out-rows are assembled on the host and land in the (cap, deg)
        edge table via a donated contiguous row write, and the reverse
        links via one donated flat scatter — no numpy graph master."""
        vec_np = np.atleast_2d(np.asarray(vectors, np.float32))
        live_before = self.live_rows()
        # neighbours from the *current* structures (the classic sequential
        # insertion queries the graph as built so far; querying once for
        # the whole batch keeps in-batch nodes unlinked to each other)
        knn = min(self.degree - 2, max(len(live_before) - 1, 1))
        b = vec_np.shape[0]
        qpad = pad_rows(vec_np)
        _, nbr = self.query(qpad, knn)
        nbr = np.asarray(nbr)[:b]                             # (B, knn)
        ids = self._append_rows(vec_np)
        if self.graph.shape[0] < self.capacity:               # slab grew
            self.graph = jnp.pad(
                self.graph,
                ((0, self.capacity - self.graph.shape[0]), (0, 0)))
        # padded lanes land on unused slots past the append (the slab
        # keeps a full write window of headroom); zeros are unreachable
        rows = np.zeros((qpad.shape[0], self.degree), np.int32)
        rev_flat: list[int] = []            # reverse-link scatter entries
        rev_vals: list[int] = []
        for row, (i, nb) in enumerate(zip(ids, nbr)):
            nb = nb[nb >= 0]
            if len(nb) == 0:  # first-ever node: all self-loops
                rows[row] = i
                continue
            out = np.full((self.degree,), i, np.int32)        # self-loop pad
            out[:len(nb)] = nb
            # shortcut slots: random live nodes (long-range edges, same
            # role as the build-time random far edges)
            n_short = self.degree - len(nb)
            if n_short > 0 and len(live_before):
                out[len(nb):] = self._rng.choice(live_before, size=n_short)
            rows[row] = out
            # reverse half: a few neighbours each give one slot back
            for j in nb[:self._REV_LINKS]:
                slot = int(self._rng.integers(self.degree))
                rev_flat.append(int(j) * self.degree + slot)
                rev_vals.append(int(i))
        self.graph = run_device(_rows_write, self.graph, jnp.asarray(rows),
                                np.int32(ids[0]))
        if rev_flat:
            oob = self.graph.size
            self.graph = run_device(
                _flat_set, self.graph,
                pad_ids(np.asarray(rev_flat, np.int32), oob),
                pad_ids(np.asarray(rev_vals, np.int32), -1))
        return ids

    # -- queries ------------------------------------------------------------

    def memory_bytes(self) -> int:
        return arrays_bytes(self.embeddings, self.graph, self.entry_points,
                            self.valid)

    def query(self, q: jax.Array, k: int):
        check_finite_queries(q, "NSWIndex.query")
        # dead nodes keep routing until refresh (mark-deleted semantics),
        # so the mask is needed as soon as any row is tombstoned; unlinked
        # slab rows beyond n_slots are unreachable (no in-edges).
        return _nsw_query(q, self.embeddings, self.graph, self.entry_points,
                          self.valid, k, self.beam, self.steps, self.expand,
                          masked=self._live != self._n_slots)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other
