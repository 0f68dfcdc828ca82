"""Product quantization codec + IVF-PQ index (the paper's remote-catalog
index: ~30 bytes/object à la FAISS IVFPQ, Sec. III).

The ADC scan runs through repro.kernels.ops.pq_adc — the one-hot-matmul TPU
adaptation of the GPU shared-memory gather (DESIGN.md §3).  An optional
exact re-rank of the top candidates (refine factor) recovers recall, which
is standard FAISS practice and what AÇAI needs to estimate true server-side
dissimilarity costs.

Mutable catalog (DESIGN.md §10): `add` is encode-on-insert — new rows are
PQ-coded with the *frozen* codebooks and binned by the stale coarse
quantizer (FAISS add-time semantics); `remove` tombstones (stale list
entries and codes are masked at query time); `refresh` re-trains both the
coarse quantizer and the PQ codebooks over the live rows and re-encodes
them.  Codebook drift between refreshes costs ADC accuracy on inserted
rows — the refine re-rank absorbs most of it, and the churn bench
quantifies the rest.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.base import (MutableRows, _rows_write, arrays_bytes,
                              check_finite_queries, pad_rows, run_device,
                              track_jit)
from repro.index.ivf import (_assign_lists, build_invlists,
                             invlist_device_append, ivf_probe)
from repro.index.kmeans import kmeans, train
from repro.kernels import ops


@track_jit("pq_encode")
@jax.jit
def _pq_encode(data: jax.Array, codebooks: jax.Array) -> jax.Array:
    """(n, d) x (m, ksub, dsub) codebooks -> (n, m) int32 codes.

    Codebooks are a *runtime* argument (not a static self): refresh
    re-trains them without leaving stale compiled entries pinned in the
    jit cache — the long-running churn regime rebuilds codecs repeatedly.
    """
    n, d = data.shape
    m, _, dsub = codebooks.shape
    sub = data.reshape(n, m, dsub).transpose(1, 0, 2)
    d2 = jax.vmap(ops.pairwise_l2_xla)(sub, codebooks)   # (m, n, ksub)
    return jnp.argmin(d2, axis=-1).T.astype(jnp.int32)    # (n, m)


@jax.jit
def _pq_decode(codes: jax.Array, codebooks: jax.Array) -> jax.Array:
    gathered = jax.vmap(lambda cb, c: cb[c], in_axes=(0, 1))(
        codebooks, codes
    )  # (m, n, dsub)
    return gathered.transpose(1, 0, 2).reshape(codes.shape[0], -1)


def _pq_adc_lut(q: jax.Array, codebooks: jax.Array) -> jax.Array:
    """(B, d) -> (B, m, ksub) per-subspace distance tables (traced inside
    _ivfpq_query; codebooks ride as a runtime argument)."""
    b = q.shape[0]
    m, _, dsub = codebooks.shape
    sub = q.reshape(b, m, dsub).transpose(1, 0, 2)        # (m, B, dsub)
    lut = jax.vmap(ops.pairwise_l2_xla)(sub, codebooks)   # (m, B, ksub)
    return lut.transpose(1, 0, 2)


_pq_adc_lut_jit = jax.jit(_pq_adc_lut)


class PQCodec:
    """M sub-spaces x 256-centroid codebooks."""

    def __init__(self, data: jax.Array, m: int = 8, nbits: int = 8,
                 train_iters: int = 12, seed: int = 0):
        n, d = data.shape
        assert d % m == 0, (d, m)
        self.m, self.dsub, self.ksub = m, d // m, 2 ** nbits
        sub = jnp.asarray(data, jnp.float32).reshape(n, m, self.dsub)
        keys = jax.random.split(jax.random.PRNGKey(seed), m)
        ksub = min(self.ksub, n)
        cents = jax.vmap(lambda k, x: train(k, x, ksub, train_iters))(
            keys, sub.transpose(1, 0, 2)
        )
        if ksub < self.ksub:  # pad tiny training sets
            pad = jnp.repeat(cents[:, :1], self.ksub - ksub, axis=1)
            cents = jnp.concatenate([cents, pad], axis=1)
        self.codebooks = cents  # (m, ksub, dsub)

    def encode(self, data: jax.Array) -> jax.Array:
        return _pq_encode(data, self.codebooks)

    def decode(self, codes: jax.Array) -> jax.Array:
        return _pq_decode(codes, self.codebooks)

    def adc_lut(self, q: jax.Array) -> jax.Array:
        """(B, d) -> (B, m, ksub) per-subspace distance tables."""
        return _pq_adc_lut_jit(q, self.codebooks)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


@track_jit("pq_query")
@partial(jax.jit, static_argnames=("k", "nprobe", "refine", "masked"))
def _ivfpq_query(q, emb, centroids, invlists, codes, codebooks, valid,
                 k: int, nprobe: int, refine: int, masked: bool):
    q = jnp.atleast_2d(q)
    b = q.shape[0]
    cand = ivf_probe(q, centroids, invlists, nprobe)    # (B, P)
    if masked:  # tombstoned rows -> the -1 invalid-slot convention
        cand = jnp.where(
            (cand >= 0) & valid[jnp.clip(cand, 0, emb.shape[0] - 1)],
            cand, -1)
    valid_slot = cand >= 0
    safe = jnp.clip(cand, 0, None)

    lut = _pq_adc_lut(q, codebooks)                      # (B, m, ksub)
    gathered = codes[safe]                               # (B, P, m)
    # per-query ADC over its own candidate rows
    d_adc = jax.vmap(lambda l, c: ops.pq_adc(l[None], c)[0])(lut, gathered)
    d_adc = jnp.where(valid_slot, d_adc, jnp.inf)

    if refine and refine > 1:
        r = min(refine * k, d_adc.shape[1])
        neg, pos = jax.lax.top_k(-d_adc, r)              # approx top-r
        rid = jnp.take_along_axis(cand, pos, axis=1)
        rid = jnp.where(jnp.isfinite(neg), rid, -1)
        # exact re-rank through the fused gather+L2+top-k scan (cand was
        # already validity-masked above)
        return ops.ivf_scan_auto(q, emb, rid, k)

    neg, pos = jax.lax.top_k(-d_adc, k)
    ids = jnp.take_along_axis(cand, pos, axis=1)
    return -neg, jnp.where(jnp.isfinite(neg), ids, -1)


class IVFPQIndex(MutableRows):
    """Coarse IVF + PQ-coded residual-free storage + optional exact refine."""

    # answer-cache capability flags (repro.serve.answer_cache): the ADC
    # shortlist is rank-R by *approximate* distance, so an add/remove can
    # move the shortlist boundary and change refined answers that never
    # contained the mutated rows — the cache must flush, not radius-check.
    answer_unstable_add = True
    answer_unstable_remove = True

    def __init__(self, embeddings, nlist: int = 64, nprobe: int = 8,
                 m: int = 8, refine: int = 4, seed: int = 0):
        self._init_rows(embeddings)
        self.nlist, self.nprobe, self.refine = nlist, nprobe, refine
        self.m, self.seed = m, seed
        # with refine the final top-k is exactly re-ranked; without it the
        # returned distances are ADC approximations (re-rank downstream)
        self.exact_distances = bool(refine and refine > 1)
        self._build_structures()

    def _compute_structures(self):
        """(Re-)train quantizer + codebooks and (re-)encode the live rows;
        ids are stable (local build ids remap to slab rows).  Pure — the
        live structures keep serving until `_install_structures`."""
        live = self.live_rows()
        n_live = len(live)
        emb_live = (self.embeddings if n_live == self.capacity
                    else self.embeddings[jnp.asarray(live)])
        nlist = min(self.nlist, max(n_live, 1))
        key = jax.random.PRNGKey(self.seed)
        centroids, assign = kmeans(key, emb_live, nlist)
        table = build_invlists(np.asarray(assign), nlist)
        if n_live != self.capacity:
            table = np.where(table >= 0, live[np.clip(table, 0, None)], -1)
        cursor = (table >= 0).sum(axis=1).astype(np.int32)
        codec = PQCodec(emb_live, m=self.m, seed=self.seed + 1)
        codes_live = codec.encode(emb_live)              # (n_live, m)
        codes = np.zeros((self.capacity, self.m), np.int32)
        codes[live] = np.asarray(codes_live)
        return (centroids, jnp.asarray(table, jnp.int32), cursor, codec,
                jnp.asarray(codes))

    def _install_structures(self, structures) -> None:
        (self.centroids, self.invlists, self._cursor, self.codec,
         self.codes) = structures

    # -- mutation -----------------------------------------------------------

    def add(self, vectors) -> np.ndarray:
        """Encode-on-insert: PQ-code the new rows with the frozen codebooks
        and append to the (stale-centroid) inverted lists.

        Device-resident fast path: the incoming batch is width-padded once,
        encoded and assigned by tracked jits, the codes land in the
        (cap, m) slab via a donated contiguous row write (appended ids are
        consecutive), and the list ids via a donated flat scatter — no
        numpy masters, no full re-uploads."""
        vec_np = np.asarray(vectors, np.float32)
        ids = self._append_rows(vec_np)
        b = ids.shape[0]
        if self.codes.shape[0] < self.capacity:  # slab grew (rare)
            self.codes = jnp.pad(
                self.codes, ((0, self.capacity - self.codes.shape[0]),
                             (0, 0)))
        vecs = pad_rows(vec_np)
        codes_new = run_device(_pq_encode, vecs, self.codec.codebooks)
        # appended ids are consecutive and the slab keeps a full write
        # window of headroom, so the padded lanes land on unused slots
        self.codes = run_device(_rows_write, self.codes, codes_new,
                                np.int32(ids[0]))
        assign = np.asarray(run_device(
            _assign_lists, vecs, self.centroids))[:b]
        self.invlists = invlist_device_append(self.invlists, self._cursor,
                                              assign, ids)
        return ids

    # -- queries ------------------------------------------------------------

    def memory_bytes(self) -> int:
        """Everything resident at query time, like every other backend:
        the float32 slab (the refine re-rank gathers from it) plus the PQ
        structures.  The paper's ~30 B/object compressed accounting is
        `compressed_bytes()`."""
        return arrays_bytes(self.embeddings, self.codes,
                            self.codec.codebooks, self.centroids,
                            self.invlists, self.valid)

    def compressed_bytes(self) -> int:
        """PQ-only footprint (codes + codebooks + coarse layer): what a
        deployment that drops the float32 slab (refine=0, re-rank
        downstream) would hold — the paper's ~30 B/object figure."""
        return arrays_bytes(self.codes, self.codec.codebooks,
                            self.centroids, self.invlists)

    def query(self, q: jax.Array, k: int):
        check_finite_queries(q, "IVFPQIndex.query")
        return _ivfpq_query(q, self.embeddings, self.centroids,
                            self.invlists, self.codes,
                            self.codec.codebooks, self.valid, k,
                            min(self.nprobe, self.centroids.shape[0]),
                            self.refine, masked=self._live != self._n_slots)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other
