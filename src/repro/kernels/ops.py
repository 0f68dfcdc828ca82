"""Jitted public wrappers for the Pallas kernels.

Handle padding to block multiples, dtype promotion, backend dispatch
(interpret=True automatically on non-TPU backends so the same call sites
run in CI/CPU and on real hardware), and the partial-top-k merge.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ivf_scan as ivf_scan_kernel
from repro.kernels import l2 as l2_kernel
from repro.kernels import l2_topk as l2_topk_kernel
from repro.kernels import pq_adc as pq_adc_kernel
from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(a: jax.Array, mult: int, value=0.0) -> jax.Array:
    n = a.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return a
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=value)


@partial(jax.jit, static_argnames=("interpret",))
def pairwise_l2(q: jax.Array, x: jax.Array, *, interpret: bool | None = None):
    """Pairwise squared-L2 distances via the Pallas kernel.

    Args:
      q: (Q, D) float query embeddings (promoted to float32 inside).
      x: (N, D) float catalog embeddings.
      interpret: force Pallas interpret mode; default = auto (compiled on
        TPU, interpret elsewhere — DESIGN.md §3 backend dispatch).

    Returns:
      (Q, N) float32 squared distances, clamped at 0 (DESIGN.md §2).  Rows
      are internally padded to the (BQ, BN) = (128, 128) tile grid of
      DESIGN.md §4 and sliced back, so any Q, N are accepted.
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    qq, n = q.shape[0], x.shape[0]
    qp = _pad_rows(q, l2_kernel.BQ)
    xp = _pad_rows(x, l2_kernel.BN)
    out = l2_kernel.pairwise_l2_pallas(qp, xp, interpret=interp)
    return out[:qq, :n]


@partial(jax.jit, static_argnames=("interpret",))
def pq_adc(lut: jax.Array, codes: jax.Array, *, interpret: bool | None = None):
    """Product-quantization asymmetric-distance scan (ADC).

    Args:
      lut: (Q, M, C) per-query, per-subspace distance tables (float32):
        lut[q, m, c] = ||r_q^{(m)} - centroid_c^{(m)}||².
      codes: (N, M) integer PQ codes in [0, C).
      interpret: see `pairwise_l2`.

    Returns:
      (Q, N) float32 approximate distances
      dist[q, n] = Σ_m lut[q, m, codes[n, m]], computed on the MXU via the
      on-the-fly one-hot contraction of DESIGN.md §3.  Tile grid
      (BQ, BN) = (128, 128); inputs are padded/sliced automatically.
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    qq, n = lut.shape[0], codes.shape[0]
    lp = _pad_rows(lut, pq_adc_kernel.BQ)
    cp = _pad_rows(codes, pq_adc_kernel.BN)
    out = pq_adc_kernel.pq_adc_pallas(lp, cp, interpret=interp)
    return out[:qq, :n]


@partial(jax.jit, static_argnames=("k", "return_passes", "interpret"))
def topk_l2(q: jax.Array, x: jax.Array, k: int, *, valid: jax.Array | None = None,
            return_passes: bool = False, interpret: bool | None = None):
    """Fused blocked distance + top-k: the (Q, N) matrix never hits HBM.

    Args:
      q: (Q, D) query embeddings.
      x: (N, D) catalog embeddings.
      k: number of nearest neighbours per query (static).
      valid: optional (N,) bool tombstone mask (mutable catalog,
        DESIGN.md §10).  Masked rows never surface; queries with fewer
        than k live rows underflow as dist = +inf, id = -1.  With
        valid=None the output is bitwise the pre-mutation scan.
      return_passes: also return the kernel's selection passes, summed
        over the catalog's tiles, per block of 128 (padded) queries.
      interpret: see `pairwise_l2`.

    Returns:
      (dists (Q, k), ids (Q, k)): the k smallest squared distances per
      query, ascending, with int32 catalog row ids; with `return_passes`,
      then int32 (ceil(Q / 128),) passes.  Each (BQ, BN) tile folds its
      distances into a running top-k held in VMEM by ranked inserts, one
      pass per tile row that can still enter some query's top-k
      (DESIGN.md §3): the catalog is read once and only Q·k results are
      written.
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    qq = q.shape[0]
    qp = _pad_rows(q, l2_topk_kernel.BQ)
    mask = None
    if valid is not None:
        # additive per-row penalty: 0 live, +inf tombstoned (the ragged
        # tail block's rows past N are masked inside the kernel)
        mask = jnp.where(_pad_rows(valid, l2_topk_kernel.BN, value=False),
                         0.0, jnp.inf)[None, :]
    dists, ids, *passes = l2_topk_kernel.l2_topk_pallas(
        qp, x, k, mask=mask, count_passes=return_passes, interpret=interp)
    dists, ids = dists.T[:qq], ids.T[:qq]
    if valid is not None:
        ids = jnp.where(jnp.isfinite(dists), ids, -1)
    return (dists, ids, *passes)


@partial(jax.jit, static_argnames=("k", "chunk"))
def topk_l2_chunked(q: jax.Array, x: jax.Array, k: int, chunk: int,
                    valid: jax.Array | None = None):
    """Chunked fused distance + top-k in pure XLA: the memory-roofline
    oracle of the Pallas `topk_l2` kernel for non-TPU backends.

    Args:
      q: (Q, D) query embeddings.
      x: (N, D) catalog embeddings (N need not divide `chunk`; the tail
        chunk is padded and masked to +inf).
      k: neighbours per query (static).
      chunk: catalog rows per scan step (static) — peak extra memory is
        O(Q · (chunk + k)) instead of O(Q · N).
      valid: optional (N,) bool tombstone mask (mutable catalog,
        DESIGN.md §10): masked rows scan as +inf, chunk by chunk, so a
        removed object can never enter the running top-k.  Queries with
        fewer than k live rows surface dist = +inf, id = -1 slots.

    Returns:
      (dists (Q, k), ids (Q, k)) exactly as `topk_l2`: ascending squared
      distances, int32 row ids.  Used by the distributed retrieval step
      (`repro.core.distributed`) and the baselines' `ServerOracle` so a
      catalog (shard) is scanned without ever materialising the (B, N)
      distance matrix.
    """
    n = x.shape[0]
    b = q.shape[0]
    xp = _pad_rows(x, chunk)
    vp = None if valid is None else _pad_rows(valid[:, None], chunk,
                                              value=False)[:, 0]
    nchunks = xp.shape[0] // chunk
    qn = jnp.sum(q * q, axis=1, keepdims=True)

    def body(carry, j):
        best_d, best_i = carry
        blk = jax.lax.dynamic_slice_in_dim(xp, j * chunk, chunk, 0)
        cn = jnp.sum(blk * blk, axis=1)[None, :]
        dots = jnp.matmul(q, blk.T, precision=jax.lax.Precision.HIGHEST)
        d2 = jnp.maximum(qn - 2.0 * dots + cn, 0.0)
        ids = j * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        d2 = jnp.where(ids < n, d2, jnp.inf)                 # padded tail
        if vp is not None:                                   # tombstones
            vblk = jax.lax.dynamic_slice_in_dim(vp, j * chunk, chunk, 0)
            d2 = jnp.where(vblk[None, :], d2, jnp.inf)
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (b, chunk))], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((b, k), jnp.inf, jnp.float32),
            jnp.zeros((b, k), jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(body, init, jnp.arange(nchunks))
    if valid is not None:
        best_i = jnp.where(jnp.isfinite(best_d), best_i, -1)
    return best_d, best_i


@partial(jax.jit, static_argnames=("k", "interpret"))
def ivf_scan_topk(q: jax.Array, x: jax.Array, cand: jax.Array, k: int, *,
                  valid: jax.Array | None = None,
                  interpret: bool | None = None):
    """Fused gather + L2 + top-k over per-query candidate id lists.

    q (B, d), x (N, d) catalog, cand (B, P) int32 with -1 = invalid slot
    (inverted-list padding, dedup sentinels).  Returns (dists (B, k),
    ids (B, k)); underflowing slots come back as dist = +inf, id = -1.

    `valid` (N,) bool is the mutable-catalog tombstone mask (DESIGN.md
    §10): candidate ids pointing at tombstoned rows are folded into the
    kernel's existing -1 invalid-slot convention *before* the scan, so a
    removed object can never surface from a stale inverted list or
    bucket — no kernel change, one extra gather + where.

    The kernel keeps a running top-k of any width k; the catalog stays in
    HBM and only the candidate rows are fetched (DESIGN.md §4).
    """
    if valid is not None:
        cand = jnp.where(
            (cand >= 0) & valid[jnp.clip(cand, 0, x.shape[0] - 1)], cand, -1)
    interp = (not _on_tpu()) if interpret is None else interpret
    if interp:
        # the kernel DMAs by hand: off the TPU it needs the TPU
        # interpreter, which models HBM, SMEM and DMA semaphores
        interp = pltpu.InterpretParams()
    b, p = cand.shape
    qp = _pad_rows(q, ivf_scan_kernel.BQ)
    cp = _pad_rows(cand, ivf_scan_kernel.BQ, value=-1)
    padp = (-p) % ivf_scan_kernel.BP
    if padp:
        cp = jnp.pad(cp, ((0, 0), (0, padp)), constant_values=-1)
    dists, pos = ivf_scan_kernel.ivf_scan_pallas(qp, x, cp, k,
                                                 interpret=interp)
    ids = jnp.take_along_axis(cp, pos, axis=1)
    ids = jnp.where(jnp.isfinite(dists), ids, -1)
    return dists[:b], ids[:b]


# jnp fallbacks, exported for benchmarking kernel vs XLA-fused baseline.
pairwise_l2_xla = jax.jit(ref.pairwise_l2_ref)
pq_adc_xla = jax.jit(ref.pq_adc_ref)
topk_l2_xla = jax.jit(ref.l2_topk_ref, static_argnames=("k",))
ivf_scan_xla = jax.jit(ref.ivf_scan_ref, static_argnames=("k",))


def topk_l2_auto(q: jax.Array, x: jax.Array, k: int,
                 valid: jax.Array | None = None):
    """Hot-path dispatch: compiled Pallas kernel on TPU, fused XLA reference
    elsewhere (interpret-mode Pallas is a correctness harness, not a perf
    path — see kernel_bench).  `valid` is the optional tombstone mask
    (every dispatch target honors it, DESIGN.md §10)."""
    if _on_tpu():
        return topk_l2(q, x, k, valid=valid)
    return topk_l2_xla(q, x, k, valid)


def ivf_scan_auto(q: jax.Array, x: jax.Array, cand: jax.Array, k: int,
                  valid: jax.Array | None = None):
    """Hot-path dispatch for the fused IVF scan (same policy as
    topk_l2_auto).  `valid` folds tombstoned rows into the -1 invalid-slot
    convention before the scan."""
    if _on_tpu():
        return ivf_scan_topk(q, x, cand, k, valid=valid)
    return ivf_scan_xla(q, x, cand, k, valid)


def topk_l2_fused(q: jax.Array, x: jax.Array, k: int, *, chunk: int,
                  valid: jax.Array | None = None):
    """Memory-roofline dispatch: fused Pallas `topk_l2` on TPU, the chunked
    XLA oracle elsewhere — on either backend the (Q, N) distance matrix is
    never materialised.  This is the scan the distributed retrieval step
    runs per catalog shard when `scan_chunk > 0`, and (with `valid`) the
    mutable-catalog `ServerOracle` scan."""
    if _on_tpu():
        return topk_l2(q, x, k, valid=valid)
    return topk_l2_chunked(q, x, k, chunk, valid)


@partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                   "written_upto", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    written_upto=None, interpret: bool | None = None):
    """Pallas flash attention.

    Args:
      q: (B, S, H, D) queries.
      k, v: (B, T, KV, D) keys/values (KV heads broadcast over H for GQA).
      causal: apply the causal mask (decode/prefill).
      window: sliding-window size; 0 = full attention.
      q_offset: absolute position of q[0] (static) for causal masking of
        decode steps against a longer cache.
      written_upto: ring-buffer watermark — keys at positions >= this are
        masked out (static-shape decode, DESIGN.md §5).
      interpret: see `pairwise_l2`.

    Returns:
      (B, S, H, Dv) attention output; S is internally padded to the BQ
      tile and sliced back.
    """
    from repro.kernels import flash_attention as fa

    interp = (not _on_tpu()) if interpret is None else interpret
    s = q.shape[1]
    bq = min(fa.BQ, s)
    bk = min(fa.BK, k.shape[1])
    pad = (-s) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = fa.flash_attention_pallas(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset,
                                    written_upto=written_upto,
                                    bq=bq, bk=bk, interpret=interp)
    return out[:, :s]


def register_tracked_jits() -> None:
    """Register the module-level jits with the mutation path's compile
    tracker (repro.index.base.track_jit), so the churn no-retrace guard
    also covers the masked scans that serve over swapped slab buffers.
    Called from repro.index's package init — a lazy hook rather than a
    top-level import to keep kernels free of an index-package cycle."""
    from repro.index.base import track_jit

    for name, fn in (("ops_pairwise_l2", pairwise_l2),
                     ("ops_pq_adc", pq_adc),
                     ("ops_topk_l2", topk_l2),
                     ("ops_topk_l2_chunked", topk_l2_chunked),
                     ("ops_ivf_scan_topk", ivf_scan_topk),
                     ("ops_pairwise_l2_xla", pairwise_l2_xla),
                     ("ops_pq_adc_xla", pq_adc_xla),
                     ("ops_topk_l2_xla", topk_l2_xla),
                     ("ops_ivf_scan_xla", ivf_scan_xla)):
        track_jit(name, fn)
