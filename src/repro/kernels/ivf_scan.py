"""Fused IVF list scan: gather + L2 + running top-k, one pass.

FAISS's IVF scan kernels walk the probed inverted lists, compute distances
on the fly and keep a per-thread-block heap of the k best.  The TPU
adaptation (DESIGN.md §3) mirrors `l2_topk.py`'s running merge but scores
a *gathered* tile instead of a dense one: the candidate id table (B, P) —
the concatenation of each query's probed inverted lists, padded with -1 —
names catalog rows, and each (BQ, BP) tile of it is fetched row by row
from the catalog, which stays in HBM, into a VMEM scratch by DMA.  The
gathered embeddings never round-trip through HBM and the catalog's size
is bounded by HBM, not by VMEM.  Each tile folds its distances into the
k best positions (along the P axis) seen so far, held in the resident
output block; the wrapper (ops.ivf_scan_topk) maps positions back to
catalog ids.

Memory (DESIGN.md §4): per grid step, a (BQ, BP, d) f32 scratch (512 KiB
at d = 128) plus the (BQ, BP) id tile, which is copied into SMEM so the
scalar core can address the row DMAs.

Invalid slots (id < 0: list padding or dedup sentinels) issue no DMA and
are scored +inf, so they can never displace a real candidate; queries
with fewer than k valid candidates surface +inf distances, which the
wrapper turns into id = -1 (underflow contract shared with
IVFFlatIndex.query).

Mutable catalogs (DESIGN.md §10) reuse the same convention: the wrapper
(`ops.ivf_scan_topk(..., valid=mask)`) folds candidate ids whose catalog
row is tombstoned into the -1 sentinel *before* the scan, so removed
objects can never surface from stale inverted lists — the kernel itself
needs no mutation awareness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.l2_topk import merge_block_topk

BQ = 8
BP = 128
_INF = float("inf")  # python literal: avoids captured-constant tracing in Pallas
_HIGHEST = jax.lax.Precision.HIGHEST


def _ivf_scan_kernel(q_ref, tile_ref, cand_hbm, x_hbm, od_ref, oi_ref, ids,
                     rows, sem):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        od_ref[...] = jnp.full(od_ref.shape, _INF, jnp.float32)
        oi_ref[...] = jnp.zeros(oi_ref.shape, jnp.int32)

    # the id tile, into SMEM: the scalar core addresses one DMA per row
    tile = pltpu.make_async_copy(
        cand_hbm.at[pl.ds(i * BQ, BQ), pl.ds(j * BP, BP)], ids, sem.at[0])
    tile.start()
    tile.wait()

    def row_copy(s):
        r, c = s // BP, s % BP
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(jnp.maximum(ids[r, c], 0), 1)],
            rows.at[r, pl.ds(c, 1)], sem.at[1])

    def start(s, carry):
        @pl.when(ids[s // BP, s % BP] >= 0)
        def _():
            row_copy(s).start()
        return carry

    def wait(s, carry):
        @pl.when(ids[s // BP, s % BP] >= 0)
        def _():
            row_copy(s).wait()
        return carry

    jax.lax.fori_loop(0, BQ * BP, start, 0)
    jax.lax.fori_loop(0, BQ * BP, wait, 0)

    q = q_ref[...].astype(jnp.float32)            # (BQ, d)
    ones = jnp.ones((1, q.shape[1]), jnp.float32)
    sub = jax.lax.broadcasted_iota(jnp.int32, (BQ, BP), 0)
    d = jnp.zeros((BQ, BP), jnp.float32)
    for r in range(BQ):                           # static: BQ = 8 queries
        diff = rows[r].astype(jnp.float32) - q[r:r + 1, :]     # (BP, d)
        # row sums of squares as a (1, BP) lane vector, on the MXU
        d_r = jax.lax.dot_general(
            ones, diff * diff, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_HIGHEST)
        d = jnp.where(sub == r, d_r, d)
    # slots that issued no DMA hold stale rows: score them +inf
    d = jnp.where(tile_ref[...] < 0, _INF, d)

    od_ref[...], oi_ref[...] = merge_block_topk(
        od_ref[...], oi_ref[...], d, j * BP, axis=1)


def ivf_scan_pallas(
    q: jax.Array, x: jax.Array, cand: jax.Array, k: int, *,
    interpret: bool = False
):
    """Running top-k over gathered candidates.

    q (B, d), x (N, d), cand (B, P) int32 (-1 = invalid slot), B a
    multiple of BQ and P of BP.  Returns (dists (B, k), positions (B, k)):
    per query the k smallest distances, ascending, and their positions
    along the P axis of `cand`; callers gather the catalog ids with
    take_along_axis (see ops.ivf_scan_topk).  Any k works — slots no
    valid candidate fills come back +inf.
    """
    b, d = q.shape
    cand = cand.astype(jnp.int32)
    bb, p = cand.shape
    assert b == bb, (b, bb)
    assert b % BQ == 0 and p % BP == 0, (b, p)
    return pl.pallas_call(
        _ivf_scan_kernel,
        grid=(b // BQ, p // BP),
        in_specs=[
            pl.BlockSpec((BQ, d), lambda i, j: (i, 0)),
            pl.BlockSpec((BQ, BP), lambda i, j: (i, j)),   # ids, vector
            pl.BlockSpec(memory_space=pl.ANY),     # id table, in HBM
            pl.BlockSpec(memory_space=pl.ANY),     # catalog, in HBM
        ],
        out_specs=[
            pl.BlockSpec((BQ, k), lambda i, j: (i, 0)),
            pl.BlockSpec((BQ, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((BQ, BP), jnp.int32),
            pltpu.VMEM((BQ, BP, d), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, cand, cand, x)
