"""Fused distance + running top-k Pallas kernel.

GPU FAISS fuses the distance GEMM with a warp-level k-selection network.
TPU has no warp shuffles; the idiomatic equivalent (DESIGN.md §3) is a
running merge: the grid walks the catalog in (BQ, BN) tiles, and each tile
folds its distances into the k best seen so far for its queries.  The k
best live in the output block itself, which stays resident in VMEM across
the catalog axis of the grid, so neither the (Q, N) distance matrix nor
any per-block partials ever reach HBM: the kernel reads the catalog once
and writes Q·k floats.

The fold is gated by the running k-th best (`merge_gated_topk`): a tile
runs one ranked-insert pass per row that can still enter some query's
top-k, at most k, and none where no row beats any query's k-th best.  In
a catalog stored in no order relative to the queries that is about one
pass per tile once the first tiles are in; a catalog stored in order of
decreasing distance to every query costs k passes a tile, as a fixed
k-pass merge would.

Layout (DESIGN.md §4): a tile is scored as (BQ, BN) and transposed to
(BN, BQ), so queries sit on the 128 lanes and the selection reduces over
sublanes; the running top-k is (k, BQ), lane-dense, and the output array
is (k, Q) — its block's last dim is 128 and its second-to-last is the
array's full k, as the TPU's tiling rule asks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128
BN = 128
_INF = float("inf")  # python literal: avoids captured-constant tracing in Pallas
_HIGHEST = jax.lax.Precision.HIGHEST  # f32 distances, not one bf16 MXU pass


# ivf_scan's selection step; l2_topk folds its tiles with merge_gated_topk
def merge_block_topk(acc_d, acc_i, d, base, axis: int):
    """Fold one tile into a running top-k by iterative masked-min
    extraction — the shared selection step of l2_topk and ivf_scan.

    acc_d / acc_i: the k best so far (ascending along `axis`) and their
    ids; d: the tile's distances, candidates along `axis`, whose ids are
    `base` + their position.  Returns the k smallest of both, ascending.
    Ties go to the running set first, then to the lower tile position, so
    a scan in id order breaks ties toward the lower id, as one `lax.top_k`
    over the whole row would.  Slots are written with an iota select (the
    TPU lowers no scatter); an exhausted tile contributes +inf."""
    k, n = acc_d.shape[axis], d.shape[axis]
    apos = jax.lax.broadcasted_iota(jnp.int32, acc_d.shape, axis)
    tpos = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis)

    def body(t, carry):
        ad, td, od, oi = carry
        ma = jnp.min(ad, axis=axis, keepdims=True)
        mt = jnp.min(td, axis=axis, keepdims=True)
        from_acc = ma <= mt
        pa = jnp.min(jnp.where(ad == ma, apos, k), axis=axis, keepdims=True)
        pt = jnp.min(jnp.where(td == mt, tpos, n), axis=axis, keepdims=True)
        ia = jnp.sum(jnp.where(apos == pa, acc_i, 0), axis=axis,
                     keepdims=True)
        slot = apos == t
        od = jnp.where(slot, jnp.where(from_acc, ma, mt), od)
        oi = jnp.where(slot, jnp.where(from_acc, ia, base + pt), oi)
        ad = jnp.where(from_acc & (apos == pa), _INF, ad)
        td = jnp.where(~from_acc & (tpos == pt), _INF, td)
        return ad, td, od, oi

    _, _, od, oi = jax.lax.fori_loop(0, k, body, (acc_d, d, acc_d, acc_i))
    return od, oi


def merge_gated_topk(acc_d, acc_i, d, base):
    """Fold one (n, BQ) tile into a running (k, BQ) top-k, gated by the
    running k-th best: l2_topk's selection step.

    acc_d / acc_i: the k best so far per lane (ascending along axis 0) and
    their ids; d: the tile's distances, whose ids are `base` + their row.
    A tile row can enter a lane's top-k only if it is strictly below the
    lane's k-th best, so the tile runs m = min(k, most such rows over the
    lanes) passes; each takes the tile's smallest remaining distance per
    lane (lowest row first) and inserts it at its rank, after any equal
    entries, where it beats the k-th best (which drops off).  Tiles come
    in id order, so ties go to the lower id, as one `lax.top_k` over the
    row would break them.  Slots that no finite distance fills (+inf) take
    the id of the running best before the tile.  Returns (dists, ids, m):
    the same k as `merge_block_topk` gives, bit for bit."""
    k, n = acc_d.shape[0], d.shape[0]
    apos = jax.lax.broadcasted_iota(jnp.int32, acc_d.shape, 0)
    tpos = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    kth = jnp.max(acc_d, axis=0, keepdims=True)
    beat = jnp.sum((d < kth).astype(jnp.int32), axis=0, keepdims=True)
    m = jnp.minimum(jnp.max(beat), k)

    def body(_, carry):
        td, od, oi = carry
        mt = jnp.min(td, axis=0, keepdims=True)
        pt = jnp.min(jnp.where(td == mt, tpos, n), axis=0, keepdims=True)
        # mt's rank in the running list; k where it does not beat the k-th
        r = jnp.sum((od <= mt).astype(jnp.int32), axis=0, keepdims=True)
        od = jnp.where(apos < r, od,
                       jnp.where(apos == r, mt, pltpu.roll(od, 1, 0)))
        oi = jnp.where(apos < r, oi,
                       jnp.where(apos == r, base + pt, pltpu.roll(oi, 1, 0)))
        td = jnp.where(tpos == pt, _INF, td)
        return td, od, oi

    _, od, oi = jax.lax.fori_loop(0, m, body, (d, acc_d, acc_i))
    return od, jnp.where(od == _INF, acc_i[:1], oi), m


def _l2_topk_kernel(n: int, masked: bool, count_passes: bool, *refs):
    q_ref, x_ref, *refs = refs
    m_ref = refs.pop(0) if masked else None
    p_ref = refs.pop() if count_passes else None
    od_ref, oi_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        od_ref[...] = jnp.full(od_ref.shape, _INF, jnp.float32)
        oi_ref[...] = jnp.zeros(oi_ref.shape, jnp.int32)
        if p_ref is not None:
            p_ref[...] = jnp.zeros(p_ref.shape, jnp.int32)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_HIGHEST,
    )
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    xn = jnp.sum(x * x, axis=1)[None, :]
    d = jnp.maximum(qn - 2.0 * dots + xn, 0.0)  # (BQ, BN)

    base = j * BN
    # the last block may run past the catalog's end (the grid is ragged,
    # the catalog is never padded): those rows hold unspecified values and
    # must never displace real candidates
    gcol = base + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(gcol >= n, _INF, d)
    if m_ref is not None:
        # additive row penalty (0 = live, +inf = tombstoned): the
        # mutable-catalog validity mask of DESIGN.md §10
        d = d + m_ref[...]

    od_ref[...], oi_ref[...], passes = merge_gated_topk(
        od_ref[...], oi_ref[...], d.T, base)
    if p_ref is not None:
        p_ref[...] += passes


def l2_topk_pallas(
    q: jax.Array, x: jax.Array, k: int, *,
    mask: jax.Array | None = None, count_passes: bool = False,
    interpret: bool = False
):
    """Returns (dists (k, Q), ids (k, Q)): per query (column), the k
    smallest squared distances, ascending, and their catalog rows.
    With `count_passes` (static) also the selection passes the kernel ran,
    summed over the catalog's tiles, for each block of BQ queries:
    int32 (Q // BQ,).

    Q must be a multiple of BQ; N need not be a multiple of BN — the
    catalog (which may be gigabytes) is read in place, ceil(N / BN) tiles
    per query block, the ragged tail masked inside the kernel.  `mask` is
    an optional (1, ceil(N / BN) * BN) float32 additive row penalty — 0
    for live rows, +inf for tombstoned ones — so a mutable catalog's
    removed rows can never displace real candidates (slots no live row
    fills come back +inf; ops.topk_l2 turns their ids into -1)."""
    qq, d = q.shape
    n, _ = x.shape
    assert qq % BQ == 0, qq
    nb = pl.cdiv(n, BN)
    if mask is not None:
        assert mask.shape == (1, nb * BN), (mask.shape, n)
    in_specs = [
        pl.BlockSpec((BQ, d), lambda i, j: (i, 0)),
        pl.BlockSpec((BN, d), lambda i, j: (j, 0)),
    ]
    args = (q, x)
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, BN), lambda i, j: (0, j)))
        args = (q, x, mask.astype(jnp.float32))
    # one output block per query block, resident across the catalog axis:
    # the running top-k (and the pass count, the same in every lane)
    out_specs = [
        pl.BlockSpec((k, BQ), lambda i, j: (0, i)),
        pl.BlockSpec((k, BQ), lambda i, j: (0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((k, qq), jnp.float32),
        jax.ShapeDtypeStruct((k, qq), jnp.int32),
    ]
    if count_passes:
        out_specs.append(pl.BlockSpec((1, BQ), lambda i, j: (0, i)))
        out_shape.append(jax.ShapeDtypeStruct((1, qq), jnp.int32))
    out = pl.pallas_call(
        functools.partial(_l2_topk_kernel, n, mask is not None,
                          count_passes),
        grid=(qq // BQ, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    if count_passes:
        dists, ids, passes = out
        return dists, ids, passes[0, ::BQ]
    return tuple(out)
