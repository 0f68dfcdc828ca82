"""Pallas kernels: shape/dtype sweeps, interpret-mode vs the jnp oracles."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("q,n,d", [(4, 100, 16), (128, 256, 128), (37, 513, 64),
                                   (1, 2000, 32), (130, 129, 48)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_pairwise_l2(q, n, d, dtype):
    rng = np.random.default_rng(0)
    qa = jnp.array(rng.normal(size=(q, d)).astype(dtype))
    xa = jnp.array(rng.normal(size=(n, d)).astype(dtype))
    got = np.array(ops.pairwise_l2(qa, xa))
    want = np.array(ref.pairwise_l2_ref(qa, xa))
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol * 10, atol=tol)


@pytest.mark.parametrize("q,n,d,k", [(4, 100, 16, 1), (64, 400, 32, 8),
                                     (9, 300, 24, 16), (1, 150, 8, 5)])
def test_topk_l2(q, n, d, k):
    rng = np.random.default_rng(1)
    qa = jnp.array(rng.normal(size=(q, d)).astype(np.float32))
    xa = jnp.array(rng.normal(size=(n, d)).astype(np.float32))
    gd, gi = ops.topk_l2(qa, xa, k)
    wd, wi = ref.l2_topk_ref(qa, xa, k)
    np.testing.assert_allclose(np.array(gd), np.array(wd), rtol=1e-4, atol=1e-4)
    # ids may differ under distance ties: check distances of returned ids
    d_of_ids = np.array(ref.pairwise_l2_ref(qa, xa))[
        np.arange(q)[:, None], np.array(gi)
    ]
    np.testing.assert_allclose(d_of_ids, np.array(wd), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q,n,k,live", [(6, 300, 8, 0.5), (3, 129, 16, 0.05)])
def test_topk_l2_tombstones(q, n, k, live):
    """The running top-k with a tombstone mask (interpret mode) vs the
    masked oracle: removed rows never surface, and queries with fewer
    than k live rows underflow as (+inf, -1) in the same slots."""
    rng = np.random.default_rng(6)
    qa = jnp.array(rng.normal(size=(q, 16)).astype(np.float32))
    xa = jnp.array(rng.normal(size=(n, 16)).astype(np.float32))
    valid = jnp.array(rng.random(n) < live)
    gd, gi = ops.topk_l2(qa, xa, k, valid=valid)
    wd, wi = ref.l2_topk_ref(qa, xa, k, valid)
    np.testing.assert_allclose(np.array(gd), np.array(wd), rtol=1e-4, atol=1e-4)
    got_i, want_i = np.array(gi), np.array(wi)
    np.testing.assert_array_equal(got_i == -1, want_i == -1)
    assert np.array(valid)[got_i[got_i >= 0]].all()


def _staircase(rng, q, n, bn=128):
    """Rows by decreasing distance to every query: tile t's rows hold
    2 (T - 1 - t) threes then twos, and one last entry of 2 or 3 (ties
    inside the tile); queries are 0/1.  Dropping two threes takes at
    least 6 off a distance, the last entry moves it by at most 5."""
    t = -(-n // bn)
    tile = np.arange(n) // bn
    x = np.full((n, 2 * (t - 1) + 1), 2)
    x[:, :-1] += np.arange(2 * (t - 1))[None, :] < 2 * (t - 1 - tile)[:, None]
    x[:, -1] += rng.integers(0, 2, n)
    return rng.integers(0, 2, (q, x.shape[1])), x


def _first_tile_best(rng, q, n, bn=128):
    """The first tile, and every other later row, are copies of one 0/1
    row; the other later rows are 2/3; queries are 0/1.  No later row is
    strictly nearer than the first tile's k-th best: the copies tie it."""
    odd_later = ((np.arange(n) >= bn) & (np.arange(n) % 2 == 1))[:, None]
    x = np.where(odd_later, rng.integers(2, 4, (n, 8)),
                 rng.integers(0, 2, 8))
    return rng.integers(0, 2, (q, 8)), x


def _ints(rng, q, n):
    return rng.integers(0, 4, (q, 4)), rng.integers(0, 4, (n, 4))


@pytest.mark.parametrize("case,data,q,n,k,live,passes", [
    ("k1", _ints, 5, 1000, 1, None, None),
    ("k64", _ints, 5, 1000, 64, None, None),
    ("k200_above_bn", _ints, 5, 1000, 200, None, None),
    ("ragged_two_blocks", _ints, 130, 300, 64, None, None),
    ("tombstones", _ints, 5, 1000, 64, 0.5, None),
    ("fewer_live_than_k", _ints, 5, 1000, 64, 0.03, None),
    ("every_tile_inserts", _staircase, 130, 1000, 64, None, [8 * 64] * 2),
    ("first_tile_only", _first_tile_best, 5, 1000, 64, None, [64]),
])
def test_topk_l2_exact(case, data, q, n, k, live, passes):
    """The gated running top-k (interpret mode) against the oracle, bit
    for bit, on integer data where every distance is exact in float32 and
    duplicate rows tie: ties go to the lower id, tombstoned and ragged-tail
    rows never surface, underflow slots are (+inf, -1).  Where the catalog's
    order fixes it, the kernel's pass count too: k passes for a tile that
    must take k rows, none for one that can take none."""
    rng = np.random.default_rng(15)
    qa, xa = (jnp.array(a.astype(np.float32)) for a in data(rng, q, n))
    valid = None if live is None else jnp.array(rng.random(n) < live)
    gd, gi, got_passes = ops.topk_l2(qa, xa, k, valid=valid,
                                     return_passes=True)
    wd, wi = ref.l2_topk_ref(qa, xa, k, valid)
    np.testing.assert_array_equal(np.array(gd), np.array(wd))
    np.testing.assert_array_equal(np.array(gi), np.array(wi))
    if valid is not None and live < 0.1:
        assert (np.array(gi) == -1).any(), case
    if passes is not None:
        np.testing.assert_array_equal(np.array(got_passes), passes)


@pytest.mark.parametrize("q,n,m,c", [(2, 64, 4, 16), (128, 300, 8, 256),
                                     (5, 1000, 16, 256), (1, 50, 2, 4)])
def test_pq_adc(q, n, m, c):
    rng = np.random.default_rng(2)
    lut = jnp.array(rng.random((q, m, c)).astype(np.float32))
    codes = jnp.array(rng.integers(0, c, (n, m)).astype(np.int32))
    got = np.array(ops.pq_adc(lut, codes))
    want = np.array(ref.pq_adc_ref(lut, codes))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("b,n,p,d,k", [(4, 200, 64, 16, 8), (5, 300, 37, 16, 8),
                                       (12, 500, 130, 32, 16), (1, 100, 9, 8, 4),
                                       (3, 400, 300, 8, 150)])
def test_ivf_scan(b, n, p, d, k):
    """Fused gather+L2+top-k (interpret mode) vs the XLA oracle, with
    padded lists and -1 sentinels."""
    rng = np.random.default_rng(4)
    x = jnp.array(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.array(rng.normal(size=(b, d)).astype(np.float32))
    cand = rng.integers(0, n, (b, p)).astype(np.int32)
    cand[rng.random((b, p)) < 0.3] = -1  # inverted-list padding
    cand = jnp.array(cand)
    gd, gi = ops.ivf_scan_topk(q, x, cand, k, interpret=True)
    wd, wi = ref.ivf_scan_ref(q, x, cand, k)
    np.testing.assert_allclose(np.array(gd), np.array(wd), rtol=1e-4, atol=1e-4)
    # ids may differ under ties: distances of the returned ids must agree,
    # and underflow sentinels must land in the same slots
    got_i, want_i = np.array(gi), np.array(wi)
    np.testing.assert_array_equal(got_i == -1, want_i == -1)
    dmat = np.array(ref.pairwise_l2_ref(q, x))
    sel = got_i >= 0
    np.testing.assert_allclose(
        dmat[np.nonzero(sel)[0], got_i[sel]], np.array(wd)[sel],
        rtol=1e-4, atol=1e-4,
    )


def test_ivf_scan_k_underflow():
    """Queries with fewer than k valid candidates surface -1 ids, +inf."""
    rng = np.random.default_rng(5)
    x = jnp.array(rng.normal(size=(50, 8)).astype(np.float32))
    q = jnp.array(rng.normal(size=(3, 8)).astype(np.float32))
    cand = np.full((3, 20), -1, np.int32)
    cand[0, :2] = [7, 31]          # 2 valid < k
    cand[1, :] = -1                # no valid candidates at all
    cand[2, :6] = [1, 1, 2, 3, 4, 5]  # duplicates allowed, 6 slots
    gd, gi = ops.ivf_scan_topk(q, x, jnp.array(cand), 5, interpret=True)
    wd, wi = ref.ivf_scan_ref(q, x, jnp.array(cand), 5)
    np.testing.assert_allclose(np.array(gd), np.array(wd), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.array(gi) == -1, np.array(wi) == -1)
    assert (np.array(gi)[1] == -1).all()
    assert np.isinf(np.array(gd)[0, 2:]).all()


def test_l2_nonnegative_and_zero_diagonal():
    rng = np.random.default_rng(3)
    x = jnp.array(rng.normal(size=(64, 32)).astype(np.float32))
    d = np.array(ops.pairwise_l2(x, x))
    assert (d >= 0).all()
    np.testing.assert_allclose(np.diag(d), 0.0, atol=1e-3)


@pytest.mark.parametrize("b,s,t,h,kv,d,causal,window",
                         [(2, 64, 64, 4, 2, 32, True, 0),
                          (1, 128, 128, 8, 8, 64, True, 0),
                          (2, 64, 64, 4, 4, 32, False, 0),
                          (2, 64, 64, 4, 2, 32, True, 24),
                          (1, 32, 128, 4, 2, 32, True, 0)])
def test_flash_attention_kernel(b, s, t, h, kv, d, causal, window):
    """Pallas flash-attention vs the dense attention_core oracle."""
    import dataclasses
    from repro.configs import SMOKE_ARCHS
    from repro.models import layers as L

    rng = np.random.default_rng(0)
    q = jnp.array(rng.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.array(rng.normal(size=(b, t, kv, d)).astype(np.float32))
    v = jnp.array(rng.normal(size=(b, t, kv, d)).astype(np.float32))
    cfg = dataclasses.replace(SMOKE_ARCHS["minitron-8b"], causal=causal,
                              sliding_window=window)
    want = L.attention_core(q, k, v, t - s, cfg, written_upto=t)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=t - s, written_upto=t)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-4)
