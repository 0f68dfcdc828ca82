"""PQ asymmetric-distance (ADC) scan — Pallas TPU kernel.

FAISS's IVF-PQ scan on GPU gathers 8-bit codes against per-query lookup
tables held in shared memory (one gather per (point, subspace)).  TPUs have
no efficient per-lane gather, so we ADAPT rather than port (DESIGN.md §3):
inside the kernel each subspace's codes are expanded to a one-hot matrix on
the fly and contracted against the LUT slice on the MXU:

    dist[q, n] = sum_m LUT[q, m, codes[n, m]]
               = sum_m ( LUT[:, m, :] @ onehot(codes[:, m])^T )[q, n]

This turns a memory-bound gather into C=256-wide matmuls — on TPU the MXU
is idle during a scan anyway, so the extra FLOPs are free and the kernel
stays HBM-bandwidth-bound on the (N, M) uint8 code stream, which is the
same bottleneck (and byte count) as the GPU original.

Blocks: (BQ=128 queries) x (BN=128 points).  The wrapper hands the kernel
subspace-major layouts — the LUT as (M, Q, C) and the codes as (M, N) — so
subspace m is a leading-axis index of the (M, BQ, C) LUT tile and one
sublane row of the (M, BN) code tile: the TPU lowers neither a dynamic
lane slice nor a gather.  The M-loop is unrolled (M is static and small,
16 for a 128-d catalog), so codes are touched once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128
BN = 128
_HIGHEST = jax.lax.Precision.HIGHEST  # the LUT's f32 values, not bf16


def _adc_kernel(lut_ref, codes_ref, o_ref):
    m, _, c = lut_ref.shape     # (M, BQ, C) float32
    bn = codes_ref.shape[1]     # (M, BN) int32
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, bn), 0)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for mi in range(m):
        onehot = (rows == codes_ref[mi:mi + 1, :]).astype(jnp.float32)
        # (BQ, C) @ (C, BN) on the MXU
        acc = acc + jax.lax.dot_general(
            lut_ref[mi], onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_HIGHEST,
        )
    o_ref[...] = acc


def pq_adc_pallas(
    lut: jax.Array, codes: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """lut (Q, M, C) float32, codes (N, M) int32 -> (Q, N) float32."""
    q, m, c = lut.shape
    n, m2 = codes.shape
    assert m == m2, (m, m2)
    assert q % BQ == 0 and n % BN == 0, (q, n)
    grid = (q // BQ, n // BN)
    return pl.pallas_call(
        _adc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, BQ, c), lambda i, j: (0, i, 0)),
            pl.BlockSpec((m, BN), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BQ, BN), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        interpret=interpret,
    )(jnp.transpose(lut, (1, 0, 2)), codes.astype(jnp.int32).T)
