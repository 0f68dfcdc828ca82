"""Candidate-set builders wiring the ANN indexes into the AÇAI policy.

Batched-first (DESIGN.md §6): `index_candidate_fn_batched` maps a whole
request mini-batch (B, d) to (ids, dists, valid) of shape (B, C); the
per-request `index_candidate_fn` (same signature as
repro.core.policy.exact_candidate_fn: fn(r, x) -> ids (C,), dists (C,),
valid (C,)) is just its B = 1 view, so sequential and batched replays share
one code path bit-for-bit.

Remote candidates come from the (approximate) remote-catalog index with a
*single* exact re-rank of the retrieved ids through the fused
gather+L2+top-k scan (AÇAI evaluates true costs on the retrieved set).
Local candidates are a top-k over *only the cached rows*: the cache
indicator x is turned into an id list (one O(N) mask pass, no distance
arithmetic), those ≤ local_cap embeddings are gathered once for the whole
batch, and a (B, cap) distance GEMM + top-k picks the candidates — the
full-catalog O(N·d) per-request scan of the seed implementation is gone.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.costs import BIG_COST
from repro.core.policy import dedup_mask_batched, per_request_view
from repro.index.base import track_jit
from repro.kernels import ops


def _local_cap(n: int, c_local: int, h: int | None, local_cap: int | None) -> int:
    """Static bound on how many cached rows the local scan gathers.

    DepRound keeps occupancy at exactly h; coupled/independent rounding
    concentrate around h (App. F), so 2h + a small slack covers every
    rounding mode.  Without h we fall back to a generous multiple of
    c_local — but callers should pass h (or local_cap): if occupancy ever
    exceeds the cap, `nonzero` silently keeps the lowest-id cached rows,
    hiding the rest from local serving (quality loss, not an error).  The
    truncation is observable: the candidate fns built here carry the cap
    as `fn.local_cap`, and the policy step books max(0, occupancy - cap)
    into `StepMetrics.local_overflow` when `AcaiConfig.debug` is on."""
    if local_cap is not None:
        return min(n, local_cap)
    if h is not None:
        return min(n, 2 * h + 64)
    return min(n, max(8 * c_local, 512))


def index_candidate_fn_batched(
    index, catalog: jax.Array, c_remote: int, c_local: int,
    h: int | None = None, local_cap: int | None = None,
):
    """Build the batched candidate generator backed by an ANN index.

    Args:
      index: remote-catalog index exposing `query(rs (B, d), k) ->
        (dists (B, k), ids (B, k))` with -1 marking underflow, and
        optionally `exact_distances = True` when its distances are already
        exact on `catalog` (skips the re-rank).
      catalog: (N, d) float32 shared embedding table.
      c_remote: remote-index candidates per request (>= cfg.k).
      c_local: cached-row candidates per request.
      h: cache capacity — sizes the static cached-row gather to 2h + 64
        (see `_local_cap`); pass it (or `local_cap`) whenever known.
      local_cap: explicit override of the cached-row gather bound.

    Returns:
      fn(rs (B, d), x (N,)) -> (ids (B, C), dists (B, C), valid (B, C))
      with C = c_remote + c_local (DESIGN.md §4 slab layout): int32
      candidate ids (n marks an invalid slot), float32 exact distances
      (BIG_COST on invalid slots), bool validity after cross-slab dedup.
      Compatible with `repro.core.policy.make_step_batched` /
      `make_replay_batched` and the B = 1 `per_request_view`.
    """
    n = catalog.shape[0]
    cap = _local_cap(n, c_local, h, local_cap)

    # Indexes whose query() already returns exact distances on the shared
    # catalog embeddings (FlatIndex, IVFFlat, LSH, NSW, IVFPQ with refine)
    # advertise `exact_distances = True`; only approximate-distance indexes
    # (e.g. refine-less IVFPQ ADC) pay the exact re-rank.
    rerank = not getattr(index, "exact_distances", False)

    def fn(rs: jax.Array, x: jax.Array):
        with jax.named_scope("acai.remote"):
            d_remote, ids_remote = index.query(rs, c_remote)  # (B, c_remote)
            if rerank:
                # single exact re-rank of the retrieved candidates (fused
                # scan); -1 misses stay -1 (dist = +inf) through the scan.
                d_remote, ids_remote = ops.ivf_scan_auto(
                    rs, catalog, ids_remote, c_remote
                )
            rmiss = ids_remote < 0
            ids_remote = jnp.where(rmiss, n, ids_remote)      # n = invalid
            d_remote = jnp.where(rmiss, BIG_COST, d_remote)

        # local side: gather the cached rows *once* (shared across the
        # batch), then one (B, cap) distance GEMM + top-k — never an (N,)
        # distance scan, and no per-query duplicate of the cached slab.
        with jax.named_scope("acai.local"):
            cached = jnp.nonzero(x > 0.5, size=cap, fill_value=-1)[0]
            cached_embs = catalog[jnp.clip(cached, 0, n - 1)]  # (cap, d)
            d_loc = ops.pairwise_l2_xla(rs, cached_embs)       # (B, cap)
            d_loc = jnp.where((cached >= 0)[None, :], d_loc, jnp.inf)
            neg, pos = jax.lax.top_k(-d_loc, c_local)
            ids_local = jnp.where(jnp.isfinite(neg), cached[pos], -1)
            d_local = -neg
            lmiss = ids_local < 0
            ids_local = jnp.where(lmiss, n, ids_local)
            d_local = jnp.where(lmiss, BIG_COST, d_local)

        with jax.named_scope("acai.merge"):
            ids = jnp.concatenate([ids_remote, ids_local], axis=1)
            d = jnp.concatenate([d_remote, d_local], axis=1)
            valid = dedup_mask_batched(ids, n)
            d = jnp.where(valid, d, BIG_COST)
        return ids, d, valid

    fn.local_cap = cap  # static cached-row bound, read by the policy step
    return fn


def index_candidate_fn(
    index, catalog: jax.Array, c_remote: int, c_local: int,
    h: int | None = None, local_cap: int | None = None,
):
    """Per-request view of index_candidate_fn_batched (B = 1)."""
    return per_request_view(index_candidate_fn_batched(
        index, catalog, c_remote, c_local, h=h, local_cap=local_cap
    ))


# ---------------------------------------------------------------------------
# Mutable-catalog candidate generation (DESIGN.md §10)
# ---------------------------------------------------------------------------

@track_jit("assemble_mutable_slab")
@partial(jax.jit, static_argnames=("c_local", "cap", "c_remote", "rerank"))
def _assemble_mutable_slab(rs, x, catalog, alive, ids_remote, d_remote,
                           c_local: int, cap: int, c_remote: int,
                           rerank: bool):
    """Jitted slab assembly behind `mutable_index_candidate_fn`: takes the
    index's (eagerly computed) remote candidates plus the live catalog
    slab/mask as runtime arguments and produces the standard candidate
    slab (ids, dists, valid) — same layout as index_candidate_fn_batched,
    with tombstoned rows resolved to invalid slots throughout."""
    n = catalog.shape[0]
    with jax.named_scope("acai.remote"):
        if rerank:
            # exact re-rank of approximate-distance retrievals, tombstones
            # folded to -1 inside the fused scan
            d_remote, ids_remote = ops.ivf_scan_auto(
                rs, catalog, ids_remote, c_remote, alive)
        else:
            # the index's own masking already excludes tombstones; belt
            # and braces for foreign indexes that predate the mutation
            # contract
            dead = (ids_remote >= 0) & ~alive[jnp.clip(ids_remote, 0, n - 1)]
            ids_remote = jnp.where(dead, -1, ids_remote)
            d_remote = jnp.where(dead, jnp.inf, d_remote)
        rmiss = ids_remote < 0
        ids_remote = jnp.where(rmiss, n, ids_remote)         # n = invalid
        d_remote = jnp.where(rmiss, BIG_COST, d_remote)

    # local side: identical to the static generator — the x(dead) = 0
    # invalidation invariant keeps tombstoned rows out of the gather
    with jax.named_scope("acai.local"):
        cached = jnp.nonzero(x > 0.5, size=cap, fill_value=-1)[0]  # (cap,)
        cached_embs = catalog[jnp.clip(cached, 0, n - 1)]          # (cap, d)
        d_loc = ops.pairwise_l2_xla(rs, cached_embs)               # (B, cap)
        ok = (cached >= 0) & alive[jnp.clip(cached, 0, n - 1)]
        d_loc = jnp.where(ok[None, :], d_loc, jnp.inf)
        neg, pos = jax.lax.top_k(-d_loc, c_local)
        ids_local = jnp.where(jnp.isfinite(neg), cached[pos], -1)
        d_local = -neg
        lmiss = ids_local < 0
        ids_local = jnp.where(lmiss, n, ids_local)
        d_local = jnp.where(lmiss, BIG_COST, d_local)

    with jax.named_scope("acai.merge"):
        ids = jnp.concatenate([ids_remote, ids_local], axis=1)
        d = jnp.concatenate([d_remote, d_local], axis=1)
        valid = dedup_mask_batched(ids, n)
        d = jnp.where(valid, d, BIG_COST)
    return ids, d, valid


def mutable_index_candidate_fn(
    index, c_remote: int, c_local: int,
    h: int | None = None, local_cap: int | None = None,
):
    """Mutable-catalog candidate generator over an ANN index.

    The static `index_candidate_fn_batched` closes over the index arrays
    at trace time, so a mutated index would serve stale candidates from a
    cached jit.  This variant runs in two stages per step instead: the
    index's `query` (itself jitted over its *runtime* structure arrays)
    executes eagerly, then `_assemble_mutable_slab` builds the candidate
    slab with the current embedding slab + liveness mask as arguments —
    zero retraces under churn at fixed capacity, one on each capacity
    doubling.

    Returns fn(rs (B, d), x (N,)) -> (ids, dists, valid) with the same
    slab conventions as `index_candidate_fn_batched` (N = the slab
    capacity, which `fn` reads from the live index on every call).
    Carries `local_cap` like the static generator so the debug overflow
    counter keeps working.
    """
    rerank = not getattr(index, "exact_distances", False)

    def fn(rs: jax.Array, x: jax.Array):
        d_remote, ids_remote = index.query(rs, c_remote)
        cap = _local_cap(index.capacity, c_local, h, local_cap)
        return _assemble_mutable_slab(
            rs, x, index.embeddings, index.valid, ids_remote, d_remote,
            c_local, cap, c_remote, rerank)

    fn.local_cap = _local_cap(index.capacity, c_local, h, local_cap)
    return fn
