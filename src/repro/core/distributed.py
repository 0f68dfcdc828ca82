"""Distributed AÇAI: the paper's retrieval/caching step at pod scale.

At production scale the catalog (10^8 x d embeddings) and the fractional
cache state y live SHARDED over the `model` mesh axis; the request batch is
data-parallel.  One serve+update step per request batch:

  1. every chip scans its catalog shard with the fused distance+top-k
     kernel (Pallas `topk_l2` on TPU, the chunked XLA oracle elsewhere, or
     the sharded-IVF probe) and takes a local top-C
                                          -> compute-bound, no comms
  2. ONE all-gather over `model` of a packed per-shard candidate payload
     [dist, bitcast(global id), y, x] + a per-section top-C re-merge.
     Because a shard only ever proposes its own rows, it attaches the
     y/x state those rows will need right in the payload — the separate
     masked-psum state gathers of the first sharded version collapse
     into the merge itself (DESIGN.md §15)
  3. per-request gain/subgradient on the merged candidates (Eq. 55)
  4. subgradients routed to the owning y-shards: one packed
     [g, bitcast(id)] all_gather over `data` + local mask — skipped
     entirely on size-1 batch axes, where every shard already holds the
     full request batch
  5. OMA multiplicative update + DISTRIBUTED capped-simplex projection:
     per-shard top-A heads and exact tail sum packed as (A + 1,) scalars,
     ONE all-gather, the global water-filling scale solved redundantly on
     every shard — the O(N log N) sort of Sec. IV-F becomes
     O(N/P log A) + an O(A.P) scalar exchange.

Per-step collective budget (pinned by tests/test_collectives.py and
reported by `collectives_per_step`): the exact sharded step spends 2
all-gathers on a 1-device data axis (3 with data-parallel requests); the
IVF step spends one more because its remote merge is issued before the
cached-row scan so XLA can overlap the exchange with local compute.

The serve answer (global ids of the k cheapest augmented copies) comes out
of the same merged candidate set.  `make_retrieval_step` is the
paper-representative roofline cell (`acai-retrieval`) lowered by the
dry-run; `make_replay_sharded` is the serving-stack twin of
`repro.core.policy.make_replay_batched` — same mini-batch OMA semantics,
state carried as (y, x, t, key), bit-consistent with the batched replay on
a 1-device mesh (see DESIGN.md §7).  `make_mutable_step_sharded` is the
churn twin: catalog slab + liveness mask as runtime arguments, mutations
routed to the owning shard by global-id arithmetic (`route_ids_by_owner`,
`sharded_slab_append`), the projection run over the live mask — bitwise
`make_mutable_step` + exact candidates on a 1-device mesh (DESIGN.md §15).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.extend import core as jax_core
from jax.sharding import PartitionSpec as P

from repro.core import gain as gain_lib
from repro.core import mirror as mirror_maps
from repro.core import oma as oma_lib
from repro.core import policy as policy_lib
from repro.core.costs import BIG_COST, pairwise_dissimilarity
from repro.core.projection import _negentropy_scale_from_sorted
from repro.kernels import ops


def _axis_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 1
    for ax in ([axes] if isinstance(axes, str) else axes):
        total *= sizes[ax]
    return total


# ---------------------------------------------------------------------------
# Sharded IVF: per-shard coarse quantizer + inverted lists (local row ids)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedIVF:
    """Per-shard IVF structures, stacked along the (sharded) row axis.

    centroids: (P * nlist, d)  — shard p owns rows [p*nlist, (p+1)*nlist)
    invlists:  (P * nlist, cap) int32 — ids are LOCAL row offsets into the
               owning catalog shard, -1 padded
    """

    centroids: jax.Array
    invlists: jax.Array
    nlist: int
    nprobe: int

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def build_sharded_ivf(catalog, n_shards: int, *, nlist: int = 32,
                      nprobe: int = 8, train_iters: int = 12,
                      seed: int = 0) -> ShardedIVF:
    """Train one IVF coarse quantizer per catalog shard.

    Each shard gets its own k-means over its rows — exactly what a chip
    would do at scale (the invlist table shards row-wise, DESIGN.md §4) —
    so the sharded retrieval step probes only shard-local lists.
    """
    from repro.index.ivf import build_invlists
    from repro.index.kmeans import kmeans

    catalog = jnp.asarray(catalog, jnp.float32)
    n = catalog.shape[0]
    assert n % n_shards == 0, (n, n_shards)
    n_shard = n // n_shards
    cents, tables = [], []
    for p in range(n_shards):
        shard = catalog[p * n_shard:(p + 1) * n_shard]
        key = jax.random.PRNGKey(seed + p)
        c, assign = kmeans(key, shard, nlist, train_iters)
        cents.append(np.asarray(c))
        tables.append(build_invlists(np.asarray(assign), nlist))
    cap = max(t.shape[1] for t in tables)
    tables = [np.pad(t, ((0, 0), (0, cap - t.shape[1])), constant_values=-1)
              for t in tables]
    return ShardedIVF(
        centroids=jnp.asarray(np.concatenate(cents, 0), jnp.float32),
        invlists=jnp.asarray(np.concatenate(tables, 0), jnp.int32),
        nlist=nlist, nprobe=nprobe)


def _check_ivf_matches_mesh(ivf: "ShardedIVF | None", n_model: int) -> None:
    """A ShardedIVF built for P shards only makes sense on a P-way model
    axis: the P(model, None) in_spec would otherwise silently hand each
    mesh shard centroid/invlist rows belonging to a different catalog
    sub-shard (local row ids reinterpreted against the wrong shard — wrong
    candidates, no shape error)."""
    if ivf is None:
        return
    built_for = ivf.centroids.shape[0] // ivf.nlist
    if built_for != n_model:
        raise ValueError(
            f"ShardedIVF was built for {built_for} shards "
            f"(centroids {ivf.centroids.shape}, nlist {ivf.nlist}) but the "
            f"mesh's model axis has {n_model} devices — rebuild with "
            f"build_sharded_ivf(catalog, {n_model}, ...)")


def _local_scan(requests, catalog, c: int, scan_chunk: int, ivf_shard):
    """Per-shard local top-c scan: (dists (b, c), local ids (b, c)).

    Three variants (DESIGN.md §7): paper-faithful full matrix
    (scan_chunk = 0, ivf = None), the fused kernel path (`ops.topk_l2_fused`
    — Pallas on TPU, chunked XLA oracle elsewhere), and the sharded-IVF
    probe that scans only this shard's probed inverted lists.  Underflowing
    slots (IVF only) come back as dist = +inf, id = -1.
    """
    if ivf_shard is not None:
        centroids, invlists, nprobe = ivf_shard
        dc = pairwise_dissimilarity(requests, centroids)
        _, probe = jax.lax.top_k(-dc, nprobe)                # (b, nprobe)
        cand = invlists[probe].reshape(requests.shape[0], -1)
        return ops.ivf_scan_auto(requests, catalog, cand, c)
    if scan_chunk:
        return ops.topk_l2_fused(requests, catalog, c, chunk=scan_chunk)
    d2 = pairwise_dissimilarity(requests, catalog)
    neg, ids = jax.lax.top_k(-d2, c)
    return -neg, ids


# ---------------------------------------------------------------------------
# Fused-collective building blocks (DESIGN.md §15)
# ---------------------------------------------------------------------------

def _ids_to_f32(ids: jax.Array) -> jax.Array:
    """Bit-preserving int32 -> float32 view so candidate ids can ride in
    the same packed all-gather payload as their float columns.  Only data
    movement (gather / concat / take_along_axis) ever touches the packed
    lane, so the bit pattern round-trips exactly."""
    return jax.lax.bitcast_convert_type(ids.astype(jnp.int32), jnp.float32)


def _f32_to_ids(f: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(f, jnp.int32)


def _candidate_payload(d, loc, miss, off, n: int, y_shard, x_shard):
    """Pack one shard-local candidate section as [d, bitcast(gid), y, x].

    The proposing shard OWNS every row it proposes, so it attaches the
    y/x state the merged slab will need — the masked-psum state gathers
    of the first sharded version collapse into the merge exchange.  Miss
    slots (IVF underflow) become (dist = +inf, id = n, y = 0, x = 0),
    matching the old sentinel semantics (out-of-range state reads were 0).
    """
    n_shard = y_shard.shape[0]
    safe = jnp.clip(loc, 0, n_shard - 1)
    return jnp.stack([
        jnp.where(miss, jnp.inf, d),
        _ids_to_f32(jnp.where(miss, n, loc + off)),
        jnp.where(miss, 0.0, y_shard[safe]),
        jnp.where(miss, 0.0, x_shard[safe]),
    ], axis=-1)


def _packed_merge(payload, counts, n_model: int, model_axis):
    """ONE all-gather of the packed candidate payload over `model`, then a
    per-section re-top-k (steps 2 of the module docstring — the fused
    replacement for per-array candidate gathers + per-state psums).

    payload: (b, sum(counts), L) float32 — the shard's candidate sections
      laid out side by side, column 0 the ascending sort key (the
      dissimilarity), the remaining columns riding along (bitcast ids,
      attached y/x state).
    counts: per-section budgets; section i re-merges to its global
      top-counts[i] independently.

    Returns one (dists (b, c), [other columns (b, c) ...]) per section.
    At P = 1 the gather is the identity and top_k over an already sorted
    section is order-preserving (stable ties) — bitwise a no-op.
    """
    b, ctot, ncol = payload.shape
    g = jax.lax.all_gather(payload, model_axis, axis=1, tiled=True)
    g = g.reshape(b, n_model, ctot, ncol)
    outs = []
    off = 0
    for c in counts:
        sec = g[:, :, off:off + c].reshape(b, n_model * c, ncol)
        negm, pos = jax.lax.top_k(-sec[..., 0], c)
        cols = [jnp.take_along_axis(sec[..., j], pos, axis=1)
                for j in range(1, ncol)]
        outs.append((-negm, cols))
        off += c
    return outs


def _route_subgradients(g_cand, ids, valid, off, n_shard: int, batch_axes,
                        n_batch: int, denom: float = 1.0):
    """Scatter-add per-request candidate subgradients into this shard's
    (n_shard,) y-slice (step 4 of the module docstring).

    The data-parallel exchange is ONE packed [g, bitcast(id)] all-gather
    over the batch axes: invalid candidate slots fold in by rewriting
    their id to -1 (owned by no shard) before packing, so the separate
    validity-mask gather of the first sharded version disappears.  On
    size-1 batch axes (`n_batch == 1`, known statically from the mesh)
    the exchange is skipped entirely — every shard already holds the full
    request batch.  `denom` is the mini-batch averaging divisor."""
    ids_eff = jnp.where(valid, ids, -1) if valid is not None else ids
    if n_batch > 1:
        packed = jnp.stack([g_cand, _ids_to_f32(ids_eff)], axis=-1)
        packed = jax.lax.all_gather(packed, batch_axes, axis=0, tiled=True)
        g_all, ids_all = packed[..., 0], _f32_to_ids(packed[..., 1])
    else:
        g_all, ids_all = g_cand, ids_eff
    mine = (ids_all >= off) & (ids_all < off + n_shard)
    lidx = jnp.clip(ids_all - off, 0, n_shard - 1)
    val = jnp.where(mine, g_all, 0.0).reshape(-1)
    if denom != 1.0:
        val = val / denom
    return jnp.zeros((n_shard,), g_cand.dtype).at[lidx.reshape(-1)].add(val)


def _distributed_projection(z, h, top_a: int, n_model: int, model_axis):
    """Distributed negentropy Bregman projection (Sec. IV-F water-filling).

    Per shard: top-A heads + exact tail sum (scatter-zero, no total-minus-
    top cancellation), packed as ONE (A + 1,) array so the whole exchange
    is a single all-gather of P·(A + 1) scalars — the first sharded
    version spent a heads all-gather plus a separate tail psum.  The
    global scale s is then solved redundantly on every shard from the same
    sorted head array — bitwise identical across shards — and applied
    locally.  At P = 1 this IS `capped_simplex_negentropy_topk`.

    Churn safety (DESIGN.md §15): dead rows must carry z = 0 — the mutable
    caller masks them — so a shard whose live count has fallen below A
    merely pads its head section with zeros, which the water-filling scan
    sorts to the tail and ignores; an all-tombstoned shard contributes
    nothing and the scale stays finite.  If no feasible water level exists
    at all (degenerate z after heavy removal) the scale falls back to 1
    instead of garbage — same guard as the single-device top-A projection.
    """
    z = jnp.maximum(z, 0.0)
    ztop, idx = jax.lax.top_k(z, top_a)
    tail = jnp.sum(z.at[idx].set(0.0))
    packed = jax.lax.all_gather(
        jnp.concatenate([ztop, tail[None]]), model_axis, tiled=True)
    packed = packed.reshape(n_model, top_a + 1)
    heads = packed[:, :top_a].reshape(-1)
    tails = jnp.sum(packed[:, top_a])
    if n_model > 1:
        heads = jnp.sort(heads)[::-1]
    s, ok = _negentropy_scale_from_sorted(heads, tails, h)
    s = jnp.where(ok, s, 1.0)
    return jnp.minimum(1.0, z * s)


# ---------------------------------------------------------------------------
# Collective accounting: the comm budget as a testable number
# ---------------------------------------------------------------------------

_COLLECTIVE_PREFIXES = ("psum", "all_gather", "all_to_all", "ppermute",
                       "reduce_scatter")


def _count_collectives(jaxpr, counts: dict) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for pref in _COLLECTIVE_PREFIXES:
            if name.startswith(pref):
                counts[name] = counts.get(name, 0) + 1
                break
        for v in eqn.params.values():
            for item in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(item, jax_core.ClosedJaxpr):
                    _count_collectives(item.jaxpr, counts)
                elif isinstance(item, jax_core.Jaxpr):
                    _count_collectives(item, counts)


def collectives_per_step(fn: Callable, *example_args, **example_kwargs):
    """Count the cross-device collectives one call of `fn` lowers to.

    Traces `fn` with `jax.make_jaxpr` and walks the program (descending
    into pjit / shard_map / scan sub-jaxprs), tallying primitives whose
    name starts with psum / all_gather / all_to_all / ppermute /
    reduce_scatter.  Returns (total, {primitive name: count}).

    This is static accounting on the traced program — no devices run — so
    `benchmarks/distributed_bench.py` can report the budget as a bench
    column and `tests/test_collectives.py` can pin it against refactors
    that would reintroduce per-candidate gathers, all without timing
    noise.  Per-step counts are per *traced call*; a scan over T steps
    reports one step's body count once (the walker counts program sites,
    not executions).
    """
    closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
    counts: dict = {}
    _count_collectives(closed.jaxpr, counts)
    return sum(counts.values()), counts


# ---------------------------------------------------------------------------
# The roofline cell: stateless retrieval + OMA step on thresholded y
# ---------------------------------------------------------------------------

def make_retrieval_step(mesh, *, n_shard: int, d: int, c: int, k: int,
                        c_f: float, h: int, eta: float, top_a: int,
                        batch_axes=("data",), model_axis: str = "model",
                        scan_chunk: int = 0, ivf: ShardedIVF | None = None):
    """Returns step(catalog_shard, y, requests) -> (y_new, answer, metrics)
    wrapped in shard_map over `mesh`.

    catalog: (N, d) sharded P(model, None);  y: (N,) sharded P(model);
    requests: (B, d) sharded P(batch_axes, None).
    scan_chunk > 0 routes the local scan through the fused kernels
    (`ops.topk_l2_fused`: Pallas l2_topk on TPU, chunked XLA oracle
    elsewhere — memory-roofline optimization; 0 = paper-faithful full
    matrix).  `ivf` switches each shard to probing only its own inverted
    lists (`ops.ivf_scan_topk` / oracle) — the approximate-index serving
    configuration of Sec. IV-B at pod scale.

    The answer is the (B, k) global object ids of the k cheapest augmented
    copies per request (Eq. 2 on the merged candidates); -1 marks answer
    slots a starved IVF probe could not fill with a real candidate.
    """
    n_model = _axis_size(mesh, model_axis)
    n_batch = _axis_size(mesh, batch_axes)
    n = n_shard * n_model
    _check_ivf_matches_mesh(ivf, n_model)

    def step(catalog, y, requests, *ivf_args):
        # ---- 1. local distance scan + top-C (per shard) -----------------
        ivf_shard = (ivf_args[0], ivf_args[1], ivf.nprobe) if ivf else None
        loc_d, loc_ids = _local_scan(requests, catalog, c, scan_chunk,
                                     ivf_shard)
        my_shard = jax.lax.axis_index(model_axis)
        off = my_shard * n_shard

        # ---- 2. ONE packed merge over `model`: [d, gid, y] ---------------
        # (x doesn't exist in the retrieval cell — serving thresholds y)
        payload = _candidate_payload(loc_d, loc_ids, loc_ids < 0, off, n,
                                     y, y)[..., :3]
        (cand_d, (idf, y_cand)), = _packed_merge(payload, (c,), n_model,
                                                 model_axis)
        cand_ids = _f32_to_ids(idf)
        cand_d = jnp.where(jnp.isfinite(cand_d), cand_d, BIG_COST)

        # ---- 3. serve + subgradient (Eq. 2 / Eq. 55) ---------------------
        serve = jax.vmap(lambda dd, xx: gain_lib.serve(dd, xx, k, c_f))(
            cand_d, (y_cand > 0.5).astype(cand_d.dtype))
        _, g_cand = jax.vmap(
            lambda dd, yy: gain_lib.gain_and_subgradient(dd, yy, k, c_f))(
            cand_d, y_cand)
        answers = jnp.take_along_axis(cand_ids, serve.answer_ids, axis=1)
        # IVF underflow can leave < k real candidates; those answer slots
        # carry the out-of-range sentinel — surface them as id = -1 (the
        # kernels' underflow convention) rather than a clamping-prone n.
        answers = jnp.where(answers < n, answers, -1)

        # ---- 4. route subgradients to owning shards ----------------------
        g_shard = _route_subgradients(g_cand, cand_ids, None, off, n_shard,
                                      batch_axes, n_batch)

        # ---- 5. OMA + distributed projection -----------------------------
        z = mirror_maps.dual_ascent_step(y, g_shard, eta,
                                         mirror_maps.NEGENTROPY)
        y_new = jnp.clip(
            _distributed_projection(z, float(h), top_a, n_model, model_axis),
            1e-12, 1.0)

        metrics = {
            "gain": jax.lax.pmean(jnp.mean(serve.gain), batch_axes),
            "served_local": jax.lax.pmean(
                jnp.mean(jnp.sum(serve.from_cache, axis=1).astype(jnp.float32)),
                batch_axes),
        }
        return y_new, answers, metrics

    in_specs = [P(model_axis, None), P(model_axis), P(batch_axes, None)]
    if ivf is not None:
        in_specs += [P(model_axis, None), P(model_axis, None)]
    mapped = shard_map(
        step, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(model_axis), P(batch_axes, None),
                   {"gain": P(), "served_local": P()}),
        check_vma=False,
    )
    if ivf is None:
        return mapped
    return lambda catalog, y, requests: mapped(
        catalog, y, requests, ivf.centroids, ivf.invlists)


def reference_step(catalog, y, requests, *, c, k, c_f, h, eta, top_a):
    """Single-device oracle with identical semantics (for tests)."""
    from repro.core import projection

    d2 = pairwise_dissimilarity(requests, catalog)
    neg, ids = jax.lax.top_k(-d2, c)
    cand_d = -neg
    y_cand = y[ids]
    serve = jax.vmap(lambda dd, xx: gain_lib.serve(dd, xx, k, c_f))(
        cand_d, (y_cand > 0.5).astype(cand_d.dtype))
    _, g_cand = jax.vmap(
        lambda dd, yy: gain_lib.gain_and_subgradient(dd, yy, k, c_f))(
        cand_d, y_cand)
    g = jnp.zeros_like(y).at[ids.reshape(-1)].add(g_cand.reshape(-1))
    z = y * jnp.exp(jnp.clip(eta * g, -60.0, 60.0))
    y_new = projection.capped_simplex_negentropy_topk(z, h, top_a)
    answers = jnp.take_along_axis(ids, serve.answer_ids, axis=1)
    return jnp.clip(y_new, 1e-12, 1.0), answers


# ---------------------------------------------------------------------------
# The serving twin: sharded make_step_batched / make_replay_batched
# ---------------------------------------------------------------------------

def make_step_sharded(
    cfg: policy_lib.AcaiConfig, mesh, catalog: jax.Array, batch: int, *,
    eta_scale: float | None = None, model_axis: str = "model",
    batch_axes=("data",), scan_chunk: int = 0,
    ivf: ShardedIVF | None = None, top_a: int | None = None,
) -> Callable:
    """Sharded mini-batch step: (CacheState, requests (B, d)) ->
    (CacheState', StepMetrics (B,)) — the multi-device twin of
    `policy.make_step_batched` + `exact_candidate_fn_batched`.

    The candidate scan (per-shard fused top-k + ONE packed top-C merge
    carrying ids and y/x state in the same exchange), serve/gain/
    subgradient, and the OMA + water-filling projection all run under
    shard_map over `mesh` (catalog/y/x sharded P(model), requests
    P(batch_axes)); rounding and metric assembly reuse the policy-layer
    code on the (small) merged state outside the map.

    Per-step collectives (pinned by tests/test_collectives.py): 2 on a
    (1, P) serving mesh — the merge gather and the projection gather —
    plus 1 subgradient gather when the batch axes are real (> 1 device).
    The IVF/scan_chunk path spends one extra merge gather, issued before
    the cached-row scan so the exchange overlaps local compute.

    Bit-consistency contract (pinned by tests/test_distributed_acai.py):
    on a 1-device mesh with `scan_chunk = 0`, `ivf = None` and
    `cfg.oma.projection_topk == top_a`, every carried state and metric is
    bitwise identical to `make_step_batched` with the exact candidate
    generator.  `top_a` defaults to `cfg.oma.projection_topk` (or 2h + 64)
    per shard — headroom for the distributed projection, Sec. IV-F.

    Requires the negentropy mirror map (the distributed water-filling
    solves the negentropy scale; euclidean would need a different exchange).
    """
    if cfg.oma.mirror != mirror_maps.NEGENTROPY:
        raise NotImplementedError(
            "make_step_sharded requires the negentropy mirror map")
    n, d = catalog.shape
    n_model = _axis_size(mesh, model_axis)
    n_batch = _axis_size(mesh, batch_axes)
    if n % n_model:
        raise ValueError(
            f"catalog rows ({n}) must divide by the mesh's {model_axis} "
            f"axis ({n_model})")
    if batch % n_batch:
        raise ValueError(
            f"batch size {batch} must divide by the mesh's batch axes "
            f"{batch_axes} (total size {n_batch}); note serve_update "
            f"(B = 1) only exists on meshes with size-1 batch axes")
    _check_ivf_matches_mesh(ivf, n_model)
    n_shard = n // n_model
    a = min(n_shard, top_a or cfg.oma.projection_topk or 2 * cfg.h + 64)
    cfg_up = policy_lib.scaled_config(cfg, batch, eta_scale)

    def local(catalog_shard, y, x, rs, *ivf_args):
        my_shard = jax.lax.axis_index(model_axis)
        off = my_shard * n_shard
        b = rs.shape[0]

        # ---- candidates: per-shard scan + ONE packed top-C merge --------
        local_overflow = jnp.zeros((), jnp.int32)
        if scan_chunk == 0 and ivf is None:
            # paper-faithful / bit-consistent path: one (b, n_shard) GEMM
            # feeds both the remote top-k and the cached-row top-k, exactly
            # as exact_candidate_fn_batched does on the full catalog (no
            # cached-row gather bound, so nothing can truncate).  Both
            # candidate sections ship in a single payload gather.
            d_full = pairwise_dissimilarity(rs, catalog_shard)
            neg_r, loc_r = jax.lax.top_k(-d_full, cfg.c_remote)
            d_cached = jnp.where(x[None, :] > 0.5, d_full, jnp.inf)
            neg_l, loc_l = jax.lax.top_k(-d_cached, cfg.c_local)
            payload = jnp.concatenate([
                _candidate_payload(-neg_r, loc_r, jnp.zeros(neg_r.shape, bool),
                                   off, n, y, x),
                _candidate_payload(-neg_l, loc_l, jnp.zeros(neg_l.shape, bool),
                                   off, n, y, x)], axis=1)
            merged = _packed_merge(payload, (cfg.c_remote, cfg.c_local),
                                   n_model, model_axis)
        else:
            ivf_shard = ((ivf_args[0], ivf_args[1], ivf.nprobe)
                         if ivf else None)
            d_r, loc_r = _local_scan(rs, catalog_shard, cfg.c_remote,
                                     scan_chunk, ivf_shard)
            # the remote merge is issued FIRST, before any cached-row
            # work it doesn't depend on: XLA overlaps the exchange with
            # the gather + GEMM below (comm/compute overlap, DESIGN.md
            # §15) at the price of one extra collective vs the exact path.
            remote = _packed_merge(
                _candidate_payload(d_r, loc_r, loc_r < 0, off, n, y, x),
                (cfg.c_remote,), n_model, model_axis)[0]
            # cached rows: gather once per shard (static 2h + 64 bound,
            # same policy as index_candidate_fn_batched) + one small GEMM.
            cap = min(n_shard, 2 * cfg.h + 64)
            if cfg.debug:
                # same truncation-visibility contract as the single-device
                # step (StepMetrics.local_overflow): per-shard excess over
                # the static gather bound, summed over the model axis.
                occ = jnp.sum((x > 0.5).astype(jnp.int32))
                local_overflow = jax.lax.psum(
                    jnp.maximum(occ - cap, 0), model_axis)
            cached = jnp.nonzero(x > 0.5, size=cap, fill_value=-1)[0]
            cached_embs = catalog_shard[jnp.clip(cached, 0, n_shard - 1)]
            d_loc = pairwise_dissimilarity(rs, cached_embs)
            d_loc = jnp.where((cached >= 0)[None, :], d_loc, jnp.inf)
            neg_l, pos = jax.lax.top_k(-d_loc, cfg.c_local)
            loc_l = jnp.where(jnp.isfinite(neg_l), cached[pos], 0)
            local_m = _packed_merge(
                _candidate_payload(-neg_l, loc_l,
                                   jnp.zeros(neg_l.shape, bool), off, n,
                                   y, x),
                (cfg.c_local,), n_model, model_axis)[0]
            merged = [remote, local_m]

        (d_remote, cols_r), (d_local, cols_l) = merged
        ids = jnp.concatenate([_f32_to_ids(cols_r[0]),
                               _f32_to_ids(cols_l[0])], axis=1)   # (b, C)
        dcand = jnp.concatenate([d_remote, d_local], axis=1)
        y_at = jnp.concatenate([cols_r[1], cols_l[1]], axis=1)
        x_at = jnp.concatenate([cols_r[2], cols_l[2]], axis=1)

        # ---- slab assembly: exactly exact_candidate_fn_batched ----------
        valid = policy_lib.dedup_mask_batched(ids, n)
        cached_ok = jnp.concatenate(
            [jnp.ones((b, cfg.c_remote), bool),
             x_at[:, cfg.c_remote:] > 0.5], axis=1)
        valid = valid & cached_ok
        dcand = jnp.where(valid & jnp.isfinite(dcand), dcand, BIG_COST)

        # ---- serve + gain/subgradient (vs the same x_t / y_t) -----------
        x_cand = jnp.where(valid, x_at, 0.0)
        y_cand = jnp.where(valid, y_at, 0.0)
        served = gain_lib.serve_batch(dcand, x_cand, cfg.k, cfg.c_f)
        gain_frac, g_cand = gain_lib.gain_and_subgradient_batch(
            dcand, y_cand, cfg.k, cfg.c_f)

        # ---- route subgradients to owning y-shards ----------------------
        g_shard = _route_subgradients(g_cand, ids, valid, off, n_shard,
                                      batch_axes, n_batch,
                                      denom=float(batch))

        # ---- OMA + distributed water-filling projection -----------------
        z = mirror_maps.dual_ascent_step(y, g_shard, cfg_up.oma.eta,
                                         cfg.oma.mirror)
        y_new = jnp.clip(
            _distributed_projection(z, cfg.h, a, n_model, model_axis),
            oma_lib.Y_FLOOR, 1.0)

        served_local = jnp.sum(served.from_cache.astype(jnp.int32), axis=1)
        return (y_new, served.gain, gain_frac, served.cost, served_local,
                local_overflow)

    in_specs = [P(model_axis, None), P(model_axis), P(model_axis),
                P(batch_axes, None)]
    extra = ()
    if ivf is not None:
        in_specs += [P(model_axis, None), P(model_axis, None)]
        extra = (ivf.centroids, ivf.invlists)
    mapped = shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs),
        # local_overflow is a model-axis psum (or a constant 0): identical
        # on every shard, hence replicated
        out_specs=(P(model_axis),) + (P(batch_axes),) * 4 + (P(),),
        check_vma=False,
    )

    def step(state: policy_lib.CacheState, rs: jax.Array):
        key, k_round = jax.random.split(state.key)
        y_new, gain_int, gain_frac, cost, served_local, overflow = mapped(
            catalog, state.y, state.x, rs, *extra)
        return policy_lib.finish_step_batched(
            cfg_up, state, key, k_round, batch, y_new, gain_int, gain_frac,
            cost, served_local, local_overflow=overflow)

    return step


def make_replay_sharded(
    cfg: policy_lib.AcaiConfig, mesh, catalog: jax.Array, batch: int,
    **kwargs,
) -> Callable:
    """Sharded mini-batched whole-trace replay — the multi-device twin of
    `policy.make_replay_batched` (same signature contract: (state,
    requests (T, d)) -> (state', StepMetrics (T,)), T divisible by batch).

    On a 1-device mesh with `cfg.oma.projection_topk == top_a` this is
    bit-consistent with `make_replay_batched` + exact candidates; on P
    shards the per-step communication is one packed candidate gather plus
    the (P·(A + 1)) projection scalars (DESIGN.md §15).
    """
    return policy_lib.make_replay_from_step(
        make_step_sharded(cfg, mesh, catalog, batch, **kwargs), batch)


# ---------------------------------------------------------------------------
# Sharded churn: the mutable-catalog serving mode at pod scale
# ---------------------------------------------------------------------------

def make_mutable_step_sharded(
    cfg: policy_lib.AcaiConfig, mesh, batch: int, *,
    eta_scale: float | None = None, model_axis: str = "model",
    batch_axes=("data",), top_a: int | None = None,
) -> Callable:
    """Sharded twin of the mutable-catalog serving mode (DESIGN.md §10/§15):
    jitted (state, requests (B, d), catalog (cap, d), alive (cap,)) ->
    (state', StepMetrics (B,)).

    The catalog slab and its liveness mask are RUNTIME arguments — exactly
    like `exact_mutable_candidates` — so online add/remove/compact change
    only array values at fixed capacity and never retrace; a capacity-
    doubling growth retraces once per doubling, same as the single-device
    path.  Per shard: the scan masks tombstoned rows to +inf, the merged
    slab uses the capacity sentinel for empty candidate slots, dead-row z
    mass is re-zeroed before the distributed projection (a shard whose
    live count has fallen below top-A — or to zero — contributes padded
    zero heads the water-filling ignores), and the post-projection alive
    mask keeps the Y_FLOOR clip from resurrecting removed rows
    (`apply_candidates_batched`'s invalidation invariant, shard-wise).

    Bit-consistency contract (pinned by tests/test_sharded_churn.py): on a
    1-device mesh with `cfg.oma.projection_topk == top_a`, state and every
    metric are bitwise `exact_mutable_candidates` + `make_mutable_step`,
    including under churn, capacity growth and compaction.

    Per-step collectives: the same 2 (serving mesh) / 3 (data-parallel)
    as the static exact step — mutability adds zero communication.
    """
    if cfg.oma.mirror != mirror_maps.NEGENTROPY:
        raise NotImplementedError(
            "make_mutable_step_sharded requires the negentropy mirror map")
    n_model = _axis_size(mesh, model_axis)
    n_batch = _axis_size(mesh, batch_axes)
    if batch % n_batch:
        raise ValueError(
            f"batch size {batch} must divide by the mesh's batch axes "
            f"{batch_axes} (total size {n_batch})")
    cfg_up = policy_lib.scaled_config(cfg, batch, eta_scale)

    def local(y, x, rs, cat_shard, alive_shard):
        my_shard = jax.lax.axis_index(model_axis)
        n_shard = cat_shard.shape[0]
        cap = n_shard * n_model
        off = my_shard * n_shard
        b = rs.shape[0]
        a = min(n_shard, top_a or cfg.oma.projection_topk or 2 * cfg.h + 64)

        # ---- candidates: exact_mutable_candidates, shard-wise -----------
        d_full = pairwise_dissimilarity(rs, cat_shard)
        d_full = jnp.where(alive_shard[None, :], d_full, jnp.inf)
        neg_r, loc_r = jax.lax.top_k(-d_full, cfg.c_remote)
        miss_r = ~jnp.isfinite(neg_r)     # fewer live rows than c_remote
        d_cached = jnp.where(x[None, :] > 0.5, d_full, jnp.inf)
        neg_l, loc_l = jax.lax.top_k(-d_cached, cfg.c_local)
        payload = jnp.concatenate([
            _candidate_payload(-neg_r, loc_r, miss_r, off, cap, y, x),
            _candidate_payload(-neg_l, loc_l, jnp.zeros(neg_l.shape, bool),
                               off, cap, y, x)], axis=1)
        (d_remote, cols_r), (d_local, cols_l) = _packed_merge(
            payload, (cfg.c_remote, cfg.c_local), n_model, model_axis)
        ids = jnp.concatenate([_f32_to_ids(cols_r[0]),
                               _f32_to_ids(cols_l[0])], axis=1)
        dcand = jnp.concatenate([d_remote, d_local], axis=1)
        y_at = jnp.concatenate([cols_r[1], cols_l[1]], axis=1)
        x_at = jnp.concatenate([cols_r[2], cols_l[2]], axis=1)

        valid = policy_lib.dedup_mask_batched(ids, cap)
        cached_ok = jnp.concatenate(
            [jnp.ones((b, cfg.c_remote), bool),
             x_at[:, cfg.c_remote:] > 0.5], axis=1)
        valid = valid & cached_ok
        dcand = jnp.where(valid, dcand, BIG_COST)

        # ---- serve + gain/subgradient -----------------------------------
        x_cand = jnp.where(valid, x_at, 0.0)
        y_cand = jnp.where(valid, y_at, 0.0)
        served = gain_lib.serve_batch(dcand, x_cand, cfg.k, cfg.c_f)
        gain_frac, g_cand = gain_lib.gain_and_subgradient_batch(
            dcand, y_cand, cfg.k, cfg.c_f)

        g_shard = _route_subgradients(g_cand, ids, valid, off, n_shard,
                                      batch_axes, n_batch,
                                      denom=float(batch))

        # ---- OMA + distributed projection over the live mask ------------
        z = mirror_maps.dual_ascent_step(y, g_shard, cfg_up.oma.eta,
                                         cfg.oma.mirror)
        # dead rows carry z = 0 by the invalidation invariant (y = 0 and
        # no routed mass); re-assert it so a shard tombstoned below top-A
        # pads the projection exchange with zeros instead of stale mass
        z = jnp.where(alive_shard, z, 0.0)
        y_new = jnp.clip(
            _distributed_projection(z, cfg.h, a, n_model, model_axis),
            oma_lib.Y_FLOOR, 1.0)
        y_new = jnp.where(alive_shard, y_new, 0.0)

        served_local = jnp.sum(served.from_cache.astype(jnp.int32), axis=1)
        return (y_new, served.gain, gain_frac, served.cost, served_local)

    mapped = shard_map(
        local, mesh=mesh,
        in_specs=(P(model_axis), P(model_axis), P(batch_axes, None),
                  P(model_axis, None), P(model_axis)),
        out_specs=(P(model_axis),) + (P(batch_axes),) * 4,
        check_vma=False,
    )

    @jax.jit
    def step(state: policy_lib.CacheState, rs, catalog, alive):
        key, k_round = jax.random.split(state.key)
        y_new, gain_int, gain_frac, cost, served_local = mapped(
            state.y, state.x, rs, catalog, alive)
        return policy_lib.finish_step_batched(
            cfg_up, state, key, k_round, batch, y_new, gain_int, gain_frac,
            cost, served_local)

    return step


# ---------------------------------------------------------------------------
# Owner-shard mutation routing: global-id arithmetic over contiguous shards
# ---------------------------------------------------------------------------

def owner_shard(ids, cap: int, n_model: int) -> np.ndarray:
    """Owning shard of each global slab row: shard p owns the contiguous
    block [p * cap / P, (p + 1) * cap / P) — pure arithmetic, no lookup
    table, so routing survives capacity growth and compaction as long as
    the capacity stays a multiple of the mesh (which the doubling schedule
    and the compaction round-up guarantee)."""
    if cap % n_model:
        raise ValueError(
            f"slab capacity {cap} must divide by the mesh's {n_model} "
            f"model shards")
    return np.asarray(ids, np.int64) // (cap // n_model)


def route_ids_by_owner(ids, cap: int, n_model: int):
    """Group a global-id mutation batch by owning shard.

    Returns [(shard, ids_subset np.int32), ...] in ascending shard order;
    each subset keeps the batch's original relative order, and the
    concatenation of all subsets is a permutation of the input (the
    round-trip property pinned by tests/test_sharded_churn.py).  At P = 1
    the single group IS the input — the single-device mutation path,
    bitwise."""
    ids = np.atleast_1d(np.asarray(ids, np.int32))
    own = owner_shard(ids, cap, n_model)
    return [(int(p), ids[own == p]) for p in np.unique(own)]


def sharded_slab_append(emb, valid, n_slots: int, vectors, n_model: int):
    """`repro.index.base.slab_append` with the write routed per owning
    shard (DESIGN.md §15): the appended rows [n_slots, n_slots + B) are
    split at shard-block boundaries (a batch can straddle two shards'
    contiguous blocks) and each run is written with its own donated
    `_slab_write` into the owner's slice.  Growth follows the same
    capacity-doubling schedule as the single-device path, rounded up to a
    multiple of the mesh so shard blocks never fracture (a no-op for the
    power-of-two capacities the doubling schedule produces on power-of-two
    meshes).  At P = 1 there is one run and this IS `slab_append` —
    bitwise, including the growth schedule.

    Returns (emb', valid', ids) with the `slab_append` contract:
    monotonic never-recycled ids = arange(n_slots, n_slots + B).
    """
    from repro.index.base import (_slab_write, bucket_width, grow_capacity,
                                  pad_rows, run_device)

    vec_np = np.atleast_2d(np.asarray(vectors, np.float32))
    b = vec_np.shape[0]
    cap = emb.shape[0]
    if cap % n_model:
        raise ValueError(
            f"slab capacity {cap} must divide by the mesh's {n_model} "
            f"model shards")
    while True:
        # split the append into per-shard-block runs, then check every
        # run's PADDED write window (the dynamic_update_slice clamp guard,
        # see slab_append) against capacity; growth moves the block
        # boundaries, so re-split until the layout is stable
        block = cap // n_model
        runs = []
        start = 0
        while start < b:
            row = n_slots + start
            run = min(b - start, (row // block + 1) * block - row)
            runs.append((row, run))
            start += run
        need = max(row + bucket_width(run) for row, run in runs)
        if need <= cap:
            break
        new_cap = grow_capacity(0, need, cap)
        new_cap += (-new_cap) % n_model
        emb = jnp.pad(emb, ((0, new_cap - cap), (0, 0)))
        valid = jnp.pad(valid, (0, new_cap - cap), constant_values=False)
        cap = new_cap
    for row, run in runs:
        lo = row - n_slots
        emb, valid = run_device(
            _slab_write, emb, valid, pad_rows(vec_np[lo:lo + run]),
            np.int32(row), np.int32(run))
    return emb, valid, np.arange(n_slots, n_slots + b, dtype=np.int32)
