"""AÇAI policy: request serving + OMA cache updates (paper Sec. IV).

Three entry points:

* `make_replay(...)` — a fully-jitted `lax.scan` over a request trace,
  carrying (y_t, x_t, key).  Per request it (1) builds the candidate set
  from the two indexes, (2) serves per Eq. (2) from x_t, (3) computes the
  subgradient Eq. (55) at y_t, (4) applies OMA + projection, (5) rounds to
  x_{t+1}.

* `make_replay_batched(...)` — the benchmark/serving hot path: scans the
  trace in request *mini-batches* of size B, vmapping serve/gain/
  subgradient per request and folding the batch into a single OMA +
  projection + rounding update (mini-batch mirror ascent, DESIGN.md §6).
  Bit-exact with make_replay at B = 1.

* `AcaiCache` — an object wrapper over the same jitted steps for the
  serving tier (repro.serve.semantic_cache) where requests arrive one by
  one (`serve_update`) or in batches (`serve_update_batch`).

Candidate sets: the union of kNN(r, local catalog) and kNN(r, remote
catalog) as returned by the two (approximate) indexes, deduplicated by
masking (duplicates get cost BIG and weight 0 so they are exactly neutral
in the augmented-catalog accounting — see repro.core.gain).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gain as gain_lib
from repro.core import oma as oma_lib
from repro.core import rounding as rounding_lib
from repro.core.costs import BIG_COST, pairwise_dissimilarity
from repro.index.base import IndexSpec, build_index


class StepMetrics(NamedTuple):
    gain_int: jax.Array    # G(r_t, x_t) — what the system actually earns
    gain_frac: jax.Array   # G(r_t, y_t) — fractional gain (analysis)
    cost: jax.Array        # C(r_t, x_t)
    served_local: jax.Array  # how many of the k answers came from the cache
    fetched: jax.Array     # cache-update traffic (# objects fetched)
    occupancy: jax.Array   # sum x_t
    # debug-mode counter (AcaiConfig.debug): cached rows the candidate
    # generator's static `local_cap` gather silently truncated this step —
    # max(0, |x_t| - cap), 0 when debug is off or the generator is uncapped
    # (see repro.index.candidates._local_cap).
    local_overflow: jax.Array | int = 0
    # resilient-serving counters (DESIGN.md §11): all zero on the
    # fault-free path, populated by repro.serve.resilience when a
    # RemoteBackend is attached.
    degraded: jax.Array | int = 0         # served locally under failure
    shed: jax.Array | int = 0             # failed, nothing local in ceiling
    remote_failures: jax.Array | int = 0  # request's remote tier failed
    retries: jax.Array | int = 0          # extra attempts beyond the first
    deadline_misses: jax.Array | int = 0  # deadline budget exceeded
    # answer-cache tier counters (DESIGN.md §13): all zero without an
    # AnswerCacheSpec, booked host-side by AcaiCache._serve_batch_direct.
    answer_hits: jax.Array | int = 0      # request's answer was memoized
    answer_misses: jax.Array | int = 0    # request needed the fused scan
    answer_invalidations: jax.Array | int = 0  # entries dropped by churn
                                               # since the previous step
                                               # (booked on the batch's
                                               # first request)


def shed_only_metrics(batch: int) -> StepMetrics:
    """StepMetrics rows for requests that never reached a policy step:
    zero gain/cost/occupancy with ``shed = 1`` on every row.

    The online serving engine's admission control (queue-depth cap,
    deadline shedding — DESIGN.md §12) books its victims through this
    helper, so engine-level shedding lands in the *same* counters the
    resilient tier populates (DESIGN.md §11) and downstream aggregation
    (NAG, goodput, shed share) never branches on who shed the request.
    """
    zf = np.zeros(batch, np.float64)
    zi = np.zeros(batch, np.int32)
    return StepMetrics(
        gain_int=zf, gain_frac=zf.copy(), cost=zf.copy(),
        served_local=zi, fetched=zi.copy(), occupancy=zf.copy(),
        local_overflow=zi.copy(), degraded=zi.copy(),
        shed=np.ones(batch, np.int32), remote_failures=zi.copy(),
        retries=zi.copy(), deadline_misses=zi.copy(),
        answer_hits=zi.copy(), answer_misses=zi.copy(),
        answer_invalidations=zi.copy())


class CacheState(NamedTuple):
    y: jax.Array  # (N,) fractional state
    x: jax.Array  # (N,) physical cache indicator
    t: jax.Array  # step counter
    key: jax.Array


def dedup_mask(ids: jax.Array, n: int) -> jax.Array:
    """valid[i] = ids[i] is a real id (< n) and its first occurrence."""
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    dup_sorted = jnp.concatenate(
        [jnp.zeros((1,), bool), sorted_ids[1:] == sorted_ids[:-1]]
    )
    dup = jnp.zeros_like(dup_sorted).at[order].set(dup_sorted)
    return (ids < n) & ~dup


dedup_mask_batched = jax.vmap(dedup_mask, in_axes=(0, None))


def exact_candidate_fn_batched(
    catalog: jax.Array, c_remote: int, c_local: int, metric: str = "sqeuclidean"
) -> Callable:
    """Batched candidate generator backed by exact (flat) search on both
    sides: (B, d) requests x (N,) cache state -> (B, C) candidate slabs.

    Models *perfect-recall* indexes; the approximate variants live in
    repro.index.candidates (same signatures) and plug in here.  One (B, N)
    distance GEMM feeds both the remote top-k and the cached-row top-k, so
    the MXU sees the whole mini-batch at once (DESIGN.md §6).
    """
    n = catalog.shape[0]

    def fn(rs: jax.Array, x: jax.Array):
        b = rs.shape[0]
        with jax.named_scope("acai.remote"):
            d_full = pairwise_dissimilarity(rs, catalog, metric)  # (B, N)
            _, ids_remote = jax.lax.top_k(-d_full, c_remote)
        with jax.named_scope("acai.local"):
            d_cached = jnp.where(x[None, :] > 0.5, d_full, jnp.inf)
            _, ids_local = jax.lax.top_k(-d_cached, c_local)
        with jax.named_scope("acai.merge"):
            ids = jnp.concatenate([ids_remote, ids_local], axis=1)
            valid = dedup_mask_batched(ids, n)
            # a "local" candidate slot is only valid if that object is
            # cached
            cached_ok = jnp.concatenate(
                [jnp.ones((b, c_remote), bool), x[ids_local] > 0.5], axis=1
            )
            valid = valid & cached_ok
            d = jnp.where(
                valid,
                jnp.take_along_axis(d_full, jnp.clip(ids, 0, n - 1), axis=1),
                BIG_COST,
            )
        return ids, d, valid

    return fn


def per_request_view(candidate_fn_batched: Callable) -> Callable:
    """Adapt a batched candidate generator to the per-request signature
    fn(r (d,), x (N,)) -> (ids (C,), d (C,), valid (C,)) as its B = 1 view,
    so sequential and batched replays share one code path bit-for-bit."""

    def fn(r: jax.Array, x: jax.Array):
        ids, d, valid = candidate_fn_batched(r[None, :], x)
        return ids[0], d[0], valid[0]

    if hasattr(candidate_fn_batched, "local_cap"):
        fn.local_cap = candidate_fn_batched.local_cap
    return fn


def exact_candidate_fn(
    catalog: jax.Array, c_remote: int, c_local: int, metric: str = "sqeuclidean"
) -> Callable:
    """Per-request view of exact_candidate_fn_batched (B = 1)."""
    return per_request_view(
        exact_candidate_fn_batched(catalog, c_remote, c_local, metric)
    )


@partial(jax.jit, static_argnames=("c_remote", "c_local", "metric"))
def exact_mutable_candidates(
    rs: jax.Array, x: jax.Array, catalog: jax.Array, alive: jax.Array,
    c_remote: int, c_local: int, metric: str = "sqeuclidean",
):
    """Mutable-catalog twin of `exact_candidate_fn_batched` (DESIGN.md §10).

    Same math, but the catalog slab and its liveness mask are *runtime*
    arguments: online add/remove/refresh changes only array values, so the
    serving step never retraces at fixed capacity (shapes move only on
    capacity-doubling growth).  Tombstoned/unassigned rows scan as +inf
    and resolve to invalid slots, so a removed object can never be served
    or fetched.  With `alive` all-True the outputs match the static
    generator exactly.

    Returns (ids (B, C), dists (B, C), valid (B, C)) — the shared
    candidate-slab layout (C = c_remote + c_local, id = N marks an invalid
    slot, BIG_COST on its distance).
    """
    n = catalog.shape[0]
    b = rs.shape[0]
    d_full = pairwise_dissimilarity(rs, catalog, metric)         # (B, N)
    d_full = jnp.where(alive[None, :], d_full, jnp.inf)
    neg_r, ids_remote = jax.lax.top_k(-d_full, c_remote)
    # a dead/unassigned row can only be selected when fewer than c_remote
    # rows are live; flag it with the invalid sentinel n
    ids_remote = jnp.where(jnp.isfinite(neg_r), ids_remote, n)
    d_cached = jnp.where(x[None, :] > 0.5, d_full, jnp.inf)
    _, ids_local = jax.lax.top_k(-d_cached, c_local)
    ids = jnp.concatenate([ids_remote, ids_local], axis=1)
    valid = dedup_mask_batched(ids, n)
    # a "local" candidate slot is only valid if that object is cached (the
    # x(dead) = 0 invalidation invariant also keeps removed rows out here)
    cached_ok = jnp.concatenate(
        [jnp.ones((b, c_remote), bool), x[ids_local] > 0.5], axis=1
    )
    valid = valid & cached_ok
    d = jnp.where(
        valid,
        jnp.take_along_axis(d_full, jnp.clip(ids, 0, n - 1), axis=1),
        BIG_COST,
    )
    return ids, d, valid


@dataclasses.dataclass(frozen=True)
class AcaiConfig:
    h: int                      # cache capacity (objects)
    k: int = 10                 # answers per request
    c_f: float = 1.0            # fetching cost
    c_remote: int = 64          # remote-index candidates (>= k!)
    c_local: int = 16           # local-index candidates
    oma: oma_lib.OMAConfig = dataclasses.field(default_factory=oma_lib.OMAConfig)
    # remote-catalog index selection (DESIGN.md §8): an IndexSpec such as
    # IndexSpec("ivf", {"nlist": 256}) makes AcaiCache build its candidate
    # generator through repro.index.base.build_index; None = exact
    # (perfect-recall) candidates.  On a mesh, "ivf_sharded" selects the
    # per-shard IVF probe; None = the exact sharded scan.
    index: "IndexSpec | None" = None
    # debug instrumentation: books StepMetrics.local_overflow (cached rows
    # truncated by the candidate generator's static local_cap gather).
    debug: bool = False


def _round_state(cfg: AcaiConfig, key, y_new, y_old, x_old, t, width=1):
    mode = cfg.oma.rounding
    if mode == "coupled":
        return rounding_lib.coupled_rounding(key, x_old, y_old, y_new)
    if mode == "independent":
        return rounding_lib.independent_rounding(key, y_new)
    if mode == "depround":
        # Re-round every M requests (Alg. 1 lines 7-9), freeze in between.
        # A batched step covers requests [t, t + width); fire iff a multiple
        # of M lands in that window, so the cadence stays ~M (not
        # lcm(M, width)).  width = 1 reduces to t % M == 0.
        return jax.lax.cond(
            ((-t) % cfg.oma.round_every) < width,
            lambda _: rounding_lib.depround(key, y_new),
            lambda _: x_old,
            None,
        )
    raise ValueError(mode)


def _overflow_counter(cfg: AcaiConfig, candidate_fn: Callable,
                      x: jax.Array) -> jax.Array:
    """Debug-mode truncation counter: how many cached rows exceed the
    candidate generator's static `local_cap` gather bound (those rows are
    silently hidden from local serving — quality loss, not an error)."""
    cap = getattr(candidate_fn, "local_cap", None)
    if not cfg.debug or cap is None:
        return jnp.zeros((), jnp.int32)
    occ = jnp.sum((x > 0.5).astype(jnp.int32))
    return jnp.maximum(occ - cap, 0)


def make_step(cfg: AcaiConfig, candidate_fn: Callable) -> Callable:
    """Build the jitted per-request step: (state, r) -> (state', metrics)."""

    def step(state: CacheState, r: jax.Array):
        key, k_round = jax.random.split(state.key)
        ids, d, valid = candidate_fn(r, state.x)
        x_cand = jnp.where(valid, state.x[jnp.clip(ids, None, state.x.shape[0] - 1)], 0.0)
        y_cand = jnp.where(valid, state.y[jnp.clip(ids, None, state.y.shape[0] - 1)], 0.0)

        served = gain_lib.serve(d, x_cand, cfg.k, cfg.c_f)
        gain_frac, g_cand = gain_lib.gain_and_subgradient(d, y_cand, cfg.k, cfg.c_f)

        g_full = (
            jnp.zeros_like(state.y)
            .at[jnp.clip(ids, None, state.y.shape[0] - 1)]
            .add(jnp.where(valid, g_cand, 0.0))
        )
        y_new = oma_lib.oma_update(state.y, g_full, cfg.h, cfg.oma)
        x_new = _round_state(cfg, k_round, y_new, state.y, state.x, state.t)

        zero = jnp.zeros((), jnp.int32)
        metrics = StepMetrics(
            gain_int=served.gain,
            gain_frac=gain_frac,
            cost=served.cost,
            served_local=jnp.sum(served.from_cache.astype(jnp.int32)),
            fetched=rounding_lib.movement(x_new, state.x),
            occupancy=jnp.sum(x_new),
            local_overflow=_overflow_counter(cfg, candidate_fn, state.x),
            degraded=zero, shed=zero, remote_failures=zero, retries=zero,
            deadline_misses=zero,
        )
        return CacheState(y_new, x_new, state.t + 1, key), metrics

    return step


def init_state(n: int, cfg: AcaiConfig, seed: int = 0, start: str = "uniform") -> CacheState:
    """start='uniform': y_1 = argmin Phi (Alg. 1 line 1); 'empty': cold cache."""
    y = oma_lib.uniform_state(n, cfg.h)
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    if start == "uniform":
        x = rounding_lib.depround(k0, y)
    else:
        x = jnp.zeros((n,), jnp.float32)
    return CacheState(y=y, x=x, t=jnp.zeros((), jnp.int32), key=key)


def make_replay(cfg: AcaiConfig, candidate_fn: Callable) -> Callable:
    """Whole-trace replay: (state, requests (T,d)) -> (state', StepMetrics (T,))."""
    step = make_step(cfg, candidate_fn)

    def replay(state: CacheState, requests: jax.Array):
        return jax.lax.scan(step, state, requests)

    return jit_closed_over_as_args(replay)


@jax.named_scope("acai.rounding")
def finish_step_batched(cfg_up: AcaiConfig, state: CacheState, key, k_round,
                        batch: int, y_new, gain_int, gain_frac, cost,
                        served_local, local_overflow=None):
    """Shared tail of every mini-batch step: rounding + metric assembly +
    state advance.  Used by both `make_step_batched` and
    `repro.core.distributed.make_step_sharded` so the two stay
    bit-consistent by construction (§6 metric reduction: `fetched` books
    the batch's cache-update traffic on its last request, `occupancy`
    repeats the post-update value, `local_overflow` — a per-batch scalar
    like occupancy — repeats the pre-update debug counter)."""
    x_new = _round_state(cfg_up, k_round, y_new, state.y, state.x, state.t,
                         width=batch)
    moved = rounding_lib.movement(x_new, state.x)
    if local_overflow is None:
        local_overflow = jnp.zeros((), jnp.int32)
    zeros = jnp.zeros((batch,), jnp.int32)  # resilience counters: always
    # materialized as arrays so tree_map/reshape over metrics never meets
    # a Python-int leaf (repro.serve.resilience overrides them per batch)
    metrics = StepMetrics(
        gain_int=gain_int, gain_frac=gain_frac, cost=cost,
        served_local=served_local,
        fetched=jnp.concatenate(
            [jnp.zeros((batch - 1,), moved.dtype), moved[None]]),
        occupancy=jnp.full((batch,), jnp.sum(x_new)),
        local_overflow=jnp.full((batch,), local_overflow),
        degraded=zeros, shed=zeros, remote_failures=zeros, retries=zeros,
        deadline_misses=zeros, answer_hits=zeros, answer_misses=zeros,
        answer_invalidations=zeros,
    )
    return CacheState(y_new, x_new, state.t + batch, key), metrics


def make_step_batched(
    cfg: AcaiConfig, candidate_fn_batched: Callable, batch: int,
    eta_scale: float | None = None,
) -> Callable:
    """Mini-batch step: (state, requests (B, d)) -> (state', StepMetrics (B,)).

    Mini-batch online mirror ascent (DESIGN.md §6): all B requests are
    served and differentiated against the *same* state x_t / y_t (candidate
    generation, serve and gain/subgradient vmap per request), the
    subgradients are batch-averaged, and a single OMA + projection +
    rounding update advances the state — the delayed-subgradient form whose
    regret the paper's analysis tolerates.  `eta_scale` (default: B)
    multiplies the learning rate so one averaged step moves as far as B
    sequential steps to first order; at B = 1 everything reduces
    bit-exactly to make_step.

    Metric reduction keeps figures B-invariant: serve metrics are per
    request (vs x_t); `fetched` books the batch's cache-update traffic on
    its last request (zero on the rest); `occupancy` repeats the
    post-update value.
    """
    cfg_up = scaled_config(cfg, batch, eta_scale)

    def step(state: CacheState, rs: jax.Array):
        ids, d, valid = candidate_fn_batched(rs, state.x)     # (B, C)
        return apply_candidates_batched(
            cfg, cfg_up, state, batch, ids, d, valid,
            local_overflow=_overflow_counter(cfg, candidate_fn_batched,
                                             state.x))

    return step


def apply_candidates_batched(cfg: AcaiConfig, cfg_up: AcaiConfig,
                             state: CacheState, batch: int, ids, d, valid,
                             alive=None, local_overflow=None):
    """Shared serve+update tail of every mini-batch step: consumes a
    precomputed candidate slab (ids, d, valid) and runs serve (Eq. 2),
    gain/subgradient (Eq. 55), the averaged OMA + projection update, and
    `finish_step_batched`.  `make_step_batched` traces it right after its
    candidate generator; the mutable-catalog step (`make_mutable_step`)
    jits it standalone, with `alive` enforcing the invalidation invariant
    (y = x = 0 on tombstoned rows, DESIGN.md §10).  One tail, two serving
    modes — with `alive=None` the computation is exactly the static path's.
    """
    key, k_round = jax.random.split(state.key)
    n = state.y.shape[0]
    with jax.named_scope("acai.gain"):
        ids_c = jnp.clip(ids, None, n - 1)
        x_cand = jnp.where(valid, state.x[ids_c], 0.0)
        y_cand = jnp.where(valid, state.y[ids_c], 0.0)

        served = gain_lib.serve_batch(d, x_cand, cfg.k, cfg.c_f)
        gain_frac, g_cand = gain_lib.gain_and_subgradient_batch(
            d, y_cand, cfg.k, cfg.c_f
        )

        g_full = (
            jnp.zeros_like(state.y)
            .at[ids_c.reshape(-1)]
            .add(jnp.where(valid, g_cand, 0.0).reshape(-1) / batch)
        )
    with jax.named_scope("acai.oma"):
        y_new = oma_lib.oma_update(state.y, g_full, cfg.h, cfg_up.oma)
        if alive is not None:
            # invalidation invariant: no fractional mass on dead rows (the
            # Y_FLOOR clip would otherwise resurrect them with 1e-12 mass,
            # and rounding could then physically cache a removed object)
            y_new = jnp.where(alive, y_new, 0.0)
    return finish_step_batched(
        cfg_up, state, key, k_round, batch, y_new, served.gain,
        gain_frac, served.cost,
        jnp.sum(served.from_cache.astype(jnp.int32), axis=1),
        local_overflow=local_overflow)


def scaled_config(cfg: AcaiConfig, batch: int,
                  eta_scale: float | None = None) -> AcaiConfig:
    """Mini-batch learning-rate scaling (DESIGN.md §6): one averaged OMA
    step moves as far as `batch` sequential steps to first order."""
    scale = float(batch) if eta_scale is None else float(eta_scale)
    return dataclasses.replace(
        cfg, oma=dataclasses.replace(cfg.oma, eta=cfg.oma.eta * scale))


def make_mutable_step(cfg: AcaiConfig, batch: int,
                      eta_scale: float | None = None) -> Callable:
    """Jitted tail for the mutable-catalog serving mode (DESIGN.md §10):
    (state, ids, d, valid, alive) -> (state', StepMetrics (B,)).

    Candidate slabs are generated *eagerly* against the current index
    structures (which mutate between steps, so they cannot be closed over
    by a cached jit) and handed to this step; `alive` is the catalog's
    liveness mask, threaded as a runtime argument so add/remove/refresh
    never retraces at fixed capacity.  With `alive` all-True the state
    advance matches `make_step_batched`'s exactly.
    """
    cfg_up = scaled_config(cfg, batch, eta_scale)

    @jax.jit
    def step(state: CacheState, ids, d, valid, alive):
        return apply_candidates_batched(cfg, cfg_up, state, batch, ids, d,
                                        valid, alive=alive)

    return step


def make_replay_from_step(step: Callable, batch: int) -> Callable:
    """Wrap a mini-batch step ((state, (B, d)) -> (state', metrics (B,)))
    into a whole-trace replay: (state, requests (T, d)) -> (state',
    StepMetrics (T,)), T divisible by batch, metrics flattened per request
    so downstream figure code is batch-invariant.  Shared by
    `make_replay_batched` and `repro.core.distributed.make_replay_sharded`
    — one replay contract, two step implementations."""

    def replay(state: CacheState, requests: jax.Array):
        t, dim = requests.shape
        assert t % batch == 0, (
            f"trace length {t} must divide by batch size {batch}"
        )
        state, m = jax.lax.scan(
            step, state, requests.reshape(t // batch, batch, dim)
        )
        return state, jax.tree_util.tree_map(
            lambda a: a.reshape(t, *a.shape[2:]), m
        )

    return jit_closed_over_as_args(replay)


def make_replay_batched(
    cfg: AcaiConfig, candidate_fn_batched: Callable, batch: int,
    eta_scale: float | None = None,
) -> Callable:
    """Mini-batched whole-trace replay.

    (state, requests (T, d)) -> (state', StepMetrics (T,)): the trace is
    scanned in (T / batch) mini-batches (T must divide), metrics come back
    flattened per request so downstream figure code is unchanged.  At
    batch = 1 this is bit-exact with make_replay.
    """
    return make_replay_from_step(
        make_step_batched(cfg, candidate_fn_batched, batch, eta_scale), batch)


# Closed-over arrays at least this large reach a compiled step as runtime
# arguments (see `jit_closed_over_as_args`); smaller ones stay constants.
ARG_BYTES = 1 << 20


def jit_closed_over_as_args(fn: Callable) -> Callable:
    """`jax.jit(fn)`, except that the large arrays `fn` closes over — the
    catalog, index tables, a sharded catalog's shards — reach the compiled
    program as runtime arguments instead of being baked into it as
    constants.  A 1M x 128 catalog is 512 MiB: as a constant it would be
    serialised into the program, constant-folded by the compiler and held
    a second time in device memory by the executable, and a sharded one
    would lose its placement.  Arrays under ARG_BYTES stay constants, so
    a small catalog compiles exactly as under `jax.jit` (the bitwise pins
    between this module's paths rely on one treatment per size).

    `fn` is traced once per argument signature (tree structure, shapes,
    dtypes); `.lower(*args)` gives the lowered program and `._cache_size()`
    the number of compiled programs, as for a jit."""
    cache: dict = {}

    def runner(args):
        leaves, tree = jax.tree_util.tree_flatten(args)
        key = (tree, tuple((np.shape(a), jnp.result_type(a)) for a in leaves))
        hit = cache.get(key)
        if hit is None:
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
            out_tree = jax.tree_util.tree_structure(out_shape)
            consts = list(closed.consts)
            big = [i for i, c in enumerate(consts)
                   if np.size(c) * jnp.result_type(c).itemsize >= ARG_BYTES]

            @jax.jit
            def run(big_vals, *flat):
                cs = list(consts)
                for i, v in zip(big, big_vals):
                    cs[i] = v
                out = jax.core.eval_jaxpr(closed.jaxpr, cs, *flat)
                return jax.tree_util.tree_unflatten(out_tree, out)

            hit = cache[key] = (run, [consts[i] for i in big])
        run, big_vals = hit
        return run, big_vals, leaves

    def call(*args):
        run, big_vals, leaves = runner(args)
        return run(big_vals, *leaves)

    def lower(*args):
        run, big_vals, leaves = runner(args)
        return run.lower(big_vals, *leaves)

    call.lower = lower
    call._cache_size = lambda: sum(run._cache_size() for run, _ in
                                   cache.values())
    return call


class AcaiCache:
    """Object API over the jitted step, for the online serving tier.

    Backend selection is config-driven (DESIGN.md §8): when
    `cfg.index` holds an `IndexSpec`, the remote-catalog index is built
    through `repro.index.base.build_index` and wired into the candidate
    slabs via `repro.index.candidates.index_candidate_fn_batched`; with
    `cfg.index = None` candidates are exact (perfect recall).

    Escape hatch (the pre-IndexSpec wiring, kept for custom generators): a
    per-request `candidate_fn` or batched `candidate_fn_batched` overrides
    the spec-built generator.  Passing one *alongside* `cfg.index` is
    deprecated — the explicit fn silently wins, which defeats the config
    knob — and warns.

    `mesh` switches both entry points to the sharded multi-device step
    (`repro.core.distributed.make_step_sharded`): catalog and cache state
    shard over the mesh's `model` axis, the candidate scan + OMA +
    projection run under shard_map, and the single-request path becomes the
    B = 1 view of the sharded batch step.  `candidate_fn*` are ignored in
    that case (the sharded step owns candidate generation); `cfg.index`
    may name the sharded backend ("ivf_sharded", built through the same
    registry) or be None for the exact sharded scan; `sharded_kwargs`
    (e.g. `scan_chunk`) further configure the step.

    Online catalog mutation (DESIGN.md §10): `add_objects(vectors)` /
    `remove_objects(ids)` / `refresh()` admit and expire objects without a
    rebuild.  The first mutation flips serving to the mutable mode — eager
    candidate slabs against the live structures plus the jitted
    `make_mutable_step` tail — which never retraces under churn at fixed
    capacity and enforces the invalidation invariant (tombstoned rows
    carry zero y/x mass forever, so a removed object can neither be served
    nor re-fetched).  On a mesh with `index=None` mutation is fully
    supported (DESIGN.md §15): slab appends and tombstones route to the
    owning shard by global-id arithmetic, serving flips to
    `repro.core.distributed.make_mutable_step_sharded` (candidates + live-
    mask projection inside shard_map, bitwise the single-device mutable
    path on a 1-device mesh), and compaction keeps the slab mesh-aligned.
    Not supported with sharded *index backends* ("ivf_sharded") or with
    explicit `candidate_fn*` escape hatches."""

    def __init__(self, catalog: jax.Array, cfg: "AcaiConfig", candidate_fn=None,
                 candidate_fn_batched=None, seed=0, mesh=None,
                 sharded_kwargs: dict | None = None, c_f: float | None = None,
                 remote=None, resilience=None, answer_cache=None):
        from repro.index.base import resolve_spec
        from repro.serve.answer_cache import resolve_answer_cache_spec

        if not isinstance(cfg, AcaiConfig):
            # PolicySpec / flat-dict / name form (DESIGN.md §9): the one
            # config knob serialized by the experiment harness and dryrun
            # provenance records (both carry their c_f; a spec without one
            # needs the `c_f` kwarg).  Only the 'acai' policy builds an
            # AcaiCache; baselines go through policy_api.build_policy.
            from repro.core.costs import CostModel
            from repro.core.policy_api import (acai_config_from_spec,
                                               resolve_policy_spec)

            spec = resolve_policy_spec(cfg)
            if spec is None or spec.name != "acai":
                raise ValueError(
                    f"AcaiCache builds the 'acai' policy; got "
                    f"{getattr(spec, 'name', spec)!r} — use "
                    f"repro.core.policy_api.build_policy for baselines")
            cfg = acai_config_from_spec(
                spec, None if c_f is None else CostModel(c_f=c_f))
        elif c_f is not None:
            raise ValueError("c_f= only applies to the PolicySpec form "
                             "(AcaiConfig already carries its c_f)")
        # normalize every serialized spec form, incl. the reserved "exact"
        # (-> None), so provenance records round-trip into configs
        resolved = resolve_spec(cfg.index)
        if resolved is not cfg.index:
            cfg = dataclasses.replace(cfg, index=resolved)
        self.cfg = cfg
        self.catalog = catalog
        self.mesh = mesh
        self.index = None  # the spec-built index (None = exact/escape hatch)
        self._sharded_kwargs = dict(sharded_kwargs or {})
        self._bsteps: dict[int, Callable] = {}
        # serve_update_batch calls so far: the `call` of its profiler spans
        self._calls = 0
        # mutable-catalog bookkeeping (DESIGN.md §10): the cache starts on
        # the static jitted path and flips to the mutable two-stage path
        # (eager candidates + jitted apply tail) on the first add/remove.
        self.valid = jnp.ones((catalog.shape[0],), bool)
        self._live = int(catalog.shape[0])
        self._n_slots = int(catalog.shape[0])
        self._mutated = False
        self._mut_fn: Callable | None = None
        self._mut_steps: dict[int, Callable] = {}
        explicit_fn = (candidate_fn is not None
                       or candidate_fn_batched is not None)
        self._custom_fn = explicit_fn
        if explicit_fn and cfg.index is not None:
            import warnings

            warnings.warn(
                "AcaiCache: cfg.index is set but "
                + ("a mesh was given — the sharded step ignores explicit "
                   "candidate fns and serves from the spec-built index"
                   if mesh is not None else
                   "explicit candidate_fn/candidate_fn_batched overrides "
                   "it — drop the kwargs or the spec"),
                DeprecationWarning, stacklevel=2)
        if mesh is not None:
            if cfg.index is not None:
                from repro.index.base import registered_backends

                if cfg.index.backend not in registered_backends(sharded=True):
                    # reject before paying the (possibly minutes-long) build
                    raise ValueError(
                        f"cfg.index backend {cfg.index.backend!r} is not a "
                        f"sharded layout; with mesh= use one of "
                        f"{registered_backends(sharded=True)} (or "
                        f"index=None for the exact sharded scan)")
                if "ivf" in self._sharded_kwargs:
                    import warnings

                    warnings.warn(
                        "AcaiCache: sharded_kwargs['ivf'] overrides "
                        "cfg.index — drop one of them",
                        DeprecationWarning, stacklevel=2)
                else:
                    built = build_index(cfg.index, catalog, mesh=mesh)
                    self.index = built
                    self._sharded_kwargs["ivf"] = built
            # built lazily on first serve_update: a B = 1 step only exists
            # on meshes whose batch axes have size 1 (serving meshes are
            # (1, P)); batched-only use of a (dp, P) mesh must not crash
            # here.
            self._step = None
        else:
            if candidate_fn_batched is None:
                if candidate_fn is None:
                    if cfg.index is not None:
                        from repro.index.candidates import \
                            index_candidate_fn_batched

                        self.index = build_index(cfg.index, catalog)
                        candidate_fn_batched = index_candidate_fn_batched(
                            self.index, catalog, cfg.c_remote, cfg.c_local,
                            h=cfg.h)
                    else:
                        candidate_fn_batched = exact_candidate_fn_batched(
                            catalog, cfg.c_remote, cfg.c_local
                        )
                else:
                    candidate_fn_batched = jax.vmap(candidate_fn,
                                                    in_axes=(0, None))
            self._fn_batched = candidate_fn_batched
            if candidate_fn is None:
                candidate_fn = per_request_view(candidate_fn_batched)
            self._step = jit_closed_over_as_args(make_step(cfg, candidate_fn))
        # answer-cache tier (DESIGN.md §13): wrap the spec-built index in
        # a CachedIndex and serve through the two-stage mutable path from
        # step 0 — the static jitted step queries the index inside its
        # trace, where nothing host-side can memoize, while the mutable
        # path's eager `index.query` is exactly the memoization point.
        self.answer_cache = None  # the CachedIndex wrapper when tier is on
        ac_spec = resolve_answer_cache_spec(answer_cache)
        if ac_spec is not None:
            from repro.serve.answer_cache import CachedIndex

            if mesh is not None:
                raise NotImplementedError(
                    "answer_cache= on a sharded mesh is not implemented "
                    "(the sharded step owns candidate generation) — use a "
                    "single-device cache")
            if self._custom_fn:
                raise ValueError(
                    "answer_cache= cannot front an explicit candidate_fn*: "
                    "the tier memoizes `Index.query` answers — drop the "
                    "escape hatch or the spec")
            if self.index is None:
                raise ValueError(
                    "answer_cache= fronts an index backend; set cfg.index "
                    "(IndexSpec('flat') gives the exact fused scan)")
            self.index = CachedIndex(self.index, ac_spec)
            self.answer_cache = self.index
            self._enter_mutable()
        self.state = self._placed(init_state(catalog.shape[0], cfg, seed=seed))
        # resilient serving mode (DESIGN.md §11): None until a
        # RemoteBackend is attached; then serve_update(_batch) dispatch
        # through the retry/degrade ladder in repro.serve.resilience.
        self._res = None
        if remote is not None or resilience is not None:
            self.attach_remote(remote, resilience)

    def attach_remote(self, remote=None, resilience=None):
        """Switch serving to the resilient mode (DESIGN.md §11): requests
        first run their remote interaction (retries / hedging / deadline /
        circuit breaker) against `remote` — a `repro.serve.remote`
        backend — and failed requests are served through the graceful-
        degradation ladder.  With a healthy backend (or `remote=None`,
        the always-ok `OracleRemote`) every batch still takes the static
        jitted step, bitwise identical to the unattached cache.  Returns
        the `AcaiResilience` controller (counters, breaker log, reports).
        """
        from repro.serve.resilience import AcaiResilience

        if self.mesh is not None:
            raise NotImplementedError(
                "resilient serving on a sharded mesh is not implemented "
                "yet (ROADMAP open item) — attach the remote to a "
                "single-device cache")
        self._res = AcaiResilience(self, remote, resilience)
        return self._res

    def _placed(self, state: CacheState) -> CacheState:
        """`state` placed where the jitted step returns it — y and x split
        over the mesh's model axis with the counters replicated, or on the
        catalog's device — so the second batch runs the first batch's
        compiled program instead of compiling one for a new placement."""
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            axis = self._sharded_kwargs.get("model_axis", "model")
            rows = NamedSharding(self.mesh, PartitionSpec(axis))
            rep = NamedSharding(self.mesh, PartitionSpec())
            return jax.device_put(state, CacheState(rows, rows, rep, rep))
        sharding = getattr(self.catalog, "sharding", None)
        return state if sharding is None else jax.device_put(state, sharding)

    def _sharded_step(self, batch: int) -> Callable:
        from repro.core.distributed import make_step_sharded

        return make_step_sharded(self.cfg, self.mesh, self.catalog, batch,
                                 **self._sharded_kwargs)

    def _mesh_model_size(self) -> int:
        from repro.core.distributed import _axis_size

        return _axis_size(self.mesh,
                          self._sharded_kwargs.get("model_axis", "model"))

    def _sharded_mutable_step(self, batch: int) -> Callable:
        from repro.core.distributed import make_mutable_step_sharded

        kw = {k: v for k, v in self._sharded_kwargs.items()
              if k in ("eta_scale", "model_axis", "batch_axes", "top_a")}
        return make_mutable_step_sharded(self.cfg, self.mesh, batch, **kw)

    def serve_update(self, r: jax.Array) -> StepMetrics:
        if self._res is not None or self._mutated:
            # B = 1 view of the resilient / mutable batch step
            m = self.serve_update_batch(r[None, :])
            return jax.tree_util.tree_map(lambda a: a[0], m)
        from repro.index.base import check_finite_queries

        check_finite_queries(r, "AcaiCache.serve_update")
        if self._step is None:  # lazy B = 1 view of the sharded step
            b1 = self._sharded_step(1)

            def _step1(state, rr):
                state, m = b1(state, rr[None, :])
                return state, jax.tree_util.tree_map(lambda a: a[0], m)

            self._step = jit_closed_over_as_args(_step1)
        self.state, metrics = self._step(self.state, r)
        return metrics

    def serve_update_batch(self, rs: jax.Array) -> StepMetrics:
        """Serve a request mini-batch (B, d): one OMA + rounding update for
        the whole batch, per-request StepMetrics (B,).  The jitted step is
        cached per batch size.  Once the catalog has mutated the step runs
        in two stages (eager candidate slab against the live structures +
        the jitted `make_mutable_step` tail).  With a RemoteBackend
        attached (`attach_remote`), the batch routes through the
        resilience ladder instead (DESIGN.md §11).

        Under `jax.profiler` the call shows as three sibling host spans,
        each carrying the same `call` number: `acai.put` (the batch to the
        device), `acai.finite_check` (read back and tested for NaN/Inf)
        and `acai.step` (the step enqueued).  With the profiler off they
        cost a few microseconds."""
        from repro.index.base import check_finite_queries

        self._calls += 1
        span = partial(jax.profiler.TraceAnnotation, call=self._calls)
        with span("acai.put"):
            rs = jnp.atleast_2d(rs)
        with span("acai.finite_check"):
            check_finite_queries(rs, "AcaiCache.serve_update_batch")
        with span("acai.step"):
            if self._res is not None:
                return self._res.serve_update_batch(rs)
            return self._serve_batch_direct(rs)

    def _serve_batch_direct(self, rs: jax.Array) -> StepMetrics:
        """The fault-oblivious serving step (also the all-ok fast path of
        the resilient mode, keeping fault-rate 0 bitwise identical)."""
        rs = jnp.atleast_2d(rs)
        b = rs.shape[0]
        if self._mutated:
            if self.mesh is not None:
                # sharded mutable serving: candidates, OMA and the live-
                # mask projection all run inside the shard_map step; the
                # (fixed-capacity) slab + mask are runtime args, so churn
                # reuses the cached jit per batch size
                step = self._mut_steps.get(b)
                if step is None:
                    step = self._sharded_mutable_step(b)
                    self._mut_steps[b] = step
                self.state, metrics = step(
                    self.state, rs, jnp.asarray(self.catalog, jnp.float32),
                    self.valid)
                return metrics
            ids, d, valid = self._mut_fn(rs, self.state.x)
            step = self._mut_steps.get(b)
            if step is None:
                step = make_mutable_step(self.cfg, b)
                self._mut_steps[b] = step
            self.state, metrics = step(self.state, ids, d, valid, self.valid)
            if self.answer_cache is not None:
                # book the answer-tier counters host-side: the hit mask of
                # the eager `CachedIndex.query` this batch just ran, plus
                # churn invalidations since the previous step (a per-batch
                # scalar like `fetched`, booked on the first request)
                mask, inval = self.answer_cache.cache.take_step_stats(b)
                hits = jnp.asarray(mask, jnp.int32)
                metrics = metrics._replace(
                    answer_hits=hits, answer_misses=1 - hits,
                    answer_invalidations=jnp.zeros(
                        (b,), jnp.int32).at[0].set(int(inval)))
            return metrics
        self.state, metrics = self._batch_step(b)(self.state, rs)
        return metrics

    def _batch_step(self, b: int) -> Callable:
        """The static jitted step for batch size `b`, built once."""
        step = self._bsteps.get(b)
        if step is None:
            fn = (self._sharded_step(b) if self.mesh is not None
                  else make_step_batched(self.cfg, self._fn_batched, b))
            step = jit_closed_over_as_args(fn)
            self._bsteps[b] = step
        return step

    def lower_batch(self, rs: jax.Array):
        """The lowered static step `serve_update_batch(rs)` runs, for
        inspecting the compiled program — e.g. that the scan is the Pallas
        kernel (`tpu_custom_call` in its text) and not an XLA fallback."""
        rs = jnp.atleast_2d(rs)
        return self._batch_step(rs.shape[0]).lower(self.state, rs)

    # -- online catalog mutation (DESIGN.md §10) ----------------------------

    def _check_mutable_supported(self) -> None:
        """Reject mutation on configurations that cannot serve it — before
        anything is touched, so a failed call leaves the cache exactly as
        it was (still on the static jitted path)."""
        if self._mutated:
            return
        if self.mesh is not None:
            if self.index is not None or "ivf" in self._sharded_kwargs:
                raise NotImplementedError(
                    "online catalog mutation on a sharded index backend is "
                    "not implemented — the sharded mutable path serves "
                    "through the exact masked scan; build the mesh cache "
                    "with index=None (or rebuild the sharded index)")
            if self._sharded_kwargs.get("scan_chunk"):
                raise NotImplementedError(
                    "online catalog mutation on a sharded mesh serves "
                    "through the exact masked scan — drop "
                    "sharded_kwargs['scan_chunk']")
            cap = self.catalog.shape[0]
            n_model = self._mesh_model_size()
            if cap % n_model:
                raise ValueError(
                    f"slab capacity {cap} must divide by the mesh's "
                    f"{n_model} model shards before mutation")
        if self._custom_fn:
            raise ValueError(
                "AcaiCache was built with an explicit candidate_fn*: the "
                "cache cannot rebuild a custom generator after catalog "
                "mutation — drop the escape hatch or rebuild the cache")

    def _enter_mutable(self) -> None:
        """Flip from the static jitted path to the mutable serving mode
        after a successful first mutation (the static path's traced
        constants would serve the pre-mutation catalog forever)."""
        if self._mutated:
            return
        if self.mesh is not None:
            # the sharded mutable step owns candidate generation inside
            # shard_map (catalog + liveness as runtime args); there is no
            # eager host-side candidate stage to build
            self._mut_fn = None
            self._mutated = True
            return
        if self.index is not None:
            from repro.index.candidates import mutable_index_candidate_fn

            self._mut_fn = mutable_index_candidate_fn(
                self.index, self.cfg.c_remote, self.cfg.c_local,
                h=self.cfg.h)
        else:

            def _exact(rs, x):
                return exact_mutable_candidates(
                    rs, x, self.catalog, self.valid, self.cfg.c_remote,
                    self.cfg.c_local)

            self._mut_fn = _exact
        self._mutated = True

    def _sync_capacity(self, new_ids) -> None:
        """Grow the OMA state to the (possibly doubled) slab capacity and
        admit the new rows at the uniform prior y = h / n_live (Alg. 1's
        y_1 for the object, fresh-start semantics; the next projection
        renormalises the small capacity excess)."""
        from repro.index.base import _flat_set, pad_ids, run_device

        cap = self.catalog.shape[0]
        y, x = self.state.y, self.state.x
        if y.shape[0] != cap:
            y = jnp.pad(y, (0, cap - y.shape[0]))
            x = jnp.pad(x, (0, cap - x.shape[0]))
        prior = min(1.0, self.cfg.h / max(self._live, 1))
        # donated width-padded scatter (padded lanes carry an OOB index
        # and are dropped): fixed shapes, no per-batch-size retrace, and
        # the state buffer mutates in place on device
        y = run_device(_flat_set, y, pad_ids(new_ids, cap),
                       jnp.float32(prior))
        self.state = CacheState(y, x, self.state.t, self.state.key)

    def add_objects(self, vectors) -> "np.ndarray":
        """Admit new catalog objects online: append to the shared slab
        (and the remote index's structures, when one is configured),
        grow the OMA state, and seed the new rows with the uniform prior.
        Returns their (monotonic, never-recycled) row ids."""
        self._check_mutable_supported()
        vectors = jnp.atleast_2d(jnp.asarray(vectors, jnp.float32))
        if self.index is not None:
            ids = self.index.add(vectors)
            self.catalog = self.index.embeddings
            self.valid = self.index.valid
        else:
            from repro.index.base import slab_append

            self.catalog = jnp.asarray(self.catalog, jnp.float32)
            if self.mesh is not None:
                # owner-shard routing (DESIGN.md §15): the append splits at
                # shard-block boundaries and each run's donated write goes
                # to the owning shard's slice; growth stays mesh-aligned
                from repro.core.distributed import sharded_slab_append

                self.catalog, self.valid, ids = sharded_slab_append(
                    self.catalog, self.valid, self._n_slots, vectors,
                    self._mesh_model_size())
            else:
                self.catalog, self.valid, ids = slab_append(
                    self.catalog, self.valid, self._n_slots, vectors)
        self._n_slots += len(ids)
        self._live += len(ids)
        self._sync_capacity(ids)
        self._enter_mutable()
        return ids

    def remove_objects(self, ids) -> None:
        """Drop catalog objects online: tombstone the rows and zero their
        fractional + physical cache mass (the invalidation invariant — a
        removed object is never served, never fetched, and frees its cache
        slot immediately; `make_mutable_step` keeps the rows at zero)."""
        self._check_mutable_supported()
        import numpy as np

        from repro.index.base import (_flat_set, _mask_clear, _mask_gather,
                                      pad_ids, run_device)

        ids = np.atleast_1d(np.asarray(ids, np.int32))
        if self.index is not None:
            self.index.remove(ids)
            self.valid = self.index.valid
        else:
            if len(ids) and (ids.min() < 0 or ids.max() >= self._n_slots):
                raise ValueError(
                    f"remove_objects: ids must be assigned rows in "
                    f"[0, {self._n_slots})")
            if len(np.unique(ids)) != len(ids):
                raise ValueError("remove_objects: duplicate ids in one "
                                 "batch")
            # width-padded aliveness gather + donated tombstone write
            # (fixed shapes — no per-batch-size retrace under churn)
            cap = self.valid.shape[0]
            alive = np.asarray(run_device(
                _mask_gather, self.valid, pad_ids(ids, cap)))[:len(ids)]
            if not alive.all():
                raise ValueError(
                    f"remove_objects: rows {ids[~alive].tolist()} are "
                    f"already dead")
            self.catalog = jnp.asarray(self.catalog, jnp.float32)
            if self.mesh is not None:
                # tombstone writes routed to the owning shard by global-id
                # arithmetic (one donated scatter per shard touched; the
                # P = 1 grouping is the single-device call, bitwise)
                from repro.core.distributed import route_ids_by_owner

                for _, gids in route_ids_by_owner(
                        ids, cap, self._mesh_model_size()):
                    self.valid = run_device(_mask_clear, self.valid,
                                            pad_ids(gids, cap))
            else:
                self.valid = run_device(_mask_clear, self.valid,
                                        pad_ids(ids, cap))
        self._live -= len(ids)
        self._enter_mutable()
        # zero the removed rows' fractional + physical mass via donated
        # padded scatters (the invalidation invariant), routed per owning
        # shard on a mesh
        scap = self.state.y.shape[0]
        if self.mesh is not None:
            from repro.core.distributed import route_ids_by_owner

            groups = [g for _, g in route_ids_by_owner(
                ids, scap, self._mesh_model_size())]
        else:
            groups = [ids]
        y, x = self.state.y, self.state.x
        for gids in groups:
            jid = pad_ids(gids, scap)
            y = run_device(_flat_set, y, jid, jnp.float32(0.0))
            x = run_device(_flat_set, x, jid, jnp.float32(0.0))
        self.state = CacheState(y, x, self.state.t, self.state.key)

    def refresh(self) -> None:
        """Rebuild the remote index's structures over the live rows
        (tombstone compaction / quantizer re-train; see Index.refresh).
        A no-op for exact candidates, whose masked scan never drifts."""
        if self.index is not None and self._mutated:
            self.index.refresh()

    def refresh_start(self) -> None:
        """Phase 1 of the double-buffered refresh (DESIGN.md §14): build
        the shadow structures while the stale ones keep serving."""
        if self.index is not None and self._mutated:
            self.index.refresh_start()

    def refresh_swap(self) -> None:
        """Phase 2: install the pending shadow — the only serving-visible
        stall, a few attribute swaps."""
        if self.index is not None and self._mutated:
            self.index.refresh_swap()

    def compact(self) -> "np.ndarray":
        """Epoch compaction (DESIGN.md §14): drop tombstoned rows, shrink
        the slab back to the live set (plus one write window of headroom),
        and renumber the survivors in ascending-id order.  The OMA y/x
        state rows move with their objects — pure permutation, no
        arithmetic.  Returns the (old_capacity,) int32 remap (new row id,
        or -1 for dead rows); callers own pushing it to every other id
        holder (payload stores, oracles, answer caches)."""
        self._check_mutable_supported()
        import numpy as np

        from repro.index.base import MIN_WRITE, grow_capacity

        old_cap = self.catalog.shape[0]
        if self.index is not None:
            remap = self.index.compact()
            self.catalog = self.index.embeddings
            self.valid = self.index.valid
        else:
            live = np.nonzero(np.asarray(self.valid))[0]
            n_live = live.size
            remap = np.full(old_cap, -1, np.int32)
            remap[live] = np.arange(n_live, dtype=np.int32)
            cap = grow_capacity(0, n_live + MIN_WRITE, 1)
            if self.mesh is not None:
                # keep the compacted slab mesh-aligned so owner-shard
                # arithmetic survives (a no-op for power-of-two meshes:
                # the doubling schedule already lands on a multiple)
                cap += (-cap) % self._mesh_model_size()
            emb_live = jnp.asarray(self.catalog,
                                   jnp.float32)[jnp.asarray(live)]
            self.catalog = jnp.pad(emb_live, ((0, cap - n_live), (0, 0)))
            self.valid = jnp.pad(jnp.ones((n_live,), bool),
                                 (0, cap - n_live))
        self._n_slots = self._live
        cap = self.catalog.shape[0]
        old_y = np.asarray(self.state.y)
        old_x = np.asarray(self.state.x)
        y = np.zeros(cap, old_y.dtype)
        x = np.zeros(cap, old_x.dtype)
        src = np.nonzero(remap >= 0)[0]
        y[remap[src]] = old_y[src]
        x[remap[src]] = old_x[src]
        self.state = CacheState(jnp.asarray(y), jnp.asarray(x),
                                self.state.t, self.state.key)
        self._enter_mutable()
        return remap

    @property
    def live_count(self) -> int:
        """Live (non-tombstoned) catalog objects."""
        return self._live

    @property
    def cached_ids(self):
        return jnp.nonzero(self.state.x > 0.5)[0]

    def normalized_gain(self, total_gain: float, t: int) -> float:
        """NAG of Eq. (11)."""
        return float(total_gain) / (self.cfg.k * self.cfg.c_f * max(t, 1))
