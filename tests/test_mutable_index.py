"""Mutable catalog (DESIGN.md §10): add/remove/refresh conformance across
every registered backend, the removed-object-is-never-served invariant,
refresh-matches-fresh-build recall, the AcaiCache/ServerOracle invalidation
hooks, and the churn_rate = 0 static-replay consistency pin."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import baselines as B
from repro.core import churn, oma, policy, trace
from repro.core import policy_api as PA
from repro.core.costs import CostModel
from repro.launch.mesh import make_mesh
from repro.index import Index, IndexSpec, build_index
from repro.index.base import TINY_BUILD_KWARGS as TINY
from repro.index.base import grow_capacity, slab_append


@pytest.fixture(scope="module")
def setup():
    catalog, reqs, _ = trace.sift_like(n=300, d=16, t=48, seed=0)
    rng = np.random.default_rng(7)
    newv = (rng.random((60, 16)) * 0.9 + 0.05).astype(np.float32)
    return jnp.array(catalog), jnp.array(reqs), jnp.asarray(newv)


# ---------------------------------------------------------------------------
# all-backends mutation conformance (the sweep mirroring test_index_api)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(TINY))
def test_add_remove_refresh_conformance(setup, backend):
    cat, rq, newv = setup
    idx = build_index(IndexSpec(backend, TINY[backend]), cat)
    assert isinstance(idx, Index)
    assert (idx.n, idx.capacity, idx.n_slots) == (300, 300, 300)

    # --- add: appended ids are monotonic, never recycled ------------------
    ids = idx.add(newv)
    assert (ids == np.arange(300, 360)).all()
    assert idx.n == 360 and idx.n_slots == 360 and idx.capacity >= 360
    # an inserted vector is findable by querying it exactly (recall on the
    # insert itself — every backend links/bins new rows at add time)
    d, got = idx.query(newv[:16], 5)
    assert d.shape == (16, 5) and got.dtype == jnp.int32
    found = [int(ids[j]) in set(np.asarray(got)[j]) for j in range(16)]
    assert sum(found) >= 14, f"{backend}: inserted rows not retrievable"

    # --- remove: a tombstoned object can NEVER surface again --------------
    d1, top = idx.query(rq[:16], 5)
    doomed = np.unique(np.asarray(top)[:, 0])
    idx.remove(doomed)
    assert idx.n == 360 - len(doomed)
    d2, after = idx.query(rq[:16], 8)
    assert not set(doomed.tolist()) & set(np.asarray(after).ravel().tolist())
    # contract still holds post-mutation: ascending dists, -1 underflow
    dd, ii = np.asarray(d2), np.asarray(after)
    valid = ii >= 0
    assert (np.diff(np.where(valid, dd, np.inf), axis=1) >= -1e-5).all()
    assert np.isinf(dd[~valid]).all()

    # --- refresh: ids stable, removed rows still dead ----------------------
    idx.refresh()
    assert idx.n == 360 - len(doomed)
    d3, again = idx.query(rq[:16], 8)
    assert not set(doomed.tolist()) & set(np.asarray(again).ravel().tolist())

    # --- double-remove / bad ids are loud errors ---------------------------
    with pytest.raises(ValueError, match="already dead"):
        idx.remove(doomed[:1])
    with pytest.raises(ValueError):
        idx.remove(np.asarray([idx.n_slots + 5]))


@pytest.mark.parametrize("backend", sorted(TINY))
def test_refresh_matches_fresh_build(setup, backend):
    """Recall after refresh matches a fresh build on the live rows: the
    rebuild runs over the live rows in slab order with a pure id remap, so
    the two indexes answer identically (the acceptance pin)."""
    cat, rq, newv = setup
    idx = build_index(IndexSpec(backend, TINY[backend]), cat)
    idx.add(newv)
    rng = np.random.default_rng(11)
    idx.remove(rng.choice(360, size=80, replace=False))
    idx.refresh()

    live = idx.live_rows()
    fresh = build_index(IndexSpec(backend, TINY[backend]),
                        idx.embeddings[jnp.asarray(live)])
    d_ref, i_ref = idx.query(rq[:16], 5)
    d_new, i_new = fresh.query(rq[:16], 5)
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_new),
                               atol=1e-4)
    # fresh ids are local row offsets into the live set: remap and compare
    i_new = np.asarray(i_new)
    remapped = np.where(i_new >= 0, live[np.clip(i_new, 0, None)], -1)
    np.testing.assert_array_equal(np.asarray(i_ref), remapped)


def test_slab_growth_and_id_monotonicity(setup):
    cat, _, newv = setup
    assert grow_capacity(300, 1, 300) == 600
    assert grow_capacity(300, 1000, 300) == 2400
    emb, valid, n_slots = cat, jnp.ones((300,), bool), 300
    all_ids = []
    for s in range(0, 60, 20):
        emb, valid, ids = slab_append(emb, valid, n_slots, newv[s:s + 20])
        n_slots += len(ids)
        all_ids.append(ids)
    assert (np.concatenate(all_ids) == np.arange(300, 360)).all()
    assert emb.shape[0] == 600 and not bool(valid[360:].any())
    np.testing.assert_allclose(np.asarray(emb[300:360]), np.asarray(newv),
                               atol=0)


# ---------------------------------------------------------------------------
# AcaiCache invalidation hooks (the OMA state drops removed objects)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cache_cfg():
    return policy.AcaiConfig(h=24, k=4, c_f=1.0, c_remote=16, c_local=8,
                             oma=oma.OMAConfig(eta=0.05,
                                               rounding="depround"))


@pytest.mark.parametrize("index", [None, IndexSpec("ivf", {"nlist": 8,
                                                           "nprobe": 4})])
def test_acai_cache_invalidation_invariant(setup, cache_cfg, index):
    cat, rq, newv = setup
    cfg = dataclasses.replace(cache_cfg, index=index)
    cache = policy.AcaiCache(cat, cfg, seed=0)
    for s in range(0, 16, 8):
        cache.serve_update_batch(rq[s:s + 8])

    ids = cache.add_objects(newv[:30])
    assert (ids == np.arange(300, 330)).all()
    assert cache.state.y.shape[0] == cache.catalog.shape[0] >= 330
    # new rows admitted at the uniform prior (they can be learned at once)
    assert float(cache.state.y[301]) > 0

    doomed = np.asarray(cache.cached_ids)[:6]
    cache.remove_objects(doomed)
    jd = jnp.asarray(doomed)
    assert float(jnp.abs(cache.state.y[jd]).sum()) == 0.0
    assert float(jnp.abs(cache.state.x[jd]).sum()) == 0.0
    for s in range(16, 48, 8):
        m = cache.serve_update_batch(rq[s:s + 8])
        # the invariant persists through every OMA + rounding update
        assert float(jnp.abs(cache.state.y[jd]).sum()) == 0.0
        assert float(jnp.abs(cache.state.x[jd]).sum()) == 0.0
    assert float(m.occupancy[0]) <= cfg.h + 1e-6
    assert cache.live_count == 330 - 6
    # B = 1 serving keeps working in mutable mode
    m1 = cache.serve_update(rq[0])
    assert m1.gain_int.shape == ()


def test_mutable_path_matches_static_when_all_alive(setup, cache_cfg):
    """Forcing the mutable serving mode without any actual mutation must
    reproduce the static path (same candidate math with the catalog as a
    runtime argument instead of a traced constant; float tolerance — the
    constant/parameter boundary reassociates the distance GEMM)."""
    cat, rq, _ = setup
    a = policy.AcaiCache(cat, cache_cfg, seed=0)
    b = policy.AcaiCache(cat, cache_cfg, seed=0)
    b._enter_mutable()
    for s in range(0, 48, 8):
        ma = a.serve_update_batch(rq[s:s + 8])
        mb = b.serve_update_batch(rq[s:s + 8])
        np.testing.assert_allclose(np.asarray(ma.gain_int),
                                   np.asarray(mb.gain_int),
                                   rtol=0, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(ma.served_local),
                                      np.asarray(mb.served_local))
    np.testing.assert_allclose(np.asarray(a.state.y), np.asarray(b.state.y),
                               rtol=0, atol=1e-5)


def test_acai_cache_mutation_guards(setup, cache_cfg):
    cat, rq, newv = setup
    fn = policy.exact_candidate_fn_batched(cat, 16, 8)
    cache = policy.AcaiCache(cat, cache_cfg, candidate_fn_batched=fn, seed=0)
    with pytest.raises(ValueError, match="explicit candidate_fn"):
        cache.add_objects(newv[:2])
    # exact sharded mutation is supported (tests/test_sharded_churn.py);
    # only configurations the exact masked scan cannot honor still reject:
    # approximate sharded structures (ivf / scan_chunk) have no mutable
    # sharded serving path
    mesh = make_mesh((1, 1), ("data", "model"))
    sharded = policy.AcaiCache(cat, cache_cfg, mesh=mesh, seed=0,
                               sharded_kwargs={"scan_chunk": 64})
    with pytest.raises(NotImplementedError, match="sharded"):
        sharded.remove_objects([0])
    assert not sharded._mutated  # rejected mutation leaves the static path

    # a rejected mutation leaves the cache on the static path with its
    # live-count intact (exact path validates duplicates/range/aliveness
    # exactly like the index path does)
    clean = policy.AcaiCache(cat, cache_cfg, seed=0)
    with pytest.raises(ValueError, match="duplicate"):
        clean.remove_objects([5, 5])
    with pytest.raises(ValueError):
        clean.remove_objects([cat.shape[0] + 7])
    assert not clean._mutated and clean.live_count == cat.shape[0]
    clean.remove_objects([5])
    with pytest.raises(ValueError, match="already dead"):
        clean.remove_objects([5])
    assert clean.live_count == cat.shape[0] - 1


def test_churn_replay_drains_tail_events(cache_cfg):
    """Events landing past the last full mini-batch still apply, so the
    catalog ends in the schedule's final state."""
    params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"])
    catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
    events = trace.rolling_catalog_events(**params)
    n0 = churn.warm_size(params["n"], params["warm"])
    cache = policy.AcaiCache(jnp.asarray(catalog[:n0]), cache_cfg, seed=0)
    # drop the trace to 60 requests: batch=16 -> tt=48, several events
    # land in [48, 64) and must be drained after the last batch
    res = churn.replay_with_churn(cache, catalog, reqs[:60], events,
                                  batch=16)
    assert res["requests"] == 48
    assert res["events_applied"] == len(events)
    assert cache.live_count == n0


# ---------------------------------------------------------------------------
# churn_rate = 0: the static-catalog consistency pin (acceptance criterion)
# ---------------------------------------------------------------------------

def test_churn_zero_is_bit_consistent_with_static_replay(cache_cfg):
    """A rolling_catalog replay with churn_rate = 0 produces bitwise the
    same metrics and state as make_replay_batched on the warm window —
    the mutable-catalog plumbing costs nothing when nothing mutates."""
    params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"],
                  churn_rate=0.0)
    catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
    events = trace.rolling_catalog_events(**params)
    assert events == []
    n0 = churn.warm_size(params["n"], params["warm"])

    cache = policy.AcaiCache(jnp.asarray(catalog[:n0]), cache_cfg, seed=0)
    res = churn.replay_with_churn(cache, catalog, reqs, events, batch=8)
    assert res["events_applied"] == 0

    replay = policy.make_replay_batched(
        cache_cfg, policy.exact_candidate_fn_batched(
            jnp.asarray(catalog[:n0]), cache_cfg.c_remote,
            cache_cfg.c_local), 8)
    st, m = replay(policy.init_state(n0, cache_cfg, seed=0),
                   jnp.asarray(reqs))
    np.testing.assert_array_equal(res["gain"], np.asarray(m.gain_int))
    np.testing.assert_array_equal(res["served_local"],
                                  np.asarray(m.served_local))
    np.testing.assert_array_equal(res["occupancy"], np.asarray(m.occupancy))
    np.testing.assert_array_equal(np.asarray(cache.state.y), np.asarray(st.y))
    np.testing.assert_array_equal(np.asarray(cache.state.x), np.asarray(st.x))


def test_churn_replay_applies_schedule(cache_cfg):
    """Under churn the driver applies every event, row ids stay aligned
    with the trace schedule, and expired objects drop out of the state."""
    params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"])
    catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
    events = trace.rolling_catalog_events(**params)
    assert len(events) > 0
    n0 = churn.warm_size(params["n"], params["warm"])
    cache = policy.AcaiCache(jnp.asarray(catalog[:n0]), cache_cfg, seed=0)
    res = churn.replay_with_churn(cache, catalog, reqs, events, batch=8,
                                  refresh_every=32)
    assert res["events_applied"] == len(events)
    assert res["gain"].shape == (64,)
    removed = np.concatenate([ev[2] for ev in events])
    assert float(jnp.abs(cache.state.y[jnp.asarray(removed)]).sum()) == 0.0
    assert cache.live_count == n0  # rolling window: one expiry per insert


def test_churn_replay_baseline_policy():
    """The same driver runs the oracle-backed baselines (online mode)."""
    params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"])
    catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
    events = trace.rolling_catalog_events(**params)
    n0 = churn.warm_size(params["n"], params["warm"])
    pol = PA.build_policy(
        PA.PolicySpec("sim_lru", dict(PA.TINY_POLICY_KWARGS["sim_lru"])),
        catalog[:n0], CostModel(c_f=1.0), seed=0)
    res = churn.replay_with_churn(pol, catalog, reqs, events, batch=8)
    assert res["events_applied"] == len(events)
    removed = set(np.concatenate([ev[2] for ev in events]).tolist())
    assert not removed & set(pol.policy.cached_object_ids().tolist())


# ---------------------------------------------------------------------------
# double-buffered refresh (DESIGN.md §14): stale serves bitwise until the
# swap; after the swap the index answers like a blocking refresh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(TINY))
def test_two_phase_refresh_stale_then_fresh(setup, backend):
    cat, rq, newv = setup
    idx = build_index(IndexSpec(backend, TINY[backend]), cat)
    ref = build_index(IndexSpec(backend, TINY[backend]), cat)
    rng = np.random.default_rng(5)
    doomed = rng.choice(300, size=40, replace=False)
    for i in (idx, ref):
        i.add(newv)
        i.remove(doomed)

    assert not idx.refresh_pending
    idx.refresh_start()
    # between start and swap the *stale* structures serve, bitwise equal
    # to a twin that never started a refresh
    d_s, i_s = idx.query(rq[:16], 5)
    d_r, i_r = ref.query(rq[:16], 5)
    np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(d_s), np.asarray(d_r))

    idx.refresh_swap()
    assert not idx.refresh_pending
    ref.refresh()  # blocking = start + swap back to back
    d_a, i_a = idx.query(rq[:16], 5)
    d_b, i_b = ref.query(rq[:16], 5)
    np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_b))
    np.testing.assert_array_equal(np.asarray(d_a), np.asarray(d_b))


def test_shadow_discarded_on_interleaved_mutation(setup):
    """A mutation between start and swap invalidates the shadow (it was
    computed over rows that no longer reflect the slab); the swap then
    installs nothing and the stale structures keep serving."""
    cat, rq, newv = setup
    spec = IndexSpec("ivf", TINY["ivf"])
    idx = build_index(spec, cat)
    twin = build_index(spec, cat)
    idx.refresh_start()
    assert idx.refresh_pending
    for i in (idx, twin):
        i.add(newv[:8])
    assert not idx.refresh_pending
    idx.refresh_swap()  # no-op: the shadow was discarded
    d_a, i_a = idx.query(rq[:8], 5)
    d_b, i_b = twin.query(rq[:8], 5)
    np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_b))
    np.testing.assert_array_equal(np.asarray(d_a), np.asarray(d_b))


# ---------------------------------------------------------------------------
# epoch compaction (DESIGN.md §14)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(TINY))
def test_compact_matches_fresh_build(setup, backend):
    """After compaction the index answers like a fresh build over the
    live rows: the remap is order-preserving, so even index-order
    tie-breaks survive the renumbering."""
    cat, rq, newv = setup
    idx = build_index(IndexSpec(backend, TINY[backend]), cat)
    idx.add(newv)
    rng = np.random.default_rng(13)
    idx.remove(rng.choice(360, size=100, replace=False))

    live = idx.live_rows()
    emb_live = idx.embeddings[jnp.asarray(live)]
    old_cap = idx.capacity
    remap = np.asarray(idx.compact())
    assert remap.shape == (old_cap,)
    # order-preserving: live rows land densely at [0, n_live) in order
    np.testing.assert_array_equal(remap[live], np.arange(len(live)))
    dead = np.setdiff1d(np.arange(old_cap), live)
    assert (remap[dead] == -1).all()
    assert idx.n == idx.n_slots == len(live)

    fresh = build_index(IndexSpec(backend, TINY[backend]), emb_live)
    d_a, i_a = idx.query(rq[:16], 5)
    d_b, i_b = fresh.query(rq[:16], 5)
    np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_b))
    np.testing.assert_allclose(np.asarray(d_a), np.asarray(d_b), atol=1e-4)

    # the id space restarts dense: post-compaction adds continue from
    # n_live, and the new rows are immediately retrievable
    ids = idx.add(newv[:4])
    np.testing.assert_array_equal(ids, np.arange(len(live), len(live) + 4))
    _, got = idx.query(newv[:4], 3)
    hits = [int(ids[j]) in set(np.asarray(got)[j]) for j in range(4)]
    assert sum(hits) >= 3, f"{backend}: rows added after compact not found"


def test_churn_compaction_parity():
    """Compaction is behavior-neutral for exact candidates: the driver's
    id translation routes the schedule through the remap, and the replay
    matches the compaction-free run on gain/served/occupancy bitwise —
    only the slab capacity (and wall time) changes.  Re-rounding is
    frozen past the initial draw (`round_every` > trace length):
    randomized rounding consumes one uniform per slab *position*, so no
    renumbering can keep its stream aligned — the deterministic pipeline
    (candidates, distances, OMA y, gains given x) is the compaction
    contract, and that is what this pins bitwise."""
    cfg = policy.AcaiConfig(
        h=24, k=4, c_f=1.0, c_remote=16, c_local=8,
        oma=oma.OMAConfig(eta=0.05, rounding="depround",
                          round_every=1_000_000))
    params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"])
    catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
    events = trace.rolling_catalog_events(**params)
    assert len(events) > 0
    n0 = churn.warm_size(params["n"], params["warm"])
    runs = {}
    for every in (0, 24):
        cache = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0)
        runs[every] = (churn.replay_with_churn(
            cache, catalog, reqs, events, batch=8, compact_every=every),
            cache)
    (r0, c0), (r1, c1) = runs[0], runs[24]
    assert r0["compactions"] == 0 and r1["compactions"] >= 1
    np.testing.assert_array_equal(r0["gain"], r1["gain"])
    np.testing.assert_array_equal(r0["served_local"], r1["served_local"])
    np.testing.assert_array_equal(r0["occupancy"], r1["occupancy"])
    # compaction reclaimed the tombstoned rows: the compacted slab is no
    # bigger, and the live mass is identical
    assert c1.catalog.shape[0] <= c0.catalog.shape[0]
    assert c1.live_count == c0.live_count
    np.testing.assert_array_equal(
        np.sort(np.asarray(c1.state.y)[np.asarray(c1.state.y) != 0]),
        np.sort(np.asarray(c0.state.y)[np.asarray(c0.state.y) != 0]))


# ---------------------------------------------------------------------------
# no-retrace guard: churn at warmed shapes compiles nothing new
# ---------------------------------------------------------------------------

def test_no_retrace_after_warmup(cache_cfg):
    """The donated-update mutation path keys its jits on (capacity,
    pow2-bucketed batch width): replaying the same churn schedule a
    second time — fresh policy, same shapes — must add zero compiled
    traces across every tracked entry point (DESIGN.md §14)."""
    from repro.index.base import tracked_compiles

    params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"])
    catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
    events = trace.rolling_catalog_events(**params)
    assert len(events) > 0
    n0 = churn.warm_size(params["n"], params["warm"])
    for attempt in ("warmup", "pinned"):
        cache = policy.AcaiCache(jnp.asarray(catalog[:n0]), cache_cfg,
                                 seed=0)
        before = tracked_compiles()
        churn.replay_with_churn(cache, catalog, reqs, events, batch=8,
                                refresh_every=32)
        grew = tracked_compiles() - before
        if attempt == "pinned":
            assert grew == 0, (
                f"churn retraced {grew} tracked jits after warmup")


# ---------------------------------------------------------------------------
# ServerOracle mutation semantics
# ---------------------------------------------------------------------------

def test_server_oracle_mutation(setup):
    cat, rq, newv = setup
    catalog = np.asarray(cat)
    o = B.ServerOracle(catalog, kmax=16, retain_all=False)
    ts = o.extend(np.asarray(rq[:8]))
    ids = o.add_objects(np.asarray(newv[:20]))
    assert (ids == np.arange(300, 320)).all()
    with pytest.raises(KeyError):          # stale precomputed answers
        o.knn(int(ts[0]), 4)
    ts2 = o.extend(np.asarray(rq[:8]))
    top = o.knn(int(ts2[0]), 1)[0]
    o.remove_objects(top)
    ts3 = o.extend(np.asarray(rq[:8]))
    block = o.knn_block(ts3, 16)
    assert int(top[0]) not in set(block.ravel().tolist())
    with pytest.raises(ValueError, match="already dead"):
        o.remove_objects(top)
    # an added-then-queried object is reachable
    tq = o.extend(np.asarray(newv[:1]))
    assert int(o.knn(int(tq[0]), 1)[0][0]) == 300
