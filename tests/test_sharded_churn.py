"""Sharded-churn coverage (DESIGN.md §15).

The mutable-catalog serving mode on a mesh: bitwise 1-device parity with
the single-device mutable path (including churn, growth and compaction),
the invalidation invariant across real multi-device shards, the
heavy-removal projection edges (live count < top-A, an all-tombstoned
shard), owner-shard routing round-trips, and compaction-remap consistency
with the answer-cache inverted map.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import churn, policy, trace
from repro.core.distributed import (make_mutable_step_sharded, owner_shard,
                                    route_ids_by_owner, sharded_slab_append)
from repro.core.oma import OMAConfig
from repro.launch.mesh import make_mesh

D = 8


@pytest.fixture(scope="module")
def cfg():
    # projection_topk == the sharded step's default top_a: the bitwise
    # contract's precondition (and small enough to stay < n_shard through
    # every capacity the growth/compaction schedule visits below)
    return policy.AcaiConfig(h=16, k=4, c_f=1.0, c_remote=16, c_local=8,
                             oma=OMAConfig(eta=0.01, projection_topk=48))


def _mesh(shape):
    return make_mesh(shape, ("data", "model"))


def _rolling():
    params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"])
    catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
    events = trace.rolling_catalog_events(**params)
    n0 = churn.warm_size(params["n"], params["warm"])
    return catalog, reqs, events, n0


# ---------------------------------------------------------------------------
# bitwise 1-device parity: the sharded mutable path IS the mutable path
# ---------------------------------------------------------------------------

def test_mutable_step_bitwise_vs_single_device_with_tombstones(cfg):
    """One step, 40 tombstoned rows: make_mutable_step_sharded on a
    (1, 1) mesh == exact_mutable_candidates + make_mutable_step, bitwise
    in every carried state and every metric field."""
    n = 128
    cat = jax.random.normal(jax.random.PRNGKey(1), (n, D))
    alive = jnp.ones((n,), bool).at[jnp.arange(40)].set(False)
    st = policy.init_state(n, cfg, seed=3)
    st = policy.CacheState(jnp.where(alive, st.y, 0.0),
                           jnp.where(alive, st.x, 0.0), st.t, st.key)
    rs = jax.random.normal(jax.random.PRNGKey(2), (8, D))

    ids, d, valid = policy.exact_mutable_candidates(
        rs, st.x, cat, alive, cfg.c_remote, cfg.c_local)
    st_ref, m_ref = policy.make_mutable_step(cfg, 8)(st, ids, d, valid,
                                                     alive)
    st_sh, m_sh = make_mutable_step_sharded(cfg, _mesh((1, 1)), 8)(
        st, rs, cat, alive)

    for name in ("y", "x", "t"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st_ref, name)),
            np.asarray(getattr(st_sh, name)), err_msg=name)
    for f in m_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(m_ref, f)),
            np.asarray(getattr(m_sh, f)), err_msg=f)


def test_churn_replay_bitwise_on_1device_mesh(cfg):
    """The whole rolling-catalog churn replay — adds, removals, capacity
    bookkeeping AND epoch compaction — is bitwise identical between the
    plain AcaiCache and the (1, 1)-mesh sharded one: metrics, y, x."""
    catalog, reqs, events, n0 = _rolling()
    assert len(events) > 0

    plain = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0)
    res_p = churn.replay_with_churn(plain, catalog, reqs, events, batch=8,
                                    compact_every=24)
    shard = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0,
                             mesh=_mesh((1, 1)))
    res_s = churn.replay_with_churn(shard, catalog, reqs, events, batch=8,
                                    compact_every=24)

    assert res_p["compactions"] == res_s["compactions"] > 0
    for k in ("gain", "served_local", "occupancy", "fetched", "cost"):
        np.testing.assert_array_equal(res_p[k], res_s[k], err_msg=k)
    np.testing.assert_array_equal(np.asarray(plain.state.y),
                                  np.asarray(shard.state.y))
    np.testing.assert_array_equal(np.asarray(plain.state.x),
                                  np.asarray(shard.state.x))
    np.testing.assert_array_equal(np.asarray(plain.valid),
                                  np.asarray(shard.valid))


def test_sharded_append_growth_matches_single_device(cfg):
    """sharded_slab_append at P = 1 follows slab_append's growth schedule
    and writes bitwise; at P = 2 a straddling batch splits into owner-
    block runs, capacity stays mesh-aligned, ids stay monotonic."""
    from repro.index.base import slab_append

    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((70, D)).astype(np.float32)
    emb_np = rng.standard_normal((128, D)).astype(np.float32)
    valid_np = np.arange(128) < 100

    def slabs():  # the append DONATES its inputs -> fresh buffers per call
        return jnp.asarray(emb_np), jnp.asarray(valid_np)

    e1, v1, i1 = slab_append(*slabs(), 100, vecs)
    e2, v2, i2 = sharded_slab_append(*slabs(), 100, vecs, 1)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(i1, i2)

    e4, v4, i4 = sharded_slab_append(*slabs(), 100, vecs, 2)
    assert e4.shape[0] % 2 == 0
    np.testing.assert_array_equal(i4, np.arange(100, 170))
    np.testing.assert_array_equal(np.asarray(e4[100:170]), vecs)
    assert bool(v4[100:170].all()) and not bool(v4[170:].any())


# ---------------------------------------------------------------------------
# multi-device: invariants on real >1-shard meshes
# ---------------------------------------------------------------------------

def test_removed_never_served_across_shards(cfg, multi_device):
    """Rows removed from different owner shards hold zero y/x through
    every subsequent sharded OMA + rounding update (the invalidation
    invariant, shard-wise)."""
    n = 128
    rng = np.random.default_rng(5)
    cat = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    rq = jnp.asarray(rng.standard_normal((40, D)), jnp.float32)
    cache = policy.AcaiCache(cat, cfg, seed=0, mesh=_mesh((1, 4)))
    removed = [3, 40, 70, 101]  # one row per owner shard (n_shard = 32)
    assert sorted(set(owner_shard(removed, n, 4))) == [0, 1, 2, 3]
    cache.remove_objects(removed)
    jd = jnp.asarray(removed)
    for s in range(0, 40, 8):
        m = cache.serve_update_batch(rq[s:s + 8])
        assert float(jnp.abs(cache.state.y[jd]).sum()) == 0.0
        assert float(jnp.abs(cache.state.x[jd]).sum()) == 0.0
    assert float(m.occupancy[0]) <= cfg.h + 1e-6
    assert cache.live_count == n - len(removed)
    assert not np.intersect1d(np.asarray(cache.cached_ids), removed).size


def test_all_tombstoned_shard_projection_edge(cfg, multi_device):
    """Heavy removal: one shard fully tombstoned (live count 0 < top-A)
    and another below top-A.  The projection's padded zero heads keep the
    water-filling finite; serving stays green and dead mass stays dead."""
    n = 128  # (1, 2) mesh -> n_shard = 64, top_a = 48
    rng = np.random.default_rng(7)
    cat = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    rq = jnp.asarray(rng.standard_normal((16, D)), jnp.float32)
    cache = policy.AcaiCache(cat, cfg, seed=0, mesh=_mesh((1, 2)))
    # kill ALL of shard 1's rows [64, 128) + most of shard 0's (8 live)
    cache.remove_objects(list(range(56, 128)))
    assert cache.live_count == 56
    cache.remove_objects(list(range(8, 56)))
    assert cache.live_count == 8
    for s in range(0, 16, 8):
        m = cache.serve_update_batch(rq[s:s + 8])
    y = np.asarray(cache.state.y)
    assert np.isfinite(y).all()
    assert float(np.abs(y[8:]).sum()) == 0.0
    assert np.isfinite(np.asarray(m.gain_int)).all()
    assert float(m.occupancy[0]) <= cfg.h + 1e-6


def test_churn_replay_multi_device_green(cfg, multi_device):
    """The rolling-catalog churn suite cell runs green on a real 2-shard
    mesh: every event applies, compaction keeps the slab mesh-aligned,
    and the final live window matches the schedule."""
    catalog, reqs, events, n0 = _rolling()
    cache = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0,
                             mesh=_mesh((1, 2)))
    res = churn.replay_with_churn(cache, catalog, reqs, events, batch=8,
                                  compact_every=24)
    assert res["events_applied"] == len(events)
    assert res["compactions"] > 0
    assert cache.live_count == n0
    assert cache.catalog.shape[0] % 2 == 0
    assert np.isfinite(res["gain"]).all()


def test_mesh_mutation_guards(cfg, multi_device):
    """Sharded *index* configurations still reject mutation (the exact
    masked scan is the only mutable sharded serving path), and mis-
    aligned capacities are caught before anything is touched."""
    rng = np.random.default_rng(0)
    cat = jnp.asarray(rng.standard_normal((128, D)), jnp.float32)
    chunked = policy.AcaiCache(cat, cfg, seed=0, mesh=_mesh((1, 2)),
                               sharded_kwargs={"scan_chunk": 64})
    with pytest.raises(NotImplementedError, match="sharded"):
        chunked.add_objects(np.zeros((2, D), np.float32))
    assert not chunked._mutated


# ---------------------------------------------------------------------------
# owner-shard routing round-trips (global-id arithmetic)
# ---------------------------------------------------------------------------

def _assert_roundtrip(ids, cap, p):
    groups = route_ids_by_owner(ids, cap, p)
    shards = [s for s, _ in groups]
    assert shards == sorted(set(shards))  # ascending, unique
    block = cap // p
    back = []
    for s, gids in groups:
        assert ((gids >= s * block) & (gids < (s + 1) * block)).all()
        # relative order within a group survives routing
        orig = [i for i in ids if s * block <= i < (s + 1) * block]
        assert gids.tolist() == orig
        back.extend(gids.tolist())
    assert sorted(back) == sorted(ids)  # a permutation of the input


def test_owner_routing_roundtrip_property():
    """Global ids survive owner-shard routing round-trips: the groups
    partition the batch by owner block, preserve relative order, and
    concatenate back to a permutation of the input.  Runs under
    hypothesis when installed; otherwise a seeded sweep of the same
    property (the repo ships no hypothesis dependency)."""
    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st

        @settings(max_examples=60, deadline=None)
        @given(st.integers(1, 4).map(lambda e: 2 ** e),
               st.integers(0, 6),
               st.lists(st.integers(0, 1023), min_size=0, max_size=40))
        def prop(p, cap_pow, raw):
            cap = p * (2 ** cap_pow)
            ids = [i % cap for i in raw]
            _assert_roundtrip(ids, cap, p)

        prop()
    except ImportError:
        rng = np.random.default_rng(42)
        for _ in range(200):
            p = int(2 ** rng.integers(0, 4))
            cap = p * int(2 ** rng.integers(0, 7))
            k = int(rng.integers(0, 41))
            ids = rng.integers(0, cap, size=k).tolist()
            _assert_roundtrip(ids, cap, p)
    # P = 1 is the identity group — the single-device path, bitwise
    assert [(0, [7, 3, 7])] == [
        (s, g.tolist()) for s, g in route_ids_by_owner([7, 3, 7], 64, 1)]
    with pytest.raises(ValueError, match="divide"):
        owner_shard([0], 130, 4)


# ---------------------------------------------------------------------------
# compaction remap vs the answer-cache inverted map
# ---------------------------------------------------------------------------

def test_compaction_remap_consistent_with_answer_cache(cfg, multi_device):
    """A sharded compact's id remap pushed through AnswerCache.remap_ids
    keeps the inverted map exactly consistent: every stored id lands on
    the row now holding the same embedding, and the inverted index maps
    each new id back to precisely the entries that reference it."""
    from repro.serve.answer_cache import AnswerCache, AnswerCacheSpec

    n = 128
    rng = np.random.default_rng(11)
    cat = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    cache = policy.AcaiCache(cat, cfg, seed=0, mesh=_mesh((1, 2)))

    ac = AnswerCache(AnswerCacheSpec(capacity=16))
    qs = rng.standard_normal((3, D)).astype(np.float32)
    stored = np.array([[2, 90, 31], [64, 5, 100], [31, 2, 127]], np.int32)
    ac.store_batch(qs, 3, np.ones((3, 3), np.float32), stored)

    removed = [0, 7, 40, 70, 111]  # none referenced by the entries
    cache.remove_objects(removed)
    assert ac.invalidate_removed(removed) == 0
    old_emb = np.asarray(cache.catalog)
    remap = cache.compact()

    assert cache.catalog.shape[0] % 2 == 0  # mesh-aligned new capacity
    ac.remap_ids(remap)
    entries = list(ac._store.values())
    new_emb = np.asarray(cache.catalog)
    for e_old, e_new in zip(stored, entries):
        np.testing.assert_array_equal(remap[e_old], e_new.ids)
        # the remapped row holds the same object (same embedding)
        np.testing.assert_array_equal(old_emb[e_old], new_emb[e_new.ids])
    for oid, keys in ac._inv.items():
        assert all(oid in ac._store[k].ids for k in keys)
    all_ids = {int(i) for e in entries for i in e.ids}
    assert set(ac._inv) == all_ids


def test_mutable_sharded_rejects_non_negentropy(cfg):
    euclid = dataclasses.replace(
        cfg, oma=dataclasses.replace(cfg.oma, mirror="euclidean"))
    with pytest.raises(NotImplementedError, match="negentropy"):
        make_mutable_step_sharded(euclid, _mesh((1, 1)), 8)
