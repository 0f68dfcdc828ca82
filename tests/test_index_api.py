"""Unified index API (DESIGN.md §8): protocol conformance across every
registered backend, IndexSpec round-tripping, registry completeness, CLI
option parsing, and the debug overflow counter."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.index as index_pkg
from repro.core import oma, policy, trace
from repro.index import (Index, IndexSpec, build_index, parse_index_opts,
                         registered_backends)
from repro.index.candidates import index_candidate_fn_batched
from repro.launch.mesh import make_mesh

# tiny-catalog build kwargs: every single-device backend, seconds to
# build — the canonical table lives in base.py (shared with smoke.sh)
from repro.index.base import TINY_BUILD_KWARGS as TINY  # noqa: E402


@pytest.fixture(scope="module")
def setup():
    catalog, reqs, _ = trace.sift_like(n=400, d=16, t=64, seed=0)
    cat, rq = jnp.array(catalog), jnp.array(reqs)
    cfg = policy.AcaiConfig(h=24, k=4, c_f=1.0, c_remote=16, c_local=8,
                            oma=oma.OMAConfig(eta=0.05))
    return cat, rq, cfg


@pytest.fixture(scope="module")
def built(setup):
    cat, _, _ = setup
    return {b: build_index(IndexSpec(b, kw), cat) for b, kw in TINY.items()}


# ---------------------------------------------------------------------------
# batched query-contract conformance (all backends, one shared test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(TINY))
def test_query_contract(setup, built, backend):
    cat, rq, _ = setup
    idx = built[backend]
    assert isinstance(idx, Index)
    assert idx.exact_distances in (True, False)
    assert idx.n == cat.shape[0]
    assert isinstance(idx.memory_bytes(), int) and idx.memory_bytes() > 0

    d, ids = idx.query(rq[:8], 5)
    assert d.shape == (8, 5) and ids.shape == (8, 5)
    assert ids.dtype == jnp.int32
    dd, ii = np.asarray(d), np.asarray(ids)
    valid = ii >= 0
    assert (ii[valid] < idx.n).all()
    assert (dd[valid] >= -1e-5).all()
    # ascending distances; underflow slots carry +inf and sort to the tail
    assert (np.diff(np.where(valid, dd, np.inf), axis=1) >= -1e-5).all()
    assert np.isinf(dd[~valid]).all()

    # a (d,) request vector is promoted to B = 1
    d1, i1 = idx.query(rq[0], 5)
    assert d1.shape == (1, 5) and i1.shape == (1, 5)


def test_underflow_marks_minus_one(setup):
    """Fewer than k reachable candidates -> id = -1, dist = +inf."""
    cat, rq, _ = setup
    # 2 tables x 4-slot buckets can reach at most 8 distinct candidates;
    # after cross-table dedup most queries see fewer than 8
    idx = build_index(
        IndexSpec("lsh", {"tables": 2, "bits": 6, "cap": 4}), cat)
    d, ids = idx.query(rq[:16], 8)
    ids, d = np.asarray(ids), np.asarray(d)
    assert (ids == -1).any(), "expected at least one underflowing slot"
    assert np.isinf(d[ids == -1]).all()
    # k beyond the structure's reachable width (NSW beam, LSH/IVF slab) is
    # structural underflow, not a crash — this is what AcaiConfig defaults
    # hit (c_remote=64) on small structures
    nsw = build_index(IndexSpec("nsw", TINY["nsw"]), cat)  # beam=16
    d, ids = nsw.query(rq[:4], 20)
    assert d.shape == (4, 20)
    assert (np.asarray(ids)[:, 16:] == -1).all()
    assert np.isinf(np.asarray(d)[:, 16:]).all()
    d, ids = idx.query(rq[:4], 16)            # LSH slab is 2*4 = 8 wide
    assert d.shape == (4, 16)
    assert (np.asarray(ids)[:, 8:] == -1).all()
    assert np.isinf(np.asarray(d)[:, 8:]).all()


# ---------------------------------------------------------------------------
# policy-stack conformance: every backend through make_replay_batched,
# and the sharded twin through the same registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(TINY))
def test_replay_batched_conformance(setup, built, backend):
    cat, rq, cfg = setup
    fnb = index_candidate_fn_batched(built[backend], cat, cfg.c_remote,
                                     cfg.c_local, h=cfg.h)
    state, m = policy.make_replay_batched(cfg, fnb, 8)(
        policy.init_state(cat.shape[0], cfg), rq)
    assert m.gain_int.shape == (64,)
    assert float(jnp.sum(m.gain_int)) >= 0
    assert int(state.t) == 64
    assert abs(float(jnp.sum(state.y)) - cfg.h) < 1e-2


def test_sharded_twin_through_registry(setup):
    """ivf_sharded builds through build_index(mesh=...) and drives the
    sharded replay twin (1-device mesh on CPU; multi-device covered by
    tests/test_distributed_acai.py and scripts/smoke.sh)."""
    from repro.core.distributed import ShardedIVF, make_replay_sharded

    cat, rq, cfg = setup
    mesh = make_mesh((1, 1), ("data", "model"))
    ivf = build_index(IndexSpec("ivf_sharded", {"nlist": 8, "nprobe": 4}),
                      cat, mesh=mesh)
    assert isinstance(ivf, ShardedIVF)
    state, m = make_replay_sharded(cfg, mesh, cat, 8, ivf=ivf)(
        policy.init_state(cat.shape[0], cfg), rq)
    assert m.gain_int.shape == (64,)
    assert float(jnp.sum(m.gain_int)) >= 0
    assert abs(float(jnp.sum(state.y)) - cfg.h) < 1e-2

    with pytest.raises(ValueError, match="sharded"):
        build_index(IndexSpec("ivf_sharded"), cat)  # mesh is required


def test_acai_cache_builds_from_spec(setup):
    """AcaiConfig.index is the one knob: AcaiCache builds its candidate
    generator from the spec; explicit candidate_fn alongside it warns."""
    import dataclasses

    from repro.index import IVFFlatIndex

    cat, rq, cfg = setup
    cfg_ivf = dataclasses.replace(
        cfg, index=IndexSpec("ivf", {"nlist": 8, "nprobe": 4}))
    cache = policy.AcaiCache(cat, cfg_ivf, seed=0)
    assert isinstance(cache.index, IVFFlatIndex)
    m1 = cache.serve_update(rq[0])
    assert m1.gain_int.shape == ()
    mb = cache.serve_update_batch(rq[1:9])
    assert mb.gain_int.shape == (8,)
    assert int(cache.state.t) == 9

    with pytest.warns(DeprecationWarning):
        policy.AcaiCache(
            cat, cfg_ivf,
            candidate_fn_batched=policy.exact_candidate_fn_batched(
                cat, cfg.c_remote, cfg.c_local))

    # the reserved "exact" spec (as written by dryrun provenance records)
    # normalizes to the spec-less exact generator instead of crashing
    cfg_exact = dataclasses.replace(cfg, index=IndexSpec("exact"))
    cache = policy.AcaiCache(cat, cfg_exact, seed=0)
    assert cache.index is None and cache.cfg.index is None
    assert cache.serve_update_batch(rq[:4]).gain_int.shape == (4,)


# ---------------------------------------------------------------------------
# IndexSpec serialization + registry + CLI parsing
# ---------------------------------------------------------------------------

def test_spec_roundtrip():
    spec = IndexSpec("ivf", {"nlist": 256, "nprobe": 16})
    d = spec.to_dict()
    assert d == {"backend": "ivf", "nlist": 256, "nprobe": 16}
    assert IndexSpec.from_dict(d) == spec
    assert IndexSpec.from_dict(spec.to_dict()).to_dict() == d
    assert spec.with_params(nprobe=32).params["nprobe"] == 32
    assert hash(spec) == hash(IndexSpec("ivf", {"nprobe": 16, "nlist": 256}))


def test_spec_errors(setup):
    cat, _, _ = setup
    with pytest.raises(ValueError, match="unknown index backend"):
        IndexSpec.from_dict({"backend": "annoy"})
    with pytest.raises(ValueError, match="unknown index backend"):
        build_index(IndexSpec("annoy"), cat)
    with pytest.raises(ValueError, match="backend"):
        IndexSpec.from_dict({"nlist": 4})
    with pytest.raises(ValueError, match="backend"):
        IndexSpec("ivf", {"backend": "ivf"})


def test_resolve_spec():
    """'exact' is the reserved spec-less name: every serialized form of it
    (the dryrun provenance record, the CLI default) resolves to None."""
    from repro.index.base import resolve_spec

    assert resolve_spec(None) is None
    assert resolve_spec("exact") is None
    assert resolve_spec({"backend": "exact"}) is None
    assert resolve_spec(IndexSpec("exact")) is None
    spec = IndexSpec("ivf", {"nlist": 8})
    assert resolve_spec(spec) is spec
    assert resolve_spec("nsw") == IndexSpec("nsw")
    assert resolve_spec({"backend": "ivf", "nlist": 8}) == spec
    with pytest.raises(ValueError, match="unknown index backend"):
        resolve_spec("annoy")
    with pytest.raises(ValueError, match="unknown index backend"):
        resolve_spec(IndexSpec("annoy"))
    with pytest.raises(ValueError, match="no params"):
        resolve_spec({"backend": "exact", "nlist": 8})
    with pytest.raises(TypeError):
        resolve_spec(42)


def test_parse_index_opts():
    assert parse_index_opts(
        ["nlist=256", "refine=0", "eta=0.5", "kernel=xla"]
    ) == {"nlist": 256, "refine": 0, "eta": 0.5, "kernel": "xla"}
    assert parse_index_opts([]) == {}
    assert parse_index_opts(None) == {}
    with pytest.raises(ValueError, match="key=value"):
        parse_index_opts(["nlist"])


def test_registry_complete(setup, built):
    """Every index class exported from repro.index is constructible through
    a registered backend, and the registry names are stable."""
    assert set(registered_backends()) == {
        "flat", "ivf", "ivfpq", "lsh", "nsw", "ivf_sharded"}
    assert registered_backends(sharded=True) == ("ivf_sharded",)
    # the shared tiny-kwargs table (this file + smoke.sh) covers every
    # single-device backend
    assert set(TINY) == set(registered_backends(sharded=False))
    exported = {name for name in index_pkg.__all__
                if name.endswith("Index") and name != "Index"}
    constructed = {type(idx).__name__ for idx in built.values()}
    assert exported <= constructed, exported - constructed


# ---------------------------------------------------------------------------
# debug-mode local_cap overflow counter
# ---------------------------------------------------------------------------

def test_local_overflow_counter(setup):
    """Occupancy above the candidate generator's static local_cap is a
    silent quality loss; with cfg.debug the step books it per request."""
    import dataclasses

    cat, rq, cfg = setup
    n = cat.shape[0]
    idx = build_index(IndexSpec("flat"), cat)
    fnb = index_candidate_fn_batched(idx, cat, cfg.c_remote, cfg.c_local,
                                     local_cap=8)
    assert fnb.local_cap == 8
    x = jnp.zeros((n,)).at[jnp.arange(20)].set(1.0)  # occupancy 20 > cap 8
    state = policy.CacheState(
        y=jnp.full((n,), cfg.h / n), x=x,
        t=jnp.zeros((), jnp.int32), key=jax.random.PRNGKey(0))

    cfg_dbg = dataclasses.replace(cfg, debug=True)
    _, m = policy.make_step_batched(cfg_dbg, fnb, 4)(state, rq[:4])
    np.testing.assert_array_equal(np.asarray(m.local_overflow),
                                  np.full((4,), 12, np.int32))
    # per-request step sees the same counter
    _, m1 = policy.make_step(cfg_dbg, policy.per_request_view(fnb))(
        state, rq[0])
    assert int(m1.local_overflow) == 12
    # debug off (the default): counter stays zero
    _, m0 = policy.make_step_batched(cfg, fnb, 4)(state, rq[:4])
    assert int(jnp.sum(m0.local_overflow)) == 0


def test_local_overflow_counter_sharded(setup):
    """The sharded step's capped cached-row gather (scan_chunk/ivf paths,
    static 2h + 64 bound per shard) books the same debug counter."""
    import dataclasses

    from repro.core.distributed import make_step_sharded

    cat, rq, cfg = setup
    n = cat.shape[0]
    cfg_dbg = dataclasses.replace(cfg, debug=True)
    cap = 2 * cfg.h + 64                      # 112 on the 1-shard mesh
    occ = cap + 30
    x = jnp.zeros((n,)).at[jnp.arange(occ)].set(1.0)
    state = policy.CacheState(
        y=jnp.full((n,), cfg.h / n), x=x,
        t=jnp.zeros((), jnp.int32), key=jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    _, m = make_step_sharded(cfg_dbg, mesh, cat, 4, scan_chunk=64)(
        state, rq[:4])
    np.testing.assert_array_equal(np.asarray(m.local_overflow),
                                  np.full((4,), 30, np.int32))
    # the uncapped exact sharded path cannot truncate: counter stays zero
    _, m0 = make_step_sharded(cfg_dbg, mesh, cat, 4)(state, rq[:4])
    assert int(jnp.sum(m0.local_overflow)) == 0
