"""Collective-budget regression pins (DESIGN.md §15).

`repro.core.distributed.collectives_per_step` statically walks the jaxpr
of a jitted sharded step and tallies cross-device collective primitives.
These tests pin the EXACT per-step budget of every sharded step variant:
the PR-2 path spent 11 collectives per exact step (4 merge all-gathers +
2 masked-psum state gathers + 3 subgradient-routing gathers + 2 in the
projection exchange); the fused layout spends 2 on a serving mesh.  A
refactor that reintroduces per-candidate or per-state gathers fails here
before it ever reaches a benchmark.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core import policy
from repro.core.distributed import (build_sharded_ivf, collectives_per_step,
                                    make_mutable_step_sharded,
                                    make_retrieval_step, make_step_sharded)
from repro.core.oma import OMAConfig
from repro.launch.mesh import make_mesh

N, D, B = 256, 8, 8


@pytest.fixture(scope="module")
def cfg():
    return policy.AcaiConfig(h=16, k=4, c_f=1.0, c_remote=16, c_local=8,
                             oma=OMAConfig(eta=0.01, projection_topk=48))


@pytest.fixture(scope="module")
def catalog():
    return jax.random.normal(jax.random.PRNGKey(0), (N, D))


def _mesh(shape):
    return make_mesh(shape, ("data", "model"))


def _static_counts(cfg, catalog, mesh, **kw):
    step = make_step_sharded(cfg, mesh, catalog, B, **kw)
    state = policy.init_state(N, cfg)
    return collectives_per_step(step, state, jnp.zeros((B, D)))


def _mutable_counts(cfg, catalog, mesh):
    step = make_mutable_step_sharded(cfg, mesh, B)
    state = policy.init_state(N, cfg)
    return collectives_per_step(step, state, jnp.zeros((B, D)), catalog,
                                jnp.ones((N,), bool))


def test_exact_step_serving_mesh_is_two_gathers(cfg, catalog):
    """(1, 1) serving mesh: ONE packed candidate merge + ONE packed
    projection exchange; the data-parallel routing gather is skipped on a
    size-1 batch axis.  11 -> 2 vs the PR-2 layout (5.5x)."""
    total, counts = _static_counts(cfg, catalog, _mesh((1, 1)))
    assert counts == {"all_gather": 2}, counts
    assert total == 2


def test_exact_step_data_parallel_adds_one_routing_gather(
        cfg, catalog, multi_device):
    """(2, 4): the packed [g, id] subgradient-routing gather over `data`
    is the only extra collective.  11 -> 3 (3.7x)."""
    total, counts = _static_counts(cfg, catalog, _mesh((2, 4)))
    assert counts == {"all_gather": 3}, counts
    assert total == 3


def test_mutable_step_costs_the_same_as_static(cfg, catalog, multi_device):
    """Catalog mutability (runtime slab + liveness mask, live-mask
    projection) adds ZERO communication."""
    assert _mutable_counts(cfg, catalog, _mesh((1, 1)))[1] == {
        "all_gather": 2}
    assert _mutable_counts(cfg, catalog, _mesh((2, 4)))[1] == {
        "all_gather": 3}


def test_ivf_and_chunk_paths_spend_one_overlap_gather(
        cfg, catalog, multi_device):
    """The approximate paths keep remote and cached-row merges separate so
    the remote exchange overlaps the local cached-row scan: exactly one
    extra all-gather vs the exact path, nothing else."""
    mesh = _mesh((2, 4))
    ivf = build_sharded_ivf(catalog, 4, nlist=8, nprobe=4)
    total, counts = _static_counts(cfg, catalog, mesh, ivf=ivf)
    assert counts == {"all_gather": 4}, counts
    total, counts = _static_counts(cfg, catalog, mesh, scan_chunk=64)
    assert counts == {"all_gather": 4}, counts


def test_retrieval_cell_budget(cfg, catalog, multi_device):
    """The roofline cell: merge + routing + projection gathers plus its
    two metric pmeans (psum-backed) — and nothing per candidate."""
    mesh = _mesh((2, 4))
    step = make_retrieval_step(mesh, n_shard=N // 4, d=D, c=16, k=4,
                               c_f=1.0, h=16, eta=0.01, top_a=32)
    total, counts = collectives_per_step(
        step, catalog, jnp.full((N,), 0.1), jnp.zeros((B, D)))
    assert counts == {"all_gather": 3, "psum": 2}, counts


def test_budget_is_independent_of_candidate_counts(cfg, catalog,
                                                   multi_device):
    """The tripwire this harness exists for: widening the candidate slabs
    must not widen the collective count (one packed payload, not one
    gather per candidate array)."""
    mesh = _mesh((2, 4))
    wide = policy.AcaiConfig(h=16, k=4, c_f=1.0, c_remote=48, c_local=24,
                             oma=OMAConfig(eta=0.01, projection_topk=48))
    assert (_static_counts(cfg, catalog, mesh)[0]
            == _static_counts(wide, catalog, mesh)[0] == 3)


def test_counts_are_a_dict_by_primitive(cfg, catalog):
    total, counts = _static_counts(cfg, catalog, _mesh((1, 1)))
    assert total == sum(counts.values())
    assert all(isinstance(v, int) and v > 0 for v in counts.values())
