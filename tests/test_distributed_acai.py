"""Distributed AÇAI retrieval step == single-device reference (subprocess
with 8 placeholder devices; same discipline as launch/dryrun.py), plus the
sharded replay twin of the batched pipeline."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_mesh

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import oma, policy, trace
    from repro.core.distributed import (build_sharded_ivf,
                                        make_replay_sharded,
                                        make_retrieval_step, reference_step)
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    N, d, B, C, k, h = 512, 16, 8, 24, 4, 32
    catalog = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    y0 = jnp.full((N,), h / N, jnp.float32)
    reqs = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)

    mesh = make_mesh((2, 4), ("data", "model"))
    kw = dict(n_shard=N // 4, d=d, c=C, k=k, c_f=1.0, h=h, eta=0.05,
              top_a=h + 16, batch_axes=("data",))
    step = make_retrieval_step(mesh, **kw)
    y1, ans, metrics = jax.jit(step)(catalog, y0, reqs)
    y_ref, ans_ref = reference_step(catalog, y0, reqs, c=C, k=k, c_f=1.0,
                                    h=h, eta=0.05, top_a=h + 16)
    err = float(jnp.abs(y1 - y_ref).max())
    # answers: compare the (sorted) answered object-id sets per request
    same = all(set(np.array(a).tolist()) == set(np.array(b).tolist())
               for a, b in zip(np.array(ans), np.array(ans_ref)))

    # ---- scan_chunk > 0: the fused-kernel local scan (ops.topk_l2_fused)
    step_c = make_retrieval_step(mesh, scan_chunk=50, **kw)
    y1c, ansc, _ = jax.jit(step_c)(catalog, y0, reqs)
    err_chunk = float(jnp.abs(y1c - y_ref).max())
    same_chunk = all(set(np.array(a).tolist()) == set(np.array(b).tolist())
                     for a, b in zip(np.array(ansc), np.array(ans_ref)))

    # ---- sharded IVF: each shard probes only its own inverted lists
    ivf = build_sharded_ivf(catalog, 4, nlist=16, nprobe=8)
    step_i = make_retrieval_step(mesh, ivf=ivf, **kw)
    y1i, ansi, mi = jax.jit(step_i)(catalog, y0, reqs)
    ivf_sum_ok = abs(float(jnp.sum(y1i)) - h) < 1e-2
    ivf_ids_ok = bool((np.array(ansi) >= 0).all()
                      and (np.array(ansi) < N).all())

    # ---- sharded replay (2 data x 4 model) vs single-device batched replay
    T = 256
    trace_cat, trace_reqs, _ = trace.sift_like(n=N, d=d, t=T, seed=0)
    tcat, treqs = jnp.array(trace_cat), jnp.array(trace_reqs)
    cfg = policy.AcaiConfig(h=h, k=k, c_f=1.0, c_remote=24, c_local=8,
                            oma=oma.OMAConfig(eta=0.05,
                                              projection_topk=2 * h + 64))
    s0 = policy.init_state(N, cfg)
    fnb = policy.exact_candidate_fn_batched(tcat, cfg.c_remote, cfg.c_local)
    _, m_b = policy.make_replay_batched(cfg, fnb, 8)(s0, treqs)
    _, m_s = jax.jit(make_replay_sharded(cfg, mesh, tcat, 8))(s0, treqs)
    nag_b = float(np.sum(np.asarray(m_b.gain_int))) / (k * 1.0 * T)
    nag_s = float(np.sum(np.asarray(m_s.gain_int))) / (k * 1.0 * T)

    print(json.dumps({"yerr": err, "answers_match": bool(same),
                      "yerr_chunk": err_chunk,
                      "answers_match_chunk": bool(same_chunk),
                      "ivf_sum_ok": ivf_sum_ok, "ivf_ids_ok": ivf_ids_ok,
                      "ivf_gain": float(mi["gain"]),
                      "nag_batched": nag_b, "nag_sharded": nag_s,
                      "gain": float(metrics["gain"]),
                      "ndev": jax.device_count()}))
""")


@pytest.fixture(scope="module")
def child_result():
    out = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                         text=True, timeout=600,
                         env={**os.environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_distributed_matches_reference(child_result):
    res = child_result
    assert res["ndev"] == 8
    assert res["yerr"] < 2e-4, res
    assert res["answers_match"], res
    # uniform init y = h/N < 0.5 => thresholded cache starts empty => zero
    # gain on the first step; it must never be negative.
    assert res["gain"] >= 0


def test_distributed_scan_chunk_fused_path(child_result):
    """scan_chunk > 0 routes the local scan through ops.topk_l2_fused; the
    numerics must match the full-matrix reference just as tightly."""
    res = child_result
    assert res["yerr_chunk"] < 2e-4, res
    assert res["answers_match_chunk"], res


def test_distributed_sharded_ivf(child_result):
    """Per-shard IVF probing keeps the OMA state on the capped simplex and
    answers with real catalog ids (approximate candidates, exact update)."""
    res = child_result
    assert res["ivf_sum_ok"], res
    assert res["ivf_ids_ok"], res
    assert res["ivf_gain"] >= 0


def test_sharded_replay_nag_close_to_batched(child_result):
    """make_replay_sharded on a (2, 4) mesh reaches the quality of the
    single-device batched replay (same trace, same config)."""
    res = child_result
    assert res["nag_sharded"] > 0.95 * res["nag_batched"], res
    assert res["nag_sharded"] > 0


def test_sharded_replay_bit_consistent_on_1device_mesh():
    """On a 1-device mesh make_replay_sharded IS make_replay_batched with
    exact candidates — every carried state and metric, bit for bit."""
    from repro.core import oma, policy, trace
    from repro.core.distributed import make_replay_sharded

    catalog, reqs, _ = trace.sift_like(n=800, d=16, t=128, seed=0)
    cat, reqs = jnp.array(catalog), jnp.array(reqs)
    a = 2 * 48 + 64
    cfg = policy.AcaiConfig(h=48, k=8, c_f=1.0, c_remote=32, c_local=16,
                            oma=oma.OMAConfig(eta=0.05, projection_topk=a))
    mesh = make_mesh((1, 1), ("data", "model"))
    fnb = policy.exact_candidate_fn_batched(cat, cfg.c_remote, cfg.c_local)
    s0 = policy.init_state(cat.shape[0], cfg)
    for b in (1, 8):
        st_a, m_a = policy.make_replay_batched(cfg, fnb, b)(s0, reqs)
        st_b, m_b = make_replay_sharded(cfg, mesh, cat, b, top_a=a)(s0, reqs)
        for name in policy.StepMetrics._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(m_a, name)),
                np.asarray(getattr(m_b, name)), err_msg=f"B={b} {name}")
        np.testing.assert_array_equal(np.asarray(st_a.y), np.asarray(st_b.y))
        np.testing.assert_array_equal(np.asarray(st_a.x), np.asarray(st_b.x))
        assert int(st_a.t) == int(st_b.t)


def test_acai_cache_mesh_serving_path():
    """AcaiCache(mesh=...) serves single requests and mini-batches through
    the sharded step (1-device mesh here; the API the serving tier uses)."""
    from repro.core import oma, policy, trace

    catalog, reqs, _ = trace.sift_like(n=400, d=16, t=32, seed=1)
    cat, reqs = jnp.array(catalog), jnp.array(reqs)
    cfg = policy.AcaiConfig(h=32, k=4, c_f=1.0, c_remote=16, c_local=8,
                            oma=oma.OMAConfig(eta=0.05))
    mesh = make_mesh((1, 1), ("data", "model"))
    cache = policy.AcaiCache(cat, cfg, seed=0, mesh=mesh)
    m1 = cache.serve_update(reqs[0])
    assert m1.gain_int.shape == ()
    mb = cache.serve_update_batch(reqs[1:9])
    assert mb.gain_int.shape == (8,)
    assert int(cache.state.t) == 9
    assert abs(float(jnp.sum(cache.state.y)) - cfg.h) < 1e-2


@pytest.mark.parametrize("where", ["device0", "mesh4"])
def test_second_batch_reuses_compiled_step(where, multi_device):
    """A catalog big enough to reach the step as an argument (committed to
    one device, or split over a 4-shard mesh): the cache state starts
    where the step returns it, so batch 2 runs batch 1's program."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import oma, policy, trace

    catalog, reqs, _ = trace.sift_like(n=1 << 15, d=16, t=32, seed=1)
    assert catalog.nbytes >= policy.ARG_BYTES
    cfg = policy.AcaiConfig(h=32, k=4, c_f=1.0, c_remote=16, c_local=8,
                            oma=oma.OMAConfig(eta=0.05))
    if where == "mesh4":
        mesh = make_mesh((1, 4), ("data", "model"),
                         devices=jax.devices()[:4])
        cat = jax.device_put(catalog,
                             NamedSharding(mesh, PartitionSpec("model")))
        cache = policy.AcaiCache(cat, cfg, seed=0, mesh=mesh,
                                 sharded_kwargs={"scan_chunk": 4096})
    else:
        cat = jax.device_put(catalog, jax.devices()[0])
        cache = policy.AcaiCache(cat, cfg, seed=0)
    for s in (0, 8, 16):
        cache.serve_update_batch(jnp.asarray(reqs[s:s + 8]))
    assert cache._bsteps[8]._cache_size() == 1
