#!/usr/bin/env bash
# Tier-1 tests + a fast all-backends index-API conformance pass + a
# mutable-catalog churn smoke + a resilient-serving smoke + an online-
# serving smoke (two arrival kinds + the fixed-window equivalence pin) +
# an answer-cache smoke (hit path + churn invalidation + on/off parity) +
# a BENCH_*.json-vs-README schema check + every example in tiny mode +
# a 2-device sharded-serving smoke step, so neither the unified index
# registry, the churn subsystem, the serving tiers, the bench schema
# docs, the runnable entry points, nor the distributed path can
# silently rot on machines without accelerators.
#
#   bash scripts/smoke.sh
#
# Opt-in (tens of minutes on CPU): SMOKE_FULL_CHURN=1 appends the
# 1M x 128 device-mutation scale check (`--suite churn --full`,
# DESIGN.md §14) and rewrites BENCH_churn_full.json.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== all-backends conformance (tiny catalog, DESIGN.md §8) =="
python - <<'EOF'
import numpy as np
import jax, jax.numpy as jnp
from repro.core import oma, policy, trace
from repro.index import Index, IndexSpec, build_index, registered_backends
from repro.index.candidates import index_candidate_fn_batched

catalog, reqs, _ = trace.sift_like(n=256, d=16, t=32, seed=0)
cat, rq = jnp.array(catalog), jnp.array(reqs)
cfg = policy.AcaiConfig(h=16, k=4, c_f=1.0, c_remote=12, c_local=8,
                        oma=oma.OMAConfig(eta=0.05))
# the canonical tiny build-kwargs table (shared with the conformance
# test): this sweep is the standalone seconds-fast re-check of the same
# contract, for runs where pytest is filtered or skipped
from repro.index.base import TINY_BUILD_KWARGS as tiny
assert set(tiny) == set(registered_backends(sharded=False)), \
    "conformance table out of date with the registry"
for backend, kw in tiny.items():
    idx = build_index(IndexSpec(backend, kw), cat)
    assert isinstance(idx, Index) and idx.n == cat.shape[0]
    d, ids = idx.query(rq[:4], 5)
    assert d.shape == (4, 5) and ids.shape == (4, 5), backend
    fnb = index_candidate_fn_batched(idx, cat, cfg.c_remote, cfg.c_local,
                                     h=cfg.h)
    st, m = policy.make_replay_batched(cfg, fnb, 8)(
        policy.init_state(cat.shape[0], cfg), rq)
    assert m.gain_int.shape == (32,) and float(jnp.sum(m.gain_int)) >= 0
    print(f"  {backend:6s} OK  (mem {idx.memory_bytes() / 1024:.0f} KiB)")
print("all-backends conformance OK")
EOF

echo "== all-policies x all-traces experiment smoke (DESIGN.md §9) =="
python - <<'EOF'
import numpy as np
from repro.core import baselines as B
from repro.core import policy_api as PA
from repro.core import trace
from repro.core.costs import CostModel

# the canonical tiny tables (shared with tests/test_policy_api.py): the
# sweep is the standalone seconds-fast re-check of the policy + trace
# registries for runs where pytest is filtered or skipped
assert set(PA.TINY_POLICY_KWARGS) == set(PA.registered_policies()), \
    "policy conformance table out of date with the registry"
assert set(trace.TINY_TRACE_KWARGS) == set(trace.registered_traces()), \
    "trace conformance table out of date with the registry"
for tname, tkw in trace.TINY_TRACE_KWARGS.items():
    catalog, reqs, _ = trace.build_trace(tname, **tkw)
    oracle = B.ServerOracle(catalog, reqs, kmax=16)
    ts = np.arange(reqs.shape[0])
    line = []
    for pname, pkw in PA.TINY_POLICY_KWARGS.items():
        pol = PA.build_policy(PA.PolicySpec(pname, pkw), catalog,
                              CostModel(c_f=1.0), oracle=oracle, seed=0)
        res = PA.replay_trace(pol, reqs, ts, batch=8)
        assert res["gain"].shape == (64,), (tname, pname)
        assert (res["occupancy"] <= pol.h + 1e-6).all(), (tname, pname)
        line.append(f"{pname}={pol.normalized_gain(res['gain'].sum(), 64):.3f}")
    print(f"  {tname:12s} NAG: " + " ".join(line))
print("all-policies x all-traces smoke OK")
EOF

echo "== mutable-catalog churn smoke (DESIGN.md §10) =="
python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from repro.core import churn, oma, policy, trace
from repro.core.trace import TINY_TRACE_KWARGS

params = dict(TINY_TRACE_KWARGS["rolling_catalog"])
n0 = churn.warm_size(params["n"], params["warm"])
cfg = policy.AcaiConfig(h=16, k=4, c_f=1.0, c_remote=12, c_local=8,
                        oma=oma.OMAConfig(eta=0.05, rounding="depround"))

# churn_rate=0 must be bit-consistent with the static batched replay
p0 = dict(params, churn_rate=0.0)
catalog0, reqs0, _ = trace.build_trace("rolling_catalog", **p0)
assert trace.rolling_catalog_events(**p0) == []
cache0 = policy.AcaiCache(jnp.asarray(catalog0[:n0]), cfg, seed=0)
res0 = churn.replay_with_churn(cache0, catalog0, reqs0, [], batch=8)
st, m = policy.make_replay_batched(
    cfg, policy.exact_candidate_fn_batched(jnp.asarray(catalog0[:n0]),
                                           cfg.c_remote, cfg.c_local), 8)(
    policy.init_state(n0, cfg, seed=0), jnp.asarray(reqs0))
assert (res0["gain"] == np.asarray(m.gain_int)).all(), "churn0 != static"

# under churn: every event applies, expired objects carry zero mass
catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
events = trace.rolling_catalog_events(**params)
from repro.index import IndexSpec
import dataclasses
cfg_ivf = dataclasses.replace(cfg, index=IndexSpec("ivf", {"nlist": 8,
                                                           "nprobe": 4}))
cache = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg_ivf, seed=0)
res = churn.replay_with_churn(cache, catalog, reqs, events, batch=8,
                              refresh_every=32)
assert res["events_applied"] == len(events) > 0
removed = np.concatenate([ev[2] for ev in events])
assert float(jnp.abs(cache.state.y[jnp.asarray(removed)]).sum()) == 0.0
print(f"churn smoke OK ({len(events)} events, "
      f"NAG={res['gain'].sum() / (cfg.k * cfg.c_f * res['requests']):.3f})")
EOF

echo "== resilient-serving smoke: outage + recovery (DESIGN.md §11) =="
python - <<'EOF'
import numpy as np
from repro.core import policy_api as PA
from repro.core import trace
from repro.core.costs import CostModel
from repro.serve.remote import FaultSpec, FaultyRemote
from repro.serve.resilience import (BreakerConfig, ResilienceConfig,
                                    ResilientPolicy, replay_resilient)

catalog, reqs, _ = trace.sift_like(n=256, d=16, t=96, seed=0)
spec = PA.PolicySpec("acai", PA.TINY_POLICY_KWARGS["acai"])
cm = CostModel(c_f=1.0)

# hard outage across the middle third, recovery after (short breaker
# cooldown so the half-open probe recloses inside the 96-request trace)
rcfg = ResilienceConfig(breaker=BreakerConfig(cooldown_requests=16))
pol = ResilientPolicy(PA.build_policy(spec, catalog, cm, seed=0),
                      remote=FaultyRemote(FaultSpec(outages=((32, 64),))),
                      resilience=rcfg)
res = replay_resilient(pol, reqs, batch=8)
c = res["counters"]
assert c["remote_failures"] >= 32, c
assert c["degraded"] + c["shed"] == c["remote_failures"], c
assert res["goodput"] > 0.9, res["goodput"]          # ladder held
deg = np.asarray(res["degraded"])
assert deg[32:40].all(), "outage window not degraded"
assert not deg[:32].any(), "pre-outage requests touched the ladder"
assert pol.session.breaker.transitions >= 1          # opened on the outage
assert np.isfinite(np.asarray(pol.inner.cache.state.y)).all()
# recovery: the tail serves healthy again once the breaker recloses
assert np.asarray(res["remote_failures"])[-8:].sum() == 0, "no recovery"

# fault-rate 0 == the static replay, bit for bit
pol0 = ResilientPolicy(PA.build_policy(spec, catalog, cm, seed=0),
                       remote=FaultyRemote(FaultSpec()),
                       resilience=ResilienceConfig())
ref = PA.build_policy(spec, catalog, cm, seed=0).replay(reqs)
got = replay_resilient(pol0, reqs, batch=8)
assert np.array_equal(got["gain"], np.asarray(ref["gain"]))
print(f"resilience smoke OK ({c['remote_failures']} failures, "
      f"{c['degraded']} degraded, {c['shed']} shed, "
      f"goodput={res['goodput']:.3f}, "
      f"{pol.session.breaker.transitions} breaker transitions)")
EOF

echo "== online-serving smoke: arrivals + batch former (DESIGN.md §12) =="
python - <<'EOF'
import numpy as np
from repro.core import policy_api as PA
from repro.core import trace
from repro.core.costs import CostModel
from repro.serve.arrivals import ArrivalSpec
from repro.serve.queue import (AdmissionConfig, BatchFormerConfig,
                               fixed_window_engine, serve_trace_online)

catalog, reqs, _ = trace.sift_like(n=256, d=16, t=96, seed=0)
spec = PA.PolicySpec("acai", PA.TINY_POLICY_KWARGS["acai"])
cm = CostModel(c_f=1.0)

# two arrival kinds through the dynamic batch former + admission control
for kind in ("poisson", "closed_loop"):
    arr = (ArrivalSpec(kind="poisson", rate_rps=2500.0, seed=3)
           if kind == "poisson"
           else ArrivalSpec(kind="closed_loop", users=6, think_ms=2.0,
                            seed=3))
    pol = PA.build_policy(spec, catalog, cm, seed=0)
    res = serve_trace_online(
        pol, reqs, arr,
        former=BatchFormerConfig(max_batch=8, max_wait_ms=4.0),
        admission=AdmissionConfig(queue_cap=32), slo_ms=25.0)
    assert res["served"] + res["shed_total"] == 96, kind
    assert (res["done_ms"] >= res["arrival_ms"]).all(), kind
    print(f"  {kind:12s} p50={res['p50_ms']:.1f}ms p99={res['p99_ms']:.1f}ms "
          f"goodput={res['goodput_slo']:.3f} batch={res['mean_batch']:.2f}")

# the equivalence pin: fixed-window engine == make_replay_batched,
# bitwise, on gain AND final policy state
pol_on = PA.build_policy(spec, catalog, cm, seed=0)
pol_off = PA.build_policy(spec, catalog, cm, seed=0)
res = fixed_window_engine(pol_on, 8).run(
    reqs, ArrivalSpec(kind="poisson", rate_rps=2500.0, seed=3))
ref = pol_off.replay(reqs)
assert np.array_equal(res["gain"], np.asarray(ref["gain"])), "gain drift"
assert np.array_equal(np.asarray(pol_on.cache.state.y),
                      np.asarray(pol_off.cache.state.y)), "y drift"
assert np.array_equal(np.asarray(pol_on.cache.state.x),
                      np.asarray(pol_off.cache.state.x)), "x drift"
print("online-serving smoke OK (fixed-window pin holds)")
EOF

echo "== answer-cache smoke: hits + invalidation + parity (DESIGN.md §13) =="
python - <<'EOF'
import numpy as np
from repro.core import policy_api as PA
from repro.core import trace
from repro.core.costs import CostModel
from repro.index import IndexSpec
from repro.serve import AnswerCacheSpec

catalog, reqs, _ = trace.sift_like(n=256, d=16, t=96, zipf_a=1.1,
                                   jitter=0.0, seed=3)
spec = PA.PolicySpec("acai", dict(PA.TINY_POLICY_KWARGS["acai"], batch=8))
cm = CostModel(c_f=1.0)

# cache on vs capacity=0 pass-through: bitwise parity, scans skipped
arms = {}
for cap in (64, 0):
    pol = PA.build_policy(spec, catalog, cm, seed=0,
                          index_spec=IndexSpec("flat"),
                          answer_cache=AnswerCacheSpec(capacity=cap))
    arms[cap] = (pol, pol.replay(reqs))
(pol_on, r_on), (pol_off, r_off) = arms[64], arms[0]
assert np.array_equal(r_on["gain"], r_off["gain"]), "gain drift"
assert np.array_equal(np.asarray(pol_on.cache.state.y),
                      np.asarray(pol_off.cache.state.y)), "y drift"
st = pol_on.answer_cache.stats()
assert st["hits"] > 0, st

# the hit path: replaying a batch the store has fully memoized must
# skip the scan (a skip needs ALL rows to hit — the batch contract)
served = [np.asarray(pol.serve_update_batch(reqs[:8])[1])
          for pol, _ in arms.values()]
assert np.array_equal(*served), "hit-path answer drift"
st = pol_on.answer_cache.stats()
assert st["scans_skipped"] >= 1, st

# churn invalidation: removing a memoized id drops exactly its entries,
# and the doomed id is never served afterwards — parity still bitwise
doomed = next(iter(pol_on.answer_cache.cache._inv))
for pol, _ in arms.values():
    pol.remove_objects([doomed])
served = [np.asarray(pol.serve_update_batch(reqs[:8])[1])
          for pol, _ in arms.values()]
assert np.array_equal(*served), "post-churn answer drift"
assert doomed not in served[0], "served a removed id"
st = pol_on.answer_cache.stats()
assert st["inv_remove"] > 0, st
print(f"answer-cache smoke OK (hit_rate={st['hit_rate']:.3f}, "
      f"{st['scans_skipped']} scans skipped, "
      f"{st['inv_remove']} precise invalidations, parity bitwise)")
EOF

echo "== BENCH_*.json schema vs README =="
python scripts/check_bench_schema.py

echo "== examples (tiny mode) =="
for ex in examples/*.py; do
    echo "-- $ex --tiny"
    python "$ex" --tiny > /dev/null
done
echo "examples OK"

echo "== 2-device sharded AÇAI smoke =="
XLA_FLAGS="--xla_force_host_platform_device_count=2 ${XLA_FLAGS:-}" \
python - <<'EOF'
import numpy as np
import jax, jax.numpy as jnp
from repro.core import oma, policy, trace
from repro.core.distributed import (make_replay_sharded,
                                    make_retrieval_step, reference_step)
from repro.index import IndexSpec, build_index
from repro.launch.mesh import make_mesh

assert jax.device_count() == 2, jax.devices()
N, d, B, C, k, h = 256, 16, 4, 16, 4, 24
rng = np.random.default_rng(0)
catalog = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
y0 = jnp.full((N,), h / N, jnp.float32)
reqs = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
mesh = make_mesh((1, 2), ("data", "model"))

# retrieval step (full-matrix + fused chunked scan) vs reference
for chunk in (0, 32):
    step = make_retrieval_step(mesh, n_shard=N // 2, d=d, c=C, k=k, c_f=1.0,
                               h=h, eta=0.05, top_a=h + 16,
                               scan_chunk=chunk)
    y1, ans, _ = jax.jit(step)(catalog, y0, reqs)
    y_ref, ans_ref = reference_step(catalog, y0, reqs, c=C, k=k, c_f=1.0,
                                    h=h, eta=0.05, top_a=h + 16)
    assert float(jnp.abs(y1 - y_ref).max()) < 2e-4, chunk
    assert all(set(np.array(a).tolist()) == set(np.array(b).tolist())
               for a, b in zip(np.array(ans), np.array(ans_ref))), chunk

# sharded replay end-to-end (exact + registry-built sharded-IVF candidates)
cat_t, reqs_t, _ = trace.sift_like(n=N, d=d, t=64, seed=0)
cat_t, reqs_t = jnp.array(cat_t), jnp.array(reqs_t)
cfg = policy.AcaiConfig(h=h, k=k, c_f=1.0, c_remote=16, c_local=8,
                        oma=oma.OMAConfig(eta=0.05))
s0 = policy.init_state(N, cfg)
ivf = build_index(IndexSpec("ivf_sharded", {"nlist": 8, "nprobe": 4}),
                  cat_t, mesh=mesh)
for kw in ({}, {"ivf": ivf}):
    st, m = jax.jit(make_replay_sharded(cfg, mesh, cat_t, 8, **kw))(
        s0, reqs_t)
    assert m.gain_int.shape == (64,)
    assert abs(float(jnp.sum(st.y)) - h) < 1e-2
    assert float(jnp.sum(np.asarray(m.gain_int))) >= 0
print("2-device sharded smoke OK")
EOF

echo "== 2-device sharded churn smoke =="
XLA_FLAGS="--xla_force_host_platform_device_count=2 ${XLA_FLAGS:-}" \
python - <<'EOF'
import numpy as np
import jax, jax.numpy as jnp
from repro.core import churn, oma, policy, trace
from repro.launch.mesh import make_mesh

assert jax.device_count() == 2, jax.devices()
params = dict(trace.TINY_TRACE_KWARGS["rolling_catalog"])
catalog, reqs, _ = trace.build_trace("rolling_catalog", **params)
events = trace.rolling_catalog_events(**params)
n0 = churn.warm_size(params["n"], params["warm"])
cfg = policy.AcaiConfig(h=16, k=4, c_f=1.0, c_remote=16, c_local=8,
                        oma=oma.OMAConfig(eta=0.01, projection_topk=48))

# mutation + epoch compaction on a real 2-shard mesh (DESIGN.md §15)
pol2 = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0,
                        mesh=make_mesh((1, 2), ("data", "model")))
res2 = churn.replay_with_churn(pol2, catalog, reqs, events, batch=8,
                               compact_every=24)
assert res2["events_applied"] == len(events) > 0
assert res2["compactions"] > 0
assert pol2.live_count == n0
assert pol2.catalog.shape[0] % 2 == 0          # slab stays mesh-aligned

# 1-device-mesh parity: the sharded mutable path IS the mutable path
pol1 = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0,
                        mesh=make_mesh((1, 1), ("data", "model")))
res1 = churn.replay_with_churn(pol1, catalog, reqs, events, batch=8,
                               compact_every=24)
plain = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0)
resp = churn.replay_with_churn(plain, catalog, reqs, events, batch=8,
                               compact_every=24)
for k in ("gain", "served_local", "occupancy"):
    assert (res1[k] == resp[k]).all(), k
assert (np.asarray(pol1.state.y) == np.asarray(plain.state.y)).all()
nag = pol2.normalized_gain(float(res2["gain"].sum()), res2["requests"])
print(f"2-device sharded churn smoke OK (NAG={nag:.4f}, "
      f"compactions={res2['compactions']})")
EOF

if [ -n "${SMOKE_FULL_CHURN:-}" ]; then
    echo "== full-scale churn bench (1M x 128, opt-in) =="
    python -m benchmarks.run --suite churn --full
fi
echo "smoke OK"
