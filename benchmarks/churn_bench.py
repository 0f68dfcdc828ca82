"""Mutable-catalog churn sweep (DESIGN.md §10/§14) — BENCH_churn.json
(default scale) and BENCH_churn_full.json (--full, 1M x 128).

Two questions, one suite:

* What does catalog churn cost?  `rolling_catalog` traces at increasing
  `churn_rate` (insert+expire events per request), replayed through AÇAI
  with exact candidates, AÇAI over an IVF index (stale-quantizer binning
  between refreshes), and the strongest classical baseline (SIM-LRU, via
  the online oracle).  Per row: NAG, hit ratio, p50 serving-step latency,
  `recall10_vs_live_exact` (the policy's post-churn top-10 against a
  fresh exact scan over the rows live at the schedule's end — the
  stale-structure gap, 1.0 by construction for exact/oracle cells),
  and the *separated* mutation/refresh wall time — churn overhead must
  never hide inside the serving latency.
* When does refreshing pay?  A `refresh_every` sweep at fixed churn for
  the IVF-backed cache: frequent rebuilds restore index recall (NAG up)
  but cost rebuild wall time (refresh_s up) — the amortization curve.

The churn_rate = 0 AÇAI-exact row doubles as the static-consistency
anchor: with no events the cache never leaves its static jitted path and
the replay is bit-consistent with `make_replay_batched`
(tests/test_mutable_index.py pins it; the bench asserts the cheap half).
"""

from __future__ import annotations

import json
import pathlib
import time

import numpy as np

from benchmarks import common
from repro.core import churn, policy, trace
from repro.core import policy_api as PA
from repro.core.costs import CostModel, calibrate_fetch_cost
from repro.core.trace import TraceSpec
from repro.index import IndexSpec

_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_JSON = _ROOT / "BENCH_churn.json"
BENCH_FULL_JSON = _ROOT / "BENCH_churn_full.json"

CHURN_RATES = (0.0, 0.02, 0.1, 0.5)
FULL_CHURN_RATES = (0.0, 0.1)    # full scale: the static anchor + one churn point
REFRESH_SWEEP = (0, 1024, 256)   # requests between refreshes (0 = never)
REFRESH_CHURN = 0.1
COMPACT_EVERY = 512              # epoch-compaction cadence (requests)
WARM = 0.5
BATCH = 8
SHARDED_SHARDS = 2               # mesh width of the sharded churn cell

# Sharded churn cell (DESIGN.md §15): the same fixed-churn rolling trace
# replayed through an AcaiCache on a (1, SHARDS) mesh — mutation routed to
# owner shards, serving through the sharded exact masked scan, epoch
# compaction keeping the slab mesh-aligned.  It runs in this process on
# the first SHARDS devices (virtual ones on the CPU, set up by
# `benchmarks.run`; on a chip a mesh wider than the chips is refused).
# The config mirrors the in-process acai-exact cell (same h/k/eta
# defaults), so its NAG is directly comparable to the shards=1 rows.
def _run_sharded_cell(n, d, t, rate, h, k, *, shards, compact_every):
    import jax.numpy as jnp

    from repro.core import oma
    from repro.launch.mesh import make_mesh, shard_devices

    mesh = make_mesh((1, shards), ("data", "model"),
                     devices=shard_devices(shards))
    catalog, reqs, _ = trace.build_trace(
        "rolling_catalog", n=n, d=d, t=t, churn_rate=rate, warm=WARM,
        seed=17)
    events = trace.rolling_catalog_events(n=n, t=t, churn_rate=rate,
                                          warm=WARM)
    n0 = churn.warm_size(n, WARM)
    c_f = float(calibrate_fetch_cost(jnp.asarray(catalog[:n0]),
                                     kth=min(50, n0 - 1), sample=256))
    cfg = policy.AcaiConfig(h=h, k=k, c_f=c_f,
                            oma=oma.OMAConfig(eta=0.05 / c_f))
    pol = policy.AcaiCache(jnp.asarray(catalog[:n0]), cfg, seed=0,
                           mesh=mesh)
    t0 = time.time()
    res = churn.replay_with_churn(pol, catalog, reqs, events, batch=BATCH,
                                  compact_every=compact_every)
    wall = time.time() - t0
    tt = res["requests"]
    return {
        "label": "acai-exact", "index": "exact", "shards": shards,
        "refresh_every": 0, "compact_every": compact_every,
        "events": res["events_applied"],
        "nag": round(pol.normalized_gain(float(res["gain"].sum()), tt), 4),
        "hit_ratio": round(float(res["hit"].mean()), 4),
        "recall10_vs_live_exact": 1.0,
        "p50_step_us": round(res["p50_step_s"] * 1e6, 1),
        "mutation_ms": round(res["mutation_s"] * 1e3, 1),
        "mutation_host_ms": round(res["mutation_host_s"] * 1e3, 1),
        "mutation_device_ms": round(res["mutation_device_s"] * 1e3, 1),
        "refresh_ms": 0.0, "refresh_stall_ms": 0.0,
        "compact_ms": round(res["compact_s"] * 1e3, 1),
        "compactions": res["compactions"],
        "us_per_request": round(wall / tt * 1e6, 2),
        "requests": tt,
    }


def _policies(c_f: float, h: int, k: int, full: bool = False):
    """(label, PolicySpec, index_spec) cells of the sweep.

    At full scale (1M rows) SIM-LRU is dropped: its online ServerOracle
    runs an exact host-side scan per mini-batch, which is intractable at
    1M x 128, and the baseline comparison already lives in BENCH_churn.json
    at bench scale.  The IVF cell scales its list count with the catalog
    (sqrt-ish rule) and trims k-means iterations so a refresh is minutes,
    not hours, on CPU."""
    ivf = (IndexSpec("ivf", {"nlist": 256, "nprobe": 16, "train_iters": 4})
           if full else IndexSpec("ivf", {"nlist": 48, "nprobe": 10}))
    cells = [
        ("acai-exact", PA.PolicySpec("acai", {"h": h, "k": k}), None),
        ("acai-ivf", PA.PolicySpec("acai", {"h": h, "k": k}), ivf),
    ]
    if not full:
        cells.append(
            ("sim_lru", PA.PolicySpec("sim_lru",
                                      {"h": h, "k": k, "k_prime": 2 * k,
                                       "c_theta": 1.5 * c_f}), None))
    return tuple(cells)


RECALL_SAMPLE = 64
RECALL_R = 10


def _recall10_vs_live_exact(pol, queries) -> float:
    """Post-churn retrieval quality: the policy's top-10 against a fresh
    exact top-10 over the rows live *right now* (the end state of the
    event schedule).  Policies that retrieve exactly by construction —
    exact AÇAI candidates, oracle-served baselines — score 1.0 without a
    scan; index-backed cells measure the real stale-structure gap."""
    idx = getattr(getattr(pol, "cache", None), "index", None)
    if idx is None:
        return 1.0
    got = np.asarray(idx.query(np.asarray(queries, np.float32),
                               RECALL_R)[1])
    q = np.asarray(queries, np.float64)
    emb = np.asarray(idx.embeddings)
    live = np.asarray(idx.valid, bool)
    qn = (q ** 2).sum(1)[:, None]
    # exact top-R over the live slab, merged chunk by chunk so the
    # (sample, capacity) distance matrix never materialises at full
    # scale (1M rows x 64 queries in float64 would be half a gigabyte)
    best_d = np.full((q.shape[0], RECALL_R), np.inf)
    best_i = np.full((q.shape[0], RECALL_R), -1, np.int64)
    chunk = 262144
    for s in range(0, emb.shape[0], chunk):
        e = emb[s:s + chunk].astype(np.float64)
        d2 = qn - 2.0 * q @ e.T + (e ** 2).sum(1)[None, :]
        d2[:, ~live[s:s + chunk]] = np.inf
        r = min(RECALL_R, d2.shape[1])
        part = np.argpartition(d2, r - 1, axis=1)[:, :r]
        cand_d = np.concatenate(
            [best_d, np.take_along_axis(d2, part, 1)], axis=1)
        cand_i = np.concatenate([best_i, part + s], axis=1)
        sel = np.argsort(cand_d, axis=1, kind="stable")[:, :RECALL_R]
        best_d = np.take_along_axis(cand_d, sel, 1)
        best_i = np.take_along_axis(cand_i, sel, 1)
    overlap = [np.intersect1d(g, e[e >= 0]).size
               for g, e in zip(got, best_i)]
    return float(np.mean(overlap)) / RECALL_R


def _run_cell(label, spec, index_spec, catalog, reqs, events, cm, *,
              refresh_every=0, compact_every=0, seed=0, warm_jits=True):
    # every cell starts on the warm prefix (the live window at t = 0), so
    # rows are comparable across churn rates — at rate 0 the window just
    # never moves
    n0 = churn.warm_size(catalog.shape[0], WARM)
    if warm_jits and len(events):
        # mutation jits are bucketed by (capacity level, pow2 write
        # width) and cached process-wide, so an identical throwaway
        # replay compiles every entry the timed one will hit and the
        # timed mutation_ms columns measure steady-state cost, not
        # first-call compilation (the no-retrace test pins that the
        # second pass adds zero compiles).  Skipped at --full, where a
        # doubled 1M replay would dwarf the one-time compile cost it
        # amortizes away.
        throwaway = PA.build_policy(spec, catalog[:n0], cm,
                                    index_spec=index_spec, seed=seed)
        churn.replay_with_churn(throwaway, catalog, reqs, events,
                                batch=BATCH, refresh_every=refresh_every,
                                compact_every=compact_every)
    pol = PA.build_policy(spec, catalog[:n0], cm, index_spec=index_spec,
                          seed=seed)
    t0 = time.time()
    res = churn.replay_with_churn(pol, catalog, reqs, events, batch=BATCH,
                                  refresh_every=refresh_every,
                                  compact_every=compact_every)
    wall = time.time() - t0
    tt = res["requests"]
    return {
        "policy": spec.to_dict(), "label": label,
        "index": index_spec.to_dict() if index_spec else "exact",
        "shards": 1,
        "refresh_every": refresh_every,
        "compact_every": compact_every,
        "events": res["events_applied"],
        "nag": round(float(res["gain"].sum()) / (pol.k * pol.c_f * tt), 4),
        "hit_ratio": round(float(res["hit"].mean()), 4),
        "recall10_vs_live_exact": round(
            _recall10_vs_live_exact(pol, reqs[tt - RECALL_SAMPLE:tt]), 4),
        "p50_step_us": round(res["p50_step_s"] * 1e6, 1),
        "mutation_ms": round(res["mutation_s"] * 1e3, 1),
        "mutation_host_ms": round(res["mutation_host_s"] * 1e3, 1),
        "mutation_device_ms": round(res["mutation_device_s"] * 1e3, 1),
        "refresh_ms": round(res["refresh_s"] * 1e3, 1),
        "refresh_stall_ms": round(res["refresh_stall_s"] * 1e3, 2),
        "compact_ms": round(res["compact_s"] * 1e3, 1),
        "compactions": res["compactions"],
        "us_per_request": round(wall / tt * 1e6, 2),
        "requests": tt,
    }


def main(full: bool = False, kind: str = None) -> None:
    # `kind` exists for run.py's suite signature only: the churn sweep is
    # pinned to rolling_catalog geometry (membership churn IS the
    # variable under study), so a trace filter is rejected rather than
    # silently ignored
    if kind not in (None, "sift", "rolling_catalog"):
        raise ValueError(
            "the churn suite runs rolling_catalog only (its churn_rate is "
            "the swept knob); --trace does not apply here")
    # --full is the 1M x 128 device-mutation scale check (DESIGN.md §14):
    # does the donated-update path hold its per-event cost when the slab
    # is three orders of magnitude bigger, and does refresh amortization
    # flip once the rebuild stall leaves the serving path?  It writes a
    # separate artifact (BENCH_churn_full.json) so the default-scale
    # sweep's history stays comparable across PRs.
    n, t, d = (1_000_000, 2048, 128) if full else (2000, 2048, 16)
    h, k = (400, 10) if full else (64, 8)
    rates = FULL_CHURN_RATES if full else CHURN_RATES
    json_path = BENCH_FULL_JSON if full else BENCH_JSON
    n0 = churn.warm_size(n, WARM)
    rows = []

    import jax
    import jax.numpy as jnp

    for rate in rates:
        tspec = TraceSpec("rolling_catalog",
                          {"n": n, "d": d, "t": t, "churn_rate": rate,
                           "warm": WARM, "seed": 17})
        catalog, reqs, _ = trace.build_trace(tspec)
        events = trace.rolling_catalog_events(**tspec.params)
        c_f = float(calibrate_fetch_cost(jnp.asarray(catalog[:n0]),
                                         kth=min(50, n0 - 1), sample=256))
        cm = CostModel(c_f=c_f)
        for label, spec, ispec in _policies(c_f, h, k, full=full):
            row = _run_cell(label, spec, ispec, catalog, reqs, events, cm,
                            warm_jits=not full)
            row.update(churn_rate=rate, trace=tspec.to_dict())
            rows.append(row)
            common.emit(
                f"churn/rate{rate:g}/{label}", row["p50_step_us"],
                f"NAG={row['nag']:.4f};hit={row['hit_ratio']:.3f};"
                f"mut_ms={row['mutation_ms']:.0f};"
                f"r10={row['recall10_vs_live_exact']:.3f}")
        if rate == 0.0 and not full:
            # cheap half of the static-consistency anchor (the full
            # bitwise pin lives in tests/test_mutable_index.py): with no
            # events the exact AÇAI replay must match the batched static
            # replay's NAG to float tolerance.  Skipped at --full: it
            # would double the 1M exact replay for a property the test
            # suite already pins at bench scale.
            from repro.core import oma

            cfg = policy.AcaiConfig(h=h, k=k, c_f=c_f,
                                    oma=oma.OMAConfig(eta=0.05 / c_f))
            replay = policy.make_replay_batched(
                cfg, policy.exact_candidate_fn_batched(
                    jnp.asarray(catalog[:n0]), cfg.c_remote, cfg.c_local),
                BATCH)
            _, m = replay(policy.init_state(n0, cfg, seed=0),
                          jnp.asarray(reqs[:(t // BATCH) * BATCH]))
            nag_static = float(np.sum(np.asarray(m.gain_int))) / (
                k * c_f * ((t // BATCH) * BATCH))
            anchor = next(r for r in rows
                          if r["label"] == "acai-exact"
                          and r["churn_rate"] == 0.0)
            assert abs(round(nag_static, 4) - anchor["nag"]) < 1e-9, (
                nag_static, anchor["nag"])
            common.emit("churn/static-anchor", 0.0,
                        f"NAG={nag_static:.4f} (== rate0 acai-exact row)")

    # refresh-amortization curve: fixed churn, IVF-backed AÇAI
    tspec = TraceSpec("rolling_catalog",
                      {"n": n, "d": d, "t": t, "churn_rate": REFRESH_CHURN,
                       "warm": WARM, "seed": 17})
    catalog, reqs, _ = trace.build_trace(tspec)
    events = trace.rolling_catalog_events(**tspec.params)
    c_f = float(calibrate_fetch_cost(jnp.asarray(catalog[:n0]),
                                     kth=min(50, n0 - 1), sample=256))
    cm = CostModel(c_f=c_f)
    pcells = _policies(c_f, h, k, full=full)
    _, spec, ispec = pcells[1]                        # acai-ivf
    for every in REFRESH_SWEEP:
        row = _run_cell("acai-ivf", spec, ispec, catalog, reqs, events, cm,
                        refresh_every=every, warm_jits=not full)
        row.update(churn_rate=REFRESH_CHURN, trace=tspec.to_dict())
        rows.append(row)
        common.emit(
            f"churn/refresh{every}/acai-ivf", row["p50_step_us"],
            f"NAG={row['nag']:.4f};refresh_ms={row['refresh_ms']:.0f};"
            f"stall_ms={row['refresh_stall_ms']:.1f}")

    # epoch-compaction cells (same fixed-churn trace): tombstoned slab
    # rows are reclaimed on a cadence.  For exact candidates compaction
    # is behavior-neutral (tests pin gain/NAG parity with the
    # compaction-free row above); the columns of interest are compact_ms
    # and the smaller slab's serving latency.
    for label, spec, ispec in pcells:
        if label == "sim_lru":
            continue
        row = _run_cell(label, spec, ispec, catalog, reqs, events, cm,
                        compact_every=COMPACT_EVERY, warm_jits=not full)
        row.update(churn_rate=REFRESH_CHURN, trace=tspec.to_dict())
        rows.append(row)
        common.emit(
            f"churn/compact{COMPACT_EVERY}/{label}", row["p50_step_us"],
            f"NAG={row['nag']:.4f};compact_ms={row['compact_ms']:.0f};"
            f"compactions={row['compactions']}")

    # sharded churn cell: the same fixed-churn + compaction workload on a
    # (1, 2) mesh (see _run_sharded_cell).  Skipped at --full:
    # host-emulated shards at 1M x 128 measure threadpool contention, not
    # the mesh mutation path (which the 2k-scale cell already exercises).
    if not full:
        row = _run_sharded_cell(n, d, t, REFRESH_CHURN, h, k,
                                shards=SHARDED_SHARDS,
                                compact_every=COMPACT_EVERY)
        row.update(churn_rate=REFRESH_CHURN, trace=tspec.to_dict(),
                   policy={"name": "acai", "params": {"h": h, "k": k}})
        rows.append(row)
        common.emit(
            f"churn/sharded{SHARDED_SHARDS}/acai-exact",
            row["p50_step_us"],
            f"NAG={row['nag']:.4f};compactions={row['compactions']};"
            f"mut_ms={row['mutation_ms']:.0f}")

    json_path.write_text(json.dumps(
        {"full": full, "n": n, "d": d, "t": t, "warm": WARM, "h": h, "k": k,
         "batch": BATCH, "backend": jax.default_backend(), "rows": rows},
        indent=2) + "\n")
    common.emit("churn/json", 0.0, str(json_path.name))


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="1M x 128 scale check -> BENCH_churn_full.json "
                         "(tens of minutes on CPU)")
    main(ap.parse_args().full)
