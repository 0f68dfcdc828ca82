#!/usr/bin/env python3
"""AÇAI edge cache on a TPU at the SIFT1M shape: the quickest proof that the
serving path still starts and answers correctly on the chip.

The deployment is the paper's: a 1,000,000 x 128 float32 catalog under
squared L2 (the TEXMEX SIFT1M shape, generated from a seed by
`repro.core.trace.sift_like`, so nothing is downloaded) and 8,192 requests
from its Zipf-over-barycentric-distance trace, served in batches of 64
through `AcaiCache` with k=10, h=1000, 64 remote and 16 local candidates
and c_f from `calibrate_fetch_cost`.

One chip (the default), three arms on the same catalog and trace:
  exact  index=None — the XLA scan over the whole catalog;
  flat   IndexSpec("flat") — the Pallas `l2_topk` kernel;
  ivf    IndexSpec("ivf", nlist=1024, nprobe=16) — the Pallas `ivf_scan`
         kernel over probed lists fetched from HBM.
Checks: the flat and IVF steps call a Pallas kernel (`tpu_custom_call` in
the compiled step); the flat top-10 equals a float64 NumPy scan on the
host and its NAG equals the exact arm's; every catalog row sits in the
IVF list of its nearest centroid, up to a tie (the arm also prints the
index's `list_stats()`); the IVF kernel equals its XLA oracle on the same
inputs.  The IVF build trains k-means in 64Ki-row
chunks (`repro.index.kmeans.CHUNK`), 256 MiB of distances at a time.

`--chips 4` runs only the sharded path, on a (1, 4) mesh of the first four
devices: the exact sharded scan with scan_chunk > 0 (the Pallas `l2_topk`
per shard) and IndexSpec("ivf_sharded"), then the same trace through the
one-chip cache on devices[0] for comparison.

Compile seconds and steady milliseconds per batch are printed per arm as
smoke readings, not benchmark numbers; the flat arm also prints how many
selection passes `l2_topk` ran per catalog tile for one batch (at most
c_remote; about one where the catalog is in no order relative to the
requests).  Any failed check or phase exits non-zero; off a TPU the
script exits non-zero before any work.  The last
line of stdout is {"ok": true, "device": {...}} with the platform, device
kind and device count as JAX reports them.

    python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

N, D = 1_000_000, 128
T, BATCH = 8192, 64
K, H, C_REMOTE, C_LOCAL = 10, 1000, 64, 16
NLIST, NPROBE = 1024, 16
SHARDS = 4
SCAN_CHUNK = 8192
SAMPLE = 256          # requests checked against the host float64 scan
TIE_RTOL = 1e-5       # distances closer than this are ties
TIE_ULPS = 32         # list check: a centroid this close to the nearest ties
EPS32 = float(np.finfo(np.float32).eps)
NAG_ATOL = 1e-5       # flat vs exact arm


def log(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")
    log(f"  ok: {what}")


def host_topk(catalog: np.ndarray, q: np.ndarray, k: int, rows=65536):
    """Exact top-k by float64 distances on the host: (ids, dists)."""
    q64 = q.astype(np.float64)
    qn = (q64 * q64).sum(1)[:, None]
    best_d = np.full((q.shape[0], 0), np.inf)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for s in range(0, catalog.shape[0], rows):
        x = catalog[s:s + rows].astype(np.float64)
        d = np.maximum(qn - 2.0 * q64 @ x.T + (x * x).sum(1)[None, :], 0.0)
        cat_d = np.concatenate([best_d, d], 1)
        cat_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(s, s + x.shape[0]), d.shape)], 1)
        part = np.argpartition(cat_d, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(cat_d, part, 1)
        best_i = np.take_along_axis(cat_i, part, 1)
    order = np.argsort(best_d, axis=1, kind="stable")
    return (np.take_along_axis(best_i, order, 1),
            np.take_along_axis(best_d, order, 1))


def dist64(catalog: np.ndarray, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 squared distances of (B, k) catalog ids to their queries."""
    diff = catalog[ids].astype(np.float64) - q.astype(np.float64)[:, None, :]
    return (diff * diff).sum(-1)


def same_up_to_ties(catalog, q, ids, ref_ids) -> tuple[bool, int]:
    """ids equal to ref_ids per query, except where a swap is a tie: the
    float64 distances of both sorted id lists agree within TIE_RTOL."""
    differ = int((np.sort(ids, 1) != np.sort(ref_ids, 1)).any(1).sum())
    d_got = np.sort(dist64(catalog, q, ids), 1)
    d_ref = np.sort(dist64(catalog, q, ref_ids), 1)
    return bool(np.allclose(d_got, d_ref, rtol=TIE_RTOL, atol=0.0)), differ


def lists_not_nearest(catalog_dev, idx, rows: int = 65536) -> int:
    """Catalog rows in no inverted list, or in a list whose centroid is
    farther than their nearest one beyond a tie (TIE_ULPS float32 ulps of
    the squared norms): 0 when the lists are the centroids' Voronoi
    cells."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    table = np.asarray(idx.invlists)
    own = np.full(N, -1, np.int32)
    own[table[table >= 0]] = np.nonzero(table >= 0)[0]
    cents = idx.centroids
    cmax = jnp.max(jnp.sum(cents * cents, axis=1))

    @jax.jit
    def bad(x, o):
        d = ops.pairwise_l2_xla(x, cents)
        mine = jnp.take_along_axis(d, jnp.maximum(o, 0)[:, None], 1)[:, 0]
        tol = TIE_ULPS * EPS32 * (jnp.sum(x * x, axis=1) + cmax)
        return jnp.sum((o < 0) | (mine - jnp.min(d, axis=1) > tol))

    return sum(int(bad(catalog_dev[s:s + rows], own[s:s + rows]))
               for s in range(0, N, rows))


def make_data(seed: int = 0):
    from repro.core import trace

    t0 = time.perf_counter()
    catalog, reqs, _ = trace.sift_like(n=N, d=D, t=T, seed=seed)
    log(f"data: catalog {catalog.shape} float32, {reqs.shape[0]} requests "
        f"({time.perf_counter() - t0:.1f} s on the host)")
    return catalog, reqs


def run_arm(name: str, catalog_dev, reqs, cfg, *, kernel: bool, **kw):
    """Serve the whole trace through one AcaiCache; returns (cache, NAG)."""
    import jax

    from repro.core import policy

    t0 = time.perf_counter()
    cache = policy.AcaiCache(catalog_dev, cfg, seed=0, **kw)
    jax.block_until_ready(cache.state)
    build_s = time.perf_counter() - t0
    batches = reqs.reshape(-1, BATCH, reqs.shape[1])
    first = jax.device_put(batches[0])
    t0 = time.perf_counter()
    compiled = cache.lower_batch(first).compile()
    compile_s = time.perf_counter() - t0
    if kernel:
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: the compiled step calls a Pallas kernel")
    total, times = 0.0, []
    for i, rb in enumerate(batches):
        rs = jax.device_put(rb)
        t0 = time.perf_counter()
        m = cache.serve_update_batch(rs)
        jax.block_until_ready(m)
        times.append(time.perf_counter() - t0)
        total += float(np.sum(np.asarray(m.gain_int)))
    nag = cache.normalized_gain(total, reqs.shape[0])
    steady = np.asarray(times[1:]) * 1e3
    check(np.isfinite(nag) and nag > 0, f"{name}: NAG {nag:.6f} finite, > 0")
    log(f"  smoke reading {name}: build {build_s:.2f} s, compile "
        f"{compile_s:.2f} s, first batch {times[0] * 1e3:.1f} ms, steady "
        f"{np.median(steady):.2f} ms/batch median "
        f"({steady.mean():.2f} mean, {len(steady)} batches)")
    return cache, nag


def one_chip(catalog: np.ndarray, reqs: np.ndarray) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import oma, policy
    from repro.core.costs import calibrate_fetch_cost
    from repro.index import IndexSpec
    from repro.kernels import l2_topk, ops

    catalog_dev = jax.device_put(catalog)
    c_f = float(calibrate_fetch_cost(catalog_dev))
    log(f"c_f = {c_f:.6f} (mean distance of the 50th neighbour)")
    base = policy.AcaiConfig(h=H, k=K, c_f=c_f, c_remote=C_REMOTE,
                             c_local=C_LOCAL,
                             oma=oma.OMAConfig(eta=0.05 / c_f))

    log("arm exact: index=None (XLA scan)")
    _, nag_exact = run_arm("exact", catalog_dev, reqs, base, kernel=False)

    log("arm flat: IndexSpec('flat') (Pallas l2_topk)")
    flat, nag_flat = run_arm(
        "flat", catalog_dev, reqs,
        dataclasses.replace(base, index=IndexSpec("flat")), kernel=True)
    check(abs(nag_flat - nag_exact) <= NAG_ATOL,
          f"flat NAG {nag_flat:.8f} == exact NAG {nag_exact:.8f} "
          f"within {NAG_ATOL}")
    rng = np.random.default_rng(1)
    q = reqs[rng.choice(reqs.shape[0], SAMPLE, replace=False)]
    d_flat, i_flat = flat.index.query(jnp.asarray(q), K)
    i_flat = np.asarray(i_flat)
    t0 = time.perf_counter()
    i_host, d_host = host_topk(catalog, q, K)
    log(f"  host float64 top-{K} of {SAMPLE} requests: "
        f"{time.perf_counter() - t0:.1f} s")
    ok, differ = same_up_to_ties(catalog, q, i_flat, i_host)
    check(ok, f"flat top-{K} == host float64 top-{K} up to ties "
              f"({differ} of {SAMPLE} requests differ only by ties)")
    err = np.abs(np.asarray(d_flat, np.float64) - d_host).max()
    log(f"  flat f32 distances vs float64: max abs error {err:.3e}")
    _, _, passes = ops.topk_l2(jnp.asarray(q[:BATCH]), catalog_dev, C_REMOTE,
                               return_passes=True)
    tiles = -(-N // l2_topk.BN)
    log(f"  l2_topk selection passes per {l2_topk.BN}-row tile, {BATCH} "
        f"requests, k = {C_REMOTE}: {int(passes[0]) / tiles:.3f} "
        f"(at most {C_REMOTE})")

    spec = IndexSpec("ivf", {"nlist": NLIST, "nprobe": NPROBE})
    log(f"arm ivf: {spec} (Pallas ivf_scan, catalog in HBM)")
    ivf, nag_ivf = run_arm("ivf", catalog_dev, reqs,
                           dataclasses.replace(base, index=spec),
                           kernel=True)
    idx = ivf.index
    log(f"  ivf: {idx.invlists.shape[0]} lists x {idx.invlists.shape[1]} "
        f"slots (P = {NPROBE * idx.invlists.shape[1]} candidates/query)")
    log(f"  ivf list_stats: {json.dumps(idx.list_stats())}")
    bad = lists_not_nearest(catalog_dev, idx)
    check(bad == 0, f"ivf lists are the centroids' Voronoi cells ({bad} of "
                    f"{N} rows in no list or not in their nearest one's)")
    qd = jnp.asarray(q[:BATCH])
    _, probe = jax.lax.top_k(-ops.pairwise_l2_xla(qd, idx.centroids), NPROBE)
    cand = idx.invlists[probe].reshape(BATCH, -1)
    d_k, i_k = ops.ivf_scan_topk(qd, catalog_dev, cand, C_REMOTE)
    d_x, i_x = ops.ivf_scan_xla(qd, catalog_dev, cand, C_REMOTE)
    d_k, i_k, d_x, i_x = map(np.asarray, (d_k, i_k, d_x, i_x))
    check(np.array_equal(i_k >= 0, i_x >= 0),
          "ivf kernel underflow slots == XLA oracle's")
    check(np.allclose(d_k, d_x, rtol=TIE_RTOL, atol=0.0),
          "ivf kernel distances == XLA oracle's")
    ok, differ = same_up_to_ties(catalog, q[:BATCH], np.maximum(i_k, 0),
                                 np.maximum(i_x, 0))
    check(ok, f"ivf kernel ids == XLA oracle's up to ties ({differ} of "
              f"{BATCH} requests differ only by ties)")
    _, i_ivf = idx.query(jnp.asarray(q), K)
    recall = np.mean([len(set(a) & set(b)) / K
                      for a, b in zip(np.asarray(i_ivf), i_host)])
    log(f"  ivf recall@{K} vs flat: {recall:.4f}; NAG exact "
        f"{nag_exact:.6f}, flat {nag_flat:.6f}, ivf {nag_ivf:.6f}")


def four_chips(catalog: np.ndarray, reqs: np.ndarray) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import oma, policy
    from repro.core.costs import calibrate_fetch_cost
    from repro.index import IndexSpec
    from repro.launch.mesh import make_mesh, shard_devices

    devices = shard_devices(SHARDS)
    mesh = make_mesh((1, SHARDS), ("data", "model"), devices=devices)
    before = [d.memory_stats()["bytes_in_use"] for d in devices]
    sharded = jax.device_put(catalog, NamedSharding(mesh, P("model", None)))
    jax.block_until_ready(sharded)
    held = [d.memory_stats()["bytes_in_use"] - b
            for d, b in zip(devices, before)]
    share = catalog.nbytes / SHARDS
    log(f"catalog over {len(sharded.sharding.device_set)} devices; bytes "
        f"added per device: {held} (N*d*4/{SHARDS} = {share:.0f})")
    check(len(sharded.sharding.device_set) == SHARDS,
          f"catalog sharded over {SHARDS} devices")
    check(all(0.9 * share <= h <= 1.1 * share for h in held),
          f"each device holds N*d*4/{SHARDS} bytes of catalog (+-10%)")

    one = jax.device_put(catalog, devices[0])
    c_f = float(calibrate_fetch_cost(one))
    log(f"c_f = {c_f:.6f}")
    cfg = policy.AcaiConfig(h=H, k=K, c_f=c_f, c_remote=C_REMOTE,
                            c_local=C_LOCAL,
                            oma=oma.OMAConfig(eta=0.05 / c_f))
    log(f"arm sharded-exact: (1, {SHARDS}) mesh, scan_chunk={SCAN_CHUNK} "
        f"(Pallas l2_topk per shard)")
    _, nag_sh = run_arm("sharded-exact", sharded, reqs, cfg, kernel=True,
                        mesh=mesh, sharded_kwargs={"scan_chunk": SCAN_CHUNK})
    # per shard NLIST / SHARDS lists, NPROBE / SHARDS probed: the same
    # candidate count per query as the one-chip IVF arm
    spec = IndexSpec("ivf_sharded", {"nlist": NLIST // SHARDS,
                                     "nprobe": NPROBE // SHARDS})
    log(f"arm sharded-ivf: {spec}")
    _, nag_ivf = run_arm("sharded-ivf", sharded, reqs,
                         dataclasses.replace(cfg, index=spec), kernel=True,
                         mesh=mesh)
    log("arm one-chip exact on devices[0], for comparison")
    _, nag_one = run_arm("one-chip-exact", one, reqs, cfg, kernel=False)
    check(nag_sh > 0.95 * nag_one,
          f"sharded NAG {nag_sh:.6f} within 5% of one-chip NAG "
          f"{nag_one:.6f} (the sharded-replay test's bound)")
    log(f"  NAG sharded-exact {nag_sh:.6f}, sharded-ivf {nag_ivf:.6f}, "
        f"one-chip exact {nag_one:.6f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDS), default=1,
                    help=f"{SHARDS}: run only the sharded path and its "
                         "one-chip comparison")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {json.dumps(device)}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform} devices")
    if device["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} chips")

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    catalog, reqs = make_data()
    if args.chips == 1:
        one_chip(catalog, reqs)
    else:
        four_chips(catalog, reqs)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
