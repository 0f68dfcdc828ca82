"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: the
production mesh is built from 512 host-platform placeholder devices, every
cell's step function is pjit-lowered with full shardings, compiled, and the
compiled artifact is mined for the roofline terms (FLOPs, bytes, per-class
collective bytes, per-device memory).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
Cells already present in --out are skipped (resumable).
"""

# The VERY FIRST lines — before ANY other import (jax locks the device
# count on first init).  Do NOT set this globally: tests/benches must see
# one device.
import os  # noqa: E402

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse   # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402
from functools import partial  # noqa: E402

import jax        # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS, SHAPES, SMOKE_ARCHS, SMOKE_SHAPES, runnable  # noqa: E402
from repro.launch.mesh import (batch_axes, make_host_mesh,  # noqa: E402
                               make_production_mesh, mesh_shape_dict)
from repro.models import init_cache, init_params  # noqa: E402
from repro.serve.engine import make_decode_step, make_prefill  # noqa: E402
from repro.sharding import specs as S  # noqa: E402
from repro.sharding.ctx import mesh_context  # noqa: E402
from repro.train import OptConfig, make_train_step  # noqa: E402
from repro.train.batching import input_specs  # noqa: E402
from repro.train.optimizer import init_opt  # noqa: E402

def sharded_bytes(shape_tree, pspec_tree, mesh_shape: dict) -> int:
    """Per-device bytes of a sharded pytree (analytic)."""
    total = 0
    for leaf, spec in zip(jax.tree.leaves(shape_tree),
                          jax.tree.leaves(pspec_tree,
                                          is_leaf=lambda x: isinstance(
                                              x, jax.sharding.PartitionSpec))):
        shards = 1
        for ax in spec:
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                shards *= mesh_shape.get(a, 1)
        total += leaf.size * leaf.dtype.itemsize // max(shards, 1)
    return total


def _accum_for(cfg, shape) -> int:
    if shape.kind != "train":
        return 1
    if shape.global_batch >= 64:
        return 8
    return 1


def build_lowering(cfg, shape, mesh, multi_pod: bool):
    mesh_shape = mesh_shape_dict(mesh)
    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    pspecs = S.param_pspecs(cfg, params_shape, mesh_shape)
    param_sh = S.as_shardings(mesh, pspecs)
    bspecs = input_specs(cfg, shape)
    bp = S.batch_pspecs(cfg, bspecs, multi_pod, mesh_shape)
    batch_sh = S.as_shardings(mesh, bp)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    info = {"params_bytes_per_device": sharded_bytes(params_shape, pspecs,
                                                     mesh_shape)}

    with mesh_context(mesh, batch_axes(multi_pod)):
        if shape.kind == "train":
            opt_shape = jax.eval_shape(partial(init_opt, cfg.optimizer),
                                       params_shape)
            ospecs = S.opt_pspecs(cfg.optimizer, params_shape, pspecs, cfg,
                                  mesh_shape)
            opt_sh = S.as_shardings(mesh, ospecs)
            info["opt_bytes_per_device"] = sharded_bytes(opt_shape, ospecs,
                                                         mesh_shape)
            accum = _accum_for(cfg, shape)
            step = make_train_step(cfg, OptConfig(name=cfg.optimizer), accum,
                                   grad_shardings=param_sh)
            jitted = jax.jit(step,
                             in_shardings=(param_sh, opt_sh, batch_sh, repl),
                             out_shardings=(param_sh, opt_sh, None))
            lowered = jitted.lower(params_shape, opt_shape, bspecs, 0)
            info["accum"] = accum
        elif shape.kind == "prefill":
            cache_shape = jax.eval_shape(
                partial(init_cache, cfg, shape.global_batch, shape.seq_len))
            cspecs = S.cache_pspecs(cfg, cache_shape, mesh_shape, multi_pod)
            cache_sh = S.as_shardings(mesh, cspecs)
            info["cache_bytes_per_device"] = sharded_bytes(
                cache_shape, cspecs, mesh_shape)
            fn = make_prefill(cfg, shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(param_sh, batch_sh, cache_sh),
                             out_shardings=(None, cache_sh))
            lowered = jitted.lower(params_shape, bspecs, cache_shape)
        else:  # decode
            cache_shape = jax.eval_shape(
                partial(init_cache, cfg, shape.global_batch, shape.seq_len))
            cspecs = S.cache_pspecs(cfg, cache_shape, mesh_shape, multi_pod)
            cache_sh = S.as_shardings(mesh, cspecs)
            info["cache_bytes_per_device"] = sharded_bytes(
                cache_shape, cspecs, mesh_shape)
            fn = make_decode_step(cfg)
            args = [params_shape, cache_shape, bspecs["tokens"],
                    jax.ShapeDtypeStruct((), np.int32)]
            in_sh = [param_sh, cache_sh, batch_sh["tokens"], repl]
            kwargs = {}
            if "positions3" in bspecs:
                kwargs["positions3"] = bspecs["positions3"]
                fn2 = lambda p, c, t, l, positions3: fn(  # noqa: E731
                    p, c, t, l, positions3=positions3)
                jitted = jax.jit(
                    fn2, in_shardings=tuple(in_sh) + (batch_sh["positions3"],),
                    out_shardings=(None, None, cache_sh))
                lowered = jitted.lower(*args, bspecs["positions3"])
            else:
                jitted = jax.jit(fn, in_shardings=tuple(in_sh),
                                 out_shardings=(None, None, cache_sh))
                lowered = jitted.lower(*args)
    return lowered, info


def analyse(lowered, compiled, info: dict, n_devices: int) -> dict:
    out = dict(info)
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        out["cost_analysis"] = {k: float(v) for k, v in cost.items()
                                if np.isscalar(v)}
    except Exception as e:  # noqa: BLE001
        out["cost_analysis_error"] = str(e)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["memory_analysis"] = {
                a: float(getattr(mem, a))
                for a in dir(mem)
                if not a.startswith("_")
                and np.isscalar(getattr(mem, a, None))
            }
    except Exception as e:  # noqa: BLE001
        out["memory_analysis_error"] = str(e)
    try:
        text = compiled.as_text()
        from repro.launch import hlo_analysis
        s = hlo_analysis.summarize(text)
        out["hlo"] = {
            "flops_per_device": s.flops,
            "hbm_bytes_per_device": s.bytes,
            "collective_bytes_per_shard": s.coll_bytes,
            "collective_counts": s.coll_counts,
            "loops": s.loops,
        }
        out["collective_bytes_per_shard_total"] = float(
            sum(s.coll_bytes.values()))
        out["hlo_lines"] = text.count("\n")
    except Exception as e:  # noqa: BLE001
        out["collectives_error"] = str(e)
    out["n_devices"] = n_devices
    return out


def apply_variant(cfg, variant: str, multi_pod: bool):
    """'opt' = the confirmed §Perf beyond-baseline configuration:
      * shard_map expert-weights-stationary MoE (moe_dp) — kills the
        partitioner's replicate-and-all-reduce of the expert buffers,
      * MLA absorbed decode — latent-space attention for deepseek decode.
    Flash-threshold lowering and the chunked retrieval scan were measured
    and REFUTED at the XLA level (EXPERIMENTS.md §Perf iterations B.1/C.1)
    — the real memory wins need the Pallas kernels
    (cfg.use_pallas_attention / FlatIndex(kernel='pallas') on TPU), which
    CPU dry-runs cannot compile; they are accounted analytically."""
    if variant != "opt":
        return cfg
    import dataclasses
    dp = 32 if multi_pod else 16
    return dataclasses.replace(cfg,
                               moe_dp=dp if cfg.n_experts else 0,
                               mla_absorbed_decode=cfg.attn_type == "mla",
                               replicate_misaligned_heads=True)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             smoke: bool = False, variant: str = "baseline") -> dict:
    cfgs = SMOKE_ARCHS if smoke else ARCHS
    shapes = SMOKE_SHAPES if smoke else SHAPES
    cfg, shape = cfgs[arch], shapes[shape_name]
    cfg = apply_variant(cfg, variant, mesh_kind == "multi")
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "kind": shape.kind, "seq_len": shape.seq_len,
              "global_batch": shape.global_batch,
              "variant": variant,
              "params_total": cfg.param_count(),
              "params_active": cfg.active_param_count()}
    ok, reason = runnable(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        return record
    if smoke:
        mesh = make_host_mesh()
        multi_pod = False
    else:
        multi_pod = mesh_kind == "multi"
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        lowered, info = build_lowering(cfg, shape, mesh, multi_pod)
        record["lower_seconds"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_seconds"] = time.time() - t1
        record.update(analyse(lowered, compiled, info, mesh.devices.size))
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001
        record["status"] = "failed"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_seconds"] = time.time() - t0
    return record


def acai_cell_meta(mesh_kind: str, *, n_catalog: int, d: int, batch: int,
                  k: int, h: int, eta: float, variant: str) -> dict:
    """Static provenance fields of the AÇAI retrieval cell, kept in a
    helper so tests can pin the record schema without compiling on a
    512-device mesh (tests/test_policy_api.py)."""
    from repro.core.policy_api import PolicySpec

    return {"arch": "acai-retrieval", "shape": f"retrieval_b{batch}",
            "mesh": mesh_kind, "kind": "serve", "variant": variant,
            "seq_len": n_catalog, "global_batch": batch,
            "params_total": n_catalog * d, "params_active": n_catalog * d,
            "shard_map_impl": "jax.shard_map",
            # index selection provenance (DESIGN.md §8): the cell lowers
            # the exact per-shard scan — 'exact' is the spec-less
            # perfect-recall configuration, same convention as
            # launch/serve.py --remote-index exact.  An approximate cell
            # would carry e.g. IndexSpec("ivf_sharded", ...).to_dict().
            "index_spec": {"backend": "exact"},
            # policy selection provenance (DESIGN.md §9), the twin knob:
            # the cell lowers AÇAI's OMA retrieval step with these
            # hyper-parameters — same serialized form as
            # launch/serve.py --policy / --policy-opt.  c_f is included
            # so the record is self-contained (round-trips into
            # AcaiCache(catalog, PolicySpec.from_dict(...))).
            "policy_spec": PolicySpec(
                "acai", {"h": h, "k": k, "eta": eta, "c_f": 1.0,
                         "batch": batch}).to_dict()}


def run_acai_cell(mesh_kind: str, *, n_catalog: int = 2 ** 27, d: int = 128,
                  batch: int = 4096, c: int = 64, k: int = 10,
                  h: int = 2 ** 20, variant: str = "baseline") -> dict:
    """The paper-representative cell: one distributed AÇAI retrieval +
    OMA-update step over a 134M-object catalog sharded on the mesh."""
    from repro.core.distributed import make_retrieval_step

    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    msd = mesh_shape_dict(mesh)
    n_model = msd["model"]
    n_shard = n_catalog // n_model
    eta = 1e-2
    record = acai_cell_meta(mesh_kind, n_catalog=n_catalog, d=d, batch=batch,
                            k=k, h=h, eta=eta, variant=variant)
    t0 = time.time()
    try:
        # NOTE: the chunked-scan variant was measured and refuted (§Perf
        # C.1); the retrieval memory win is the Pallas l2_topk kernel on
        # TPU, so the XLA-level lowering is identical for both variants.
        step = make_retrieval_step(
            mesh, n_shard=n_shard, d=d, c=c, k=k, c_f=1.0, h=h, eta=eta,
            top_a=4096, batch_axes=batch_axes(multi_pod), scan_chunk=0)
        from jax.sharding import NamedSharding, PartitionSpec as P
        cat_sh = NamedSharding(mesh, P("model", None))
        y_sh = NamedSharding(mesh, P("model"))
        req_sh = NamedSharding(mesh, P(batch_axes(multi_pod), None))
        jitted = jax.jit(step, in_shardings=(cat_sh, y_sh, req_sh))
        lowered = jitted.lower(
            jax.ShapeDtypeStruct((n_catalog, d), np.float32),
            jax.ShapeDtypeStruct((n_catalog,), np.float32),
            jax.ShapeDtypeStruct((batch, d), np.float32))
        record["lower_seconds"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_seconds"] = time.time() - t1
        info = {"params_bytes_per_device": n_catalog * d * 4 // n_model}
        record.update(analyse(lowered, compiled, info, mesh.devices.size))
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001
        record["status"] = "failed"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_seconds"] = time.time() - t0
    return record


def cell_path(out_dir, arch, shape, mesh_kind):
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt"])
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.arch == "acai-retrieval" or args.all:
        for mesh_kind in meshes:
            path = cell_path(args.out, "acai-retrieval", "retrieval_b4096",
                             mesh_kind)
            if os.path.exists(path) and not args.force:
                with open(path) as f:
                    if json.load(f).get("status") == "ok":
                        print(f"[cached] acai-retrieval {mesh_kind}")
                        continue
            rec = run_acai_cell(mesh_kind, variant=args.variant)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[{rec['status']:7s}] acai-retrieval {mesh_kind} "
                  f"({rec.get('total_seconds', 0):.0f}s) "
                  f"{rec.get('error', '')}", flush=True)
        if args.arch == "acai-retrieval":
            return

    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = cell_path(args.out, arch, shape, mesh_kind)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached] {arch} {shape} {mesh_kind}: "
                              f"{prev['status']}")
                        continue
                rec = run_cell(arch, shape, mesh_kind, args.out,
                               smoke=args.smoke, variant=args.variant)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                msg = rec.get("reason") or rec.get("error", "")
                print(f"[{rec['status']:7s}] {arch} {shape} {mesh_kind} "
                      f"({rec.get('total_seconds', 0):.0f}s) {msg}",
                      flush=True)


if __name__ == "__main__":
    main()
