"""Sharding specs + a real multi-device pjit execution in a subprocess.

The main test process must keep the default 1-device CPU; multi-device
runs happen in a child process that sets XLA_FLAGS before importing jax —
the same discipline as launch/dryrun.py.
"""

import json
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import SMOKE_ARCHS
from repro.models import init_params
from repro.sharding import specs as S


def test_param_pspecs_cover_all_leaves():
    mesh_shape = {"data": 16, "model": 16}
    for name, cfg in SMOKE_ARCHS.items():
        params_shape = jax.eval_shape(
            lambda c=cfg: init_params(jax.random.PRNGKey(0), c))
        pspecs = S.param_pspecs(cfg, params_shape, mesh_shape)
        n_leaves = len(jax.tree.leaves(params_shape))
        n_specs = len(jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        assert n_leaves == n_specs, name


def test_pspec_divisibility_respected():
    """Every sharded dim must be divisible by its mesh-axis size."""
    from repro.configs import ARCHS
    mesh_shape = {"pod": 2, "data": 16, "model": 16}
    for name, cfg in ARCHS.items():
        params_shape = jax.eval_shape(
            lambda c=cfg: init_params(jax.random.PRNGKey(0), c))
        pspecs = S.param_pspecs(cfg, params_shape, mesh_shape)
        for leaf, spec in zip(
                jax.tree.leaves(params_shape),
                jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))):
            for dim, ax in zip(leaf.shape, spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = 1
                for a in axes:
                    size *= mesh_shape[a]
                assert dim % size == 0, (name, leaf.shape, tuple(spec))


_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import SMOKE_ARCHS
    from repro.launch.mesh import mesh_shape_dict
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.sharding import specs as S
    from repro.sharding.ctx import mesh_context
    from repro.train import OptConfig, make_train_step
    from repro.train.optimizer import init_opt
    from repro.train.batching import synthetic_batch
    from repro.configs.shapes import ShapeSpec

    cfg = SMOKE_ARCHS["mixtral-8x22b"]
    mesh = make_mesh((2, 4), ("data", "model"))
    msd = mesh_shape_dict(mesh)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pspecs = S.param_pspecs(cfg, jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), cfg)), msd)
    param_sh = S.as_shardings(mesh, pspecs)
    params = jax.tree.map(jax.device_put, params, param_sh)
    opt = init_opt(cfg.optimizer, params)
    batch = synthetic_batch(cfg, ShapeSpec("train", 16, 4, "train"))
    with mesh_context(mesh, ("data",)):
        step = jax.jit(make_train_step(cfg, OptConfig(name=cfg.optimizer)))
        p, o, m = step(params, opt, batch, 0)
        loss1 = float(m.loss)
        p, o, m = step(p, o, batch, 1)
        loss2 = float(m.loss)
    print(json.dumps({"loss1": loss1, "loss2": loss2,
                      "n_dev": jax.device_count()}))
""")


def test_multidevice_pjit_execution_subprocess():
    out = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_dev"] == 8
    assert res["loss2"] < res["loss1"] * 1.5  # finite and sane
