"""Checkpoint save/restore, crash-resume, straggler monitor, elastic reshard."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SMOKE_ARCHS
from repro.configs.shapes import ShapeSpec
from repro.launch.mesh import make_mesh
from repro.train import OptConfig, init_train_state, make_train_step
from repro.train import checkpoint
from repro.train.batching import synthetic_batch
from repro.train.data import SyntheticDataset
from repro.train.fault import StragglerMonitor, TrainLoop


@pytest.fixture()
def small_state():
    cfg = SMOKE_ARCHS["qwen1.5-0.5b"]
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
    return cfg, {"params": params, "opt": opt}


def _trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_save_restore_roundtrip(tmp_path, small_state):
    cfg, state = small_state
    checkpoint.save(str(tmp_path), 7, state)
    assert checkpoint.latest_step(str(tmp_path)) == 7
    restored = checkpoint.restore(str(tmp_path), 7, state)
    _trees_equal(state, restored)
    # bf16 dtypes survive the uint16 view round-trip
    assert restored["params"]["embed"].dtype == state["params"]["embed"].dtype


def test_checkpoint_gc_keeps_latest(tmp_path, small_state):
    cfg, state = small_state
    for s in (1, 2, 3, 4, 5):
        checkpoint.save(str(tmp_path), s, state, keep=2)
    assert checkpoint.all_steps(str(tmp_path)) == [4, 5]


def test_atomic_commit_no_tmp_left(tmp_path, small_state):
    cfg, state = small_state
    checkpoint.save(str(tmp_path), 1, state)
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_crash_resume_loop(tmp_path, small_state):
    """Inject a failure mid-run; the loop must resume from the checkpoint
    and produce the same final state as an uninterrupted run."""
    cfg, state0 = small_state
    shape = ShapeSpec("train", 16, 2, "train")
    step_fn = jax.jit(make_train_step(cfg, OptConfig(lr=1e-3)))
    dataset = SyntheticDataset(cfg, shape)

    def make_loop_step(crash_at, crashed):
        def loop_step(state, batch, step):
            if step == crash_at and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("injected failure")
            p, o, _ = step_fn(state["params"], state["opt"], batch, step)
            return {"params": p, "opt": o}
        return loop_step

    # interrupted run
    crashed = {"done": False}
    loop = TrainLoop(make_loop_step(7, crashed), jax.tree.map(jnp.copy, state0),
                     str(tmp_path / "a"), ckpt_every=5)
    final_a = loop.run(10, lambda s: dataset.batch(s))
    assert crashed["done"] and loop.restarts == 1

    # clean run
    loop_b = TrainLoop(make_loop_step(-1, {"done": True}),
                       jax.tree.map(jnp.copy, state0), str(tmp_path / "b"),
                       ckpt_every=5)
    final_b = loop_b.run(10, lambda s: dataset.batch(s))
    _trees_equal(final_a, final_b)


def test_straggler_monitor_flags_slow_steps():
    mon = StragglerMonitor(threshold=2.0)
    for s in range(20):
        mon.record(s, 0.1)
    assert not mon.flagged
    mon.record(20, 0.5)
    assert mon.flagged and mon.flagged[-1][0] == 20


def test_elastic_restore_changes_placement(tmp_path, small_state):
    """Restore with an explicit sharding tree (1-device mesh here; the same
    path re-shards onto any mesh at scale)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    cfg, state = small_state
    checkpoint.save(str(tmp_path), 3, state)
    mesh = make_mesh((1,), ("data",))
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    restored = checkpoint.restore(str(tmp_path), 3, state, shardings)
    _trees_equal(state, restored)
    leaf = jax.tree.leaves(restored)[0]
    assert isinstance(leaf.sharding, NamedSharding)
