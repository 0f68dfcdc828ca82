"""Ahead-of-time compiles for a described TPU v5e chip at deployment shapes.

The retrieval kernels run in interpret mode everywhere else in this suite,
which accepts programs the TPU's compiler refuses (unaligned blocks, lane
slices, in-kernel gathers, VMEM overflow).  These tests hand the Pallas
kernels and the jitted AÇAI step, at the SIFT1M shape (1M x 128 f32,
B = 64), to the real compiler for a v5e that is described, not attached:
shapes only, nothing runs.  The topology is described inside a
module-scoped fixture, so collecting this file touches no TPU library.
"""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import policy
from repro.core.oma import OMAConfig
from repro.index import exact as exact_index
from repro.index import ivf as ivf_index
from repro.index.candidates import index_candidate_fn_batched
from repro.kernels import ops

kmeans = importlib.import_module("repro.index.kmeans")

N, D, B = 1_000_000, 128, 64
NLIST, NPROBE, LIST_CAP = 1024, 16, 2048
V5E_HBM = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                          sharding=one_chip)


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch wrappers ask `default_backend()`, which is the CPU
    here: steer them to the Pallas path the chip takes."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("k,d,passes", [
    pytest.param(16, D, False, id="16"),
    pytest.param(64, D, False, id="64"),
    # the GIST1M cell's width, with the selection pass counter
    pytest.param(64, 960, True, id="64-d960-passes"),
])
def test_topk_l2_compiles(shape, k, d, passes, masked):
    if masked:
        _compile(lambda q, x, v: ops.topk_l2(q, x, k, valid=v,
                                             return_passes=passes,
                                             interpret=False),
                 shape((B, d)), shape((N, d)), shape((N,), jnp.bool_))
    else:
        _compile(lambda q, x: ops.topk_l2(q, x, k, return_passes=passes,
                                          interpret=False),
                 shape((B, d)), shape((N, d)))


@pytest.mark.parametrize("k", [10, 64, 200])
def test_ivf_scan_compiles(shape, k):
    """Catalog in HBM at 1M rows; k above the 128-wide tile included."""
    c = _compile(lambda q, x, cand: ops.ivf_scan_topk(q, x, cand, k,
                                                      interpret=False),
                 shape((B, D)), shape((N, D)),
                 shape((B, NPROBE * 256), jnp.int32))
    # the catalog is read in place: no (N, d) copy on the device
    assert c.memory_analysis().temp_size_in_bytes < N * D * 4


@pytest.mark.parametrize("step", ["train", "nearest"])
def test_kmeans_compares_float32(shape, step):
    """The IVF build at 1M rows and 1024 centroids, its Lloyd iterations
    and its final assignment: no reduce of the compiled program carries a
    bfloat16 value (the TPU compiler keeps `argmin`'s running minimum in
    bfloat16 where only its index is used), and no (N, nlist) distance
    array is held at once."""
    if step == "train":
        c = kmeans.train.lower(shape((2,), jnp.uint32), shape((N, D)),
                               k=NLIST).compile()
    else:
        c = kmeans.nearest.lower(shape((N, D)), shape((NLIST, D))).compile()
    narrowed = [line for line in c.as_text().splitlines()
                if " reduce(" in line and "bf16[" in line]
    assert not narrowed, narrowed[:1]
    assert c.memory_analysis().temp_size_in_bytes < N * NLIST * 4 // 4


def test_pq_adc_compiles(shape):
    """The per-query (1, M, 256) LUT over gathered codes, vmapped over the
    batch exactly as the IVF-PQ query does it."""
    m = 16
    _compile(jax.vmap(lambda lut, codes: ops.pq_adc(lut[None], codes,
                                                    interpret=False)[0]),
             shape((B, m, 256)), shape((B, NPROBE * 256, m), jnp.int32))


def _state(shape):
    return policy.CacheState(y=shape((N,)), x=shape((N,)),
                             t=shape((), jnp.int32),
                             key=shape((2,), jnp.uint32))


class _ArgIndex:
    """An index whose query closes over the compiled step's arguments."""

    exact_distances = True

    def __init__(self, query):
        self.query = query


_CFG = policy.AcaiConfig(h=1000, k=10, c_f=1.0, c_remote=64, c_local=16,
                         oma=OMAConfig(eta=0.05))


def test_flat_step_compiles(shape, on_tpu):
    """The jitted batched step over IndexSpec('flat') at 1M rows."""

    def step(state, rs, emb):
        idx = _ArgIndex(lambda q, k: exact_index._flat_query(
            q, emb, jnp.ones((N,), bool), k, "auto", False))
        fn = index_candidate_fn_batched(idx, emb, _CFG.c_remote,
                                        _CFG.c_local, h=_CFG.h)
        return policy.make_step_batched(_CFG, fn, B)(state, rs)

    c = _compile(step, _state(shape), shape((B, D)), shape((N, D)))
    assert c.memory_analysis().temp_size_in_bytes < V5E_HBM // 4


def test_ivf_step_compiles(shape, on_tpu):
    """The jitted batched step over IndexSpec('ivf', nlist=1024,
    nprobe=16) at 1M rows: probed lists scanned by the Pallas kernel."""

    def step(state, rs, emb, cents, lists):
        idx = _ArgIndex(lambda q, k: ivf_index._ivf_query(
            q, emb, cents, lists, jnp.ones((N,), bool), k, NPROBE, False))
        fn = index_candidate_fn_batched(idx, emb, _CFG.c_remote,
                                        _CFG.c_local, h=_CFG.h)
        return policy.make_step_batched(_CFG, fn, B)(state, rs)

    c = _compile(step, _state(shape), shape((B, D)), shape((N, D)),
                 shape((NLIST, D)), shape((NLIST, LIST_CAP), jnp.int32))
    assert c.memory_analysis().temp_size_in_bytes < V5E_HBM // 4
