"""The AÇAI step's profiler names: the `acai.*` scopes of the jitted step
reach the compiled program's op metadata, `serve_update_batch` opens its
three host spans, in order, each with the same `call`, and the IVF build
opens its three."""

import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import policy
from repro.core.oma import OMAConfig
from repro.index import IndexSpec

SCOPES = ("acai.remote", "acai.local", "acai.merge", "acai.gain",
          "acai.oma", "acai.rounding")
SPANS = ("acai.put", "acai.finite_check", "acai.step")


def _cache():
    rng = np.random.default_rng(0)
    catalog = jax.numpy.asarray(rng.random((512, 16), np.float32))
    cfg = policy.AcaiConfig(h=16, k=4, c_remote=8, c_local=4,
                            oma=OMAConfig(eta=0.01), index=IndexSpec("flat"))
    return policy.AcaiCache(catalog, cfg, seed=0), rng


def test_step_scopes_reach_the_compiled_metadata():
    cache, rng = _cache()
    rs = rng.random((8, 16), np.float32)
    text = cache.lower_batch(rs).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope


@pytest.mark.parametrize("resilient", [False, True])
def test_serve_update_batch_opens_three_host_spans(tmp_path, resilient):
    """With a remote attached the batch takes the resilience ladder, which
    runs inside `acai.step`."""
    cache, rng = _cache()
    if resilient:
        cache.attach_remote()
    rs = rng.random((8, 16), np.float32)
    jax.block_until_ready(cache.serve_update_batch(rs))    # compile first
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(cache.serve_update_batch(rs))
    data = ProfileData.from_file(str(next(tmp_path.glob("**/*.xplane.pb"))))
    spans = sorted(
        (ev.start_ns, ev.name.split("#")[0],
         next(v for k, v in ev.stats if k == "call"), ev.end_ns)
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("acai."))
    assert [s[1] for s in spans] == list(SPANS)
    assert {s[2] for s in spans} == {2}     # the second call of the cache
    # siblings: each begins once the one before it has ended
    assert spans[0][3] <= spans[1][0] and spans[1][3] <= spans[2][0]


def test_ivf_probe_scope_sits_inside_the_remote_scope():
    rng = np.random.default_rng(1)
    catalog = jax.numpy.asarray(rng.random((512, 16), np.float32))
    cfg = policy.AcaiConfig(h=16, k=4, c_remote=8, c_local=4,
                            oma=OMAConfig(eta=0.01),
                            index=IndexSpec("ivf", {"nlist": 8, "nprobe": 2}))
    cache = policy.AcaiCache(catalog, cfg, seed=0)
    text = cache.lower_batch(rng.random((8, 16), np.float32)).compile(
        ).as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    probe = [n for n in names if "/acai.ivf_probe/" in n]
    assert probe and all("/acai.remote/" in n for n in probe)


def test_ivf_build_opens_its_host_spans_in_order(tmp_path):
    from repro.index.ivf import IVFFlatIndex

    data = jax.numpy.asarray(
        np.random.default_rng(2).random((300, 8), np.float32))
    IVFFlatIndex(data, nlist=4, nprobe=2)                   # compile first
    with jax.profiler.trace(str(tmp_path)):
        IVFFlatIndex(data, nlist=4, nprobe=2)
    prof = ProfileData.from_file(str(next(tmp_path.glob("**/*.xplane.pb"))))
    spans = sorted((ev.start_ns, ev.name, ev.end_ns)
                   for plane in prof.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("ivf."))
    assert [s[1] for s in spans] == ["ivf.kmeans", "ivf.assign", "ivf.lists"]
    assert spans[0][2] <= spans[1][0] and spans[1][2] <= spans[2][0]
