"""Faults planted under the timed path, each of which the check has to
find: the step returns its state unchanged; the update averages half of
the batch and leaves the rest out; the remote scan names a wrong row as a
request's nearest answer.  (An exchange between chips left out does not
apply: every cell runs on one chip.)

`plant(name, setattr_)` patches the program's module attributes through
`setattr_(obj, attr, value)`: pytest's `monkeypatch.setattr` in the
tests, the builtin `setattr` in `bench/control.py --fault` on the chip.
Jitted functions keep the traces made before a fault is planted, so call
`jax.clear_caches()` first.
"""

from __future__ import annotations

import jax.numpy as jnp


def _state_unchanged(setattr_):
    from repro.core import policy

    direct = policy.AcaiCache._serve_batch_direct

    def step(self, rs):
        before = self.state
        m = direct(self, rs)
        self.state = before
        return m

    setattr_(policy.AcaiCache, "_serve_batch_direct", step)


def _half_batch(setattr_):
    """The update averages the first half of the batch's subgradients and
    leaves the rest out."""
    from repro.core import gain

    whole = gain.gain_and_subgradient_batch

    def half(d, y, k, c_f):
        g_frac, g = whole(d, y, k, c_f)
        b = g.shape[0]
        keep = (jnp.arange(b) < b // 2)[:, None]
        return g_frac, jnp.where(keep, 2.0 * g, 0.0)

    setattr_(gain, "gain_and_subgradient_batch", half)


def _answer_altered(setattr_):
    """The remote scan names the wrong row for each request's nearest
    answer: the fused XLA scans off the TPU, the Pallas kernels on it."""
    from repro.kernels import ops

    for name in ("topk_l2_xla", "ivf_scan_xla", "topk_l2", "ivf_scan_topk"):
        good = getattr(ops, name)

        def bad(*args, _good=good, **kw):
            d, i = _good(*args, **kw)
            return d, i.at[:, 0].set((i[:, 0] + 1) % 4096)

        setattr_(ops, name, bad)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


def plant(name: str, setattr_=setattr) -> None:
    FAULTS[name](setattr_)
