"""The control and the faults: the reference computed in bfloat16 put in
the program's place, and each fault of `bench/faults.py` planted under
the timed path, come out not correct in a whole run of the harness (CPU,
tiny size)."""

import jax
import pytest

from bench import faults

from test_bench_run import run


@pytest.mark.parametrize("cell", ["sift1m-ivf.backlog", "gist1m-flat.backlog"])
def test_control_is_not_correct(cell):
    out = run(cell, control=True)
    assert not out["correct"], out["checks"]


@pytest.fixture
def fresh_traces():
    """Module-level jits keep the traces of earlier tests: drop them before
    a fault is planted and after it is taken out."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", ["sift1m-ivf.backlog", "gist1m-flat.backlog"])
def test_fault_is_not_correct(cell, fault, monkeypatch, fresh_traces):
    faults.plant(fault, monkeypatch.setattr)
    out = run(cell)
    assert not out["correct"], out["checks"]


def test_control_readings_script():
    """bench/control.py, which takes the readings the limits are set from
    on the chip, at a tiny size: the control reads far above the
    program."""
    from bench import control
    from test_bench_run import TRAFFIC, bench, tiny

    rows = control.collect("sift1m-ivf.backlog", 0.3, 2, 1, 2**31 + 7,
                           cfg_override=tiny("sift1m-ivf"),
                           traffic_override=TRAFFIC, bench=bench())
    s = control.summary(rows)
    assert s["seeds"] == 2 and s["control_seeds"] == 1
    for name in ("cost_gap", "state_gap", "ivf_lists_bad"):
        assert s["control_min"][name] > 3 * s["program_max"][name], name
