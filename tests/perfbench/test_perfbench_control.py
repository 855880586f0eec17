"""The comparison that decides ``correct`` fails the control and every
fault a one-chip cell can have, with the cell's own limits.

The control is the reference computed in bfloat16, put in the program's
place.  The faults are planted under the timed path (perfbench/faults.py)
and a whole run is driven with the chip check skipped; the four-chip
fault, the exchange left out, is in test_perfbench_reference.py."""

import time

import jax.numpy as jnp
import pytest

from perfbench import cell as cell_mod
from perfbench import check, faults


@pytest.mark.parametrize("workload", ["dqn_cartpole.ratio2.xla",
                                      "ddpg_pendulum.ratio1.xla"])
def test_control_is_not_correct(tiny_cell, workload):
    cell = tiny_cell(workload)
    p = cell_mod.probe(cell, 41)
    rows = cell_mod.host_rows(p.built, p.rows)
    expected = cell_mod.expected_counters(cell, p.built)
    ref = cell_mod.follow(cell, p.built, 41, rows)
    ctrl = cell_mod.follow(cell, p.built, 41, rows, dtype=jnp.bfloat16)
    checks = check.compare(check.probe_of(ctrl, p.built.capacity, expected),
                           ref, expected, cell.limits)
    assert not check.all_within(checks), checks


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "wrong_leaf"])
def test_fault_under_the_timed_path_is_not_correct(tiny_cell, fault):
    cell = tiny_cell("dqn_cartpole.ratio2.xla")
    with faults.FAULTS[fault]():
        result = cell_mod.run(cell, 43, 0.2, False, cell_mod.CompileClock(),
                              time.perf_counter())
    assert result["correct"] is False, result["checks"]
