"""The plain reference agrees with the program's sampler and learner at
a tiny size on the CPU (where a float32 matmul is exact float32), on
both tree backends and across four virtual devices."""

import json
import os
import subprocess
import sys
import textwrap

from conftest import ROOT

from perfbench import cell as cell_mod
from perfbench import check


def readings(cell, seed):
    p = cell_mod.probe(cell, seed)
    rows = cell_mod.host_rows(p.built, p.rows)
    ref = cell_mod.follow(cell, p.built, seed, rows)
    return check.readings(p.probe, ref, cell_mod.expected_counters(
        cell, p.built))


def test_dqn_xla_matches_reference(tiny_cell):
    r = readings(tiny_cell("dqn_cartpole.ratio2.xla"), 2 ** 32 + 9)
    assert r["counters"] == 0
    assert r["prio_gap"] <= 1e-6
    assert r["loss_gap"] <= 1e-5


def test_dqn_pallas_kernels_match_reference(tiny_cell):
    cell = tiny_cell("dqn_cartpole.ratio2.pallas")
    r = readings(cell, 17)
    assert r["counters"] == 0
    assert r["prio_gap"] <= 1e-6
    # the kernels' prefix sums round differently from the reference's
    # float64 ones, so from the second draw on a draw within rounding
    # of a leaf boundary may take the neighbour: the loss of the later
    # updates differs by that, not by ulps
    assert r["loss_gap"] <= 1e-2


def test_ddpg_matches_reference(tiny_cell):
    r = readings(tiny_cell("ddpg_pendulum.ratio1.xla"), 23)
    assert r["counters"] == 0
    assert r["prio_gap"] <= 1e-6


FOUR = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {root!r})
    from conftest import tiny
    from perfbench.spec import resolve
    from perfbench import cell as cell_mod, check, faults
    cell = tiny(resolve("dqn_cartpole.ratio2.xla.4chip"))
    out = {{}}
    for arm in ("program", "no_exchange"):
        if arm == "program":
            p = cell_mod.probe(cell, 31)
        else:
            with faults.no_exchange():
                p = cell_mod.probe(cell, 31)
        rows = cell_mod.host_rows(p.built, p.rows)
        ref = cell_mod.follow(cell, p.built, 31, rows)
        out[arm] = check.readings(
            p.probe, ref, cell_mod.expected_counters(cell, p.built))
    out["limits"] = cell.limits
    print(json.dumps(out))
""")


def test_four_devices_match_reference_and_lose_the_exchange():
    """Four virtual devices: the sharded program matches the reference
    (per-shard draws against the global mass, averaged gradients,
    replicated parameters); with the gradient exchange left out the
    comparison comes out not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = FOUR.format(root=os.path.join(ROOT, "tests", "perfbench"))
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    prog, fault = out["program"], out["no_exchange"]
    assert prog["counters"] == 0 and prog["param_spread"] == 0
    assert prog["prio_gap"] <= 1e-6
    assert prog["loss_gap"] <= out["limits"]["loss_gap"]
    assert fault["param_spread"] > out["limits"]["param_spread"]
