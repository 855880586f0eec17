"""The harness finds every cell's files by name, picks up a cell and an
algorithm family added as files and one entry each, prints the result line's keys, and refuses to
measure anywhere but on a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import time

import pytest
from conftest import ROOT

from perfbench import algorithms, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_every_cell_resolves_by_name():
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "perfbench/run.py"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert e2e == {"env_steps_per_s", "setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} == e2e
        assert cell.per_layer, w["name"]
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert set(cell.limits) >= {"loss_gap", "prio_gap", "counters"}
        for name in (w["name"], w["config"], w["traffic"]):
            assert NAME.match(name), name
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["unit"] == "%"
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in bench["workloads"]}


def test_configs_hold_what_the_harness_builds():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert set(c["reduced"]) == set(config["reduced"]), c["name"]
        assert set(c["reduced"]) <= set(config), c["name"]
        assert c["source"].split()[0] in config["source"], c["name"]
        algorithms.of(config).check_config(config)


def test_check_config_refuses_a_cut_width():
    with open(os.path.join(ROOT, "perfbench/configs/dqn_cartpole.json")) as f:
        config = json.load(f)
    dqn = algorithms.of(config)
    with pytest.raises(ValueError, match="hidden_sizes"):
        dqn.check_config(dict(config, hidden_sizes=[64, 64]))
    with pytest.raises(ValueError, match="replay_capacity"):
        dqn.check_config(dict(config, replay_capacity=2 ** 14))


def test_unknown_algorithm_lists_the_families(tmp_path):
    have = algorithms.names()
    assert {"dqn", "ddpg"} <= set(have)
    with pytest.raises(KeyError, match="no algorithm family 'ppo'") as err:
        algorithms.load("ppo")
    for name in have:
        assert repr(name) in str(err.value)
    root = _checkout(tmp_path)
    path = root / "perfbench" / "configs" / "dqn_cartpole.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    algorithm="ppo")))
    with pytest.raises(KeyError, match="no algorithm family 'ppo'"):
        spec.resolve("dqn_cartpole.ratio2.xla", str(root))


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def test_new_cell_needs_only_files_and_one_entry(tmp_path):
    """A later change adds a configuration, a traffic mix, limits and a
    per-layer metric as new files plus one entry each: no code edit."""
    root = _checkout(tmp_path)
    pb = root / "perfbench"
    config = json.loads((pb / "configs" / "dqn_cartpole.json").read_text())
    config["name"] = "dqn_cartpole_wide"
    (pb / "configs" / "dqn_cartpole_wide.json").write_text(json.dumps(config))
    traffic = json.loads((pb / "traffic" / "ratio2.xla.json").read_text())
    traffic["n_envs"] = 128
    (pb / "traffic" / "ratio8.xla.json").write_text(json.dumps(traffic))
    (pb / "limits" / "dqn_cartpole_wide.ratio8.xla.json").write_text(
        json.dumps({"loss_gap": 1.0, "prio_gap": 1.0, "counters": 0}))
    (pb / "metrics" / "window_seconds.py").write_text(
        "def read(ctx):\n    return ctx['window_s']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="dqn_cartpole_wide",
                                 file="perfbench/configs/dqn_cartpole_wide.json"))
    bench["workloads"].append({"name": "dqn_cartpole_wide.ratio8.xla",
                               "config": "dqn_cartpole_wide",
                               "traffic": "ratio8.xla", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "window_seconds", "unit": "s",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "env_steps_per_s",
                               "workloads": ["dqn_cartpole_wide.ratio8.xla"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("dqn_cartpole_wide.ratio8.xla", str(root))
    assert cell.config["name"] == "dqn_cartpole_wide"
    assert cell.traffic["n_envs"] == 128
    assert cell.readers["window_seconds"]({"window_s": 2.5}) == 2.5
    assert "window_seconds" not in spec.resolve(
        "dqn_cartpole.ratio2.xla", str(root)).readers


TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_family")

RUN_TOY = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    from perfbench import cell as cell_mod, faults
    from perfbench.spec import resolve
    cell = resolve("linq_walk.walk")
    out = {{}}
    for arm in ("program", "half_batch"):
        run = lambda: cell_mod.run(cell, 2 ** 31 + 77, 0.2, False,
                                   cell_mod.CompileClock(),
                                   time.perf_counter())
        if arm == "program":
            out[arm] = run()
        else:
            with faults.half_batch():
                out[arm] = run()
    print(json.dumps(out))
""")


def test_new_family_needs_only_files_and_one_entry(tmp_path):
    """A later change adds an algorithm family (its module, with its own
    env, Q-function, reference loss and work counts), a configuration,
    a traffic mix and limits as new files plus one entry each.  A whole
    run of the new cell (the chip check skipped) is correct on the CPU,
    and not correct with half of each batch left out under the timed
    path."""
    root = _checkout(tmp_path)
    pb = root / "perfbench"
    for name, dest in (("linq.py", "algorithms"), ("linq_walk.json", "configs"),
                       ("walk.json", "traffic"),
                       ("linq_walk.walk.json", "limits")):
        shutil.copy(os.path.join(TOY, name), pb / dest / name)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "linq_walk", "source": "test",
                             "file": "perfbench/configs/linq_walk.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "linq_walk.walk", "config": "linq_walk",
                               "traffic": "walk", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = subprocess.run(
        [sys.executable, "-c", RUN_TOY.format(
            root=str(root), src=os.path.join(ROOT, "src"))],
        cwd=str(root), capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    program, fault = out["program"], out["half_batch"]
    assert program["correct"] is True, program["checks"]
    assert program["attempted"] > 0 and program["failed"] == 0
    assert fault["correct"] is False, fault["checks"]


def test_result_line_keys(tiny_cell):
    """A whole run (the chip check skipped) at a tiny size on the CPU:
    the result's keys, the metrics of the cell, and the compared numbers
    last, each beside its limit."""
    from perfbench import cell as cell_mod

    cell = tiny_cell("dqn_cartpole.ratio2.xla")
    result = cell_mod.run(cell, 2 ** 31 + 5, 0.5, False,
                          cell_mod.CompileClock(), time.perf_counter())
    assert list(result) == RESULT_KEYS + ["checks"]
    assert result["correct"] is True
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert result["metrics"]["env_steps_per_s"]["unit"] == "env_steps/s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    assert set(result["checks"]) == set(cell.limits)
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    assert result["attempted"] > 0 and result["failed"] == 0
    json.dumps(result)


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *args], cwd=cwd,
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_run_refuses_a_cpu_backend():
    r = _run(["--workload", "dqn_cartpole.ratio2.xla", "--seed", "3",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(["--workload", "dqn_cartpole.ratio2.xla", "--seed", "3",
              "--seconds", "1", "--trace", "0"], str(tmp_path),
             PYTHONPATH="")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
