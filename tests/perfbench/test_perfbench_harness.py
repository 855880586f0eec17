"""The harness finds every cell's files by name, picks up a cell added
as files and one entry, prints the result line's keys, and refuses to
measure anywhere but on a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

from conftest import ROOT

from perfbench import spec

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
        assert config["replay_capacity"] == 2 ** 20
        # both sources publish two 256-wide hidden layers
        assert config["hidden_sizes"] == [256, 256]
        assert "256, 256" in json.dumps(config["published"]) or \
            "256 -> 256" in json.dumps(config["published"])


def test_new_cell_needs_only_files_and_one_entry(tmp_path):
    """A later change adds a configuration, a traffic mix, limits and a
    per-layer metric as new files plus one entry each: no code edit."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
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
