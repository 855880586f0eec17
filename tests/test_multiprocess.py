"""Wall-clock launcher (launch/multiprocess.py): coordinator handshake
failure surfaces as a clear error (never a hang), and the degenerate
single-process launch is bit-exact against the in-process FusedExecutor
— the distributed runtime at N=1 must be a no-op.

These tests spawn real OS processes (each imports jax); they are the
slowest tier-1 tests by design — the wallclock-smoke CI job runs them
against the real gloo transport.
"""

import functools
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from repro.launch import multiprocess as mp


def test_parse_kv_takes_upper_snake_lines_later_wins():
    text = ("garbage\nSTEPS_PER_S=12.5\nnoise a=b\nlower=skipped\n"
            "STEPS_PER_S=13.0\nREL_SPREAD=0.01\n")
    kv = mp.parse_kv(text)
    assert kv == {"STEPS_PER_S": "13.0", "REL_SPREAD": "0.01"}


def test_worker_env_pins_only_cpu_roles(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    cpu = mp.worker_env(2, "cpu")
    assert cpu["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=2" in cpu["XLA_FLAGS"]
    # a role on the default platform inherits the caller's choice; this
    # process runs JAX on the CPU, so it holds no chip a child would need
    default = mp.worker_env(1, None)
    assert default["JAX_PLATFORMS"] == "tpu"
    assert "XLA_FLAGS" not in default
    assert default["PYTHONPATH"].split(os.pathsep)[0].endswith("src")


def test_launch_rejects_empty_gang():
    with pytest.raises(ValueError, match="n_procs"):
        mp.launch(["--mode", "fused"], n_procs=0)


def test_handshake_timeout_raises_clear_error_not_hang():
    """A worker whose coordinator never comes up (process 0 missing from
    the gang) must exit with the initialize_distributed RuntimeError
    naming the coordinator — within the handshake timeout, not a
    collective-deadline hang."""
    port = mp.free_port()   # bound by nobody: the handshake cannot succeed
    cmd = [sys.executable, "-m", "repro.launch.multiprocess",
           "--coordinator", f"127.0.0.1:{port}",
           "--n-procs", "2", "--process-id", "1",
           "--handshake-timeout", "8",
           "--mode", "fused", "--iters", "1"]
    t0 = time.monotonic()
    res = subprocess.run(cmd, env=mp.worker_env(1), capture_output=True,
                         text=True, timeout=180)
    elapsed = time.monotonic() - t0
    assert res.returncode != 0
    out = res.stdout + res.stderr
    assert "coordinator handshake failed" in out, out[-2000:]
    assert f"127.0.0.1:{port}" in out
    # timeout (8s) + interpreter/jax startup, nowhere near the 180s hang
    assert elapsed < 120, elapsed


def test_launch_surfaces_worker_failure_with_output_tail():
    """Parent-side contract: a worker that exits non-zero after the
    handshake (here: --mode fused on a 2-process gang, which the worker
    rejects) turns into a RuntimeError carrying the worker's output tail
    — and the rest of the gang is killed rather than left wedged at the
    next collective."""
    with pytest.raises(RuntimeError, match="wall-clock worker"):
        mp.launch(["--mode", "fused", "--iters", "1"], n_procs=2,
                  timeout_s=300.0)


def test_single_process_launch_bit_exact_vs_in_process_fused():
    """The degenerate launch: one worker through the full coordinator
    handshake runs the exact FusedExecutor program — final loss, env
    steps and a parameter checksum must match the same executor driven
    in-process, bit for bit."""
    from repro.agents.dqn import DQNConfig, make_dqn
    from repro.core.replay import PrioritizedReplay, ReplayConfig
    from repro.envs.classic import make_vec
    from repro.runtime.executors import FusedExecutor
    from repro.runtime.loop import LoopConfig

    iters, n_envs, scan_chunk, seed = 30, 8, 10, 0
    out = mp.launch(["--mode", "fused",
                     "--iters", str(iters),
                     "--n-envs", str(n_envs),
                     "--scan-chunk", str(scan_chunk),
                     "--seed", str(seed)],
                    n_procs=1, timeout_s=600.0)
    kv = mp.parse_kv(out[0])

    # in-process reference: mirrors multiprocess._build_executor exactly
    env_fn = functools.partial(make_vec, "cartpole")
    spec, _, _ = env_fn(1)
    agent = make_dqn(spec, DQNConfig())
    example = {
        "obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros(()),
        "next_obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "done": jnp.zeros(()),
    }
    cfg = LoopConfig(batch_size=64, warmup=64, epsilon=0.1)
    replay = PrioritizedReplay(
        ReplayConfig(capacity=50_000, fanout=128), example)
    ex = FusedExecutor(agent, replay, env_fn, cfg, n_envs,
                       scan_chunk=scan_chunk)
    state, hist = ex.train(iters, jax.random.PRNGKey(seed))
    params = jax.device_get(state.agent.params)
    checksum = 0.0
    for leaf in jax.tree.leaves(params):
        checksum += float(abs(leaf.astype("float64")).sum())

    assert float(kv["FINAL_LOSS"]) == float(hist["loss"][-1])
    assert float(kv["FINAL_RETURN"]) == float(
        hist["mean_episode_return"][-1])
    assert int(kv["ENV_STEPS"]) == int(hist["env_steps"][-1])
    assert float(kv["PARAMS_CHECKSUM"]) == checksum
