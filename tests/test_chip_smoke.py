"""``chip_smoke.py``, the TPU bring-up script: it refuses to run without
a TPU, and each of its phases passes at toy sizes on the CPU (Pallas in
interpret mode), so a later change that breaks a phase fails here
before it costs a chip run."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_exits_nonzero_without_tpu(tmp_path, where):
    """Without a TPU, and in a directory that holds the script and
    nothing else of the repo, it fails and prints no result line."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        [sys.executable, script, "--out", str(tmp_path / "out")],
        cwd=os.path.dirname(script), env=env, capture_output=True,
        text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU found" in res.stderr, res.stderr[-2000:]


def test_train_phase_toy_size():
    cs = _chip_smoke()
    out = cs.train_phase(capacity=2 ** 12, n_envs=8, batch=256,
                         update_interval=8, warmup=64, scan_chunk=4,
                         chunks=3, min_learns=4, clock=cs.CompileClock())
    assert out["pallas"]["kernel_path_ok"]
    assert out["pallas"]["env_steps"] == out["xla"]["env_steps"] == 96
    assert out["kernel_check"]["rows_exact"]


def test_serve_phase_toy_size():
    from repro.configs import granite_8b

    cs = _chip_smoke()
    cfg = dataclasses.replace(granite_8b.CONFIG, num_layers=2, d_model=256,
                              num_heads=4, num_kv_heads=2, d_ff=512,
                              vocab_size=512)
    out = cs.serve_phase(cfg, slots=3, max_len=64, buckets=(16, 32),
                         requests=5, gen=8, prompt_lens=(4, 32),
                         clock=cs.CompileClock())
    assert out["decode_compiles"] == 1
    assert out["max_rel_l2"] <= out["rel_tol"]


FOUR_DEVICES = textwrap.dedent("""
    import importlib.util, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    out = cs.sharded_phase(capacity_per_shard=4096, clock=cs.CompileClock())
    assert out["param_diff_1d_vs_2x2"] <= 1e-5, out
    print("FOUR_DEVICES_OK")
""")


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", FOUR_DEVICES, SCRIPT],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=600)
    assert "FOUR_DEVICES_OK" in res.stdout, (res.stdout[-2000:]
                                             + res.stderr[-3000:])


def test_compile_cache_dir(monkeypatch, tmp_path):
    """Unset: a fixed directory in the checkout.  Set: JAX's own reading
    of JAX_COMPILATION_CACHE_DIR decides, and the helper changes
    nothing."""
    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == before
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
