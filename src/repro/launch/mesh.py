"""Production mesh construction (never touches jax device state at import).

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the pod axis
is the slow inter-pod interconnect; gradients crossing it may use the
int8 error-feedback compressed reduce (optim/compress.py)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from repro.models.config import ShardingConfig


def data_mesh(n_shards: Optional[int] = None, axis: str = "data"):
    """1-D mesh over ``n_shards`` devices for the sharded replay/learner
    data path (defaults to all visible devices)."""
    devices = jax.devices()
    n = n_shards or len(devices)
    if len(devices) < n:
        raise RuntimeError(
            f"data mesh needs {n} devices, found {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before any "
            "jax import to force host-platform shards.")
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(n), (axis,))


def pod_data_mesh(n_pods: int, n_data: int, axes: Tuple[str, str] = ("pod", "data")):
    """2-D ``(pod, data)`` mesh for the two-axis sharded executor.

    The first (outer) axis is the slow inter-pod interconnect — the one
    the int8 error-feedback compressed reduce crosses
    (``ShardedExecutor(compress_pod_reduce=True)``); the second is the
    fast intra-pod data axis where gradients reduce in f32.  Device
    order is row-major pod-major, matching the executor's flattened
    shard ids, so a ``pod_data_mesh(P, 1)`` run reproduces a
    ``data_mesh(P)`` run exactly from the same seed.
    """
    if n_pods < 1 or n_data < 1:
        raise ValueError(f"pod_data_mesh({n_pods}, {n_data}): both axis "
                         "extents must be ≥ 1")
    n = n_pods * n_data
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"pod×data mesh ({n_pods}, {n_data}) needs {n} devices, found "
            f"{len(devices)} — set XLA_FLAGS="
            "--xla_force_host_platform_device_count before any jax import "
            "to force host-platform shards.")
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(n_pods, n_data), axes)


def mesh_from_plan(plan):
    """Mesh for a planner-selected runtime config
    (``runtime.planner.PlannedConfig``, duck-typed on
    ``n_pods``/``n_data``): ``None`` for the fused program, a 1-D data
    mesh for single-pod sharding, the two-axis (pod, data) mesh when the
    plan crosses pods.  The caller must have forced
    ``plan.n_devices`` host devices before the first jax call —
    quickstart's ``--plan`` path does."""
    if not plan.n_data:
        return None
    if plan.n_pods > 1:
        return pod_data_mesh(plan.n_pods, plan.n_data)
    return data_mesh(plan.n_data)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import (launch/dryrun.py does)."
        )
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)


def sharding_config(multi_pod: bool = False) -> ShardingConfig:
    return ShardingConfig(
        fsdp=("pod", "data") if multi_pod else ("data",),
        tp="model",
        tp_extent=16,
        dp_extent=32 if multi_pod else 16,
    )


def small_mesh(n_data: Optional[int] = None, n_model: int = 1):
    """Host-size mesh for tests/examples (uses however many devices exist)."""
    devs = jax.devices()
    n_data = n_data or (len(devs) // n_model)
    dev_array = np.asarray(devs[: n_data * n_model]).reshape(n_data, n_model)
    return jax.sharding.Mesh(dev_array, ("data", "model"))
