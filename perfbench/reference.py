"""Plain reference of the loop's first iteration of learner updates.

It imports nothing of the program.  From the seed it makes the agent's
weights by the configuration's recipe (the same key derivation as the
loop's initial state, written out here), takes the buffer's rows as
input (the benchmark's fill, the way a served model's reference takes
the prompts), and follows the first iteration's ``learns`` updates:
inverse-CDF draws over the leaf priorities, gathered rows, PER
importance weights, the loss and gradient of the configuration's
algorithm family (``perfbench/algorithms/``), Adam, the
Polyak target, and the priority write-back that the next draw sees.
With several shards each draws from its own buffer against the global
mass and maximum weight, and the gradients are averaged across shards.

``dtype=float32`` runs at ``highest`` matmul precision (the reference);
``dtype=bfloat16`` is the control, the same code one precision lower.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import algorithms


def seed_key(seed: int) -> np.ndarray:
    """A raw uint32[2] PRNG key that keeps all 64 bits of ``seed``."""
    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def init_params(config: dict, seed: int):
    """Weights as the loop's initial state makes them from ``seed``:
    key → (env, agent, rng) by a three-way split; the family makes its
    weights from the agent key."""
    _, k_agent, _ = jax.random.split(jnp.asarray(seed_key(seed)), 3)
    return algorithms.of(config).init_params(config, k_agent)


def sample_keys(seed: int, shard: int, learns: int):
    """The uniform-draw key of each learn of the first iteration."""
    _, _, k_rng = jax.random.split(jnp.asarray(seed_key(seed)), 3)
    _, k = jax.random.split(k_rng)
    k = jax.random.fold_in(k, shard)
    _, _, k_sample = jax.random.split(k, 3)
    return [jax.random.fold_in(k_sample, i) for i in range(learns)]


def _adam(config: dict, grads, m, v, params, count):
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
    clip = config["grad_clip_norm"]
    if clip > 0:
        s = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree.map(lambda g: (g * s).astype(g.dtype), grads)
    b1, b2, eps = config["adam_b1"], config["adam_b2"], config["adam_eps"]
    lr = config["learning_rate"]
    b1c, b2c = 1.0 - b1 ** count, 1.0 - b2 ** count
    dt = leaves[0].dtype
    m = jax.tree.map(lambda g, mm: (b1 * mm + (1 - b1) * g).astype(dt), grads, m)
    v = jax.tree.map(lambda g, vv: (b2 * vv + (1 - b2) * g * g).astype(dt),
                     grads, v)
    params = jax.tree.map(
        lambda p, mm, vv: (p - lr * (mm / b1c) / (jnp.sqrt(vv / b2c) + eps)
                           ).astype(dt), params, m, v)
    return params, m, v


@dataclasses.dataclass
class Followed:
    """What the reference (or the control) says of the first iteration."""
    loss: float                  # mean over learns and shards
    loss_scale: float            # the same mean of |critic| + |actor|
    per_learn: List[List[tuple]]
    # per shard, per learn: (leaves drawn, priorities written there)


def descend(pri: np.ndarray, u: np.ndarray) -> np.ndarray:
    """Inverse-CDF draw: the first leaf whose prefix sum reaches
    ``u * total``, with the residual formed in float32 as the tree does."""
    cum = np.cumsum(pri, dtype=np.float64)
    total = np.float32(cum[-1])
    uc = np.clip(u.astype(np.float32), np.float32(1e-12),
                 np.float32(1.0 - 1e-7))
    residual = (uc * total).astype(np.float32).astype(np.float64)
    idx = np.searchsorted(cum, residual, side="left")
    return np.minimum(idx, pri.shape[0] - 1)


def follow_first_iteration(config: dict, seed: int, rows: List[Dict],
                           n_envs_local: int, batch_local: int, learns: int,
                           dtype=jnp.float32, device=None) -> Followed:
    """Follow the first iteration's ``learns`` updates over ``len(rows)``
    shards.  ``rows[d]`` holds shard d's buffer (numpy, capacity rows)."""
    device = device or jax.devices("cpu")[0]
    n_shards = len(rows)
    cap = next(iter(rows[0].values())).shape[0]
    alpha, eps, beta = config["per_alpha"], config["per_eps"], config["per_beta"]
    loss_fn = algorithms.of(config).loss(config)
    precision = "highest" if dtype == jnp.float32 else "default"
    cast = lambda t: jax.tree.map(lambda x: jnp.asarray(x, dtype), t)

    with jax.default_device(device), jax.default_matmul_precision(precision):
        params = cast(init_params(config, seed))
        target = jax.tree.map(jnp.copy, params)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        adam = jax.jit(lambda g, m, v, p, c: _adam(config, g, m, v, p, c))
        ema = jax.jit(lambda t, p: jax.tree.map(
            lambda a, b: (a * (1 - config["tau"]) + b * config["tau"]
                          ).astype(dtype), t, p))
        keys = [sample_keys(seed, d, learns) for d in range(n_shards)]
        # every stored row carries P_max = 1; the iteration's in-flight
        # slots (the FIFO head after a full fill is 0) are zeroed
        pri = [np.ones((cap,), np.float32) for _ in range(n_shards)]
        for p in pri:
            p[:n_envs_local] = 0.0
        losses, scales, written = [], [], [[] for _ in range(n_shards)]
        for i in range(learns):
            total = sum(float(np.float32(p.sum(dtype=np.float64))) for p in pri)
            count = float(cap * n_shards)
            draws = []
            for d in range(n_shards):
                u = np.asarray(jax.random.uniform(keys[d][i], (batch_local,)))
                idx = descend(pri[d], u)
                p = pri[d][idx].astype(np.float64)
                w = (count * np.maximum(p / max(total, 1e-12), 1e-12)) ** -beta
                draws.append((idx, np.where(p > 0, w, 0.0)))
            w_max = max(float(w.max()) for _, w in draws)
            grads_sum, shard_loss, shard_scale = None, [], []
            for d, (idx, w) in enumerate(draws):
                b = {k: np.asarray(val[idx]) for k, val in rows[d].items()}
                b = {k: (cast(x) if np.issubdtype(x.dtype, np.floating)
                         else jnp.asarray(x)) for k, x in b.items()}
                wd = cast(w / max(w_max, 1e-12))
                (loss, (td, scale)), g = grad_fn(params, target, b, wd)
                shard_loss.append(float(loss))
                shard_scale.append(float(scale))
                grads_sum = g if grads_sum is None else jax.tree.map(
                    jnp.add, grads_sum, g)
                td = np.asarray(td, np.float64)
                cur = pri[d][idx]
                new = np.where(cur > 0, (np.abs(td) + eps) ** alpha, 0.0)
                pri[d][idx] = new.astype(np.float32)   # last writer wins
                written[d].append((idx, new.astype(np.float32)))
            grads = jax.tree.map(lambda g: (g / n_shards).astype(dtype),
                                 grads_sum)
            params, m, v = adam(grads, m, v, params, float(i + 1))
            target = ema(target, params)
            losses.append(float(np.mean(shard_loss)))
            scales.append(float(np.mean(shard_scale)))
    return Followed(loss=float(np.mean(losses)),
                    loss_scale=float(np.mean(scales)), per_learn=written)
