"""The dense MLP of the DQN and DDPG families: the reference's weights
and forward pass, written out with plain ``jnp``, and its FLOPs."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, sizes):
    ks = jax.random.split(key, len(sizes) - 1)
    return [{"w": jax.random.normal(ks[i], (a, b)) * (2.0 / (a + b)) ** 0.5,
             "b": jnp.zeros((b,))}
            for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))]


def apply(params, x):
    for i, layer in enumerate(params):
        x = jnp.dot(x, layer["w"]) + layer["b"]
        if i < len(params) - 1:
            x = jnp.maximum(x, 0)
    return x


def flops(sizes) -> int:
    """FLOPs of one row through a dense MLP with layer widths ``sizes``."""
    return 2 * sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
