"""What the DQN and DDPG families share: a classic-control env of the
program (``envs/classic.py``), its transition row, a uniformly random
policy over it for the fill, and the configuration files' sizes."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp


def env_fn(config: dict):
    from repro.envs.classic import make_vec

    return functools.partial(make_vec, config["env"])


def _action(spec):
    """(shape, dtype) of one stored action."""
    if spec.discrete:
        return (), jnp.int32
    return (spec.action_dim,), jnp.float32


def example(spec) -> dict:
    shape, dtype = _action(spec)
    return {"obs": jnp.zeros((spec.obs_dim,), jnp.float32),
            "action": jnp.zeros(shape, dtype),
            "reward": jnp.zeros((), jnp.float32),
            "next_obs": jnp.zeros((spec.obs_dim,), jnp.float32),
            "done": jnp.zeros((), jnp.float32)}


def fill(config: dict, n: int):
    """``n`` envs stepped under a uniformly random policy; each step's
    row holds the true next observation (before an auto-reset)."""
    spec, v_reset, v_step = env_fn(config)(n)
    _, dtype = _action(spec)

    def actions(k):
        if spec.discrete:
            return jax.random.randint(k, (n,), 0, spec.action_dim)
        return jax.random.uniform(k, (n, spec.action_dim),
                                  minval=spec.action_low,
                                  maxval=spec.action_high)

    def step(carry, k):
        st, obs = carry
        ka, ke = jax.random.split(k)
        a = actions(ka)
        st, obs_next, rew, done, true_next = v_step(st, a, ke)
        row = {"obs": obs, "action": a.astype(dtype),
               "reward": rew.astype(jnp.float32), "next_obs": true_next,
               "done": done.astype(jnp.float32)}
        return (st, obs_next), row

    return v_reset, step


def row_bytes(config: dict, action_len: int) -> int:
    """Bytes of one stored transition (every leaf is 4-byte f32/int32)."""
    obs = config["obs_dim"]
    return 4 * (obs + action_len + 1 + obs + 1)


def tiny(config: dict, traffic: dict):
    """The published widths run on a CPU as they are."""
    return config, traffic


def check_config(config: dict):
    """The buffer the cells are sized to, and the two 256-wide hidden
    layers that both sources publish."""
    if config["replay_capacity"] != 2 ** 20:
        raise ValueError(f"{config['name']}: replay_capacity "
                         f"{config['replay_capacity']}, the cells hold 2^20")
    published = json.dumps(config["published"])
    if config["hidden_sizes"] != [256, 256] or not (
            "256, 256" in published or "256 -> 256" in published):
        raise ValueError(f"{config['name']}: hidden_sizes "
                         f"{config['hidden_sizes']}, published {published}")
