"""A toy algorithm family, added as files alone: DQN over a linear
Q-function on a small random walk written here.  It shares nothing with
the DQN and DDPG families but the program's Agent bundle, Adam and
replay."""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

STEP = jnp.array([0.1, 0.05, 0.025])


@dataclasses.dataclass(frozen=True)
class WalkSpec:
    name: str = "walk"
    obs_dim: int = 3
    action_dim: int = 2
    discrete: bool = True
    max_steps: int = 16


class WalkState(NamedTuple):
    x: jax.Array
    t: jax.Array


def _reset(key):
    x = jax.random.uniform(key, (3,), minval=-1.0, maxval=1.0)
    return WalkState(x, jnp.zeros((), jnp.int32)), x


def _step(s, a, key):
    x = 0.9 * s.x + (2.0 * a - 1.0) * STEP + 0.05 * jax.random.normal(key, (3,))
    t = s.t + 1
    done = (t >= 16) | (jnp.abs(x[0]) > 2.0)
    return WalkState(x, t), x, 1.0 - x[0] ** 2, done


def make_walk(n: int):
    """``n`` auto-resetting walks: ``(spec, v_reset, v_step)``."""
    def v_reset(key):
        return jax.vmap(_reset)(jax.random.split(key, n))

    def v_step(states, actions, key):
        st, obs, rew, done = jax.vmap(_step)(states, actions,
                                             jax.random.split(key, n))
        rst, robs = v_reset(jax.random.fold_in(key, 1))
        st = jax.tree.map(lambda a, b: jnp.where(
            done.reshape((n,) + (1,) * (a.ndim - 1)), b, a), st, rst)
        return st, jnp.where(done[:, None], robs, obs), rew, done, obs

    return WalkSpec(), v_reset, v_step


def _q(p, obs):
    return jnp.dot(obs, p["w"]) + p["b"]


def init_params(config, key):
    n_obs, n_act = config["obs_dim"], config["num_actions"]
    return {"w": 0.1 * jax.random.normal(key, (n_obs, n_act)),
            "b": jnp.zeros((n_act,))}


def loss(config):
    gamma = config["gamma"]

    def f(params, target, b, w):
        v_next = jnp.max(_q(target, b["next_obs"]), axis=-1)
        tgt = b["reward"] + gamma * (1.0 - b["done"]) * v_next
        q_sa = jnp.take_along_axis(_q(params, b["obs"]),
                                   b["action"][:, None], 1)[:, 0]
        td = q_sa - jax.lax.stop_gradient(tgt)
        value = jnp.mean(w * td * td)
        return value, (td, value)
    return f


def build(config, traffic, opt):
    """The program's agent: its own Q-function, weights and loss, written
    apart from the reference's ``init_params`` and ``loss``."""
    from repro.agents.base import Agent, AgentState
    from repro.optim import adam

    n_obs, n_act, gamma = (config["obs_dim"], config["num_actions"],
                           config["gamma"])

    def q(p, obs):
        return obs @ p["w"] + p["b"]

    def learn_loss(params, target, b, w):
        y = b["reward"] + gamma * (1.0 - b["done"]) * q(
            target, b["next_obs"]).max(axis=-1)
        q_sa = (q(params, b["obs"]) * jax.nn.one_hot(b["action"], n_act)
                ).sum(axis=-1)
        td = q_sa - jax.lax.stop_gradient(y)
        return (w * jnp.square(td)).mean(), td

    def init(key):
        params = {"w": jax.random.normal(key, (n_obs, n_act)) * 0.1,
                  "b": jnp.zeros((n_act,))}
        return AgentState(params, jax.tree.map(jnp.copy, params),
                          adam.init(params, opt), jnp.zeros((), jnp.int32))

    def act(state, obs, rng, epsilon=0.0):
        greedy = jnp.argmax(q(state.params, obs), axis=-1)
        rnd = jax.random.randint(rng, greedy.shape, 0, n_act)
        explore = jax.random.uniform(jax.random.fold_in(rng, 1),
                                     greedy.shape) < epsilon
        return jnp.where(explore, rnd, greedy)

    def learn(state, batch, is_w):
        (value, td), grads = jax.value_and_grad(learn_loss, has_aux=True)(
            state.params, state.target, batch, is_w)
        params, opt_state, gnorm = adam.update(grads, state.opt, state.params,
                                               opt)
        target = adam.ema_update(state.target, params, config["tau"])
        return (AgentState(params, target, opt_state, state.step + 1),
                {"loss": value, "grad_norm": gnorm}, jnp.abs(td))

    spec, _, _ = make_walk(1)
    if (spec.obs_dim, spec.action_dim) != (config["obs_dim"],
                                           config["num_actions"]):
        raise ValueError("the walk does not match the config")
    example = {"obs": jnp.zeros((3,), jnp.float32),
               "action": jnp.zeros((), jnp.int32),
               "reward": jnp.zeros((), jnp.float32),
               "next_obs": jnp.zeros((3,), jnp.float32),
               "done": jnp.zeros((), jnp.float32)}
    return Agent(name="linq", init=init, act=act, learn=learn), make_walk, \
        example


def fill(config, n):
    _, v_reset, v_step = make_walk(n)

    def step(carry, key):
        st, obs = carry
        ka, ke = jax.random.split(key)
        a = jax.random.randint(ka, (n,), 0, config["num_actions"])
        st, obs_next, rew, done, true_next = v_step(st, a, ke)
        row = {"obs": obs, "action": a, "reward": rew, "next_obs": true_next,
               "done": done.astype(jnp.float32)}
        return (st, obs_next), row

    return v_reset, step


def act_flops_per_row(config):
    return 2 * config["obs_dim"] * config["num_actions"]


def learn_flops_per_row(config):
    """Target on ``next_obs``, online forward and backward on ``obs``."""
    return 4 * act_flops_per_row(config)


def row_bytes(config):
    return 4 * (2 * config["obs_dim"] + 3)


def tiny(config, traffic):
    return config, traffic


def check_config(config):
    if config["num_actions"] != 2:
        raise ValueError("the walk has two actions")
