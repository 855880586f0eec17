"""DQN (and double DQN with ``double_q``) over an MLP Q-network on a
discrete classic-control env: the ``dqn_*`` configurations."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.algorithms import _classic, _mlp

fill = _classic.fill
tiny = _classic.tiny
check_config = _classic.check_config


def build(config: dict, traffic: dict, opt):
    from repro.agents.dqn import DQNConfig, make_dqn

    env_fn = _classic.env_fn(config)
    spec, _, _ = env_fn(1)
    if (spec.obs_dim, spec.action_dim) != (config["obs_dim"],
                                           config["num_actions"]):
        raise ValueError(f"env {config['env']} does not match the config")
    agent = make_dqn(spec, DQNConfig(hidden=tuple(config["hidden_sizes"]),
                                     gamma=config["gamma"], tau=config["tau"],
                                     double_q=config["double_q"], opt=opt))
    return agent, env_fn, _classic.example(spec)


def _sizes(config: dict):
    return (config["obs_dim"], *config["hidden_sizes"], config["num_actions"])


def init_params(config: dict, key):
    return _mlp.init(key, _sizes(config))


def loss(config: dict):
    gamma = config["gamma"]

    def f(params, target, b, w):
        q_next_t = _mlp.apply(target, b["next_obs"])
        if config["double_q"]:
            sel = jnp.argmax(_mlp.apply(params, b["next_obs"]), axis=-1)
            v_next = jnp.take_along_axis(q_next_t, sel[:, None], 1)[:, 0]
        else:
            v_next = jnp.max(q_next_t, axis=-1)
        tgt = b["reward"] + gamma * (1.0 - b["done"]) * v_next
        q_all = _mlp.apply(params, b["obs"])
        q_sa = jnp.take_along_axis(q_all, b["action"][:, None], 1)[:, 0]
        td = q_sa - jax.lax.stop_gradient(tgt)
        value = jnp.mean(w * td * td)
        return value, (td, value)
    return f


def act_flops_per_row(config: dict) -> int:
    """One actor's action: the Q-net."""
    return _mlp.flops(_sizes(config))


def learn_flops_per_row(config: dict) -> int:
    """The target net and (double-Q) the online net on ``next_obs``, the
    online net forward and backward on ``obs``: (2 + double) F + 2F."""
    f = _mlp.flops(_sizes(config))
    return (3 if config["double_q"] else 2) * f + 2 * f


def row_bytes(config: dict) -> int:
    return _classic.row_bytes(config, 1)
