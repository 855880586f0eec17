"""DDPG over an MLP policy and critic on a continuous classic-control
env: the ``ddpg_*`` configurations."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.algorithms import _classic, _mlp

fill = _classic.fill
tiny = _classic.tiny
check_config = _classic.check_config


def build(config: dict, traffic: dict, opt):
    from repro.agents.ddpg import DDPGConfig, make_ddpg

    env_fn = _classic.env_fn(config)
    spec, _, _ = env_fn(1)
    if (spec.obs_dim, spec.action_dim, spec.action_low,
            spec.action_high) != (config["obs_dim"], config["action_dim"],
                                  config["action_low"], config["action_high"]):
        raise ValueError(f"env {config['env']} does not match the config")
    agent = make_ddpg(spec, DDPGConfig(hidden=tuple(config["hidden_sizes"]),
                                       gamma=config["gamma"],
                                       tau=config["tau"],
                                       expl_noise=config["expl_noise"],
                                       opt=opt))
    return agent, env_fn, _classic.example(spec)


def _pi_sizes(config: dict):
    return (config["obs_dim"], *config["hidden_sizes"], config["action_dim"])


def _critic_sizes(config: dict):
    return (config["obs_dim"] + config["action_dim"],
            *config["hidden_sizes"], 1)


def init_params(config: dict, key):
    """The agent key split again into policy and critic."""
    kp, kq = jax.random.split(key)
    return {"pi": _mlp.init(kp, _pi_sizes(config)),
            "q": _mlp.init(kq, _critic_sizes(config))}


def loss(config: dict):
    """Critic and actor loss summed, differentiated once; the actor term
    stops the critic's gradient."""
    gamma = config["gamma"]
    lo, hi = config["action_low"], config["action_high"]
    scale, mid = (hi - lo) / 2.0, (hi + lo) / 2.0

    def pi(p, obs):
        return jnp.tanh(_mlp.apply(p, obs)) * scale + mid

    def q(p, obs, act):
        return _mlp.apply(p, jnp.concatenate([obs, act], -1))[..., 0]

    def f(params, target, b, w):
        tgt = b["reward"] + gamma * (1 - b["done"]) * q(
            target["q"], b["next_obs"], pi(target["pi"], b["next_obs"]))
        td = q(params["q"], b["obs"], b["action"]) - jax.lax.stop_gradient(tgt)
        critic = jnp.mean(w * td * td)
        frozen = jax.lax.stop_gradient(params["q"])
        actor = -jnp.mean(q(frozen, b["obs"], pi(params["pi"], b["obs"])))
        return critic + actor, (td, jnp.abs(critic) + jnp.abs(actor))
    return f


def act_flops_per_row(config: dict) -> int:
    """One actor's action: the policy."""
    return _mlp.flops(_pi_sizes(config))


def learn_flops_per_row(config: dict) -> int:
    """Target policy and target critic on ``next_obs`` (Fp + Fq), the
    critic forward and backward (3 Fq), the actor term's policy forward
    and backward (3 Fp) and critic forward plus its gradient with respect
    to the action alone (2 Fq): 4 Fp + 6 Fq."""
    fp = _mlp.flops(_pi_sizes(config))
    fq = _mlp.flops(_critic_sizes(config))
    return 4 * fp + 6 * fq


def row_bytes(config: dict) -> int:
    return _classic.row_bytes(config, config["action_dim"])
