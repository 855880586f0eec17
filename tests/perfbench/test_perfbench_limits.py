"""Each one-chip cell's ``prio_gap`` limit lies between what one MXU
pass reads and what the bfloat16 control reads.

On a TPU a float32 matmul at default precision is one pass of the
matrix unit: operands rounded to bfloat16, products accumulated in
float32.  The reference run that way stands in for the program's first
update here; the control is the whole reference in bfloat16.  Both are
read against the float32 reference at ``highest`` on the same rows,
synthetic ones over the env's ranges."""

import contextlib

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import check, reference
from perfbench.algorithms import _mlp
from perfbench.spec import resolve

CAPACITY = 2 ** 14


def mlp_one_pass(params, x):
    for i, layer in enumerate(params):
        x = jnp.dot(x.astype(jnp.bfloat16), layer["w"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32) + layer["b"]
        if i < len(params) - 1:
            x = jnp.maximum(x, 0)
    return x


@contextlib.contextmanager
def one_mxu_pass():
    plain = _mlp.apply
    _mlp.apply = mlp_one_pass
    try:
        yield
    finally:
        _mlp.apply = plain


def synthetic_rows(config, seed):
    r = np.random.default_rng(seed)
    n = CAPACITY
    if config["algorithm"] == "ddpg":
        def obs():
            th, thd = r.uniform(-np.pi, np.pi, n), r.uniform(-8, 8, n)
            return np.stack([np.cos(th), np.sin(th), thd], 1).astype(np.float32)
        o, no = obs(), obs()
        act = r.uniform(-2, 2, (n, 1)).astype(np.float32)
        rew = -r.uniform(0, 16.3, n).astype(np.float32)
    else:
        o = r.uniform(-0.2, 0.2, (n, 4)).astype(np.float32)
        no = o + r.normal(0, 0.05, (n, 4)).astype(np.float32)
        act = r.integers(0, 2, n).astype(np.int32)
        rew = np.ones(n, np.float32)
    done = (r.uniform(size=n) < 0.05).astype(np.float32)
    return [{"obs": o, "action": act, "reward": rew, "next_obs": no,
             "done": done}]


def first_update_gap(followed, ref):
    probe = check.probe_of(followed, CAPACITY, {})
    return float(np.quantile(check.first_update_gaps(probe, ref), 0.75))


@pytest.mark.parametrize("workload", ["dqn_cartpole.ratio2.xla",
                                      "ddpg_pendulum.ratio1.xla"])
def test_prio_gap_limit_between_one_pass_and_control(workload):
    cell = resolve(workload)
    limit = cell.limits["prio_gap"]
    t = cell.traffic
    for seed in (2 ** 31 + 101, 2 ** 31 + 102):
        rows = synthetic_rows(cell.config, seed)
        follow = lambda **kw: reference.follow_first_iteration(
            cell.config, seed, rows, t["n_envs"], t["batch_size"], 1, **kw)
        ref = follow()
        with one_mxu_pass():
            one_pass = first_update_gap(follow(), ref)
        control = first_update_gap(follow(dtype=jnp.bfloat16), ref)
        assert one_pass < limit < control, (one_pass, limit, control)
