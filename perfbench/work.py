"""Operations and bytes the algorithm needs, counted from shapes.

These are the yardstick of ``train_mfu`` and ``sample_gather_roofline``:
the work the agent and the sampler *require*, whatever the program does
to get it done.  Only matrix-multiplication FLOPs are counted for the
networks (bias adds, activations and the optimizer's elementwise update
are under 1% of them at these widths); a backward pass counts twice its
forward.
"""

from __future__ import annotations


def mlp_flops(sizes) -> int:
    """FLOPs of one row through a dense MLP with layer widths ``sizes``."""
    return 2 * sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def q_sizes(config: dict):
    return (config["obs_dim"], *config["hidden_sizes"], config["num_actions"])


def pi_sizes(config: dict):
    return (config["obs_dim"], *config["hidden_sizes"], config["action_dim"])


def critic_sizes(config: dict):
    return (config["obs_dim"] + config["action_dim"], *config["hidden_sizes"], 1)


def act_flops_per_row(config: dict) -> int:
    """One actor's action: the Q-net (DQN) or the policy (DDPG)."""
    if config["algorithm"] == "ddpg":
        return mlp_flops(pi_sizes(config))
    return mlp_flops(q_sizes(config))


def learn_flops_per_row(config: dict) -> int:
    """One sampled row through one learner update.

    DQN: the target net and (double-Q) the online net on ``next_obs``,
    the online net forward and backward on ``obs``: (2 + double) F + 2F.
    DDPG: target policy and target critic on ``next_obs`` (Fp + Fq), the
    critic forward and backward (3 Fq), the actor term's policy forward
    and backward (3 Fp) and critic forward plus its gradient with respect
    to the action alone (2 Fq): 4 Fp + 6 Fq.
    """
    if config["algorithm"] == "ddpg":
        fp = mlp_flops(pi_sizes(config))
        fq = mlp_flops(critic_sizes(config))
        return 4 * fp + 6 * fq
    f = mlp_flops(q_sizes(config))
    return (3 if config["double_q"] else 2) * f + 2 * f


def train_flops_per_iteration(config: dict, n_envs: int, batch: int,
                              learns: int) -> int:
    """Global FLOPs of one loop iteration: ``n_envs`` actions and
    ``learns`` updates on ``batch`` rows each (global counts)."""
    return (n_envs * act_flops_per_row(config)
            + learns * batch * learn_flops_per_row(config))


def row_bytes(config: dict) -> int:
    """Bytes of one stored transition (every leaf is 4-byte f32/int32)."""
    obs = config["obs_dim"]
    act = 1 if config["algorithm"] != "ddpg" else config["action_dim"]
    return 4 * (obs + act + 1 + obs + 1)


def tree_levels(capacity: int, fanout: int) -> int:
    """Levels one descent reads below the padded root of a K-ary tree."""
    levels, nodes = 1, -(-capacity // fanout) * fanout
    while nodes > fanout:
        nodes = -(-(nodes // fanout) // fanout) * fanout
        levels += 1
    return levels


def sample_gather_work(batch: int, levels: int, fanout: int,
                       row_bytes_: int):
    """(FLOPs, bytes) that ``batch`` prioritized draws require.

    Per draw: at each of ``levels`` levels one row of ``fanout`` children
    is read and prefix-summed against the residual (one add and one
    compare per child); then one storage row is read and written out,
    and the draw's uniform is read and its index and priority written.
    """
    flops = batch * levels * fanout * 2
    nbytes = batch * (4 + levels * fanout * 4 + 2 * row_bytes_ + 8)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks):
    """(seconds, bound) of the roofline: the larger of compute and
    memory time, and which of the two it is."""
    t_c = flops / peaks.bf16_flops
    t_m = nbytes / peaks.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
