"""Operations and bytes the algorithm needs, counted from shapes.

These are the yardstick of ``train_mfu`` and ``sample_gather_roofline``:
the work the agent and the sampler *require*, whatever the program does
to get it done.  Each algorithm family counts its own networks and rows
(``perfbench/algorithms/``): only matrix-multiplication FLOPs (bias
adds, activations and the optimizer's elementwise update are under 1%
of them at these widths), a backward pass twice its forward.
"""

from __future__ import annotations

from perfbench import algorithms


def train_flops_per_iteration(config: dict, n_envs: int, batch: int,
                              learns: int) -> int:
    """Global FLOPs of one loop iteration: ``n_envs`` actions and
    ``learns`` updates on ``batch`` rows each (global counts), by the
    configuration's algorithm family."""
    family = algorithms.of(config)
    return (n_envs * family.act_flops_per_row(config)
            + learns * batch * family.learn_flops_per_row(config))


def row_bytes(config: dict) -> int:
    """Bytes of one stored row, by the configuration's algorithm family."""
    return algorithms.of(config).row_bytes(config)


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
