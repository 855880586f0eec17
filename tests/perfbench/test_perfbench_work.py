"""Peaks table and the work counts behind train_mfu and
sample_gather_roofline, against hand counts at small sizes."""

import pytest

from perfbench import algorithms, peaks, work
from perfbench.algorithms import _mlp
from perfbench.spec import resolve

DQN = {"algorithm": "dqn", "double_q": True, "obs_dim": 4, "num_actions": 2,
       "hidden_sizes": [8, 8]}
DDPG = {"algorithm": "ddpg", "obs_dim": 3, "action_dim": 1,
        "hidden_sizes": [8, 8]}


def test_peaks_v5e_row_and_unknown_kind_raises():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9,
                                                             16e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_mlp_and_train_flops_by_hand():
    dqn, ddpg = algorithms.load("dqn"), algorithms.load("ddpg")
    # 4->8->8->2: 2*(32 + 64 + 16) = 224 per row
    assert _mlp.flops((4, 8, 8, 2)) == 224
    assert dqn.act_flops_per_row(DQN) == 224
    # double DQN: target + online on next_obs, online fwd + bwd on obs
    assert dqn.learn_flops_per_row(DQN) == 5 * 224
    assert dqn.learn_flops_per_row(dict(DQN, double_q=False)) == 4 * 224
    # 3 envs acting, 2 learns of batch 5
    assert work.train_flops_per_iteration(DQN, 3, 5, 2) == 3 * 224 + 2 * 5 * 1120
    # DDPG: policy 3->8->8->1 = 2*(24+64+8) = 192; critic 4->8->8->1 = 208
    assert ddpg.act_flops_per_row(DDPG) == 192
    assert ddpg.learn_flops_per_row(DDPG) == 4 * 192 + 6 * 208


def test_row_bytes_and_tree_levels():
    assert work.row_bytes(DQN) == 4 * (4 + 1 + 1 + 4 + 1)
    assert work.row_bytes(DDPG) == 4 * (3 + 1 + 1 + 3 + 1)
    assert work.tree_levels(2 ** 20, 128) == 3
    assert work.tree_levels(128, 128) == 1
    assert work.tree_levels(129, 128) == 2
    assert work.tree_levels(2 ** 14, 128) == 2


# each cell's counts as the harness computed them before the families
# moved into perfbench/algorithms/: (learns per iteration, FLOPs per
# iteration, row bytes)
BEFORE_THE_MOVE = {
    "dqn_cartpole.ratio2.pallas": (8, 276873216, 44),
    "ddpg_pendulum.ratio1.xla": (16, 5467308032, 36),
    "dqn_cartpole.ratio2.xla.4chip": (8, 1107492864, 44),
    "dqn_cartpole.ratio2.xla": (8, 276873216, 44),
}


@pytest.mark.parametrize("workload", sorted(BEFORE_THE_MOVE))
def test_cell_work_counts_unchanged_by_the_move(workload):
    learns, flops, nbytes = BEFORE_THE_MOVE[workload]
    cell = resolve(workload)
    t = cell.traffic
    assert work.train_flops_per_iteration(
        cell.config, t["n_envs"], t["batch_size"], learns) == flops
    assert work.row_bytes(cell.config) == nbytes


def test_sample_gather_work_and_roofline_bound():
    flops, nbytes = work.sample_gather_work(batch=2, levels=3, fanout=4,
                                            row_bytes_=44)
    assert flops == 2 * 3 * 4 * 2
    assert nbytes == 2 * (4 + 3 * 4 * 4 + 2 * 44 + 8)
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_time(flops, nbytes, p)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    t, bound = work.least_time(1e15, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)
