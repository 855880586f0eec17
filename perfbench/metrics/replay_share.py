"""replay_share (%): device self time of the ops in the step's replay
phases (``insert_begin``, ``flush``, ``sample``, ``write_back``,
``insert_commit``) over the busy self time, summed over the chips, on
every tree backend (perfbench/phase_time.py)."""

from perfbench import phase_time

REPLAY_PHASES = ("insert_begin", "flush", "sample", "write_back",
                 "insert_commit")


def read(ctx):
    return phase_time.share(ctx, REPLAY_PHASES)
