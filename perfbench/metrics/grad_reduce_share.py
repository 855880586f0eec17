"""grad_reduce_share (%): device self time of the ops in the step's
``grad_reduce`` phase (the cross-shard gradient reduce) over the busy
self time, summed over the chips (perfbench/phase_time.py)."""

from perfbench import phase_time


def read(ctx):
    return phase_time.share(ctx, ("grad_reduce",))
