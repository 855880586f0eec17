"""learner_share (%): device self time of the ops in the step's
``learner_update`` phase (forward, backward, optimizer) and of the
learn ``cond``'s own ops (``learn``) over the busy self time, summed
over the chips (perfbench/phase_time.py)."""

from perfbench import phase_time


def read(ctx):
    return phase_time.share(ctx, ("learner_update", "learn"))
