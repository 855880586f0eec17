"""act_share (%): device self time of the ops in the step's ``act``
phase (the rng split, epsilon and the actors' env step) over the busy
self time, summed over the chips (perfbench/phase_time.py)."""

from perfbench import phase_time


def read(ctx):
    return phase_time.share(ctx, ("act",))
