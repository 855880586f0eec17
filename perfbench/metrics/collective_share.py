"""collective_share (%): device time of all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all ops over device busy
time, summed over the chips."""


def read(ctx):
    reduced = ctx["reduced"]
    coll, busy = reduced.collective_s(), reduced.busy_s_total()
    if coll <= 0 or busy <= 0:
        return None
    return 100.0 * coll / busy
