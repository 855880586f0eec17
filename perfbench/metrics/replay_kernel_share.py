"""replay_kernel_share (%): device time of the replay kernels
(pallas_calls sumtree_update, sumtree_sample, sample_gather,
gather_rows) over device busy time, summed over the chips."""

from perfbench.trace_reduce import REPLAY_KERNELS


def read(ctx):
    reduced = ctx["reduced"]
    kernel_s = sum(reduced.kernel_s(k) for k in REPLAY_KERNELS)
    busy = reduced.busy_s_total()
    if kernel_s <= 0 or busy <= 0:
        return None
    return 100.0 * kernel_s / busy
