"""sample_gather_roofline (%): the least time the chip needs for the
draws one call of the fused sample+gather kernel serves (the larger of
required FLOPs over peak FLOP/s and required bytes over peak HBM
bandwidth; perfbench/work.py), times the calls, over the kernel's
summed device time in the trace."""


def read(ctx):
    reduced, work, c = ctx["reduced"], ctx["work"], ctx["config"]
    calls = reduced.kernel_calls("sample_gather")
    spent = reduced.kernel_s("sample_gather")
    if calls == 0 or spent <= 0:
        return None
    batch = ctx["traffic"]["batch_size"] // ctx["chips"]
    flops, nbytes = work.sample_gather_work(
        batch, work.tree_levels(c["replay_capacity"], c["fanout"]),
        c["fanout"], work.row_bytes(c))
    least, _ = work.least_time(flops, nbytes, ctx["peaks"])
    return 100.0 * least * calls / spent
