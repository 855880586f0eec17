"""train_mfu (%): the FLOPs the agent's forward and backward passes
require per loop iteration (perfbench/work.py), times iterations per
second over the traced window, over chips x the bf16 peak."""


def read(ctx):
    t, work = ctx["traffic"], ctx["work"]
    if ctx["window_s"] <= 0 or not ctx["iterations"]:
        return None
    flops = work.train_flops_per_iteration(
        ctx["config"], t["n_envs"], t["batch_size"], ctx["learns"])
    rate = flops * ctx["iterations"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"].bf16_flops)
