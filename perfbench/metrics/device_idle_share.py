"""device_idle_share (%): 1 - (union of device-op intervals) / traced
window, the mean over the cell's chips."""


def read(ctx):
    reduced = ctx["reduced"]
    if not reduced.devices or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced.busy_s_mean / ctx["window_s"])
