"""dispatch_share (%): the host's time in ``Executor.run_chunk`` (the
summed length of the host plane's ``run_chunk`` spans) over the traced
window."""


def read(ctx):
    spans = [end - start for name, start, end in ctx["reduced"].host_events
             if name == "run_chunk"]
    if not spans or ctx["window_s"] <= 0:
        return None
    return 100.0 * sum(spans) / 1e9 / ctx["window_s"]
