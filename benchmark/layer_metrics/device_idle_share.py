"""Device: 1 - (union of the device's operation intervals, averaged
over chips) / traced window, from the profiler trace."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
