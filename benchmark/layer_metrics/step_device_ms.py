"""Step program (``core/train_loop.py``): device busy time per training
step, from the profiler trace of the traced sub-window (union of the
chip's ``XLA Ops`` intervals, averaged over chips, over the steps the
benchmark counted between the two syncs)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("steps"):
        return None
    return 1e3 * trace["busy_s"] / trace["steps"]
