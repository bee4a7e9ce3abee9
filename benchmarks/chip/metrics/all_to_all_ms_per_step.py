"""Device time of the trace's all-to-all ops (by ``trace.op_kind``), averaged
over the cell's chips, per traced step, in ms. Nothing when the trace holds
none."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.steps or "all-to-all" not in t["kind_s"]:
        return None
    return 1e3 * t["kind_s"]["all-to-all"] / ctx.steps
