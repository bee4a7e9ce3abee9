"""Tower FLOPs per example (3 x forward, the reference's own count) x
examples/s of the traced window, over chips x the chip's peak, in %."""
import peaks


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.steps or t["window_s"] <= 0:
        return None
    rate = ctx.steps * ctx.global_batch / t["window_s"]
    flops = 3 * ctx.forward_flops_per_example * rate
    return 100.0 * flops / (ctx.chips * peaks.of(ctx.device_kind).flops)
