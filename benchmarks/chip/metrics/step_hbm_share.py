"""The fewest HBM bytes a step needs on the sparse side, at the traced
window's steps/s, over chips x the chip's HBM bandwidth, in %.

Bytes of a step: for each dim-group, its distinct ids U x the row's bytes x
7 (one gather, then a read and a write of each of ``emb``, ``m``, ``v``),
plus 8 bytes for each id of the batch."""
import peaks


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.steps or t["window_s"] <= 0:
        return None
    per_step = [sum(u * ctx.row_bytes[g] * 7 for g, u in uniq.items()) + 8 * ctx.ids_per_step
                for uniq in ctx.unique_per_step]
    rate = sum(per_step) / t["window_s"]
    return 100.0 * rate / (ctx.chips * peaks.of(ctx.device_kind).hbm_bw)
