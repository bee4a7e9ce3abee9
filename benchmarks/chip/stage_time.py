"""Device time of the program's named stages, for the per-layer metrics
that read ``trace.reduce``'s ``stage_s``."""
from __future__ import annotations


def ms_per_step(ctx, stages: tuple[str, ...]) -> float | None:
    """Device time of ``stages``, forward and backward, averaged over the
    cell's chips, per traced step, in ms; None when the trace names none of
    them."""
    t = ctx.trace
    if t is None or not ctx.steps or not any(s in t["stage_s"] for s in stages):
        return None
    return 1e3 * sum(sum(t["stage_s"].get(s, {}).values()) for s in stages) / ctx.steps
