"""Device time of the IDMap's pass 2, the claim loop (``recis.idmap.claim``),
per traced step, in ms, averaged over the cell's chips."""
import stage_time


def read(ctx):
    return stage_time.ms_per_step(ctx, ("recis.idmap.claim",))
