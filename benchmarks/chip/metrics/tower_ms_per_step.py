"""Device time of the towers, forward and backward, per traced step, in ms,
averaged over the cell's chips: pooling (``recis.embed.pool``), the dense
model with its loss (``recis.tower``) and AdamW (``recis.dense.adamw``)."""
import stage_time

STAGES = ("recis.embed.pool", "recis.tower", "recis.dense.adamw")


def read(ctx):
    return stage_time.ms_per_step(ctx, STAGES)
