"""95th percentile, by nearest rank, of the intervals between successive
batch pulls by the Trainer over the window: each holds placing one batch
and running its step. With fewer than 20 steps it is the slowest step."""
import math


def read(ctx):
    xs = sorted(ctx.intervals)
    if not xs:
        return None
    return float(xs[math.ceil(0.95 * len(xs)) - 1])
