"""Device time of the exchange, both directions, per traced step, in ms,
averaged over the cell's chips: the ids' hash (``recis.ids.hash``), the
requester's dedupe and owner bucketing (``recis.exchange.bucket``), the
all-to-all (``recis.exchange.all_to_all``), the owner's merge
(``recis.exchange.owner_merge``) and the route of rows to their values and
of their gradients back (``recis.embed.route``, whose backward is the
scatter-add)."""
import stage_time

STAGES = ("recis.ids.hash", "recis.exchange.bucket", "recis.exchange.all_to_all",
          "recis.exchange.owner_merge", "recis.embed.route")


def read(ctx):
    return stage_time.ms_per_step(ctx, STAGES)
