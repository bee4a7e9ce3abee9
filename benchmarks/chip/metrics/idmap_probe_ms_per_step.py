"""Device time of the IDMap's pass 1, the probe for the keys it holds
(``recis.idmap.probe``), per traced step, in ms, averaged over the cell's
chips."""
import stage_time


def read(ctx):
    return stage_time.ms_per_step(ctx, ("recis.idmap.probe",))
