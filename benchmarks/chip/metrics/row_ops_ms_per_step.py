"""Device time of the owner's row operations, per traced step, in ms,
averaged over the cell's chips: row allocation (``recis.idmap.alloc``), the
new rows' initial values (``recis.blocks.init_rows``), the rows read from
Blocks (``recis.blocks.gather``) and SparseAdam on the touched rows
(``recis.sparse.adam``)."""
import stage_time

STAGES = ("recis.idmap.alloc", "recis.blocks.init_rows", "recis.blocks.gather",
          "recis.sparse.adam")


def read(ctx):
    return stage_time.ms_per_step(ctx, STAGES)
