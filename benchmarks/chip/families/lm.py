"""Language-model cells (``repro.launch.lm_cell``).

The train shape is the global batch and the sequence length. The traffic
makes the engine's batch format: one token column of B·T ids, T to a row;
the train cell takes the tokens as a (B, T) int32 array, so ``place`` lays
the column out as that array. The cell keeps its engine to itself, so
``build`` records the one engine that ``lm_cell._engine_for`` makes while
the cell is built: the engine whose tables and exchange the step runs.
"""
from __future__ import annotations

CONFIG_KEYS = ("global_batch", "seq_len")


def shape(cfg: dict) -> dict:
    return {"global_batch": int(cfg["global_batch"]), "seq_len": int(cfg["seq_len"])}


def build(make):
    from repro.launch import lm_cell

    made = []
    real = lm_cell._engine_for

    def record(*args, **kwargs):
        out = real(*args, **kwargs)
        made.append(out[0])
        return out

    lm_cell._engine_for = record
    try:
        cell = make()
    finally:
        lm_cell._engine_for = real
    if len(made) != 1:
        raise ValueError(f"the LM train cell built {len(made)} engines; the harness reads one")
    return cell, made[0]


def init_dense(cell):
    from repro.launch.common import mesh_info
    from repro.models import transformer as tfm

    ep = mesh_info(cell.mesh)["tp"]
    return lambda key: tfm.init(key, cell.arch.model, ep_size=ep)


def columns(cell, engine) -> list[dict]:
    (group,) = engine.groups.values()
    (tokens,) = group.features
    b, t = cell.batch_specs.shape
    return [{"name": tokens.name, "values": b * t, "splits": b + cell.mesh.size, "ids": True,
             "table": tokens.table_key()}]


def place(cell, host: dict, sharding):
    import jax
    import numpy as np

    (name,) = (n for n in host if not n.startswith("_"))
    values, _ = host[name]
    return jax.device_put(values.reshape(cell.batch_specs.shape).astype(np.int32), sharding)
