"""Recommendation cells (``repro.launch.recsys_cell``).

The train shape is the global batch, and every table is cut to the
configuration's share of rows per chip; the cell keeps its engine as
``cell.engine`` and takes the engine's own batch format, {column: Ragged};
the towers are the model module's ``init``.
"""
from __future__ import annotations

import harness

CONFIG_KEYS = ("global_batch", "rows_per_table_per_chip")


def shape(cfg: dict) -> dict:
    return {"batch": int(cfg["global_batch"])}


def build(make):
    cell = make()
    return cell, cell.engine


def init_dense(cell):
    from repro.launch.recsys_cell import _model_mod

    model = _model_mod(cell.arch.arch_id)
    return lambda key: model.init(key, cell.arch.model)


def columns(cell, engine) -> list[dict]:
    return harness.ragged_columns(cell.batch_specs, engine)


def place(cell, host: dict, sharding):
    return harness.place_ragged(host, sharding)
