"""The one traffic generator: a mix file of parameters → host batches.

A mix (``traffic/<mix>.json``) gives:

* ``ids``: ``"zipf"`` (ranks drawn with P(k) ∝ k^-``zipf_exponent`` over
  ``1..vocab``) or ``"uniform"`` (ranks uniform over ``1..vocab``);
* ``vocab``: ids per table, a number or ``"rows_per_table_per_chip_x_chips"``,
  the configuration's table share times the chips of the cell, so that the
  table never overflows however many steps a run makes;
* ``dense``: ``"normal"``, N(0, 1) raw features; ``label_p``: P(label = 1);
* ``ring_batches``: how many distinct batches are made in set-up; the
  window cycles through them;
* ``check_steps``: the first batches, which set-up trains and the
  comparison with the reference replays.

Ranks become 63-bit raw ids by a hash salted by the seed and the table, so
the hot ids of every table and every seed differ. Every seed gets the same
sizes; only the draws differ.
"""
from __future__ import annotations

import numpy as np

_MIX = np.uint64(0x9E3779B97F4A7C15)


def _mix64(x: np.ndarray) -> np.ndarray:
    z = x.astype(np.uint64) + _MIX
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def vocab_of(mix: dict, cfg: dict, chips: int) -> int:
    v = mix["vocab"]
    if v == "rows_per_table_per_chip_x_chips":
        return int(cfg["rows_per_table_per_chip"]) * chips
    return int(v)


class Ranks:
    """Draws ranks 1..vocab from the mix's distribution."""

    def __init__(self, mix: dict, vocab: int):
        self.vocab = vocab
        self.kind = mix["ids"]
        if self.kind == "zipf":
            p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(mix["zipf_exponent"])
            self.cdf = np.cumsum(p / p.sum())
            self.cdf[-1] = 1.0
        elif self.kind != "uniform":
            raise ValueError(f"unknown id distribution {self.kind!r}")

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        if self.kind == "uniform":
            return rng.integers(1, self.vocab + 1, size=n, dtype=np.int64)
        return np.searchsorted(self.cdf, rng.random(n), side="right").astype(np.int64) + 1


def _fnv1a64(name: str) -> int:
    h = 1469598103934665603
    for ch in name.encode():
        h = ((h ^ ch) * 1099511628211) & ((1 << 64) - 1)
    return h


def raw_ids(ranks: np.ndarray, seed: int, table: str) -> np.ndarray:
    """63-bit raw ids of ``ranks`` for one table under one seed."""
    salt = _mix64(np.array([seed % (1 << 64)], np.uint64))[0] ^ np.uint64(_fnv1a64(table))
    return (_mix64(ranks.astype(np.uint64) ^ salt) >> np.uint64(1)).astype(np.int64)


def make_ring(mix: dict, cfg: dict, chips: int, columns: list[dict], seed: int) -> list[dict]:
    """``ring_batches`` host batches.

    ``columns`` describe the program's batch: ``name``, ``values`` length,
    ``splits`` length, ``ids`` (bool), ``table`` (for id columns), over
    ``chips`` equal shards laid end to end. Each batch is
    ``{name: (values, row_splits)}``; ``_ranks`` holds each id column's ranks.
    """
    rng = np.random.default_rng(seed % (1 << 63))
    ranks = Ranks(mix, vocab_of(mix, cfg, chips))
    ring = []
    for _ in range(int(mix["ring_batches"])):
        batch, batch_ranks = {}, {}
        for c in columns:
            n, s = c["values"], c["splits"]
            b_loc = s // chips - 1
            k = n // (chips * b_loc)
            splits = np.tile(np.arange(b_loc + 1, dtype=np.int32) * k, chips)
            if c["ids"]:
                r = ranks.draw(rng, n)
                batch_ranks[c["name"]] = r
                vals = raw_ids(r, seed, c["table"])
            elif c["name"] == "label":
                vals = (rng.random(n) < float(mix["label_p"])).astype(np.float32)
            elif mix["dense"] == "normal":
                vals = rng.standard_normal(n, dtype=np.float32)
            else:
                raise ValueError(f"unknown dense distribution {mix['dense']!r}")
            batch[c["name"]] = (vals, splits)
        batch["_ranks"] = batch_ranks
        ring.append(batch)
    return ring


def unique_per_group(batch: dict, groups: dict[str, list[tuple[str, str]]]) -> dict[str, int]:
    """Distinct (table, id) pairs of one batch in each dim-group."""
    out = {}
    for g, cols in groups.items():
        by_table: dict[str, list] = {}
        for name, table in cols:
            by_table.setdefault(table, []).append(batch["_ranks"][name])
        out[g] = sum(np.unique(np.concatenate(r)).size for r in by_table.values())
    return out
