"""Embedding Engine — the RecIS core (§2.1, §2.2.2), unified sparse side.

Responsibilities:
  * **Parameter Aggregation** — every feature with the same embedding dim is
    merged into one logical table (a dim-group). Features are kept
    conflict-free inside the merged table by salting: the engine key is
    ``hash_combine(raw_id, table_salt)``; features sharing
    ``FeatureSpec.shared_table`` share a salt and therefore rows.
  * **Request Merging** — a dim-group's lookups from all feature columns are
    concatenated into one exchange (`core/exchange.py`), so the device sees
    ~one fused lookup per *dimension*, not per column (paper: memory
    coalescing by dim; "vast majority of features employ identical dims").
  * **Two-tier storage** — per device shard: IDMap (tier 1) + Blocks
    (tier 2), stacked with a leading device axis for shard_map.
  * **Pooling** — sum / mean / none (sequence) / tile, per feature, via
    segment reduction (Pallas kernel optional — kernels/segment_reduce).

The engine is deliberately split into a non-differentiable `fetch` (routing,
IDMap insert, row gather → compact ``rows_r``) and a differentiable,
*linear* `activations` so that `jax.grad` w.r.t. ``rows_r`` yields exactly
the paper's compact row-gradient, which `update` applies with SparseAdam.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blocks as blocks_lib
from repro.core import exchange
from repro.core import idmap as idmap_lib
from repro.core import write_log
from repro.core.feature_engine import FeatureSpec, hash_combine, splitmix64
from repro.io.ragged import Ragged
from repro.obs.stages import stage
from repro.optim.sparse_adam import SparseAdamConfig, apply_row_updates
from repro.storage.tiered import StorageConfig, TieredEmbeddingStore

PAD = jnp.int64(-1)

# per-chip metrics that are maxima, not counts: ``idmap_rounds`` is each
# probe pass's budget on every chip, the ``*_rounds`` the rounds a pass ran,
# and the depths the deepest round used
MAX_METRICS = ("idmap_rounds", "idmap_probe_rounds", "idmap_claim_rounds",
               "idmap_probe_depth", "idmap_claim_depth")


def reduce_metrics(metrics: Mapping[str, jax.Array], axes) -> dict:
    """The engine's metrics across chips, inside shard_map: ``MAX_METRICS``
    (under any ``<group>/`` prefix) by ``pmax``, every count by ``psum``."""
    maxed = {k: v for k, v in metrics.items() if k.rsplit("/", 1)[-1] in MAX_METRICS}
    summed = {k: v for k, v in metrics.items() if k not in maxed}
    return {**jax.lax.psum(summed, axes), **jax.lax.pmax(maxed, axes)}


def _stable_salt(name: str) -> int:
    """Deterministic 63-bit salt from a table name (no Python hash())."""
    h = 1469598103934665603
    for ch in name.encode():  # FNV-1a, 64-bit wraparound
        h = ((h ^ ch) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h & 0x7FFFFFFFFFFFFFFF


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    """Static description of one merged dim-group."""

    dim: int
    features: tuple[FeatureSpec, ...]
    rows_per_shard: int
    map_capacity_per_shard: int
    exchange: exchange.ExchangeSpec

    @property
    def key(self) -> str:
        return f"dim{self.dim}"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    mesh_axes: tuple[str, ...]
    n_devices: int
    rows_per_shard: int = 1 << 16
    map_capacity_per_shard: int = 1 << 17
    u_budget: int = 4096
    per_dest_cap: int = 256
    recv_budget: int = 8192
    # per-dim overrides: dim -> dict of the five knobs above
    overrides: Mapping[int, Mapping[str, int]] = dataclasses.field(default_factory=dict)
    # tiered storage: non-None turns the device tier into an HBM cache over
    # a host-DRAM backing store (DESIGN.md §3); rows_per_shard then bounds
    # HOT rows only, not live rows.
    storage: StorageConfig | None = None


class EmbeddingEngine:
    def __init__(self, specs: Sequence[FeatureSpec], cfg: EngineConfig):
        self.cfg = cfg
        emb_specs = [s for s in specs if s.emb_dim is not None]
        by_dim: dict[int, list[FeatureSpec]] = {}
        for s in emb_specs:
            by_dim.setdefault(s.emb_dim, []).append(s)
        self.groups: dict[str, GroupSpec] = {}
        for dim, feats in sorted(by_dim.items()):
            ov = dict(cfg.overrides.get(dim, {}))
            ex = exchange.ExchangeSpec(
                axes=cfg.mesh_axes,
                n_devices=cfg.n_devices,
                u_budget=ov.get("u_budget", cfg.u_budget),
                per_dest_cap=ov.get("per_dest_cap", cfg.per_dest_cap),
                recv_budget=ov.get("recv_budget", cfg.recv_budget),
            )
            g = GroupSpec(
                dim=dim,
                features=tuple(feats),
                rows_per_shard=ov.get("rows_per_shard", cfg.rows_per_shard),
                map_capacity_per_shard=ov.get("map_capacity_per_shard", cfg.map_capacity_per_shard),
                exchange=ex,
            )
            self.groups[g.key] = g
        self.salts = {
            s.name: jnp.int64(_stable_salt(s.table_key())) for s in emb_specs
        }
        self.storage: TieredEmbeddingStore | None = None
        if cfg.storage is not None:
            self.storage = TieredEmbeddingStore(
                {k: (g.dim, g.rows_per_shard) for k, g in self.groups.items()},
                cfg.n_devices, cfg.storage,
            )

    # ------------------------------------------------------------------ state
    def init_state(self) -> dict:
        """Global-view state: every leaf carries a leading device axis [D, ...]
        so shard_map can shard it with P(mesh_axes) on axis 0."""
        D = self.cfg.n_devices

        def stack(x):
            return jnp.broadcast_to(x[None], (D,) + x.shape)

        state = {}
        for key, g in self.groups.items():
            m = idmap_lib.create(g.map_capacity_per_shard, g.rows_per_shard)
            b = blocks_lib.create(g.rows_per_shard, g.dim)
            state[key] = {
                "idmap": jax.tree.map(stack, m),
                "blocks": jax.tree.map(stack, b),
            }
        return state

    def state_sharding_spec(self):
        """PartitionSpec for every leaf: shard the leading device axis."""
        from jax.sharding import PartitionSpec as P

        return P(self.cfg.mesh_axes)

    # -------------------------------------------------------------- engine ids
    def engine_ids(self, ids_by_feature: Mapping[str, Ragged]) -> dict[str, jax.Array]:
        """Per dim-group: salted, concatenated id vector [L_group]."""
        out = {}
        for key, g in self.groups.items():
            parts = []
            for s in g.features:
                r = ids_by_feature[s.name]
                eng = hash_combine(r.values.astype(jnp.uint64), jnp.uint64(self.salts[s.name])).astype(jnp.int64)
                parts.append(jnp.where(r.valid_mask(), eng, PAD))
            out[key] = jnp.concatenate(parts)
        return out

    # ------------------------------------------------------------ fetch (local)
    def fetch_local(
        self,
        state_local: dict,
        ids_by_feature: Mapping[str, Ragged],
        step: jax.Array,
        train: bool = True,
    ):
        """Runs INSIDE shard_map (local views, leading axis squeezed).

        Returns (state', rows_r {group: [R, dim]}, plans, metrics)."""
        with stage("recis.ids.hash"):
            eng_ids = self.engine_ids(ids_by_feature)
        new_state, rows_r, plans, metrics = {}, {}, {}, {}
        for key, g in self.groups.items():
            m = state_local[key]["idmap"]
            b = state_local[key]["blocks"]
            m, b, rr, plan, met = exchange.fetch(
                m, b, eng_ids[key], g.exchange, step, train
            )
            new_state[key] = {"idmap": m, "blocks": b}
            rows_r[key] = rr
            plans[key] = plan
            for mk, mv in met.items():
                metrics[f"{key}/{mk}"] = mv
            # device-tier occupancy: the capacity-pressure signal the tiered
            # store's spill/fill passes act on (DESIGN.md §3)
            metrics[f"{key}/dev_rows_live"] = m.n_live()
        return new_state, rows_r, plans, metrics

    # ------------------------------------------ activations (local, differentiable)
    def activations(
        self,
        rows_r: Mapping[str, jax.Array],
        plans: Mapping[str, exchange.Plan],
        ids_by_feature: Mapping[str, Ragged],
        use_pallas: bool = False,
    ) -> dict[str, jax.Array]:
        """rows_r → per-feature pooled activations. Linear in rows_r."""
        out = {}
        for key, g in self.groups.items():
            vals = exchange.route_rows(rows_r[key], plans[key], g.exchange)
            ofs = 0
            for s in g.features:
                r = ids_by_feature[s.name]
                with stage("recis.embed.pool"):
                    rows = vals[ofs: ofs + r.nnz_budget]
                    out[s.name] = _pool(rows, r, s, use_pallas=use_pallas)
                ofs += r.nnz_budget
        return out

    # ------------------------------------------------------------ update (local)
    def update_local(
        self,
        state_local: dict,
        plans: Mapping[str, exchange.Plan],
        grads_rows_r: Mapping[str, jax.Array],
        opt: SparseAdamConfig,
        step: jax.Array,
    ) -> dict:
        """Apply compact row gradients with SparseAdam(W) — paper's Backward
        Update: offsets retained from forward, rows updated in place."""
        new_state = {}
        for key, g in self.groups.items():
            plan = plans[key]
            with stage("recis.sparse.adam"):
                b = apply_row_updates(opt, state_local[key]["blocks"], plan.offsets_r,
                                      grads_rows_r[key], plan.valid_r, step)
            new_state[key] = {"idmap": state_local[key]["idmap"], "blocks": b}
        return new_state

    # ------------------------------------------------------- elastic reshard
    def export_rows(self, state) -> dict:
        """Global stacked state [D, ...] → {group: (ids, emb, slots, last_use)}
        of all LIVE rows, host-side numpy. The checkpoint-portable form: no
        device-count or slot-layout dependence (DESIGN.md §8 elasticity).

        With a tiered store the export is the UNION of both tiers — host-
        resident rows are appended and per-id access counts ride along, so
        elastic N→M restore is tier-transparent (DESIGN.md §3)."""
        out = {}
        for key, g in self.groups.items():
            m = jax.tree.map(np.asarray, state[key]["idmap"])
            b = jax.tree.map(np.asarray, state[key]["blocks"])
            ids, emb, slots, last = [], [], {k: [] for k in b.slots}, []
            D = m.keys.shape[0]
            for d in range(D):
                occ = m.occupied[d] & (m.offsets[d] != idmap_lib.OVERFLOW_ROW)
                ids.append(m.keys[d][occ])
                offs = m.offsets[d][occ]
                emb.append(b.emb[d][offs])
                for sk in b.slots:
                    slots[sk].append(b.slots[sk][d][offs])
                last.append(m.last_use[d][occ])
            if self.storage is not None:
                h = self.storage.host[key].export()
                ids.append(h["ids"])
                emb.append(h["emb"])
                for sk in b.slots:
                    slots[sk].append(h["slots"][sk])
                last.append(h["last_use"])
            out[key] = {
                "ids": np.concatenate(ids) if ids else np.zeros(0, np.int64),
                "emb": np.concatenate(emb),
                "slots": {k: np.concatenate(v) for k, v in slots.items()},
                "last_use": np.concatenate(last),
            }
            if self.storage is not None:
                cnt = self.storage.counts[key]
                out[key]["counts"] = np.fromiter(
                    (cnt.get(int(i), 1) for i in out[key]["ids"]),
                    np.int64, out[key]["ids"].size)
        return out

    def import_rows(self, rows: Mapping[str, Mapping]) -> dict:
        """Rebuild stacked state for THIS engine's device count from exported
        rows — the N→M elastic restore path. Rows are re-hash-sharded by the
        same owner function the exchange uses, then re-inserted per shard.

        With a tiered store, each shard's hottest rows (by exported
        last_use) fill the device tier up to capacity and the remainder
        lands in the host tier — a checkpoint taken at one device count and
        tier split restores onto any other (tier-transparent elasticity)."""
        from repro.core.exchange import _owner_of

        state = self.init_state()
        D = self.cfg.n_devices
        for key, g in self.groups.items():
            if key not in rows:
                continue  # this engine has dims the checkpoint lacks
            data = rows[key]
            ids = np.asarray(data["ids"])
            if self.storage is not None:
                self.storage.host[key].clear()
                counts = np.asarray(
                    data.get("counts", np.ones(ids.shape, np.int64)))
                self.storage.counts[key] = {
                    int(i): int(c) for i, c in zip(ids, counts)}
            if ids.size == 0:
                continue
            owner = np.asarray(_owner_of(jnp.asarray(ids), D))
            cap = g.rows_per_shard - 1  # row 0 reserved
            maps, blks = [], []
            for d in range(D):
                sel = np.flatnonzero(owner == d)
                m = jax.tree.map(lambda x: x[d], state[key]["idmap"])
                b = jax.tree.map(lambda x: x[d], state[key]["blocks"])
                if self.storage is not None and sel.size > cap:
                    # hottest rows stay device-resident; the tail spills
                    last = np.asarray(data["last_use"])[sel]
                    hot = sel[np.lexsort((ids[sel], -last))]
                    sel, cold = hot[:cap], hot[cap:]
                    self.storage.host[key].put(
                        ids[cold], np.asarray(data["emb"])[cold],
                        {k: np.asarray(v)[cold]
                         for k, v in data["slots"].items()},
                        np.asarray(data["last_use"])[cold])
                if sel.size:
                    sid = jnp.asarray(ids[sel])
                    # per-row last_use rides along (vector step), so the
                    # restored staleness clock is bit-identical to the
                    # writer's — eviction decisions survive a restore
                    m, offs, is_new, _ = idmap_lib.lookup_or_insert(
                        m, sid, jnp.asarray(np.asarray(data["last_use"])[sel]))
                    dst = jnp.where(is_new, offs, b.emb.shape[0])
                    emb = b.emb.at[dst].set(jnp.asarray(np.asarray(data["emb"])[sel]), mode="drop")
                    slots = {k: v.at[dst].set(jnp.asarray(np.asarray(data["slots"][k])[sel]),
                                              mode="drop")
                             for k, v in b.slots.items()}
                    b = blocks_lib.Blocks(emb=emb, slots=slots)
                maps.append(m)
                blks.append(b)
            state[key] = {
                "idmap": jax.tree.map(lambda *xs: jnp.stack(xs), *maps),
                "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blks),
            }
        if self.storage is not None:
            self.storage.sync_from_state(state)
        return state

    # ------------------------------------------------------------------ evict
    def evict_local(self, state_local: dict, older_than: jax.Array) -> tuple[dict, dict]:
        """In-jit staleness discard (single shard). With a tiered store
        configured, prefer ``evict_to_host`` at a step edge — it SPILLS the
        stale rows to the host tier instead of discarding them."""
        new_state, metrics = {}, {}
        for key in self.groups:
            m, n = idmap_lib.evict(state_local[key]["idmap"], older_than)
            new_state[key] = {"idmap": m, "blocks": state_local[key]["blocks"]}
            metrics[f"{key}/evicted"] = n
        return new_state, metrics

    # ------------------------------------------- tiered storage (step edges)
    # The host tier is numpy-backed, so host↔device row traffic runs at step
    # EDGES on the stacked global-view state (DESIGN.md §3): prefetch fills
    # before fetch_local's in-jit lookup, admit/evict spill after update.
    def storage_prefetch(
        self, state: dict, ids_by_feature: Mapping[str, Ragged], step
    ) -> tuple[dict, dict]:
        """Fill pass: promote this step's host-resident rows into HBM (and
        demote policy-chosen victims under capacity pressure) so the jitted
        step hits no overflow fallbacks. Returns (state', metrics)."""
        assert self.storage is not None, "EngineConfig.storage not set"
        eng = {k: np.asarray(v)
               for k, v in self.engine_ids(ids_by_feature).items()}
        return self.storage.prefetch(state, eng, int(step))

    def storage_admit(self, state: dict, step) -> tuple[dict, dict]:
        """Spill pass: demote rows that entered HBM this step but fail the
        admission policy (e.g. below ``min_count_to_admit``)."""
        assert self.storage is not None, "EngineConfig.storage not set"
        return self.storage.post_step(state, int(step))

    def evict_to_host(self, state: dict, older_than) -> tuple[dict, dict]:
        """Staleness pass over the stacked state. Tiered engines spill the
        stale rows device→host (state is preserved); plain engines discard
        them exactly like ``evict_local``."""
        if self.storage is not None:
            return self.storage.evict_stale(state, int(older_than))
        D = self.cfg.n_devices
        new_state, metrics = {}, {}
        for key in self.groups:
            maps, n_total = [], 0
            for d in range(D):
                m = jax.tree.map(lambda x: x[d], state[key]["idmap"])
                with write_log.shard_scope(key, d):
                    m, n = idmap_lib.evict(m, jnp.int32(older_than))
                maps.append(m)
                n_total += int(n)
            new_state[key] = {
                "idmap": jax.tree.map(lambda *xs: jnp.stack(xs), *maps),
                "blocks": state[key]["blocks"],
            }
            metrics[f"{key}/evicted"] = n_total
        return new_state, metrics


def _pool(rows: jax.Array, r: Ragged, s: FeatureSpec, use_pallas: bool = False) -> jax.Array:
    """Per-feature pooling of per-value embedding rows.

    sum / mean → (n_rows, dim); none → (n_rows, max_len, dim);
    tile → (n_rows, tile_k * dim)  [paper's concat aggregation].
    """
    if s.pooling == "values":
        return rows  # (nnz_budget, dim) — per-id rows in CSR order (LM tokens)
    seg = r.segment_ids()
    n = r.n_rows
    if s.pooling in ("sum", "mean"):
        if use_pallas:
            from repro.kernels.segment_reduce import ops as sr_ops

            pooled = sr_ops.segment_sum(rows, seg, n)
        else:
            pooled = jax.ops.segment_sum(rows, seg, num_segments=n)
        if s.pooling == "mean":
            cnt = jnp.maximum(r.row_lengths().astype(rows.dtype), 1.0)
            pooled = pooled / cnt[:, None]
        return pooled
    if s.pooling == "none":
        assert s.max_len is not None, f"{s.name}: sequence pooling needs max_len"
        idx = r.row_splits[:-1, None] + jnp.arange(s.max_len)[None, :]
        mask = jnp.arange(s.max_len)[None, :] < r.row_lengths()[:, None]
        idx = jnp.clip(idx, 0, r.nnz_budget - 1)
        return rows[idx] * mask[..., None].astype(rows.dtype)
    if s.pooling == "tile":
        k = s.tile_k or 1
        if use_pallas:
            from repro.kernels.sequence_tile import ops as st_ops

            return st_ops.sequence_tile(rows, r.row_splits, k)
        idx = r.row_splits[:-1, None] + jnp.arange(k)[None, :]
        mask = jnp.arange(k)[None, :] < r.row_lengths()[:, None]
        idx = jnp.clip(idx, 0, r.nnz_budget - 1)
        tiles = rows[idx] * mask[..., None].astype(rows.dtype)
        return tiles.reshape(n, k * rows.shape[-1])
    raise ValueError(s.pooling)
