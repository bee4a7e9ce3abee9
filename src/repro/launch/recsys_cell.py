"""Recsys-family cells — the paper's core workload.

Pure DP on the dense side (tiny MLPs, batch sharded over ALL mesh axes),
Embedding Engine full-sharding on the sparse side. One fused transform pass
(Feature Engine) + one exchange per embedding dim — the RecIS fusion story.

Batch convention: {column: Ragged} where values/row_splits are global
arrays sharded on axis 0 over all mesh axes (each device owns its batch
slice in CSR form — the ColumnIO output layout).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.core.embedding_engine import EmbeddingEngine, EngineConfig, reduce_metrics
from repro.core.feature_engine import FeatureEngine, FeatureSpec
from repro.io.ragged import Ragged
from repro.launch.common import Cell, CellOptions, abstractify, mesh_info, round_up
from repro.models.layers import MIXED
from repro.obs.stages import stage
from repro.optim import adamw
from repro.optim.sparse_adam import SparseAdamConfig
from jax import shard_map

_MODELS = {}


def _model_mod(arch_id: str):
    if not _MODELS:
        from repro.models.recsys import dlrm, mind, sasrec, wide_deep

        _MODELS.update({
            "dlrm-mlperf": dlrm, "mind": mind, "sasrec": sasrec, "wide-deep": wide_deep,
        })
    return _MODELS[arch_id]


def _ids_per_row(s: FeatureSpec) -> int:
    if s.pooling == "none":
        return s.max_len or 1
    if s.transform == "raw":
        return s.max_len or 1
    return 1  # single-valued categorical


def _cand_specs(arch_id: str, model_cfg) -> list[FeatureSpec]:
    """Candidate columns for retrieval cells (share the item tables)."""
    if arch_id == "dlrm-mlperf":
        return [FeatureSpec("cand_items", transform="hash", emb_dim=model_cfg.embed_dim,
                            pooling="values", shared_table="cat_0")]
    if arch_id == "wide-deep":
        return [
            FeatureSpec("cand_items", transform="hash", emb_dim=model_cfg.embed_dim,
                        pooling="values", shared_table="cat_0"),
            FeatureSpec("cand_wide", transform="hash", emb_dim=model_cfg.wide_dim,
                        pooling="values", shared_table="wide_tbl_0"),
        ]
    return [FeatureSpec("cand_items", transform="hash", emb_dim=model_cfg.embed_dim,
                        pooling="values", shared_table="items")]


@dataclasses.dataclass
class _Plumbing:
    engine: EmbeddingEngine
    fengine: FeatureEngine
    specs: list[FeatureSpec]
    nnz_loc: dict[str, int]
    b_loc: int
    mesh: object
    axes: tuple
    D: int

    replicated: bool = False  # True → one copy on every device (retrieval user)

    def batch_struct(self):
        """ShapeDtypeStructs for the global batch pytree."""
        rep = 1 if self.replicated else self.D
        spec_v = P(None) if self.replicated else P(self.axes)
        out = {}
        for s in self.specs:
            n = self.nnz_loc[s.name]
            vdt = jnp.float32 if s.transform == "raw" else jnp.int64
            out[s.name] = Ragged(
                jax.ShapeDtypeStruct((rep * n,), vdt,
                                     sharding=jax.NamedSharding(self.mesh, spec_v)),
                jax.ShapeDtypeStruct((rep * (self.b_loc + 1),), jnp.int32,
                                     sharding=jax.NamedSharding(self.mesh, spec_v)),
            )
        return out

    def in_spec(self):
        return P(None) if self.replicated else P(self.axes)

    def make_batch(self, seed: int, vocab: int = 1 << 30):
        """Concrete synthetic batch (power-law ids) matching batch_struct,
        placed on its shardings: each device receives only its slice."""
        r = np.random.default_rng(seed)
        rep = 1 if self.replicated else self.D
        sh = jax.NamedSharding(self.mesh, self.in_spec())
        out = {}
        for s in self.specs:
            n = self.nnz_loc[s.name]
            k = _ids_per_row(s)
            if s.transform == "raw":
                vals = r.normal(size=(rep * n,)).astype(np.float32)
                if s.name == "label":
                    vals = (vals > 0).astype(np.float32)
            else:
                vals = (r.zipf(1.2, size=(rep * n,)) % vocab).astype(np.int64)
            splits = np.tile(np.arange(self.b_loc + 1, dtype=np.int32) * k, rep)
            out[s.name] = Ragged(jax.device_put(vals, sh),
                                 jax.device_put(splits, sh))
        return out

    def prepared(self, batch_local: Mapping[str, Ragged]):
        """Feature Engine transforms (fused) → ids + dense, local view."""
        return self.fengine.apply(batch_local)


def _rows_per_dim(arch: ArchConfig, table_rows: int | None = None) -> dict[int, int]:
    """Global KV row capacity per dim-group: the arch's table sizes, or
    ``table_rows`` rows for every table when given."""
    m = arch.model
    if arch.arch_id in ("dlrm-mlperf", "wide-deep"):
        rows = m.n_sparse * (table_rows or m.vocab_per_feature)
        dims = (m.embed_dim, m.wide_dim) if arch.arch_id == "wide-deep" else (m.embed_dim,)
        return {d: rows for d in dims}
    return {m.embed_dim: table_rows or m.vocab}  # sasrec / mind: one shared item table


def _plumbing(arch: ArchConfig, mesh, b_loc: int, specs: list[FeatureSpec],
              opts: CellOptions, replicated: bool = False) -> _Plumbing:
    mi = mesh_info(mesh)
    D = mi["D"]
    rows_global = _rows_per_dim(
        arch, opts.chip_table_rows and opts.chip_table_rows * D)
    by_dim: dict[int, int] = {}
    for s in specs:
        if s.emb_dim is not None:
            by_dim[s.emb_dim] = by_dim.get(s.emb_dim, 0) + b_loc * _ids_per_row(s)
    overrides = {}
    for dim, L in by_dim.items():
        u = max(round_up(L, 8), 16)
        # no destination can receive more than the U unique ids
        c = min(u, max(8, round_up(int(np.ceil(u / D * opts.capacity_slack)), 8)))
        r = min(D * c, max(round_up(int(opts.recv_slack * u), 8), 64))
        rows = max(round_up(int(rows_global.get(dim, 1 << 20) * 1.5 / D), 128), 1024)
        if opts.storage is not None and opts.storage_device_rows is not None:
            # tiered mode: rows_per_shard is the HBM hot-row cache size, not
            # the live-row ceiling — the host tier absorbs the rest
            rows = opts.storage_device_rows
        overrides[dim] = dict(u_budget=u, per_dest_cap=c, recv_budget=r,
                              rows_per_shard=rows, map_capacity_per_shard=2 * rows)
    eng = EmbeddingEngine(specs, EngineConfig(
        mesh_axes=mi["axes"], n_devices=D, overrides=overrides,
        storage=opts.storage))
    fe = FeatureEngine(specs, use_pallas=opts.use_pallas)
    nnz = {s.name: b_loc * _ids_per_row(s) for s in specs}
    return _Plumbing(engine=eng, fengine=fe, specs=specs, nnz_loc=nnz,
                     b_loc=b_loc, mesh=mesh, axes=mi["axes"], D=D, replicated=replicated)


def _split_local(pl: _Plumbing, flat_batch):
    """Rebuild {name: Ragged} local views inside shard_map."""
    return {s.name: flat_batch[s.name] for s in pl.specs}


def _acts_specs(pl: _Plumbing, replicated: bool = False):
    """out_specs for activations: batch-dim sharded over all axes."""
    sp = P(None) if replicated else P(pl.axes)
    return {s.name: sp for s in pl.specs if s.emb_dim is not None}


def build(arch: ArchConfig, shape: ShapeCell, mesh, opts: CellOptions = CellOptions()) -> Cell:
    model = _model_mod(arch.arch_id)
    mcfg = arch.model
    mi = mesh_info(mesh)
    axes, D = mi["axes"], mi["D"]
    train = shape.kind == "train"

    if shape.kind == "retrieval":
        return _build_retrieval(arch, shape, mesh, opts)

    B = shape["batch"]
    assert B % D == 0, (B, D)
    b_loc = B // D
    specs = model.feature_specs(mcfg)
    pl = _plumbing(arch, mesh, b_loc, specs, opts)
    gkeys = list(pl.engine.groups)
    sp = P(axes)
    sopt = SparseAdamConfig(lr=opts.sparse_opt_lr)
    acfg = adamw.AdamWConfig(lr=opts.dense_opt_lr)

    def fetch_fn(sp_state, batch, step):
        st = jax.tree.map(lambda x: x[0], sp_state)
        with stage("recis.ids.hash"):
            ids, _ = pl.prepared(_split_local(pl, batch))
        st, rows_r, plans, met = pl.engine.fetch_local(st, ids, step, train=train and opts.train_insert)
        met = reduce_metrics(met, axes)
        return (jax.tree.map(lambda x: x[None], st),
                tuple(rows_r[k] for k in gkeys), tuple(plans[k] for k in gkeys), met)

    fetch = shard_map(fetch_fn, mesh=mesh, in_specs=(sp, sp, P()),
                          out_specs=(sp, sp, sp, P()), check_vma=False)

    def route_fn(rows_r, plans, batch):
        ids, _ = pl.prepared(_split_local(pl, batch))
        acts = pl.engine.activations(dict(zip(gkeys, rows_r)), dict(zip(gkeys, plans)),
                                     ids, use_pallas=opts.use_pallas)
        return acts

    route = shard_map(route_fn, mesh=mesh, in_specs=(sp, sp, sp),
                          out_specs=_acts_specs(pl), check_vma=False)

    def dense_fn(batch):
        """Raw numeric columns → dense arrays, under GSPMD (pure gather)."""
        out = {}
        for s in pl.specs:
            if s.transform == "raw":
                r = batch[s.name]
                k = s.max_len or 1
                n_rows = r.row_splits.shape[0] - 1  # D*(b_loc+1)-ish global view
                vals = r.values.reshape(-1, k)
                out[s.name] = vals.astype(jnp.float32)
        return out

    def update_fn(sp_state, plans, grows, step):
        st = jax.tree.map(lambda x: x[0], sp_state)
        st = pl.engine.update_local(st, dict(zip(gkeys, plans)),
                                    dict(zip(gkeys, grows)), sopt, step)
        return jax.tree.map(lambda x: x[None], st)

    update = shard_map(update_fn, mesh=mesh, in_specs=(sp, sp, sp, P()),
                           out_specs=sp, check_vma=False)

    def init_fn():
        dense = model.init(jax.random.PRNGKey(0), mcfg)
        st = {"step": jnp.zeros((), jnp.int32), "dense": dense,
              "sparse": pl.engine.init_state()}
        if train:
            st["opt"] = adamw.init(dense)
        return st

    dspec = model.pspec(mcfg)
    state_spec = {"step": P(), "dense": dspec,
                  "sparse": jax.tree.map(lambda _: P(axes), jax.eval_shape(pl.engine.init_state))}
    if train:
        state_spec["opt"] = {"m": dspec, "v": dspec}

    if train:
        def step_fn(state, batch):
            step = state["step"] + 1
            new_sparse, rows_r, plans, met = fetch(state["sparse"], batch, step)
            dense_feats = dense_fn(batch)

            def loss_fn(dense_params, rows_r):
                acts = route(rows_r, plans, batch)
                with stage("recis.tower"):
                    return model.loss(dense_params, mcfg, acts, dense_feats, MIXED)

            loss, (gdense, grows) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                state["dense"], rows_r)
            with stage("recis.dense.adamw"):
                new_dense, new_opt = adamw.update(acfg, state["dense"], gdense, state["opt"], step)
            new_sparse = update(new_sparse, plans, grows, step)
            return ({"step": step, "dense": new_dense, "opt": new_opt, "sparse": new_sparse},
                    {"loss": loss, **met})
    else:
        def step_fn(state, batch):
            _, rows_r, plans, met = fetch(state["sparse"], batch, state["step"])
            acts = route(rows_r, plans, batch)
            logits = model.apply(state["dense"], mcfg, acts, dense_fn(batch), MIXED)
            return {"logits": logits, **met}

    abstract_state = abstractify(jax.eval_shape(init_fn), state_spec, mesh)
    cell = Cell(arch=arch, shape=shape, mesh=mesh, step_fn=step_fn,
                abstract_state=abstract_state, batch_specs=pl.batch_struct(),
                state_shardings=state_spec, init_state=init_fn,
                make_batch=lambda seed: pl.make_batch(seed),
                donate_state=opts.donate_state and train, returns_state=train)
    cell.engine = pl.engine  # public: checkpoint export/import, serving
    # batch → {feature: Ragged} ids pytree, as the engine's fetch_local sees
    # it — the id seam both hook kinds (storage spill/fill, ft dirty-row
    # tracking) need to observe the step's sparse traffic on the host
    cell.ids_fn = lambda batch: pl.prepared(_split_local(pl, batch))[0]
    if train and pl.engine.storage is not None:
        from repro.storage.integration import StorageTrainerHooks

        # step-edge hooks for the Trainer: host↔HBM spill/fill around the
        # jitted step + host-tier checkpointing (pass as Trainer(hooks=...))
        cell.storage_hooks = StorageTrainerHooks(
            pl.engine, cell.ids_fn, state_key="sparse")
    return cell


def _build_retrieval(arch: ArchConfig, shape: ShapeCell, mesh, opts: CellOptions) -> Cell:
    """One user (replicated) × n_candidates (sharded over all axes)."""
    model = _model_mod(arch.arch_id)
    mcfg = arch.model
    mi = mesh_info(mesh)
    axes, D = mi["axes"], mi["D"]
    # pad the candidate set up to a mesh multiple (1,000,000 % 256 != 0);
    # scores beyond the true nc are padding the caller slices off.
    nc = round_up(shape["n_candidates"], D)
    nc_loc = nc // D

    user_specs = [s for s in model.feature_specs(mcfg) if s.name != "label"]
    cand_specs = _cand_specs(arch.arch_id, mcfg)
    # user columns replicated (B=1), candidate columns sharded
    pl_u = _plumbing(arch, mesh, 1, user_specs, opts, replicated=True)
    pl_c = _plumbing(arch, mesh, nc_loc, cand_specs, opts)
    gk_u, gk_c = list(pl_u.engine.groups), list(pl_c.engine.groups)
    sp = P(axes)

    def fetch_fn(sp_state_u, sp_state_c, ub, cb, step):
        st_u = jax.tree.map(lambda x: x[0], sp_state_u)
        st_c = jax.tree.map(lambda x: x[0], sp_state_c)
        ids_u, _ = pl_u.prepared(_split_local(pl_u, ub))
        ids_c, _ = pl_c.prepared(_split_local(pl_c, cb))
        st_u, rows_u, plans_u, met1 = pl_u.engine.fetch_local(st_u, ids_u, step, train=False)
        st_c, rows_c, plans_c, met2 = pl_c.engine.fetch_local(st_c, ids_c, step, train=False)
        acts_u = pl_u.engine.activations(rows_u, plans_u, ids_u, use_pallas=opts.use_pallas)
        acts_c = pl_c.engine.activations(rows_c, plans_c, ids_c, use_pallas=opts.use_pallas)
        met = reduce_metrics({**met1, **met2}, axes)
        return acts_u, acts_c, met

    acts_u_specs = {s.name: P(None) for s in user_specs if s.emb_dim is not None}
    acts_c_specs = {s.name: P(axes) for s in cand_specs}
    fetch = shard_map(fetch_fn, mesh=mesh,
                          in_specs=(sp, sp, pl_u.in_spec(), pl_c.in_spec(), P()),
                          out_specs=(acts_u_specs, acts_c_specs, P()), check_vma=False)

    def dense_fn(batch, specs):
        out = {}
        for s in specs:
            if s.transform == "raw":
                out[s.name] = batch[s.name].values.reshape(-1, s.max_len or 1).astype(jnp.float32)
        return out

    def step_fn(state, batch):
        ub, cb = batch["user"], batch["cand"]
        acts_u, acts_c, met = fetch(state["sparse_user"], state["sparse_cand"],
                                    ub, cb, state["step"])
        dense_u = dense_fn(ub, user_specs)
        kwargs = {}
        if arch.arch_id == "wide-deep":
            kwargs["cand_wide"] = acts_c["cand_wide"]
        scores = model.score_candidates(state["dense"], mcfg, acts_u, dense_u,
                                        acts_c["cand_items"], **kwargs)
        return {"scores": scores, **met}

    def init_fn():
        dense = model.init(jax.random.PRNGKey(0), mcfg)
        return {"step": jnp.zeros((), jnp.int32), "dense": dense,
                "sparse_user": pl_u.engine.init_state(),
                "sparse_cand": pl_c.engine.init_state()}

    state_spec = {
        "step": P(), "dense": model.pspec(mcfg),
        "sparse_user": jax.tree.map(lambda _: P(axes), jax.eval_shape(pl_u.engine.init_state)),
        "sparse_cand": jax.tree.map(lambda _: P(axes), jax.eval_shape(pl_c.engine.init_state)),
    }
    batch_specs = {"user": pl_u.batch_struct(), "cand": pl_c.batch_struct()}
    abstract_state = abstractify(jax.eval_shape(init_fn), state_spec, mesh)

    def make_batch(seed: int):
        return {"user": pl_u.make_batch(seed), "cand": pl_c.make_batch(seed + 1)}

    cell = Cell(arch=arch, shape=shape, mesh=mesh, step_fn=step_fn,
                abstract_state=abstract_state, batch_specs=batch_specs,
                state_shardings=state_spec, init_state=init_fn, make_batch=make_batch,
                donate_state=False, returns_state=False)
    cell.engine_user = pl_u.engine  # public: serving state import
    cell.engine_cand = pl_c.engine
    return cell
