"""LM-family cells: train / prefill / decode (incl. 524k long-context).

Dataflow per DESIGN.md §4–5. The vocab table lives in the Embedding Engine
hash-sharded over ALL mesh axes (paper's full sharding); tokens are split
(batch over dp, sequence over "model") so each device requests a distinct
token slice; pooled per-token rows come back sequence-sharded over "model",
which is exactly the SP layout the transformer wants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.core import exchange
from repro.core.embedding_engine import EmbeddingEngine, EngineConfig, reduce_metrics
from repro.core.feature_engine import FeatureSpec
from repro.io.ragged import Ragged
from repro.launch.common import Cell, CellOptions, abstractify, mesh_info, round_up
from repro.models import transformer as tfm
from repro.models.layers import MIXED
from repro.models.transformer import MeshCtx
from repro.optim import adamw
from repro.optim.sparse_adam import SparseAdamConfig
from jax import shard_map


def _engine_for(cfg, mesh, L_local: int, opts: CellOptions) -> tuple[EmbeddingEngine, str]:
    mi = mesh_info(mesh)
    D = mi["D"]
    u = max(round_up(L_local, 8), 16)
    c = max(8, round_up(int(np.ceil(u / D * opts.capacity_slack)), 8))
    r = min(D * c, max(round_up(int(opts.recv_slack * u), 8), 64))
    rows = max(round_up(int(cfg.vocab_size / D * 2.0), 128), 256)
    eng = EmbeddingEngine(
        [FeatureSpec("tokens", transform="mod", vocab_size=cfg.vocab_size,
                     emb_dim=cfg.d_model, pooling="values")],
        EngineConfig(
            mesh_axes=mi["axes"], n_devices=D,
            rows_per_shard=rows, map_capacity_per_shard=2 * rows,
            u_budget=u, per_dest_cap=c, recv_budget=r,
        ),
    )
    return eng, f"dim{cfg.d_model}"


def _fetch_sm(engine: EmbeddingEngine, gkey: str, mesh, axes, ids_spec, L_local, train: bool):
    """shard_map'd engine fetch: (sparse_state, ids, step) → (state', rows_r, plan, met)."""
    espec = engine.groups[gkey].exchange
    sp = P(axes)

    def fetch_fn(sp_state, ids, step):
        st = jax.tree.map(lambda x: x[0], sp_state)
        flat = ids.reshape(-1).astype(jnp.int64)
        # row structure is irrelevant for pooling="values": one row holds all ids.
        ragged = Ragged(flat, jnp.array([0, L_local], jnp.int32))
        st, rows_r, plans, met = engine.fetch_local(st, {"tokens": ragged}, step, train=train)
        met = reduce_metrics(met, axes)
        return (jax.tree.map(lambda x: x[None], st), rows_r[gkey], plans[gkey], met)

    return shard_map(
        fetch_fn, mesh=mesh,
        in_specs=(sp, ids_spec, P()),
        out_specs=(sp, sp, sp, P()),
        check_vma=False,
    ), espec


def _route_sm(engine, gkey, mesh, axes, out_spec, L_local, b_loc, t_loc):
    espec = engine.groups[gkey].exchange

    def route_fn(rows_r, plan):
        vals = exchange.route_rows(rows_r, plan, espec)         # (L, d) fp32
        return vals.reshape(b_loc, t_loc, vals.shape[-1])

    return shard_map(
        route_fn, mesh=mesh, in_specs=(P(axes), P(axes)), out_specs=out_spec,
        check_vma=False,
    )


def _update_sm(engine, gkey, mesh, axes, opt: SparseAdamConfig):
    sp = P(axes)

    def upd_fn(sp_state, plan, grows, step):
        st = jax.tree.map(lambda x: x[0], sp_state)
        st = engine.update_local(st, {gkey: plan}, {gkey: grows}, opt, step)
        return jax.tree.map(lambda x: x[None], st)

    return shard_map(
        upd_fn, mesh=mesh, in_specs=(sp, sp, sp, P()), out_specs=sp,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# train cell
# ---------------------------------------------------------------------------

def make_train_cell(arch: ArchConfig, shape: ShapeCell, mesh, opts: CellOptions) -> Cell:
    import dataclasses as _dc

    cfg = arch.model
    if opts.moe_capacity_factor and cfg.moe:
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, capacity_factor=opts.moe_capacity_factor))
    cfg = _dc.replace(cfg, remat=opts.remat, remat_policy=opts.remat_policy)
    mi = mesh_info(mesh)
    axes, dp, tp, D = mi["axes"], mi["dp"], mi["tp"], mi["D"]
    tp_ax = "model" if "model" in axes else None
    B, T = shape["global_batch"], shape["seq_len"]
    assert B % mi["dp_size"] == 0 and T % tp == 0
    b_loc, t_loc = B // mi["dp_size"], T // tp
    L = b_loc * t_loc

    engine, gkey = _engine_for(cfg, mesh, L, opts)
    fetch, espec = _fetch_sm(engine, gkey, mesh, axes, P(dp, tp_ax), L, opts.train_insert)
    route = _route_sm(engine, gkey, mesh, axes, P(dp, tp_ax, None), L, b_loc, t_loc)
    update = _update_sm(engine, gkey, mesh, axes, SparseAdamConfig(lr=opts.sparse_opt_lr))
    acfg = adamw.AdamWConfig(lr=opts.dense_opt_lr)
    ctx = MeshCtx(mesh=mesh, dp=dp, tp=tp_ax)

    def init_fn():
        dense = tfm.init(jax.random.PRNGKey(0), cfg, ep_size=tp)
        return {
            "step": jnp.zeros((), jnp.int32),
            "dense": dense,
            "opt": adamw.init(dense),
            "sparse": engine.init_state(),
        }

    dense_spec = tfm.pspec(cfg)
    state_spec = {
        "step": P(),
        "dense": dense_spec,
        "opt": None,  # filled below (needs shapes for zero1)
        "sparse": jax.tree.map(lambda _: P(axes), jax.eval_shape(engine.init_state)),
    }
    shapes = jax.eval_shape(init_fn)
    if opts.zero1 and dp:
        ospec = adamw.zero1_pspec(dense_spec, shapes["dense"], shard_axis=dp[-1])
    else:
        ospec = dense_spec
    state_spec["opt"] = {"m": ospec, "v": ospec}

    def train_step(state, tokens):
        step = state["step"] + 1
        new_sparse, rows_r, plan, met = fetch(state["sparse"], tokens, step)
        labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)

        def loss_fn(dense, rows_r):
            x_emb = route(rows_r, plan)
            loss, aux = tfm.lm_loss(dense, cfg, x_emb, labels, ctx, MIXED,
                                    attn_impl=opts.attn_impl,
                                    fused_ce=opts.fused_ce,
                                    sp_residual=opts.sp_residual)
            return loss + aux, loss

        (total, loss), (gdense, grows) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(state["dense"], rows_r)
        new_dense, new_opt = adamw.update(acfg, state["dense"], gdense, state["opt"], step)
        new_sparse = update(new_sparse, plan, grows, step)
        new_state = {"step": step, "dense": new_dense, "opt": new_opt, "sparse": new_sparse}
        return new_state, {"loss": loss, **met}

    batch_specs = jax.ShapeDtypeStruct((B, T), jnp.int32,
                                       sharding=jax.NamedSharding(mesh, P(dp, tp_ax)))
    abstract_state = abstractify(shapes, state_spec, mesh)

    def make_batch(seed: int):
        r = np.random.default_rng(seed)
        return jnp.asarray(r.integers(0, cfg.vocab_size, size=(B, T)), jnp.int32)

    return Cell(arch=arch, shape=shape, mesh=mesh, step_fn=train_step,
                abstract_state=abstract_state, batch_specs=batch_specs,
                state_shardings=state_spec, init_state=init_fn, make_batch=make_batch,
                donate_state=opts.donate_state)


# ---------------------------------------------------------------------------
# prefill cell (serve)
# ---------------------------------------------------------------------------

def make_prefill_cell(arch: ArchConfig, shape: ShapeCell, mesh, opts: CellOptions) -> Cell:
    import dataclasses as _dc

    cfg = _dc.replace(arch.model, remat=False)
    mi = mesh_info(mesh)
    axes, dp, tp = mi["axes"], mi["dp"], mi["tp"]
    tp_ax = "model" if "model" in axes else None
    B, T = shape["global_batch"], shape["seq_len"]
    b_loc, t_loc = B // mi["dp_size"], T // tp
    L = b_loc * t_loc

    engine, gkey = _engine_for(cfg, mesh, L, opts)
    fetch, _ = _fetch_sm(engine, gkey, mesh, axes, P(dp, tp_ax), L, train=False)
    route = _route_sm(engine, gkey, mesh, axes, P(dp, tp_ax, None), L, b_loc, t_loc)
    ctx = MeshCtx(mesh=mesh, dp=dp, tp=tp_ax)

    def init_fn():
        dense = tfm.init(jax.random.PRNGKey(0), cfg, ep_size=tp)
        return {"step": jnp.zeros((), jnp.int32), "dense": dense,
                "sparse": engine.init_state()}

    state_spec = {
        "step": P(),
        "dense": tfm.pspec(cfg),
        "sparse": jax.tree.map(lambda _: P(axes), jax.eval_shape(engine.init_state)),
    }

    def serve_step(state, tokens):
        _, rows_r, plan, met = fetch(state["sparse"], tokens, state["step"])
        x_emb = route(rows_r, plan)
        h, _, cache = tfm.apply(state["dense"], cfg, x_emb, ctx, MIXED,
                                attn_impl=opts.attn_impl, collect_cache=True)
        h_last = h[:, -1, :]
        from repro.models.layers import dense_apply

        logits = dense_apply(state["dense"]["head"], h_last, MIXED).astype(jnp.float32)
        k, v = cache
        cast = lambda c: ctx.wsc(c.astype(jnp.bfloat16), None, dp, tp_ax, None, None)
        return {"logits": logits, "cache_k": cast(k), "cache_v": cast(v), **met}

    batch_specs = jax.ShapeDtypeStruct((B, T), jnp.int32,
                                       sharding=jax.NamedSharding(mesh, P(dp, tp_ax)))
    abstract_state = abstractify(jax.eval_shape(init_fn), state_spec, mesh)

    def make_batch(seed: int):
        r = np.random.default_rng(seed)
        return jnp.asarray(r.integers(0, cfg.vocab_size, size=(B, T)), jnp.int32)

    return Cell(arch=arch, shape=shape, mesh=mesh, step_fn=serve_step,
                abstract_state=abstract_state, batch_specs=batch_specs,
                state_shardings=state_spec, init_state=init_fn, make_batch=make_batch,
                donate_state=False, returns_state=False)


# ---------------------------------------------------------------------------
# decode cell (serve; decode_32k and long_500k)
# ---------------------------------------------------------------------------

def make_decode_cell(arch: ArchConfig, shape: ShapeCell, mesh, opts: CellOptions) -> Cell:
    import dataclasses as _dc

    cfg = _dc.replace(arch.model, remat=False)
    mi = mesh_info(mesh)
    axes, dp, tp = mi["axes"], mi["dp"], mi["tp"]
    tp_ax = "model" if "model" in axes else None
    B, S = shape["global_batch"], shape["seq_len"]
    long_ctx = bool(shape.get("long_context"))
    if long_ctx:
        cell_dp: tuple = ()
        seq_shards: tuple = axes          # shard the 524k cache over everything
        b_loc = B
    else:
        cell_dp = dp
        seq_shards = (tp_ax,) if tp_ax else ()
        b_loc = B // mi["dp_size"]
    L = max(b_loc, 1)

    engine, gkey = _engine_for(cfg, mesh, L, opts)
    ids_spec = P(cell_dp or None)
    fetch, _ = _fetch_sm(engine, gkey, mesh, axes, ids_spec, L, train=False)
    route = _route_sm(engine, gkey, mesh, axes, P(cell_dp or None, None, None), L, b_loc, 1)
    ctx = MeshCtx(mesh=mesh, dp=cell_dp, tp=tp_ax, seq_shards=seq_shards)

    def init_fn():
        dense = tfm.init(jax.random.PRNGKey(0), cfg, ep_size=tp)
        cache = tfm.init_cache(cfg, B, S)
        return {"step": jnp.zeros((), jnp.int32), "pos": jnp.zeros((), jnp.int32),
                "dense": dense, "sparse": engine.init_state(), "cache": cache}

    cache_spec = {"k": P(None, cell_dp or None, seq_shards or None, None, None),
                  "v": P(None, cell_dp or None, seq_shards or None, None, None)}
    state_spec = {
        "step": P(), "pos": P(),
        "dense": tfm.pspec(cfg),
        "sparse": jax.tree.map(lambda _: P(axes), jax.eval_shape(engine.init_state)),
        "cache": cache_spec,
    }

    def serve_step(state, token_ids):
        pos = state["pos"]
        _, rows_r, plan, met = fetch(state["sparse"], token_ids, state["step"])
        x_emb = route(rows_r, plan)                     # (B, 1, d)
        logits, cache = tfm.decode_step(state["dense"], cfg, x_emb, state["cache"],
                                        pos, ctx, MIXED)
        new_state = dict(state)
        new_state["cache"] = cache
        new_state["pos"] = pos + 1
        return new_state, {"logits": logits, **met}

    batch_specs = jax.ShapeDtypeStruct(
        (B,), jnp.int32, sharding=jax.NamedSharding(mesh, ids_spec))
    abstract_state = abstractify(jax.eval_shape(init_fn), state_spec, mesh)

    def make_batch(seed: int):
        r = np.random.default_rng(seed)
        return jnp.asarray(r.integers(0, cfg.vocab_size, size=(B,)), jnp.int32)

    return Cell(arch=arch, shape=shape, mesh=mesh, step_fn=serve_step,
                abstract_state=abstract_state, batch_specs=batch_specs,
                state_shardings=state_spec, init_state=init_fn, make_batch=make_batch,
                donate_state=opts.donate_state)


def build(arch: ArchConfig, shape: ShapeCell, mesh, opts: CellOptions = CellOptions()) -> Cell:
    if shape.kind == "train":
        return make_train_cell(arch, shape, mesh, opts)
    if shape.kind == "prefill":
        return make_prefill_cell(arch, shape, mesh, opts)
    if shape.kind == "decode":
        return make_decode_cell(arch, shape, mesh, opts)
    raise ValueError(shape.kind)
