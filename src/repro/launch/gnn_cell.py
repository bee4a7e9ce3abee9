"""GNN-family cells (GIN).

full_graph  — edge-parallel: node features replicated, edge list sharded
              over ALL mesh axes, partial segment-sum aggregations psum'd
              (the psum doubles as gradient sync; DESIGN.md §6).
minibatch   — sampled subgraphs (fanout 15-10), DP over all axes.
graph_batch — batched small graphs (molecule), DP over the dp axes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.launch.common import Cell, CellOptions, abstractify, mesh_info, round_up
from repro.models import gnn
from repro.models.gnn import GraphBatch
from repro.models.layers import MIXED
from repro.optim import adamw
from jax import shard_map


def _graph_specs(mesh, spec_map: dict) -> GraphBatch:
    """ShapeDtypeStructs for a GraphBatch given {field: (shape, dtype, pspec)}."""
    f = {k: jax.ShapeDtypeStruct(sh, dt, sharding=jax.NamedSharding(mesh, sp))
         for k, (sh, dt, sp) in spec_map.items()}
    return GraphBatch(**f)


def build(arch: ArchConfig, shape: ShapeCell, mesh, opts: CellOptions = CellOptions()) -> Cell:
    mi = mesh_info(mesh)
    axes, D = mi["axes"], mi["D"]
    cfg = dataclasses.replace(
        arch.model,
        d_feat=shape["d_feat"], n_classes=shape["n_classes"],
        task="graph" if shape.kind == "graph_batch" else "node",
    )
    acfg = adamw.AdamWConfig(lr=opts.dense_opt_lr)

    if shape.kind == "full_graph":
        return _full_graph_cell(arch, shape, mesh, cfg, acfg, opts)
    return _dp_cell(arch, shape, mesh, cfg, acfg, opts)


def _full_graph_cell(arch, shape, mesh, cfg, acfg, opts: CellOptions):
    mi = mesh_info(mesh)
    axes, D = mi["axes"], mi["D"]
    N = shape["n_nodes"]
    E = round_up(shape["n_edges"], D)
    e_loc = E // D

    def loss_local(params, g: GraphBatch):
        return gnn.loss_fn(params, cfg, g, MIXED, psum_axes=axes,
                           use_pallas=opts.use_pallas)

    smapped = shard_map(
        loss_local, mesh=mesh,
        in_specs=(P(), GraphBatch(
            feats=P(None, None), edge_src=P(axes), edge_dst=P(axes),
            edge_mask=P(axes), node_graph=P(None), node_mask=P(None), labels=P(None))),
        out_specs=P(), check_vma=False)

    def init_fn():
        dense = gnn.init(jax.random.PRNGKey(0), cfg)
        return {"step": jnp.zeros((), jnp.int32), "dense": dense, "opt": adamw.init(dense)}

    dspec = gnn.pspec(cfg)
    state_spec = {"step": P(), "dense": dspec, "opt": {"m": dspec, "v": dspec}}

    def step_fn(state, g):
        step = state["step"] + 1
        loss, grads = jax.value_and_grad(smapped)(state["dense"], g)
        new_dense, new_opt = adamw.update(acfg, state["dense"], grads, state["opt"], step)
        return {"step": step, "dense": new_dense, "opt": new_opt}, {"loss": loss}

    batch_specs = _graph_specs(mesh, {
        "feats": ((N, cfg.d_feat), jnp.float32, P(None, None)),
        "edge_src": ((E,), jnp.int32, P(axes)),
        "edge_dst": ((E,), jnp.int32, P(axes)),
        "edge_mask": ((E,), jnp.bool_, P(axes)),
        "node_graph": ((N,), jnp.int32, P(None)),
        "node_mask": ((N,), jnp.bool_, P(None)),
        "labels": ((N,), jnp.int32, P(None)),
    })
    abstract_state = abstractify(jax.eval_shape(init_fn), state_spec, mesh)

    def make_batch(seed: int):
        r = np.random.default_rng(seed)
        ne = shape["n_edges"]
        return GraphBatch(
            feats=jnp.asarray(r.normal(size=(N, cfg.d_feat)).astype(np.float32)),
            edge_src=jnp.asarray(np.pad(r.integers(0, N, ne), (0, E - ne)).astype(np.int32)),
            edge_dst=jnp.asarray(np.pad(r.integers(0, N, ne), (0, E - ne)).astype(np.int32)),
            edge_mask=jnp.asarray(np.arange(E) < ne),
            node_graph=jnp.zeros((N,), jnp.int32),
            node_mask=jnp.ones((N,), bool),
            labels=jnp.asarray(r.integers(0, cfg.n_classes, N).astype(np.int32)),
        )

    return Cell(arch=arch, shape=shape, mesh=mesh, step_fn=step_fn,
                abstract_state=abstract_state, batch_specs=batch_specs,
                state_shardings=state_spec, init_state=init_fn, make_batch=make_batch,
                donate_state=opts.donate_state)


def _dp_cell(arch, shape, mesh, cfg, acfg, opts: CellOptions):
    """minibatch (sampled subgraphs) and graph_batch (molecule) cells.

    ``opts.compress_grads``: the DP gradient sync runs as int8+error-feedback
    compressed psum inside the shard_map (optim/adamw.compressed_psum) —
    ~4× fewer collective bytes than the fp32 all-reduce; the quantization
    residual is carried per shard (§Perf beyond-paper lever)."""
    mi = mesh_info(mesh)
    axes, dp = mi["axes"], mi["dp"]
    if shape.kind == "minibatch":
        shard_axes = axes                              # 1024 seeds over all chips
        n_shards = mi["D"]
        seeds = shape["batch_nodes"] // n_shards
        f1, f2 = shape["fanout"]
        n_loc = seeds * (1 + f1 + f1 * f2)             # node budget per shard
        e_loc = seeds * (f1 + f1 * f2)                 # edge budget per shard
        graphs_loc = 0                                  # node task
    else:  # molecule: batch graphs over the dp axes only (128 < 256 chips)
        shard_axes = dp
        n_shards = mi["dp_size"]
        graphs_loc = shape["batch"] // n_shards
        n_loc = graphs_loc * shape["n_nodes"]
        e_loc = graphs_loc * shape["n_edges"]

    gspec = GraphBatch(
        feats=P(shard_axes, None), edge_src=P(shard_axes), edge_dst=P(shard_axes),
        edge_mask=P(shard_axes), node_graph=P(shard_axes), node_mask=P(shard_axes),
        labels=P(shard_axes))

    def loss_local(params, g: GraphBatch):
        l = gnn.loss_fn(params, cfg, g, MIXED, psum_axes=None, use_pallas=opts.use_pallas)
        return jax.lax.pmean(l, shard_axes)

    smapped = shard_map(loss_local, mesh=mesh, in_specs=(P(), gspec),
                            out_specs=P(), check_vma=False)

    n_sh = n_shards

    def grad_local(params, g: GraphBatch, err):
        """Per-shard grads + int8 compressed psum (error feedback carried)."""
        loss, grads = jax.value_and_grad(gnn.loss_fn)(
            params, cfg, g, MIXED, psum_axes=None, use_pallas=opts.use_pallas)
        loss = jax.lax.pmean(loss, shard_axes)
        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        flat_e = jax.tree_util.tree_leaves(err)   # local views [1, ...]
        out_g, out_e = [], []
        for gg, ee in zip(flat_g, flat_e):
            s, ne = adamw.compressed_psum(gg / n_sh, shard_axes, ee[0])
            out_g.append(s)
            out_e.append(ne[None])                # restack the shard axis
        return (loss, jax.tree_util.tree_unflatten(tdef, out_g),
                jax.tree_util.tree_unflatten(tdef, out_e))

    def init_fn():
        dense = gnn.init(jax.random.PRNGKey(0), cfg)
        st = {"step": jnp.zeros((), jnp.int32), "dense": dense, "opt": adamw.init(dense)}
        if opts.compress_grads:
            # per-shard error-feedback residual, stacked [n_shards, ...]
            st["ef"] = jax.tree.map(
                lambda p: jnp.zeros((n_sh,) + p.shape, jnp.float32), dense)
        return st

    dspec = gnn.pspec(cfg)
    state_spec = {"step": P(), "dense": dspec, "opt": {"m": dspec, "v": dspec}}
    if opts.compress_grads:
        state_spec["ef"] = jax.tree.map(
            lambda s: P(*((shard_axes,) + tuple(s))), dspec,
            is_leaf=lambda x: isinstance(x, P))
        gmapped = shard_map(
            grad_local, mesh=mesh,
            in_specs=(P(), gspec, jax.tree.map(
                lambda s: P(*((shard_axes,) + tuple(s))), dspec,
                is_leaf=lambda x: isinstance(x, P))),
            out_specs=(P(), P(), jax.tree.map(
                lambda s: P(*((shard_axes,) + tuple(s))), dspec,
                is_leaf=lambda x: isinstance(x, P))),
            check_vma=False)

    def step_fn(state, g):
        step = state["step"] + 1
        if opts.compress_grads:
            loss, grads, new_ef = gmapped(state["dense"], g, state["ef"])
            new_dense, new_opt = adamw.update(acfg, state["dense"], grads,
                                              state["opt"], step)
            return ({"step": step, "dense": new_dense, "opt": new_opt,
                     "ef": new_ef}, {"loss": loss})
        loss, grads = jax.value_and_grad(smapped)(state["dense"], g)
        new_dense, new_opt = adamw.update(acfg, state["dense"], grads, state["opt"], step)
        return {"step": step, "dense": new_dense, "opt": new_opt}, {"loss": loss}

    NG, EG = n_shards * n_loc, n_shards * e_loc
    n_labels = NG  # node task labels per node; graph task labels per graph
    if cfg.task == "graph":
        n_labels = n_shards * graphs_loc
    batch_specs = _graph_specs(mesh, {
        "feats": ((NG, cfg.d_feat), jnp.float32, P(shard_axes, None)),
        "edge_src": ((EG,), jnp.int32, P(shard_axes)),
        "edge_dst": ((EG,), jnp.int32, P(shard_axes)),
        "edge_mask": ((EG,), jnp.bool_, P(shard_axes)),
        "node_graph": ((NG,), jnp.int32, P(shard_axes)),
        "node_mask": ((NG,), jnp.bool_, P(shard_axes)),
        "labels": ((n_labels,), jnp.int32, P(shard_axes)),
    })
    abstract_state = abstractify(jax.eval_shape(init_fn), state_spec, mesh)

    def make_batch(seed: int):
        r = np.random.default_rng(seed)
        # local subgraphs with LOCAL node indices, concatenated per shard
        src = r.integers(0, n_loc, (n_shards, e_loc)).astype(np.int32)
        dst = r.integers(0, n_loc, (n_shards, e_loc)).astype(np.int32)
        if cfg.task == "graph":
            npg = shape["n_nodes"]
            node_graph = np.tile(np.repeat(np.arange(graphs_loc), npg), n_shards)
            labels = r.integers(0, cfg.n_classes, (n_shards * graphs_loc,))
        else:
            node_graph = np.zeros((NG,), np.int32)
            lab = r.integers(0, cfg.n_classes, (n_shards, n_loc))
            seeds_mask = np.arange(n_loc) >= 0
            labels = np.where(np.arange(n_loc)[None, :] < (n_loc if shape.kind != "minibatch" else max(1, n_loc // 166)), lab, -1)
            labels = labels.reshape(-1)
        return GraphBatch(
            feats=jnp.asarray(r.normal(size=(NG, cfg.d_feat)).astype(np.float32)),
            edge_src=jnp.asarray(src.reshape(-1)),
            edge_dst=jnp.asarray(dst.reshape(-1)),
            edge_mask=jnp.ones((EG,), bool),
            node_graph=jnp.asarray(node_graph.astype(np.int32)),
            node_mask=jnp.ones((NG,), bool),
            labels=jnp.asarray(np.asarray(labels).astype(np.int32)),
        )

    return Cell(arch=arch, shape=shape, mesh=mesh, step_fn=step_fn,
                abstract_state=abstract_state, batch_specs=batch_specs,
                state_shardings=state_spec, init_state=init_fn, make_batch=make_batch,
                donate_state=opts.donate_state)
