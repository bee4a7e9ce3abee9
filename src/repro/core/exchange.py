"""Sharded embedding exchange — RecIS §2.2.2 "Load Balancing".

Implements the paper's aggregation-and-full-sharding dataflow on a JAX mesh:

  requester side                         owner side
  --------------                         ----------
  ids (this device's batch slice)
    → unique ("ids partition")
    → hash-shard by owner  ──all_to_all──→ merge + unique recv'd ids
                                           → IDMap lookup_or_insert
                                           → Blocks gather rows
  rows for my requests    ←──all_to_all──  per-request rows
    → un-bucket to unique order
    → expand to per-value rows
    → segment-reduce pooling

Row storage is hash-sharded over **all** mesh axes (the paper's "evenly
distributed across multiple GPUs"); the Law of Large Numbers gives balance.
Everything below runs inside `shard_map` over the full mesh.

Static budgets (TPU needs static shapes — DESIGN.md §2 assumption (b)):
  L  ids per device per step (padded input)
  U  unique ids per device          (requester dedupe budget)
  C  ids per destination device     (send-bucket capacity)
  R  unique recv'd ids per device   (owner merge budget)
Overflow at any stage routes to the overflow row and is *counted* in
metrics, never silently mixed into a wrong row.

The differentiable part (`route_rows`) is linear in the gathered owner rows,
so JAX's autodiff produces the reverse all-to-all for the gradient path
automatically — the paper's backward all-to-all — and the `invR` gather
transposes into the owner-side duplicate-merging scatter-add.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import blocks as blocks_lib
from repro.core import idmap as idmap_lib
from repro.core.feature_engine import splitmix64
from repro.obs.stages import stage

PAD = jnp.int64(-1)


@dataclasses.dataclass(frozen=True)
class ExchangeSpec:
    """Static budgets + mesh axes of one embedding dim-group's exchange."""

    axes: tuple[str, ...]  # mesh axes the table is sharded over (all axes)
    n_devices: int         # product of axis sizes (static)
    u_budget: int          # U
    per_dest_cap: int      # C
    recv_budget: int       # R  (≤ n_devices * C)

    def __post_init__(self):
        assert self.recv_budget <= self.n_devices * self.per_dest_cap


class Plan(NamedTuple):
    """Integer routing state retained from the forward pass (per device)."""

    inv_u: jax.Array      # (L,)   value index   → unique index
    ok_val: jax.Array     # (L,)   value survived dedupe budget & not PAD
    owner_u: jax.Array    # (U,)   unique index  → owner device
    pos_u: jax.Array      # (U,)   unique index  → slot within owner bucket
    ok_u: jax.Array       # (U,)   unique id made it into the send buffer
    inv_r: jax.Array      # (D*C,) request slot  → owner-unique index
    ok_r: jax.Array       # (D*C,) request slot survived owner merge (and not PAD)
    offsets_r: jax.Array  # (R,)   owner-unique index → Blocks row
    valid_r: jax.Array    # (R,)   owner-unique id is live (not fill)


def _owner_of(ids: jax.Array, n_devices: int) -> jax.Array:
    """Owner shard of an id. Uses high bits of a re-mix so the choice is
    independent of the IDMap's slot hash."""
    mix = splitmix64(ids.astype(jnp.uint64) ^ jnp.uint64(0xA24BAED4963EE407))
    own = (mix % jnp.uint64(n_devices)).astype(jnp.int32)
    return jnp.where(ids == PAD, n_devices, own)


def unique(ids: jax.Array, size: int) -> tuple[jax.Array, jax.Array]:
    """Sorted unique ids padded with PAD to ``size``, and each id's index in
    them — ``jnp.unique(ids, size=size, fill_value=PAD, return_inverse=True)``
    with ONE sort. Uniques past ``size`` are dropped; their ids get index
    ``size - 1``, which callers detect as ``uniq[inv] != ids``.

    jnp.unique sorts twice (the values, then a stable argsort for the
    inverse); each large 64-bit sort costs tens of seconds of TPU compile.
    """
    n = ids.shape[0]
    srt, perm = jax.lax.sort((ids, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    first = jnp.concatenate([jnp.ones((1,), bool), srt[1:] != srt[:-1]])
    g = jnp.cumsum(first.astype(jnp.int32)) - 1          # unique index, sorted order
    uniq = jnp.full((size,), PAD, ids.dtype).at[jnp.where(first, g, size)].set(
        srt, mode="drop")
    inv = jnp.zeros((n,), jnp.int32).at[perm].set(jnp.minimum(g, size - 1))
    return uniq, inv


def build_send(
    ids: jax.Array, spec: ExchangeSpec
) -> tuple[jax.Array, Plan, dict]:
    """Requester side: dedupe + bucket-by-owner. Returns (send_ids[D,C], plan⁰)."""
    D, U, C = spec.n_devices, spec.u_budget, spec.per_dest_cap
    uniq, inv = unique(ids, U)
    # budget overflow: a value whose unique was truncated points at a wrong
    # slot — detect and mask (counted).
    ok_val = (uniq[inv] == ids) & (ids != PAD)

    owner = _owner_of(uniq, D)
    order = jnp.argsort(owner, stable=True)
    sowner = owner[order]
    start = jnp.searchsorted(sowner, jnp.arange(D, dtype=sowner.dtype))
    pos_sorted = jnp.arange(U, dtype=jnp.int32) - start[jnp.clip(sowner, 0, D - 1)].astype(jnp.int32)
    ok_sorted = (sowner < D) & (pos_sorted < C)
    dst_r = jnp.where(ok_sorted, sowner, D)
    dst_c = jnp.where(ok_sorted, pos_sorted, 0)
    send = jnp.full((D, C), PAD, dtype=jnp.int64).at[dst_r, dst_c].set(
        uniq[order], mode="drop"
    )
    # scatter bucket coordinates back to unique order
    owner_u = jnp.zeros((U,), jnp.int32).at[order].set(sowner.astype(jnp.int32))
    pos_u = jnp.zeros((U,), jnp.int32).at[order].set(pos_sorted)
    ok_u = jnp.zeros((U,), jnp.bool_).at[order].set(ok_sorted)

    plan = Plan(
        inv_u=inv, ok_val=ok_val, owner_u=owner_u, pos_u=pos_u, ok_u=ok_u,
        inv_r=jnp.zeros((D * C,), jnp.int32), ok_r=jnp.zeros((D * C,), jnp.bool_),
        offsets_r=jnp.zeros((spec.recv_budget,), jnp.int32),
        valid_r=jnp.zeros((spec.recv_budget,), jnp.bool_),
    )
    metrics = {
        "exch_uniq_overflow": ((ids != PAD) & ~ok_val).sum(dtype=jnp.int32),
        "exch_send_overflow": ((owner < D) & ~ok_u).sum(dtype=jnp.int32),
        # ids in, and left after the dedupe (of U, and of the D*C sent)
        "exch_ids": (ids != PAD).sum(dtype=jnp.int32),
        "exch_uniq": (uniq != PAD).sum(dtype=jnp.int32),
    }
    return send, plan, metrics


def owner_merge(recv_ids: jax.Array, spec: ExchangeSpec) -> tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Owner side: merge + unique the D*C received ids (paper's request merge)."""
    flat = recv_ids.reshape(-1)
    uniq_r, inv_r = unique(flat, spec.recv_budget)
    ok_r = (uniq_r[inv_r] == flat) & (flat != PAD)
    metrics = {
        "exch_recv_overflow": ((flat != PAD) & ~ok_r).sum(dtype=jnp.int32),
        # ids left after the owner merge, of the R rows every row op processes
        "exch_recv_uniq": (uniq_r != PAD).sum(dtype=jnp.int32),
    }
    return uniq_r, inv_r, ok_r, metrics


def fetch(
    m: idmap_lib.IDMap,
    b: blocks_lib.Blocks,
    ids: jax.Array,
    spec: ExchangeSpec,
    step: jax.Array,
    train: bool,
) -> tuple[idmap_lib.IDMap, blocks_lib.Blocks, jax.Array, Plan, dict]:
    """Non-differentiable phase: routing + IDMap insert + row gather.

    Returns (idmap', blocks', rows_r [R, dim], plan, metrics). ``rows_r`` is
    the compact per-owner-unique row matrix — the ONLY tensor the
    differentiable phase depends on.
    """
    with stage("recis.exchange.bucket"):
        send, plan, met1 = build_send(ids, spec)
    if spec.axes and spec.n_devices > 1:
        with stage("recis.exchange.all_to_all"):
            recv = jax.lax.all_to_all(send, spec.axes, split_axis=0, concat_axis=0, tiled=True)
    else:  # single-device fast path (smoke tests)
        recv = send
    with stage("recis.exchange.owner_merge"):
        uniq_r, inv_r, ok_r, met2 = owner_merge(recv, spec)
    if train:
        m, offsets_r, is_new, met3 = idmap_lib.lookup_or_insert(m, uniq_r, step)
        with stage("recis.blocks.init_rows"):
            b = blocks_lib.init_rows(b, offsets_r, uniq_r, is_new)
    else:
        offsets_r = idmap_lib.lookup(m, uniq_r)
        met3 = {}
    # Ids that landed on the reserved overflow row (probe/row-capacity
    # exhaustion, or missing at serve time) act as ZERO embeddings and are
    # excluded from updates: several distinct ids share row 0, so training
    # it would accumulate duplicate Adam updates and blow up — graceful
    # degradation instead (the overflow is already counted in metrics).
    with stage("recis.blocks.gather"):
        valid_r = (uniq_r != PAD) & (offsets_r != idmap_lib.OVERFLOW_ROW)
        rows_r = blocks_lib.gather(b, offsets_r) * valid_r[:, None].astype(b.emb.dtype)
    plan = plan._replace(
        inv_r=inv_r, ok_r=ok_r, offsets_r=offsets_r, valid_r=valid_r
    )
    return m, b, rows_r, plan, {**met1, **met2, **met3}


def route_rows(rows_r: jax.Array, plan: Plan, spec: ExchangeSpec) -> jax.Array:
    """Differentiable phase: owner rows [R, dim] → per-value rows [L, dim].

    Linear map; its transpose (generated by jax.grad) is the backward
    all-to-all + owner-side duplicate-summing scatter of the paper.
    """
    D, C = spec.n_devices, spec.per_dest_cap
    dim = rows_r.shape[-1]
    with stage("recis.embed.route"):
        per_req = rows_r[plan.inv_r] * plan.ok_r[:, None].astype(rows_r.dtype)
        back = per_req.reshape(D, C, dim)
    if spec.axes and spec.n_devices > 1:
        with stage("recis.exchange.all_to_all"):
            back = jax.lax.all_to_all(back, spec.axes, split_axis=0, concat_axis=0, tiled=True)
    with stage("recis.embed.route"):
        uniq_rows = back[plan.owner_u, plan.pos_u] * plan.ok_u[:, None].astype(rows_r.dtype)
        return uniq_rows[plan.inv_u] * plan.ok_val[:, None].astype(rows_r.dtype)
