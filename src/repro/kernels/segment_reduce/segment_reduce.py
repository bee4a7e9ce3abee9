"""Segment reduction as blocked one-hot matmul on the MXU.

TPU adaptation of the paper's atomic-operation optimization (§2.2.2): a GPU
does variable-length embedding pooling with AtomicAdd and fights memory
contention with warp-level merging. A TPU has no atomics — instead we turn
the reduction into *compute*: for a VMEM tile of values (TN, D) and their
segment ids, build the one-hot matrix ``oh[TN, TS] = (seg == segment ids of
the out tile)`` and accumulate ``ohᵀ @ values`` into the (TS, D) output tile
with the MXU. Contention-free by construction; the paper's "adjacent rows
reduce together" locality insight survives as tile-local accumulation in
VMEM before any HBM write.

Grid layout: ``(S_tiles, N_tiles)`` with N innermost so each output tile
stays resident in VMEM across the whole values stream and is written to HBM
exactly once (maximum MBU: out traffic = S·D·4 bytes, the lower bound).

For *sorted* segment ids (the CSR layout guarantees this) almost every
(s, n) pair is empty. The kernel stays dense across the grid — on TPU the
win would come from a `pl.when` skip driven by a prefetched per-tile
[min_seg, max_seg) range; that variant is `seg_bounds` below and is what
`ops.segment_sum(..., skip_empty=True)` uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ZERO


def _kernel(seg_ref, val_ref, out_ref, *, ts: int, tn: int):
    """One (s, n) grid step: accumulate ohᵀ @ values into out tile s."""
    n = pl.program_id(1)
    s = pl.program_id(0)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...].reshape(tn)                       # (TN,) int32
    vals = val_ref[...]                                  # (TN, D) f32
    seg_base = s * ts
    # one-hot: oh[i, j] = (seg[i] == seg_base + j)  → (TN, TS)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tn, ts), 1) + seg_base
    oh = (seg[:, None] == cols).astype(vals.dtype)
    # MXU matmul: (TS, TN) @ (TN, D) — fp32 accumulation
    out_ref[...] += jax.lax.dot_general(
        oh, vals, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def _kernel_skip(bounds_ref, seg_ref, val_ref, out_ref, *, ts: int, tn: int):
    """Sorted-segment variant: skip value tiles that cannot touch out tile s.

    ``bounds_ref`` is a scalar-prefetch (N_tiles, 2) int32 array of each value
    tile's [min_seg, max_seg] — computed host/XLA-side in ops.py. The `pl.when`
    predicate keeps the MXU idle for non-overlapping (s, n) pairs, which for
    CSR-sorted inputs reduces the executed work from O(S·N) to O(S + N) tiles.
    """
    n = pl.program_id(1)
    s = pl.program_id(0)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo = bounds_ref[n, 0]
    hi = bounds_ref[n, 1]
    seg_base = s * ts

    @pl.when(jnp.logical_and(hi >= seg_base, lo < seg_base + ts))
    def _accum():
        seg = seg_ref[...].reshape(tn)
        vals = val_ref[...]
        cols = jax.lax.broadcasted_iota(jnp.int32, (tn, ts), 1) + seg_base
        oh = (seg[:, None] == cols).astype(vals.dtype)
        out_ref[...] += jax.lax.dot_general(
            oh, vals, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "ts", "tn", "interpret", "skip_empty")
)
def segment_sum_padded(
    values: jax.Array,        # (N, D) f32, N % tn == 0, D lane-padded by caller
    segment_ids: jax.Array,   # (N,) int32; out-of-range ids are dropped
    num_segments: int,        # S, % ts == 0
    *,
    ts: int,
    tn: int,
    interpret: bool,
    skip_empty: bool,
) -> jax.Array:
    n, d = values.shape
    assert n % tn == 0 and num_segments % ts == 0, (n, tn, num_segments, ts)
    grid = (num_segments // ts, n // tn)
    seg2d = segment_ids.astype(jnp.int32).reshape(n, 1)

    if skip_empty:
        tiles = segment_ids.astype(jnp.int32).reshape(n // tn, tn)
        bounds = jnp.stack([tiles.min(axis=1), tiles.max(axis=1)], axis=1)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tn, 1), lambda s, n_, b: (n_, ZERO)),
                pl.BlockSpec((tn, d), lambda s, n_, b: (n_, ZERO)),
            ],
            out_specs=pl.BlockSpec((ts, d), lambda s, n_, b: (s, ZERO)),
        )
        return pl.pallas_call(
            functools.partial(_kernel_skip, ts=ts, tn=tn),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_segments, d), values.dtype),
            interpret=interpret,
        )(bounds, seg2d, values)

    return pl.pallas_call(
        functools.partial(_kernel, ts=ts, tn=tn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, 1), lambda s, n_: (n_, ZERO)),
            pl.BlockSpec((tn, d), lambda s, n_: (n_, ZERO)),
        ],
        out_specs=pl.BlockSpec((ts, d), lambda s, n_: (s, ZERO)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), values.dtype),
        interpret=interpret,
    )(seg2d, values)
