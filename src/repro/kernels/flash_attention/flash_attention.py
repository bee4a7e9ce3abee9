"""Causal FlashAttention (fwd + bwd) with explicit BlockSpec VMEM tiling.

Paper §2.2.3: the dense component's compute wall is broken with fused
attention kernels. TPU mapping of the FlashAttention-2 schedule:

  forward   grid (BH, nQ, nK), K innermost. Q tile (TQ, hd) stays in VMEM
            across the K stream; online-softmax stats (m, l) and the fp32
            accumulator live in VMEM scratch that persists across grid
            steps (TPU grids are sequential per core). Causal blocks with
            kb > qb are predicated off with `pl.when` — the MXU sees only
            the lower-triangle tiles, halving compute.
  backward  two kernels, same tiling discipline:
              dkv: grid (BH, nK, nQ) — dK,dV accumulate per K tile.
              dq : grid (BH, nQ, nK) — dQ accumulates per Q tile.
            Stats are not recomputed: the forward saves LSE = m + log l
            (one (BH, T) fp32 vector — the FlashAttention-2 trick), and
            the backward re-materializes P = exp(S·scale − LSE) in VMEM.

All matmuls run through the MXU with fp32 accumulation
(`preferred_element_type=f32`); hd and tiles are 128-aligned by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ZERO

NEG_INF = np.float32(-1e30)


def _dot(a, b, ta=False, tb=False):
    dims = (((0,) if ta else (1,), (1,) if tb else (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, tq: int, tk: int, scale: float, causal: bool, nk: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    run = jnp.logical_or(not causal, kb * tk <= qb * tq + tq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)             # (TQ, hd)
        k = k_ref[0].astype(jnp.float32)             # (TK, hd)
        s = _dot(q, k, tb=True) * scale              # (TQ, TK)
        if causal:
            rows = qb * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            cols = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_sc[...]                           # (TQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # (TQ, TK)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
        m_sc[...] = m_new
        acc[...] = acc[...] * alpha + _dot(p, v_ref[0].astype(jnp.float32))

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[...] + jnp.log(l)).astype(lse_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tq", "tk", "causal", "interpret", "scale")
)
def flash_fwd(
    q: jax.Array,  # (BH, T, hd) — B and H pre-flattened, hd 128-aligned
    k: jax.Array,
    v: jax.Array,
    *,
    tq: int,
    tk: int,
    causal: bool,
    interpret: bool,
    scale: float,  # 1/sqrt(UNPADDED head dim)
) -> tuple[jax.Array, jax.Array]:
    bh, t, hd = q.shape
    assert t % tq == 0 and t % tk == 0
    nq, nk = t // tq, t // tk
    grid = (bh, nq, nk)
    out_shapes = (
        jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),   # LSE
    )
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, tq=tq, tk=tk, scale=scale,
                          causal=causal, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda b, qb, kb: (b, qb, ZERO)),
            pl.BlockSpec((1, tk, hd), lambda b, qb, kb: (b, kb, ZERO)),
            pl.BlockSpec((1, tk, hd), lambda b, qb, kb: (b, kb, ZERO)),
        ],
        out_specs=(
            pl.BlockSpec((1, tq, hd), lambda b, qb, kb: (b, qb, ZERO)),
            pl.BlockSpec((1, tq, 1), lambda b, qb, kb: (b, qb, ZERO)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((tq, hd), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward: dkv kernel (grid over K tiles, Q innermost)
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, tq: int, tk: int, scale: float, causal: bool, nq: int):
    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = jnp.logical_or(not causal, qb * tq + tq - 1 >= kb * tk)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)              # (TQ, hd)
        k = k_ref[0].astype(jnp.float32)              # (TK, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)            # (TQ, hd)
        lse = lse_ref[0]                              # (TQ, 1)
        delta = delta_ref[0]                          # (TQ, 1) rowsum(dO·O)
        s = _dot(q, k, tb=True) * scale               # (TQ, TK)
        if causal:
            rows = qb * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            cols = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                          # (TQ, TK)
        dv_acc[...] += _dot(p, do, ta=True)           # Pᵀ dO → (TK, hd)
        dp = _dot(do, v, tb=True)                     # (TQ, TK)
        ds = p * (dp - delta) * scale
        dk_acc[...] += _dot(ds, q, ta=True)           # dSᵀ Q → (TK, hd)

    @pl.when(qb == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dq kernel (grid over Q tiles, K innermost)
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc,
               *, tq: int, tk: int, scale: float, causal: bool, nk: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = jnp.logical_or(not causal, kb * tk <= qb * tq + tq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = _dot(q, k, tb=True) * scale
        if causal:
            rows = qb * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            cols = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, tb=True)
        ds = p * (dp - delta) * scale                 # (TQ, TK)
        dq_acc[...] += _dot(ds, k)                    # (TQ, hd)

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tq", "tk", "causal", "interpret", "scale")
)
def flash_bwd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    o: jax.Array, lse: jax.Array, do: jax.Array,
    *,
    tq: int, tk: int, causal: bool, interpret: bool,
    scale: float,  # 1/sqrt(UNPADDED head dim)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    bh, t, hd = q.shape
    assert t % tq == 0 and t % tk == 0, (t, tq, tk)
    nq, nk = t // tq, t // tk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (BH,T)
    lse3 = lse[..., None]
    delta3 = delta[..., None]

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, tq=tq, tk=tk, scale=scale,
                          causal=causal, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda b, kb, qb: (b, qb, ZERO)),
            pl.BlockSpec((1, tk, hd), lambda b, kb, qb: (b, kb, ZERO)),
            pl.BlockSpec((1, tk, hd), lambda b, kb, qb: (b, kb, ZERO)),
            pl.BlockSpec((1, tq, hd), lambda b, kb, qb: (b, qb, ZERO)),
            pl.BlockSpec((1, tq, 1), lambda b, kb, qb: (b, qb, ZERO)),
            pl.BlockSpec((1, tq, 1), lambda b, kb, qb: (b, qb, ZERO)),
        ],
        out_specs=(
            pl.BlockSpec((1, tk, hd), lambda b, kb, qb: (b, kb, ZERO)),
            pl.BlockSpec((1, tk, hd), lambda b, kb, qb: (b, kb, ZERO)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((tk, hd), jnp.float32),
            pltpu.VMEM((tk, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, tq=tq, tk=tk, scale=scale,
                          causal=causal, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda b, qb, kb: (b, qb, ZERO)),
            pl.BlockSpec((1, tk, hd), lambda b, qb, kb: (b, kb, ZERO)),
            pl.BlockSpec((1, tk, hd), lambda b, qb, kb: (b, kb, ZERO)),
            pl.BlockSpec((1, tq, hd), lambda b, qb, kb: (b, qb, ZERO)),
            pl.BlockSpec((1, tq, 1), lambda b, qb, kb: (b, qb, ZERO)),
            pl.BlockSpec((1, tq, 1), lambda b, qb, kb: (b, qb, ZERO)),
        ],
        out_specs=pl.BlockSpec((1, tq, hd), lambda b, qb, kb: (b, qb, ZERO)),
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((tq, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv
