"""GQA attention with RoPE, KV-cache decode, and distributed flash-decode.

Supports the LM-family archs' head layouts (MHA kv=H, GQA kv<H, MQA kv=1)
plus optional QKV bias (Qwen-style). The decode path supports a
sequence-sharded KV cache: each shard computes local softmax statistics and
the shards combine with a 2-term psum — a TPU-native distributed
flash-decode (DESIGN.md §5 "SP"), which is what makes the `long_500k`
(524k-token KV) decode cell feasible.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import MIXED, Precision, dense_apply, dense_pspec, make_dense

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def make_attn(rng, cfg: AttnConfig) -> dict:
    kq, kk, kv, ko = jax.random.split(rng, 4)
    hd = cfg.head_dim
    return {
        "wq": make_dense(kq, cfg.d_model, cfg.n_heads * hd, bias=cfg.qkv_bias),
        "wk": make_dense(kk, cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": make_dense(kv, cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": make_dense(ko, cfg.n_heads * hd, cfg.d_model, bias=False),
    }


def attn_pspec(cfg: AttnConfig, shard_kv: bool) -> dict:
    """TP: shard q heads over "model"; kv heads too when divisible."""
    kv_spec = "model" if shard_kv else None
    return {
        "wq": dense_pspec(None, "model", bias=cfg.qkv_bias),
        "wk": dense_pspec(None, kv_spec, bias=cfg.qkv_bias),
        "wv": dense_pspec(None, kv_spec, bias=cfg.qkv_bias),
        "wo": dense_pspec("model", None, bias=False),
    }


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, hd); positions: (..., T) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# core attention
# ---------------------------------------------------------------------------

def _expand_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, Hk, hd) → (B, S, Hk*G, hd) by repeating each kv head G times."""
    b, s, hk, hd = k.shape
    return jnp.repeat(k, groups, axis=2)


def causal_attention(
    q: jax.Array,  # (B, T, H, hd)
    k: jax.Array,  # (B, T, Hk, hd)
    v: jax.Array,
    prec: Precision = MIXED,
    impl: str = "chunked",
) -> jax.Array:
    """Causal attention for train/prefill, three implementations:

    naive   — materializes fp32 (B,H,T,T) scores in HBM. The unfused
              comparator (what the paper's Table 2 calls "PyTorch").
    chunked — FlashAttention dataflow in pure XLA ops: scan over KV blocks
              with running (max, denom, out) so no T² tensor ever hits HBM.
              This is the paper-faithful fused path (§2.2.3) and the exact
              blocking the Pallas kernel implements on real TPUs.
    pallas  — the Pallas kernel (kernels/flash_attention); TPU runtime path,
              validated on CPU via interpret=True in tests.
    skip    — COST-ACCOUNTING ONLY (dry-run layer extrapolation): the core
              is replaced by identity so XLA measures everything-but-
              attention; the kernel's analytic flop/byte model is added
              back (roofline.flash_attention_cost). Never used for math.
    """
    b, t, h, hd = q.shape
    if impl == "skip":
        return q.reshape(b, t, h * hd)
    g = h // k.shape[2]
    if impl == "pallas":
        if t % 128:
            raise ValueError(f"impl='pallas' needs seq_len % 128 == 0, got {t}")
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(
            q, _expand_kv(k, g), _expand_kv(v, g), causal=True
        ).reshape(b, t, h * hd)
    if impl == "chunked":
        return _chunked_causal(q, k, v, prec).reshape(b, t, h * hd)
    k = _expand_kv(k, g)
    v = _expand_kv(v, g)
    scale = np.float32(1.0 / np.sqrt(hd))
    s = jnp.einsum("bthd,bshd->bhts", prec.cast(q), prec.cast(k)).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", prec.cast(p), prec.cast(v))
    return o.reshape(b, t, h * hd)


def _chunked_causal(q, k, v, prec: Precision, q_chunk: int = 1024,
                    k_chunk: int = 1024) -> jax.Array:
    """Online-softmax (flash) attention: O(T·d) HBM traffic, fp32 stats."""
    b, t, h, hd = q.shape
    hk = k.shape[2]
    g = h // hk
    cq = min(q_chunk, t)
    ck = min(k_chunk, t)
    nq, nk = t // cq, t // ck
    scale = np.float32(1.0 / np.sqrt(hd))
    qc = prec.cast(q).reshape(b, nq, cq, hk, g, hd)
    kc = prec.cast(k).reshape(b, nk, ck, hk, hd)
    vc = prec.cast(v).reshape(b, nk, ck, hk, hd)
    pos_q = jnp.arange(cq)
    pos_k = jnp.arange(ck)

    def q_block(qi, qb):  # qb: (b, cq, hk, g, hd)
        m0 = jnp.full((b, hk, g, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hk, g, cq), jnp.float32)
        o0 = jnp.zeros((b, hk, g, cq, hd), jnp.float32)

        def k_block(carry, ki):
            m, l, o = carry
            kb, vb = kc[:, ki], vc[:, ki]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32) * scale
            mask = (qi * cq + pos_q)[:, None] >= (ki * ck + pos_k)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m2 = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m2)
            p = jnp.exp(s - m2[..., None])
            l = l * alpha + p.sum(-1)
            o = o * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(qb.dtype), vb).astype(jnp.float32)
            return (m2, l, o), None

        (m, l, o), _ = jax.lax.scan(k_block, (m0, l0, o0), jnp.arange(nk))
        out = o / jnp.maximum(l[..., None], 1e-30)             # (b,hk,g,cq,hd)
        return out.transpose(0, 3, 1, 2, 4)                     # (b,cq,hk,g,hd)

    outs = jax.lax.map(lambda i: q_block(i, qc[:, i]), jnp.arange(nq))  # (nq,b,cq,hk,g,hd)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h, hd)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,        # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S_local, Hk, hd)
    v_cache: jax.Array,
    pos: jax.Array,      # () int32 — global position of the new token
    seq_axis: str | tuple | None = None,
    prec: Precision = MIXED,
) -> jax.Array:
    """Single-token attention against a (possibly sequence-sharded) KV cache.

    When ``seq_axis`` names mesh axes, the cache holds this shard's slice of
    the sequence and shards combine softmax statistics with psum — the
    distributed flash-decode. O(S_local) per chip.
    """
    b, _, h, hd = q.shape
    s_local = k_cache.shape[1]
    g = h // k_cache.shape[2]
    k = _expand_kv(k_cache, g)
    v = _expand_kv(v_cache, g)
    scale = np.float32(1.0 / np.sqrt(hd))

    if seq_axis is not None:
        shard = jax.lax.axis_index(seq_axis)
        offset = shard.astype(jnp.int32) * s_local
    else:
        offset = jnp.int32(0)
    gpos = offset + jnp.arange(s_local, dtype=jnp.int32)  # global positions
    valid = gpos <= pos  # causal: attend to positions ≤ pos (incl. new token)

    scores = jnp.einsum("bqhd,bshd->bhqs", prec.cast(q), prec.cast(k)).astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)  # (B, H, 1, 1) local max
    if seq_axis is not None:
        m_global = jax.lax.pmax(m, seq_axis)
    else:
        m_global = m
    p = jnp.exp(scores - m_global)
    l = jnp.sum(p, axis=-1, keepdims=True)                       # (B, H, 1, 1)
    o = jnp.einsum("bhqs,bshd->bqhd", prec.cast(p), prec.cast(v)).astype(jnp.float32)
    if seq_axis is not None:
        l = jax.lax.psum(l, seq_axis)
        o = jax.lax.psum(o, seq_axis)
    out = o / jnp.maximum(l.transpose(0, 2, 1, 3), 1e-30)
    return out.reshape(b, 1, h * hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# module-level apply
# ---------------------------------------------------------------------------

def attn_apply(
    p: dict,
    cfg: AttnConfig,
    x: jax.Array,             # (B, T, d)
    positions: jax.Array,     # (B, T)
    prec: Precision = MIXED,
    impl: str = "chunked",
) -> jax.Array:
    b, t, _ = x.shape
    hd = cfg.head_dim
    q = dense_apply(p["wq"], x, prec).reshape(b, t, cfg.n_heads, hd)
    k = dense_apply(p["wk"], x, prec).reshape(b, t, cfg.n_kv_heads, hd)
    v = dense_apply(p["wv"], x, prec).reshape(b, t, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = causal_attention(q, k, v, prec, impl=impl)
    return dense_apply(p["wo"], o, prec)


def attn_decode_apply(
    p: dict,
    cfg: AttnConfig,
    x: jax.Array,        # (B, 1, d)
    cache_k: jax.Array,  # (B, S_local, Hk, hd)
    cache_v: jax.Array,
    pos: jax.Array,      # () global position of this token
    seq_axis=None,
    prec: Precision = MIXED,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (out (B,1,d), new_cache_k, new_cache_v)."""
    b = x.shape[0]
    hd = cfg.head_dim
    q = dense_apply(p["wq"], x, prec).reshape(b, 1, cfg.n_heads, hd)
    k = dense_apply(p["wk"], x, prec).reshape(b, 1, cfg.n_kv_heads, hd)
    v = dense_apply(p["wv"], x, prec).reshape(b, 1, cfg.n_kv_heads, hd)
    ppos = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    q = apply_rope(q, ppos, cfg.rope_theta)
    k = apply_rope(k, ppos, cfg.rope_theta)

    s_local = cache_k.shape[1]
    if seq_axis is not None:
        shard = jax.lax.axis_index(seq_axis).astype(jnp.int32)
        local_pos = pos - shard * s_local
        in_range = (local_pos >= 0) & (local_pos < s_local)
        idx = jnp.clip(local_pos, 0, s_local - 1)
        upd_k = jnp.where(in_range, k.astype(cache_k.dtype), cache_k[:, idx][:, None].astype(cache_k.dtype))
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, upd_k, idx, axis=1)
        upd_v = jnp.where(in_range, v.astype(cache_v.dtype), cache_v[:, idx][:, None].astype(cache_v.dtype))
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, upd_v, idx, axis=1)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)

    o = decode_attention(q, cache_k, cache_v, pos, seq_axis, prec)
    out = dense_apply(p["wo"], o, prec)
    return out, cache_k, cache_v
