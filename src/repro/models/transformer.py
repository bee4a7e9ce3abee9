"""Transformer LM stack (the paper's "large-scale dense component", §2.2.3).

Llama-family: RMSNorm → GQA attention → RMSNorm → SwiGLU (or MoE) with
residuals, RoPE positions, vocab head. Layers are scanned (stacked params)
so the HLO stays compact at 52 layers and the dry-run compiles fast.

Distribution (GSPMD + shard_map islands; DESIGN.md §5):
  * TP: attention heads + FFN hidden sharded over "model" (Megatron),
  * SP: the residual stream between blocks is sequence-sharded over
    "model" (`P(dp, "model", None)`) so saved activations fit HBM,
  * EP: MoE layers dispatch via shard_map sort-based all_to_all,
  * decode: sequence-sharded KV cache + distributed flash-decode psum.

Token embeddings come from the Embedding Engine (sparse side) and enter
here as dense activations; the LM head is a TP-sharded dense param.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import moe as moe_lib
from jax import shard_map
from repro.models.layers import (
    MIXED, Precision, dense_apply, dense_pspec, make_dense, make_rmsnorm,
    make_swiglu, rmsnorm_apply, swiglu_apply, swiglu_pspec,
)


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    """How this model maps onto the mesh. None mesh = single-device smoke."""

    mesh: Any = None
    dp: tuple[str, ...] = ()          # batch axes
    tp: str | None = None              # tensor/EP axis
    seq_shards: tuple[str, ...] = ()   # KV-cache sequence shard axes (decode)

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp] if (self.mesh and self.tp) else 1

    @property
    def dp_size(self) -> int:
        if not (self.mesh and self.dp):
            return 1
        import numpy as _np

        return int(_np.prod([self.mesh.shape[a] for a in self.dp]))

    def wsc(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(self.mesh, P(*spec)))


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    moe: moe_lib.MoEConfig | None = None
    remat: bool = True
    remat_policy: str = "full"   # full | dots (save MXU outputs, recompute rest)
    scan_layers: bool = True  # False → python loop (dry-run flop accounting)

    @property
    def attn_cfg(self) -> attn.AttnConfig:
        return attn.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, qkv_bias=self.qkv_bias,
            rope_theta=self.rope_theta,
        )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def param_count(self) -> int:
        """Dense-equivalent N for MODEL_FLOPS = 6·N·D (active params for MoE)."""
        d, hd = self.d_model, self.head_dim
        attn_p = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.moe is None:
            ffn_p = 3 * d * self.d_ff
        else:  # active experts only
            ffn_p = 3 * d * self.moe.d_ff * (self.moe.top_k + self.moe.n_shared) + d * self.moe.n_experts
        return self.n_layers * (attn_p + ffn_p) + 2 * d * self.vocab_size


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _make_layer(rng, cfg: TransformerConfig, ep_size: int) -> dict:
    k1, k2 = jax.random.split(rng)
    p = {
        "attn_norm": make_rmsnorm(cfg.d_model),
        "attn": attn.make_attn(k1, cfg.attn_cfg),
        "ffn_norm": make_rmsnorm(cfg.d_model),
    }
    if cfg.moe is None:
        p["ffn"] = make_swiglu(k2, cfg.d_model, cfg.d_ff)
    else:
        # global (stacked) expert count, padded to a multiple of the EP size
        p["moe"] = moe_lib.make_moe(k2, cfg.moe, cfg.moe.n_local_experts(ep_size) * ep_size)
    return p


def init(rng, cfg: TransformerConfig, ep_size: int = 1) -> dict:
    kl, kh, kn = jax.random.split(rng, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _make_layer(k, cfg, ep_size))(layer_keys)
    return {
        "layers": layers,  # every leaf stacked on axis 0: (L, ...)
        "final_norm": make_rmsnorm(cfg.d_model),
        "head": make_dense(kh, cfg.d_model, cfg.vocab_size, bias=False),
    }


def pspec(cfg: TransformerConfig) -> dict:
    shard_kv = cfg.n_kv_heads >= 8  # only shard kv heads when divisible by tp
    layer = {
        "attn_norm": {"scale": P(None)},
        "attn": attn.attn_pspec(cfg.attn_cfg, shard_kv),
        "ffn_norm": {"scale": P(None)},
    }
    if cfg.moe is None:
        layer["ffn"] = swiglu_pspec()
    else:
        layer["moe"] = moe_lib.moe_pspec(cfg.moe)

    def add_layer_axis(p):
        return P(*((None,) + tuple(p)))

    layers = jax.tree.map(add_layer_axis, layer,
                          is_leaf=lambda x: isinstance(x, P))
    return {
        "layers": layers,
        "final_norm": {"scale": P(None)},
        "head": dense_pspec(None, "model", bias=False),
    }


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _ffn_block(lp: dict, cfg: TransformerConfig, h: jax.Array, ctx: MeshCtx,
               prec: Precision) -> tuple[jax.Array, jax.Array]:
    """Returns (ffn_out, aux_loss)."""
    b, t, d = h.shape
    if cfg.moe is None:
        return swiglu_apply(lp["ffn"], h, prec), jnp.float32(0.0)
    mcfg = cfg.moe
    ep = ctx.tp_size
    if ctx.mesh is None or ep == 1 or (t % ep) or (ctx.dp and b % ctx.dp_size):
        # decode (t == 1) & smoke paths: dense dispatch; GSPMD still computes
        # it expert-parallel from the P("model", ...) param sharding.
        y, aux, _ = _moe_single(lp["moe"], mcfg, h.reshape(-1, d), prec)
        return y.reshape(b, t, d), aux

    def body(x_loc, pp):
        y, aux, _ = moe_lib.moe_apply_local(pp, mcfg, x_loc.reshape(-1, d), ctx.tp, ep, prec)
        return y.reshape(x_loc.shape), aux

    y, aux = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ctx.dp, ctx.tp, None), moe_lib.moe_pspec(mcfg)),
        out_specs=(P(ctx.dp, ctx.tp, None), P()),
        check_vma=False,
    )(h, lp["moe"])
    return y, aux


def _moe_single(p, mcfg, x, prec):
    """Single-device MoE (smoke tests): dense top-k dispatch, no EP."""
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    top_w, top_e = jax.lax.top_k(probs, mcfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    y = jnp.zeros_like(x)
    e_total = p["gate"].shape[0]
    onehot = jax.nn.one_hot(top_e, e_total, dtype=x.dtype)       # (N, k, E)
    w_e = (onehot * top_w[..., None].astype(x.dtype)).sum(1)     # (N, E)
    g = jax.nn.silu(jnp.einsum("nd,edf->enf", prec.cast(x), prec.cast(p["gate"])))
    u = jnp.einsum("nd,edf->enf", prec.cast(x), prec.cast(p["up"]))
    ye = jnp.einsum("enf,efd->end", g * u, prec.cast(p["down"]))
    y = jnp.einsum("end,ne->nd", ye, w_e.astype(ye.dtype))
    # aux loss over the REAL expert count (router logits span n_experts;
    # e_total may be padded up to a multiple of the EP size, e.g. 60 → 64)
    me = probs.mean(0)
    ce = jnp.zeros((mcfg.n_experts,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (x.shape[0] * mcfg.top_k)
    aux = mcfg.router_aux_weight * mcfg.n_experts * jnp.sum(me * ce)
    if mcfg.n_shared:
        sh = p["shared"]
        gs = jax.nn.silu(prec.cast(x) @ prec.cast(sh["gate"]))
        us = prec.cast(x) @ prec.cast(sh["up"])
        y = y + (gs * us) @ prec.cast(sh["down"])
    return y.astype(x.dtype), aux, {}


def sp_layer_applicable(cfg: TransformerConfig, ctx: MeshCtx) -> bool:
    return (ctx.mesh is not None and bool(ctx.tp) and ctx.tp_size > 1
            and cfg.moe is None and cfg.n_heads % ctx.tp_size == 0)


def _layer_body_sp(lp: dict, cfg: TransformerConfig, x: jax.Array,
                   ctx: MeshCtx, prec: Precision, attn_impl: str) -> jax.Array:
    """Manual Megatron-SP layer under shard_map — the `sp_residual` lever.

    The residual stream stays sequence-sharded over the TP axis. Each
    boundary is ONE explicit collective of N bytes:
      g  all_gather(seq)      before qkv / gate-up (column-parallel in)
      ḡ  psum_scatter(seq)    after wo / down (row-parallel out) — the
                              matmul's partial products stay LOCAL until
                              this reduce-scatter, folding the TP psum and
                              the sequence re-shard into one op.
    GSPMD's generic resharding of the same dataflow emits masked
    all-reduces (2N bytes each) — §Perf measures the halving.
    Autodiff inside shard_map transposes all_gather ↔ psum_scatter, so the
    backward gets the mirrored schedule for free.
    """
    tp, tp_size = ctx.tp, ctx.tp_size
    hd = cfg.head_dim
    h_loc = cfg.n_heads // tp_size
    kv_shard = cfg.n_kv_heads % tp_size == 0 and cfg.n_kv_heads >= tp_size
    kv_loc = cfg.n_kv_heads // tp_size if kv_shard else cfg.n_kv_heads
    q_per_kv = cfg.n_heads // cfg.n_kv_heads

    def body(x_loc, lpp):
        b, t_loc, d = x_loc.shape
        t = t_loc * tp_size
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        # ---- attention
        h = rmsnorm_apply(lpp["attn_norm"], x_loc)
        h = jax.lax.all_gather(h, tp, axis=1, tiled=True)          # g
        q = dense_apply(lpp["attn"]["wq"], h, prec).reshape(b, t, h_loc, hd)
        k = dense_apply(lpp["attn"]["wk"], h, prec).reshape(b, t, kv_loc, hd)
        v = dense_apply(lpp["attn"]["wv"], h, prec).reshape(b, t, kv_loc, hd)
        q = attn.apply_rope(q, positions, cfg.rope_theta)
        k = attn.apply_rope(k, positions, cfg.rope_theta)
        if not kv_shard:
            # kv replicated (GQA kv ∤ tp): select each LOCAL q head's kv
            # head so attention runs 1:1 — shard s owns q heads
            # [s·h_loc, …); global q head g uses kv head g // q_per_kv.
            shard = jax.lax.axis_index(tp).astype(jnp.int32)
            qidx = shard * h_loc + jnp.arange(h_loc, dtype=jnp.int32)
            k = jnp.take(k, qidx // q_per_kv, axis=2)
            v = jnp.take(v, qidx // q_per_kv, axis=2)
        o = attn.causal_attention(q, k, v, prec, impl=attn_impl)
        a_part = dense_apply(lpp["attn"]["wo"], o, prec)           # partial sum
        x_loc = x_loc + jax.lax.psum_scatter(a_part, tp, scatter_dimension=1,
                                             tiled=True)           # ḡ
        # ---- ffn
        h = rmsnorm_apply(lpp["ffn_norm"], x_loc)
        h = jax.lax.all_gather(h, tp, axis=1, tiled=True)          # g
        g = jax.nn.silu(dense_apply(lpp["ffn"]["gate"], h, prec))
        u = dense_apply(lpp["ffn"]["up"], h, prec)
        f_part = dense_apply(lpp["ffn"]["down"], g * u, prec)      # partial sum
        return x_loc + jax.lax.psum_scatter(f_part, tp, scatter_dimension=1,
                                            tiled=True)            # ḡ

    # weight specs: column-parallel shard the LOCAL output dim, row-parallel
    # the LOCAL input dim; kv replicated when not divisible (GQA kv<tp).
    kv_spec = "model" if kv_shard else None
    wspec = {
        "attn_norm": {"scale": P(None)},
        "ffn_norm": {"scale": P(None)},
        "attn": attn.attn_pspec(cfg.attn_cfg, kv_shard),
        "ffn": swiglu_pspec(),
    }
    if cfg.qkv_bias and not kv_shard:
        pass  # attn_pspec already emits the right bias specs
    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ctx.dp or None, tp, None), wspec),
        out_specs=P(ctx.dp or None, tp, None), check_vma=False,
    )(x, lp)


def _layer_body(lp: dict, cfg: TransformerConfig, x: jax.Array, positions: jax.Array,
                ctx: MeshCtx, prec: Precision, attn_impl: str,
                sp_residual: bool = False) -> tuple[jax.Array, jax.Array]:
    # SP: residual stream sequence-sharded; attention needs full sequence.
    if sp_residual and sp_layer_applicable(cfg, ctx):
        return _layer_body_sp(lp, cfg, x, ctx, prec, attn_impl), jnp.float32(0.0)
    h = rmsnorm_apply(lp["attn_norm"], x)
    h = ctx.wsc(h, ctx.dp, None, None)  # gather sequence for attention
    a = attn.attn_apply(lp["attn"], cfg.attn_cfg, h, positions, prec, impl=attn_impl)
    x = x + ctx.wsc(a, ctx.dp, ctx.tp and "model", None)
    h = rmsnorm_apply(lp["ffn_norm"], x)
    f, aux = _ffn_block(lp, cfg, h, ctx, prec)
    x = x + ctx.wsc(f, ctx.dp, ctx.tp and "model", None)
    return x, aux


def apply(
    params: dict,
    cfg: TransformerConfig,
    x_emb: jax.Array,       # (B, T, d) token embeddings from the engine
    ctx: MeshCtx = MeshCtx(),
    prec: Precision = MIXED,
    attn_impl: str = "chunked",
    collect_cache: bool = False,
    sp_residual: bool = False,
):
    """Returns (hidden (B,T,d), aux_loss, cache|None)."""
    b, t, d = x_emb.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x = prec.cast(x_emb)
    x = ctx.wsc(x, ctx.dp, ctx.tp and "model", None)

    def body(carry, lp):
        x, aux = carry
        x2, aux2 = _layer_body(lp, cfg, x, positions, ctx, prec, attn_impl,
                               sp_residual=sp_residual)
        out = None
        if collect_cache:
            hd = cfg.head_dim
            h = rmsnorm_apply(lp["attn_norm"], x)
            k = dense_apply(lp["attn"]["wk"], h, prec).reshape(b, t, cfg.n_kv_heads, hd)
            v = dense_apply(lp["attn"]["wv"], h, prec).reshape(b, t, cfg.n_kv_heads, hd)
            k = attn.apply_rope(k, positions, cfg.rope_theta)
            out = (k, v)
        return (x2, aux + aux2), out

    if cfg.remat and not collect_cache:
        if cfg.remat_policy == "dots":
            # save matmul outputs; recompute only cheap elementwise/norm ops —
            # trades a little saved-activation HBM for NOT re-running the MXU
            # work in the backward (§Perf memory-term lever)
            fn = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        else:
            fn = jax.checkpoint(body)
    else:
        fn = body
    if cfg.scan_layers:
        (x, aux), cache = jax.lax.scan(fn, (x, jnp.float32(0.0)), params["layers"])
    else:  # unrolled: identical math; used by the dry-run's per-layer costing
        carry, caches = (x, jnp.float32(0.0)), []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda v: v[i], params["layers"])
            carry, c = fn(carry, lp)
            caches.append(c)
        (x, aux) = carry
        cache = jax.tree.map(lambda *cs: jnp.stack(cs), *caches) if collect_cache else None
    x = rmsnorm_apply(params["final_norm"], x)
    return x, aux, cache


def lm_loss(
    params: dict,
    cfg: TransformerConfig,
    x_emb: jax.Array,
    labels: jax.Array,      # (B, T) int32
    ctx: MeshCtx = MeshCtx(),
    prec: Precision = MIXED,
    attn_impl: str = "chunked",
    fused_ce: bool = False,
    sp_residual: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Next-token CE (mean over tokens). Returns (loss, aux_loss).

    ``fused_ce`` (paper §2.2.3 FusedSoftmaxCrossEntropy, mgmalek-style):
    the (B, T, V) fp32 logits tensor never materializes — the head matmul
    and the online logsumexp run per sequence-chunk under a remat wrapper,
    so HBM sees only the (B, T) statistics. For a 92k-vocab arch this
    removes the single largest activation of the whole step.
    """
    h, aux, _ = apply(params, cfg, x_emb, ctx, prec, attn_impl,
                      sp_residual=sp_residual)
    h = ctx.wsc(h, ctx.dp, None, None)
    if fused_ce:
        return _chunked_ce(params["head"], h, labels, ctx, prec), aux
    logits = dense_apply(params["head"], h, prec)           # (B, T, V) V-sharded
    logits = ctx.wsc(logits, ctx.dp, None, ctx.tp and "model")
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    loss = jnp.mean(lse - gold)
    return loss, aux


def _chunked_ce(head: dict, h: jax.Array, labels: jax.Array, ctx: MeshCtx,
                prec: Precision, t_chunk: int = 256) -> jax.Array:
    """Memory-lean CE: scan over sequence chunks; each chunk's logits live
    only inside the (rematerialized) scan body. Backward recomputes the
    chunk logits instead of reading a stored (B,T,V) tensor — trading
    ~2× head-matmul FLOPs for ~V/2 × fewer activation bytes."""
    b, t, d = h.shape
    tc = min(t_chunk, t)
    n = t // tc
    hc = h[:, : n * tc].reshape(b, n, tc, d).swapaxes(0, 1)        # (n, B, tc, d)
    lc = labels[:, : n * tc].reshape(b, n, tc).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(hx, lx):
        logits = dense_apply(head, hx, prec)
        logits = ctx.wsc(logits.astype(jnp.float32), ctx.dp, None,
                         ctx.tp and "model")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, lx[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    def body(acc, xs):
        hx, lx = xs
        return acc + chunk_loss(hx, lx), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, lc))
    # tail (t % tc) — full path on the remainder
    if n * tc < t:
        total = total + chunk_loss(h[:, n * tc:], labels[:, n * tc:])
    return total / (b * t)


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    hd = cfg.head_dim
    shape = (cfg.n_layers, batch, seq_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_pspec(ctx: MeshCtx) -> dict:
    s = P(None, ctx.dp, ctx.seq_shards or None, None, None)
    return {"k": s, "v": s}


def decode_step(
    params: dict,
    cfg: TransformerConfig,
    x_emb: jax.Array,   # (B, 1, d) embedding of the new token
    cache: dict,        # stacked (L, B, S(, local), Hk, hd)
    pos: jax.Array,     # () int32 — global position being generated
    ctx: MeshCtx = MeshCtx(),
    prec: Precision = MIXED,
) -> tuple[jax.Array, dict]:
    """One token for the whole stack. Returns (logits (B, V), new_cache)."""
    x = prec.cast(x_emb)
    # replicate attn weights inside the decode shard_map (comm-free there)
    aspec_rep = jax.tree.map(lambda _: P(), attn.attn_pspec(cfg.attn_cfg, shard_kv=False),
                             is_leaf=lambda s: isinstance(s, P))

    def scan_body(x, xs):
        lp, ck, cv = xs
        h = rmsnorm_apply(lp["attn_norm"], x)
        if ctx.mesh is not None and ctx.seq_shards:
            cspec = P(ctx.dp or None, ctx.seq_shards, None, None)

            def body(h_loc, ck_loc, cv_loc, pp):
                return attn.attn_decode_apply(
                    pp, cfg.attn_cfg, h_loc, ck_loc, cv_loc, pos,
                    seq_axis=ctx.seq_shards, prec=prec)

            a, ck, cv = shard_map(
                body, mesh=ctx.mesh,
                in_specs=(P(ctx.dp or None, None, None), cspec, cspec, aspec_rep),
                out_specs=(P(ctx.dp or None, None, None), cspec, cspec),
                check_vma=False,
            )(h, ck, cv, lp["attn"])
        else:
            a, ck, cv = attn.attn_decode_apply(
                lp["attn"], cfg.attn_cfg, h, ck, cv, pos, seq_axis=None, prec=prec)
        x = x + a
        h = rmsnorm_apply(lp["ffn_norm"], x)
        f, _ = _ffn_block(lp, cfg, h, ctx, prec)
        x = x + f
        return x, (ck, cv)

    if cfg.scan_layers:
        x, (new_k, new_v) = jax.lax.scan(scan_body, x, (params["layers"], cache["k"], cache["v"]))
    else:
        ks, vs = [], []
        for i in range(cfg.n_layers):
            xs = jax.tree.map(lambda v: v[i], (params["layers"], cache["k"], cache["v"]))
            x, (nk, nv) = scan_body(x, xs)
            ks.append(nk)
            vs.append(nv)
        new_k, new_v = jnp.stack(ks), jnp.stack(vs)
    x = rmsnorm_apply(params["final_norm"], x)
    logits = dense_apply(params["head"], x, prec)[:, 0, :]
    logits = ctx.wsc(logits, ctx.dp, ctx.tp and "model")
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}
