"""Plain reference of Qwen2.5 (https://huggingface.co/Qwen/Qwen2.5-3B) as a
language model that reads its token rows from the sparse engine.

A decoder of ``n_layers`` blocks over the rows of the tokens (B, T, d):
RMSNorm (eps 1e-6) → grouped-query attention (``n_heads`` query heads over
``n_kv_heads`` key/value heads, query head ``h`` reading kv head
``h // (n_heads / n_kv_heads)``; q, k, v with a bias, o without) with
rotary positions on q and k (each head's first and second halves rotated as
a pair, frequency ``rope_theta ** (-2i / head_dim)``) and a causal softmax
scaled by 1/sqrt(head_dim) → residual; RMSNorm → SwiGLU (silu(x W_gate) ·
x W_up, then W_down) → residual. A last RMSNorm, an untied head over the
vocabulary, and each token's cross-entropy against the next token of its
row; the last position's label is the row's first token. One loss to a
token: (B·T,).

The towers are drawn from the seed as the program draws them: the key
splits in three (layers, head, unused); the layers' key splits once per
layer, each layer's in two (attention, feed-forward), attention's in four
(q, k, v, o) and the feed-forward's in three (gate, up, down); a weight is
uniform in ±1/sqrt(fan-in), a bias 0, a norm's scale 1. Each parameter of
the layers is stacked over them on a leading axis. Kept with the CPU tests
as the reference of the program's smoke-size ``qwen2.5-3b``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def groups(cfg: dict):
    m = cfg["model"]
    return [(f"dim{m['d_model']}", m["d_model"], [("tokens", "tokens", m["vocab_size"])])]


def _weight(key, d_in: int, d_out: int):
    s = 1.0 / math.sqrt(d_in)
    return jax.random.uniform(key, (d_in, d_out), jnp.float32, -s, s)


def _layer(key, m: dict) -> dict:
    d, hd = m["d_model"], m["d_model"] // m["n_heads"]
    k_attn, k_ffn = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    kg, ku, kd = jax.random.split(k_ffn, 3)
    bias = m["qkv_bias"]

    def proj(k, n_out):
        p = {"w": _weight(k, d, n_out)}
        if bias:
            p["b"] = jnp.zeros((n_out,), jnp.float32)
        return p

    return {
        "attn_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "attn": {"wq": proj(kq, m["n_heads"] * hd), "wk": proj(kk, m["n_kv_heads"] * hd),
                 "wv": proj(kv, m["n_kv_heads"] * hd),
                 "wo": {"w": _weight(ko, m["n_heads"] * hd, d)}},
        "ffn_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "ffn": {"gate": {"w": _weight(kg, d, m["d_ff"])}, "up": {"w": _weight(ku, d, m["d_ff"])},
                "down": {"w": _weight(kd, m["d_ff"], d)}},
    }


def init_dense(key, cfg: dict) -> dict:
    m = cfg["model"]
    k_layers, k_head, _ = jax.random.split(key, 3)
    layers = [_layer(k, m) for k in jax.random.split(k_layers, m["n_layers"])]
    return {"layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
            "final_norm": {"scale": jnp.ones((m["d_model"],), jnp.float32)},
            "head": {"w": _weight(k_head, m["d_model"], m["vocab_size"])}}


def _mm(x, w, quantize):
    return quantize(jnp.matmul(quantize(x), quantize(w), precision=HI))


def _proj(p: dict, x, quantize):
    y = _mm(x, p["w"], quantize)
    return quantize(y + quantize(p["b"])) if "b" in p else y


def _rmsnorm(p: dict, x, quantize):
    return quantize(x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
                    * p["scale"])


def _rope(x, theta: float):
    """x: (B, T, H, hd); position t rotates the pair (x_i, x_{i + hd/2})."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(t, dtype=np.float32)[:, None] * freqs          # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p: dict, m: dict, x, quantize):
    b, t, d = x.shape
    h, hk = m["n_heads"], m["n_kv_heads"]
    hd = d // h
    q = _rope(_proj(p["wq"], x, quantize).reshape(b, t, h, hd), m["rope_theta"])
    k = _rope(_proj(p["wk"], x, quantize).reshape(b, t, hk, hd), m["rope_theta"])
    v = _proj(p["wv"], x, quantize).reshape(b, t, hk, hd)
    k, v = jnp.repeat(k, h // hk, axis=2), jnp.repeat(v, h // hk, axis=2)
    s = quantize(jnp.einsum("bthd,bshd->bhts", quantize(q), quantize(k), precision=HI))
    s = s / math.sqrt(hd)
    s = jnp.where(np.tril(np.ones((t, t), bool))[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = quantize(jnp.einsum("bhts,bshd->bthd", quantize(a), quantize(v), precision=HI))
    return _mm(o.reshape(b, t, h * hd), p["wo"]["w"], quantize)


def _swiglu(p: dict, x, quantize):
    g = jax.nn.silu(_mm(x, p["gate"]["w"], quantize))
    return _mm(quantize(g * _mm(x, p["up"]["w"], quantize)), p["down"]["w"], quantize)


def example_losses(params: dict, cfg: dict, rows: dict, batch: dict, quantize) -> jax.Array:
    """(B·T,) cross-entropy of each token's logits against the next token."""
    m = cfg["model"]
    x = quantize(rows[f"dim{m['d_model']}"])                        # (B, T, d)
    tokens = batch["tokens"].reshape(x.shape[:2])
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)

    def layer(x, lp):
        x = quantize(x + _attention(lp["attn"], m, _rmsnorm(lp["attn_norm"], x, quantize),
                                    quantize))
        x = quantize(x + _swiglu(lp["ffn"], _rmsnorm(lp["ffn_norm"], x, quantize), quantize))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    logits = _mm(_rmsnorm(params["final_norm"], x, quantize), params["head"]["w"], quantize)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(logits, axis=-1) - gold).reshape(-1)


def forward_flops_per_example(cfg: dict) -> int:
    """Multiply-adds of one sequence's forward pass, counted twice: the
    projections, the feed-forward and the head per token, and the causal
    attention's scores and values over the T (T + 1) / 2 pairs it keeps."""
    m = cfg["model"]
    d, h, hk, t = m["d_model"], m["n_heads"], m["n_kv_heads"], cfg["seq_len"]
    hd = d // h
    per_token = m["n_layers"] * (d * (h + 2 * hk) * hd + h * hd * d + 3 * d * m["d_ff"]) \
        + d * m["vocab_size"]
    attn = m["n_layers"] * 2 * h * hd * t * (t + 1) // 2
    return 2 * (per_token * t + attn)
