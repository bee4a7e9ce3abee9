"""Plain reference of Wide & Deep (arXiv:1606.07792) as the program models it.

Deep part: 40 single-valued categorical features, each one row of its own
``embed_dim`` table, concatenated into the deep MLP (ReLU after every
layer), then one unit to a logit. Wide part: each feature also reads one
row of its own ``wide_dim`` table ("wide_tbl_<i>"); the 40 rows are summed
and projected to a logit (a learned ``wide_dim`` → 1 map that keeps the
wide part linear). The logit is the two plus a scalar bias. Parameters are
drawn from the seed: the key splits in three (deep MLP, deep output, wide
projection); a weight is uniform in ±1/sqrt(fan-in), a bias 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import common


def groups(cfg: dict):
    m = cfg["model"]
    deep = [(f"cat_{i}", f"cat_{i}") for i in range(m["n_sparse"])]
    wide = [(f"wide_{i}", f"wide_tbl_{i}") for i in range(m["n_sparse"])]
    if m["embed_dim"] == m["wide_dim"]:
        return [(f"dim{m['embed_dim']}", m["embed_dim"], _interleave(deep, wide))]
    return sorted([(f"dim{m['embed_dim']}", m["embed_dim"], deep),
                   (f"dim{m['wide_dim']}", m["wide_dim"], wide)], key=lambda g: g[1])


def _interleave(a, b):
    return [c for pair in zip(a, b) for c in pair]


def init_dense(key, cfg: dict) -> dict:
    m = cfg["model"]
    k1, k2, k3 = jax.random.split(key, 3)
    return {"deep": common.mlp_params(k1, [m["n_sparse"] * m["embed_dim"]] + list(m["mlp"])),
            "deep_out": common.uniform_dense(k2, m["mlp"][-1], 1),
            "wide_proj": common.uniform_dense(k3, m["wide_dim"], 1),
            "bias": jnp.zeros((), jnp.float32)}


def _split(cfg, rows):
    """(deep (B, 40, embed_dim), wide (B, 40, wide_dim)) from the group rows."""
    m = cfg["model"]
    if m["embed_dim"] == m["wide_dim"]:
        both = rows[f"dim{m['embed_dim']}"]
        return both[:, 0::2], both[:, 1::2]
    return rows[f"dim{m['embed_dim']}"], rows[f"dim{m['wide_dim']}"]


def forward(params: dict, cfg: dict, rows: dict, batch: dict, quantize) -> jax.Array:
    deep_rows, wide_rows = _split(cfg, rows)
    b = deep_rows.shape[0]
    deep_in = quantize(deep_rows).reshape(b, -1)
    deep = common.mlp(params["deep"], deep_in, quantize, final_relu=True)
    deep_logit = common.dense(params["deep_out"], deep, quantize)[:, 0]
    wide_sum = quantize(wide_rows[:, 0])
    for i in range(1, wide_rows.shape[1]):                  # summed in order, as rounded
        wide_sum = quantize(wide_sum + quantize(wide_rows[:, i]))
    wide_logit = common.dense(params["wide_proj"], wide_sum, quantize)[:, 0]
    return quantize(deep_logit + wide_logit) + params["bias"]


def forward_flops_per_example(cfg: dict) -> int:
    """Multiply-adds of one example's forward pass, counted twice."""
    m = cfg["model"]
    deep = [m["n_sparse"] * m["embed_dim"]] + list(m["mlp"])
    macs = sum(a * b for a, b in zip(deep, deep[1:])) + m["mlp"][-1] + m["wide_dim"]
    return 2 * macs
