"""Plain reference of DLRM (arXiv:1906.00091) as MLPerf trains it.

13 dense features through the bottom MLP (ReLU after every layer), 26
single-valued categorical features, each one row of its own table; the 26
rows and the bottom output take pairwise dot products (the strict lower
triangle, row by row), which follow the bottom output into the top MLP
(ReLU between layers, none after the last). The towers' parameters are
drawn from the seed: the key splits in two, bottom then top, and each MLP's
key splits once per layer; a weight is uniform in ±1/sqrt(fan-in), a bias 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import common


def groups(cfg: dict):
    m = cfg["model"]
    cols = [(f"cat_{i}", f"cat_{i}") for i in range(m["n_sparse"])]
    return [(f"dim{m['embed_dim']}", m["embed_dim"], cols)]


def _top_in(m: dict) -> int:
    f = m["n_sparse"] + 1
    return m["bot_mlp"][-1] + f * (f - 1) // 2


def init_dense(key, cfg: dict) -> dict:
    m = cfg["model"]
    k1, k2 = jax.random.split(key)
    return {"bot": common.mlp_params(k1, [m["n_dense"]] + list(m["bot_mlp"])),
            "top": common.mlp_params(k2, [_top_in(m)] + list(m["top_mlp"]))}


def forward(params: dict, cfg: dict, rows: dict, batch: dict, quantize) -> jax.Array:
    m = cfg["model"]
    x = batch["dense"].reshape(-1, m["n_dense"])
    bot = common.mlp(params["bot"], x, quantize, final_relu=True)
    emb = quantize(rows[f"dim{m['embed_dim']}"])            # (B, 26, d)
    vecs = jnp.concatenate([emb, bot[:, None, :]], axis=1)   # (B, 27, d)
    z = quantize(jnp.einsum("bfd,bgd->bfg", vecs, vecs, precision=jax.lax.Precision.HIGHEST))
    iu, ju = np.tril_indices(vecs.shape[1], k=-1)
    top_in = jnp.concatenate([bot, z[:, iu, ju]], axis=-1)
    return common.mlp(params["top"], top_in, quantize)[:, 0]


def forward_flops_per_example(cfg: dict) -> int:
    """Multiply-adds of one example's forward pass, counted twice."""
    m = cfg["model"]
    bot = [m["n_dense"]] + list(m["bot_mlp"])
    top = [_top_in(m)] + list(m["top_mlp"])
    f, d = m["n_sparse"] + 1, m["bot_mlp"][-1]
    macs = sum(a * b for a, b in zip(bot, bot[1:])) + f * f * d \
        + sum(a * b for a, b in zip(top, top[1:]))
    return 2 * macs
