"""Plain float32 training reference shared by the per-configuration files.

It imports nothing of the program. What it shares with the program is the
published contract of a step: how a raw id becomes a table key (the feature
hash and the table salt), how a new row is initialised from its key, the
loss, and the two optimizers. Each is written out here from that contract:

* a raw id of column ``c`` becomes ``splitmix64(raw ^ colsalt(c))`` (a
  token column takes ``|raw| mod vocab`` instead) and then
  ``splitmix64(that ^ (splitmix64(tablesalt(c)) + C1))``, where ``colsalt``
  mixes the 31-bit FNV-1a of the table's name and ``tablesalt`` is its 63-bit
  FNV-1a;
* a new row of width ``d`` is ``(2 u - 1) / sqrt(d)``, ``u`` the top 24 bits
  of ``splitmix64(key * C1 + column)``; its Adam moments start at 0;
* the loss is the mean over the batch of each example's loss, which the
  configuration's module gives (``example_losses``: a recommendation
  model's sigmoid cross-entropy, ``sigmoid_ce``);
* the towers' parameters take AdamW with the gradient clipped to a global
  norm (decoupled weight decay), the rows take lazy row-wise Adam: only rows
  of the batch move, with bias correction by the global step.

The reference keeps one row per distinct key of the steps it runs (a plain
lookup table), and computes the towers in float32, every matrix product at
the ``highest`` precision. ``quantize`` rounds every value of the towers
(inputs, weights, products, sums, logits) to a compute precision instead,
as the program rounds them to bfloat16; ``fp8`` is how the control steps
one precision below that, and ``bf16`` rounds them as the program does (a
witness of what bfloat16 alone moves, not a control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)  # table keys are 64-bit integers

C1 = 0x9E3779B97F4A7C15
C2 = 0xBF58476D1CE4E5B9
C3 = 0x94D049BB133111EB
M64 = (1 << 64) - 1


# ----------------------------------------------------------------- hashing
def splitmix64_np(x: np.ndarray) -> np.ndarray:
    z = x.astype(np.uint64) + np.uint64(C1)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(C2)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(C3)
    return z ^ (z >> np.uint64(31))


def _splitmix64_int(x: int) -> int:
    z = (x + C1) & M64
    z = ((z ^ (z >> 30)) * C2) & M64
    z = ((z ^ (z >> 27)) * C3) & M64
    return z ^ (z >> 31)


def _fnv1a64(name: str) -> int:
    h = 1469598103934665603
    for ch in name.encode():
        h = ((h ^ ch) * 1099511628211) & M64
    return h


def table_keys(raw: np.ndarray, table: str, vocab: int | None = None) -> np.ndarray:
    """int64 table keys of raw ids of one column that reads ``table``: a
    hashed column, or with ``vocab`` a token column (ids modulo ``vocab``)."""
    if vocab is None:
        col_salt = _splitmix64_int(_fnv1a64(table) & 0x7FFFFFFF)
        hashed = splitmix64_np(raw.astype(np.int64).view(np.uint64) ^ np.uint64(col_salt))
    else:
        hashed = (np.abs(raw.astype(np.int64)) % vocab).view(np.uint64)
    tab_salt = _fnv1a64(table) & 0x7FFFFFFFFFFFFFFF
    mix = np.uint64((_splitmix64_int(tab_salt) + C1) & M64)
    return splitmix64_np(hashed ^ mix).view(np.int64)


def splitmix64_jnp(x: jax.Array) -> jax.Array:
    z = x.astype(jnp.uint64) + jnp.uint64(C1)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(C2)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(C3)
    return z ^ (z >> jnp.uint64(31))


def init_rows(keys: jax.Array, dim: int) -> jax.Array:
    """(n, dim) float32 initial rows of int64 ``keys``."""
    cols = jnp.arange(dim, dtype=jnp.uint64)[None, :]
    bits = splitmix64_jnp(keys.astype(jnp.uint64)[:, None] * jnp.uint64(C1) + cols)
    u = (bits >> jnp.uint64(40)).astype(jnp.float32) * np.float32(2.0 ** -24)
    return (u * np.float32(2.0) - np.float32(1.0)) * np.float32(1.0 / math.sqrt(dim))


_init_rows = jax.jit(init_rows, static_argnums=1)
BUCKET = 1 << 16


def bucket(n: int) -> int:
    """``n`` rounded up to a multiple of 65,536: arrays of that many rows
    have the same shape from seed to seed, so their programs compile once."""
    return max(BUCKET, -(-n // BUCKET) * BUCKET)


def init_rows_host(keys: np.ndarray, dim: int) -> np.ndarray:
    """``init_rows`` of host keys, on the host."""
    padded = np.zeros(bucket(len(keys)), np.int64)
    padded[:len(keys)] = keys
    return np.asarray(_init_rows(jnp.asarray(padded), dim))[:len(keys)]


# ------------------------------------------------------------------ towers
def uniform_dense(key, d_in: int, d_out: int) -> dict:
    s = 1.0 / math.sqrt(d_in)
    return {"w": jax.random.uniform(key, (d_in, d_out), jnp.float32, -s, s),
            "b": jnp.zeros((d_out,), jnp.float32)}


def mlp_params(key, dims) -> dict:
    keys = jax.random.split(key, len(dims) - 1)
    return {f"l{i}": uniform_dense(k, dims[i], dims[i + 1]) for i, k in enumerate(keys)}


def identity(x):
    return x


def _to_fp8(x):
    """Round to float8 e4m3 with one scale per tensor (its largest magnitude
    maps to e4m3's largest, 448), as scaled fp8 training rounds: 3 mantissa
    bits, round half to even, spacing 2^-9 below e4m3's smallest normal.
    Written in float32 arithmetic (every step is exact but the rounding),
    since a compiler may drop a convert to an 8-bit float and back."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    y = jnp.clip(x / s, -448.0, 448.0)
    _, e = jnp.frexp(y)                      # |y| = m 2^e, m in [0.5, 1)
    spacing = jnp.exp2(jnp.maximum(e - 4, -9).astype(jnp.float32))
    return jnp.round(y / spacing) * spacing * s


@jax.custom_vjp
def fp8(x):
    """A tower value one step below bfloat16: rounded to scaled float8 e4m3
    going forward, and its cotangent likewise coming back."""
    return _to_fp8(x)


fp8.defvjp(lambda x: (_to_fp8(x), None), lambda _, g: (_to_fp8(g),))


def _to_bf16(x):
    """Round float32 to bfloat16 (round half to even) in integer arithmetic
    on the bits, which no compiler folds away as it may a convert pair."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


@jax.custom_vjp
def bf16(x):
    """A tower value rounded to bfloat16 going forward, and its cotangent
    likewise coming back."""
    return _to_bf16(x)


bf16.defvjp(lambda x: (_to_bf16(x), None), lambda _, g: (_to_bf16(g),))


def dense(p: dict, x: jax.Array, quantize) -> jax.Array:
    """x @ w + b, every value in the compute precision that ``quantize``
    rounds to (the identity: float32)."""
    y = quantize(jnp.matmul(quantize(x), quantize(p["w"]), precision=jax.lax.Precision.HIGHEST))
    return quantize(y + quantize(p["b"]))


def mlp(p: dict, x: jax.Array, quantize, final_relu: bool = False) -> jax.Array:
    n = len(p)
    for i in range(n):
        x = dense(p[f"l{i}"], x, quantize)
        if i < n - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def sigmoid_ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Each example's sigmoid cross-entropy of its logit against its label."""
    z, y = logits.astype(jnp.float32), labels.astype(jnp.float32)
    return jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))


def flat(tree: dict, prefix: str = "") -> dict:
    """Nested dict → {"a/b/c": leaf}."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, name + "/"))
        else:
            out[name] = v
    return out


# ------------------------------------------------------------------ training
def _column_keys(model, cfg, batch: dict, chips: int, fault: str | None):
    """Per group: (B, K) int64 keys, and (B, K) int64 row-identity keys.

    ``model.groups(cfg)`` lists each group's columns as ``(name, table)``,
    or ``(name, table, vocab)`` for a token column. A column holds the same
    number of ids in every row (a categorical feature one, a sequence of
    tokens T), over ``chips`` shards of rows laid end to end; a row's K keys
    are its ids of each column in turn. Row identity is the key, except
    under the ``no_exchange`` fault, where each chip keeps its own copy of a
    row (the key paired with the chip of the example). The ``altered_id``
    fault flips the low bit of the first column's keys, as a feature hash
    that altered its output would."""
    out = {}
    for gi, (group, dim, columns) in enumerate(model.groups(cfg)):
        cols = []
        for name, table, *vocab in columns:
            values, splits = batch[name]
            rows = len(splits) - chips
            cols.append(table_keys(values, table, *vocab).reshape(rows, -1))
        if fault == "altered_id" and gi == 0:
            cols[0] = cols[0] ^ 1
        keys = np.concatenate(cols, axis=1)
        ident = keys
        if fault == "no_exchange":
            b = keys.shape[0]
            chip = (np.arange(b) // (b // chips)).astype(np.int64)[:, None]
            ident = np.stack([np.broadcast_to(chip, keys.shape), keys], axis=-1)
        out[group] = (dim, keys, ident)
    return out


def run(model, cfg: dict, seed: int, batches: list, chips: int,
        quantize=identity, fault: str | None = None) -> dict:
    """Train ``len(batches)`` steps from the seed; return the readings.

    ``batches`` are host batches ({column: (values, row_splits)}), as many
    ids in every row of an id column. Returns the loss of every step, each leaf's
    gradient norm as the optimizer got it at step 1, and each leaf's change
    after the last step; leaves are ``"dense/<path>"`` and
    ``"sparse/<group>"``; the first moments after step 1 (``m1``: the
    towers' per leaf, and per group the rows that step touched, with their
    keys); and the ``state``: the towers' parameters at the start and the
    end, and each group's rows with their keys and moments.
    """
    hp = cfg["optimizer"]
    dhp, shp = hp["dense"], hp["sparse"]
    with jax.default_matmul_precision("highest"):
        params = model.init_dense(jax.random.PRNGKey(seed), cfg)
        p0 = jax.tree.map(jnp.array, params)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)

        per_step = [_column_keys(model, cfg, b, chips, fault) for b in batches]
        tables = {}
        for group in per_step[0]:
            dim = per_step[0][group][0]
            idents = np.concatenate([s[group][2].reshape(-1, *s[group][2].shape[2:])
                                     for s in per_step])
            keys = np.concatenate([s[group][1].reshape(-1) for s in per_step])
            uniq, first, inv = np.unique(idents, axis=0 if idents.ndim > 1 else None,
                                         return_index=True, return_inverse=True)
            inv = inv.reshape(-1)
            # the table holds bucket(n) rows; the rows past the n keys are
            # never looked up, and are left out of what is returned
            padded = np.zeros(bucket(len(first)), np.int64)
            padded[:len(first)] = keys[first]
            rows0 = _init_rows(jnp.asarray(padded), dim)
            tables[group] = {"emb": rows0, "init": rows0, "keys": keys[first], "n": len(first),
                             "m": jnp.zeros_like(rows0), "v": jnp.zeros_like(rows0),
                             "inv": np.split(inv, len(batches))}

        loss_grad = jax.jit(jax.value_and_grad(
            lambda prm, rows, b: _loss(model, cfg, prm, rows, b, quantize, fault),
            argnums=(0, 1)))
        dense_step = jax.jit(lambda p, g, m, v, t: _adamw(dhp, p, g, m, v, t))
        row_step = jax.jit(lambda t_, g, inv, t: _sparse_adam(shp, t_, g, inv, t))

        losses, grad_norms, m1 = [], {}, {"dense": {}, "rows": {}}
        for k, b in enumerate(batches):
            t = jnp.float32(k + 1)
            rows = {}
            for group, tab in tables.items():
                shape = per_step[k][group][1].shape
                rows[group] = tab["emb"][jnp.asarray(tab["inv"][k])].reshape(*shape, -1)
            host = {name: jnp.asarray(vals) for name, (vals, _) in b.items()
                    if vals.dtype != np.int64}
            loss, (g_dense, g_rows) = loss_grad(params, rows, host)
            losses.append(float(loss))
            params, m, v, g_used = dense_step(params, g_dense, m, v, t)
            for group, tab in tables.items():
                g = g_rows[group].reshape(-1, g_rows[group].shape[-1])
                tab["emb"], tab["m"], tab["v"], g_tab = row_step(
                    (tab["emb"], tab["m"], tab["v"]), g, jnp.asarray(tab["inv"][k]), t)
                if k == 0:
                    grad_norms[f"sparse/{group}"] = float(jnp.linalg.norm(g_tab))
                    hit = np.unique(tab["inv"][0])
                    m1["rows"][group] = {"keys": tab["keys"][hit],
                                         "m": np.asarray(tab["m"])[hit]}
            if k == 0:
                for name, g in flat(g_used).items():
                    grad_norms[f"dense/{name}"] = float(jnp.linalg.norm(g))
                m1["dense"] = {name: np.asarray(x) for name, x in flat(m).items()}

        change = {f"dense/{name}": float(jnp.linalg.norm(a - b))
                  for (name, a), b in zip(flat(params).items(), flat(p0).values())}
        for group, tab in tables.items():
            change[f"sparse/{group}"] = float(jnp.linalg.norm(tab["emb"] - tab["init"]))
    return {
        "losses": losses, "grad_norms": grad_norms, "change_norms": change, "m1": m1,
        "state": {
            "dense": {k: np.asarray(v) for k, v in flat(params).items()},
            "dense0": {k: np.asarray(v) for k, v in flat(p0).items()},
            "tables": {g: {"keys": t["keys"],
                           **{k: np.asarray(t[k])[:t["n"]] for k in ("emb", "m", "v")}}
                       for g, t in tables.items()}}}


def _loss(model, cfg, params, rows, batch, quantize, fault):
    """The mean of the model's per-example losses; under the ``half_batch``
    fault, of the first half of the batch."""
    losses = model.example_losses(params, cfg, rows, batch, quantize)
    if fault == "half_batch":
        losses = losses[:losses.shape[0] // 2]
    return jnp.mean(losses)


def _adamw(hp, params, grads, m, v, t):
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, hp["grad_clip_norm"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps, lr, wd = hp["b1"], hp["b2"], hp["eps"], hp["lr"], hp["weight_decay"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * p),
        params, m, v)
    return params, m, v, grads


def _sparse_adam(hp, table, g_vals, inv, t):
    emb, m, v = table
    n = emb.shape[0]
    g = jax.ops.segment_sum(g_vals, inv, num_segments=n)
    touched = (jnp.zeros((n,), jnp.int32).at[inv].add(1) > 0)[:, None]
    b1, b2, eps, lr = hp["b1"], hp["b2"], hp["eps"], hp["lr"]
    m1 = b1 * m + (1 - b1) * g
    v1 = b2 * v + (1 - b2) * g * g
    upd = (m1 / (1 - b1 ** t)) / (jnp.sqrt(v1 / (1 - b2 ** t)) + eps)
    emb = jnp.where(touched, emb - lr * upd, emb)
    return emb, jnp.where(touched, m1, m), jnp.where(touched, v1, v), g
