"""Peak device memory of ``reference/common.run`` at Moonlight-16B-A3B's
one-chip share: 527 M float32 tower parameters (dense layer 83 M, 4 expert
layers of 8 routed + 2 shared experts 100.4 M each, head 42 M), one table
of 2,048-wide token rows over a 20,480 slice, 3 steps of 1 x 8,192 tokens.
The forward is a stand-in that touches every leaf (no attention over T x T),
each layer under jax.checkpoint, so the peak is the run's own state: the
towers, their moments and gradient, the table and the head's logits.

    python3 benchmarks/chip/reference_fit.py [scale]   (1, the default: full
                                                        size; 0.01 on the CPU)

The last line of standard output is one JSON object: the device's peak
and limit, the tower parameters and the wall time of a first (compiling)
and a second replay; where a replay fails, its error and the peak so far.
"""
import json
import pathlib
import sys
import time
import traceback

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "reference"))

import common  # noqa: E402  (turns on 64-bit types)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import traffic  # noqa: E402

S = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
D = 2048 if S >= 1 else 64
F_DENSE, F_EXP = max(8, int(11264 * S)), max(8, int(1408 * S))
VOCAB, T = max(64, int(20480 * S)), max(16, int(8192 * S))
ATT = [("wq", D, 16 * 192), ("kv_a", D, 576), ("kv_b", 512, 16 * 256), ("wo", 16 * 128, D)]


def _w(key, i, o):
    s = 1.0 / float(np.sqrt(i))  # a weak Python float: the leaf stays float32
    return jax.random.uniform(key, (i, o), jnp.float32, -s, s)


def _att(key):
    ks = jax.random.split(key, len(ATT))
    return {n: _w(k, i, o) for k, (n, i, o) in zip(ks, ATT)}


def _ffn(key, f, n=None):
    kg, ku, kd = jax.random.split(key, 3)
    if n is None:
        return {"gate": _w(kg, D, f), "up": _w(ku, D, f), "down": _w(kd, f, D)}
    return {"gate": jax.vmap(lambda k: _w(k, D, f))(jax.random.split(kg, n)),
            "up": jax.vmap(lambda k: _w(k, D, f))(jax.random.split(ku, n)),
            "down": jax.vmap(lambda k: _w(k, f, D))(jax.random.split(kd, n))}


class Share:
    @staticmethod
    def groups(cfg):
        return [("dim", D, [("tokens", "tokens", VOCAB)])]

    @staticmethod
    def init_dense(key, cfg):
        k = jax.random.split(key, 12)
        exp = [{"att": _att(k[2 + 2 * i]), "routed": _ffn(k[3 + 2 * i], F_EXP, 8),
                "shared": _ffn(jax.random.fold_in(k[3 + 2 * i], 1), F_EXP, 2),
                "router": _w(jax.random.fold_in(k[3 + 2 * i], 2), D, 64)} for i in range(4)]
        return {"dense": {"att": _att(k[0]), "ffn": _ffn(k[1], F_DENSE)},
                "experts": jax.tree.map(lambda *x: jnp.stack(x), *exp),
                "head": _w(k[11], D, VOCAB)}

    @staticmethod
    def example_losses(params, cfg, rows, batch, quantize):
        x = rows["dim"].reshape(-1, D)
        tokens = batch["tokens"].reshape(-1)
        labels = jnp.roll(tokens, -1)
        mm = lambda a, b: jnp.matmul(a, b, precision="highest")  # noqa: E731

        def att(p, x):
            q = mm(x, p["wq"])[:, :16 * 128]
            kv = mm(mm(x, p["kv_a"])[:, :512], p["kv_b"])[:, :16 * 128]
            return mm(jnp.tanh(q) * kv, p["wo"])

        def ffn(p, x):
            return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])

        @jax.checkpoint
        def dense_layer(p, x):
            x = x + att(p["att"], x)
            return x + ffn(p["ffn"], x)

        @jax.checkpoint
        def expert_layer(p, x):
            x = x + att(p["att"], x)
            gate = jax.nn.sigmoid(mm(x, p["router"]))[:, :8]
            out = sum(gate[:, e:e + 1] * ffn(jax.tree.map(lambda a: a[e], p["routed"]), x)
                      for e in range(8))
            out = out + sum(ffn(jax.tree.map(lambda a: a[e], p["shared"]), x) for e in range(2))
            return x + out / 10

        x = dense_layer(params["dense"], x)
        x, _ = jax.lax.scan(lambda x, p: (expert_layer(p, x), None), x, params["experts"])
        logits = mm(x, params["head"])
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, (labels % VOCAB)[:, None], -1)[:, 0]


def main():
    cfg = {"optimizer": json.loads((BENCH / "configs/dlrm-mlperf.json")
                                   .read_text())["optimizer"],
           "model": {"vocab_size": VOCAB}}
    mix = {"ids": "zipf", "zipf_exponent": 1.0, "vocab": "model.vocab_size",
           "id_space": "vocab", "ring_batches": 3, "id_seed": 0}
    cols = [{"name": "tokens", "values": T, "splits": 2, "ids": True, "table": "tokens"}]
    ring = traffic.make_ring(mix, cfg, 1, cols, 2**31 + 99)
    host = [{k: v for k, v in b.items() if not k.startswith("_")} for b in ring]
    dev = jax.devices()[0]
    shapes = jax.tree.leaves(jax.eval_shape(lambda: Share.init_dense(jax.random.PRNGKey(0), cfg)))
    assert {str(x.dtype) for x in shapes} == {"float32"}
    n = sum(x.size for x in shapes)
    out = {"platform": dev.platform, "tower_params": int(n), "tower_copy_bytes": 4 * int(n),
           "tokens": T, "vocab": VOCAB}
    steps, loss_grad = common.make_steps(Share, cfg), common.make_loss_grad(Share, cfg)
    try:
        for attempt in ("cold", "warm"):
            t = time.perf_counter()
            r = common.run(Share, cfg, 2**31 + 99, host, 1, steps=steps, loss_grad=loss_grad)
            out[f"run_s_{attempt}"] = time.perf_counter() - t
            out[f"losses_{attempt}"] = r["losses"]
            del r
    except Exception as e:  # noqa: BLE001 - the error is the reading
        traceback.print_exc()
        out["error"] = f"{type(e).__name__}: {str(e)[:2000]}"
    stats = dev.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["bytes_limit"] = stats.get("bytes_limit")
    print(json.dumps(out))
    return 1 if "error" in out else 0


if __name__ == "__main__":
    sys.exit(main())
