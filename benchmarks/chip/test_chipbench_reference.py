"""CPU tests of the plain references against the program at smoke size:
the same keys (of hashed columns and of token columns with many ids to a
row), the same initial rows and towers, and, over the checked steps, the
same losses, rows with their Adam moments, and tower parameters to within
what the program's bfloat16 tower products allow; the references' own
readings at one seed, which are recorded to the bit; and how the reference
runs: what it keeps on the host, what its AdamW step takes in place, and
the state it threads from step to step by a rule."""
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "reference"))

import check  # noqa: E402
import common  # noqa: E402
import conftest  # noqa: E402
import harness  # noqa: E402
import registry  # noqa: E402
import traffic  # noqa: E402

# share of a state's change or size by which the program may differ from the
# float32 reference: the towers' products round to bfloat16 (2^-8)
TOL = 0.25


def test_keys_and_initial_rows_match_the_program():
    from repro.core import blocks
    from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
    from repro.core.feature_engine import FeatureEngine, FeatureSpec
    from repro.io.ragged import Ragged

    specs = [FeatureSpec("cat_0", emb_dim=8), FeatureSpec("cat_1", emb_dim=8),
             FeatureSpec("wide_0", emb_dim=8, shared_table="wide_tbl_0")]
    raw = np.random.default_rng(0).integers(0, 2**63 - 1, size=64, dtype=np.int64)
    batch = {s.name: Ragged(jnp.asarray(raw + i), jnp.arange(65, dtype=jnp.int32))
             for i, s in enumerate(specs)}
    ids, _ = FeatureEngine(specs).apply(batch)
    prog = np.asarray(EmbeddingEngine(specs, EngineConfig(("data",), 1, u_budget=256, per_dest_cap=256,
                                                    recv_budget=256)).engine_ids(ids)["dim8"])
    ref = np.concatenate([common.table_keys(raw + i, s.table_key())
                          for i, s in enumerate(specs)])
    np.testing.assert_array_equal(prog, ref)
    for dim in (8, 32, 128):
        want = blocks._hash_uniform(jnp.asarray(ref), dim) * np.float32(1 / np.sqrt(dim))
        np.testing.assert_array_equal(np.asarray(common.init_rows(jnp.asarray(ref), dim)),
                                      np.asarray(want))


def test_token_column_keys_take_many_ids_to_a_row():
    """A (B·T)-long token column, T ids to a row over two chips' shards,
    gives (B, T) keys, row by row, equal to the program's engine ids."""
    from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
    from repro.core.feature_engine import FeatureEngine, FeatureSpec
    from repro.io.ragged import Ragged

    vocab, b, t, chips = 1000, 6, 5, 2
    spec = FeatureSpec("tokens", transform="mod", vocab_size=vocab, emb_dim=8,
                       pooling="values")
    raw = np.random.default_rng(1).integers(0, 3 * vocab, size=b * t, dtype=np.int64)
    splits = np.tile(np.arange(b // chips + 1, dtype=np.int32) * t, chips)
    ids, _ = FeatureEngine([spec]).apply(
        {"tokens": Ragged(jnp.asarray(raw), jnp.arange(b * t + 1, dtype=jnp.int32))})
    prog = np.asarray(EmbeddingEngine([spec], EngineConfig(
        ("data",), 1, u_budget=64, per_dest_cap=64, recv_budget=64)).engine_ids(ids)["dim8"])

    class Tokens:
        @staticmethod
        def groups(cfg):
            return [("dim8", 8, [("tokens", "tokens", vocab)])]

    for fault in (None, "no_exchange"):
        (dim, keys, ident), = common._column_keys(Tokens, {}, {"tokens": (raw, splits)},
                                                  chips, fault).values()
        assert dim == 8 and keys.shape == (b, t)
        np.testing.assert_array_equal(keys.reshape(-1), prog)
    # under no_exchange, a row's ids are owned by the chip of its example
    np.testing.assert_array_equal(ident[..., 0], np.repeat([[0], [0], [0], [1], [1], [1]], t, 1))


def _smoke_columns(model, cfg, chips, batch=256):
    cols = [{"name": n, "values": batch, "splits": batch + chips, "ids": True, "table": t}
            for _, _, group in model.groups(cfg) for n, t in group]
    return cols + [{"name": n, "values": batch * k, "splits": batch + chips, "ids": False,
                    "table": None} for n, k in (("label", 1), ("dense", 13))]


READINGS = json.loads((BENCH / "testdata" / "reference_readings.json").read_text())


@pytest.mark.parametrize("case", sorted(READINGS))
def test_reference_readings_are_recorded_to_the_bit(case):
    """The references' losses, step-1 gradient norms and 3-step changes at
    the smoke widths, seed 2**31 + 7, batch 256, on the CPU, equal those
    that ``testdata/reference_readings.json`` records: read with the loss
    taken as a mean sigmoid cross-entropy in ``common`` itself, before each
    configuration's module gave its per-example losses. A fault's run
    (``<config>/<fault>``) is read on two chips for ``no_exchange``, one
    otherwise."""
    config, fault = case.split("/")
    fault = None if fault == "none" else fault
    chips = 2 if fault == "no_exchange" else 1
    bench = registry.Bench(ROOT)
    cfg = dict(bench.config("dlrm-mlperf"), arch=config, model=conftest.SMOKE_MODELS[config],
               rows_per_table_per_chip=512, global_batch=256)
    model = registry.load(BENCH / "reference", config)
    mix = dict(bench.traffic("train.zipf"), ring_batches=3)
    seed = 2**31 + 7
    ring = traffic.make_ring(mix, cfg, chips, _smoke_columns(model, cfg, chips), seed)
    host = [{k: v for k, v in b.items() if not k.startswith("_")} for b in ring]
    r = common.run(model, cfg, seed, host, chips, fault=fault)
    got = {"losses": [float(x).hex() for x in r["losses"]],
           **{k: {leaf: float(v).hex() for leaf, v in sorted(r[k].items())}
              for k in ("grad_norms", "change_norms")}}
    assert got == READINGS[case]


def test_bf16_witness_rounds_as_bfloat16_does():
    x = np.random.default_rng(0).standard_normal(4096).astype(np.float32)
    # ties: halfway between two bfloat16 numbers round to the even one
    ties = np.array([1 + 2**-8, 1 + 3 * 2**-8, -(1 + 2**-8)], np.float32)
    for v in (x, x * 1e-30, x * 1e30, ties):
        want = np.asarray(jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(jax.jit(common.bf16)(jnp.asarray(v))), want)
    g = jax.grad(lambda v: jnp.sum(common.bf16(v) * np.float32(1 + 2**-8)))(jnp.ones(2))
    np.testing.assert_array_equal(np.asarray(g), [1.0, 1.0])  # the cotangent rounds too


@pytest.mark.parametrize("workload", ["wide-deep-smoke.train.zipf",
                                      "dlrm-mlperf-smoke.train.zipf"])
def test_checked_steps_match_the_reference(smoke_root, workload):
    h = harness.Harness(smoke_root, workload, jax.devices())
    seed = 2**31 + 7
    ring = h.ring(seed)
    state, dense0 = h.fresh_state(seed)
    state, prog = h.checked_steps(h.trainer(), state, dense0, ring)
    rows = h.cell.engine.export_rows(state["sparse"])
    dense = {k: np.asarray(v) for k, v in check.flat(jax.device_get(state["dense"])).items()}
    ref = h.reference(seed, ring)
    lr = h.cfg["optimizer"]["sparse"]["lr"]

    # the loss: bfloat16 logits of O(0.1) round at ~1e-3 each, and their
    # errors average out over the batch
    np.testing.assert_allclose(prog["losses"], ref["losses"], atol=2e-4)
    for g, t in ref["state"]["tables"].items():
        r = rows[g]
        order = np.argsort(r["ids"])
        ids = r["ids"][order]
        # every key of the reference has exactly one live row: the hashing,
        # dedupe and IDMap insert agree
        assert len(ids) == len(t["keys"])
        pos = np.searchsorted(ids, t["keys"])
        np.testing.assert_array_equal(ids[pos], t["keys"])
        emb = r["emb"][order][pos]
        moved = np.linalg.norm(t["emb"] - np.asarray(common.init_rows(jnp.asarray(t["keys"]),
                                                                      t["emb"].shape[1])))
        # Adam moves an element by about lr a step whatever the gradient's
        # size, so a bfloat16 rounding that flips the sign of a near-zero
        # gradient moves it the other way: an element differs by at most
        # 2 lr a step, and a few percent of them do
        d = np.abs(emb - t["emb"])
        assert d.max() <= 2 * len(prog["losses"]) * lr
        assert np.linalg.norm(d) <= TOL * moved, g
        for k in ("m", "v"):
            p, q = r["slots"][k][order][pos], t[k]
            assert np.linalg.norm(p - q) <= TOL * np.linalg.norm(q), (g, k)
    # the towers over all their parameters: a small leaf such as a bias has
    # too few elements for its own share to be steady
    diff = moved = 0.0
    for k, v in ref["state"]["dense"].items():
        d = np.abs(dense[k] - v)
        assert d.max() <= 2 * len(prog["losses"]) * lr, k
        diff += np.sum(d.astype(np.float64) ** 2)
        moved += np.sum((v - dense0[k]) ** 2)
    assert np.sqrt(diff) <= TOL * np.sqrt(moved)


def test_language_model_reference_draws_the_programs_towers():
    """``testdata/qwen2.5-3b.py`` draws the towers key for key as the
    program's ``transformer.init`` does, leaf by leaf and to the bit (each
    in one jitted call, as the harness and the reference draw them)."""
    from repro.configs import get_config
    from repro.models import transformer as tfm

    arch = get_config("qwen2.5-3b", smoke=True)
    cfg = {"model": json.loads(json.dumps(dataclasses.asdict(arch.model)))}
    ref = registry.load(BENCH / "testdata", "qwen2.5-3b")
    key = jax.random.PRNGKey(7)
    prog = check.flat(jax.device_get(jax.jit(lambda k: tfm.init(k, arch.model))(key)))
    mine = check.flat(jax.device_get(jax.jit(lambda k: ref.init_dense(k, cfg))(key)))
    assert prog.keys() == mine.keys()
    for k in prog:
        np.testing.assert_array_equal(mine[k], prog[k], err_msg=k)


def test_reference_keeps_the_starts_on_the_host_and_steps_in_place():
    """At smoke size: the towers' start and each table's initial rows come
    back as host arrays beside the device's parameters and rows, and the
    AdamW step takes the parameters, the gradient and the moments in place
    (the arrays given to it are gone after it)."""
    bench = registry.Bench(ROOT)
    cfg = dict(bench.config("dlrm-mlperf"), model=conftest.SMOKE_MODELS["dlrm-mlperf"],
               rows_per_table_per_chip=512, global_batch=256)
    model = registry.load(BENCH / "reference", "dlrm-mlperf")
    init_dense, dense_step, _ = common.make_steps(model, cfg)
    params, p0, m, v = common._towers(init_dense, 2**31 + 7)
    flat_p, flat_p0 = check.flat(params), check.flat(p0)
    assert flat_p.keys() == flat_p0.keys()
    for k, x in flat_p0.items():
        assert isinstance(x, np.ndarray) and isinstance(flat_p[k], jax.Array)
        np.testing.assert_array_equal(x, np.asarray(flat_p[k]))

    mix = dict(bench.traffic("train.zipf"), ring_batches=1)
    (batch,) = traffic.make_ring(mix, cfg, 1, _smoke_columns(model, cfg, 1), 2**31 + 7)
    (tab,) = common._tables([common._column_keys(model, cfg, batch, 1, None)]).values()
    assert isinstance(tab["init"], np.ndarray) and isinstance(tab["emb"], jax.Array)
    assert tab["init"].shape == tab["emb"].shape == (common.bucket(tab["n"], 16), 16)
    np.testing.assert_array_equal(tab["init"][:tab["n"]],
                                  common.init_rows_host(tab["keys"], 16))

    grads = jax.tree.map(jnp.ones_like, params)
    given = jax.tree.leaves((params, grads, m, v))
    dense_step(params, grads, m, v, jnp.float32(1))
    assert all(x.is_deleted() for x in given)


def test_table_padding_is_a_multiple_of_2_to_the_23_values():
    """65,536 rows at 128 wide (as before) and at any narrower width, 4,096
    rows at 2,048 wide; never fewer than one multiple."""
    assert common.bucket(1, 128) == common.bucket(65_536, 128) == 65_536
    assert common.bucket(65_537, 128) == 131_072
    assert common.bucket(1, 16) == common.bucket(65_536, 8) == 65_536
    assert common.bucket(20_480, 2048) == 20_480
    assert common.bucket(20_481, 2048) == 24_576
    assert common.bucket(1, 2048) == 4_096


class _Counted:
    """A toy configuration: one scalar tower ``w`` and no table; each
    example's loss is (1 + s) w, where ``s`` is a buffer that counts the
    steps run, moved by a rule and not by the gradient, which the program
    would keep among its towers' parameters (``dense/s``). Its examples are
    the raw ids of an int64 column."""

    @staticmethod
    def groups(cfg):
        return []

    @staticmethod
    def init_dense(key, cfg):
        return {"w": jnp.float32(2.0)}

    @staticmethod
    def init_buffers(cfg):
        return {"dense": {"s": jnp.float32(0.0)}}

    @staticmethod
    def example_losses(params, cfg, rows, batch, quantize, buffers):
        ids = batch["ids"]
        assert ids.dtype == jnp.int64
        s = buffers["dense"]["s"]
        losses = (1 + s) * params["w"] * jnp.ones(ids.shape, jnp.float32)
        return losses, {"dense": {"s": s + 1}}


def test_buffers_are_threaded_from_step_to_step():
    """``init_buffers`` gives the state that ``example_losses`` takes and
    returns; ``run`` hands each step's buffers to the next. Step k's loss is
    k w_{k-1}, and since every gradient (k) clips to 1, AdamW moves ``w`` by
    lr (1 + wd w) a step (to within its eps). The buffers come back at the
    start and the end, and ``buffer_gap`` compares the program's leaf at
    their path with the reference's: a program that moves it as the
    reference does reads 0, one that leaves it unchanged reads 1 and not
    ``correct``; the program's own readings of that leaf, kept among its
    towers' parameters, are left out of the other numbers."""
    cfg = {"optimizer": registry.Bench(ROOT).config("dlrm-mlperf")["optimizer"]}
    batch = {"ids": (np.arange(8, dtype=np.int64) * 2**40, np.arange(9, dtype=np.int32))}
    r = common.run(_Counted, cfg, 0, [batch] * 3, 1)
    hp = cfg["optimizer"]["dense"]
    w, want = 2.0, []
    for k in range(1, 4):
        want.append(k * w)
        w -= hp["lr"] * (1 + hp["weight_decay"] * w)
    np.testing.assert_allclose(r["losses"], want, rtol=1e-6)
    assert r["grad_norms"] == {"dense/w": 1.0}
    np.testing.assert_allclose(r["state"]["dense"]["w"], w, rtol=1e-6)
    assert (r["state"]["buffers0"], r["state"]["buffers"]) == ({"dense/s": 0.0}, {"dense/s": 3.0})

    limits = {"loss_gap": 1e-6, "change_gap": 1e-6, "buffer_gap": 0.1}
    for kept, gap in ((3.0, 0.0), (0.0, 1.0)):
        # the reference in the program's place, reading the buffer as a
        # program that keeps it among its towers' parameters would
        prog = dict(r, rows={}, dense={**r["state"]["dense"], "s": np.float32(kept)},
                    buffers={"dense/s": np.float32(kept)},
                    grad_norms={**r["grad_norms"], "dense/s": 0.0},
                    change_norms={**r["change_norms"], "dense/s": kept},
                    m1={**r["m1"], "dense": {**r["m1"]["dense"], "s": np.float32(0)}})
        nums = check.numbers(prog, r, common.init_rows_host)
        assert nums["buffer_gap"] == (gap, "dense/s")
        assert nums["loss_gap"][0] == nums["change_gap"][0] == nums["dense_gap"][0] == 0
        assert check.judge(nums, limits)[0] is (gap == 0)
