"""CPU tests of the plain references against the program at smoke size:
the same keys, the same initial rows, and, over the checked steps, the same
losses, rows with their Adam moments, and tower parameters to within what
the program's bfloat16 tower products allow."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "reference"))

import check  # noqa: E402
import common  # noqa: E402
import harness  # noqa: E402

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
