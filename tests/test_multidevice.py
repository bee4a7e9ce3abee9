"""Multi-device semantics via subprocess (8 forced host devices) — keeps the
main pytest process single-device per the dry-run isolation rule.

Covers: all-to-all exchange correctness vs single-device oracle, sharded
recsys train step on a 2-axis mesh, LM train step with TP, ZeRO-1 opt-state
sharding, and elastic N→M checkpoint restore.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str, n_dev: int = 8, timeout: int = 480) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        assert jax.device_count() == {n_dev}
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


class TestExchangeMultiDevice:
    def test_sharded_fetch_matches_single_device(self):
        """8-way sharded engine fetch == 1-device engine fetch (same ids)."""
        run_sub("""
            from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
            from repro.core.feature_engine import FeatureSpec
            from repro.io.ragged import Ragged

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("data",))
            specs = [FeatureSpec("f", transform="hash", emb_dim=8, pooling="sum")]

            def build(axes, n_dev):
                return EmbeddingEngine(specs, EngineConfig(
                    mesh_axes=axes, n_devices=n_dev, rows_per_shard=512,
                    map_capacity_per_shard=1024, u_budget=64, per_dest_cap=64,
                    recv_budget=min(128, 64 * n_dev)))

            r = np.random.default_rng(0)
            rows = [list(r.integers(0, 40, 3)) for _ in range(16)]

            # ---- single-device oracle
            eng1 = build((), 1)
            st1 = jax.tree.map(lambda x: x[0], eng1.init_state())
            ids1 = {"f": Ragged.from_lists(rows, nnz_budget=48)}
            st1, rr1, pl1, _ = eng1.fetch_local(st1, ids1, jnp.int32(1))
            acts1 = eng1.activations(rr1, pl1, ids1)["f"]

            # ---- 8-way sharded: each device gets 2 rows
            eng8 = build(("data",), 8)
            state8 = eng8.init_state()
            per_dev = [Ragged.from_lists(rows[i*2:(i+1)*2], nnz_budget=6)
                       for i in range(8)]
            vals = jnp.concatenate([p.values for p in per_dev])
            splits = jnp.concatenate([p.row_splits for p in per_dev])
            sp = P("data")

            def step(sp_state, vals, splits):
                st = jax.tree.map(lambda x: x[0], sp_state)
                ids = {"f": Ragged(vals, splits)}
                st, rr, pl, met = eng8.fetch_local(st, ids, jnp.int32(1))
                acts = eng8.activations(rr, pl, ids)["f"]
                return acts

            acts8 = jax.jit(shard_map(
                step, mesh=mesh, in_specs=(sp, sp, sp), out_specs=sp,
                check_vma=False))(state8, vals, splits)
            np.testing.assert_allclose(np.asarray(acts8), np.asarray(acts1),
                                       rtol=1e-5, atol=1e-5)
            print("EXCHANGE_OK")
        """)

    def test_counters_sum_counts_and_max_depths_across_chips(self):
        """On 4 devices, the engine's metrics leave shard_map as the sum of
        each device's counts and the max of its rounds and depths."""
        out = run_sub("""
            from repro.core.embedding_engine import (
                EmbeddingEngine, EngineConfig, MAX_METRICS, reduce_metrics)
            from repro.core.feature_engine import FeatureSpec
            from repro.io.ragged import Ragged
            from repro.launch.mesh import make_mesh

            mesh = make_mesh((4,), ("data",))
            specs = [FeatureSpec("f", transform="hash", emb_dim=4, pooling="sum")]
            # a small map per device, so that probe chains grow
            eng = EmbeddingEngine(specs, EngineConfig(
                mesh_axes=("data",), n_devices=4, rows_per_shard=128,
                map_capacity_per_shard=128, u_budget=64, per_dest_cap=64,
                recv_budget=128))
            r = np.random.default_rng(0)
            vals = jnp.asarray(r.integers(0, 1 << 40, 4 * 48), jnp.int64)
            splits = jnp.tile(jnp.arange(49, dtype=jnp.int32), 4)
            sp = P("data")

            def step(sp_state, vals, splits):
                st = jax.tree.map(lambda x: x[0], sp_state)
                st, _, _, met = eng.fetch_local(st, {"f": Ragged(vals, splits)},
                                                jnp.int32(1))
                return (jax.tree.map(lambda x: x[None], st),
                        {k: v[None] for k, v in met.items()},
                        reduce_metrics(met, ("data",)))

            fetch = jax.jit(shard_map(
                step, mesh=mesh, in_specs=(sp, sp, sp), out_specs=(sp, sp, P()),
                check_vma=False))
            state, per, red = fetch(eng.init_state(), vals, splits)
            # a second call: its probe runs each chip's max_depth
            _, per2, red2 = fetch(state, vals + (1 << 41), splits)
            for p_, r_ in ((per, red), (per2, red2)):
                assert set(r_) == set(p_)
                for k, v in r_.items():
                    each = np.asarray(p_[k])
                    want = each.max() if k.split("/")[-1] in MAX_METRICS else each.sum()
                    assert int(v) == int(want), (k, v, each)
                assert int(r_["dim4/idmap_rounds"]) == 32
                assert int(r_["dim4/exch_ids"]) == 4 * 48
                # the claim runs to its depth on each chip, or to the budget
                # where an id found no slot; the max leaves
                claim = np.asarray(p_["dim4/idmap_claim_rounds"])
                np.testing.assert_array_equal(claim, np.where(
                    np.asarray(p_["dim4/idmap_probe_overflow"]) > 0, 32,
                    np.asarray(p_["dim4/idmap_claim_depth"])))
                assert int(r_["dim4/idmap_claim_rounds"]) == claim.max()
            assert int(red["dim4/idmap_inserted"]) == int(red["dim4/exch_recv_uniq"])
            depth = np.asarray(per["dim4/idmap_claim_depth"])
            assert depth.max() > 1 and depth.sum() > depth.max()
            # the probe runs each chip's max_depth: 0, then the first depths
            assert int(red["dim4/idmap_probe_rounds"]) == 0
            probe = np.asarray(per2["dim4/idmap_probe_rounds"])
            np.testing.assert_array_equal(probe, depth)
            np.testing.assert_array_equal(np.asarray(state["dim4"]["idmap"].max_depth), depth)
            assert int(red2["dim4/idmap_probe_rounds"]) == probe.max() < probe.sum()
            print("COUNTERS_OK", depth.tolist())
        """, n_dev=4)
        assert "COUNTERS_OK" in out

    def test_grad_update_consistency(self):
        """Sharded update: a second fetch sees the updated rows (train cycle)."""
        run_sub("""
            from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
            from repro.core.feature_engine import FeatureSpec
            from repro.io.ragged import Ragged
            from repro.optim.sparse_adam import SparseAdamConfig

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("data",))
            specs = [FeatureSpec("f", transform="hash", emb_dim=4, pooling="sum")]
            eng = EmbeddingEngine(specs, EngineConfig(
                mesh_axes=("data",), n_devices=8, rows_per_shard=256,
                map_capacity_per_shard=512, u_budget=16, per_dest_cap=16,
                recv_budget=64))
            state = eng.init_state()
            sp = P("data")
            vals = jnp.tile(jnp.asarray([3, 9], jnp.int64), 8)
            splits = jnp.tile(jnp.asarray([0, 1, 2], jnp.int32), 8)

            def step2(sp_state, vals, splits):
                st = jax.tree.map(lambda x: x[0], sp_state)
                ids = {"f": Ragged(vals, splits)}
                st, rr, pl, _ = eng.fetch_local(st, ids, jnp.int32(1))
                g = {k: jnp.ones_like(v) for k, v in rr.items()}
                st = eng.update_local(st, pl, g, SparseAdamConfig(lr=0.1), jnp.int32(1))
                _, rr2, pl2, _ = eng.fetch_local(st, ids, jnp.int32(2))
                valid = pl2["dim4"].valid_r
                delta = (rr2["dim4"] - rr["dim4"]) * valid[:, None]
                return delta

            delta = jax.jit(shard_map(
                step2, mesh=mesh, in_specs=(sp, sp, sp), out_specs=sp,
                check_vma=False))(state, vals, splits)
            d = np.asarray(delta)
            live = np.abs(d).sum(axis=1) > 0
            assert live.sum() == 2, f"expected exactly 2 touched rows, got {{live.sum()}}"
            assert (d[live] < 0).all()  # all-ones grad -> negative Adam step
            print("UPDATE_OK")
        """)


class TestCellsMultiDevice:
    def test_recsys_train_cell_2d_mesh(self):
        run_sub("""
            from repro.configs.base import ShapeCell
            from repro.launch.cells import build_cell
            from repro.launch.common import CellOptions

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            shape = ShapeCell("train_batch", "train", {"batch": 32})
            cell = build_cell("dlrm-mlperf", "train_batch", mesh,
                              CellOptions(remat=False, zero1=False),
                              smoke=True, shape_override=shape)
            with mesh:
                state = cell.init_state()
                step = jax.jit(cell.step_fn)
                for s in range(3):
                    state, out = step(state, cell.make_batch(s))
            loss = float(out["loss"])
            assert 0 < loss < 5 and not np.isnan(loss)
            print("RECSYS_2D_OK", loss)
        """)

    def test_lm_train_cell_tp(self):
        run_sub("""
            from repro.configs.base import ShapeCell
            from repro.launch.cells import build_cell
            from repro.launch.common import CellOptions

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            shape = ShapeCell("train_4k", "train", {"seq_len": 32, "global_batch": 4})
            cell = build_cell("qwen2.5-3b", "train_4k", mesh,
                              CellOptions(remat=False, zero1=True),
                              smoke=True, shape_override=shape)
            with mesh:
                state = cell.init_state()
                step = jax.jit(cell.step_fn)
                l0 = None
                for s in range(5):
                    state, out = step(state, cell.make_batch(0))
                    l0 = l0 or float(out["loss"])
            assert float(out["loss"]) < l0  # same batch -> loss must drop
            print("LM_TP_OK", l0, float(out["loss"]))
        """)

    def test_moe_ep_dispatch(self):
        run_sub("""
            from repro.configs.base import ShapeCell
            from repro.launch.cells import build_cell
            from repro.launch.common import CellOptions

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            shape = ShapeCell("train_4k", "train", {"seq_len": 32, "global_batch": 4})
            cell = build_cell("qwen2-moe-a2.7b", "train_4k", mesh,
                              CellOptions(remat=False, zero1=False),
                              smoke=True, shape_override=shape)
            with mesh:
                state = cell.init_state()
                state, out = jax.jit(cell.step_fn)(state, cell.make_batch(0))
            assert not np.isnan(float(out["loss"]))
            print("MOE_EP_OK")
        """)

    def test_perf_levers_match_baseline(self):
        """sp_residual (manual SP layer incl. GQA kv∤tp) and fused_ce must be
        numerically equivalent to the GSPMD baseline (§Perf levers)."""
        run_sub("""
            from repro.models import transformer as tfm
            from repro.models.layers import FP32

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            for n_kv in (4, 2):   # 4 = kv==tp path; 2 = GQA kv∤tp select path
                cfg = tfm.TransformerConfig(name="t", n_layers=2, d_model=32,
                                            n_heads=8, n_kv_heads=n_kv,
                                            d_ff=64, vocab_size=61,
                                            remat=False, scan_layers=True)
                params = tfm.init(jax.random.PRNGKey(0), cfg)
                ctx = tfm.MeshCtx(mesh=mesh, dp=("data",), tp="model")
                r = np.random.default_rng(0)
                x = jnp.asarray(r.normal(size=(4, 32, 32)).astype(np.float32)) * 0.3
                labels = jnp.asarray(r.integers(0, 61, (4, 32)), jnp.int32)
                with mesh:
                    f0 = jax.jit(lambda p: tfm.lm_loss(p, cfg, x, labels, ctx, FP32)[0])
                    f1 = jax.jit(lambda p: tfm.lm_loss(
                        p, cfg, x, labels, ctx, FP32, sp_residual=True,
                        fused_ce=True)[0])
                    assert abs(float(f0(params)) - float(f1(params))) < 1e-4
                    g0 = jax.jit(jax.grad(f0))(params)
                    g1 = jax.jit(jax.grad(f1))(params)
                    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
                        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                   rtol=2e-3, atol=2e-4)
            print("LEVERS_OK")
        """)

    def test_compressed_grads_trains(self):
        """int8+EF compressed gradient psum: GIN trains to ~the fp32 loss."""
        run_sub("""
            from repro.configs.base import ShapeCell
            from repro.launch.cells import build_cell
            from repro.launch.common import CellOptions

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("data",))
            shape = ShapeCell("molecule", "graph_batch",
                              {"n_nodes": 10, "n_edges": 20, "batch": 16,
                               "d_feat": 8, "n_classes": 2})
            finals = {}
            for compress in (False, True):
                cell = build_cell("gin-tu", "molecule", mesh,
                                  CellOptions(remat=False, zero1=False,
                                              compress_grads=compress),
                                  smoke=True, shape_override=shape)
                with mesh:
                    state = cell.init_state()
                    step = jax.jit(cell.step_fn)
                    for s in range(25):
                        state, out = step(state, cell.make_batch(0))
                finals[compress] = float(out["loss"])
            assert abs(finals[True] - finals[False]) < 0.05, finals
            print("COMPRESS_OK", finals)
        """)

    def test_elastic_restore_4_to_8(self, tmp_path):
        """Engine state trained on 4 devices restores onto 8 via the
        export/import reshard path + sharded safetensors (DESIGN.md §8)."""
        body = f"""
            from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
            from repro.core.feature_engine import FeatureSpec
            from repro.io.ragged import Ragged
            from repro.optim.sparse_adam import SparseAdamConfig
            from repro.checkpoint import saver
            from jax.sharding import PartitionSpec as P

            n_dev = jax.device_count()
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((n_dev,), ("data",))
            specs = [FeatureSpec("f", transform="hash", emb_dim=4, pooling="sum")]
            eng = EmbeddingEngine(specs, EngineConfig(
                mesh_axes=("data",), n_devices=n_dev, rows_per_shard=128,
                map_capacity_per_shard=256, u_budget=16, per_dest_cap=16,
                recv_budget=min(64, 16 * n_dev)))
            sp = P("data")

            if n_dev == 4:
                state = eng.init_state()
                vals = jnp.tile(jnp.asarray([3, 9, 11], jnp.int64), n_dev)
                splits = jnp.tile(jnp.asarray([0, 2, 3], jnp.int32), n_dev)

                def step(sp_state, vals, splits):
                    st = jax.tree.map(lambda x: x[0], sp_state)
                    ids = {{"f": Ragged(vals, splits)}}
                    st, rr, pl, _ = eng.fetch_local(st, ids, jnp.int32(1))
                    g = {{k: jnp.ones_like(v) for k, v in rr.items()}}
                    st = eng.update_local(st, pl, g, SparseAdamConfig(lr=0.1),
                                          jnp.int32(1))
                    return jax.tree.map(lambda x: x[None], st)

                state = jax.jit(shard_map(step, mesh=mesh,
                    in_specs=(sp, sp, sp), out_specs=sp, check_vma=False))(
                    state, vals, splits)
                rows = eng.export_rows(state)
                saver.save(rows, r"{tmp_path}", step=1, n_shards=2)
                ids_sorted = np.sort(rows["dim4"]["ids"])
                print("SAVED4", ids_sorted.tolist())
            else:
                like = {{"dim4": {{"ids": np.zeros(3, np.int64),
                                   "emb": np.zeros((3, 4), np.float32),
                                   "slots": {{"m": np.zeros((3, 4), np.float32),
                                              "v": np.zeros((3, 4), np.float32)}},
                                   "last_use": np.zeros(3, np.int32)}}}}
                rows = saver.restore(r"{tmp_path}", like)
                state8 = eng.import_rows(rows)
                back = eng.export_rows(state8)
                np.testing.assert_array_equal(
                    np.sort(back["dim4"]["ids"]), np.sort(rows["dim4"]["ids"]))
                oa = np.argsort(rows["dim4"]["ids"]); ob = np.argsort(back["dim4"]["ids"])
                np.testing.assert_allclose(rows["dim4"]["emb"][oa],
                                           back["dim4"]["emb"][ob], rtol=1e-6)
                print("RESTORED8")
        """
        out4 = run_sub(body, n_dev=4)
        assert "SAVED4" in out4
        assert "RESTORED8" in run_sub(body, n_dev=8)
