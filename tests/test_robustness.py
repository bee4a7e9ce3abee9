"""Regression tests for production-robustness fixes (EXPERIMENTS.md
§Robustness) + perf-lever equivalence checks (§Perf)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blocks as blocks_lib, exchange, idmap as idmap_lib
from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
from repro.core.feature_engine import FeatureSpec
from repro.io.ragged import Ragged
from repro.optim import adamw
from repro.optim.sparse_adam import SparseAdamConfig


class TestOverflowRowPoisoning:
    """Row-capacity exhaustion must degrade to zero embeddings, never NaN."""

    def _tiny_engine(self):
        # 8 data rows only → exhausts immediately
        return EmbeddingEngine(
            [FeatureSpec("f", transform="hash", emb_dim=4, pooling="sum")],
            EngineConfig(mesh_axes=(), n_devices=1, rows_per_shard=8,
                         map_capacity_per_shard=64, u_budget=32,
                         per_dest_cap=32, recv_budget=32))

    def test_overflow_rows_are_zero_and_untrained(self):
        eng = self._tiny_engine()
        st = jax.tree.map(lambda x: x[0], eng.init_state())
        opt = SparseAdamConfig(lr=0.5)
        for step in range(1, 30):
            ids = {"f": Ragged.from_lists(
                [[step * 100 + j] for j in range(16)], nnz_budget=16)}
            st, rows_r, plans, met = eng.fetch_local(st, ids, jnp.int32(step))
            # overflow ids must come back as EXACT zeros
            valid = np.asarray(plans["dim4"].valid_r)
            rr = np.asarray(rows_r["dim4"])
            assert not np.isnan(rr).any()
            assert (rr[~valid] == 0).all()
            g = {k: jnp.ones_like(v) for k, v in rows_r.items()}
            st = eng.update_local(st, plans, g, opt, jnp.int32(step))
        # overflow row 0 must have never been trained (exponential-NaN bug)
        emb = np.asarray(st["dim4"]["blocks"].emb)
        assert (emb[idmap_lib.OVERFLOW_ROW] == 0).all()
        assert np.abs(emb).max() < 10.0  # no runaway rows anywhere

    def test_serve_time_missing_ids_are_zero(self):
        eng = self._tiny_engine()
        st = jax.tree.map(lambda x: x[0], eng.init_state())
        ids = {"f": Ragged.from_lists([[123], [456]], nnz_budget=2)}
        # train=False: ids never inserted → must read as zeros, not garbage
        st, rows_r, plans, _ = eng.fetch_local(st, ids, jnp.int32(1), train=False)
        acts = eng.activations(rows_r, plans, ids)
        np.testing.assert_array_equal(np.asarray(acts["f"]), 0.0)


class TestCompressedPsum:
    def test_single_device_identity_with_error_feedback(self, rng):
        g = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        err = jnp.zeros_like(g)
        total = jnp.zeros_like(g)
        # accumulated compressed sums converge to accumulated true sums
        # (error feedback: quantization residue is carried, not lost)
        acc_true = np.zeros(64, np.float32)
        for i in range(50):
            out, err = adamw.compressed_psum(g, (), err)
            total = total + out
            acc_true += np.asarray(g)
            # int8 quantization error per step ≤ scale/2; with EF the
            # ACCUMULATED error stays bounded by one step's scale
            scale = float(jnp.max(jnp.abs(g))) / 127.0
            assert float(jnp.abs(total - acc_true).max()) <= scale + 1e-6

    def test_quantization_is_int8_payload(self, rng):
        # the traced collective operand must be int32 of int8-clipped values
        g = jnp.asarray(rng.normal(size=(32,)).astype(np.float32)) * 100
        out, err = adamw.compressed_psum(g, (), jnp.zeros_like(g))
        # reconstruction error bounded by scale
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        assert float(jnp.abs(out - g).max()) <= scale * 0.5 + 1e-5


class TestElasticReshard:
    @pytest.mark.parametrize("d_from,d_to", [(1, 4), (4, 1), (2, 8)])
    def test_roundtrip_preserves_rows(self, rng, d_from, d_to):
        specs = [FeatureSpec("f", transform="hash", emb_dim=4, pooling="sum")]

        def build(n):
            return EmbeddingEngine(specs, EngineConfig(
                mesh_axes=(), n_devices=n, rows_per_shard=128,
                map_capacity_per_shard=256, u_budget=32, per_dest_cap=32,
                recv_budget=32))

        e1 = build(d_from)
        st = e1.init_state()
        # touch some rows on shard 0 (single-host test; multi-host path is
        # the same per-shard code under shard_map — test_multidevice.py)
        stl = jax.tree.map(lambda x: x[0], st)
        ids = {"f": Ragged.from_lists([[1, 2, 3], [4, 5]], nnz_budget=8)}
        stl, rr, pl, _ = e1.fetch_local(stl, ids, jnp.int32(1))
        g = {k: jnp.ones_like(v) for k, v in rr.items()}
        stl = e1.update_local(stl, pl, g, SparseAdamConfig(lr=0.1), jnp.int32(1))
        st = jax.tree.map(lambda a, b: a.at[0].set(b), st, stl)

        rows = e1.export_rows(st)
        e2 = build(d_to)
        st2 = e2.import_rows(rows)
        back = e2.export_rows(st2)
        a, b = rows["dim4"], back["dim4"]
        oa, ob = np.argsort(a["ids"]), np.argsort(b["ids"])
        np.testing.assert_array_equal(a["ids"][oa], b["ids"][ob])
        np.testing.assert_allclose(a["emb"][oa], b["emb"][ob], rtol=1e-6)
        for k in a["slots"]:
            np.testing.assert_allclose(a["slots"][k][oa], b["slots"][k][ob],
                                       rtol=1e-6)


class TestSharedTableSalts:
    """Shared-table id-space consistency (EXPERIMENTS.md §Robustness #4):
    a column with shared_table=X must map raw ids EXACTLY like column X,
    through BOTH hashing layers (FeatureEngine column salt + engine table
    salt) — and deterministically across processes (no Python hash())."""

    def test_shared_table_columns_alias(self):
        from repro.core.feature_engine import FeatureEngine

        specs = [
            FeatureSpec("cat_0", transform="hash", emb_dim=8),
            FeatureSpec("cand_items", transform="hash", emb_dim=8,
                        shared_table="cat_0"),
            FeatureSpec("other", transform="hash", emb_dim=8),
        ]
        fe = FeatureEngine(specs)
        eng = EmbeddingEngine(specs, EngineConfig(
            mesh_axes=(), n_devices=1, rows_per_shard=64,
            map_capacity_per_shard=128, u_budget=16, per_dest_cap=16,
            recv_budget=16))
        raw = Ragged.from_lists([[42], [7]], nnz_budget=2)
        ids, _ = fe.apply({"cat_0": raw, "cand_items": raw, "other": raw})
        a = np.asarray(ids["cat_0"].values)
        b = np.asarray(ids["cand_items"].values)
        c = np.asarray(ids["other"].values)
        np.testing.assert_array_equal(a, b)     # shared table → same fe-hash
        assert (a != c).all()                   # distinct table → distinct
        eids = eng.engine_ids(ids)["dim8"]
        e = np.asarray(eids)
        np.testing.assert_array_equal(e[0:2], e[2:4])  # same engine ids too

    def test_salts_process_deterministic(self):
        """The fe salt must be a pure function of the table name (FNV), not
        Python's per-process randomized hash()."""
        import subprocess, sys, os

        code = (
            "import os; os.environ.setdefault('PYTHONHASHSEED', '0');\n"
            "from repro.core.feature_engine import FeatureEngine, FeatureSpec\n"
            "import numpy as np\n"
            "fe = FeatureEngine([FeatureSpec('x', transform='hash', emb_dim=4)])\n"
            "print(int(np.asarray(fe._hash_salts)[0]))\n"
        )
        env = dict(os.environ, PYTHONPATH=os.path.join(
            os.path.dirname(__file__), "..", "src"))
        outs = set()
        for seed in ("1", "2"):
            env["PYTHONHASHSEED"] = seed
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True, timeout=120)
            assert r.returncode == 0, r.stderr[-500:]
            outs.add(r.stdout.strip())
        assert len(outs) == 1, f"salt differs across hash seeds: {outs}"


class TestMBUModel:
    def test_traffic_models_positive_and_bandwidth_bound(self):
        from repro.core import mbu

        for t in (mbu.t_bucketize(1000, 64), mbu.t_mod(1000),
                  mbu.t_ids_partition(1000), mbu.t_sequence_tile(100, 8, 16),
                  mbu.t_reduce(1000, 16), mbu.t_gather(1000, 16),
                  mbu.t_scatter(1000, 16)):
            assert t.essential_bytes > 0
            # the paper's premise: every sparse op has AI < 1 FLOP/byte
            assert t.arithmetic_intensity < 1.0, t.name

    def test_structural_mbu_of_pure_copy_is_high(self):
        from repro.core import mbu

        n = 1 << 16
        t = mbu.OpTraffic("copy", essential_bytes=8 * n)
        x = jnp.arange(n, dtype=jnp.float32)
        res = mbu.structural(t, lambda v: v * 2.0, x, target="TPU v5 lite")
        assert res.moved_bytes is not None
        assert res.bandwidth_intensity is not None
        assert res.bandwidth_intensity > 0.5  # elementwise ≈ roofline


class TestCrashRecoveryMatrix:
    """Delta-checkpoint crash matrix (DESIGN.md §13): a single injected
    fault at each persistence site — mid shard write, torn shard write,
    before the manifest commit, after the manifest but before HEAD — must
    leave a chain that recovers bit-identical to the previous committed
    save, and the restarted run must converge to the reference. Recovery
    is additionally exercised onto a DIFFERENT device count (elastic)."""

    CASES = [
        ("crash@frame:3", 1),     # mid-shard: save 2's first frame dies
        ("torn@frame:3", 2),      # torn shard AT the final path
        ("crash@manifest:2", 1),  # frames landed, manifest never renamed
        ("crash@head:2", 2),      # manifest committed, HEAD not updated
    ]

    @pytest.mark.parametrize("spec,d_recover", CASES)
    def test_single_fault_recovers_bit_identical(self, tmp_path, spec,
                                                 d_recover):
        from ft_harness import (FakeTrainer, assert_rows_equal, build_engine,
                                reference_run, run_chaos)
        from repro import obs
        from repro.ft import ChaosIO, ChaosSchedule, DeltaCheckpointer, \
            DirtyTracker

        total = 8
        ref = reference_run(total)
        io = ChaosIO(ChaosSchedule.parse(spec))
        recovered, attempts, tr = run_chaos(
            tmp_path, io, total_steps=total, save_every=2, ref=ref)
        assert [str(e) for e in io.fired] == [spec]
        # every fault lands during save@4; recovery falls back to save@2
        assert recovered == [2]
        assert_rows_equal(tr.engine.export_rows(tr.state), ref[total])
        # elastic: recover the finished chain onto another device count
        e2 = build_engine(n_devices=d_recover)
        ck2 = DeltaCheckpointer(tmp_path, e2,
                                DirtyTracker(registry=obs.MetricsRegistry()),
                                registry=obs.MetricsRegistry())
        res = ck2.recover(like_state=FakeTrainer(e2).full_state())
        assert res.step == total
        assert_rows_equal(e2.export_rows(res.state["sparse"]), ref[total])


class TestChaosAcceptance:
    """The §13 acceptance run: FIVE injected faults across one training
    run — a crash before the first HEAD write, a mid-shard crash, a TORN
    shard write during a COMPACTION save, a crash before a manifest
    commit, and a late mid-shard crash — each followed by a restart.
    At every crash point the recovered state must be bit-identical to an
    uninterrupted reference at the recovered step (the invariant: any
    prefix of a crash schedule recovers to a bit-identical model)."""

    SPEC = ("crash@head:1,crash@frame:5,torn@frame:9,"
            "crash@manifest:4,crash@frame:17")

    def test_five_fault_schedule_recovers_bit_identical_everywhere(
            self, tmp_path):
        from ft_harness import (FakeTrainer, assert_rows_equal, build_engine,
                                reference_run, run_chaos)
        from repro import obs
        from repro.ft import ChaosIO, ChaosSchedule, DeltaCheckpointer, \
            DirtyTracker

        total = 12
        ref = reference_run(total)
        io = ChaosIO(ChaosSchedule.parse(self.SPEC))
        recovered, attempts, tr = run_chaos(
            tmp_path, io, total_steps=total, save_every=2, ref=ref)
        assert len(io.fired) == 5
        assert sorted(str(e) for e in io.fired) == sorted(self.SPEC.split(","))
        # crash@head:1 recovers via the manifest scan (no HEAD yet); the
        # double 6 is the compaction save crashing twice (torn frame,
        # then manifest) before landing on the third try
        assert recovered == [2, 4, 6, 6, 10]
        crashed = [(s, comp) for s, status, comp in attempts
                   if status == "crashed"]
        assert crashed == [(2, False), (6, False), (8, True), (8, True),
                           (12, False)]
        # the torn shard write fired during a compaction save
        assert io.fired[2].action == "torn" and crashed[2][1]
        # the survivor equals the uninterrupted run, and so does a fresh
        # recovery of what it left on disk — on a resharded engine too
        assert_rows_equal(tr.engine.export_rows(tr.state), ref[total])
        for n_dev in (1, 2):
            e2 = build_engine(n_devices=n_dev)
            ck2 = DeltaCheckpointer(
                tmp_path, e2, DirtyTracker(registry=obs.MetricsRegistry()),
                registry=obs.MetricsRegistry())
            res = ck2.recover(like_state=FakeTrainer(e2).full_state())
            assert res.step == total
            assert_rows_equal(e2.export_rows(res.state["sparse"]), ref[total])

    @pytest.mark.parametrize("seed", [3, 11])
    def test_seeded_schedules_always_converge(self, tmp_path, seed):
        """Property sweep: ANY seeded schedule (torn frame guaranteed
        first) must drive to completion with every recovery bit-identical
        to the reference — no hand-placed crash points."""
        from ft_harness import reference_run, run_chaos
        from repro.ft import ChaosIO, ChaosSchedule

        total = 12
        ref = reference_run(total)
        sched = ChaosSchedule.seeded(seed, n_events=4, max_count=10)
        io = ChaosIO(sched)
        recovered, _, tr = run_chaos(
            tmp_path, io, total_steps=total, save_every=2, ref=ref)
        assert io.fired, f"schedule {sched} never fired"
        from ft_harness import assert_rows_equal
        assert_rows_equal(tr.engine.export_rows(tr.state), ref[total])
