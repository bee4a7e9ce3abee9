"""Tiered embedding storage tests (host tier + HBM cache, DESIGN.md §3-§4).

Covers the ISSUE-1 checklist: bitwise promote→train→demote→promote
round-trips (embedding AND SparseAdam slots), LRU vs LFU victim selection,
frequency-admission filtering, tier-union checkpointing across a changed
device count, and the acceptance run — a Trainer training loop whose
device tier is far smaller than the live working set matching an all-HBM
control run's loss trajectory with zero overflow fallbacks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
from repro.core.feature_engine import FeatureSpec
from repro.io.ragged import Ragged
from repro.optim.sparse_adam import SparseAdamConfig
from repro.storage import (
    FrequencyAdmissionPolicy, HostStore, LFUPolicy, LRUPolicy, StorageConfig,
    make_policy,
)

SOPT = SparseAdamConfig(lr=0.1)


def _engine(rows=8, storage=None, n_devices=1):
    specs = [FeatureSpec("f", transform="hash", emb_dim=4, pooling="sum")]
    return EmbeddingEngine(specs, EngineConfig(
        mesh_axes=(), n_devices=n_devices, rows_per_shard=rows,
        map_capacity_per_shard=128, u_budget=16, per_dest_cap=16,
        recv_budget=16, storage=storage))


def _step(eng, state, ids_list, i, tiered=True):
    """One single-shard train step with value-dependent gradients."""
    ids = {"f": Ragged.from_lists([list(ids_list)], nnz_budget=8)}
    met = {}
    if tiered:
        state, met = eng.storage_prefetch(state, ids, i)
    stl = jax.tree.map(lambda x: x[0], state)
    stl, rows, plans, fmet = eng.fetch_local(stl, ids, jnp.int32(i))
    g = {k: rows[k] * 0.5 for k in rows}
    stl = eng.update_local(stl, plans, g, SOPT, jnp.int32(i))
    state = jax.tree.map(lambda S, L: S.at[0].set(L), state, stl)
    if tiered:
        state, amet = eng.storage_admit(state, i)
        met.update(amet)
    return state, met, fmet


def _eng_id(eng, raw: int) -> int:
    r = Ragged.from_lists([[raw]], nnz_budget=1)
    return int(np.asarray(eng.engine_ids({"f": r})["dim4"])[0])


def _sorted_export(rows):
    o = np.argsort(rows["ids"])
    return (rows["ids"][o], rows["emb"][o],
            {k: v[o] for k, v in rows["slots"].items()})


# ---------------------------------------------------------------------------
# HostStore (numpy arena)
# ---------------------------------------------------------------------------

class TestHostStore:
    def test_put_get_bitwise(self, rng):
        hs = HostStore(dim=4, init_capacity=16)
        ids = np.array([5, 9, 1], np.int64)
        emb = rng.normal(size=(3, 4)).astype(np.float32)
        slots = {"m": rng.normal(size=(3, 4)).astype(np.float32),
                 "v": rng.normal(size=(3, 4)).astype(np.float32)}
        hs.put(ids, emb, slots, np.array([1, 2, 3], np.int32))
        found, e, s, lu = hs.get(np.array([9, 1, 7], np.int64))
        np.testing.assert_array_equal(found, [True, True, False])
        np.testing.assert_array_equal(e[0], emb[1])  # bitwise
        np.testing.assert_array_equal(s["v"][1], slots["v"][2])
        assert hs.n_rows == 3

    def test_upsert_overwrites_in_place(self, rng):
        hs = HostStore(dim=2, init_capacity=16)
        hs.put([3], np.ones((1, 2), np.float32),
               {"m": np.zeros((1, 2), np.float32),
                "v": np.zeros((1, 2), np.float32)}, [1])
        hs.put([3], 2 * np.ones((1, 2), np.float32),
               {"m": np.ones((1, 2), np.float32),
                "v": np.ones((1, 2), np.float32)}, [2])
        assert hs.n_rows == 1
        _, e, s, lu = hs.get([3])
        np.testing.assert_array_equal(e[0], [2.0, 2.0])
        assert int(lu[0]) == 2

    def test_growth_and_compaction(self, rng):
        hs = HostStore(dim=2, init_capacity=4, compact_waste=0.25)
        zeros = lambda n: {"m": np.zeros((n, 2), np.float32),
                           "v": np.zeros((n, 2), np.float32)}
        ids = np.arange(100, dtype=np.int64)
        hs.put(ids, rng.normal(size=(100, 2)).astype(np.float32),
               zeros(100), np.zeros(100, np.int32))
        assert hs.capacity >= 100
        hs.remove(ids[:80])
        assert hs.n_dead == 80
        # next append triggers compaction instead of growth once waste > 25%
        cap_before = hs.capacity
        big = np.arange(200, 200 + cap_before - hs.top + 1, dtype=np.int64)
        hs.put(big, rng.normal(size=(big.size, 2)).astype(np.float32),
               zeros(big.size), np.zeros(big.size, np.int32))
        assert hs.n_dead == 0  # compacted
        assert hs.n_rows == 20 + big.size

    def test_mixed_upsert_surviving_compaction(self, rng):
        """A put() mixing existing + fresh ids that triggers compaction must
        resolve arena rows AFTER relocation (regression: stale indices wrote
        one id's record over another's)."""
        hs = HostStore(dim=2, init_capacity=8, compact_waste=0.1)
        zeros = lambda n: {"m": np.zeros((n, 2), np.float32),
                           "v": np.zeros((n, 2), np.float32)}
        ids = np.arange(8, dtype=np.int64)
        emb = np.arange(16, dtype=np.float32).reshape(8, 2)
        hs.put(ids, emb, zeros(8), np.zeros(8, np.int32))
        hs.remove(ids[:5])  # holes → next append compacts
        keep_emb = emb[5:].copy()
        # upsert one existing id (6) + fresh ids → forces compact mid-put
        up = np.array([6, 100, 101, 102], np.int64)
        hs.put(up, np.full((4, 2), 9.0, np.float32), zeros(4),
               np.ones(4, np.int32))
        _, e, _, _ = hs.get([5, 6, 7])
        np.testing.assert_array_equal(e[0], keep_emb[0])  # untouched survives
        np.testing.assert_array_equal(e[1], [9.0, 9.0])   # upsert landed
        np.testing.assert_array_equal(e[2], keep_emb[2])
        found, e, _, _ = hs.get([100, 101, 102])
        assert found.all()
        np.testing.assert_array_equal(e, np.full((3, 2), 9.0))

    def test_pop_is_move(self, rng):
        hs = HostStore(dim=2, init_capacity=8)
        hs.put([7], np.ones((1, 2), np.float32),
               {"m": np.zeros((1, 2), np.float32),
                "v": np.zeros((1, 2), np.float32)}, [1])
        found, e, _, _ = hs.pop([7])
        assert found[0] and hs.n_rows == 0
        found, _, _, _ = hs.get([7])
        assert not found[0]

    def test_export_load_roundtrip(self, rng):
        hs = HostStore(dim=3, init_capacity=8)
        ids = np.array([11, 4, 2], np.int64)
        emb = rng.normal(size=(3, 3)).astype(np.float32)
        slots = {"m": rng.normal(size=(3, 3)).astype(np.float32),
                 "v": rng.normal(size=(3, 3)).astype(np.float32)}
        hs.put(ids, emb, slots, np.array([5, 6, 7], np.int32))
        hs.remove([4])
        data = hs.export()
        hs2 = HostStore(dim=3, init_capacity=8)
        hs2.load(data)
        assert hs2.n_rows == 2
        _, e, s, _ = hs2.get([11, 2])
        np.testing.assert_array_equal(e[0], emb[0])
        np.testing.assert_array_equal(s["v"][1], slots["v"][2])


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------

class TestPolicies:
    IDS = np.array([10, 20, 30], np.int64)

    def test_lru_picks_least_recent(self):
        v = LRUPolicy().select_victims(
            self.IDS, np.array([5, 2, 9]), np.array([1, 9, 9]), 1)
        np.testing.assert_array_equal(v, [20])

    def test_lfu_picks_least_frequent_recency_tiebreak(self):
        v = LFUPolicy().select_victims(
            self.IDS, np.array([5, 2, 9]), np.array([3, 1, 1]), 2)
        np.testing.assert_array_equal(v, [20, 30])  # counts 1,1 → older first
        v = LFUPolicy().select_victims(
            self.IDS, np.array([5, 9, 2]), np.array([3, 1, 1]), 1)
        np.testing.assert_array_equal(v, [30])  # tie broken by last_use

    def test_admission_mask(self):
        p = FrequencyAdmissionPolicy(min_count_to_admit=3)
        np.testing.assert_array_equal(
            p.admit(np.array([1, 3, 2, 7])), [False, True, False, True])
        assert LRUPolicy().admit(np.array([1, 1])).all()

    def test_make_policy_parsing(self):
        assert make_policy("lru").name == "lru"
        assert make_policy("lfu").name == "lfu"
        p = make_policy("freq:4:lfu")
        assert p.min_count_to_admit == 4 and isinstance(p.base, LFUPolicy)
        with pytest.raises(ValueError):
            make_policy("arc")


# ---------------------------------------------------------------------------
# Tiered coordinator + engine integration
# ---------------------------------------------------------------------------

class TestTieredRoundTrip:
    def test_tiered_matches_all_hbm_bitwise(self):
        """Heavy churn (capacity 7 ≪ working set 20) vs an all-HBM control:
        every embedding and Adam slot value must round-trip bit-exactly
        through arbitrarily many demote→promote cycles."""
        def run(eng, tiered):
            state = eng.init_state()
            r = np.random.default_rng(0)
            for i in range(1, 15):
                state, _, fmet = _step(eng, state, r.integers(0, 20, 5), i,
                                       tiered=tiered)
                assert int(fmet["dim4/idmap_row_overflow"]) == 0
            return eng.export_rows(state)

        ctl = run(_engine(rows=64), tiered=False)["dim4"]
        tier = run(_engine(rows=8, storage=StorageConfig(policy="lru")),
                   tiered=True)["dim4"]
        ia, ea, sa = _sorted_export(ctl)
        ib, eb, sb = _sorted_export(tier)
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(ea, eb)  # bitwise
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])

    def test_explicit_demote_promote_cycle(self):
        """evict_to_host spills rows (state preserved), the next touch
        promotes them back bitwise-identically."""
        eng = _engine(rows=8, storage=StorageConfig(policy="lru"))
        state = eng.init_state()
        for i in range(1, 4):
            state, _, _ = _step(eng, state, [1, 2, 3], i)
        before = eng.export_rows(state)["dim4"]
        assert eng.storage.host_rows() == 0

        state, met = eng.evict_to_host(state, older_than=100)
        assert met["spilled_stale"] == 3
        assert eng.storage.device_resident() == 0
        assert eng.storage.host_rows() == 3
        mid = eng.export_rows(state)["dim4"]  # union export sees host rows
        np.testing.assert_array_equal(np.sort(mid["ids"]), np.sort(before["ids"]))

        state, met, _ = _step(eng, state, [1, 2, 3], 4)
        assert met["promoted"] == 3 and met["hit_rate"] == 0.0
        # control: same 4 steps, no demote cycle in between
        ctl_eng = _engine(rows=8, storage=StorageConfig(policy="lru"))
        ctl = ctl_eng.init_state()
        for i in range(1, 5):
            ctl, _, _ = _step(ctl_eng, ctl, [1, 2, 3], i)
        ia, ea, sa = _sorted_export(ctl_eng.export_rows(ctl)["dim4"])
        ib, eb, sb = _sorted_export(eng.export_rows(state)["dim4"])
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(ea, eb)
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])

    def test_lru_vs_lfu_victim_selection(self):
        """X is frequent-but-old, Y is recent-but-rare: LRU demotes X,
        LFU demotes Y."""
        def run(policy):
            eng = _engine(rows=3, storage=StorageConfig(policy=policy))  # 2 usable
            state = eng.init_state()
            for i, ids in enumerate(([8], [8], [8], [9], [7]), start=1):
                state, _, _ = _step(eng, state, ids, i)
            hs = eng.storage.host[ "dim4"]
            demoted_raw = [r for r in (8, 9)
                           if hs.contains(np.array([_eng_id(eng, r)]))[0]]
            return demoted_raw

        assert run("lru") == [8]   # X=8 oldest last_use
        assert run("lfu") == [9]   # Y=9 lowest count

    def test_admission_rejects_first_timers(self):
        """freq:2 — a first-seen id is trained but spilled post-step; its
        second occurrence promotes it back and it stays resident."""
        eng = _engine(rows=8, storage=StorageConfig(policy="freq:2"))
        state = eng.init_state()
        state, met, _ = _step(eng, state, [42], 1)
        assert met["admission_demoted"] == 1
        assert eng.storage.device_resident() == 0
        assert eng.storage.host_rows() == 1

        state, met, _ = _step(eng, state, [42], 2)
        assert met["promoted"] == 1 and met["admission_demoted"] == 0
        assert eng.storage.device_resident() == 1
        assert eng.storage.host_rows() == 0

        # trained through both steps exactly like an unfiltered control
        ctl_eng = _engine(rows=8, storage=StorageConfig(policy="lru"))
        ctl = ctl_eng.init_state()
        for i in (1, 2):
            ctl, _, _ = _step(ctl_eng, ctl, [42], i)
        _, ea, sa = _sorted_export(ctl_eng.export_rows(ctl)["dim4"])
        _, eb, sb = _sorted_export(eng.export_rows(state)["dim4"])
        np.testing.assert_array_equal(ea, eb)
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])

    def test_checkpoint_across_device_count_with_both_tiers(self):
        """Export the tier UNION from a 1-shard engine under capacity
        pressure, import into a 2-shard engine whose device tier is also
        too small — rows land across tiers, nothing is lost, values are
        bitwise-preserved, and counts survive for the policies."""
        e1 = _engine(rows=8, storage=StorageConfig(policy="lru"))
        state = e1.init_state()
        r = np.random.default_rng(1)
        for i in range(1, 12):
            state, _, _ = _step(e1, state, r.integers(0, 20, 5), i)
        rows = e1.export_rows(state)
        assert e1.storage.host_rows() > 0          # both tiers populated
        assert "counts" in rows["dim4"]

        e2 = _engine(rows=8, storage=StorageConfig(policy="lfu"), n_devices=2)
        st2 = e2.import_rows(rows)
        back = e2.export_rows(st2)
        ia, ea, sa = _sorted_export(rows["dim4"])
        ib, eb, sb = _sorted_export(back["dim4"])
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(ea, eb)
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])
        n_live = ia.size
        assert n_live > 2 * 7                       # must not fit in HBM alone
        assert e2.storage.host_rows() > 0
        assert e2.storage.device_resident() + e2.storage.host_rows() == n_live
        # counts survived the trip (admission/LFU state)
        cnts = e2.storage.counts["dim4"]
        assert sum(cnts.values()) == int(rows["dim4"]["counts"].sum())


# ---------------------------------------------------------------------------
# Acceptance: Trainer run, device tier ≪ live working set
# ---------------------------------------------------------------------------

class TestTrainerAcceptance:
    def test_tiered_training_matches_all_hbm_loss(self):
        from repro.configs.base import ShapeCell
        from repro.launch.cells import build_cell
        from repro.launch.common import CellOptions
        from repro.launch.mesh import make_mesh
        from repro.pipelines import TrainConfig, Trainer

        steps = 15
        shape = ShapeCell("train_batch", "train", {"batch": 32})

        def run(opts, hooks):
            cell = build_cell("wide-deep", "train_batch", make_mesh(),
                              opts, smoke=True, shape_override=shape)
            tr = Trainer(cell, TrainConfig(total_steps=steps, log_every=1,
                                           watchdog=False),
                         hooks=cell.storage_hooks if hooks else None)
            with cell.mesh:
                state = cell.init_state()
                res = tr.run(state, (cell.make_batch(s) for s in range(steps)))
            return res, cell

        # device tier: 512 rows ≪ live working set (~4k ids over 15 steps)
        res_t, cell_t = run(CellOptions(
            remat=False, zero1=False, storage=StorageConfig(policy="lru"),
            storage_device_rows=512), hooks=True)
        res_c, _ = run(CellOptions(remat=False, zero1=False), hooks=False)

        hist_t, hist_c = res_t.metrics_history, res_c.metrics_history
        assert res_t.steps_run == steps
        # no overflow-row fallbacks, ever
        for m in hist_t:
            assert m["dim8/idmap_row_overflow"] == 0
            assert m["storage/unplaceable"] == 0
        # cache hit-rate metrics are reported
        assert all("storage/hit_rate" in m for m in hist_t)
        assert 0.0 < hist_t[-1]["storage/hit_rate"] <= 1.0
        # the device tier really is a small cache over a larger host tier
        assert hist_t[-1]["storage/device_rows"] <= 511
        assert hist_t[-1]["storage/host_rows"] > 1000
        # loss trajectory matches the all-HBM control within tolerance
        lt = [m["loss"] for m in hist_t]
        lc = [m["loss"] for m in hist_c]
        np.testing.assert_allclose(lt, lc, rtol=1e-4, atol=1e-6)

    def test_tiered_checkpoint_resume(self, tmp_path):
        """Preemption path: the host tier rides the checkpoint
        (extra.safetensors) and a resumed tiered run — whose restored state
        leaves are NUMPY arrays — continues identically to a straight run."""
        from repro.configs.base import ShapeCell
        from repro.launch.cells import build_cell
        from repro.launch.common import CellOptions
        from repro.launch.mesh import make_mesh
        from repro.pipelines import TrainConfig, Trainer

        shape = ShapeCell("train_batch", "train", {"batch": 32})
        opts = CellOptions(remat=False, zero1=False,
                           storage=StorageConfig(policy="lru"),
                           storage_device_rows=512)

        def run(ckpt, steps, resume):
            cell = build_cell("wide-deep", "train_batch", make_mesh(),
                              opts, smoke=True, shape_override=shape)
            tr = Trainer(cell, TrainConfig(total_steps=steps,
                                           ckpt_dir=str(ckpt), ckpt_every=3,
                                           resume=resume, log_every=1,
                                           watchdog=False),
                         hooks=cell.storage_hooks)
            with cell.mesh:
                state = cell.init_state()
                state, start, _ = tr.try_resume(state)
                res = tr.run(state,
                             (cell.make_batch(s) for s in range(start, steps)),
                             start_step=start)
            return res

        straight = run(tmp_path / "a", 6, resume=False)
        run(tmp_path / "b", 3, resume=False)
        resumed = run(tmp_path / "b", 6, resume=True)
        assert resumed.resumed_from == 3
        assert (tmp_path / "b" / "step_0000000006" / "extra.safetensors").exists()
        a, b = straight.metrics_history[-1], resumed.metrics_history[-1]
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
        assert a["storage/host_rows"] == b["storage/host_rows"]
        assert a["storage/device_rows"] == b["storage/device_rows"]
