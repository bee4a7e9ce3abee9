"""Unit + property tests for the RecIS core: Ragged/CSR, Feature Engine,
IDMap, Blocks, exchange (single-device), Embedding Engine, SparseAdam."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import blocks as blocks_lib, exchange, idmap as idmap_lib
from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
from repro.core.feature_engine import (
    FeatureEngine, FeatureSpec, fused_bucketize, fused_hash, fused_mod,
    hash_combine, splitmix64,
)
from repro.io.ragged import Ragged
from repro.optim.sparse_adam import SparseAdamConfig, apply_row_updates


# ---------------------------------------------------------------------------
# Ragged (CSR layout, §2.2.1)
# ---------------------------------------------------------------------------

class TestRagged:
    def test_from_lists_roundtrip(self):
        rows = [[1, 2, 3], [], [4], [5, 6]]
        r = Ragged.from_lists(rows, nnz_budget=10)
        assert r.n_rows == 4
        assert int(r.live_nnz()) == 6
        np.testing.assert_array_equal(np.asarray(r.row_lengths()), [3, 0, 1, 2])
        dense, mask = r.to_padded(3)
        np.testing.assert_array_equal(np.asarray(dense[0]), [1, 2, 3])
        assert not bool(mask[1].any())

    def test_budget_truncation_counts(self):
        r = Ragged.from_lists([[1] * 5, [2] * 5], nnz_budget=7)
        assert int(r.live_nnz()) == 7  # truncated, not crashed
        assert r.nnz_budget == 7

    def test_segment_ids_padding(self):
        r = Ragged.from_lists([[1, 2], [3]], nnz_budget=8)
        seg = np.asarray(r.segment_ids())
        np.testing.assert_array_equal(seg[:3], [0, 0, 1])
        assert (seg[3:] == r.n_rows).all()  # dead tail → out-of-range segment

    def test_truncate(self):
        r = Ragged.from_lists([[1, 2, 3, 4], [5], [6, 7]], nnz_budget=10)
        t = r.truncate(2)
        np.testing.assert_array_equal(np.asarray(t.row_lengths()), [2, 1, 2])
        dense, _ = t.to_padded(2)
        np.testing.assert_array_equal(np.asarray(dense), [[1, 2], [5, 0], [6, 7]])

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), budget_slack=st.integers(0, 10))
    def test_csr_invariants(self, seed, budget_slack):
        """Property: row_splits monotone; live prefix == Σ lengths; to_padded
        masks exactly the CSR structure."""
        r_ = np.random.default_rng(seed)
        rows = [list(r_.integers(0, 100, r_.integers(0, 6))) for _ in range(r_.integers(1, 12))]
        total = sum(len(x) for x in rows)
        rg = Ragged.from_lists(rows, nnz_budget=total + budget_slack)
        splits = np.asarray(rg.row_splits)
        assert (np.diff(splits) >= 0).all()
        assert splits[-1] == min(total, rg.nnz_budget)
        assert np.asarray(rg.valid_mask()).sum() == splits[-1]


# ---------------------------------------------------------------------------
# Feature Engine (fused transforms, §2.2.2)
# ---------------------------------------------------------------------------

class TestFeatureEngine:
    def test_fusion_count_is_per_type(self):
        """The paper's headline: >600 column transforms → ~3 fused ops."""
        specs = (
            [FeatureSpec(f"h{i}", transform="hash", emb_dim=8) for i in range(300)]
            + [FeatureSpec(f"m{i}", transform="mod", vocab_size=100, emb_dim=8)
               for i in range(200)]
            + [FeatureSpec(f"b{i}", transform="bucketize", boundaries=(0.0, 1.0),
                           emb_dim=8) for i in range(100)]
        )
        fe = FeatureEngine(specs)
        assert fe.n_fused_ops == 3

    def test_hash_deterministic_and_salted(self):
        specs = [FeatureSpec("a", transform="hash", emb_dim=8),
                 FeatureSpec("b", transform="hash", emb_dim=8)]
        fe = FeatureEngine(specs)
        batch = {n: Ragged.from_lists([[7], [9]], nnz_budget=4) for n in "ab"}
        ids1, _ = fe.apply(batch)
        ids2, _ = fe.apply(batch)
        np.testing.assert_array_equal(np.asarray(ids1["a"].values),
                                      np.asarray(ids2["a"].values))
        # same raw id, different column → different engine id (salting)
        assert int(ids1["a"].values[0]) != int(ids1["b"].values[0])

    def test_mod_semantics(self):
        vals = jnp.asarray([5, -7, 123], jnp.int64)
        cids = jnp.asarray([0, 0, 1], jnp.int32)
        out = fused_mod(vals, cids, jnp.asarray([3, 10], jnp.int64))
        np.testing.assert_array_equal(np.asarray(out), [2, 1, 3])

    def test_bucketize_matches_searchsorted(self, rng):
        b = np.sort(rng.normal(size=9)).astype(np.float32)
        vals = jnp.asarray(rng.normal(size=50).astype(np.float32))
        out = fused_bucketize(vals, jnp.zeros(50, jnp.int32),
                              jnp.asarray(b), jnp.asarray([0, 9], jnp.int32))
        want = np.searchsorted(b, np.asarray(vals), side="right")
        np.testing.assert_array_equal(np.asarray(out), want)

    def test_cross_produces_pairs(self):
        specs = [
            FeatureSpec("u", transform="hash", emb_dim=8),
            FeatureSpec("i", transform="hash", emb_dim=8),
            FeatureSpec("ux_i", transform="cross", cross_of=("u", "i"), emb_dim=8),
        ]
        fe = FeatureEngine(specs)
        batch = {"u": Ragged.from_lists([[1, 2]], nnz_budget=2),
                 "i": Ragged.from_lists([[10]], nnz_budget=2)}
        ids, _ = fe.apply(batch)
        assert int(ids["ux_i"].row_lengths()[0]) == 2  # 2×1 cartesian

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_splitmix_uniformity(self, seed):
        """Property (LLN balance, §2.2.2): hash-mod binning of any id set is
        within 5x of uniform across 8 bins for ≥512 ids."""
        r = np.random.default_rng(seed)
        ids = jnp.asarray(r.integers(0, 1 << 62, size=(2048,)).astype(np.int64))
        bins = np.asarray(splitmix64(ids) % jnp.uint64(8)).astype(np.int64)
        counts = np.bincount(bins, minlength=8)
        assert counts.max() <= 5 * max(counts.min(), 1)


# ---------------------------------------------------------------------------
# IDMap (conflict-free two-tier storage, §2.2.2)
# ---------------------------------------------------------------------------

class TestIDMap:
    def test_insert_then_lookup(self):
        m = idmap_lib.create(64, 32)
        ids = jnp.asarray([5, 9, 123456789, -1], jnp.int64)
        m, off, is_new, met = idmap_lib.lookup_or_insert(m, ids, jnp.int32(1))
        assert int(met["idmap_inserted"]) == 3
        assert bool(is_new[:3].all()) and not bool(is_new[3])
        off2 = idmap_lib.lookup(m, ids)
        np.testing.assert_array_equal(np.asarray(off[:3]), np.asarray(off2[:3]))
        assert int(off2[3]) == idmap_lib.OVERFLOW_ROW

    def test_conflict_free(self):
        """Distinct ids NEVER share a row (the paper's zero-conflict claim)."""
        m = idmap_lib.create(256, 200)
        r = np.random.default_rng(3)
        seen = {}
        for step in range(5):
            ids = jnp.asarray(np.unique(r.integers(0, 1 << 40, 30)), jnp.int64)
            m, off, _, met = idmap_lib.lookup_or_insert(m, ids, jnp.int32(step))
            assert int(met["idmap_probe_overflow"]) == 0
            for i, o in zip(np.asarray(ids), np.asarray(off)):
                if int(o) == idmap_lib.OVERFLOW_ROW:
                    continue
                assert seen.setdefault(int(i), int(o)) == int(o)
        rows = [v for v in seen.values()]
        assert len(rows) == len(set(rows))  # injective id → row

    def test_row_capacity_overflow_counted(self):
        m = idmap_lib.create(64, 4)  # only rows 1..3 available
        ids = jnp.asarray(np.arange(10), jnp.int64)
        m, off, is_new, met = idmap_lib.lookup_or_insert(m, ids, jnp.int32(1))
        assert int(met["idmap_row_overflow"]) == 7
        assert (np.asarray(off) == idmap_lib.OVERFLOW_ROW).sum() == 7

    def test_evict_and_reuse(self):
        m = idmap_lib.create(64, 32)
        ids1 = jnp.asarray([1, 2, 3], jnp.int64)
        m, off1, _, _ = idmap_lib.lookup_or_insert(m, ids1, jnp.int32(1))
        m, n = idmap_lib.evict(m, jnp.int32(2))  # evict last_use < 2 → all
        assert int(n) == 3
        assert int(m.n_live()) == 0
        ids2 = jnp.asarray([7, 8, 9], jnp.int64)
        m, off2, _, _ = idmap_lib.lookup_or_insert(m, ids2, jnp.int32(2))
        # recycled rows reused (free-stack pop)
        assert set(np.asarray(off2).tolist()) == set(np.asarray(off1).tolist())

    def test_probe_and_claim_depths_count_useful_rounds(self):
        """Planted collisions: three ids homed on the last slot wrap to
        slots 0 and 1 (claimed in rounds 1, 2, 3), and an id homed on slot
        0 then finds 0 and 1 taken (round 3). Of the 32-round budget, the
        claim runs until its last id is placed (its depth) and the probe
        runs the table's ``max_depth`` as it was before the call."""
        cap = 64
        cand = jnp.arange(1, 4096, dtype=jnp.int64)
        home = np.asarray(idmap_lib._home(cand, cap))
        last = np.asarray(cand)[home == cap - 1][:3]
        first = np.asarray(cand)[home == 0][:1]
        m = idmap_lib.create(cap, 32)

        def rounds(met):
            return (int(met["idmap_probe_rounds"]), int(met["idmap_claim_rounds"]))

        m, _, _, met = idmap_lib.lookup_or_insert(m, jnp.asarray(last), jnp.int32(1))
        assert int(met["idmap_rounds"]) == 32
        assert (int(met["idmap_probe_depth"]), int(met["idmap_claim_depth"])) == (0, 3)
        assert rounds(met) == (0, 3)
        assert int(m.max_depth) == 3
        assert (int(met["idmap_lookups"]), int(met["idmap_hits"])) == (3, 0)

        m, _, _, met = idmap_lib.lookup_or_insert(m, jnp.asarray(first), jnp.int32(2))
        assert (int(met["idmap_probe_depth"]), int(met["idmap_claim_depth"])) == (0, 3)
        assert rounds(met) == (3, 3)
        assert int(met["idmap_rounds"]) == 32

        both = jnp.asarray(np.concatenate([last, first, [-1, -1]]), jnp.int64)
        m, _, new, met = idmap_lib.lookup_or_insert(m, both, jnp.int32(3))
        assert not bool(new.any())
        assert (int(met["idmap_probe_depth"]), int(met["idmap_claim_depth"])) == (3, 0)
        assert rounds(met) == (3, 0)
        assert int(met["idmap_rounds"]) == 32
        assert (int(met["idmap_lookups"]), int(met["idmap_hits"])) == (4, 4)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_idempotent_reinsert(self, seed):
        """Property: re-inserting the same ids returns identical offsets and
        allocates nothing."""
        r = np.random.default_rng(seed)
        m = idmap_lib.create(128, 64)
        ids = jnp.asarray(np.unique(r.integers(0, 1 << 50, 20)), jnp.int64)
        m, off1, _, _ = idmap_lib.lookup_or_insert(m, ids, jnp.int32(1))
        m, off2, new2, met2 = idmap_lib.lookup_or_insert(m, ids, jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(off1), np.asarray(off2))
        assert int(met2["idmap_inserted"]) == 0
        assert not bool(new2.any())


# ---------------------------------------------------------------------------
# exchange — single-device path (multi-device in test_multidevice.py)
# ---------------------------------------------------------------------------

def _small_engine(n_dev):
    """One dim-4 group, 64 slots per shard: ~30 ids a shard collide."""
    return EmbeddingEngine(
        [FeatureSpec("f", transform="hash", emb_dim=4, pooling="sum")],
        EngineConfig(mesh_axes=(), n_devices=n_dev, rows_per_shard=64,
                     map_capacity_per_shard=64, u_budget=32, per_dest_cap=32,
                     recv_budget=32))


def _placed_depths(state):
    """Per shard, the largest probe distance of a live key, from the table
    itself (1-based; 0 for an empty shard)."""
    m = state["dim4"]["idmap"]
    keys, occ = np.asarray(m.keys), np.asarray(m.occupied)
    cap = keys.shape[1]
    out = []
    for d in range(keys.shape[0]):
        slots = np.flatnonzero(occ[d])
        home = np.asarray(idmap_lib._home(jnp.asarray(keys[d][slots]), cap))
        out.append(int(((slots - home) % cap + 1).max(initial=0)))
    return out


def _synthetic_rows(n_ids, seed=0):
    r = np.random.default_rng(seed)
    ids = r.choice(1 << 40, n_ids, replace=False).astype(np.int64)
    emb = r.normal(size=(n_ids, 4)).astype(np.float32)
    return {"dim4": {"ids": ids, "emb": emb,
                     "slots": {"m": emb * 0.5, "v": emb * emb},
                     "last_use": np.arange(n_ids, dtype=np.int32)}}


class TestMaxDepth:
    """``max_depth`` bounds the probe, so every path that saves or rebuilds
    a table has to carry it: a bound too low would hide stored keys."""

    @pytest.mark.parametrize("d_from,d_to", [(1, 1), (1, 2), (2, 1)])
    def test_export_import_rebuilds_max_depth(self, d_from, d_to):
        e1 = _small_engine(d_from)
        st = e1.import_rows(_synthetic_rows(30 * d_from))
        assert list(np.asarray(st["dim4"]["idmap"].max_depth)) == _placed_depths(st)
        assert max(_placed_depths(st)) > 1  # the chains did collide

        rows = e1.export_rows(st)
        st2 = _small_engine(d_to).import_rows(rows)
        depth = np.asarray(st2["dim4"]["idmap"].max_depth)
        assert depth.tolist() == _placed_depths(st2)
        back = _small_engine(d_to).export_rows(st2)
        np.testing.assert_array_equal(np.sort(back["dim4"]["ids"]),
                                      np.sort(rows["dim4"]["ids"]))

    def test_max_depth_survives_save_restore(self, tmp_path):
        from repro.checkpoint import saver

        eng = _small_engine(2)
        rows = _synthetic_rows(60)
        st = eng.import_rows(rows)
        depth = np.asarray(st["dim4"]["idmap"].max_depth)
        assert depth.max() > 1
        saver.save(st, tmp_path, step=1, n_shards=2)
        out = saver.restore(tmp_path, eng.init_state())
        np.testing.assert_array_equal(np.asarray(out["dim4"]["idmap"].max_depth), depth)
        # every key is still found by a probe bounded by the restored depth
        ids = rows["dim4"]["ids"]
        back = eng.export_rows(out)
        np.testing.assert_array_equal(np.sort(back["dim4"]["ids"]), np.sort(ids))
        for d in range(2):
            m = jax.tree.map(lambda x: jnp.asarray(x[d]), out["dim4"]["idmap"])
            keys = np.asarray(m.keys)[np.asarray(m.occupied)]
            assert bool((np.asarray(idmap_lib.lookup(m, jnp.asarray(keys)))
                         != idmap_lib.OVERFLOW_ROW).all())


def _spec(u=32, c=64, r=64):
    return exchange.ExchangeSpec(axes=(), n_devices=1, u_budget=u,
                                 per_dest_cap=c, recv_budget=r)


class TestExchange:
    @pytest.mark.parametrize("n,size,hi", [(20, 32, 50), (64, 16, 1000),
                                           (40, 40, 5)])
    def test_unique_matches_jnp_unique(self, rng, n, size, hi):
        """One-sort unique == jnp.unique(size, fill PAD, return_inverse),
        including PAD ids and more uniques than ``size``."""
        ids = rng.integers(-1, hi, n).astype(np.int64)
        uniq, inv = exchange.unique(jnp.asarray(ids), size)
        want, want_inv = jnp.unique(jnp.asarray(ids), size=size,
                                    fill_value=exchange.PAD,
                                    return_inverse=True)
        np.testing.assert_array_equal(np.asarray(uniq), np.asarray(want))
        kept = np.isin(ids, np.asarray(want)[:len(np.unique(ids))])
        np.testing.assert_array_equal(np.asarray(inv)[kept],
                                      np.asarray(want_inv).reshape(-1)[kept])
        # an id whose unique was dropped is detectable as uniq[inv] != id
        assert (np.asarray(uniq)[np.asarray(inv)] == ids).tolist() == kept.tolist()

    def test_fetch_route_roundtrip(self, rng):
        spec = _spec()
        m = idmap_lib.create(256, 128)
        b = blocks_lib.create(128, 8)
        ids = jnp.asarray(rng.integers(0, 50, 20).astype(np.int64))
        m, b, rows_r, plan, met = exchange.fetch(m, b, ids, spec, jnp.int32(1), True)
        vals = exchange.route_rows(rows_r, plan, spec)
        assert vals.shape == (20, 8)
        # same id → same routed row
        idn = np.asarray(ids)
        for i in range(20):
            for j in range(i + 1, 20):
                if idn[i] == idn[j]:
                    np.testing.assert_array_equal(np.asarray(vals[i]),
                                                  np.asarray(vals[j]))

    def test_grad_routing_sums_duplicates(self, rng):
        """The transpose of route_rows must SUM gradients of duplicate ids
        (the paper's backward all-to-all + merge)."""
        spec = _spec()
        m = idmap_lib.create(256, 128)
        b = blocks_lib.create(128, 4)
        ids = jnp.asarray([5, 5, 9], jnp.int64)
        m, b, rows_r, plan, _ = exchange.fetch(m, b, ids, spec, jnp.int32(1), True)

        g = jax.grad(lambda rr: exchange.route_rows(rr, plan, spec)[0:2].sum() * 2.0
                     + exchange.route_rows(rr, plan, spec)[2].sum())(rows_r)
        uniq = np.asarray(jnp.unique(ids, size=3, fill_value=-1))
        # row of id 5 gets 2 (from two dup values × 2.0 → 4.0 per dim? no:
        # each of the two value-slots contributes grad 2.0 per dim → 4.0)
        off = np.asarray(plan.offsets_r)
        valid = np.asarray(plan.valid_r)
        gsum = np.asarray(g).sum(axis=1)
        live = gsum[valid[: len(gsum)]] if valid.any() else gsum
        assert set(np.round(gsum[gsum != 0]).astype(int).tolist()) == {16, 4}
        # 16 = id5: two slots × 2.0 × dim4; 4 = id9: one slot × 1.0 × dim4

    @pytest.mark.parametrize("u, want", [(32, (5, 3, 3)), (3, (5, 2, 2))])
    def test_exchange_counts_ids_at_each_boundary(self, u, want):
        """Active ids in, after the requester dedupe (at most U, of which
        a PAD in the batch takes one), after the owner merge; PAD is never
        counted."""
        m = idmap_lib.create(256, 128)
        b = blocks_lib.create(128, 4)
        ids = jnp.asarray([5, 5, 7, -1, 9, 7], jnp.int64)
        *_, met = exchange.fetch(m, b, ids, _spec(u=u, c=u, r=u), jnp.int32(1), True)
        assert tuple(int(met[k]) for k in ("exch_ids", "exch_uniq", "exch_recv_uniq")) == want

    def test_overflow_counted_not_silent(self, rng):
        spec = _spec(u=8, c=8, r=8)
        m = idmap_lib.create(256, 128)
        b = blocks_lib.create(128, 4)
        ids = jnp.asarray(np.arange(100).astype(np.int64))  # 100 uniques > U=8
        m, b, rows_r, plan, met = exchange.fetch(m, b, ids, spec, jnp.int32(1), True)
        assert int(met["exch_uniq_overflow"]) > 0


class TestExchangeProperties:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 40))
    def test_same_id_same_row_property(self, seed, n):
        """Property: after fetch+route, equal ids ALWAYS receive equal rows
        and distinct ids receive distinct rows (conflict-free, end to end)."""
        r = np.random.default_rng(seed)
        spec = _spec()
        m = idmap_lib.create(256, 128)
        b = blocks_lib.create(128, 4)
        ids = jnp.asarray(r.integers(0, 12, n).astype(np.int64))
        m, b, rows_r, plan, _ = exchange.fetch(m, b, ids, spec, jnp.int32(1), True)
        vals = np.asarray(exchange.route_rows(rows_r, plan, spec))
        idn = np.asarray(ids)
        for i in range(n):
            for j in range(i + 1, n):
                if idn[i] == idn[j]:
                    np.testing.assert_array_equal(vals[i], vals[j])
                else:
                    assert not np.allclose(vals[i], vals[j])

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_grad_mass_conservation(self, seed):
        """Property: Σ over unique-row grads == Σ over per-value grads
        (the backward all-to-all + duplicate merge loses nothing)."""
        r = np.random.default_rng(seed)
        spec = _spec()
        m = idmap_lib.create(256, 128)
        b = blocks_lib.create(128, 4)
        n = 24
        ids = jnp.asarray(r.integers(0, 9, n).astype(np.int64))
        m, b, rows_r, plan, _ = exchange.fetch(m, b, ids, spec, jnp.int32(1), True)
        g_vals = jnp.asarray(r.normal(size=(n, 4)).astype(np.float32))
        _, vjp = jax.vjp(lambda rr: exchange.route_rows(rr, plan, spec), rows_r)
        (g_rows,) = vjp(g_vals)
        np.testing.assert_allclose(np.asarray(g_rows).sum(axis=0),
                                   np.asarray(g_vals).sum(axis=0),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Embedding Engine (merge-by-dim + pooling)
# ---------------------------------------------------------------------------

def _engine(specs):
    return EmbeddingEngine(specs, EngineConfig(
        mesh_axes=(), n_devices=1, rows_per_shard=512,
        map_capacity_per_shard=1024, u_budget=64, per_dest_cap=64,
        recv_budget=64))


class TestEmbeddingEngine:
    def test_merge_by_dim(self):
        specs = [
            FeatureSpec("a", transform="hash", emb_dim=8),
            FeatureSpec("b", transform="hash", emb_dim=8),
            FeatureSpec("c", transform="hash", emb_dim=16),
        ]
        eng = _engine(specs)
        assert set(eng.groups) == {"dim8", "dim16"}
        assert len(eng.groups["dim8"].features) == 2

    def test_shared_table_vs_salted(self):
        specs = [
            FeatureSpec("a", transform="hash", emb_dim=8),
            FeatureSpec("b", transform="hash", emb_dim=8),
            FeatureSpec("a2", transform="hash", emb_dim=8, shared_table="a"),
        ]
        eng = _engine(specs)
        r = Ragged.from_lists([[42]], nnz_budget=2)
        eids = eng.engine_ids({"a": r, "b": r, "a2": r})["dim8"]
        e = np.asarray(eids)
        assert e[0] == e[4]      # a and a2 share a salt → same engine id
        assert e[0] != e[2]      # b is salted differently

    def test_fetch_pool_update_cycle(self, rng):
        specs = [FeatureSpec("f", transform="hash", emb_dim=8, pooling="sum")]
        eng = _engine(specs)
        state = eng.init_state()
        st_local = jax.tree.map(lambda x: x[0], state)
        ids = {"f": Ragged.from_lists([[1, 2], [3]], nnz_budget=4)}
        st_local, rows_r, plans, met = eng.fetch_local(st_local, ids, jnp.int32(1))
        acts = eng.activations(rows_r, plans, ids)
        assert acts["f"].shape == (2, 8)
        # grad → update decreases a re-fetched row along the grad direction
        g = {k: jnp.ones_like(v) for k, v in rows_r.items()}
        st2 = eng.update_local(st_local, plans, g, SparseAdamConfig(lr=0.1),
                               jnp.int32(1))
        st2c, rows2, plans2, _ = eng.fetch_local(st2, ids, jnp.int32(2))
        valid = np.asarray(plans["dim8"].valid_r)
        delta = np.asarray(rows2["dim8"] - rows_r["dim8"])[valid]
        assert (delta < 0).all()  # Adam step with all-ones grad is negative

    def test_pooling_mean_none_tile(self, rng):
        specs = [
            FeatureSpec("s", transform="hash", emb_dim=8, pooling="mean"),
            FeatureSpec("q", transform="hash", emb_dim=8, pooling="none", max_len=3),
            FeatureSpec("t", transform="hash", emb_dim=8, pooling="tile", tile_k=2),
        ]
        eng = _engine(specs)
        st_local = jax.tree.map(lambda x: x[0], eng.init_state())
        ids = {n: Ragged.from_lists([[1, 2, 3], [4]], nnz_budget=6) for n in "sqt"}
        st_local, rows_r, plans, _ = eng.fetch_local(st_local, ids, jnp.int32(1))
        acts = eng.activations(rows_r, plans, ids)
        assert acts["s"].shape == (2, 8)
        assert acts["q"].shape == (2, 3, 8)
        assert acts["t"].shape == (2, 16)
        # mean pooling row 1 == its single row embedding
        vals = exchange.route_rows(rows_r["dim8"], plans["dim8"],
                                   eng.groups["dim8"].exchange)
        np.testing.assert_allclose(np.asarray(acts["s"][1]),
                                   np.asarray(vals[3]), rtol=1e-6)

    def test_pallas_equals_pure(self, rng):
        specs = [FeatureSpec("f", transform="hash", emb_dim=8, pooling="sum"),
                 FeatureSpec("t", transform="hash", emb_dim=8, pooling="tile",
                             tile_k=2)]
        eng = _engine(specs)
        st_local = jax.tree.map(lambda x: x[0], eng.init_state())
        ids = {n: Ragged.from_lists([[1, 2], [3, 4, 5]], nnz_budget=8) for n in "ft"}
        st_local, rows_r, plans, _ = eng.fetch_local(st_local, ids, jnp.int32(1))
        a1 = eng.activations(rows_r, plans, ids, use_pallas=False)
        a2 = eng.activations(rows_r, plans, ids, use_pallas=True)
        for k in a1:
            np.testing.assert_allclose(np.asarray(a1[k]), np.asarray(a2[k]),
                                       rtol=1e-5, atol=1e-5)

    def test_eviction(self):
        specs = [FeatureSpec("f", transform="hash", emb_dim=8)]
        eng = _engine(specs)
        st_local = jax.tree.map(lambda x: x[0], eng.init_state())
        ids = {"f": Ragged.from_lists([[1], [2]], nnz_budget=2)}
        st_local, *_ = eng.fetch_local(st_local, ids, jnp.int32(1))
        st_local, met = eng.evict_local(st_local, jnp.int32(5))
        assert int(met["dim8/evicted"]) == 2


# ---------------------------------------------------------------------------
# SparseAdam vs dense-Adam oracle
# ---------------------------------------------------------------------------

class TestSparseAdam:
    def test_matches_dense_adam_on_touched_rows(self, rng):
        cfg = SparseAdamConfig(lr=0.01)
        b = blocks_lib.create(16, 4)
        b = blocks_lib.Blocks(emb=jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32)),
                              slots=b.slots)
        offs = jnp.asarray([3, 7], jnp.int32)
        g = jnp.asarray(rng.normal(size=(2, 4)).astype(np.float32))
        valid = jnp.ones(2, bool)
        b2 = apply_row_updates(cfg, b, offs, g, valid, jnp.int32(1))
        # dense oracle (step 1, zero moments)
        m1 = 0.1 * np.asarray(g)
        v1 = 0.001 * np.asarray(g) ** 2
        upd = (m1 / (1 - 0.9)) / (np.sqrt(v1 / (1 - 0.999)) + 1e-8)
        want = np.asarray(b.emb)[np.asarray(offs)] - 0.01 * upd
        np.testing.assert_allclose(np.asarray(b2.emb)[np.asarray(offs)], want,
                                   rtol=1e-5, atol=1e-6)
        # untouched rows unchanged (lazy semantics)
        mask = np.ones(16, bool)
        mask[np.asarray(offs)] = False
        np.testing.assert_array_equal(np.asarray(b2.emb)[mask],
                                      np.asarray(b.emb)[mask])

    def test_invalid_rows_untouched(self, rng):
        cfg = SparseAdamConfig(lr=0.5)
        b = blocks_lib.create(8, 4)
        offs = jnp.asarray([2, 5], jnp.int32)
        g = jnp.ones((2, 4), jnp.float32)
        valid = jnp.asarray([True, False])
        b2 = apply_row_updates(cfg, b, offs, g, valid, jnp.int32(1))
        assert np.asarray(b2.emb)[5].sum() == 0.0
        assert np.asarray(b2.emb)[2].sum() != 0.0

    def test_weight_decay_adamw(self, rng):
        cfg = SparseAdamConfig(lr=0.1, weight_decay=0.1)
        emb = jnp.ones((4, 2), jnp.float32)
        b = blocks_lib.Blocks(emb=emb, slots={"m": jnp.zeros_like(emb),
                                              "v": jnp.zeros_like(emb)})
        b2 = apply_row_updates(cfg, b, jnp.asarray([1], jnp.int32),
                               jnp.zeros((1, 2), jnp.float32),
                               jnp.ones(1, bool), jnp.int32(1))
        # zero grad → pure decoupled decay: w ← w − lr·wd·w
        np.testing.assert_allclose(np.asarray(b2.emb)[1], 1.0 - 0.1 * 0.1 * 1.0,
                                   rtol=1e-6)
