"""Property-based invariants for the sparse core (idmap + blocks).

Runs under hypothesis when the package is installed (``hypothesis_compat``
turns the ``@given`` tests into skips otherwise); the same property
checkers are ALSO driven by seeded numpy examples so the invariants are
exercised on every environment, not just where hypothesis exists.

Properties:

  * ``idmap.remove`` → ``lookup_or_insert`` round-trip — removed ids
    re-insert as new, recycling exactly the freed rows (LIFO from the
    free stack, so ``next_row`` never grows back); survivors keep their
    original offsets; row 0 (OVERFLOW_ROW) never enters the free stack.
  * ``blocks.write_rows`` → ``gather_with_slots`` slot-consistency —
    masked rows round-trip embedding AND every optimizer slot together;
    unmasked rows are untouched; ``clear_rows`` zeroes exactly the
    masked rows.
  * the IDMap's bounded loops (probe to ``max_depth``, claim while an id
    is unplaced) give the same table and outputs as fixed ``max_probes``
    loops, through seeded sequences of inserts, removes, evicts and
    lookups on a half-full table with chains that wrap past the last slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import blocks as blocks_lib, idmap as idmap_lib
from repro.core.idmap import OVERFLOW_ROW, PAD


# ---------------------------------------------------------------------------
# property checkers (pure asserts — shared by hypothesis and seeded paths)
# ---------------------------------------------------------------------------

def check_remove_reinsert_roundtrip(ids: np.ndarray, n_remove: int):
    """ids: unique non-negative int64; remove the first n_remove, re-insert."""
    n = len(ids)
    cap, n_rows = 4 * n, 2 * n + 1  # roomy: no probe/row exhaustion noise
    m = idmap_lib.create(cap, n_rows)
    jids = jnp.asarray(ids, jnp.int64)
    m, off0, is_new0, _ = idmap_lib.lookup_or_insert(m, jids, jnp.int32(0))
    off0 = np.asarray(off0)
    assert bool(np.all(np.asarray(is_new0)))
    assert bool(np.all(off0 != OVERFLOW_ROW))      # row 0 stays reserved
    assert len(np.unique(off0)) == n               # conflict-free rows
    next_row0 = int(m.next_row)

    rm = jnp.asarray(ids[:n_remove], jnp.int64)
    m, rm_off, freeable = idmap_lib.remove(m, rm)
    rm_off, freeable = np.asarray(rm_off), np.asarray(freeable)
    assert bool(np.all(freeable))                  # all were present
    np.testing.assert_array_equal(rm_off, off0[:n_remove])
    assert int(m.free_size) == n_remove
    # the free stack holds exactly the freed rows, in push order
    np.testing.assert_array_equal(
        np.asarray(m.free_stack)[:n_remove], rm_off)

    # removed ids are gone; survivors still resolve to their original rows
    assert bool(np.all(np.asarray(idmap_lib.lookup(m, rm)) == OVERFLOW_ROW))
    if n_remove < n:
        keep = jnp.asarray(ids[n_remove:], jnp.int64)
        np.testing.assert_array_equal(
            np.asarray(idmap_lib.lookup(m, keep)), off0[n_remove:])

    m, off1, is_new1, _ = idmap_lib.lookup_or_insert(m, rm, jnp.int32(1))
    off1 = np.asarray(off1)
    assert bool(np.all(np.asarray(is_new1)))       # re-insert is a fresh row
    # rows are RECYCLED: the same set of offsets comes back (LIFO — the
    # i-th re-insert pops stack top), and the bump allocator never moved
    assert set(off1.tolist()) == set(rm_off.tolist())
    np.testing.assert_array_equal(off1, rm_off[::-1])
    assert int(m.next_row) == next_row0            # no leaked rows
    assert int(m.free_size) == 0
    # full map still conflict-free after the churn
    all_off = np.asarray(idmap_lib.lookup(m, jids))
    assert len(np.unique(all_off)) == n
    assert bool(np.all(all_off != OVERFLOW_ROW))


def check_write_gather_slot_consistency(seed: int, n_rows: int, dim: int,
                                        n_write: int):
    r = np.random.default_rng(seed)
    b = blocks_lib.create(n_rows, dim, slot_names=("m", "v"))
    # unique target rows ≥ 1 (row 0 is the reserved overflow bucket)
    offs = jnp.asarray(
        r.choice(np.arange(1, n_rows), size=n_write, replace=False).astype(
            np.int32))
    emb = jnp.asarray(r.normal(size=(n_write, dim)).astype(np.float32))
    slots = {k: jnp.asarray(r.normal(size=(n_write, dim)).astype(np.float32))
             for k in ("m", "v")}
    mask = jnp.asarray(r.integers(0, 2, size=n_write).astype(bool))
    before_emb, before_slots = blocks_lib.gather_with_slots(b, offs)

    b = blocks_lib.write_rows(b, offs, emb, slots, mask)
    got_emb, got_slots = blocks_lib.gather_with_slots(b, offs)
    mk = np.asarray(mask)[:, None]
    # masked rows carry the payload — embedding and BOTH slots together
    np.testing.assert_array_equal(
        np.asarray(got_emb), np.where(mk, np.asarray(emb),
                                      np.asarray(before_emb)))
    for k in ("m", "v"):
        np.testing.assert_array_equal(
            np.asarray(got_slots[k]), np.where(mk, np.asarray(slots[k]),
                                               np.asarray(before_slots[k])))

    # clear_rows zeroes exactly the masked rows (emb + slots move together)
    b = blocks_lib.clear_rows(b, offs, mask)
    got_emb, got_slots = blocks_lib.gather_with_slots(b, offs)
    np.testing.assert_array_equal(
        np.asarray(got_emb), np.where(mk, 0.0, np.asarray(before_emb)))
    for k in ("m", "v"):
        np.testing.assert_array_equal(
            np.asarray(got_slots[k]), np.where(mk, 0.0,
                                               np.asarray(before_slots[k])))


# ---------------------------------------------------------------------------
# bounded probe / claim loops against fixed ``max_probes`` loops
# ---------------------------------------------------------------------------

def _fixed_probe_find(keys, occupied, ids, home, max_probes):
    """The probe as it was: all ``max_probes`` rounds, every call."""
    cap = keys.shape[0]
    active = ids != PAD
    found = jnp.full(ids.shape, -1, jnp.int32)

    def body(r, found):
        slot = (home + r) % cap
        need = active & (found < 0)
        hit = need & occupied[slot] & (keys[slot] == ids)
        return jnp.where(hit, slot, found)

    return jax.lax.fori_loop(0, max_probes, body, found)


@jax.jit
def _fixed_lookup_or_insert(m, ids, step):
    """``lookup_or_insert`` as it was: both passes run all ``max_probes``
    rounds. Returns (map, offsets, is_new)."""
    cap, n = m.capacity, ids.shape[0]
    home = idmap_lib._home(ids, cap)
    active = ids != PAD
    found = _fixed_probe_find(m.keys, m.occupied, ids, home, m.max_probes)
    inserting = active & (found < 0)
    rank = jnp.arange(n, dtype=jnp.int32)

    def body(r, carry):
        keys, occ, found = carry
        slot = (home + r) % cap
        want = inserting & (found < 0) & ~occ[slot]
        claims = jnp.full((cap,), n, jnp.int32).at[slot].min(
            jnp.where(want, rank, n), mode="drop")
        won = want & (claims[slot] == rank)
        wslot = jnp.where(won, slot, cap)
        keys = keys.at[wslot].set(ids, mode="drop")
        occ = occ.at[wslot].set(True, mode="drop")
        return keys, occ, jnp.where(won, slot, found)

    keys, occ, found = jax.lax.fori_loop(
        0, m.max_probes, body, (m.keys, m.occupied, found))
    is_new = inserting & (found >= 0)
    new_rank = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    n_inserted = is_new.sum(dtype=jnp.int32)
    from_stack = new_rank < m.free_size
    stack_idx = jnp.clip(m.free_size - 1 - new_rank, 0, cap - 1)
    row = jnp.where(from_stack, m.free_stack[stack_idx],
                    m.next_row + (new_rank - m.free_size))
    row_ok = row < m.n_rows
    row = jnp.where(is_new & row_ok, row, OVERFLOW_ROW).astype(jnp.int32)
    taken = jnp.minimum(n_inserted, m.free_size)
    offsets = m.offsets.at[jnp.where(is_new, found, cap)].set(row, mode="drop")
    last_use = m.last_use.at[jnp.where(found >= 0, found, cap)].set(
        step.astype(jnp.int32), mode="drop")
    out = jnp.where(found >= 0, offsets[jnp.maximum(found, 0)], OVERFLOW_ROW)
    new_m = idmap_lib.IDMap(
        keys=keys, occupied=occ, offsets=offsets, last_use=last_use,
        free_stack=m.free_stack, free_size=m.free_size - taken,
        next_row=jnp.minimum(m.next_row + jnp.maximum(n_inserted - taken, 0),
                             m.n_rows),
        max_depth=m.max_depth, n_rows=m.n_rows, max_probes=m.max_probes)
    return new_m, out, is_new & row_ok


def _fixed_find(m, ids):
    return _fixed_probe_find(m.keys, m.occupied, ids,
                             idmap_lib._home(ids, m.capacity), m.max_probes)


def _fixed_lookup(m, ids):
    found = _fixed_find(m, ids)
    return jnp.where(found >= 0, m.offsets[jnp.maximum(found, 0)], OVERFLOW_ROW)


def _fixed_remove(m, ids):
    """``remove`` as it was, over the fixed probe."""
    cap = m.capacity
    found = _fixed_find(m, ids)
    offs = m.offsets[jnp.maximum(found, 0)]
    freeable = (found >= 0) & (offs != OVERFLOW_ROW)
    dst = jnp.where(freeable, m.free_size + jnp.cumsum(freeable.astype(jnp.int32)) - 1, cap)
    new_m = idmap_lib.IDMap(
        keys=m.keys,
        occupied=m.occupied.at[jnp.where(found >= 0, found, cap)].set(False, mode="drop"),
        offsets=m.offsets, last_use=m.last_use,
        free_stack=m.free_stack.at[dst].set(offs, mode="drop"),
        free_size=jnp.minimum(m.free_size + freeable.sum(dtype=jnp.int32), cap),
        next_row=m.next_row, max_depth=m.max_depth, n_rows=m.n_rows,
        max_probes=m.max_probes)
    return new_m, jnp.where(freeable, offs, OVERFLOW_ROW), freeable


def _same_table(a, b):
    for k in ("keys", "occupied", "offsets", "last_use", "free_stack",
              "free_size", "next_row"):
        np.testing.assert_array_equal(np.asarray(getattr(a, k)),
                                      np.asarray(getattr(b, k)), err_msg=k)


def drive_bounded_against_fixed(seed: int, n_ops: int = 14):
    """One seeded sequence on a 64-slot table kept near half full. Four ids
    home on the last slot and two on the one before, so their chains wrap
    to slots 0, 1, ...; a mid-chain remove is followed by lookups of the
    ids further along. After every op the bounded and the fixed table and
    outputs are identical, and the new counters agree with the depths.
    Returns the highest load the table reached."""
    r = np.random.default_rng(seed)
    cap, batch = 64, 32
    cand = np.arange(1, 1 << 14, dtype=np.int64)
    home = np.asarray(idmap_lib._home(jnp.asarray(cand), cap))
    planted = np.concatenate([cand[home == cap - 1][:4], cand[home == cap - 2][:2]])
    pool = np.concatenate([planted, r.choice(cand[home < cap - 2], 46, replace=False)])

    def pad(ids):
        return jnp.asarray(np.concatenate(
            [ids, np.full(batch - len(ids), -1, np.int64)]), jnp.int64)

    bounded = fixed = idmap_lib.create(cap, cap)

    def insert(ids, step):
        nonlocal bounded, fixed
        depth = int(bounded.max_depth)
        bounded, off_b, new_b, met = idmap_lib.lookup_or_insert(bounded, ids, step)
        fixed, off_f, new_f = _fixed_lookup_or_insert(fixed, ids, step)
        np.testing.assert_array_equal(np.asarray(off_b), np.asarray(off_f))
        np.testing.assert_array_equal(np.asarray(new_b), np.asarray(new_f))
        assert int(met["idmap_probe_rounds"]) == depth
        if int(met["idmap_probe_overflow"]) == 0:
            assert int(met["idmap_claim_rounds"]) == int(met["idmap_claim_depth"])
        assert int(bounded.max_depth) == max(depth, int(met["idmap_claim_depth"]))

    def lookup(ids):
        np.testing.assert_array_equal(np.asarray(idmap_lib.lookup(bounded, ids)),
                                      np.asarray(_fixed_lookup(fixed, ids)))

    def remove(ids):
        nonlocal bounded, fixed
        bounded, off_b, freed_b = idmap_lib.remove(bounded, ids)
        fixed, off_f, freed_f = _fixed_remove(fixed, ids)
        np.testing.assert_array_equal(np.asarray(off_b), np.asarray(off_f))
        np.testing.assert_array_equal(np.asarray(freed_b), np.asarray(freed_f))

    # the planted chains, then a mid-chain remove and the ids beyond it
    insert(pad(pool[:32]), jnp.int32(0))
    _same_table(bounded, fixed)
    assert int(bounded.max_depth) >= 4
    peak = int(bounded.n_live()) / cap
    remove(pad(planted[1:2]))
    lookup(pad(planted))
    insert(pad(planted), jnp.int32(1))
    _same_table(bounded, fixed)
    for step in range(2, n_ops):
        op = r.choice(["insert", "insert", "remove", "evict", "lookup"])
        ids = r.choice(pool, int(r.integers(1, batch + 1)), replace=False)
        if op == "insert":
            insert(pad(ids), jnp.int32(step))
        elif op == "remove":
            remove(pad(ids[: batch // 4]))
        elif op == "evict":
            older = jnp.int32(step - int(r.integers(1, 4)))
            bounded, n_b = idmap_lib.evict(bounded, older)
            fixed, n_f = idmap_lib.evict(fixed, older)
            assert int(n_b) == int(n_f)
        lookup(pad(ids))
        _same_table(bounded, fixed)
        peak = max(peak, int(bounded.n_live()) / cap)
    return peak


class TestBoundedLoops:
    @pytest.mark.parametrize("seed", range(6))
    def test_bounded_loops_match_fixed_rounds(self, seed):
        peak_load = drive_bounded_against_fixed(seed)
        assert peak_load >= 0.5


# ---------------------------------------------------------------------------
# seeded example drive (runs everywhere, hypothesis or not)
# ---------------------------------------------------------------------------

class TestSeededExamples:
    @pytest.mark.parametrize("seed", range(5))
    def test_idmap_remove_reinsert(self, seed):
        r = np.random.default_rng(seed)
        n = int(r.integers(2, 48))
        ids = r.choice(1 << 40, size=n, replace=False).astype(np.int64)
        check_remove_reinsert_roundtrip(ids, int(r.integers(1, n + 1)))

    def test_idmap_remove_all_then_reinsert_all(self):
        ids = np.arange(1, 33, dtype=np.int64) * 7919
        check_remove_reinsert_roundtrip(ids, 32)

    @pytest.mark.parametrize("seed", range(5))
    def test_blocks_slot_consistency(self, seed):
        r = np.random.default_rng(100 + seed)
        n_rows = int(r.integers(8, 64))
        check_write_gather_slot_consistency(
            seed, n_rows, dim=int(r.integers(1, 9)),
            n_write=int(r.integers(1, n_rows)))


# ---------------------------------------------------------------------------
# hypothesis drive (skipped cleanly when the package is absent)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _ids_strategy = st.lists(
        st.integers(min_value=0, max_value=(1 << 62) - 1),
        min_size=2, max_size=64, unique=True)
else:  # the stub's strategies are inert; @given skips the test anyway
    _ids_strategy = None


class TestHypothesis:
    @given(ids=_ids_strategy, frac=st.floats(min_value=0.01, max_value=1.0))
    @settings(max_examples=50, deadline=None)
    def test_idmap_remove_reinsert(self, ids, frac):
        arr = np.asarray(ids, dtype=np.int64)
        n_remove = max(1, int(round(frac * len(arr))))
        check_remove_reinsert_roundtrip(arr, min(n_remove, len(arr)))

    @given(seed=st.integers(min_value=0, max_value=1 << 30),
           n_rows=st.integers(min_value=4, max_value=96),
           dim=st.integers(min_value=1, max_value=16))
    @settings(max_examples=50, deadline=None)
    def test_blocks_slot_consistency(self, seed, n_rows, dim):
        r = np.random.default_rng(seed)
        check_write_gather_slot_consistency(
            seed, n_rows, dim, n_write=int(r.integers(1, n_rows)))
