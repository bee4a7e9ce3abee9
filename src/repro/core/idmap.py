"""IDMap — tier-1 of the RecIS Embedding Engine (§2.2.2 "Moving to GPU").

A conflict-free, dynamically-growing feature-ID → row-offset map, stored as
plain JAX arrays in device HBM so every probe runs at HBM bandwidth (the
paper's point: the accelerator's bandwidth is 2 orders of magnitude above
the host's). Open addressing with linear probing; *full 64-bit keys* are
stored, so two distinct feature IDs can never share an embedding row —
unlike static `id % vocab` tables. Collisions only exhaust after
``max_probes`` slots, which at load factor ≤ 0.5 is vanishingly rare; such
ids fall back to the reserved overflow row 0 and are **counted**, never
dropped silently.

Deletes (evict / remove) leave no tombstones. A probe instead scans every
round up to ``max_depth``, the deepest probe distance at which any key was
ever placed in the table, with no early-out on an empty slot: no stored key
lies further from its home, so a slot cleared mid-chain cannot hide one.
The claim loop runs while an inserting id is still unplaced. Both loops stop
at ``max_probes`` at the latest.

All operations are jit-compatible, vectorized, and run fully on-device:
  lookup            pure probe (serving path)
  lookup_or_insert  probe + parallel claim of empty slots (training path)
  evict             free rows whose last access is older than a threshold
                    (continuous / online-window training, §2.1)

Insertion uses a scatter-min "claim" per probe round: every inserting id
writes its batch rank into the slot; the minimum rank wins the slot, losers
continue probing. This is the TPU-native replacement for the CUDA CAS loop
a GPU hash table would use (no atomics on TPU — DESIGN.md §2).

Input ids of a single call MUST be unique (except PAD -1 padding); the
Embedding Engine's ids-partition (dedupe) stage guarantees this.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import write_log
from repro.core.feature_engine import splitmix64
from repro.obs.stages import stage

PAD = jnp.int64(-1)
OVERFLOW_ROW = 0  # blocks row 0 is the reserved collision/overflow bucket


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IDMap:
    keys: jax.Array        # (capacity,) int64
    occupied: jax.Array    # (capacity,) bool
    offsets: jax.Array     # (capacity,) int32 — row in Blocks
    last_use: jax.Array    # (capacity,) int32 — step of last access
    free_stack: jax.Array  # (capacity,) int32 — recycled row offsets
    free_size: jax.Array   # () int32
    next_row: jax.Array    # () int32 — bump allocator (row 0 reserved)
    max_depth: jax.Array   # () int32 — deepest 1-based probe distance ever placed
    n_rows: int            # static: Blocks row capacity
    max_probes: int        # static

    def tree_flatten(self):
        children = (
            self.keys, self.occupied, self.offsets, self.last_use,
            self.free_stack, self.free_size, self.next_row, self.max_depth,
        )
        return children, (self.n_rows, self.max_probes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    def n_live(self) -> jax.Array:
        return self.occupied.sum(dtype=jnp.int32)


def create(capacity: int, n_rows: int, max_probes: int = 32) -> IDMap:
    return IDMap(
        keys=jnp.zeros((capacity,), jnp.int64),
        occupied=jnp.zeros((capacity,), jnp.bool_),
        offsets=jnp.zeros((capacity,), jnp.int32),
        last_use=jnp.zeros((capacity,), jnp.int32),
        free_stack=jnp.zeros((capacity,), jnp.int32),
        free_size=jnp.zeros((), jnp.int32),
        next_row=jnp.ones((), jnp.int32),  # row 0 reserved for overflow
        max_depth=jnp.zeros((), jnp.int32),
        n_rows=n_rows,
        max_probes=max_probes,
    )


def _home(ids: jax.Array, capacity: int) -> jax.Array:
    return (splitmix64(ids) % jnp.uint64(capacity)).astype(jnp.int32)


def _probe_rounds(m: IDMap) -> jax.Array:
    """Rounds a probe of ``m`` runs: no key lies beyond ``max_depth``."""
    return jnp.minimum(m.max_depth, m.max_probes)


def _probe_find(keys: jax.Array, occupied: jax.Array, ids: jax.Array,
                home: jax.Array, rounds: jax.Array) -> jax.Array:
    """Slot of each id along its probe chain, -1 when absent.

    Probes all ``rounds`` (``_probe_rounds``: every distance at which a key
    was ever placed) with no early-out on empty slots, so deletions (evict /
    remove) need no tombstones: a cleared slot mid-chain cannot hide a key
    stored further along, and no key is stored beyond ``max_depth``.
    """
    cap = keys.shape[0]
    active = ids != PAD
    found = jnp.full(ids.shape, -1, jnp.int32)

    def body(r, found):
        slot = (home + r) % cap
        need = active & (found < 0)
        hit = need & occupied[slot] & (keys[slot] == ids)
        return jnp.where(hit, slot, found)

    return jax.lax.fori_loop(0, rounds, body, found)


def lookup(m: IDMap, ids: jax.Array) -> jax.Array:
    """Probe-only. Returns row offsets; missing/pad ids → OVERFLOW_ROW."""
    with stage("recis.idmap.probe"):
        found = _probe_find(m.keys, m.occupied, ids, _home(ids, m.capacity),
                            _probe_rounds(m))
        return jnp.where(found >= 0, m.offsets[jnp.maximum(found, 0)], OVERFLOW_ROW)


def _depth(found: jax.Array, home: jax.Array, mask: jax.Array, cap: int) -> jax.Array:
    """The last probe round (1-based) in which a ``mask`` id was found or
    placed: the rounds that did useful work. 0 when no id is in ``mask``."""
    return jnp.where(mask, (found - home) % cap + 1, 0).max(initial=0)


def lookup_or_insert(
    m: IDMap, ids: jax.Array, step: jax.Array
) -> tuple[IDMap, jax.Array, jax.Array, dict]:
    """Training-path probe. Returns (new_map, offsets, is_new, metrics).

    ids: (n,) int64, unique up to PAD(-1) padding.
    offsets: (n,) int32 row in Blocks (OVERFLOW_ROW on probe exhaustion /
    row-capacity exhaustion / pad).

    Thin un-jitted wrapper around the jitted probe so eager callers (the
    tiered store's step-edge promote path) feed the write-observation seam;
    traced callers pass straight through (`write_log` skips tracers).
    """
    new_m, offsets, is_new, metrics = _lookup_or_insert_jit(m, ids, step)
    write_log.note_insert(ids, is_new)
    return new_m, offsets, is_new, metrics


@partial(jax.jit, static_argnames=())
def _lookup_or_insert_jit(
    m: IDMap, ids: jax.Array, step: jax.Array
) -> tuple[IDMap, jax.Array, jax.Array, dict]:
    cap = m.capacity
    n = ids.shape[0]

    # Pass 1 — find existing keys along the whole probe chain (to
    # ``max_depth``). This must complete before any empty slot is claimed:
    # after evict/remove cleared a mid-chain slot, claiming it eagerly would
    # duplicate a key that still lives further along (and re-init its row).
    with stage("recis.idmap.probe"):
        home = _home(ids, cap)
        active = ids != PAD
        probe_rounds = _probe_rounds(m)
        found = _probe_find(m.keys, m.occupied, ids, home, probe_rounds)
        hit = found >= 0
        probe_depth = _depth(found, home, hit, cap)

    # Pass 2 — only genuinely-missing ids claim empty slots, via scatter-min
    # of batch rank per round (parallel-safe; no atomics on TPU). It stops
    # once every inserting id holds a slot: later rounds could place none.
    with stage("recis.idmap.claim"):
        inserting = active & (found < 0)
        rank = jnp.arange(n, dtype=jnp.int32)

        def cond(carry):
            r, _, _, found = carry
            return (r < m.max_probes) & jnp.any(inserting & (found < 0))

        def body(carry):
            r, keys, occ, found = carry
            slot = (home + r) % cap
            want = inserting & (found < 0) & ~occ[slot]
            claims = jnp.full((cap,), n, jnp.int32).at[slot].min(
                jnp.where(want, rank, n), mode="drop"
            )
            won = want & (claims[slot] == rank)
            wslot = jnp.where(won, slot, cap)  # cap = out-of-range → dropped
            keys = keys.at[wslot].set(ids, mode="drop")
            occ = occ.at[wslot].set(True, mode="drop")
            found = jnp.where(won, slot, found)
            return r + 1, keys, occ, found

        claim_rounds, keys, occ, found = jax.lax.while_loop(
            cond, body, (jnp.int32(0), m.keys, m.occupied, found)
        )
        is_new = inserting & (found >= 0)
        claim_depth = _depth(found, home, is_new, cap)

    # ---- allocate rows for the winners: recycled offsets first, then bump
    with stage("recis.idmap.alloc"):
        new_rank = jnp.cumsum(is_new.astype(jnp.int32)) - 1
        n_inserted = is_new.sum(dtype=jnp.int32)
        from_stack = new_rank < m.free_size
        stack_idx = jnp.clip(m.free_size - 1 - new_rank, 0, cap - 1)
        bumped = m.next_row + (new_rank - m.free_size)
        row = jnp.where(from_stack, m.free_stack[stack_idx], bumped)
        row_ok = row < m.n_rows
        row = jnp.where(is_new & row_ok, row, OVERFLOW_ROW).astype(jnp.int32)

        taken_from_stack = jnp.minimum(n_inserted, m.free_size)
        free_size = m.free_size - taken_from_stack
        next_row = jnp.minimum(
            m.next_row + jnp.maximum(n_inserted - taken_from_stack, 0), m.n_rows
        )

        offsets = m.offsets.at[jnp.where(is_new, found, cap)].set(row, mode="drop")
        touched_slot = jnp.where(found >= 0, found, cap)
        last_use = m.last_use.at[touched_slot].set(step.astype(jnp.int32), mode="drop")

        out_off = jnp.where(found >= 0, offsets[jnp.maximum(found, 0)], OVERFLOW_ROW)
        metrics = {
            "idmap_inserted": n_inserted,
            "idmap_probe_overflow": (active & (found < 0)).sum(dtype=jnp.int32),
            "idmap_row_overflow": (is_new & ~row_ok).sum(dtype=jnp.int32),
            # work of the two probe passes: ``idmap_rounds`` is the budget,
            # the ``*_rounds`` are the rounds each pass ran, the depths the
            # last round that found or placed an id (per chip: reduced with
            # a max, the rest summed)
            "idmap_rounds": jnp.int32(m.max_probes),
            "idmap_probe_rounds": probe_rounds,
            "idmap_claim_rounds": claim_rounds,
            "idmap_probe_depth": probe_depth,
            "idmap_claim_depth": claim_depth,
            "idmap_lookups": active.sum(dtype=jnp.int32),
            "idmap_hits": hit.sum(dtype=jnp.int32),
        }
    new_m = IDMap(
        keys=keys, occupied=occ, offsets=offsets, last_use=last_use,
        free_stack=m.free_stack, free_size=free_size, next_row=next_row,
        max_depth=jnp.maximum(m.max_depth, claim_depth),
        n_rows=m.n_rows, max_probes=m.max_probes,
    )
    return new_m, out_off, is_new & row_ok, metrics


def remove(m: IDMap, ids: jax.Array) -> tuple[IDMap, jax.Array, jax.Array]:
    """Remove specific ids; their rows are recycled via the free stack.

    The demotion primitive of the tiered store (DESIGN.md §4): the caller
    gathers the rows at the returned offsets BEFORE dropping its reference
    to the old Blocks, then spills them to the host tier. Probe-chain safety
    relies on ``_probe_find`` scanning every round to ``max_depth``, which a
    cleared slot leaves as it is, so no tombstone is needed. Returns
    (new_map, offsets, found_mask); offsets of missing/pad ids are
    OVERFLOW_ROW.

    ids MUST be unique up to PAD padding (same contract as insert).
    """
    cap = m.capacity
    found = _probe_find(m.keys, m.occupied, ids, _home(ids, cap),
                        _probe_rounds(m))
    found_mask = found >= 0
    offs = m.offsets[jnp.maximum(found, 0)]
    occupied = m.occupied.at[jnp.where(found_mask, found, cap)].set(
        False, mode="drop"
    )
    # Push freed row offsets onto the free stack for reuse. Ids whose row
    # allocation failed at insert time sit on OVERFLOW_ROW — their slot is
    # cleared but row 0 (reserved) must never enter the free stack.
    freeable = found_mask & (offs != OVERFLOW_ROW)
    pos = jnp.cumsum(freeable.astype(jnp.int32)) - 1
    n_freed = freeable.sum(dtype=jnp.int32)
    dst = jnp.where(freeable, m.free_size + pos, cap)
    free_stack = m.free_stack.at[dst].set(offs, mode="drop")
    new_m = IDMap(
        keys=m.keys,
        occupied=occupied,
        offsets=m.offsets,
        last_use=m.last_use,
        free_stack=free_stack,
        free_size=jnp.minimum(m.free_size + n_freed, cap),
        next_row=m.next_row,
        max_depth=m.max_depth,
        n_rows=m.n_rows,
        max_probes=m.max_probes,
    )
    write_log.note_remove(ids, found_mask)
    return new_m, jnp.where(freeable, offs, OVERFLOW_ROW), freeable


def evict(m: IDMap, older_than: jax.Array) -> tuple[IDMap, jax.Array]:
    """Free every row whose last access predates ``older_than``.

    The slot is cleared and the row offset is pushed onto the free stack for
    reuse — the paper's stale-feature eviction for continuous training.
    Returns (new_map, n_evicted).
    """
    cap = m.capacity
    stale = m.occupied & (m.last_use < older_than.astype(jnp.int32))
    if write_log.get_observer() is not None \
            and not isinstance(stale, jax.core.Tracer):
        # discarding evict: no surviving copy → tombstone for recovery
        write_log.note_evict(np.asarray(m.keys)[np.asarray(stale)])
    pos = jnp.cumsum(stale.astype(jnp.int32)) - 1
    n_evicted = stale.sum(dtype=jnp.int32)
    dst = jnp.where(stale, m.free_size + pos, cap)
    free_stack = m.free_stack.at[dst].set(m.offsets, mode="drop")
    new_m = IDMap(
        keys=m.keys,
        occupied=m.occupied & ~stale,
        offsets=m.offsets,
        last_use=m.last_use,
        free_stack=free_stack,
        free_size=jnp.minimum(m.free_size + n_evicted, cap),
        next_row=m.next_row,
        max_depth=m.max_depth,
        n_rows=m.n_rows,
        max_probes=m.max_probes,
    )
    return new_m, n_evicted
