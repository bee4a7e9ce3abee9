"""Cell assembly plumbing shared by the per-family builders.

A *cell* = (architecture × input shape × mesh) with a ready-to-lower step
function, abstract state, and fully-sharded input specs. ``dryrun.py``
lowers+compiles cells; ``train.py`` runs them with concrete data.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here. Otherwise, on an accelerator, the cache is
    ``.jax_cache/`` at the root of the checkout (a fixed path: the path is
    part of the cache key); CPU compiles are not cached. Returns the
    directory in use, or None.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass(frozen=True)
class CellOptions:
    """Perf-iteration knobs (§Perf hillclimbing levers)."""

    use_pallas: bool = False
    attn_impl: str = "chunked"    # naive | chunked | pallas (train/prefill attn)
    remat: bool = True
    remat_policy: str = "full"    # full | dots
    zero1: bool = True
    capacity_slack: float = 4.0       # exchange per-dest slack over U/D
    recv_slack: float = 2.0           # owner recv-unique budget over U
    train_insert: bool = True          # lookup_or_insert vs lookup in train
    donate_state: bool = True
    moe_capacity_factor: float | None = None
    sparse_opt_lr: float = 1e-3
    dense_opt_lr: float = 1e-3
    # hillclimb levers (documented in EXPERIMENTS.md §Perf) — all default to
    # the paper-faithful GSPMD baseline; dryrun --tag variants flip them.
    sp_residual: bool = False          # manual SP layer (ag/rs boundaries)
    fused_ce: bool = False             # chunked/fused softmax-CE
    compress_grads: bool = False       # int8+EF DP grad compression (recsys)
    # tiered embedding storage (repro.storage.StorageConfig); non-None turns
    # the device tier into an HBM cache over a host-DRAM backing store and
    # makes the cell expose step-edge hooks for the Trainer (DESIGN.md §3)
    storage: Any | None = None
    # device-tier rows per shard override when storage is on (the HBM cache
    # size); None keeps the arch-derived all-HBM sizing
    storage_device_rows: int | None = None
    # rows of every embedding table one chip holds (the chip's share of a
    # deployment); None keeps the arch's published table sizes
    chip_table_rows: int | None = None


@dataclasses.dataclass
class Cell:
    arch: ArchConfig
    shape: ShapeCell
    mesh: Any
    step_fn: Callable                  # (state, batch) -> (state, out)
    abstract_state: Any                # pytree of ShapeDtypeStruct (sharded)
    batch_specs: Any                   # pytree of ShapeDtypeStruct (sharded)
    state_shardings: Any
    init_state: Callable[[], Any] | None = None   # concrete init (small meshes)
    make_batch: Callable[[int], Any] | None = None  # concrete batch (seed)
    donate_state: bool = True
    returns_state: bool = True  # False: pure serve step, outputs only

    def lower(self):
        kwargs = {"donate_argnums": (0,)} if (self.donate_state and self.returns_state) else {}
        jitted = jax.jit(self.step_fn, **kwargs)
        return jitted.lower(self.abstract_state, self.batch_specs)

    def shardings(self):
        """(state, batch) pytrees of the NamedShardings the step runs under."""
        def of(tree):
            return jax.tree.map(lambda s: s.sharding, tree)

        return of(self.abstract_state), of(self.batch_specs)

    def init(self):
        """Concrete state built on the devices straight into its shardings,
        so a table is never materialised whole on one device first."""
        return jax.jit(self.init_state, out_shardings=self.shardings()[0])()


def named(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def sds(shape, dtype, mesh=None, spec: P | None = None):
    sh = named(mesh, spec) if (mesh is not None and spec is not None) else None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def sanitize_spec(spec: P, mesh) -> P:
    """Drop axis names the mesh doesn't have (reduced smoke meshes have no
    "model" axis; the full production specs degrade to replicated there)."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*(keep(e) for e in spec))


def abstractify(tree, pspec_tree, mesh):
    """Concrete-or-abstract pytree → ShapeDtypeStructs with NamedShardings."""

    def one(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=named(mesh, sanitize_spec(spec, mesh)))

    return jax.tree.map(one, tree, pspec_tree,
                        is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))


def tree_pspec_like(tree, spec: P):
    return jax.tree.map(lambda _: spec, tree)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def mesh_info(mesh):
    axes = tuple(mesh.axis_names)
    dp = tuple(a for a in axes if a != "model")
    return {
        "axes": axes,
        "dp": dp,
        "D": int(np.prod([mesh.shape[a] for a in axes])),
        "tp": int(mesh.shape.get("model", 1)),
        "dp_size": int(np.prod([mesh.shape[a] for a in dp])) if dp else 1,
    }
