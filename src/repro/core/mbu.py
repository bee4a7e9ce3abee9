"""MBU — Model Bandwidth Utilization, the paper's contribution #2 (§1.4.2).

The sparse path's operators (unique, embedding lookup, reduce, transform)
have arithmetic intensity < 1 FLOP/byte: a FLOP roofline (MFU) says nothing
about them. RecIS proposes a *bandwidth-based roofline*:

    x-axis  bandwidth intensity  BI = essential_bytes / moved_bytes
    y-axis  achieved bandwidth   = essential_bytes / wall_time
    MBU     = achieved bandwidth / peak HBM bandwidth

``essential_bytes`` is the information-theoretic minimum traffic of the op
(e.g. a gather of K rows × D dims × 4B must move exactly K·D·4 in + out);
``moved_bytes`` is what the implementation actually moves (re-reads,
padding, scratch spills). A perfectly-fused op has BI = 1 and its roofline
IS the memory roofline — the paper's Table 1 reports how far each system
sits below it.

Two measurement modes:
  * `measured` — wall-time on the current backend (CPU here; the benchmark
    harness uses it for *relative* fused-vs-naive comparisons, Table 1).
  * `structural` — dry-run mode: essential vs moved bytes derived from
    compiled HLO (`bytes accessed`), giving an implementation-quality
    ratio that is hardware-independent. EXPERIMENTS.md §Roofline reports
    structural MBU for the sparse path on the production mesh.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import numpy as np

from repro.roofline.analysis import chip_peaks


@dataclasses.dataclass(frozen=True)
class OpTraffic:
    """Essential traffic model of one sparse op (bytes in + out)."""

    name: str
    essential_bytes: int
    flops: int = 0

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.essential_bytes, 1)


# ---------------------------------------------------------------------------
# essential-traffic models for the paper's Table-1 ops
# ---------------------------------------------------------------------------

def t_bucketize(n: int, n_boundaries: int) -> OpTraffic:
    # read n f32 + cids, write n i32; boundary table is VMEM-resident
    return OpTraffic("bucketize", 4 * n + 4 * n + 4 * n + 4 * n_boundaries,
                     flops=int(n * np.ceil(np.log2(max(n_boundaries, 2)))))


def t_mod(n: int) -> OpTraffic:
    return OpTraffic("mod", 8 * n + 8 * n + 8 * n, flops=n)


def t_ids_partition(n: int) -> OpTraffic:
    # unique+shard: ids in, unique out, inverse out (sort-based ~2 passes)
    return OpTraffic("ids_partition", 8 * n * 3, flops=0)


def t_sequence_tile(n_rows: int, k: int, dim: int) -> OpTraffic:
    return OpTraffic("sequence_tile", 4 * dim * (n_rows * k) * 2, flops=0)


def t_reduce(n: int, dim: int) -> OpTraffic:
    # read n rows, write n_segments rows (≤ n) — lower bound is in-traffic
    return OpTraffic("reduce", 4 * dim * n + 4 * n, flops=n * dim)


def t_gather(k: int, dim: int) -> OpTraffic:
    return OpTraffic("gather", 4 * dim * k * 2 + 4 * k, flops=0)


def t_scatter(k: int, dim: int) -> OpTraffic:
    # read + modify + write each touched row
    return OpTraffic("scatter", 4 * dim * k * 3 + 4 * k, flops=k * dim)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MBUResult:
    name: str
    essential_bytes: int
    wall_s: float
    achieved_bw: float        # essential_bytes / wall_s
    mbu: float                # achieved_bw / the target's peak HBM bytes/s
    moved_bytes: int | None = None
    bandwidth_intensity: float | None = None   # essential / moved

    def row(self) -> str:
        bi = f"{self.bandwidth_intensity:6.3f}" if self.bandwidth_intensity else "   n/a"
        return (f"{self.name:16s} ess={self.essential_bytes/1e6:9.2f}MB "
                f"t={self.wall_s*1e3:8.3f}ms bw={self.achieved_bw/1e9:8.2f}GB/s "
                f"BI={bi} MBU={self.mbu*100:6.2f}%")


def measure(traffic: OpTraffic, fn: Callable, *args, target: str,
            iters: int = 10, warmup: int = 2, registry=None) -> MBUResult:
    """Wall-time MBU of ``fn(*args)`` on the current backend, against the
    peak HBM bandwidth of ``target`` (a ``device_kind`` in ``CHIP_PEAKS``).

    The MBU is a device metric only when the backend IS ``target``; a CPU
    timing over a TPU peak is a relative number, never a device MBU.

    ``registry`` (an ``obs.MetricsRegistry``) folds the result into the
    unified ``mbu/`` namespace so kernel-quality and runtime metrics land
    in one snapshot (DESIGN.md §9).
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    bw = traffic.essential_bytes / dt
    res = MBUResult(traffic.name, traffic.essential_bytes, dt, bw,
                    bw / chip_peaks(target).hbm_bw)
    if registry is not None:
        from repro.obs import record_mbu
        record_mbu(res, registry)
    return res


def structural(traffic: OpTraffic, fn: Callable, *args, target: str,
               registry=None) -> MBUResult:
    """Dry-run MBU: essential vs compiled `bytes accessed` (moved bytes).

    mbu_structural = BI = essential / moved — the fraction of the memory
    roofline the op would achieve on ANY bandwidth-bound hardware, assuming
    the moved bytes stream at peak. This is the §Roofline sparse-path
    metric (hardware-independent implementation quality).
    """
    lowered = jax.jit(fn).lower(*args)
    cost = lowered.compile().cost_analysis() or {}
    moved = int(cost.get("bytes accessed", 0)) or None
    bi = traffic.essential_bytes / moved if moved else None
    wall = (moved or traffic.essential_bytes) / chip_peaks(target).hbm_bw
    res = MBUResult(
        traffic.name, traffic.essential_bytes, wall,
        traffic.essential_bytes / wall, bi or 0.0,
        moved_bytes=moved, bandwidth_intensity=bi,
    )
    if registry is not None:
        from repro.obs import record_mbu
        record_mbu(res, registry)
    return res
