"""Pallas TPU kernels for the sparse hot-spots RecIS optimizes (paper §2.2.2
"Maximizing Bandwidth Utilization" + §2.2.3 Fused Kernels).

Every kernel package has three files:
  <name>.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public wrapper (padding, tiling choice, interpret fallback)
  ref.py     pure-jnp oracle used by the tests' allclose sweeps

Mapping to the paper's Table 1 operators:
  segment_reduce   reduce sum/mean (hard+easy)  — MXU one-hot matmul, no atomics
  fused_gather     gather                        — scalar-prefetch row DMA
  fused_scatter    scatter                       — row scatter-update
  fused_transform  bucketize (fused, multi-col)  — shared binary search in VMEM
  sequence_tile    sequence tile (concat pool)   — prefetch-driven row copy
  flash_attention  dense-side fused attention    — §2.2.3 (compute wall)

CPU validation: every op wrapper takes ``interpret=None``, which means the
Pallas interpreter on the CPU backend and the compiled kernel on TPU; any
other backend is an error rather than a silent fallback.

The package runs with x64 on (``repro/__init__.py``), under which a bare
Python int in an index map or kernel body traces as int64 and a Python float
as float64 — both refused by the TPU compiler. Kernels spell such literals
as ``ZERO`` or ``np.float32(...)``.
"""
import numpy as np

ZERO = np.int32(0)


def default_interpret() -> bool:
    import jax

    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels target TPU; no path for {backend!r}")
    return backend == "cpu"
