#!/usr/bin/env python3
"""Chip smoke test: dlrm-mlperf training at its published widths on a TPU.

Drives the training entry point (``repro.launch.train.run``) in this one
process and checks what comes out.

Default, one chip:
  1. dlrm-mlperf at its published widths — 13 dense and 26 sparse features,
     embed_dim 128, bottom MLP 512-256-128, top MLP 1024-1024-512-256-1 —
     with TABLE_ROWS rows of every table on the chip and the published
     train_batch, for STEPS steps on the XLA path and again with the Pallas
     kernels. No step after the first may compile.
  2. The Pallas program holds a Mosaic kernel (``tpu_custom_call``), and its
     step-1 loss matches the XLA path's.
  3. At smoke size, the chip's first losses match the same steps run on the
     host's CPU backend in this process.

``--four-chip`` runs only the sharded path, on four chips: a 4-chip
("data",) step against a 1-chip step on the same global batch, then the
published widths on four chips at 4x the one-chip rows, printing the
exchange's overflow counters.

The last line of stdout is the JSON verdict, printed only when every check
passed. With no TPU, the script exits non-zero and prints no verdict.

Usage: python chip_smoke.py [--four-chip]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The CPU comparison needs the host backend beside the TPU.
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import repro  # noqa: E402,F401  (x64 on before any array exists)
import jax  # noqa: E402

from repro.launch import train as train_mod  # noqa: E402
from repro.launch.common import enable_compile_cache  # noqa: E402

ARCH = "dlrm-mlperf"
TABLE_ROWS = 131_072      # rows of each of the 26 tables held by one chip
BATCH = 65_536            # the published train_batch
STEPS = 3
SMOKE_BATCH = 256
# MIXED runs the dense matmuls in bf16 with fp32 accumulation. Two backends,
# two pooling kernels or 1 vs 4 shards sum in different orders, so their
# batch-mean losses may differ by up to bf16's unit roundoff, 2^-8, of the
# loss; the printed |diff| shows how much closer they came.
LOSS_RTOL = 2.0 ** -8


def fail(msg: str):
    print(f"FAIL: {msg}", flush=True)
    raise SystemExit(1)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)
    print(f"  ok: {msg}", flush=True)


def check_close(a: float, b: float, what: str):
    check(abs(a - b) <= LOSS_RTOL * max(abs(a), abs(b)),
          f"{what}: {a:.6f} vs {b:.6f}, |diff| {abs(a - b):.2e}")


def train(devices, *argv: str):
    """One training run through the user's entry point; returns the logged
    per-step rows and the (TrainResult, Trainer, Cell) triple."""
    args = train_mod.parser().parse_args(
        ["--arch", ARCH, "--log-every", "1", "--steps", str(STEPS), *argv])
    res, trainer, cell = train_mod.run(args, devices=devices)
    rows = res.metrics_history
    if len(rows) != STEPS:
        fail(f"{len(rows)} of {STEPS} steps logged")
    return rows, trainer, cell


def group(row) -> str:
    """dlrm-mlperf's one merged embedding dim-group ("dim128" at published
    widths), as the engine's metric names spell it."""
    return next(k.split("/")[0] for k in row if k.endswith("/idmap_row_overflow"))


def report(label: str, rows, device) -> list[float]:
    print(f"[{label}]", flush=True)
    G = group(rows[0])
    for m in rows:
        print(f"  step {m['step']}: loss={m['loss']:.6f} "
              f"idmap_row_overflow={m[f'{G}/idmap_row_overflow']:.0f} "
              f"dev_rows_live={m[f'{G}/dev_rows_live']:.0f} "
              f"idmap_probe_depth={m[f'{G}/idmap_probe_depth']:.0f} "
              f"idmap_claim_depth={m[f'{G}/idmap_claim_depth']:.0f} "
              f"idmap_probe_rounds={m[f'{G}/idmap_probe_rounds']:.0f} "
              f"idmap_claim_rounds={m[f'{G}/idmap_claim_rounds']:.0f} "
              f"idmap_rounds={m[f'{G}/idmap_rounds']:.0f} "
              f"compiles={m['compiles']:.0f} compile_s={m['compile_s']:.1f} "
              f"wall_s={m['wall_s']:.3f}", flush=True)
    stats = device.memory_stats() or {}
    print(f"  peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")
    losses = [m["loss"] for m in rows]
    after = sum(m["compiles"] for m in rows[1:])
    print(f"  compiles after step 1: {after:.0f}", flush=True)
    check(all(map(_finite, losses)), f"{label}: losses finite")
    check(after == 0, f"{label}: no compile after step 1")
    check(all(m[f"{G}/idmap_row_overflow"] == 0 for m in rows),
          f"{label}: no row overflow")
    return losses


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


def one_chip(dev) -> None:
    published = ["--config", "published", "--table-rows", str(TABLE_ROWS),
                 "--batch", str(BATCH)]
    xla = report("published widths, XLA", train([dev], *published)[0], dev)
    release()

    rows, trainer, _ = train([dev], *published, "--use-pallas")
    pallas = report("published widths, Pallas", rows, dev)
    text = trainer.compiled().as_text()
    check("tpu_custom_call" in text, "Pallas program holds tpu_custom_call")
    check_close(pallas[0], xla[0], "step-1 loss, Pallas vs XLA")
    del rows, trainer, text
    release()

    smoke = ["--batch", str(SMOKE_BATCH)]
    chip = report("smoke, chip", train([dev], *smoke)[0], dev)
    cpu_dev = jax.devices("cpu")[0]
    cpu = report("smoke, cpu", train([cpu_dev], *smoke)[0], cpu_dev)
    for i, (a, b) in enumerate(zip(chip, cpu), 1):
        check_close(a, b, f"smoke step {i} loss, chip vs cpu")


def release() -> None:
    """Drop the finished run's device state before the next run places its
    own: two published-width tables do not fit one chip."""
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    print(f"  live device bytes after the run: {live}", flush=True)


def four_chip(devs) -> None:
    if len(devs) != 4:
        fail(f"--four-chip needs 4 chips, JAX found {len(devs)}")
    # at a row count one chip holds: the same global batch on 1 and 4 chips
    small = ["--config", "published", "--table-rows", "8192", "--batch", "512"]
    one = report("published widths, 1 chip", train(devs[:1], *small)[0], devs[0])
    four = report("published widths, 4 chips", train(devs, *small)[0], devs[0])
    for i, (a, b) in enumerate(zip(four, one), 1):
        check_close(a, b, f"step {i} loss, 4 chips vs 1 chip")
    release()

    rows, _, _ = train(devs, "--config", "published", "--table-rows",
                       str(TABLE_ROWS), "--batch", str(BATCH))
    report(f"published widths, 4 chips x {TABLE_ROWS} rows/table", rows, devs[0])
    G = group(rows[0])
    for m in rows:
        print(f"  step {m['step']}: " + " ".join(
            f"{k.split('/')[1]}={m[k]:.0f}" for k in sorted(m)
            if k.startswith(f"{G}/exch_")), flush=True)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chip", action="store_true",
                   help="run only the sharded path, on four chips")
    args = p.parse_args()

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {d.platform!r}",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    print(f"config: {ARCH} published widths (src/repro/configs/dlrm_mlperf.py), "
          f"batch {BATCH}, {STEPS} steps, random weights from seed 0, "
          f"compile cache {cache}")
    print(f"reduced: table rows {TABLE_ROWS} per table per chip "
          f"(published: 4,000,000 per table); {STEPS} steps")
    if args.four_chip:
        four_chip(devs)
    else:
        one_chip(d)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
