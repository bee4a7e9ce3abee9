"""Plant one fault in the program's timed path, then drive a run.

    python3 benchmarks/chip/faults.py --fault <fault> [--no-chip] \
        -- --workload <name> --seed <n> --seconds <s> --trace 0

Faults (a sound comparison reads ``correct`` false under each one the cell
can have; ``none`` plants nothing):

* ``state_unchanged``: the train step returns the state it was given;
* ``half_batch``: the loss is the mean over the first half of the batch,
  the rest left out;
* ``no_exchange``: the all-to-all between chips is left out (each chip
  looks its own ids up in its own shard);
* ``altered_id``: the feature hash flips the low bit of the first hashed
  column's ids where it produces them.

``--no-chip`` skips the harness's look for a chip (the CPU tests).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
FAULTS = ("none", "state_unchanged", "half_batch", "no_exchange", "altered_id")


def plant(fault: str, src: pathlib.Path) -> None:
    sys.path.insert(0, str(src))
    import jax

    if fault == "none":
        return
    if fault == "state_unchanged":
        from repro.launch import cells

        build = cells.build_cell

        def build_cell(*a, **k):
            cell = build(*a, **k)
            step = cell.step_fn
            cell.step_fn = lambda state, batch: (state, step(state, batch)[1])
            return cell

        cells.build_cell = build_cell
    elif fault == "half_batch":
        from repro.models.recsys import common, dlrm, wide_deep

        def half(logits, labels):
            n = logits.shape[0] // 2
            return common.bce_with_logits(logits[:n], labels[:n])

        for mod in (dlrm, wide_deep):
            mod.bce_with_logits = half
    elif fault == "no_exchange":
        jax.lax.all_to_all = lambda x, *a, **k: x
    elif fault == "altered_id":
        import jax.numpy as jnp

        from repro.core import feature_engine

        hashed = feature_engine.fused_hash

        def fused_hash(values, column_ids, salts):
            return hashed(values, column_ids, salts) ^ (column_ids == 0).astype(jnp.int64)

        feature_engine.fused_hash = fused_hash
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--fault", required=True, choices=FAULTS)
    p.add_argument("--no-chip", action="store_true")
    p.add_argument("--root", default=str(BENCH.parents[1]))
    p.add_argument("run_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import harness
    import run

    root = pathlib.Path(args.root)
    harness.set_env(root)
    plant(args.fault, root / "src")
    rest = args.run_args[1:] if args.run_args[:1] == ["--"] else args.run_args
    out = run.run(rest, root=root, require_tpu=not args.no_chip)
    if out is None:
        return run.NO_CHIP
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
