"""Readings that the limits of a cell are set from (``limits/<cell>.json``).

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1 2 3 ...

For each seed, in one process at the cell's own size: the program's
readings of its checked steps against the reference (the lower reading is
the largest over the seeds), the control's (the reference with every tower
product's operands rounded to float8 e4m3, one step below the bfloat16 that
the configuration states), and the faults planted in the reference put in
the program's place: half of the batch left out of the loss, one feature's
ids altered where they are hashed, and, on more than one chip, the exchange
between chips left out. A step that returns its state unchanged reads 1 on
``change_gap`` and needs no run. ``bf16`` is a witness, not a control: the
reference with its tower values rounded as the program rounds them, to
see how much of a gap bfloat16 alone makes. One JSON line per seed and
reading goes to standard output, with the seconds each reading took; the
last line sums them up.

``--check-steps 1`` reads the numbers of step 1 alone (``moment_gap`` and
``sign_flip_share`` need no more); ``--budget-s`` starts no seed once that
many seconds have passed, so that a call on the chip ends on time.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


def variants(h) -> list[str]:
    return ["control", "half_batch", "altered_id"] + (["no_exchange"] if h.chips > 1 else [])


def readings(h, seed: int, trainer=None, which=None) -> dict:
    """{reading: {number: (value, worst leaf or step)}} of one seed; the
    program's only with a ``trainer``; the variants ``which`` (the control,
    the faults, ``bf16``; default: the control and the faults)."""
    import harness
    import traffic

    ring = traffic.make_ring(dict(h.mix, ring_batches=h.n_check), h.cfg, h.chips,
                             h.columns, seed)
    out, secs = {}, {}
    if trainer is not None:  # first, so that the peaks are the program's
        t = time.perf_counter()
        state, dense0 = h.fresh_state(seed)
        state, out["program"] = h.checked_steps(trainer, state, dense0, ring)
        peaks = {"step1": out["program"]["peak_bytes_step1"],
                 "after_readouts": harness.peak_bytes(h.devices)}
        h.free(state)
        secs["program"] = time.perf_counter() - t
    t = time.perf_counter()
    ref = h.reference(seed, ring)
    secs["reference"] = time.perf_counter() - t
    for v in variants(h) if which is None else which:
        t = time.perf_counter()
        if v == "control":
            out[v] = h.reference(seed, ring, quantize=h.common.fp8)
        elif v == "bf16":
            out[v] = h.reference(seed, ring, quantize=h.common.bf16)
        else:
            out[v] = h.reference(seed, ring, fault=v)
        # the reference put in the program's place
        out[v].update(rows=out[v]["state"]["tables"], dense=out[v]["state"]["dense"])
        secs[v] = time.perf_counter() - t
    nums = {k: h.numbers(r, ref) for k, r in out.items()}
    nums["raw"] = {k: {n: r[n] for n in ("losses", "grad_norms", "change_norms")}
                   for k, r in (("reference", ref), *out.items())}
    nums["seconds"] = secs
    if trainer is not None:
        nums["peak_bytes"] = peaks
    return nums


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--variants", nargs="*", default=None,
                   help="control, half_batch, altered_id, no_exchange, bf16 "
                        "(default: the control and the cell's faults)")
    p.add_argument("--variant-seeds", type=int, default=None, metavar="N",
                   help="read the variants on the first N seeds only")
    p.add_argument("--no-program", action="store_true",
                   help="read only the variants")
    p.add_argument("--check-steps", type=int, default=None, metavar="N",
                   help="read the first N steps (default: the mix's check_steps)")
    p.add_argument("--budget-s", type=float, default=None,
                   help="start no seed once this many seconds have passed")
    args = p.parse_args(argv)
    t0 = time.perf_counter()
    sys.path.insert(0, str(BENCH))
    import harness

    harness.set_env(ROOT)
    import jax

    harness.use_cache()
    h = harness.Harness(ROOT, args.workload, jax.devices())
    if args.check_steps:
        h.n_check = args.check_steps
    per_seed, trainer = [], None if args.no_program else h.trainer()
    n_var = len(args.seeds) if args.variant_seeds is None else args.variant_seeds
    for i, seed in enumerate(args.seeds):
        if args.budget_s is not None and time.perf_counter() - t0 > args.budget_s:
            break
        r = readings(h, seed, trainer, which=args.variants if i < n_var else [])
        per_seed.append(r)
        print(json.dumps({"seed": seed, **r}), flush=True)
    summary = {}
    for k in per_seed[0]:
        if k in ("raw", "seconds", "peak_bytes"):
            continue
        for name in per_seed[0][k]:
            vals = [r[k][name][0] for r in per_seed if k in r]
            # the program's largest reading; each variant's least
            summary.setdefault(name, {})[k] = max(vals) if k == "program" else min(vals)
    print(json.dumps({"summary": summary, "seeds": args.seeds[:len(per_seed)],
                      "check_steps": h.n_check}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
