"""Chip benchmark of RecIS training: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up builds the cell through the program's own path
(``repro.launch.cells.build_cell`` with ``CellOptions(chip_table_rows=...)``,
state placed by ``Cell.init()``, the towers' weights drawn from the seed),
makes a ring of distinct batches on the host from the seed and the cell's
traffic mix, and trains the mix's first ``check_steps`` batches through
``Trainer.run`` (the first step compiles). The window then drives the same
``Trainer`` and state on the ring for ``--seconds``: each step places its
batch on the device through the cell's batch shardings, and the clock stops
once the returned state is ready. With ``--trace 1`` the window runs under
the profiler, for at most ``TRACE_STEPS`` steps, and the per-layer metrics
are read from the trace.

After the window the program's state is freed and the plain reference
replays the first steps; ``check.py`` compares the two. The last lines of
standard error give each compared number beside its limit, and the last
line of standard output is the result as one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
TRACE_STEPS = 8
NO_CHIP = 3


def parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default=None, metavar="DIR",
                   help="copy the profiler trace to DIR (for reading it by hand)")
    return p


def run(argv=None, root: pathlib.Path = ROOT, require_tpu: bool = True) -> dict | None:
    """One run; returns the result (None without the chip it needs)."""
    args = parser().parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import harness

    harness.set_env(root)
    import jax

    import registry

    chips = int(registry.Bench(root).workload(args.workload)["chips"])
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"run: needs {chips} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return None
    harness.use_cache()
    import numpy as np

    import check
    import traffic
    trace_lib = registry.load(BENCH, "trace")  # not the standard library's trace

    h = harness.Harness(root, args.workload, devices)
    ring = h.ring(args.seed)
    state, dense0 = h.fresh_state(args.seed)
    trainer = h.trainer(profile=bool(args.trace))
    state, prog = h.checked_steps(trainer, state, dense0, ring)
    jax.block_until_ready(state)

    # ----------------------------------------------------------- the window
    trace_dir = BENCH / ".traces" / f"{args.workload}-{args.seed}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    pulls: list[float] = []
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
        res = trainer.run(state, h.batches(ring, h.n_check, deadline=t0 + args.seconds,
                                           max_steps=TRACE_STEPS if args.trace else None,
                                           pulls=pulls), start_step=h.n_check)
        jax.block_until_ready(res.state)
    t1 = time.perf_counter()
    summary = None
    if args.trace:
        jax.profiler.stop_trace()
        xplane = trace_lib.find_xplane(trace_dir)
        if args.keep_trace and xplane is not None:
            shutil.copytree(xplane.parent, args.keep_trace, dirs_exist_ok=True)
        roots = trace_lib.fusion_roots(trainer.compiled().as_text())
        summary = trace_lib.reduce(xplane, roots) if xplane is not None else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    steps, hist = res.steps_run, res.metrics_history
    peak = harness.peak_bytes(h.devices)

    # --------------------------------------- free the program, then check
    h.free(res.state)
    del res, state, trainer
    ref = h.reference(args.seed, ring)
    nums = h.numbers(prog, ref)
    correct, compared = check.judge(nums, h.bench.limits(args.workload))

    # -------------------------------------------------------------- metrics
    window_uniq = [traffic.unique_per_group(ring[(h.n_check + i) % len(ring)], h.groups)
                   for i in range(steps)]
    inserted = sum(x.get(f"{g}/idmap_inserted", 0.0) for x in hist for g in h.groups)
    kind = devices[0].device_kind
    ctx = types.SimpleNamespace(
        workload=args.workload, chips=chips, global_batch=h.batch, cfg=h.cfg,
        device_kind=kind, setup_s=setup_s, peak_bytes=peak,
        steps=steps, window_s=t1 - t0, intervals=list(np.diff(pulls)),
        examples=steps * h.batch, trace=summary,
        forward_flops_per_example=h.ref.forward_flops_per_example(h.cfg),
        unique_per_step=window_uniq, row_bytes={g: 4 * d for g, d in h.dims.items()},
        ids_per_step=sum(c["values"] for c in h.columns if c["ids"]),
    )
    metrics = {}
    for m in h.bench.metrics_of(args.workload, bool(args.trace)):
        value = h.bench.metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": steps, "failed": 0, "metrics": metrics,
           "device": device}
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["window"] = {
        "compiles": sum(x.get("compiles", 0) for x in hist),
        "peak_bytes_step1": prog["peak_bytes_step1"],
        "new_id_share": inserted / max(1, sum(sum(u.values()) for u in window_uniq)),
        "op_kind_s": summary and summary["kind_s"],
        "worst": {k: v[1] for k, v in nums.items()},
        "readings": {k: {n: r[n] for n in ("losses", "grad_norms", "change_norms")}
                     for k, r in (("program", prog), ("reference", ref))},
    }
    out["check"] = compared
    return out


def main(argv=None) -> int:
    out = run(argv)
    if out is None:
        return NO_CHIP
    for name, c in out["check"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
