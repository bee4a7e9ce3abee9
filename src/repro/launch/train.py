"""End-to-end train driver: --arch/--shape → cell → Trainer loop.

It runs on a one-axis ("data",) mesh over every device of the default
backend. ``--config smoke`` (the default) is the reduced same-family
config the CPU tests use; ``--config published`` is the arch at its
published widths, with ``--table-rows`` rows of every embedding table on
each chip (the chip's share of the deployment's tables).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-mlperf \
      --steps 100 --batch 256 --ckpt-dir /tmp/ckpt [--resume]
  python -m repro.launch.train --arch dlrm-mlperf --config published \
      --table-rows 131072 --batch 65536 --steps 3     # on a TPU chip

With ``--data-dir`` (recsys archs, single-device smoke mesh) batches
stream from a ColumnIO table through an AsyncLoader instead of the
synthetic generator; ``--autoscale`` then closes the loop with a
``PipelineController`` (DESIGN.md §10) that resizes the reader pool and
rebalances shards from the registry's step-edge signals.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

import jax

from repro import obs
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeCell
from repro.ft.chaos import InjectedCrash
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions, enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.pipelines import TrainConfig, Trainer

CHAOS_EXIT = 42  # an injected crash is "the process died here" — not an error


def run_shape(arch, shape_name: str | None, batch: int, seq_len: int) -> ShapeCell:
    fam = arch.family
    if fam == "lm":
        return ShapeCell(shape_name or "train_4k", "train",
                         {"seq_len": seq_len, "global_batch": batch})
    if fam == "recsys":
        return ShapeCell(shape_name or "train_batch", "train", {"batch": batch})
    return ShapeCell(shape_name or "molecule", "graph_batch",
                     {"n_nodes": 12, "n_edges": 24, "batch": batch,
                      "d_feat": 16, "n_classes": 2})


def _with_step_chaos(stream, chaos, start: int):
    """Fire the schedule's step events as the trainer pulls batches: the
    batch yielded k-th becomes trainer step ``start + k``."""
    step = start
    for batch in stream:
        step += 1
        chaos.on_step(step)
        yield batch


def parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=ARCH_IDS)
    p.add_argument("--config", choices=("smoke", "published"), default="smoke",
                   help="smoke = reduced same-family config; published = "
                        "the arch's published widths")
    p.add_argument("--table-rows", type=int, default=None, metavar="N",
                   help="rows of every embedding table each chip holds "
                        "(default: the config's table sizes)")
    p.add_argument("--shape", default=None)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--use-pallas", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--telemetry", default=None, metavar="PATH",
                   help="write a JSONL step-phase trace (DESIGN.md §9)")
    p.add_argument("--console-every", type=int, default=0,
                   help="print a registry report every N steps")
    p.add_argument("--profile-spans", action="store_true",
                   help="bridge step-phase spans to jax.profiler")
    # ColumnIO data path + pipeline autoscaler (DESIGN.md §10)
    p.add_argument("--data-dir", default=None, metavar="DIR",
                   help="stream batches from a ColumnIO table (synthesized "
                        "there on first use; recsys archs only)")
    p.add_argument("--data-rows", type=int, default=8192,
                   help="rows to synthesize when --data-dir is empty")
    p.add_argument("--data-parts", type=int, default=4,
                   help="part files when synthesizing the table")
    p.add_argument("--io-threads", type=int, default=2,
                   help="initial AsyncLoader reader threads")
    p.add_argument("--prefetch", type=int, default=8,
                   help="AsyncLoader prefetch-queue capacity")
    p.add_argument("--autoscale", action="store_true",
                   help="closed-loop reader-pool autoscaler (needs --data-dir)")
    p.add_argument("--autoscale-min", type=int, default=1,
                   help="reader-pool floor")
    p.add_argument("--autoscale-max", type=int, default=8,
                   help="reader-pool ceiling")
    # fault tolerance (DESIGN.md §13)
    p.add_argument("--ckpt-mode", choices=("full", "delta"), default="full",
                   help="full = sharded snapshot saver; delta = incremental "
                        "dirty-row frames on a crash-consistent manifest "
                        "chain (sparse-engine archs, needs --ckpt-dir)")
    p.add_argument("--chaos-schedule", default=None, metavar="SPEC",
                   help="deterministic fault injection, e.g. "
                        "'torn@frame:2,crash@manifest:3,sigterm@step:40' "
                        f"(an injected crash exits {CHAOS_EXIT})")
    # cross-process telemetry (DESIGN.md §12)
    p.add_argument("--worker-id", default=None, metavar="ID",
                   help="worker id stamped on telemetry snapshots")
    p.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                   help="emit a mergeable registry snapshot every N steps "
                        "(needs --telemetry; 0 = off)")
    p.add_argument("--prometheus-port", type=int, default=None, metavar="P",
                   help="serve GET /metrics for scraping (0 = ephemeral)")
    p.add_argument("--aggregate", nargs="*", default=None, metavar="GLOB",
                   help="tail peer telemetry files; publishes agg/* and "
                        "gates the autoscaler on the fleet queue")
    return p


def run(args: argparse.Namespace, devices=None):
    """Build the cell and train ``args.steps`` steps on a ("data",) mesh over
    ``devices`` (default: all of the default backend's). Returns
    (TrainResult, Trainer, Cell). Argument errors raise SystemExit(2)."""
    def error(msg):
        print(f"train: error: {msg}", file=sys.stderr)
        raise SystemExit(2)

    if args.snapshot_every and not args.telemetry:
        error("--snapshot-every requires --telemetry (snapshots ride the "
              "JSONL trace)")
    if args.autoscale and not args.data_dir:
        error("--autoscale requires --data-dir (nothing to scale without "
              "an AsyncLoader)")

    mesh = make_mesh(devices=devices)
    smoke = args.config == "smoke"
    arch = get_config(args.arch, smoke=smoke)
    shape = run_shape(arch, args.shape, args.batch, args.seq_len)
    opts = CellOptions(use_pallas=args.use_pallas, remat=False, zero1=False,
                       chip_table_rows=args.table_rows)
    cell = build_cell(args.arch, shape.name, mesh, opts, smoke=smoke,
                      shape_override=shape)

    loader = controller = None
    if args.data_dir:
        if arch.family != "recsys":
            error("--data-dir is a recsys-family data path")
        if mesh.devices.size != 1:
            error("--data-dir streaming needs a single-device mesh")
        from repro.io import datagen
        from repro.io.columnio import AsyncLoader, BatchSpec
        from repro.launch.recsys_cell import _ids_per_row, _model_mod

        table = pathlib.Path(args.data_dir)
        model_specs = _model_mod(args.arch).feature_specs(arch.model)
        if not any(table.glob("part-*.col")):
            gens = datagen.gen_for_specs(model_specs, seq_mean_len=4.0)
            datagen.write_table(table, gens, n_rows=args.data_rows,
                                rows_per_group=256, n_parts=args.data_parts)
            print(f"synthesized table: {table} ({args.data_rows} rows, "
                  f"{args.data_parts} parts)")
        # budgets must equal the cell's static jit shapes exactly: the
        # loader pads every column to its budget (batch * ids-per-row)
        bspec = BatchSpec(batch_rows=args.batch,
                          nnz_budget={s.name: args.batch * _ids_per_row(s)
                                      for s in model_specs})
        loader = AsyncLoader(table, bspec, n_threads=args.io_threads,
                             prefetch=args.prefetch, loop=True)
        if args.autoscale:
            from repro.io.autoscale import AutoscaleConfig, PipelineController
            aggregator = None
            if args.aggregate is not None:
                aggregator = obs.TelemetryAggregator()
                for pat in args.aggregate:
                    aggregator.discover(pat)
            controller = PipelineController(
                loader, AutoscaleConfig(min_readers=args.autoscale_min,
                                        max_readers=args.autoscale_max),
                aggregator=aggregator)

    hooks = ft_io = step_chaos = None
    if args.chaos_schedule:
        from repro.ft import ChaosIO, ChaosSchedule, StepChaos
        sched = ChaosSchedule.parse(args.chaos_schedule)
        step_chaos = StepChaos(sched)
        if args.ckpt_mode == "delta":
            ft_io = ChaosIO(sched)
        print(f"chaos schedule: {sched}")
    if args.ckpt_mode == "delta":
        if not args.ckpt_dir:
            error("--ckpt-mode delta requires --ckpt-dir")
        hooks = getattr(cell, "storage_hooks", None)
        if hooks is None:
            engine = getattr(cell, "engine", None)
            ids_fn = getattr(cell, "ids_fn", None)
            if engine is None or ids_fn is None:
                error("--ckpt-mode delta needs a sparse-engine arch "
                      "(recsys family)")
            from repro.ft import FTTrainerHooks
            hooks = FTTrainerHooks(engine, ids_fn, state_key="sparse")

    tcfg = TrainConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, resume=args.resume,
                       log_every=args.log_every,
                       telemetry_path=args.telemetry,
                       console_every=args.console_every,
                       profile_spans=args.profile_spans,
                       worker=args.worker_id,
                       snapshot_every=args.snapshot_every,
                       ft_mode=args.ckpt_mode, ft_io=ft_io)
    trainer = Trainer(cell, tcfg, hooks=hooks, controller=controller)
    exporter = None
    if args.prometheus_port is not None:
        exporter = obs.PrometheusExporter(trainer.registry,
                                          port=args.prometheus_port)
        print(f"prometheus: serving /metrics on port {exporter.start()}")

    with mesh:
        state = cell.init()
        state, start, cursor = trainer.try_resume(state)
        if start:
            print(f"resumed from step {start} (cursor={cursor})")

        def batches():
            s = args.seed + start
            while True:
                yield cell.make_batch(s)
                s += 1

        stream = iter(loader) if loader is not None else batches()
        if step_chaos is not None:
            stream = _with_step_chaos(stream, step_chaos, start)
        cursor_fn = ((lambda: loader.cursor) if loader is not None
                     else (lambda: {"part": 0, "group": 0}))
        try:
            res = trainer.run(state, stream, start_step=start,
                              cursor_fn=cursor_fn, install_signals=True)
        except InjectedCrash as e:
            # stands in for SIGKILL: nothing that would normally run on the
            # way out (final save, GC, loader drain) may run after it
            print(f"CHAOS: {e}", flush=True)
            os._exit(CHAOS_EXIT)
    if loader is not None:
        loader.stop()
    if exporter is not None:
        exporter.stop()
    if controller is not None:
        print(f"autoscale: {len(controller.actions_log)} actions, "
              f"final readers={loader.n_readers}")
        for s, act in controller.actions_log:
            print(f"  step {s}: {act}")
    return res, trainer, cell


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    enable_compile_cache()
    res, _, _ = run(args)
    for m in res.metrics_history[-5:]:
        print({k: round(v, 5) if isinstance(v, float) else v for k, v in m.items()})
    print(f"ran {res.steps_run} steps"
          + (f", resumed from {res.resumed_from}" if res.resumed_from else "")
          + (", PREEMPTED" if res.preempted else ""))
    if res.straggler_events:
        print(f"straggler events: {len(res.straggler_events)}")
        for ev in res.straggler_events[-3:]:
            print(f"  step {ev.step}: {ev.wall_s*1e3:.1f}ms "
                  f"(thresh {ev.threshold*1e3:.1f}ms, phase={ev.phase})")
    # phase timeline summary from the unified registry (DESIGN.md §9)
    snap = res.registry.snapshot()
    for name in sorted(snap):
        if name.startswith("trace/") and isinstance(snap[name], dict) \
                and snap[name].get("count"):
            s = snap[name]
            print(f"{name:28s} p50={s['p50']*1e3:8.3f}ms "
                  f"p99={s['p99']*1e3:8.3f}ms total={s['sum']:.3f}s")
    if args.telemetry:
        print(f"telemetry trace: {args.telemetry}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
