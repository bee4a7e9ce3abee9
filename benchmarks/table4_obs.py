"""Benchmark — observability instrumentation overhead (DESIGN.md §9).

The observability layer's contract is that it is always-on capable: full
telemetry (MetricsRegistry counters + step-phase spans + JSONL export +
watchdog phase attribution) must cost < 5% of step wall-time, or nobody
will leave it enabled and the phase timeline will never be there when the
straggler shows up.

Two measurements:
  * end-to-end — the same wide-deep smoke cell trained twice through the
    Trainer: telemetry fully ON (JSONL trace + registry + spans) vs OFF
    (no writer; spans still run, which is the Trainer's floor). Each
    variant does a full warm run first so jit compile never pollutes the
    timed run; best-of-``REPEATS`` to shed scheduler noise.
  * micro — ns/op for the primitives (counter.inc, histogram.observe with
    three P² estimators, a traced span, one JSONL emit), so a regression
    is attributable.

Emits ``BENCH_obs.json`` at the repo root (overhead_fraction is the
acceptance number).

Run: PYTHONPATH=src python -m benchmarks.run --only obs
"""
from __future__ import annotations

import json
import pathlib
import tempfile
import time

from repro import obs
from repro.configs.base import ShapeCell
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions
from repro.launch.mesh import make_mesh
from repro.pipelines import TrainConfig, Trainer

STEPS = 50
REPEATS = 3
MICRO_N = 100_000

# ns/op measured before the batched-P² drain rewrite (sequential estimator
# update per observe). Kept in the emitted JSON so the CI gate can assert
# the rewrite's win never silently regresses (scripts/ci.sh).
MICRO_NS_PREV = {
    "counter_inc_ns": 272.94,
    "histogram_observe_ns": 10706.59,
    "span_ns": 12939.10,
    "jsonl_emit_ns": 7039.08,
}


def _steps_per_s(telemetry: bool, workdir: pathlib.Path) -> float:
    shape = ShapeCell("train_batch", "train", {"batch": 32})
    cell = build_cell("wide-deep", "train_batch", make_mesh(),
                      CellOptions(remat=False, zero1=False),
                      smoke=True, shape_override=shape)
    cfg = TrainConfig(
        total_steps=STEPS, log_every=10, watchdog=True,
        telemetry_path=str(workdir / "trace.jsonl") if telemetry else None)
    tr = Trainer(cell, cfg, registry=obs.MetricsRegistry())
    best = 0.0
    with cell.mesh:
        state = cell.init_state()
        # compile + warm; step state forward (donated buffers)
        state = tr.run(state,
                       (cell.make_batch(s) for s in range(STEPS))).state
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            res = tr.run(state, (cell.make_batch(s) for s in range(STEPS)))
            dt = time.perf_counter() - t0
            state = res.state
            assert res.steps_run == STEPS
            best = max(best, STEPS / dt)
    return best


def _micro() -> dict[str, float]:
    reg = obs.MetricsRegistry()
    out = {}

    c = reg.counter("bench/counter")
    t0 = time.perf_counter()
    for _ in range(MICRO_N):
        c.inc()
    out["counter_inc_ns"] = (time.perf_counter() - t0) / MICRO_N * 1e9

    h = reg.histogram("bench/hist")
    t0 = time.perf_counter()
    for i in range(MICRO_N):
        h.observe(i * 1e-6)
    out["histogram_observe_ns"] = (time.perf_counter() - t0) / MICRO_N * 1e9

    tracer = obs.Tracer(reg, writer=None)
    n = MICRO_N // 10
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.span("device_step"):
            pass
    out["span_ns"] = (time.perf_counter() - t0) / n * 1e9

    with tempfile.TemporaryDirectory() as td:
        w = obs.TelemetryWriter(pathlib.Path(td) / "t.jsonl")
        rec = {"type": "step", "step": 1,
               "spans": {"data_wait": 0.001, "device_step": 0.004}}
        t0 = time.perf_counter()
        for _ in range(n):
            w.emit(rec)
        out["jsonl_emit_ns"] = (time.perf_counter() - t0) / n * 1e9
        w.close()

    # aggregator hot path: capture → serialize → 3-way merge of a
    # representative registry (DESIGN.md §12)
    sreg = obs.MetricsRegistry()
    sreg.counter("train/steps_total").inc(1000)
    sreg.gauge("io/queue_depth").set(5.0)
    sh = sreg.histogram("trace/device_step_s")
    for i in range(512):
        sh.observe(1e-3 + i * 1e-6)
    n = 200
    t0 = time.perf_counter()
    for _ in range(n):
        s = obs.RegistrySnapshot.capture(sreg, worker="w0", t=0.0)
        obs.merge_snapshots([s, s, s]).to_json_str()
    out["snapshot_merge3_us"] = (time.perf_counter() - t0) / n * 1e6
    return out


def run() -> dict:
    print("=" * 88)
    print("Table 4 — observability: instrumentation overhead "
          "(telemetry ON vs OFF, same cell)")
    print("=" * 88)
    micro = _micro()
    for k, v in micro.items():
        prev = MICRO_NS_PREV.get(k)
        delta = f"  (was {prev:.0f}, {prev / v:4.1f}x)" if prev else ""
        print(f"  micro {k:24s} {v:10.0f} {k.rsplit('_', 1)[-1]}/op{delta}")
    with tempfile.TemporaryDirectory() as td:
        base = _steps_per_s(False, pathlib.Path(td))
        full = _steps_per_s(True, pathlib.Path(td))
        n_records = len(obs.read_jsonl(pathlib.Path(td) / "trace.jsonl"))
    overhead = max(0.0, 1.0 - full / base)
    print(f"  telemetry OFF  {base:8.2f} steps/s")
    print(f"  telemetry ON   {full:8.2f} steps/s   "
          f"({n_records} JSONL records)")
    print(f"  overhead       {overhead * 100:8.2f} %  (budget: < 5%)")
    results = {
        "steps": STEPS,
        "base_steps_per_s": base,
        "telemetry_steps_per_s": full,
        "overhead_fraction": overhead,
        "jsonl_records": n_records,
        "micro_ns": micro,
        "micro_ns_prev": MICRO_NS_PREV,
    }
    out_path = pathlib.Path(__file__).resolve().parents[1] / "BENCH_obs.json"
    out_path.write_text(json.dumps(results, indent=2))
    print(f"wrote {out_path}")
    return results


if __name__ == "__main__":
    run()
