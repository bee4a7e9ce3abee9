"""Observability layer tests (DESIGN.md §9): registry instruments,
streaming quantiles, JSONL telemetry + rotation, step-phase tracing, the
phase-aware straggler watchdog, PreemptionGuard round-trip, interval
hook-metric accumulation, metric-name lint — and the acceptance run: a
telemetry-enabled Trainer emits a parseable phase-attributed JSONL trace
with storage/IO counters under unified names."""
import os
import signal
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.stages import STAGES, backward_of, op_names, stage, stage_of
from repro.pipelines import (
    PreemptionGuard, StragglerWatchdog, TrainConfig, Trainer,
)

PHASES = ("data_wait", "pre_step", "device_step", "post_step")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("io/rows")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert reg.counter("io/rows") is c  # create-or-get
        g = reg.gauge("storage/host_rows")
        g.set(7)
        g.set(3)
        assert g.value == 3
        assert reg.snapshot() == {"io/rows": 5, "storage/host_rows": 3}

    def test_histogram_streaming_quantiles(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("trainer/step_wall_s")
        r = np.random.default_rng(0)
        xs = r.lognormal(0.0, 0.5, 10_000)
        for x in xs:
            h.observe(x)
        s = h.summary()
        assert s["count"] == 10_000
        assert s["min"] == xs.min() and s["max"] == xs.max()
        np.testing.assert_allclose(s["mean"], xs.mean(), rtol=1e-6)
        # P² estimates vs exact quantiles — no samples stored
        for p in (50, 95, 99):
            np.testing.assert_allclose(
                s[f"p{p}"], np.percentile(xs, p), rtol=0.05)

    def test_histogram_small_sample(self):
        h = obs.MetricsRegistry().histogram("a/b")
        for x in (3.0, 1.0, 2.0):
            h.observe(x)
        assert h.summary()["p50"] == 2.0

    def test_name_lint(self):
        reg = obs.MetricsRegistry()
        for bad in ("BadName", "noprefix", "io/CamelCase", "io/", "/io",
                    "io//x", "io/has-dash", "9io/x"):
            with pytest.raises(ValueError):
                reg.counter(bad)
        # multi-level prefixes are fine
        reg.gauge("roofline/wide_deep/train_batch/cpu1/compute_s")

    def test_kind_conflict(self):
        reg = obs.MetricsRegistry()
        reg.counter("io/rows")
        with pytest.raises(TypeError):
            reg.gauge("io/rows")

    def test_flat_expands_histograms(self):
        reg = obs.MetricsRegistry()
        reg.histogram("io/read_group_s").observe(0.5)
        flat = reg.flat()
        assert flat["io/read_group_s/count"] == 1
        assert flat["io/read_group_s/p50"] == 0.5
        assert all(obs.valid_name(k) for k in flat)

    def test_sanitize(self):
        assert obs.sanitize("wide-deep") == "wide_deep"
        assert obs.valid_name(f"mbu/{obs.sanitize('Ids Partition!')}/bi")


# ---------------------------------------------------------------------------
# telemetry writer
# ---------------------------------------------------------------------------

class TestTelemetryWriter:
    def test_jsonl_roundtrip(self, tmp_path):
        w = obs.TelemetryWriter(tmp_path / "t.jsonl")
        w.emit({"type": "event", "x": 1})
        w.emit({"type": "event", "x": np.int64(2), "arr": np.arange(2)})
        w.close()
        recs = obs.read_jsonl(tmp_path / "t.jsonl")
        assert [r["x"] for r in recs] == [1, 2]
        assert recs[1]["arr"] == [0, 1]
        assert all("t" in r for r in recs)

    def test_rotation(self, tmp_path):
        w = obs.TelemetryWriter(tmp_path / "t.jsonl", max_bytes=200,
                                max_files=2)
        for i in range(50):
            w.emit({"type": "event", "i": i})
        w.close()
        files = sorted(p.name for p in tmp_path.glob("t.jsonl*"))
        assert files == ["t.jsonl", "t.jsonl.1", "t.jsonl.2"]
        # every surviving file is parseable; the newest record survives
        assert obs.read_jsonl(tmp_path / "t.jsonl")[-1]["i"] == 49
        assert w.records_written == 50


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_step_record_and_histograms(self, tmp_path):
        reg = obs.MetricsRegistry()
        w = obs.TelemetryWriter(tmp_path / "t.jsonl")
        tr = obs.Tracer(reg, w)
        with tr.step(3) as st:
            with tr.span("data_wait"):
                pass
            with tr.span("device_step"):
                pass
            with tr.span("device_step"):  # repeated spans accumulate
                pass
            st.annotate(loss=0.5)
        w.close()
        (rec,) = obs.read_jsonl(tmp_path / "t.jsonl")
        assert rec["type"] == "step" and rec["step"] == 3
        assert set(rec["spans"]) == {"data_wait", "device_step"}
        assert rec["loss"] == 0.5
        assert reg.histogram("trace/device_step_s").count == 2

    def test_standalone_span_and_cancel(self, tmp_path):
        w = obs.TelemetryWriter(tmp_path / "t.jsonl")
        tr = obs.Tracer(None, w)
        with tr.span("checkpoint"):
            pass
        with tr.step(1) as st:
            st.cancel()
        w.close()
        recs = obs.read_jsonl(tmp_path / "t.jsonl")
        assert len(recs) == 1 and recs[0]["type"] == "span"
        assert recs[0]["name"] == "checkpoint"


class TestProfilerStepMarker:
    def test_profiled_steps_open_the_profilers_step_marker(self, monkeypatch):
        opened = []

        class Marker:
            def __init__(self, name, **kw):
                opened.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Marker)
        with obs.Tracer(profile=True).step(7):
            pass
        with obs.Tracer().step(8):  # unprofiled: no marker
            pass
        assert opened == [("train", {"step_num": 7})]


# ---------------------------------------------------------------------------
# stage scopes of the train step (repro.obs.stages)
# ---------------------------------------------------------------------------

class TestStages:
    def test_stages_are_dotted_recis_names_and_unknown_ones_raise(self):
        assert len(set(STAGES)) == len(STAGES)
        assert all(stage_of(f"jit(f)/{s}/add") == s for s in STAGES)
        with pytest.raises(ValueError, match="not a stage"):
            stage("recis.idmap.nothing")

    def test_stage_of_is_the_innermost_and_backward_of_the_direction(self):
        fwd = "jit(step_fn)/jvp()/shard_map/recis.embed.route/gather"
        bwd = "jit(step_fn)/transpose(jvp())/shard_map/recis.embed.route/scatter-add"
        inner = "jit(f)/recis.embed.route/recis.exchange.all_to_all/all_to_all"
        assert stage_of(fwd) == stage_of(bwd) == "recis.embed.route"
        assert not backward_of(fwd) and backward_of(bwd)
        assert stage_of(inner) == "recis.exchange.all_to_all"
        assert stage_of("jit(step_fn)/shard_map/add") is None

    def test_scope_reaches_the_compiled_program_in_both_directions(self):
        def loss(x, i):
            with stage("recis.blocks.gather"):
                return (x[i] * 2.0).sum()

        text = jax.jit(jax.grad(loss)).lower(jnp.ones(8), jnp.arange(3)).compile().as_text()
        seen = {(stage_of(n), backward_of(n)) for n in op_names(text).values()}
        assert {("recis.blocks.gather", False), ("recis.blocks.gather", True)} <= seen

    def test_op_names_fill_in_instructions_the_compiler_made(self):
        """A scatter the compiler rebuilt without metadata takes its fused
        computation's stage, and so does the fusion that calls it."""
        names = op_names(textwrap.dedent("""\
            %fused_computation.1 (p0: f32[8], p1: s32[2], p2: f32[2]) -> f32[8] {
              %p0 = f32[8]{0} parameter(0)
              %p1 = s32[2]{0} parameter(1)
              %p2 = f32[2]{0} parameter(2)
              %t = f32[2]{0} transpose(%p2), dimensions={0}, metadata={op_name="jit(f)/transpose(jvp())/recis.embed.route/mul"}
              ROOT %scatter.1 = f32[8]{0} scatter(%p0, %p1, %t), to_apply=%add
            }

            ENTRY %main (x: f32[8], i: s32[2], u: f32[2]) -> f32[8] {
              %x = f32[8]{0} parameter(0)
              %i = s32[2]{0} parameter(1)
              %u = f32[2]{0} parameter(2)
              %fusion.1 = f32[8]{0} fusion(%x, %i, %u), kind=kCustom, calls=%fused_computation.1
              ROOT %copy.1 = f32[8]{0} copy(%fusion.1)
            }
            """))
        assert stage_of(names["scatter.1"]) == stage_of(names["fusion.1"]) == "recis.embed.route"
        assert backward_of(names["fusion.1"])
        assert names["copy.1"] == ""  # outside any fusion: nothing to inherit


class TestSpanNamespace:
    """Spans and metrics share ONE namespace (DESIGN.md §9/§11): every
    span folds into a ``trace/<name>_s`` histogram, so span names are
    check_name-validated at span entry — not at step-record time."""

    def test_span_name_returns_histogram_name(self):
        assert obs.span_name("data_wait") == "trace/data_wait_s"
        assert obs.span_name("eval/val_loss") == "trace/eval/val_loss_s"

    def test_span_name_rejects_non_metric_names(self):
        for bad in ("Bad-Phase", "data wait", "_leading", "trailing/", ""):
            with pytest.raises(ValueError):
                obs.span_name(bad)

    def test_all_phases_are_valid_span_names(self):
        for phase in obs.PHASES:
            assert obs.span_name(phase) == f"trace/{phase}_s"

    def test_tracer_rejects_bad_span_at_entry(self):
        tr = obs.Tracer(obs.MetricsRegistry())
        with pytest.raises(ValueError, match="bad metric name"):
            with tr.span("Not A Phase"):
                pass  # pragma: no cover — span() raises before the body

    def test_null_tracer_still_validates(self):
        from repro.obs.tracing import NullTracer
        tr = NullTracer()
        with pytest.raises(ValueError):
            with tr.span("Bad-Name"):
                pass  # pragma: no cover
        with tr.span("data_wait"):   # valid names stay zero-cost
            pass

    def test_span_histogram_lands_in_trace_namespace(self):
        reg = obs.MetricsRegistry()
        tr = obs.Tracer(reg)
        with tr.span("pre_step"):
            pass
        assert reg.names() == ["trace/pre_step_s"]
        assert obs.NAME_RE.match("trace/pre_step_s")


# ---------------------------------------------------------------------------
# watchdog edge cases (satellite)
# ---------------------------------------------------------------------------

class TestWatchdogEdges:
    def test_warmup_boundary(self):
        wd = StragglerWatchdog(k=4.0, warmup=5)
        # an outlier INSIDE warmup never flags (baseline still priming)
        for i, dt in enumerate([0.1, 0.1, 5.0, 0.1, 0.1], start=1):
            assert not wd.observe(i, dt)
        # first post-warmup observation is judged against the EMA
        assert wd.observe(6, 50.0)
        assert len(wd.events) == 1

    def test_zero_variance_stream(self):
        wd = StragglerWatchdog(k=4.0, warmup=4)
        for i in range(20):
            assert not wd.observe(i, 0.1)   # identical steps: never flag
        assert wd.var < 1e-9
        # threshold floor is 5% of the mean, so 2× the constant flags
        assert wd.observe(21, 0.2)

    def test_baseline_freeze_on_anomaly(self):
        wd = StragglerWatchdog(k=4.0, warmup=4)
        for i in range(12):
            wd.observe(i, 0.1)
        mean_before = wd.mean
        assert wd.observe(13, 10.0)          # anomalous step…
        assert wd.mean == mean_before        # …does not move the baseline
        assert not wd.observe(14, 0.1)       # normal step still normal

    def test_ring_buffer_cap_and_dropped(self):
        wd = StragglerWatchdog(k=4.0, warmup=2, max_events=4)
        wd.observe(1, 0.1)
        wd.observe(2, 0.1)
        for s in range(3, 13):               # 10 stragglers
            assert wd.observe(s, 10.0)
        assert len(wd.events) == 4
        assert wd.dropped == 6
        assert wd.events[-1].step == 12      # newest kept

    def test_phase_attribution(self):
        wd = StragglerWatchdog(k=4.0, warmup=3)
        base = {"data_wait": 0.01, "device_step": 0.09}
        for i in range(10):
            wd.observe(i, 0.1, base)
        slow = {"data_wait": 0.91, "device_step": 0.09}
        assert wd.observe(11, 1.0, slow)
        assert wd.events[-1].phase == "data_wait"


# ---------------------------------------------------------------------------
# PreemptionGuard (satellite)
# ---------------------------------------------------------------------------

class TestPreemptionGuard:
    def test_handler_roundtrip(self):
        prev = signal.getsignal(signal.SIGUSR1)
        guard = PreemptionGuard(install=True, signals=(signal.SIGUSR1,))
        assert signal.getsignal(signal.SIGUSR1) == guard._handler
        os.kill(os.getpid(), signal.SIGUSR1)
        assert guard.requested
        guard.restore()
        assert signal.getsignal(signal.SIGUSR1) == prev
        guard.restore()  # idempotent
        assert signal.getsignal(signal.SIGUSR1) == prev

    def test_default_installs_sigterm_only(self):
        prev_term = signal.getsignal(signal.SIGTERM)
        prev_int = signal.getsignal(signal.SIGINT)
        guard = PreemptionGuard(install=True)
        assert signal.getsignal(signal.SIGTERM) == guard._handler
        assert signal.getsignal(signal.SIGINT) == prev_int  # untouched
        guard.restore()
        assert signal.getsignal(signal.SIGTERM) == prev_term


# ---------------------------------------------------------------------------
# Trainer loop: interval accumulation with a lightweight fake cell
# ---------------------------------------------------------------------------

class _FakeCell:
    returns_state = True
    donate_state = False

    @staticmethod
    def step_fn(state, batch):
        return state, {"loss": jnp.float32(1.0)}


class _CountingHooks:
    """Deterministic per-step hook metrics: 1 hit + 2 lookups per step
    pre-step, 1 admission demote per step post-step."""

    def pre_step(self, state, batch, step):
        return state, {"storage/hits": 1, "storage/lookups": 2,
                       "storage/hit_rate": 0.5, "storage/host_rows": step}

    def post_step(self, state, step):
        return state, {"storage/admission_demoted": 1}


class _MetricsCell:
    returns_state = True
    donate_state = False

    @staticmethod
    def step_fn(state, batch):
        w = state["w"] + 1.0
        return {"w": w}, {"loss": w * 0.5, "g/idmap_hits": (3 * w).astype(jnp.int32),
                          "rows": jnp.arange(3.0)}


class TestStepReadout:
    def test_scalar_metrics_leave_the_device_in_one_transfer(self, monkeypatch):
        real, pulls = jax.device_get, []

        def spy(tree):
            if isinstance(tree, dict):  # as each scalar was read one by one
                pulls.append({k: float(np.asarray(v)) for k, v in tree.items()})
            return real(tree)

        monkeypatch.setattr(jax, "device_get", spy)
        tr = Trainer(_MetricsCell(), TrainConfig(total_steps=3, log_every=1,
                                                 watchdog=False),
                     registry=obs.MetricsRegistry())
        res = tr.run({"w": jnp.zeros(())}, iter(range(3)))
        assert len(pulls) == 3  # one transfer per step
        for row, one_by_one in zip(res.metrics_history, pulls):
            assert {k: row[k] for k in one_by_one} == one_by_one
            assert "rows" not in row  # not a scalar
        assert [r["loss"] for r in res.metrics_history] == [0.5, 1.0, 1.5]
        assert [r["g/idmap_hits"] for r in res.metrics_history] == [3.0, 6.0, 9.0]


class TestIntervalAccumulation:
    def test_counts_cover_whole_interval(self):
        tr = Trainer(_FakeCell(), TrainConfig(total_steps=10, log_every=5,
                                              watchdog=False),
                     hooks=_CountingHooks(), registry=obs.MetricsRegistry())
        res = tr.run({"w": jnp.zeros(())}, iter(range(10)))
        assert res.steps_run == 10
        assert len(res.metrics_history) == 2
        for row in res.metrics_history:
            # counts are summed over the 5-step interval…
            assert row["storage/hits"] == 5
            assert row["storage/lookups"] == 10
            assert row["storage/admission_demoted"] == 5
            # …ratios recomputed over the interval, gauges keep last value
            assert row["storage/hit_rate"] == 0.5
        assert res.metrics_history[0]["storage/host_rows"] == 5
        assert res.metrics_history[1]["storage/host_rows"] == 10

    def test_log_every_one_matches_per_step(self):
        tr = Trainer(_FakeCell(), TrainConfig(total_steps=3, log_every=1,
                                              watchdog=False),
                     hooks=_CountingHooks(), registry=obs.MetricsRegistry())
        res = tr.run({"w": jnp.zeros(())}, iter(range(3)))
        for row in res.metrics_history:
            assert row["storage/hits"] == 1
            assert row["storage/hit_rate"] == 0.5


# ---------------------------------------------------------------------------
# acceptance: telemetry-enabled Trainer run emits a phase-attributed trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    from repro.configs.base import ShapeCell
    from repro.launch.cells import build_cell
    from repro.launch.common import CellOptions
    from repro.launch.mesh import make_mesh
    from repro.storage import StorageConfig

    tmp = tmp_path_factory.mktemp("obs")
    trace = tmp / "trace.jsonl"
    steps = 12
    reg = obs.MetricsRegistry()
    obs.set_registry(reg)  # engine-internal store binds the default registry
    try:
        shape = ShapeCell("train_batch", "train", {"batch": 32})
        cell = build_cell(
            "wide-deep", "train_batch", make_mesh(),
            CellOptions(remat=False, zero1=False,
                        storage=StorageConfig(policy="lru"),
                        storage_device_rows=512),
            smoke=True, shape_override=shape)
        tr = Trainer(cell, TrainConfig(total_steps=steps, log_every=4,
                                       ckpt_dir=str(tmp / "ckpt"),
                                       ckpt_every=6, watchdog=True,
                                       telemetry_path=str(trace)),
                     hooks=cell.storage_hooks, registry=reg)
        with cell.mesh:
            state = cell.init_state()
            res = tr.run(state, (cell.make_batch(s) for s in range(steps)))
    finally:
        obs.reset_default_registry()
    return res, obs.read_jsonl(trace), reg, steps


class TestTrainerTelemetryAcceptance:
    def test_every_step_has_phase_spans(self, telemetry_run):
        res, recs, reg, steps = telemetry_run
        assert res.steps_run == steps
        step_recs = [r for r in recs if r["type"] == "step"]
        assert [r["step"] for r in step_recs] == list(range(1, steps + 1))
        for r in step_recs:
            for phase in PHASES:
                assert phase in r["spans"], (r["step"], phase)
                assert r["spans"][phase] >= 0.0
            assert r["wall_s"] > 0
            assert "loss" in r["metrics"]

    def test_checkpoint_span_present(self, telemetry_run):
        _, recs, reg, _ = telemetry_run
        ck = [r for r in recs if r["type"] == "step"
              and "checkpoint" in r["spans"]]
        assert any(r["step"] == 6 for r in ck)   # periodic save at step 6
        assert reg.counter("ckpt/saves").value >= 1
        assert reg.counter("ckpt/bytes_written").value > 0

    def test_summary_record(self, telemetry_run):
        _, recs, _, steps = telemetry_run
        (summ,) = [r for r in recs if r["type"] == "summary"]
        assert summ["steps_run"] == steps
        assert summ["metrics"]["trainer/steps"] == steps
        assert summ["metrics"]["trace/device_step_s"]["count"] == steps

    def test_storage_counters_unified(self, telemetry_run):
        res, _, reg, _ = telemetry_run
        assert reg.counter("storage/lookups").value > 0
        assert reg.counter("storage/promoted").value > 0
        assert 0.0 < reg.gauge("storage/hit_rate").value <= 1.0
        assert reg.gauge("storage/host_rows").value > 0
        # history rows still carry the per-interval storage metrics
        assert all("storage/hit_rate" in m for m in res.metrics_history)

    def test_metric_name_lint(self, telemetry_run):
        """Every name registered by a full trainer+storage+ckpt run is
        stable snake_case with a subsystem prefix."""
        _, _, reg, _ = telemetry_run
        names = reg.names()
        assert names, "registry is empty"
        for n in names:
            assert obs.NAME_RE.match(n), n
        subsystems = {n.split("/")[0] for n in names}
        assert {"trainer", "trace", "storage", "ckpt"} <= subsystems


# ---------------------------------------------------------------------------
# loader + mbu land in the same namespace
# ---------------------------------------------------------------------------

class TestUnifiedNamespace:
    def test_loader_metrics(self, tmp_path):
        from repro.io.columnio import (
            AsyncLoader, BatchSpec, ColumnSchema, ColumnWriter,
        )
        reg = obs.MetricsRegistry()
        with ColumnWriter(tmp_path / "part-000.col",
                          [ColumnSchema("f")]) as w:
            w.write_group({"f": [[1, 2], [3], [4, 5, 6], [7]] * 4})
        loader = AsyncLoader(tmp_path, BatchSpec(4, {"f": 8}),
                             n_threads=1, registry=reg)
        batches = list(loader)
        assert batches
        assert reg.counter("io/row_groups_read").value == 1
        assert reg.counter("io/batches_assembled").value == len(batches)
        assert reg.counter("io/rows").value == 4 * len(batches)
        assert reg.histogram("io/read_group_s").count == 1
        for n in reg.names():
            assert obs.NAME_RE.match(n), n

    def test_mbu_bridge(self):
        import jax.numpy as jnp

        from repro.core import mbu
        reg = obs.MetricsRegistry()
        res = mbu.measure(mbu.t_mod(1024), lambda x: x % 97,
                          jnp.arange(1024), target="TPU v5 lite", iters=2,
                          warmup=1, registry=reg)
        flat = reg.flat()
        assert flat["mbu/mod/mbu"] == pytest.approx(res.mbu)
        assert flat["mbu/mod/achieved_gbps"] > 0
        obs.record_roofline("wide-deep", "train_batch", "cpu:1",
                            {"compute_s": 0.1, "bound": "memory"}, reg)
        assert reg.gauge(
            "roofline/wide_deep/train_batch/cpu_1/compute_s").value == 0.1
        for n in reg.names():
            assert obs.NAME_RE.match(n), n


# ---------------------------------------------------------------------------
# label support + per-shard storage series (DESIGN.md §9/§10)
# ---------------------------------------------------------------------------

class TestLabels:
    def test_label_appends_sorted_key_value_segments(self):
        assert obs.label("storage/hits", shard=3) == "storage/hits/shard3"
        # keys are sorted, so label order never forks the series name
        assert (obs.label("io/read_group_s", reader=1, part=2)
                == obs.label("io/read_group_s", part=2, reader=1)
                == "io/read_group_s/part2/reader1")

    def test_label_sanitizes_string_values(self):
        assert obs.label("trainer/steps", host="node-1") \
            == "trainer/steps/hostnode_1"

    def test_label_result_must_lint(self):
        with pytest.raises(ValueError):
            obs.label("storage/hits", **{"9bad": "x"})

    def test_labelled_instruments_are_plain_registry_entries(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("storage/hits", shard=2)
        c.inc(5)
        assert reg.get("storage/hits/shard2").value == 5.0
        assert reg.counter("storage/hits").value == 0.0  # distinct series
        for n in reg.names():
            assert obs.NAME_RE.match(n), n

    def test_tiered_store_emits_per_shard_counters(self):
        """The store's lookup/hit/promote traffic lands on per-shard
        ``storage/<k>/shard<d>`` series next to the aggregates, so a hot
        shard is visible as one counter pulling ahead of its peers."""
        from repro.core.embedding_engine import EmbeddingEngine, EngineConfig
        from repro.core.feature_engine import FeatureSpec
        from repro.io.ragged import Ragged
        from repro.storage import StorageConfig

        reg = obs.set_registry(obs.MetricsRegistry())
        try:
            specs = [FeatureSpec("f", transform="hash", emb_dim=4,
                                 pooling="sum")]
            eng = EmbeddingEngine(specs, EngineConfig(
                mesh_axes=(), n_devices=2, rows_per_shard=16,
                map_capacity_per_shard=128, u_budget=16, per_dest_cap=16,
                recv_budget=16, storage=StorageConfig(policy="lru")))
            state = eng.init_state()
            # same ids every step: step 0 is all misses, later steps all
            # hits — both series must appear on both shards
            ids = Ragged.from_lists([[7 * j + 1 for j in range(10)]],
                                    nnz_budget=16)
            for step in range(3):
                state, _ = eng.storage_prefetch(state, {"f": ids}, step)
            flat = reg.flat()
            shard_lookups = [flat.get(f"storage/lookups/shard{d}", 0.0)
                             for d in range(2)]
            # per-shard series exist, are non-trivial, and partition the
            # aggregate exactly (nothing double- or under-counted)
            assert all(v > 0 for v in shard_lookups)
            assert sum(shard_lookups) == flat["storage/lookups"]
            assert (flat["storage/hits/shard0"] + flat["storage/hits/shard1"]
                    == flat["storage/hits"])
        finally:
            obs.set_registry(obs.MetricsRegistry())
