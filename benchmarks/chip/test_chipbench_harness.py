"""CPU tests of the benchmark harness: files found by name, new files picked
up with no edit (a recommendation configuration, and a language model's),
the traffic generator, the FLOP count, the readers of stages and counters,
no TPU no result."""
import hashlib
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import registry  # noqa: E402
import stage_time  # noqa: E402
import traffic  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def test_every_workload_resolves_its_files_by_name():
    from repro.configs import get_config

    assert registry.Bench(ROOT).family("recsys").CONFIG_KEYS == (
        "global_batch", "rows_per_table_per_chip")
    bench = registry.Bench(ROOT)
    spec = bench.spec
    assert {c["name"] for c in spec["configs"]} == {w["config"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        cfg = bench.config(w["config"])
        # each family states what its configuration has to give: a
        # recommendation cell its global batch and its rows per table per chip
        family = bench.family(get_config(cfg["arch"]).family)
        assert cfg["arch"] and {"global_batch"} <= set(family.CONFIG_KEYS)
        assert all(cfg[k] > 0 for k in family.CONFIG_KEYS)
        mix = bench.traffic(w["traffic"])
        assert mix["check_steps"] < mix["ring_batches"]
        ref = bench.reference(w["config"])
        assert ref.forward_flops_per_example(cfg) > 0
        limits = {k: v for k, v in (bench.limits(w["name"]) or {}).items()
                  if k in check.NUMBERS}
        assert limits and all(v > 0 for v in limits.values())
        for trace in (False, True):
            for m in bench.metrics_of(w["name"], trace):
                assert callable(bench.metric(m["name"]).read)
    for m in spec["per_layer"] + spec["end_to_end"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def _tree_digest(path: pathlib.Path) -> str:
    h = hashlib.sha256()
    for p in sorted(path.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts and not p.name.startswith("."):
            h.update(str(p.relative_to(path)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def test_new_config_mix_and_metric_are_picked_up_with_no_edit(smoke_root):
    """A configuration, a traffic mix and a metric added as new files plus a
    workloads entry run, and no file that was there changes."""
    before = _tree_digest(BENCH), (ROOT / "BENCHMARK.json").read_bytes()
    bench = smoke_root / "benchmarks" / "chip"
    old = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "wide-deep-smoke.json").read_text())
    cfg["global_batch"] = 128
    (bench / "configs" / "tiny-wd.json").write_text(json.dumps(cfg))
    (bench / "reference" / "tiny-wd.py").write_bytes(
        (bench / "reference" / "wide-deep-smoke.py").read_bytes())
    mix = json.loads((bench / "traffic" / "train.zipf.json").read_text())
    mix.update(ids="uniform", ring_batches=5)
    (bench / "traffic" / "train.uniform.json").write_text(json.dumps(mix))
    (bench / "metrics" / "window_steps.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    spec = json.loads((smoke_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-wd", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmarks/chip/configs/tiny-wd.json"})
    spec["workloads"].append({"name": "tiny-wd.train.uniform", "config": "tiny-wd",
                              "traffic": "train.uniform", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                               "bound": 0.1, "source": "host_clock",
                               "workloads": ["tiny-wd.train.uniform"]})
    (smoke_root / "BENCHMARK.json").write_text(json.dumps(spec))

    out = subprocess.run(
        [sys.executable, str(bench / "faults.py"), "--fault", "none", "--no-chip", "--root", str(smoke_root),
         "--", "--workload", "tiny-wd.train.uniform", "--seed", "11", "--seconds", "1"],
        capture_output=True, text=True, env=_cpu_env(), timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["metrics"]["window_steps"]["value"] == res["attempted"] > 0
    assert {"train_examples_per_s", "step_s_p95", "setup_s"} <= set(res["metrics"])
    assert all(p.read_bytes() == b for p, b in old.items())
    assert (_tree_digest(BENCH), (ROOT / "BENCHMARK.json").read_bytes()) == before


def test_language_model_added_as_new_files_runs_through_the_harness(smoke_root):
    """A language model's configuration (the program's smoke-size
    moonshot-v1-16b-a3b), a token mix and a metric, added as new files in a
    checkout, run through ``Harness``: the ring, fresh state, the checked
    steps and a 2-step window, and the metrics that need no reference. No
    file that was there changes."""
    import dataclasses

    import jax

    import harness
    import run

    from repro.configs import get_config

    before = _tree_digest(BENCH), (ROOT / "BENCHMARK.json").read_bytes()
    bench = smoke_root / "benchmarks" / "chip"
    old = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    base = json.loads((bench / "configs" / "dlrm-mlperf.json").read_text())
    arch = get_config("moonshot-v1-16b-a3b", smoke=True)
    cfg = {"arch": arch.arch_id, "arch_smoke": True, "model": dataclasses.asdict(arch.model),
           "global_batch": 4, "seq_len": 32, "optimizer": base["optimizer"]}
    (bench / "configs" / "lm-smoke.json").write_text(json.dumps(cfg))
    mix = {"kind": "train", "ids": "zipf", "zipf_exponent": 1.0, "vocab": "model.vocab_size",
           "id_space": "vocab", "ring_batches": 4, "check_steps": 2}
    (bench / "traffic" / "train.tokens.json").write_text(json.dumps(mix))
    (bench / "metrics" / "window_steps.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    spec = json.loads((smoke_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "lm-smoke", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmarks/chip/configs/lm-smoke.json"})
    cell = "lm-smoke.train.tokens"
    spec["workloads"].append({"name": cell, "config": "lm-smoke", "traffic": "train.tokens",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                               "bound": 0.1, "source": "host_clock", "workloads": [cell]})
    (smoke_root / "BENCHMARK.json").write_text(json.dumps(spec))

    h = harness.Harness(smoke_root, cell, jax.devices())
    assert h.ref is None and h.groups == {"dim64": [("tokens", "tokens")]}
    ring = h.ring(2**31 + 9)
    values, splits = ring[0]["tokens"]
    assert values.shape == (4 * 32,) and splits.tolist() == list(range(0, 4 * 32 + 1, 32))
    assert 0 <= values.min() and values.max() < arch.model.vocab_size
    placed = h.place(ring[0])
    assert placed.shape == (4, 32)
    np.testing.assert_array_equal(np.asarray(placed).reshape(-1), values)
    state, dense0 = h.fresh_state(2**31 + 9)
    trainer = h.trainer()
    state, prog = h.checked_steps(trainer, state, dense0, ring)
    assert len(prog["losses"]) == 2 and all(np.isfinite(prog["losses"]))
    assert set(prog["grad_norms"]) == set(prog["change_norms"])
    assert prog["change_norms"]["sparse/dim64"] > 0
    w = run.window(h, trainer, state, ring, seconds=60, max_steps=2)
    assert w.res.steps_run == 2
    ctx = run.context(h, w, ring, setup_s=1.0, peak=None, device_kind="cpu")
    got = run.read_metrics(h, ctx, trace=False)
    assert {"train_examples_per_s", "step_s_p95", "setup_s", "window_steps"} <= set(got)
    assert got["window_steps"]["value"] == 2.0
    assert 0 < registry.Bench(smoke_root).metric("idmap_round_use_share").read(ctx) <= 100
    assert all(p.read_bytes() == b for p, b in old.items())
    assert (_tree_digest(BENCH), (ROOT / "BENCHMARK.json").read_bytes()) == before


def test_a_configuration_without_its_familys_numbers_is_refused(smoke_root):
    """A recommendation configuration that leaves out its rows per table
    per chip is refused before a cell is built."""
    import jax

    import harness

    path = smoke_root / "benchmarks" / "chip" / "configs" / "wide-deep-smoke.json"
    cfg = json.loads(path.read_text())
    del cfg["rows_per_table_per_chip"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="rows_per_table_per_chip"):
        harness.Harness(smoke_root, "wide-deep-smoke.train.zipf", jax.devices())


def test_an_engine_that_is_not_the_cells_is_refused(smoke_root):
    """A family whose engine would not make the cell's sparse state (here:
    a wide-deep engine with twice the rows) stops the harness, so that no
    count or budget is read from an engine the step does not run."""
    import jax

    import harness

    path = smoke_root / "benchmarks" / "chip" / "families" / "recsys.py"
    path.write_text(path.read_text() + textwrap.dedent("""
        def build(make):
            import dataclasses
            from repro.core.embedding_engine import EmbeddingEngine

            cell = make()
            e = cell.engine
            (g,) = e.groups.values()
            over = {d: dict(o, rows_per_shard=2 * g.rows_per_shard)
                    for d, o in e.cfg.overrides.items()}
            cfg = dataclasses.replace(e.cfg, overrides=over, rows_per_shard=2 * g.rows_per_shard)
            return cell, EmbeddingEngine(g.features, cfg)
        """))
    with pytest.raises(ValueError, match="the engine does not make the cell's state"):
        harness.Harness(smoke_root, "wide-deep-smoke.train.zipf", jax.devices())


def _columns(n_cols=6, batch=512, chips=1):
    cols = [{"name": f"c{i}", "values": batch, "splits": batch + chips, "ids": True,
             "table": f"t{i}"} for i in range(n_cols)]
    cols.append({"name": "label", "values": batch, "splits": batch + chips, "ids": False,
                 "table": None})
    cols.append({"name": "dense", "values": batch * 13, "splits": batch + chips,
                 "ids": False, "table": None})
    return cols


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**62 + 3])
def test_train_zipf_is_deterministic_and_bounded(seed):
    mix = registry.Bench(ROOT).traffic("train.zipf")
    cfg = {"rows_per_table_per_chip": 300}
    a = traffic.make_ring(mix, cfg, 2, _columns(chips=2), seed)
    b = traffic.make_ring(mix, cfg, 2, _columns(chips=2), seed)
    c = traffic.make_ring(mix, cfg, 2, _columns(chips=2), seed + 1)
    assert len(a) == mix["ring_batches"]
    for x, y in zip(a, b):
        for k in ("c0", "label", "dense"):
            np.testing.assert_array_equal(x[k][0], y[k][0])
    assert not np.array_equal(a[0]["c0"][0], c[0]["c0"][0])
    ranks = np.concatenate([r for batch in a for r in batch["_ranks"].values()])
    assert ranks.min() >= 1 and ranks.max() <= 600  # vocab = 300 rows x 2 chips
    assert (a[0]["c0"][0] >= 0).all() and a[0]["c0"][0].dtype == np.int64
    assert a[0]["c0"][1].tolist() == list(range(257)) * 2
    # distinct ids: at most the vocabulary, one table per column
    assert traffic.unique_per_group(a[0], {"g": [("c0", "t0")]})["g"] <= 600


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**62 + 3])
def test_fixed_ids_give_every_seed_the_same_ids_in_another_order(seed):
    mix = registry.Bench(ROOT).traffic("train.zipf.fixed_ids")
    cfg = {"rows_per_table_per_chip": 300}
    cols = _columns(chips=2)
    ids = [c["name"] for c in cols if c["ids"]]
    a = traffic.make_ring(mix, cfg, 2, cols, seed)
    b = traffic.make_ring(mix, cfg, 2, cols, seed)
    c = traffic.make_ring(mix, cfg, 2, cols, seed + 1)
    groups = {"g": [(name, f"t{i}") for i, name in enumerate(ids)]}
    for x, y, z in zip(a, b, c):
        for k in ids + ["label", "dense"]:
            np.testing.assert_array_equal(x[k][0], y[k][0])
        # the same rows under both seeds, each keeping its ids together
        rows_x = np.stack([x[k][0] for k in ids], axis=1)
        rows_z = np.stack([z[k][0] for k in ids], axis=1)
        np.testing.assert_array_equal(rows_x[np.lexsort(rows_x.T)], rows_z[np.lexsort(rows_z.T)])
        assert x["c0"][1].tolist() == z["c0"][1].tolist()
        assert traffic.unique_per_group(x, groups) == traffic.unique_per_group(z, groups)
    # in another order, with the other columns drawn from the run's seed
    assert not np.array_equal(a[0]["c0"][0], c[0]["c0"][0])
    assert not np.array_equal(a[0]["dense"][0], c[0]["dense"][0])
    assert not np.array_equal(a[0]["c0"][0], a[1]["c0"][0])


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_token_ids_are_a_seeded_permutation_of_the_vocabulary(seed):
    mix = {"ids": "zipf", "zipf_exponent": 1.0, "vocab": "model.vocab_size",
           "id_space": "vocab", "ring_batches": 2}
    cfg = {"model": {"vocab_size": 1000}}
    cols = [{"name": "tokens", "values": 8 * 64, "splits": 8 + 2, "ids": True,
             "table": "tokens"}]
    a = traffic.make_ring(mix, cfg, 2, cols, seed)
    b = traffic.make_ring(mix, cfg, 2, cols, seed)
    c = traffic.make_ring(mix, cfg, 2, cols, seed + 1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"][0], y["tokens"][0])
    tokens, splits = a[0]["tokens"]
    assert tokens.dtype == np.int64 and 0 <= tokens.min() and tokens.max() < 1000
    assert splits.tolist() == list(range(0, 257, 64)) * 2
    # one token per rank: the most frequent token is rank 1's, and a seed moves it
    assert (tokens == traffic.vocab_ids(np.array([1]), seed, "tokens", 1000)[0]).sum() == \
        (a[0]["_ranks"]["tokens"] == 1).sum()
    assert not np.array_equal(tokens, c[0]["tokens"][0])


def _ctx(stage_s=None, steps=4, history=()):
    class Ctx:
        pass

    ctx = Ctx()
    ctx.trace = None if stage_s is None else {"stage_s": stage_s}
    ctx.steps, ctx.history, ctx.budgets = steps, list(history), {"dim128": {}}
    return ctx


def test_stage_readers_sum_their_stages_both_ways_per_step():
    bench = registry.Bench(ROOT)
    stage_s = {"recis.idmap.claim": {"forward": 2.0, "backward": 0.0},
               "recis.embed.route": {"forward": 0.1, "backward": 0.3},
               "recis.exchange.bucket": {"forward": 0.4, "backward": 0.0},
               "": {"forward": 5.0, "backward": 0.0}}
    ctx = _ctx(stage_s)
    assert bench.metric("idmap_claim_ms_per_step").read(ctx) == 500.0
    assert bench.metric("exchange_ms_per_step").read(ctx) == pytest.approx(200.0)
    # a stage the trace does not name reads nothing, never 0
    assert bench.metric("row_ops_ms_per_step").read(ctx) is None
    assert bench.metric("idmap_probe_ms_per_step").read(_ctx(None)) is None
    assert stage_time.ms_per_step(_ctx(stage_s, steps=0), ("recis.idmap.claim",)) is None


def test_round_use_share_reads_the_step_counters():
    share = registry.Bench(ROOT).metric("idmap_round_use_share")
    steps = [{"dim128/idmap_probe_depth": 3.0, "dim128/idmap_claim_depth": 4.0,
              "dim128/idmap_probe_rounds": 4.0, "dim128/idmap_claim_rounds": 4.0},
             {"dim128/idmap_probe_depth": 0.0, "dim128/idmap_claim_depth": 0.0,
              "dim128/idmap_probe_rounds": 4.0, "dim128/idmap_claim_rounds": 0.0}]
    assert share.read(_ctx(history=steps)) == pytest.approx(100.0 * 7 / 12)
    assert share.read(_ctx(history=[{"dim128/idmap_probe_rounds": 0.0}])) is None


def test_flop_count_is_three_forward_passes():
    bench = registry.Bench(ROOT)
    dlrm = bench.reference("dlrm-mlperf")
    fwd = dlrm.forward_flops_per_example(bench.config("dlrm-mlperf"))
    assert 3 * fwd == 14_750_976  # 14.75 MFLOP a trained dlrm example
    wd = bench.reference("wide-deep")
    wide_deep = {"model": {"n_sparse": 40, "embed_dim": 32, "wide_dim": 8,
                           "mlp": [1024, 512, 256]}}
    assert 3 * wd.forward_flops_per_example(wide_deep) == 11_798_064


def test_step_p95_is_nearest_rank():
    p95 = registry.Bench(ROOT).metric("step_s_p95")

    class Ctx:
        intervals = list(range(1, 21))

    assert p95.read(Ctx) == 19
    Ctx.intervals = [3.0, 1.0, 2.0]
    assert p95.read(Ctx) == 3.0


def test_smoke_configuration_is_refused_on_a_tpu(smoke_root):
    import harness

    class Tpu:
        platform = "tpu"

    with pytest.raises(ValueError, match="smoke-size"):
        harness.Harness(smoke_root, "dlrm-mlperf-smoke.train.zipf", [Tpu()])


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "dlrm-mlperf.train.zipf.4chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_cpu_env(), timeout=120, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "TPU" in out.stderr
