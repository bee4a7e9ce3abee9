"""CPU tests of the benchmark harness: files found by name, new files picked
up with no edit, the traffic generator, the FLOP count, no TPU no result."""
import hashlib
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import registry  # noqa: E402
import traffic  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def test_every_workload_resolves_its_files_by_name():
    bench = registry.Bench(ROOT)
    spec = bench.spec
    assert {c["name"] for c in spec["configs"]} == {w["config"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        cfg = bench.config(w["config"])
        assert cfg["arch"] and cfg["rows_per_table_per_chip"] > 0
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
