"""Fixtures of the benchmark's CPU tests: a checkout in a temporary
directory that holds smoke-size cells of the two configurations."""
import json
import pathlib
import shutil

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]

SMOKE_MODELS = {
    "wide-deep": {"n_sparse": 8, "embed_dim": 8, "wide_dim": 8, "mlp": [32, 16]},
    "dlrm-mlperf": {"n_dense": 13, "n_sparse": 26, "embed_dim": 16,
                    "bot_mlp": [32, 16], "top_mlp": [64, 32, 1]},
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout with the benchmark as committed plus, per model, a smoke
    cell ``<config>-smoke.train.zipf`` (one chip) and for dlrm-mlperf
    ``dlrm-mlperf-smoke.train.zipf.4chip``: the program's smoke widths
    (``arch_smoke``), 512 rows per table per chip, batch 256, dlrm-mlperf's
    precision and optimizers, and the full cell's limits where it has one."""
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".jax_cache", ".traces", "__pycache__", "testdata"))
    (tmp / "src").symlink_to(ROOT / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    full = {w["config"]: w["name"] for w in spec["workloads"]}
    base = json.loads((bench / "configs" / "dlrm-mlperf.json").read_text())
    for config, model in SMOKE_MODELS.items():
        name = f"{config}-smoke"
        cfg = dict(base, arch=config, arch_smoke=True, model=model,
                   rows_per_table_per_chip=512, global_batch=256)
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        shutil.copy(bench / "reference" / f"{config}.py", bench / "reference" / f"{name}.py")
        spec["configs"].append({"name": name, "source": "smoke", "reduced": [], "why": "smoke",
                                "file": f"benchmarks/chip/configs/{name}.json"})
        cells = [(f"{name}.train.zipf", 1)]
        if config == "dlrm-mlperf":
            cells.append((f"{name}.train.zipf.4chip", 4))
        for cell, chips in cells:
            spec["workloads"].append({"name": cell, "config": name, "traffic": "train.zipf",
                                      "chips": chips, "why": "smoke"})
            limits = bench / "limits" / f"{full.get(config)}.json"
            if config in full and limits.exists():
                shutil.copy(limits, bench / "limits" / f"{cell}.json")
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return tmp


@pytest.fixture
def smoke_root(tmp_path):
    return make_root(tmp_path)


LM_CELL = "qwen2.5-smoke.train.tokens"


def add_lm_cell(root: pathlib.Path) -> str:
    """Add to a checkout ``qwen2.5-smoke.train.tokens``: the program's
    smoke-size qwen2.5-3b (2 layers, 64 wide, GQA 4 over 2 heads, qkv bias,
    a 512-token vocabulary) at 4 sequences of 64 tokens, dlrm-mlperf's
    optimizers, its plain reference (``testdata/qwen2.5-3b.py``) and a token
    mix of Zipf(1.0) ids over the vocabulary, 2 checked steps."""
    import dataclasses

    from repro.configs import get_config

    bench = root / "benchmarks" / "chip"
    arch = get_config("qwen2.5-3b", smoke=True)
    base = json.loads((bench / "configs" / "dlrm-mlperf.json").read_text())
    cfg = {"arch": arch.arch_id, "arch_smoke": True,
           "model": json.loads(json.dumps(dataclasses.asdict(arch.model))),
           "global_batch": 4, "seq_len": 64, "precision": base["precision"],
           "optimizer": base["optimizer"]}
    (bench / "configs" / "qwen2.5-smoke.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "testdata" / "qwen2.5-3b.py", bench / "reference" / "qwen2.5-smoke.py")
    mix = {"kind": "train", "ids": "zipf", "zipf_exponent": 1.0, "vocab": "model.vocab_size",
           "id_space": "vocab", "ring_batches": 4, "check_steps": 2}
    (bench / "traffic" / "train.tokens.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "qwen2.5-smoke", "source": "smoke", "reduced": [],
                            "why": "smoke", "file": "benchmarks/chip/configs/qwen2.5-smoke.json"})
    spec["workloads"].append({"name": LM_CELL, "config": "qwen2.5-smoke",
                              "traffic": "train.tokens", "chips": 1, "why": "smoke"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return LM_CELL
