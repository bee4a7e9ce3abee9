"""Entry points on the CPU backend: ``repro.launch.train`` on its default
mesh, ``make_mesh``, the chip-peak table and chip_smoke.py's refusal to run
without a TPU."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import ShapeCell
from repro.launch import train
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.pipelines import TrainConfig, Trainer
from repro.roofline import analysis as ra

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_train_main_runs_smoke_steps_on_default_mesh(capsys):
    """jax.make_mesh's default Explicit axes made the dense backward raise
    ShardingTypeError here; the default mesh must train."""
    assert train.main(["--arch", "dlrm-mlperf", "--steps", "2", "--batch",
                       "32", "--log-every", "1"]) == 0
    out = capsys.readouterr().out
    assert "ran 2 steps" in out
    assert "'compiles': 0.0" in out  # step 2 reuses step 1's program


def test_published_config_takes_the_chip_row_share():
    args = train.parser().parse_args(
        ["--arch", "dlrm-mlperf", "--config", "published",
         "--table-rows", "256", "--batch", "16", "--steps", "1",
         "--log-every", "1"])
    res, _, cell = train.run(args)
    g = cell.engine.groups["dim128"]
    assert g.rows_per_shard == 26 * 256 * 3 // 2
    assert g.map_capacity_per_shard == 2 * g.rows_per_shard
    assert cell.arch.model.top_mlp == (1024, 1024, 512, 256, 1)
    assert np.isfinite(res.metrics_history[-1]["loss"])


def test_bad_flag_combination_exits_2():
    args = train.parser().parse_args(["--arch", "wide-deep", "--autoscale"])
    with pytest.raises(SystemExit) as e:
        train.run(args)
    assert e.value.code == 2


def test_no_step_after_the_first_compiles():
    shape = ShapeCell("train_batch", "train", {"batch": 16})
    cell = build_cell("wide-deep", "train_batch", make_mesh(),
                      CellOptions(remat=False, zero1=False), smoke=True,
                      shape_override=shape)
    state = cell.init()
    assert all(leaf.sharding == s for leaf, s in zip(
        jax.tree.leaves(state), jax.tree.leaves(cell.shardings()[0])))
    tr = Trainer(cell, TrainConfig(total_steps=4, log_every=1, watchdog=False))
    res = tr.run(state, (cell.make_batch(s) for s in range(4)))
    assert [m["compiles"] for m in res.metrics_history[1:]] == [0, 0, 0]
    assert res.metrics_history[0]["compiles"] >= 1


@pytest.mark.parametrize("axes", [("data",), ("data", "model")])
def test_make_mesh_axes_are_auto(axes):
    shape = (1,) * len(axes)
    mesh = make_mesh(shape, axes)
    assert mesh.axis_names == axes
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}


def test_production_mesh_uses_make_mesh():
    devs = np.array([jax.devices()[0]] * 256)  # shape only; never run
    mesh = make_production_mesh(devices=devs)
    assert dict(mesh.shape) == {"data": 16, "model": 16}
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}


def test_chip_peaks_known_and_unknown():
    p = ra.chip_peaks("TPU v5 lite")
    assert (p.flops, p.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="cpu"):
        ra.chip_peaks("cpu")


def test_roofline_reads_its_target_peaks():
    r = ra.Roofline(flops=197e12, hbm_bytes=819e9 / 2, coll_bytes=0.0,
                    chips=1, target="TPU v5 lite")
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(0.5)
    assert r.bound == "compute"
    with pytest.raises(KeyError):
        ra.Roofline(1.0, 1.0, 0.0, chips=1, target="TPU v9").compute_s


def test_chip_smoke_refuses_cpu_and_prints_no_verdict():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("kind, mflop", [("train", 14.75), ("serve", 4.917)])
def test_dlrm_model_flops_per_example(kind, mflop):
    """A forward pass at 2 FLOPs per MAC (bottom MLP, dot interaction, top
    MLP), three of them to train: 14.75 MFLOP per example."""
    from repro.configs import get_config

    flops = ra.model_flops(get_config("dlrm-mlperf"), ShapeCell("x", kind, {"batch": 1}))
    assert flops / 1e6 == pytest.approx(mflop, rel=1e-4)
