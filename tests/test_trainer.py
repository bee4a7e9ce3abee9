"""Pipelines tests: trainer loop, watchdog, checkpoint/resume equivalence,
preemption, eviction windows, online-window pipeline, multitask loss."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeCell
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions
from repro.launch.mesh import make_mesh
from repro.pipelines import (
    OnlineWindowPipeline, StragglerWatchdog, TrainConfig, Trainer, multitask_loss,
)


def _mesh():
    return make_mesh()


def _cell(batch=32):
    shape = ShapeCell("train_batch", "train", {"batch": batch})
    return build_cell("wide-deep", "train_batch", _mesh(),
                      CellOptions(remat=False, zero1=False),
                      smoke=True, shape_override=shape)


class TestWatchdog:
    def test_flags_outlier_only(self):
        wd = StragglerWatchdog(k=4.0, warmup=4)
        for i in range(20):
            assert not wd.observe(i, 0.1 + 0.001 * (i % 3))
        assert wd.observe(21, 2.0)          # 20× the EMA → straggler
        assert not wd.observe(22, 0.1)      # baseline not poisoned
        assert len(wd.events) == 1


class TestTrainer:
    def test_loss_decreases(self):
        cell = _cell()
        tr = Trainer(cell, TrainConfig(total_steps=60, ckpt_dir=None,
                                       log_every=1, watchdog=False))
        with cell.mesh:
            state = cell.init_state()
            res = tr.run(state, (cell.make_batch(0) for _ in range(60)))
        losses = [m["loss"] for m in res.metrics_history]
        assert res.steps_run == 60
        assert np.mean(losses[-10:]) < np.mean(losses[:10])  # same batch → must fit

    def test_checkpoint_resume_bitwise(self, tmp_path):
        """Run 6 steps straight vs 3 + resume + 3 — identical final loss."""
        def run(ckpt, steps, resume):
            cell = _cell()
            tr = Trainer(cell, TrainConfig(total_steps=steps, ckpt_dir=str(ckpt),
                                           ckpt_every=3, resume=resume,
                                           log_every=1, watchdog=False))
            with cell.mesh:
                state = cell.init_state()
                state, start, _ = tr.try_resume(state)
                res = tr.run(state, (cell.make_batch(s) for s in range(start, steps)),
                             start_step=start)
            return res

        straight = run(tmp_path / "a", 6, resume=False)
        run(tmp_path / "b", 3, resume=False)
        resumed = run(tmp_path / "b", 6, resume=True)
        assert resumed.resumed_from == 3
        np.testing.assert_allclose(
            straight.metrics_history[-1]["loss"],
            resumed.metrics_history[-1]["loss"], rtol=1e-5)

    def test_serve_cell_no_state(self):
        shape = ShapeCell("serve_p99", "serve", {"batch": 16})
        cell = build_cell("wide-deep", "serve_p99", _mesh(),
                          CellOptions(remat=False, zero1=False),
                          smoke=True, shape_override=shape)
        tr = Trainer(cell, TrainConfig(total_steps=3, watchdog=False, log_every=1))
        with cell.mesh:
            state = cell.init_state()
            res = tr.run(state, (cell.make_batch(s) for s in range(3)))
        assert res.steps_run == 3


class TestOnlineWindows:
    def test_windowed_training_with_eviction(self, tmp_path):
        cell = _cell()
        evict_calls = []

        def evict_fn(state, older_than):
            evict_calls.append(older_than)
            return state

        tr = Trainer(cell, TrainConfig(total_steps=0, watchdog=False,
                                       log_every=1, evict_age_steps=5),
                     evict_fn=evict_fn)
        with cell.mesh:
            state = cell.init_state()
            pipe = OnlineWindowPipeline(
                tr, make_window_iter=lambda w: (cell.make_batch(100 * w + i)
                                                for i in range(10)),
                steps_per_window=10)
            state, results = pipe.run(state, n_windows=3)
        assert len(results) == 3
        assert len(evict_calls) == 3


def test_multitask_loss():
    total, per = multitask_loss(
        {"ctr": jnp.float32(1.0), "cvr": jnp.float32(2.0)}, {"cvr": 0.5})
    assert float(total) == 2.0
    assert set(per) == {"loss_ctr", "loss_cvr"}
