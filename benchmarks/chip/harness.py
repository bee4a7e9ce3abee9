"""One cell of the benchmark, built through the program's own path.

``Harness`` holds what a run and the calibration share: the cell
(``repro.launch.cells.build_cell`` with ``CellOptions(chip_table_rows=...)``),
the traffic ring of a seed, fresh state with the towers drawn from the seed,
the Trainer, the checked first steps, and the reference's replay of them.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent


def set_env(root: pathlib.Path) -> None:
    """Caches and logs inside the checkout; call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(pathlib.Path(root) / "benchmarks/chip/.jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"


def use_cache() -> None:
    """Cache every program, so that only a cell's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _check_model(arch, cfg: dict) -> None:
    have = {k: list(v) if isinstance(v, tuple) else v
            for k, v in dataclasses.asdict(arch.model).items() if k != "vocab_per_feature"}
    if have != cfg["model"]:
        raise ValueError(f"config {cfg['model']} is not the program's {arch.arch_id}: {have}")


def _check_optimizer(cfg: dict) -> None:
    from repro.optim.adamw import AdamWConfig
    from repro.optim.sparse_adam import SparseAdamConfig

    for side, prog in (("dense", AdamWConfig()), ("sparse", SparseAdamConfig())):
        stated = {k: v for k, v in cfg["optimizer"][side].items() if k not in ("kind", "lr")}
        got = {k: getattr(prog, k) for k in stated}
        if got != stated or (side == "sparse" and prog.weight_decay != 0.0):
            raise ValueError(f"{side} optimizer {dataclasses.asdict(prog)} is not the "
                             f"configuration's {cfg['optimizer'][side]}")


def peak_bytes(devices) -> int | None:
    """The largest ``peak_bytes_in_use`` so far over ``devices``."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) for s in stats) or None


class Harness:
    def __init__(self, root, workload: str, devices):
        import jax

        root = pathlib.Path(root)
        sys.path.insert(0, str(BENCH))
        sys.path.insert(0, str(root / "src"))
        import registry

        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        from repro.launch import cells
        from repro.launch.common import CellOptions
        from repro.launch.mesh import make_mesh
        from repro.launch.recsys_cell import _model_mod

        self.bench = registry.Bench(root)
        self.workload = workload
        wl = self.bench.workload(workload)
        self.chips = int(wl["chips"])
        self.cfg = cfg = self.bench.config(wl["config"])
        self.mix = self.bench.traffic(wl["traffic"])
        self.ref = self.bench.reference(wl["config"])
        self.n_check = int(self.mix["check_steps"])

        arch_id, smoke = cfg["arch"], bool(cfg.get("arch_smoke", False))
        if smoke and devices[0].platform == "tpu":
            raise ValueError(f"{wl['config']} is a smoke-size configuration, for CPU tests only")
        _check_model(get_config(arch_id, smoke=smoke), cfg)
        _check_optimizer(cfg)
        self.arch = get_config(arch_id, smoke=smoke)
        self.devices = devices[:self.chips]
        self.mesh = make_mesh(devices=self.devices)
        self.batch = int(cfg["global_batch"])
        shape = ShapeCell("train_batch", "train", {"batch": self.batch})
        opts = CellOptions(remat=False, zero1=False,
                           chip_table_rows=int(cfg["rows_per_table_per_chip"]),
                           sparse_opt_lr=cfg["optimizer"]["sparse"]["lr"],
                           dense_opt_lr=cfg["optimizer"]["dense"]["lr"])
        self.cell = cells.build_cell(arch_id, shape.name, self.mesh, opts, smoke=smoke,
                                     shape_override=shape)
        self.model = _model_mod(arch_id)
        self.state_sh, self.batch_sh = self.cell.shardings()
        tables, self.groups = {}, {}
        for g, spec in self.cell.engine.groups.items():
            self.groups[g] = [(f.name, f.table_key()) for f in spec.features]
            tables.update({f.name: f.table_key() for f in spec.features})
        self.dims = {g: spec.dim for g, spec in self.cell.engine.groups.items()}
        self.columns = [{"name": n, "values": r.values.shape[0],
                         "splits": r.row_splits.shape[0], "ids": n in tables,
                         "table": tables.get(n)}
                        for n, r in self.cell.batch_specs.items()]
        self._init_dense = jax.jit(lambda k: self.model.init(k, self.arch.model),
                                   out_shardings=self.state_sh["dense"])

    # ------------------------------------------------------------- inputs
    def ring(self, seed: int) -> list[dict]:
        import traffic

        return traffic.make_ring(self.mix, self.cfg, self.chips, self.columns, seed)

    def place(self, host: dict):
        import jax
        from repro.io.ragged import Ragged

        return jax.device_put({n: Ragged(*host[n]) for n in self.batch_sh}, self.batch_sh)

    def batches(self, ring, start, deadline=None, max_steps=None, pulls=None):
        """Batches of the ring from ``start``, placed as they are pulled,
        until ``deadline`` or ``max_steps``; each pull's time goes to
        ``pulls``."""
        i = start
        while True:
            now = time.perf_counter()
            if pulls is not None:
                pulls.append(now)
            if (deadline is not None and now >= deadline) or \
                    (max_steps is not None and i - start >= max_steps):
                return
            yield self.place(ring[i % len(ring)])
            i += 1

    # -------------------------------------------------------------- state
    def fresh_state(self, seed: int):
        """``Cell.init()`` with the towers drawn from the seed; and a host
        copy of the towers."""
        import jax
        import numpy as np

        import check

        state = self.cell.init()
        state["dense"] = self._init_dense(jax.random.PRNGKey(seed))
        dense0 = {k: np.asarray(v, np.float64)
                  for k, v in check.flat(jax.device_get(state["dense"])).items()}
        return state, dense0

    def trainer(self, profile: bool = False):
        from repro import obs
        from repro.pipelines import TrainConfig, Trainer

        return Trainer(self.cell, TrainConfig(total_steps=1 << 40, log_every=1, resume=False,
                                              profile_spans=profile),
                       registry=obs.MetricsRegistry())

    def checked_steps(self, trainer, state, dense0: dict, ring):
        """Train the ring's first ``check_steps`` batches; return the state
        and the program's readings."""
        import jax
        import numpy as np

        import check

        b1 = self.cfg["optimizer"]["dense"]["b1"]
        res = trainer.run(state, self.batches(ring, 0, max_steps=1))
        losses = [h["loss"] for h in res.metrics_history]
        prog = {"peak_bytes_step1": peak_bytes(self.devices),
                "grad_norms": check.grad_norms(res.state, b1),
                "m1": check.moments(res.state, self.mesh)}
        res = trainer.run(res.state, self.batches(ring, 1, max_steps=self.n_check - 1),
                          start_step=1)
        prog["losses"] = losses + [h["loss"] for h in res.metrics_history]
        prog["change_norms"] = check.change_norms(res.state, dense0, self.ref.common.init_rows,
                                                  self.mesh)
        prog["rows"] = check.live_rows(res.state, self.mesh)
        prog["dense"] = {k: np.asarray(v) for k, v in
                         check.flat(jax.device_get(res.state["dense"])).items()}
        return res.state, prog

    def numbers(self, prog: dict, ref: dict) -> dict:
        import check

        return check.numbers(prog, ref, self.ref.common.init_rows_host)

    def reference(self, seed: int, ring, quantize=None, fault=None) -> dict:
        host = [{k: v for k, v in b.items() if not k.startswith("_")}
                for b in ring[:self.n_check]]
        return self.ref.common.run(self.ref, self.cfg, seed, host, self.chips,
                                   quantize=quantize or self.ref.common.identity, fault=fault)

    @staticmethod
    def free(state) -> None:
        import jax

        for leaf in jax.tree.leaves(state):
            leaf.delete()
