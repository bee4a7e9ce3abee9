"""Compiles for one chip of a described TPU v5e topology — no chip needed.

The TPU compiler is installed beside the CPU backend, so the kernels and
the dlrm-mlperf train step compile here for a v5e that is described, not
attached: what the chip's compiler refuses (a Mosaic kernel that does not
legalize, a program that does not fit HBM) fails here at no chip time.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ShapeCell
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.segment_reduce import ops as sr_ops
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions
from repro.launch.mesh import make_mesh
from repro.obs.stages import STAGES, backward_of, op_names, stage_of
from repro.pipelines import TrainConfig, Trainer
from repro.roofline.analysis import chip_peaks

# the one-chip run chip_smoke.py makes: published widths, the published
# train_batch, 131072 rows of each of the 26 tables
TABLE_ROWS = 131_072
BATCH = 65_536


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_described_chip_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert chip_peaks("TPU v5 lite").hbm_bytes == 16 * 2**30


def test_segment_reduce_compiles_at_dlrm_pooling_shape(one_chip):
    """dlrm's --use-pallas sum pooling: one id per row, batch rows, dim 128,
    with the program's x64 on."""
    assert jax.config.jax_enable_x64
    n, d = BATCH, 128

    def pool(v, s):
        return sr_ops.segment_sum(v, s, n, interpret=False)

    compiled = jax.jit(jax.value_and_grad(lambda v, s: pool(v, s).sum())).lower(
        _sds((n, d), jnp.float32, one_chip), _sds((n,), jnp.int32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("t", [1024, 4096])
def test_flash_attention_compiles_fwd_and_bwd(one_chip, t):
    assert jax.config.jax_enable_x64
    q = _sds((1, t, 8, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return fa_ops.flash_attention(q, k, v, True, False).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dlrm_published_train_step_fits_one_chip(topo):
    """The train step chip_smoke.py runs, as the Trainer jits it, on one
    described chip: it compiles, and state + temporaries fit its HBM."""
    mesh = make_mesh(devices=topo.devices[:1])
    shape = ShapeCell("train_batch", "train", {"batch": BATCH})
    cell = build_cell("dlrm-mlperf", "train_batch", mesh,
                      CellOptions(remat=False, zero1=False,
                                  chip_table_rows=TABLE_ROWS),
                      shape_override=shape)
    m = cell.arch.model
    assert (m.n_dense, m.n_sparse, m.embed_dim) == (13, 26, 128)
    assert m.bot_mlp == (512, 256, 128) and m.top_mlp == (1024, 1024, 512, 256, 1)
    rows = cell.engine.groups["dim128"].rows_per_shard
    assert rows == 26 * TABLE_ROWS * 3 // 2

    mem = Trainer(cell, TrainConfig(watchdog=False)).compiled().memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > rows * 128 * 4 * 3  # emb, m, v
    assert held < chip_peaks("TPU v5 lite").hbm_bytes, held


_SPARSE_OPS = ("sort", "scatter", "gather", "all-to-all")


def _sparse_ops(hlo_text: str) -> set[str]:
    """Every sort, scatter, gather and all-to-all instruction, and every
    fusion that holds one (at any depth)."""
    comps, body = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"\s*(?:ENTRY\s+)?%(\S+) .*\{\s*$", line)
        if head and " = " not in line:
            body = comps.setdefault(head.group(1), [])
            continue
        name = re.match(r"\s*(?:ROOT\s+)?%([^\s=]+) = ", line)
        if body is None or name is None:
            continue
        op = re.search(r"\s([a-z][a-z0-9-]*)\(%", line)
        called = re.search(r"calls=%([\w.\-]+)", line)
        body.append((name.group(1), op and op.group(1), called and called.group(1)))

    def holds(comp):
        return any(op in _SPARSE_OPS or (op == "fusion" and holds(c))
                   for _, op, c in comps.get(comp, []))

    return {n for body in comps.values() for n, op, c in body
            if op in _SPARSE_OPS or (op == "fusion" and holds(c))}


def test_every_sparse_op_of_the_sharded_step_names_one_stage(topo):
    """The dlrm train step on the four described chips, at a size that
    compiles in well under a minute: each sort, scatter, gather and
    all-to-all, and each fusion holding one, names exactly one stage in its
    op_name; every stage appears; route and the all-to-all appear in both
    directions."""
    assert len(topo.devices) == 4
    mesh = make_mesh(devices=topo.devices)
    shape = ShapeCell("train_batch", "train", {"batch": 512})
    cell = build_cell("dlrm-mlperf", "train_batch", mesh,
                      CellOptions(remat=False, zero1=False, chip_table_rows=1024),
                      shape_override=shape)
    text = Trainer(cell, TrainConfig(watchdog=False)).compiled().as_text()
    names = op_names(text)
    ops = _sparse_ops(text)
    assert len(ops) > 100
    named = {n: set(re.findall(r"recis(?:\.\w+)+", names[n])) for n in ops}
    assert {n: s for n, s in named.items() if len(s) != 1 or not s <= set(STAGES)} == {}
    assert {stage_of(n) for n in names.values()} >= set(STAGES)
    both = {(stage_of(names[n]), backward_of(names[n])) for n in ops}
    for s in ("recis.embed.route", "recis.exchange.all_to_all"):
        assert {(s, False), (s, True)} <= both
