"""Per-architecture smoke tests (deliverable (f)): every assigned arch, a
REDUCED same-family config, one forward/train step on CPU, asserting output
shapes and no NaNs. Full configs are exercised only via the dry-run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeCell
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions
from repro.launch.mesh import make_mesh

OPTS = CellOptions(remat=False, zero1=False)


def _mesh():
    return make_mesh()


def _smoke_shape(arch_id: str, kind: str) -> ShapeCell:
    fam = get_config(arch_id).family
    if fam == "lm":
        if kind == "train":
            return ShapeCell("train_4k", "train", {"seq_len": 64, "global_batch": 4})
        if kind == "prefill":
            return ShapeCell("prefill_32k", "prefill", {"seq_len": 64, "global_batch": 2})
        return ShapeCell("decode_32k", "decode", {"seq_len": 128, "global_batch": 4})
    if fam == "recsys":
        if kind == "train":
            return ShapeCell("train_batch", "train", {"batch": 32})
        if kind == "retrieval":
            return ShapeCell("retrieval_cand", "retrieval",
                             {"batch": 1, "n_candidates": 64})
        return ShapeCell("serve_p99", "serve", {"batch": 32})
    # gnn
    if kind == "full_graph":
        return ShapeCell("full_graph_sm", "full_graph",
                         {"n_nodes": 64, "n_edges": 256, "d_feat": 24, "n_classes": 5})
    if kind == "minibatch":
        return ShapeCell("minibatch_lg", "minibatch",
                         {"n_nodes": 1000, "n_edges": 4000, "batch_nodes": 8,
                          "fanout": (3, 2), "d_feat": 12, "n_classes": 4})
    return ShapeCell("molecule", "graph_batch",
                     {"n_nodes": 10, "n_edges": 20, "batch": 8,
                      "d_feat": 16, "n_classes": 2})


def _no_nans(tree):
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            assert not bool(jnp.isnan(leaf).any()), "NaN in output"


def _run_cell(arch_id: str, kind: str, steps: int = 2):
    mesh = _mesh()
    shape = _smoke_shape(arch_id, kind)
    cell = build_cell(arch_id, shape.name, mesh, OPTS, smoke=True,
                      shape_override=shape)
    with mesh:
        state = cell.init_state()
        step = jax.jit(cell.step_fn)
        out = None
        for s in range(steps):
            if cell.returns_state:
                state, out = step(state, cell.make_batch(s))
            else:
                out = step(state, cell.make_batch(s))
        return state, out


LM_ARCHS = [a for a in ARCH_IDS if get_config(a).family == "lm"]
RECSYS_ARCHS = [a for a in ARCH_IDS if get_config(a).family == "recsys"]
GNN_ARCHS = [a for a in ARCH_IDS if get_config(a).family == "gnn"]


@pytest.mark.parametrize("arch_id", LM_ARCHS)
def test_lm_train_step(arch_id):
    state, out = _run_cell(arch_id, "train")
    assert float(out["loss"]) > 0
    _no_nans(out)
    _no_nans(state["dense"])


@pytest.mark.parametrize("arch_id", LM_ARCHS)
def test_lm_decode_step(arch_id):
    state, out = _run_cell(arch_id, "decode")
    vocab = get_config(arch_id, smoke=True).model.vocab_size
    assert out["logits"].shape[-1] == vocab
    _no_nans(out)


@pytest.mark.parametrize("arch_id", ["qwen2.5-3b"])
def test_lm_prefill_step(arch_id):
    _, out = _run_cell(arch_id, "prefill", steps=1)
    assert "logits" in out and "cache_k" in out
    _no_nans(out)


@pytest.mark.parametrize("arch_id", RECSYS_ARCHS)
def test_recsys_train_step(arch_id):
    state, out = _run_cell(arch_id, "train")
    assert 0 < float(out["loss"]) < 10
    _no_nans(out)


@pytest.mark.parametrize("arch_id", RECSYS_ARCHS)
def test_recsys_serve_step(arch_id):
    _, out = _run_cell(arch_id, "serve", steps=1)
    assert out["logits"].shape[0] == 32
    _no_nans(out)


@pytest.mark.parametrize("arch_id", RECSYS_ARCHS)
def test_recsys_retrieval_step(arch_id):
    _, out = _run_cell(arch_id, "retrieval", steps=1)
    assert out["scores"].shape[-1] >= 64  # padded to mesh multiple
    _no_nans(out)


@pytest.mark.parametrize("kind", ["full_graph", "minibatch", "graph_batch"])
def test_gin_train_step(kind):
    state, out = _run_cell("gin-tu", kind)
    assert float(out["loss"]) > 0
    _no_nans(out)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_matches_assignment(arch_id):
    """The FULL (non-smoke) config carries the exact published numbers."""
    arch = get_config(arch_id)
    m = arch.model
    expect = {
        "moonshot-v1-16b-a3b": dict(n_layers=48, d_model=2048, n_heads=16,
                                    vocab_size=163840),
        "qwen2-moe-a2.7b": dict(n_layers=24, d_model=2048, n_heads=16,
                                vocab_size=151936),
        "granite-20b": dict(n_layers=52, d_model=6144, n_heads=48,
                            n_kv_heads=1, d_ff=24576, vocab_size=49152),
        "internlm2-20b": dict(n_layers=48, d_model=6144, n_heads=48,
                              n_kv_heads=8, d_ff=16384, vocab_size=92544),
        "qwen2.5-3b": dict(n_layers=36, d_model=2048, n_heads=16,
                           n_kv_heads=2, d_ff=11008, vocab_size=151936),
        "gin-tu": dict(n_layers=5, d_hidden=64),
        "mind": dict(embed_dim=64, n_interests=4, capsule_iters=3),
        "sasrec": dict(embed_dim=50, n_blocks=2, n_heads=1, seq_len=50),
        "dlrm-mlperf": dict(n_dense=13, n_sparse=26, embed_dim=128),
        "wide-deep": dict(n_sparse=40, embed_dim=32),
    }[arch_id]
    for k, v in expect.items():
        assert getattr(m, k) == v, (arch_id, k, getattr(m, k), v)
    # MoE extras
    if arch_id == "moonshot-v1-16b-a3b":
        assert m.moe.n_experts == 64 and m.moe.top_k == 6
    if arch_id == "qwen2-moe-a2.7b":
        assert m.moe.n_experts == 60 and m.moe.top_k == 4 and m.moe.n_shared == 4
