"""Serving example: batched retrieval scoring (deliverable (b), serving
side) — one user context scored against a large candidate set, the
`retrieval_cand` cell shape of the recsys archs (paper §3.2: RecIS serves
the same engine state it trains; SafeTensors checkpoints are "used for
delivery to the online inference service").

Flow: train a few steps (train cell) → checkpoint → restore into a SERVE
cell (train=False fetch: missing ids read as zeros, no inserts) → score
batches of candidates and report a latency histogram.

Run:  PYTHONPATH=src python examples/serve_retrieval.py
"""
import tempfile
import time

import jax
import numpy as np

from repro.checkpoint import saver
from repro.configs.base import ShapeCell
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions

OPTS = CellOptions(remat=False, zero1=False)


def mesh1():
    from repro.launch.mesh import make_mesh

    return make_mesh()


def main():
    workdir = tempfile.mkdtemp(prefix="recis_serve_")
    mesh = mesh1()

    # --- 1) train briefly, checkpoint the state
    tshape = ShapeCell("train_batch", "train", {"batch": 64})
    tcell = build_cell("wide-deep", "train_batch", mesh, OPTS, smoke=True,
                       shape_override=tshape)
    with mesh:
        state = tcell.init_state()
        step = jax.jit(tcell.step_fn)
        for s in range(20):
            state, out = step(state, tcell.make_batch(s))
    print(f"trained 20 steps, loss={float(out['loss']):.4f}")
    saver.save(jax.tree.map(np.asarray, state), workdir, step=20)

    # --- 2) build the retrieval serve cell, restore the trained sparse state
    rshape = ShapeCell("retrieval_cand", "retrieval",
                       {"batch": 1, "n_candidates": 4096})
    rcell = build_cell("wide-deep", "retrieval_cand", mesh, OPTS, smoke=True,
                       shape_override=rshape)
    with mesh:
        rstate = rcell.init_state()
        # dense params come from the checkpoint; the TRAINED embedding rows
        # are ported into the serve cell's engines through the portable
        # export/import form (re-hash-sharded for the serve cell's budgets).
        # Ids never trained still read as zero embeddings (graceful
        # degradation), but trained items now carry real scores.
        ckpt = saver.restore(workdir, {"step": np.int64(0),
                                       "dense": jax.tree.map(np.asarray, rstate["dense"])},
                             step=20)
        rstate["dense"] = jax.tree.map(jax.numpy.asarray, ckpt["dense"])
        rows = tcell.engine.export_rows(state["sparse"])
        rstate["sparse_user"] = rcell.engine_user.import_rows(rows)
        rstate["sparse_cand"] = rcell.engine_cand.import_rows(rows)

        serve = jax.jit(rcell.step_fn)
        lat = []
        for s in range(12):
            batch = rcell.make_batch(100 + s)
            t0 = time.perf_counter()
            out = serve(rstate, batch)
            jax.block_until_ready(out["scores"])
            lat.append(time.perf_counter() - t0)
        scores = np.asarray(out["scores"]).reshape(-1)

    lat_ms = np.array(lat[2:]) * 1e3  # drop warmup
    print(f"scored {scores.shape[0]} candidates/request")
    print(f"latency p50={np.percentile(lat_ms, 50):.2f}ms "
          f"p99={np.percentile(lat_ms, 99):.2f}ms over {len(lat_ms)} requests")
    top = np.argsort(scores)[-5:][::-1]
    print("top-5 candidates:", top.tolist())
    assert np.isfinite(scores).all()
    # trained candidate embeddings must differentiate the scores (this
    # assertion caught the shared-table salt bug — see EXPERIMENTS.md
    # §Robustness #4)
    assert np.unique(scores).size > 100, "scores are degenerate"


if __name__ == "__main__":
    main()
