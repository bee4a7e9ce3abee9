"""Records the trace that the reduction's test reads: three steps of a
small jitted program with a sort, a scatter-add and a matmul, under the
harness's window and phase annotations, on one TPU chip. The sort runs in
the program's stage ``recis.exchange.bucket``; the scatter-add is the
backward of a gather in ``recis.embed.route``; the matmul is in no stage.
It writes ``small.xplane.pb`` and ``small.hlo.txt`` (the program's
optimized HLO, which names what each fusion is rooted in and each
instruction's stage) into OUT (default: beside this file).

    python3 benchmarks/chip/testdata/record_trace.py [OUT]
"""
import os
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent


def main() -> None:
    out = pathlib.Path(sys.argv[1]) if len(sys.argv) > 1 else HERE
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(HERE.parent))
    sys.path.insert(0, str(HERE.parents[2] / "src"))
    import registry

    from repro.obs.stages import stage

    trace = registry.load(HERE.parent, "trace")
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("records a TPU trace")

    def route(t, idx, x):
        with stage("recis.embed.route"):
            return jnp.sum(t[idx] * x)

    @jax.jit
    def step(x, idx):
        with stage("recis.exchange.bucket"):
            s = jnp.sort(x[:, 0])
        t = jax.grad(route)(jnp.zeros((4096, 128), jnp.float32), idx, x)  # a scatter-add
        return s, t, x @ x.T

    x = jax.random.normal(jax.random.PRNGKey(0), (8192, 128))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8192,), 0, 4096)
    jax.block_until_ready(step(x, idx))
    hlo = step.lower(x, idx).compile().as_text()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("repro/data_wait"):
                y = jax.block_until_ready(x + 1.0)
            with jax.profiler.TraceAnnotation("repro/device_step"):
                jax.block_until_ready(step(y, idx))
    jax.profiler.stop_trace()
    found = sorted(pathlib.Path(tmp).glob("**/*.xplane.pb"))
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(found[-1], out / "small.xplane.pb")
    (out / "small.hlo.txt").write_text(hlo)
    shutil.rmtree(tmp)
    print((out / "small.xplane.pb").stat().st_size)


if __name__ == "__main__":
    main()
