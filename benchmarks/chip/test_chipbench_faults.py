"""CPU tests that the comparison deciding ``correct`` catches what it must,
at smoke size: the control (the reference one precision step down) fails
the limits and the program passes them, and a run driven through the
harness with a fault planted in the program's timed path reads ``correct``
false; for the recommendation models and for a language model (the
program's smoke-size qwen2.5-3b against ``testdata/qwen2.5-3b.py``). The
smoke cells' limits are set by the full cells' rule from smoke-size
readings on the CPU (``SMOKE_LIMITS``)."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import calibrate  # noqa: E402
import check  # noqa: E402
import conftest  # noqa: E402
import harness  # noqa: E402

# per smoke cell: the largest program reading over twelve seeds
# (2**31 + 10 ... 2**31 + 21) on the CPU, the smallest control reading over
# them, and the limit set between the two (lower^(1/3) upper^(2/3), rounded
# down). At batch 256 the control reads 2.1-2.5x the program on ``row_gap``
# and 1.4-3.8x on the step-1 numbers; the smoke cells show the machinery,
# the chip cell's limits are set by the rule from chip readings. The
# language model's 256 tokens a step (4 x 64): the control reads 3.5x the
# program on ``row_gap`` and 10x on the step-1 numbers.
SMOKE_LIMITS = {
    "wide-deep-smoke.train.zipf": {                 # program / control
        "row_gap": 0.2,                             # 0.114 / 0.288
        "sign_flip_share": 0.045},                  # 0.0235 / 0.0622
    "dlrm-mlperf-smoke.train.zipf": {
        "row_gap": 0.33,                            # 0.209 / 0.477
        "moment_gap": 0.18,                         # 0.0883 / 0.260
        "sign_flip_share": 0.06},                   # 0.0248 / 0.0946
    "dlrm-mlperf-smoke.train.zipf.4chip": {
        "row_gap": 0.35,                            # 0.228 / 0.490
        "moment_gap": 0.19,                         # 0.0925 / 0.279
        "sign_flip_share": 0.062},                  # 0.0259 / 0.0975
    conftest.LM_CELL: {
        "row_gap": 0.22,                            # 0.0980 / 0.341
        "moment_gap": 0.043,                        # 0.00940 / 0.0944
        "sign_flip_share": 0.018},                  # 0.00388 / 0.0399
}


def _limits(root, cell):
    (root / "benchmarks/chip/limits" / f"{cell}.json").write_text(
        json.dumps(SMOKE_LIMITS[cell]))


@pytest.mark.parametrize("cell", ["wide-deep-smoke.train.zipf",
                                  "dlrm-mlperf-smoke.train.zipf", conftest.LM_CELL])
def test_control_fails_and_program_passes(smoke_root, cell):
    if cell == conftest.LM_CELL:
        conftest.add_lm_cell(smoke_root)
    _limits(smoke_root, cell)
    h = harness.Harness(smoke_root, cell, jax.devices())
    trainer = h.trainer()
    for seed in (2**31 + 1, 2**31 + 2, 2**31 + 3):
        r = calibrate.readings(h, seed, trainer)
        limits = h.bench.limits(cell)
        ok, _ = check.judge(r["program"], limits)
        assert ok, r["program"]
        for reading in ("control", "half_batch", "altered_id"):
            bad, _ = check.judge(r[reading], limits)
            assert not bad, (reading, r[reading])


def _argv(root, cell, fault, seconds="1"):
    return [sys.executable, str(root / "benchmarks/chip/faults.py"), "--fault", fault,
            "--no-chip", "--root", str(root), "--", "--workload", cell, "--seed",
            str(2**31 + 5), "--seconds", seconds]


def _env(devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _result(returncode, stdout, stderr):
    assert returncode == 0, stderr[-3000:]
    return json.loads(stdout.splitlines()[-1])


def _run(root, cell, fault, devices=1):
    out = subprocess.run(_argv(root, cell, fault), capture_output=True, text=True,
                         env=_env(devices), timeout=300)
    return _result(out.returncode, out.stdout, out.stderr)


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch", "altered_id"])
def test_planted_fault_reads_not_correct(smoke_root, fault):
    cell = "dlrm-mlperf-smoke.train.zipf"
    _limits(smoke_root, cell)
    res = _run(smoke_root, cell, fault)
    assert res["correct"] is (fault == "none"), res["check"]
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_exchange_left_out_reads_not_correct(smoke_root, fault):
    cell = "dlrm-mlperf-smoke.train.zipf.4chip"
    _limits(smoke_root, cell)
    res = _run(smoke_root, cell, fault, devices=4)
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault == "none"), res["check"]


def test_language_model_faults_read_not_correct(tmp_path):
    """``faults.py --no-chip`` on the language model's smoke cell, each
    fault in a checkout of its own, the four runs at once: nothing planted
    reads ``correct``; a step that returns its state unchanged, the loss of
    the first half of each sequence, and token ids altered where the step
    looks their rows up each read not ``correct``. The window is empty
    (``--seconds 0``): the checked steps go through the window's own
    ``Trainer.run``, and no run waits on the clock."""
    faults = ("none", "state_unchanged", "half_batch", "altered_id")
    procs = {}
    for fault in faults:
        root = conftest.make_root(tmp_path / fault)
        conftest.add_lm_cell(root)
        _limits(root, conftest.LM_CELL)
        procs[fault] = subprocess.Popen(_argv(root, conftest.LM_CELL, fault, seconds="0"),
                                        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                                        text=True, env=_env())
    try:
        outs = {fault: p.communicate(timeout=300) for fault, p in procs.items()}
    finally:
        for p in procs.values():
            p.kill()
            p.wait()
    for fault in faults:
        res = _result(procs[fault].returncode, *outs[fault])
        assert res["correct"] is (fault == "none"), (fault, res["check"])
        assert list(res)[-1] == "check"
