"""CPU tests that the comparison deciding ``correct`` catches what it must,
at smoke size: the control (the reference one precision step down) fails
the limits and the program passes them, and a run driven through the
harness with a fault planted in the program's timed path reads ``correct``
false. The smoke cells' limits are set by the full cells' rule from
smoke-size readings on the CPU (``SMOKE_LIMITS``)."""
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
import harness  # noqa: E402

# per smoke cell: the largest program reading over twelve seeds
# (2**31 + 10 ... 2**31 + 21) on the CPU, the smallest control reading over
# them, and the limit set between the two (lower^(1/3) upper^(2/3), rounded
# down). At batch 256 the control reads 2.1-2.5x the program on ``row_gap``
# and 1.4-3.8x on the step-1 numbers; the smoke cells show the machinery,
# the chip cell's limits are set by the rule from chip readings.
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
}


def _limits(root, cell):
    (root / "benchmarks/chip/limits" / f"{cell}.json").write_text(
        json.dumps(SMOKE_LIMITS[cell]))


@pytest.mark.parametrize("cell", ["wide-deep-smoke.train.zipf",
                                  "dlrm-mlperf-smoke.train.zipf"])
def test_control_fails_and_program_passes(smoke_root, cell):
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


def _run(root, cell, fault, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run(
        [sys.executable, str(root / "benchmarks/chip/faults.py"), "--fault", fault,
         "--no-chip", "--root", str(root), "--", "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


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
