"""CPU tests of the trace reduction, on a small trace recorded on one TPU
v5e chip by ``testdata/record_trace.py``."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import registry  # noqa: E402

trace = registry.load(BENCH, "trace")
DATA = BENCH / "testdata"


def test_small_chip_trace_reduces():
    roots = trace.fusion_roots((DATA / "small.hlo.txt").read_text())
    s = trace.reduce(DATA / "small.xplane.pb", roots)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    # each op's own time is counted once: the kinds add up to the busy time
    assert sum(s["kind_s"].values()) == pytest.approx(s["busy_s"], rel=1e-6)
    assert s["kind_s"].get("sort", 0) > 0 and s["kind_s"].get("scatter", 0) > 0
    assert all(name in ("data_wait", "device_step", "host") for name, _ in s["idle_gaps"])
    assert s["idle_gaps"] == sorted(s["idle_gaps"], key=lambda g: -g[1])
    assert len(s["device_ops"]) <= 10


def test_fusions_of_unknown_root_count_as_other():
    s = trace.reduce(DATA / "small.xplane.pb", {})
    assert s is not None
    assert set(s["kind_s"]) <= {"sort", "scatter", "gather", "all-to-all", "other"}


@pytest.mark.parametrize("text,name,op", [
    ("%while.4 = (u32[]{:T(128)}, s32[8]{0}) while((u32[], s32[8]) %tuple.1), "
     "condition=%c, body=%b", "while.4", "while"),
    ("%fusion.3 = u32[64]{0:T(1024)} fusion(u32[512]{0} %p, s32[64]{0} %i), "
     "kind=kCustom, calls=%fused_computation.9.clone", "fusion.3", "fusion"),
    ("%sort.2 = f32[16]{0} sort(f32[16]{0} %x), dimensions={0}", "sort.2", "sort"),
    ("%all-to-all.1 = f32[4,8]{1,0} all-to-all(f32[4,8]{1,0} %s), dimensions={0}",
     "all-to-all.1", "all-to-all"),
])
def test_opcode_of_an_hlo_instruction(text, name, op):
    assert trace.opcode(text) == (name, op)


def test_fusion_kind_comes_from_its_root():
    hlo = """
%fused_computation.9.clone (p0: u32[512], p1: s32[64], p2: u32[64]) -> u32[512] {
  %p0 = u32[512]{0} parameter(0)
  %p1 = s32[64]{0} parameter(1)
  %p2 = u32[64]{0} parameter(2)
  %scatter.1 = u32[512]{0} scatter(u32[512]{0} %p0, s32[64]{0} %p1, u32[64]{0} %p2)
  ROOT %bitcast.2 = u32[512]{0} bitcast(u32[512]{0} %scatter.1)
}
"""
    roots = trace.fusion_roots(hlo)
    assert roots == {"fused_computation.9.clone": "scatter"}
    text = ("%fusion.3 = u32[512]{0} fusion(u32[512]{0} %a, s32[64]{0} %b, u32[64]{0} %c), "
            "kind=kCustom, calls=%fused_computation.9.clone")
    assert trace.op_kind(text, roots) == "scatter"
    assert trace.op_kind(text, {}) == "other"


def test_exclusive_time_gives_nested_ops_their_own():
    own = trace._exclusive([(0, 10, "outer"), (2, 5, "inner"), (5, 5, "empty"),
                            (12, 14, "next")])
    assert own == [7, 3, 0, 2]
