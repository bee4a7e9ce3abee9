"""CPU tests of the trace reduction, on a small trace recorded on one TPU
v5e chip by ``testdata/record_trace.py``: a sort in the program's stage
``recis.exchange.bucket``, a scatter-add that is the backward of a gather in
``recis.embed.route``, and a matmul in no stage."""
import pathlib
import sys
import textwrap

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import registry  # noqa: E402

trace = registry.load(BENCH, "trace")
DATA = BENCH / "testdata"


def _summary():
    hlo = (DATA / "small.hlo.txt").read_text()
    return trace.reduce(DATA / "small.xplane.pb", trace.fusion_roots(hlo),
                        stages=(trace.module_name(hlo), trace.stage_map(hlo)))


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


def test_time_goes_to_the_stage_of_each_op():
    s = _summary()
    st = s["stage_s"]
    assert set(st) == {"", "recis.exchange.bucket", "recis.embed.route"}
    # the sort runs in its stage going forward, the scatter-add in the
    # gather's stage coming back
    assert st["recis.exchange.bucket"]["backward"] == 0
    assert st["recis.exchange.bucket"]["forward"] >= s["kind_s"]["sort"] > 0
    assert st["recis.embed.route"]["backward"] >= s["kind_s"]["scatter"] > 0
    # the matmul, in no stage, is most of the step
    assert st[""]["forward"] > st["recis.embed.route"]["backward"]
    # the stages and the time no stage names add up to the busy time
    total = sum(v for dirs in st.values() for v in dirs.values())
    assert total == pytest.approx(s["busy_s"], rel=1e-9)


def test_each_instruction_of_the_recorded_step_reads_its_stage():
    """The attribution the stage metrics rest on, pinned on the recorded
    step: the sort in its stage, the scatter-add fusion and the scatter it
    calls as the gather's backward, and the matmul's loop in none."""
    m = trace.stage_map((DATA / "small.hlo.txt").read_text())
    assert m["sort.11"] == ("recis.exchange.bucket", "forward")
    assert m["fusion"] == m["scatter.3"] == ("recis.embed.route", "backward")
    assert m["fusion.52"] == ("recis.embed.route", "forward")
    assert m["while.15"] == m["tuple.65"] == ("", "forward")
    assert sum(s != "" for s, _ in m.values()) == 32


def test_instructions_the_compiler_made_take_their_fusions_stage():
    """A scatter rebuilt without metadata takes its fused computation's
    stage, and so does the fusion that calls it; an instruction outside any
    fusion has nothing to take."""
    m = trace.stage_map(textwrap.dedent("""\
        %fused_computation.1 (p0: f32[8], p1: s32[2], p2: f32[2]) -> f32[8] {
          %p0 = f32[8]{0} parameter(0)
          %p1 = s32[2]{0} parameter(1)
          %p2 = f32[2]{0} parameter(2)
          %t = f32[2]{0} transpose(%p2), dimensions={0}, metadata={op_name="jit(f)/transpose(jvp())/recis.embed.route/mul"}
          ROOT %scatter.1 = f32[8]{0} scatter(%p0, %p1, %t), to_apply=%add
        }

        ENTRY %main (x: f32[8], i: s32[2], u: f32[2]) -> f32[8] {
          %x = f32[8]{0} parameter(0)
          %i = s32[2]{0} parameter(1)
          %u = f32[2]{0} parameter(2)
          %fusion.1 = f32[8]{0} fusion(%x, %i, %u), kind=kCustom, calls=%fused_computation.1
          %sort.1 = f32[8]{0} sort(%fusion.1), dimensions={0}, metadata={op_name="jit(f)/recis.embed.route/recis.exchange.bucket/sort"}
          ROOT %copy.1 = f32[8]{0} copy(%sort.1)
        }
        """))
    assert m["scatter.1"] == m["fusion.1"] == ("recis.embed.route", "backward")
    assert m["sort.1"] == ("recis.exchange.bucket", "forward")  # the innermost scope
    assert m["copy.1"] == ("", "forward")


def test_ops_of_other_modules_take_no_stage():
    hlo = (DATA / "small.hlo.txt").read_text()
    s = trace.reduce(DATA / "small.xplane.pb", trace.fusion_roots(hlo),
                     stages=("jit_some_other_step", trace.stage_map(hlo)))
    assert set(s["stage_s"]) == {""}
    assert s["stage_s"][""]["forward"] == pytest.approx(s["busy_s"], rel=1e-9)
    assert trace.module_name(hlo) == "jit_step"


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
