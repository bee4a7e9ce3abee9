"""reclint tests (DESIGN.md §11): per-family fixture snippets (true
positive, true negative, suppression), baseline round-trip, CLI exit
codes — and the acceptance run: the analyzer is clean on the live tree."""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (
    Finding, all_rules, load_baseline, run_lint, write_baseline,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


def lint_snippet(tmp_path, source, name="mod.py", rules=None, baseline=None):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return run_lint([tmp_path], rules=rules, baseline_path=baseline,
                    root=tmp_path)


def rule_ids(result):
    return sorted({f.rule for f in result.findings})


# ---------------------------------------------------------------------------
# P — JAX purity
# ---------------------------------------------------------------------------

class TestPurity:
    def test_global_mutation_under_jit_flags(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import jax
            _calls = 0

            @jax.jit
            def step(x):
                global _calls
                _calls += 1
                return x + 1
        """)
        assert "P001" in rule_ids(res)

    def test_print_under_partial_jit_flags(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import functools, jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def step(x, n):
                print("tracing", n)
                return x * n
        """)
        assert "P002" in rule_ids(res)

    def test_branch_on_traced_param_flags(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import jax

            @jax.jit
            def relu_bad(x):
                if x > 0:
                    return x
                return 0.0
        """)
        assert "P003" in rule_ids(res)

    def test_static_and_shape_branches_pass(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import functools, jax
            import jax.numpy as jnp

            @functools.partial(jax.jit, static_argnames=("mode",))
            def f(x, mode):
                if mode:                 # static argname: fine
                    x = x * 2
                if x.ndim == 2:          # shape metadata: fine
                    x = x.sum(-1)
                if x.shape[0] > 4:       # shape metadata: fine
                    x = x[:4]
                return jnp.where(x > 0, x, 0.0)   # traced branch done right
        """)
        assert res.findings == []

    def test_shard_map_and_pallas_closures_are_traced(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import functools
            from jax.experimental import pallas as pl
            from jax import shard_map

            def outer(mesh, x):
                def body(x_loc):
                    print(x_loc)         # side effect under trace
                    return x_loc
                return shard_map(body, mesh=mesh, in_specs=None,
                                 out_specs=None)(x)

            def _kernel(x_ref, o_ref, *, causal):
                if causal:               # partial-bound python bool: fine
                    o_ref[...] = x_ref[...]

            def launch(x):
                return pl.pallas_call(
                    functools.partial(_kernel, causal=True),
                    out_shape=x)(x)
        """)
        assert rule_ids(res) == ["P002"]

    def test_suppression_comment(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                print(x)  # reclint: disable=P002
                return x
        """)
        assert res.findings == []


# ---------------------------------------------------------------------------
# K — Pallas kernel contracts
# ---------------------------------------------------------------------------

GOOD_REF = """
def op(x, y, scale=1.0):
    return x + y * scale
"""

GOOD_OPS = """
def op(x, y, scale=1.0, interpret=None):
    return x + y * scale
"""


class TestKernelContracts:
    def write_pkg(self, tmp_path, ref, ops):
        pkg = tmp_path / "mykernel"
        pkg.mkdir(exist_ok=True)
        (pkg / "ref.py").write_text(textwrap.dedent(ref))
        (pkg / "ops.py").write_text(textwrap.dedent(ops))
        return run_lint([tmp_path], rules=["K001"], root=tmp_path)

    def test_matching_signatures_pass(self, tmp_path):
        res = self.write_pkg(tmp_path, GOOD_REF, GOOD_OPS)
        assert res.findings == []

    def test_missing_counterpart_flags(self, tmp_path):
        res = self.write_pkg(tmp_path, GOOD_REF, "def other(x):\n    return x\n")
        assert rule_ids(res) == ["K001"]

    def test_param_and_default_drift_flags(self, tmp_path):
        renamed = self.write_pkg(
            tmp_path, GOOD_REF, "def op(x, z, scale=1.0):\n    return x\n")
        assert rule_ids(renamed) == ["K001"]
        drifted = self.write_pkg(
            tmp_path, GOOD_REF, "def op(x, y, scale=2.0):\n    return x\n")
        assert rule_ids(drifted) == ["K001"]
        no_default = self.write_pkg(
            tmp_path, GOOD_REF,
            "def op(x, y, scale=1.0, *, interpret):\n    return x\n")
        assert rule_ids(no_default) == ["K001"]

    def test_grid_division_needs_guard(self, tmp_path):
        res = lint_snippet(tmp_path, """
            from jax.experimental import pallas as pl

            def launch(x, t):
                n = x.shape[0]
                return pl.pallas_call(_k, grid=(n // t,), out_shape=x)(x)
        """, rules=["K002"])
        assert rule_ids(res) == ["K002"]

    def test_grid_division_with_assert_passes(self, tmp_path):
        res = lint_snippet(tmp_path, """
            from jax.experimental import pallas as pl

            def launch(x, t):
                n = x.shape[0]
                assert n % t == 0
                grid = (n // t,)
                return pl.pallas_call(_k, grid=grid, out_shape=x)(x)

            def launch_padded(x, t):
                n = _round_up(x.shape[0], t)
                return pl.pallas_call(_k, grid=(n // t,), out_shape=x)(x)
        """, rules=["K002"])
        assert res.findings == []

    def test_blockspec_literal_alignment(self, tmp_path):
        res = lint_snippet(tmp_path, """
            from jax.experimental import pallas as pl

            bad = pl.BlockSpec((7, 96), lambda i: (i, 0))
            good = pl.BlockSpec((8, 128), lambda i: (i, 0))
            row = pl.BlockSpec((1, 1), lambda i: (i, 0))
        """, rules=["K003"])
        assert len(res.findings) == 2          # 7 (sublane) and 96 (lane)
        assert rule_ids(res) == ["K003"]

    def test_live_kernel_packages_hold_the_contract(self):
        res = run_lint([REPO / "src" / "repro" / "kernels"],
                       rules=["K001", "K002", "K003"], root=REPO)
        assert res.findings == []


# ---------------------------------------------------------------------------
# T — thread-safety
# ---------------------------------------------------------------------------

THREADED_TP = """
import threading

class Pool:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        threading.Thread(target=self.work).start()

    def work(self):
        self.count += 1          # raced with reset()

    def reset(self):
        self.count = 0
"""


class TestThreadSafety:
    def test_cross_method_unlocked_write_flags(self, tmp_path):
        res = lint_snippet(tmp_path, THREADED_TP)
        assert rule_ids(res) == ["T001"]
        assert len(res.findings) == 2       # both unlocked sites

    def test_locked_and_locked_suffix_pass(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import threading

            class Pool:
                def __init__(self):
                    self.count = 0
                    self._lock = threading.Lock()
                    threading.Thread(target=self.work).start()

                def work(self):
                    with self._lock:
                        self.count += 1

                def _bump_locked(self):   # caller holds the lock
                    self.count += 1
        """)
        assert res.findings == []

    def test_non_threaded_module_exempt(self, tmp_path):
        res = lint_snippet(tmp_path, """
            class Accum:                 # no threads anywhere in module
                def __init__(self):
                    self.count = 0

                def bump(self):
                    self.count += 1
        """)
        assert res.findings == []


# ---------------------------------------------------------------------------
# M — metric/span name discipline
# ---------------------------------------------------------------------------

class TestMetricNames:
    def test_bad_literal_flags_good_passes(self, tmp_path):
        res = lint_snippet(tmp_path, """
            def setup(reg):
                reg.counter("io/rows")              # fine
                reg.gauge("Storage/HitRate")        # M001: not snake_case
                reg.histogram("no_subsystem")       # M001: no '/' prefix
                reg.counter(name_var)               # dynamic: runtime's job
        """)
        assert [f.rule for f in res.findings] == ["M001", "M001"]

    def test_label_and_check_name_sites(self, tmp_path):
        res = lint_snippet(tmp_path, """
            from repro.obs import check_name, label

            def setup():
                label("storage/hits", shard=3)      # fine
                check_name("bad name")              # M001
        """)
        assert [f.rule for f in res.findings] == ["M001"]

    def test_span_literals_share_trace_namespace(self, tmp_path):
        res = lint_snippet(tmp_path, """
            def run(tracer):
                with tracer.span("data_wait"):      # fine
                    pass
                with tracer.span("Bad-Phase"):      # M002
                    pass
        """)
        assert [f.rule for f in res.findings] == ["M002"]

    def test_mangling_collision_same_file(self, tmp_path):
        res = lint_snippet(tmp_path, """
            def setup(reg):
                reg.counter("io/rows_total")        # fine
                reg.counter("agg/skew_x")           # first sighting
                reg.gauge("agg/skew/x")             # M003: same mangled name
        """, rules=["M003"])
        assert [f.rule for f in res.findings] == ["M003"]
        assert "recis_agg_skew_x" in res.findings[0].message

    def test_mangling_collision_cross_file(self, tmp_path):
        (tmp_path / "a.py").write_text(
            'def f(reg):\n    reg.counter("io/rows_a")\n')
        (tmp_path / "b.py").write_text(
            'def g(reg):\n    reg.counter("io/rows_a")\n'   # same name: fine
            'def h(reg):\n    reg.counter("io/rows/a")\n')  # M003 vs a.py
        from repro.analysis import run_lint
        res = run_lint([tmp_path], rules=["M003"], root=tmp_path)
        assert [f.rule for f in res.findings] == ["M003"]
        assert res.findings[0].path == "b.py"
        assert "a.py" in res.findings[0].message

    def test_mangling_collision_span_vs_histogram(self, tmp_path):
        # a span's derived trace/<name>_s histogram can collide too
        res = lint_snippet(tmp_path, """
            def run(tracer, reg):
                with tracer.span("device/step"):    # → trace/device/step_s
                    pass
                reg.histogram("trace/device_step_s")  # M003
        """, rules=["M003"])
        assert [f.rule for f in res.findings] == ["M003"]

    def test_mangling_state_resets_between_runs(self, tmp_path):
        # cross-run leakage would make the second identical run flag the
        # same literal against its own first-run sighting
        src = 'def f(reg):\n    reg.counter("io/rows_total")\n'
        (tmp_path / "a.py").write_text(src)
        from repro.analysis import run_lint
        for _ in range(2):
            res = run_lint([tmp_path], rules=["M003"], root=tmp_path)
            assert res.findings == []


# ---------------------------------------------------------------------------
# D — determinism of decide()-reachable / simulated code
# ---------------------------------------------------------------------------

class TestDeterminism:
    def test_clock_random_and_set_iteration_flag(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import random
            import time

            def _jitter():
                return random.random()

            def decide(sig, state):
                now = time.time()
                for rid in {1, 2, 3}:
                    pass
                return _jitter(), now

            class SimPipeline:
                def step(self):
                    time.sleep(0.01)
        """)
        assert rule_ids(res) == ["D001", "D002", "D003"]
        d001_lines = sorted(f.line for f in res.findings if f.rule == "D001")
        assert len(d001_lines) == 2         # decide AND SimPipeline.step

    def test_sorted_iteration_and_unrelated_module_pass(self, tmp_path):
        res = lint_snippet(tmp_path, """
            def decide(sig, state):
                for rid in sorted({1, 2, 3}):
                    pass
                return ()

            def helper():                  # not decide()-reachable
                import time
                return time.time()
        """)
        assert res.findings == []

    def test_live_autoscaler_is_deterministic(self):
        res = run_lint([REPO / "src" / "repro" / "io" / "autoscale.py"],
                       rules=["D001", "D002", "D003"], root=REPO)
        assert res.findings == []


# ---------------------------------------------------------------------------
# F — crash-consistent persistence (checkpoint/ and ft/ only)
# ---------------------------------------------------------------------------

class TestPersistence:
    def test_bare_write_in_checkpoint_module_flags(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import json

            def save_manifest(man, path):
                with open(path, "w") as f:
                    json.dump(man, f)

            def save_head(path, text):
                path.write_text(text)

            def save_blob(path, data):
                path.write_bytes(data)
        """, name="checkpoint/mod.py", rules=["F001"])
        assert [f.rule for f in res.findings] == ["F001"] * 3
        assert "torn file" in res.findings[0].message

    def test_stage_and_rename_passes(self, tmp_path):
        res = lint_snippet(tmp_path, """
            import os

            def write_atomic(data, path):
                tmp = path.with_name(path.name + ".tmp")
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)

            def write_via_rename(data, path):
                tmp = path.with_name(path.name + ".tmp")
                tmp.write_bytes(data)
                tmp.rename(path)
        """, name="ft/mod.py", rules=["F001"])
        assert res.findings == []

    def test_reads_and_out_of_scope_modules_exempt(self, tmp_path):
        # reads never flag, and the same torn write outside checkpoint/
        # or ft/ is out of the rule's jurisdiction
        read_only = """
            def load(path):
                with open(path, "rb") as f:
                    return f.read()

            def fix_name(s):
                return s.replace("a", "b")
        """
        assert lint_snippet(tmp_path, read_only, name="ft/reader.py",
                            rules=["F001"]).findings == []
        torn = """
            def dump(path, data):
                with open(path, "wb") as f:
                    f.write(data)
        """
        assert lint_snippet(tmp_path, torn, name="io/writer.py",
                            rules=["F001"]).findings == []

    def test_suppression_comment_respected(self, tmp_path):
        res = lint_snippet(tmp_path, """
            def torn_on_purpose(path, data):
                with open(path, "wb") as f:  # reclint: disable=F001
                    f.write(data[: len(data) // 2])
        """, name="ft/chaos_mod.py", rules=["F001"])
        assert res.findings == []

    def test_live_checkpoint_and_ft_trees_are_clean(self):
        res = run_lint([REPO / "src" / "repro" / "checkpoint",
                        REPO / "src" / "repro" / "ft"],
                       rules=["F001"], root=REPO)
        assert res.findings == []


# ---------------------------------------------------------------------------
# baseline + CLI + acceptance
# ---------------------------------------------------------------------------

class TestBaselineAndCli:
    def test_baseline_round_trip(self, tmp_path):
        src = tmp_path / "mod.py"
        src.write_text(textwrap.dedent(THREADED_TP))
        first = run_lint([tmp_path], root=tmp_path)
        assert first.exit_code == 1
        base = tmp_path / "base.json"
        write_baseline(base, first.findings)
        assert len(load_baseline(base)) == 2

        second = run_lint([tmp_path], baseline_path=base, root=tmp_path)
        assert second.exit_code == 0
        assert all(f.baselined for f in second.findings)

        # a NEW finding is not absorbed by the grandfathered entries
        src.write_text(textwrap.dedent(THREADED_TP) + textwrap.dedent("""
            class Extra:
                def __init__(self):
                    self.n = 0
                    threading.Thread(target=self.tick).start()

                def tick(self):
                    self.n += 1

                def clear(self):
                    self.n = 0
        """))
        third = run_lint([tmp_path], baseline_path=base, root=tmp_path)
        assert third.exit_code == 1
        assert sorted(f.baselined for f in third.findings) == [
            False, False, True, True]

    def test_fingerprint_ignores_line_numbers(self):
        a = Finding("T001", "m.py", 10, "msg")
        b = Finding("T001", "m.py", 99, "msg")
        assert a.fingerprint() == b.fingerprint()

    def test_rule_catalog_covers_all_families(self):
        ids = set(all_rules())
        assert {i[0] for i in ids} == {"P", "K", "T", "M", "D", "F"}
        assert len(ids) >= 10

    def test_unknown_rule_id_raises(self, tmp_path):
        with pytest.raises(ValueError, match="unknown rule"):
            run_lint([tmp_path], rules=["Z999"])

    def test_cli_json_and_exit_codes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(THREADED_TP))
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--no-baseline",
             "--json", str(bad)],
            capture_output=True, text=True, cwd=REPO,
            env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
        assert proc.returncode == 1, proc.stderr
        findings = json.loads(proc.stdout)
        assert {f["rule"] for f in findings} == {"T001"}

        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--no-baseline",
             str(clean)],
            capture_output=True, text=True, cwd=REPO,
            env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_acceptance_live_tree_is_clean(self):
        """`make lint` must exit 0: the tree + committed baseline lint
        clean, and the baseline honors the ≤5-findings growth policy."""
        baseline = REPO / "reclint-baseline.json"
        res = run_lint([REPO / "src" / "repro"], baseline_path=baseline,
                       root=REPO)
        assert [f.render() for f in res.failures] == []
        assert len(load_baseline(baseline)) <= 5
