"""Unit tests for the `repro.analysis` static-analysis subsystem.

Parser-level tests use handcrafted HLO snippets shaped like real XLA:CPU
output (async tuple `-start` forms with operand echoes and u32[] control
slots, `-done` pairs, replica-group annotations) so the byte-accounting
conventions are pinned independently of whatever XLA emits today. The
rule/CLI tests run the real grid programs and the seeded violations.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    Program,
    analyze_hlo,
    check_narrowing,
    check_scatter,
    collective_bytes_from_hlo,
    convert_ops,
    count_primitives,
    input_output_aliases_from_hlo,
    iter_eqns,
    narrowing_converts,
    primitive_names,
    run_rules,
    violation_program,
)
from repro.analysis.deadcode import (
    collect_exports,
    dead_exports,
    reference_counts,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HLO parser: handcrafted snippets
# ---------------------------------------------------------------------------


def test_hlo_plain_collective_bytes():
    """A sync collective's payload is its output shape."""
    hlo = """
      ar = f32[128,8]{1,0} all-reduce(f32[128,8]{1,0} x), replica_groups={{0,1,2,3}}, to_apply=add
    """
    res = analyze_hlo(hlo)
    assert res["count_per_kind"] == {"all-reduce": 1}
    assert res["bytes_per_kind"] == {"all-reduce": 128 * 8 * 4}
    (op,) = res["collectives"]
    assert not op.is_start
    assert op.replica_groups == [[0, 1, 2, 3]]
    assert op.group_size == 4


def test_hlo_start_done_counted_once():
    """An async pair is one transfer: the -start tuple drops the u32[]
    control slots and the operand echo; the -done line is skipped."""
    hlo = """
      ags = (f32[64]{0}, f32[128]{0}, u32[], u32[]) all-gather-start(f32[64]{0} p), replica_groups={{0,1}}, dimensions={0}
      agd = f32[128]{0} all-gather-done((f32[64]{0}, f32[128]{0}, u32[], u32[]) ags)
    """
    res = analyze_hlo(hlo)
    assert res["count_per_kind"] == {"all-gather": 1}
    # 128 floats survive: the 64-float operand echo and both u32[] slots go
    assert res["bytes_per_kind"] == {"all-gather": 128 * 4}
    assert res["collectives"][0].is_start


def test_hlo_start_identity_output_not_zeroed():
    """An all-reduce-start whose output equals its operand still counts its
    single payload — echo-dropping never removes the last entry."""
    hlo = """
      ars = (f32[32]{0}, f32[32]{0}, u32[], u32[]) all-reduce-start(f32[32]{0} p), to_apply=add
      ard = f32[32]{0} all-reduce-done((f32[32]{0}, f32[32]{0}, u32[], u32[]) ars)
    """
    res = analyze_hlo(hlo)
    assert res["bytes_per_kind"] == {"all-reduce": 32 * 4}
    assert res["count_per_kind"] == {"all-reduce": 1}


def test_hlo_int8_payload_and_permute_pairs():
    hlo = """
      cp = s8[1024]{0} collective-permute(s8[1024]{0} x), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
      ag = f32[8]{0} all-gather(f32[4]{0} y), replica_groups={{0,1},{2,3}}, dimensions={0}
    """
    res = analyze_hlo(hlo)
    assert res["bytes_per_kind"] == {"collective-permute": 1024,
                                     "all-gather": 32}
    cp, ag = res["collectives"]
    assert cp.dtypes == ("s8",)
    assert cp.source_target_pairs == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert cp.group_size == 4
    # multi-group annotations must not truncate at the first inner brace
    assert ag.replica_groups == [[0, 1], [2, 3]]
    assert ag.group_size == 2


def test_hlo_scatter_census_excludes_lookalikes():
    """reduce-scatter and select-and-scatter are NOT data-dependent
    scatters; a real `scatter` is."""
    hlo = """
      rs = f32[16]{0} reduce-scatter(f32[64]{0} x), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=add
      sas = f32[8,8]{1,0} select-and-scatter(f32[8,8]{1,0} a, f32[4,4]{1,0} b, f32[] c), select=ge, scatter=add
      sc = f32[64,8]{1,0} scatter(f32[64,8]{1,0} h, s32[32,1]{1,0} idx, f32[32,8]{1,0} upd), to_apply=add
    """
    res = analyze_hlo(hlo)
    assert res["scatter_ops"] == 1
    assert res["count_per_kind"] == {"reduce-scatter": 1}
    assert res["bytes_per_kind"] == {"reduce-scatter": 16 * 4}


def test_hlo_convert_ops():
    hlo = """
      c1 = s8[256]{0} convert(f32[256]{0} x)
      c2 = s8[256]{0} convert(f32[256]{0} y)
      c3 = f32[256]{0} convert(s8[256]{0} z)
    """
    res = analyze_hlo(hlo)
    assert res["convert_ops"] == {("f32", "s8"): 2, ("s8", "f32"): 1}


def test_hlo_input_output_alias_header():
    hlo = ("HloModule jit_step, input_output_alias={ {0}: (1, {}, may-alias),"
           " {1}: (3, {}, may-alias) }, entry_computation_layout=...")
    assert input_output_aliases_from_hlo(hlo) == [(0, 1), (1, 3)]
    assert input_output_aliases_from_hlo("HloModule jit_f\n  x = f32[]") == []


def test_collective_bytes_historical_shape():
    hlo = "  ar = f32[4]{0} all-reduce(f32[4]{0} x), to_apply=add"
    res = collective_bytes_from_hlo(hlo)
    assert set(res) == {"bytes_per_kind", "count_per_kind", "total_bytes"}
    assert res["total_bytes"] == 16


def test_donation_probe_aliases_on_cpu():
    """jit(donate_argnums) leaves an input_output_alias header even on
    XLA:CPU — the donation rule's alias probe is meaningful here."""
    f = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    txt = f.lower(jnp.ones(16, jnp.float32)).compile().as_text()
    pairs = input_output_aliases_from_hlo(txt)
    assert pairs and pairs[0][1] == 0


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------


def test_iter_eqns_recurses_into_subjaxprs():
    """Primitives inside scan/jit bodies are visible to the walker."""

    def body(c, _):
        return jnp.sin(c) * 2.0, None

    def fn(x):
        inner = jax.jit(lambda y: jnp.cos(y))(x)
        out, _ = jax.lax.scan(body, inner, None, length=3)
        return out

    cj = jax.make_jaxpr(fn)(jnp.ones(4))
    names = primitive_names(cj)
    assert {"sin", "cos", "scan", "jit"} <= names
    counts = count_primitives(cj)
    assert counts["sin"] == 1 and counts["cos"] == 1
    assert len(list(iter_eqns(cj))) == sum(counts.values())


def test_convert_walker_and_narrowing_filter():
    def fn(x, idx):
        wire = x.astype(jnp.bfloat16).astype(jnp.float32)   # narrowing
        small = idx.astype(jnp.int8)                        # integer churn
        return wire.sum() + small.sum()

    cj = jax.make_jaxpr(fn)(jnp.ones(8, jnp.float32),
                            np.arange(8, dtype=np.int32))
    conv = convert_ops(cj)
    assert conv[("float32", "bfloat16")] == 1
    assert conv[("int32", "int8")] == 1
    # only the float shrink is wire compression
    assert narrowing_converts(cj) == {("float32", "bfloat16"): 1}


def test_check_scatter_both_directions():
    def scatters(x, idx):
        return jnp.zeros(16).at[idx].add(x)

    def clean(x):
        return x * 2.0

    cj_scatter = jax.make_jaxpr(scatters)(jnp.ones(4), jnp.arange(4))
    cj_clean = jax.make_jaxpr(clean)(jnp.ones(4))
    assert check_scatter([cj_clean], expect_free=True) is None
    assert check_scatter([cj_scatter], expect_free=False) is None
    msg = check_scatter([cj_scatter], expect_free=True)
    assert msg and "scatter" in msg
    # anchor direction: a clean trace where a scatter was REQUIRED means
    # the walker went blind
    assert check_scatter([cj_clean], expect_free=False) is not None


def test_check_narrowing_respects_codec_license():
    def narrow(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32).sum()

    cj = jax.make_jaxpr(narrow)(jnp.ones(8, jnp.float32))
    assert check_narrowing([cj], "bf16") == []
    offenders = check_narrowing([cj], "fp32")
    assert offenders == [("float32", "bfloat16", 1)]


# ---------------------------------------------------------------------------
# retrace-guard (satellite: deliberate shape-dependent retrace is caught)
# ---------------------------------------------------------------------------


def test_retrace_guard_green_path():
    """A warmed, shape-stable hot loop compiles nothing: budget 0 holds."""
    step = jax.jit(lambda x: x * 2.0)

    def sweep():
        def hot():
            step(jnp.ones(4, jnp.float32)).block_until_ready()
            step(jnp.ones(4, jnp.float32)).block_until_ready()
        return hot

    prog = Program(name="retrace/green", kind="retrace",
                   sweep=sweep, retrace_budget=0)
    report = run_rules([prog], ["retrace-guard"])
    assert report.exit_code == 0, [f.message for f in report.findings]


def test_retrace_guard_catches_shape_dependent_retrace():
    """The seeded violation — a fresh jit fed three distinct shapes —
    exceeds its budget and turns the gate red."""
    report = run_rules([violation_program("retrace-guard")],
                       ["retrace-guard"])
    assert report.exit_code == 1
    (err,) = report.errors
    assert "compiles" in err.message and "budget" in err.message


# ---------------------------------------------------------------------------
# dead-export sweep
# ---------------------------------------------------------------------------


def _fake_repo(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(
        "def used():\n    return 1\n\n"
        "def unused():\n    return 2\n\n"
        "def kept():  # lint: keep\n    return 3\n\n"
        "def _private():\n    return 4\n\n"
        "CONST = 7\n"
    )
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_mod.py").write_text(
        "from repro.mod import used\n\n"
        "def test_u():\n    assert used() == 1\n"
    )
    return tmp_path


def test_dead_exports_flags_only_unreferenced_public(tmp_path):
    root = _fake_repo(tmp_path)
    exports = collect_exports(root)
    assert set(exports) == {"used", "unused", "CONST"}  # kept/_private skipped
    dead = dict(dead_exports(root))
    assert set(dead) == {"unused", "CONST"}


def test_reference_counts_are_token_matches(tmp_path):
    f = tmp_path / "x.py"
    f.write_text("run_rules = 1\nrerun = 2\n")
    counts = reference_counts(["run"], [f])
    assert counts["run"] == 0  # substrings of other identifiers don't count


def test_repo_has_no_unannotated_dead_exports():
    """The advisory sweep stays clean on the repo itself — new dead exports
    must be deleted or `# lint: keep`-annotated."""
    assert dead_exports(REPO) == []


# ---------------------------------------------------------------------------
# gnn_lint CLI
# ---------------------------------------------------------------------------


def _lint(*argv):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.gnn_lint", *argv],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=REPO,
    )


def test_cli_tiny_grid_green_and_report_schema(tmp_path):
    out = tmp_path / "report.json"
    proc = _lint("--grid", "tiny", "--out-json", str(out))
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-1000:]
    report = json.loads(out.read_text())
    assert report["schema"] == "gnn-lint-report/v1"
    assert set(report) >= {"programs", "rules", "counts", "exit_code",
                           "elapsed_s", "findings"}
    assert report["exit_code"] == 0 and report["counts"]["error"] == 0
    assert set(report["rules"]) == {"no-scatter", "dtype-policy",
                                    "collective-budget", "donation",
                                    "retrace-guard"}


def test_cli_seeded_violation_exits_nonzero():
    proc = _lint("--grid", "tiny", "--rules", "no-scatter",
                 "--inject-violation", "no-scatter", "--out-json", "-")
    assert proc.returncode == 1, proc.stderr[-3000:]
    report = json.loads(proc.stdout[: proc.stdout.rindex("}") + 1])
    errs = [f for f in report["findings"] if f["level"] == "error"]
    assert errs and errs[0]["rule"] == "no-scatter"


def test_cli_rejects_unknown_rule():
    proc = _lint("--rules", "no-such-rule")
    assert proc.returncode == 2
    assert "unknown rules" in proc.stderr
