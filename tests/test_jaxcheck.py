"""Static analyzer falsifiability + budget-gate tests (DESIGN.md §12).

A checker that cannot be tripped is not checking anything: every jaxpr
checker gets a doctored program that MUST flag and the clean twin that
MUST pass; every AST rule gets a doctored source string and a clean one.
Plus: budget-diff semantics (increase fails, cond-decrease fails,
allowlist waives, jax-version demotes), the end-to-end sweep over the
registry, and the CLI's nonzero exit on a seeded regression.
"""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.analysis import (analyze, build_ledger, clean_trace, diff_ledger,
                            doctored_trace, iter_traces, lint_source,
                            lint_tree, load_ledger, refresh_ledger,
                            static_sigs)
from repro.analysis.checkers import ProgramTrace, check_donation_policy
from repro.analysis.rules import AST_RULES, JAXPR_RULES, RULES
from repro.api import runners

ROOT = Path(__file__).resolve().parent.parent


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# falsifiability: each jaxpr checker trips on its doctored program
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", ["sort-in-loop", "scatter-in-loop",
                                  "dtype-drift", "batched-cond", "donation"])
def test_doctored_program_trips_checker(rule):
    findings, _ = analyze([doctored_trace(rule)])
    assert rule in _rules_of(findings), \
        f"doctored program for {rule} did not trip it"
    # and the finding names the doctored program, not something else
    assert any(f.rule == rule and "doctored" in f.where for f in findings)


def test_carry_stability_trips_on_divergent_same_meta_carries():
    """Two programs sharing (meta, kind) but carrying different widths."""
    findings, _ = analyze([clean_trace(), clean_trace(n_packets=96)])
    assert "carry-stability" in _rules_of(findings)


def test_missing_engine_loop_is_flagged():
    closed = jax.make_jaxpr(lambda x: x + 1.0)(
        jax.ShapeDtypeStruct((8,), "float32"))
    trace = ProgramTrace(key="t/loopless", kind="serial", scenario="t",
                         meta="m", closed=closed, axes={"packets": 8})
    findings, _ = analyze([trace])
    assert any("no-loop" in f.key for f in findings)


def test_clean_program_passes_every_checker():
    findings, programs = analyze([clean_trace()])
    assert findings == []
    row = programs["doctored/clean"]
    assert row["loop"]["cond"] == 1 and row["loop"]["sort"] == 0


def test_donation_policy_checker_and_falsifiability():
    assert check_donation_policy(runners.DONATE_ARGNUMS) == []
    # a policy that does not donate the state argument must be flagged
    assert any(f.rule == "donation" for f in check_donation_policy(()))


# ---------------------------------------------------------------------------
# AST rules: doctored source flags, clean source passes, disable suppresses
# ---------------------------------------------------------------------------

ENGINE_PATH = "src/repro/core/fake.py"
BENCH_PATH = "benchmarks/fake.py"

AST_CASES = {
    "tracer-cast": (
        "def step(s):\n    return float(s.time)\n",
        "def step(s):\n    import jax.numpy as jnp\n"
        "    return jnp.float32(s.time)\n",
        ENGINE_PATH),
    "item-call": (
        "def step(s):\n    return s.time.item()\n",
        "def step(s):\n    return s.time\n",
        ENGINE_PATH),
    "unseeded-random": (
        "import numpy as np\nx = np.random.rand(3)\n",
        "import numpy as np\nx = np.random.default_rng(0).random(3)\n",
        ENGINE_PATH),
    "random-module": (
        "import random\n",
        "import numpy as np\n",
        ENGINE_PATH),
    "naked-timer": (
        "import time\n\ndef bench(f):\n    t0 = time.perf_counter()\n"
        "    f()\n    return time.perf_counter() - t0\n",
        "import time\nimport jax\n\ndef bench(f):\n"
        "    t0 = time.perf_counter()\n    jax.block_until_ready(f())\n"
        "    return time.perf_counter() - t0\n",
        BENCH_PATH),
    "meta-subscript": (
        "def f(meta):\n    return meta['n_links']\n",
        "def f(meta):\n    return meta.n_links\n",
        ENGINE_PATH),
    "frozen-mutation": (
        "def f(meta):\n    meta.n_links = 3\n",
        "import dataclasses\n\ndef f(meta):\n"
        "    return dataclasses.replace(meta, n_links=3)\n",
        ENGINE_PATH),
    "f64-literal": (
        "import jax.numpy as jnp\nx = jnp.zeros(3, jnp.float64)\n",
        "import numpy as np\nx = np.zeros(3, np.float64)\n",
        ENGINE_PATH),
}


@pytest.mark.parametrize("rule", sorted(AST_CASES))
def test_ast_rule_falsifiability(rule):
    doctored, clean, relpath = AST_CASES[rule]
    assert rule in _rules_of(lint_source(doctored, relpath)), \
        f"doctored source for {rule} did not flag"
    assert rule not in _rules_of(lint_source(clean, relpath)), \
        f"clean source for {rule} flagged"


def test_ast_disable_comment_suppresses():
    doctored, _, relpath = AST_CASES["meta-subscript"]
    line = doctored.splitlines()[1] + "  # jaxcheck: disable=meta-subscript"
    text = doctored.splitlines()[0] + "\n" + line + "\n"
    assert lint_source(text, relpath) == []


def test_ast_rules_scope_outside_engine_is_quiet():
    """Engine-only rules must not fire on e.g. results-extraction code."""
    doctored, _, _ = AST_CASES["tracer-cast"]
    assert lint_source(doctored, "src/repro/api/results_fake_doc.py") != []
    assert lint_source(doctored, "examples/whatever.py") == []


# ---------------------------------------------------------------------------
# budget-diff semantics
# ---------------------------------------------------------------------------


def _fake_programs():
    return {"scn/serial": {
        "loop": {"sort": 2, "scatter": 1, "cond": 3, "select_n": 10},
        "eqns": 100,
        "carry": {"leaves": 5, "bytes": 128, "sig": "abc"}}}


def _bump(programs, prim, delta):
    out = json.loads(json.dumps(programs))
    out["scn/serial"]["loop"][prim] += delta
    return out


def test_budget_watched_increase_fails_decrease_ok():
    base = build_ledger(_fake_programs())
    up, _ = diff_ledger(_bump(_fake_programs(), "sort", +1), base)
    assert any(f.key == "scn/serial:sort" and f.severity == "error"
               for f in up)
    down, _ = diff_ledger(_bump(_fake_programs(), "sort", -1), base)
    assert down == []


def test_budget_cond_is_inverted():
    base = build_ledger(_fake_programs())
    down, _ = diff_ledger(_bump(_fake_programs(), "cond", -1), base)
    assert any(f.key == "scn/serial:cond" for f in down)
    up, _ = diff_ledger(_bump(_fake_programs(), "cond", +1), base)
    assert up == []


def test_budget_carry_change_fails_and_allowlist_waives():
    cur = _fake_programs()
    cur["scn/serial"]["carry"]["sig"] = "zzz"
    base = build_ledger(_fake_programs())
    findings, _ = diff_ledger(cur, base)
    assert any(f.key == "scn/serial:carry" for f in findings)
    waived = build_ledger(_fake_programs(),
                          allowlist={"scn/serial:carry": "reviewed"})
    findings, _ = diff_ledger(cur, waived)
    assert findings == []


def test_budget_membership_drift_full_sweep_only():
    base = build_ledger(_fake_programs())
    extra = dict(_fake_programs(), **{"scn/other": {"loop": {}, "eqns": 1}})
    full, _ = diff_ledger(extra, base, full_sweep=True)
    assert any(f.key == "scn/other:new" for f in full)
    partial, _ = diff_ledger(extra, base, full_sweep=False)
    assert partial == []
    gone, _ = diff_ledger({}, base, full_sweep=True)
    assert any(f.key == "scn/serial:gone" for f in gone)


def test_budget_jax_version_mismatch_demotes_to_warning():
    base = build_ledger(_fake_programs())
    base["jax"] = "0.0.0-not-this-one"
    findings, notes = diff_ledger(_bump(_fake_programs(), "sort", +1), base)
    assert findings and all(f.severity == "warning" for f in findings)
    assert notes


def test_refresh_preserves_allowlist():
    old = build_ledger(_fake_programs(), allowlist={"k": "why"})
    new = refresh_ledger(_fake_programs(), old)
    assert new["allowlist"] == {"k": "why"}


# ---------------------------------------------------------------------------
# end-to-end over the registry + the committed ledger + the clean tree
# ---------------------------------------------------------------------------


def test_quick_sweep_and_committed_budget_clean():
    """paper-fabric x all kinds x one signature: zero findings, and the
    derived rows match the committed PRIM_BUDGET.json exactly."""
    traces = list(iter_traces(["paper-fabric"], sigs=static_sigs()[:1]))
    findings, programs = analyze(traces)
    findings += check_donation_policy(runners.DONATE_ARGNUMS)
    assert [f.render() for f in findings] == []
    baseline = load_ledger(ROOT / "experiments" / "PRIM_BUDGET.json")
    assert baseline is not None, "committed PRIM_BUDGET.json missing"
    diff, _ = diff_ledger(programs, baseline, full_sweep=False)
    errors = [f for f in diff if f.severity == "error"]
    assert [f.render() for f in errors] == []


def test_ast_pass_clean_on_tree():
    findings = lint_tree(ROOT)
    assert [f.render() for f in findings] == []


@pytest.mark.slow
def test_full_registry_sweep_zero_unallowlisted_findings():
    """Every registry scenario x kind x static signature against the
    committed ledger: nothing unallowlisted may fire."""
    findings, programs = analyze(list(iter_traces()))
    findings += check_donation_policy(runners.DONATE_ARGNUMS)
    baseline = load_ledger(ROOT / "experiments" / "PRIM_BUDGET.json")
    diff, _ = diff_ledger(programs, baseline, full_sweep=True)
    errors = [f for f in findings + diff if f.severity == "error"]
    assert [f.render() for f in errors] == []


# ---------------------------------------------------------------------------
# the CLI: seeded regression goes red, quick clean run goes green
# ---------------------------------------------------------------------------


def test_cli_seeded_regression_exits_nonzero(capsys):
    jaxcheck = _load_tool("jaxcheck")
    rc = jaxcheck.main(["--quick", "--quiet", "--no-ast",
                        "--seed", "sort-in-loop"])
    out = capsys.readouterr().out
    assert rc != 0
    assert "sort-in-loop" in out


def test_cli_quick_clean_exits_zero():
    jaxcheck = _load_tool("jaxcheck")
    assert jaxcheck.main(["--quick", "--quiet", "--no-ast"]) == 0


def test_cli_refuses_partial_baseline_update(tmp_path):
    jaxcheck = _load_tool("jaxcheck")
    rc = jaxcheck.main(["--quick", "--quiet", "--no-ast",
                        "--update-baseline",
                        "--baseline", str(tmp_path / "b.json")])
    assert rc == 2
    assert not (tmp_path / "b.json").exists()


# ---------------------------------------------------------------------------
# docs contract: every rule documented, every token resolvable
# ---------------------------------------------------------------------------


def test_every_rule_documented_in_design_md():
    checker = _load_tool("check_design_refs")
    documented = checker.documented_rules(ROOT / "DESIGN.md")
    assert set(RULES) <= documented, \
        f"rules missing from DESIGN.md §12: {set(RULES) - documented}"
    assert set(RULES) == set(JAXPR_RULES) | set(AST_RULES)


def test_unknown_rule_token_fails_design_refs(tmp_path):
    checker = _load_tool("check_design_refs")
    root = tmp_path
    (root / "src").mkdir()
    # build the token at runtime so the real-tree scan never sees it here
    (root / "src" / "x.py").write_text(
        "# see " + "jaxcheck" + ":not-a-real-rule\n")
    (root / "DESIGN.md").write_text("# §1 heading\njaxcheck:sort-in-loop\n")
    errors = checker.check(root)
    assert any("not-a-real-rule" in e for e in errors)
