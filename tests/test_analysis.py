"""repro.analysis (ISSUE 7): lint rules, contracts, baseline, CLI gate.

Golden findings per fixture (rule id + line), a zero-finding pass over the
clean fixture, baseline mechanics, runtime parity for the static
topology/config mirrors, and the repo-wide gate: the current tree scans
clean against the checked-in ``analysis_baseline.json``.
"""

import json
from pathlib import Path

import pytest

from repro.analysis import contracts
from repro.analysis.callgraph import lint_program
from repro.analysis.cli import main as analysis_main
from repro.analysis.findings import Baseline, Finding, apply_baseline
from repro.analysis.lint import RULES, iter_python_files, lint_file

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "analysis_fixtures"
PROGRAM = FIXTURES / "program"

# golden (line, severity) findings per fixture file — every shipped rule
# demonstrably fires, at exactly these sites and no others
GOLDEN = {
    "host_sync_fixture.py": {
        "host-sync-in-jit": {(11, "error"), (12, "error"), (13, "error"),
                             (14, "error"), (15, "error"), (20, "error")},
    },
    "retrace_fixture.py": {
        "retrace-hazard": {(8, "warn"), (13, "error"), (21, "warn")},
    },
    "np_mix_fixture.py": {
        "np-jnp-mixing": {(12, "error"), (13, "error")},
    },
    "frozen_fixture.py": {
        "frozen-mutation": {(11, "note"), (14, "error"), (18, "error"),
                            (19, "error"), (20, "error")},
    },
    "unordered_fixture.py": {
        "unordered-iteration": {(7, "warn"), (9, "warn"), (10, "warn")},
    },
    "contract_fixture.py": {
        "exactness-contract": {(3, "error"), (4, "error"), (5, "error")},
    },
    "topology_fixture.py": {
        "topology-config": {(5, "error"), (6, "error"), (7, "error"),
                            (8, "error"), (9, "error"), (10, "error"),
                            (12, "error")},
    },
    "registry_fixture.py": {
        "registry-counter-mutation": {(8, "error"), (9, "error"),
                                      (10, "error"), (18, "error"),
                                      (26, "error"), (27, "error")},
    },
    # ISSUE 10: determinism & numerics rules
    "overflow_fixture.py": {
        "int32-overflow": {(9, "error"), (15, "error"), (21, "error"),
                           (30, "error"), (31, "error")},
    },
    "rng_fixture.py": {
        "unseeded-rng": {(10, "error"), (11, "error"), (12, "error"),
                         (13, "error"), (14, "error"), (15, "error"),
                         (16, "error")},
    },
    "wallclock_fixture.py": {
        "wall-clock-leak": {(5, "warn"), (10, "warn"), (15, "warn")},
    },
    "sig_fixture.py": {
        "unbounded-signature": {(12, "warn")},
    },
    "interproc_fixture.py": {
        "interproc-unordered-iteration": {(13, "warn"), (15, "warn")},
    },
}

#: one near-miss clean fixture per ISSUE-10 rule (plus the original):
#: similar shape, zero findings across *all* rules
CLEAN_FIXTURES = (
    "clean_fixture.py",
    "overflow_clean_fixture.py",   # int64 accumulators / unaccumulated ids
    "rng_clean_fixture.py",        # seeded, threaded generators
    "wallclock_clean_fixture.py",  # elapsed-time print that never escapes
    "sig_clean_fixture.py",        # pow2-bucketed / boolean key elements
    "interproc_clean_fixture.py",  # sorted at the set boundary
)


# ---------------------------------------------------------------------------
# golden findings: each rule fires exactly where the fixture says
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture", sorted(GOLDEN))
def test_fixture_golden_findings(fixture):
    found = lint_file(FIXTURES / fixture, REPO)
    got = {}
    for f in found:
        got.setdefault(f.rule, set()).add((f.line, f.severity))
    assert got == GOLDEN[fixture]
    for f in found:
        assert f.message and f.hint  # every finding carries a fix-it hint


@pytest.mark.parametrize("fixture", CLEAN_FIXTURES)
def test_clean_fixture_has_no_findings(fixture):
    assert lint_file(FIXTURES / fixture, REPO) == []


def test_cross_module_traced_closure_and_interproc():
    """The whole-program layer sees what per-file scans cannot: a hazard
    in a helper module only traced through another module's jit root, and
    iteration over an imported set-returning callee."""
    findings = lint_program([PROGRAM], REPO, excludes=())
    by = {}
    for f in findings:
        by.setdefault(Path(f.path).name, set()).add((f.rule, f.line))
    assert by == {
        "xjit_b.py": {("host-sync-in-jit", 6), ("np-jnp-mixing", 7)},
        "set_consumer.py": {("interproc-unordered-iteration", 6)},
    }
    # the clean pair stays clean even once traced across the module edge
    assert "xjit_clean_b.py" not in by
    # and the same files are blind spots for the intra-module scan —
    # exactly the gap the call graph closes
    assert lint_file(PROGRAM / "xjit_b.py", REPO) == []
    assert lint_file(PROGRAM / "set_consumer.py", REPO) == []


def test_every_rule_covered_by_a_fixture():
    covered = {rule for per_file in GOLDEN.values() for rule in per_file}
    assert covered == set(RULES)


def test_fixture_dir_excluded_from_default_scan():
    files = iter_python_files([REPO / "tests"])
    assert not any("analysis_fixtures" in f.parts for f in files)
    assert any(f.name == "test_analysis.py" for f in files)


# ---------------------------------------------------------------------------
# the repo gate: current tree is clean against the checked-in baseline,
# and the baseline is exercised by real pre-existing findings
# ---------------------------------------------------------------------------


def _repo_scan():
    paths = [REPO / p for p in ("src", "tests", "benchmarks", "examples")
             if (REPO / p).exists()]
    return lint_program(paths, REPO)


def test_repo_scans_clean_against_baseline():
    findings = _repo_scan()
    baseline = Baseline.load(REPO / "analysis_baseline.json")
    fresh, stale = apply_baseline(findings, baseline)
    assert fresh == [], "new findings:\n" + "\n".join(
        f.format() for f in fresh)
    assert stale == [], f"stale baseline entries (fixed? remove): {stale}"
    # no rule is fixture-only: the baseline carries real-tree findings
    assert len(findings) > 0
    baselined_rules = {fp.split("::", 1)[0] for fp in baseline.entries}
    assert baselined_rules  # ≥1 rule fired on real pre-existing code


def test_baseline_justifications_are_real():
    baseline = Baseline.load(REPO / "analysis_baseline.json")
    for fp, (count, why) in baseline.entries.items():
        assert count >= 1
        assert len(why) > 20 and "TODO" not in why, fp


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


def _finding(rule="host-sync-in-jit", path="src/x.py", line=3,
             scope="f") -> Finding:
    return Finding(rule=rule, path=path, line=line, col=0,
                   severity="error", message="m", hint="h", scope=scope)


def test_baseline_suppresses_by_fingerprint_and_count():
    f1, f2 = _finding(line=3), _finding(line=9)  # same scope: same print
    b = Baseline({f1.fingerprint: (1, "justified")})
    fresh, stale = apply_baseline([f1, f2], b)
    assert fresh == [f2]  # count=1 covers one instance; the excess is new
    assert stale == []
    fresh2, _ = apply_baseline(
        [f1, f2], Baseline({f1.fingerprint: (2, "justified")}))
    assert fresh2 == []


def test_baseline_fingerprint_survives_line_drift():
    before, after = _finding(line=3), _finding(line=40)
    assert before.fingerprint == after.fingerprint


def test_baseline_reports_stale_entries():
    b = Baseline({"unseeded-rng::src/gone.py::f": (1, "was justified")})
    fresh, stale = apply_baseline([], b)
    assert fresh == [] and stale == ["unseeded-rng::src/gone.py::f"]


def test_baseline_requires_why(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"version": 1, "accepted": [
        {"fingerprint": "r::p::s", "count": 1, "why": "  "}]}))
    with pytest.raises(ValueError, match="why"):
        Baseline.load(p)


def test_baseline_write_roundtrip(tmp_path):
    p = tmp_path / "b.json"
    f = _finding()
    Baseline({f.fingerprint: (1, "kept justification")}).dump(
        p, findings=[f, _finding(line=9)])
    loaded = Baseline.load(p)
    assert loaded.entries[f.fingerprint] == (2, "kept justification")
    # dump always writes schema v2, stamped with the audited scale target
    data = json.loads(p.read_text())
    assert data["version"] == 2
    assert data["scale_target"] == contracts.SCALE_TARGET
    assert loaded.scale_target == contracts.SCALE_TARGET


def test_baseline_v1_still_loads(tmp_path):
    """Migration path: a v1 baseline (no scale_target) loads as legacy."""
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"version": 1, "accepted": [
        {"fingerprint": "r::p::s", "count": 1, "why": "old justification"}]}))
    b = Baseline.load(p)
    assert b.entries["r::p::s"] == (1, "old justification")
    assert b.scale_target is None


def test_baseline_v2_pins_scale_target(tmp_path):
    """v2 requires scale_target, and it must match contracts.SCALE_TARGET —
    moving the target invalidates every audited justification loudly."""
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"version": 2, "accepted": []}))
    with pytest.raises(ValueError, match="scale_target"):
        Baseline.load(p)
    p.write_text(json.dumps({
        "version": 2, "scale_target": contracts.SCALE_TARGET * 100,
        "accepted": []}))
    with pytest.raises(ValueError, match="re-audit"):
        Baseline.load(p)
    p.write_text(json.dumps({
        "version": 2, "scale_target": contracts.SCALE_TARGET,
        "accepted": []}))
    assert Baseline.load(p).scale_target == contracts.SCALE_TARGET


def test_checked_in_baseline_is_v2():
    data = json.loads((REPO / "analysis_baseline.json").read_text())
    assert data["version"] == 2
    assert data["scale_target"] == contracts.SCALE_TARGET


# ---------------------------------------------------------------------------
# contracts: the exactness table is the single source of truth
# ---------------------------------------------------------------------------


def test_exactness_table_shape():
    assert set(contracts.EXACTNESS) == {
        (s, m) for s in contracts.SCHEMES for m in contracts.ENGINE_MODES}
    # the reference oracle is trivially exact for every scheme
    assert all(contracts.exactness(s, "reference") == contracts.EXACT
               for s in contracts.SCHEMES)
    # batched and fused carry the same routing contract per scheme
    for s in contracts.SCHEMES:
        assert contracts.exactness(s, "batched") == \
            contracts.exactness(s, "fused")


def test_exactness_partitions():
    assert set(contracts.EXACT_SCHEMES) | set(contracts.BANDED_SCHEMES) \
        == set(contracts.SCHEMES)
    assert not set(contracts.EXACT_SCHEMES) & set(contracts.BANDED_SCHEMES)
    assert contracts.DRIFT_SCHEMES == contracts.BANDED_SCHEMES
    with pytest.raises(ValueError):
        contracts.exactness("nope", "fused")
    with pytest.raises(ValueError):
        contracts.exactness("sg", "warp")


def test_static_mirrors_match_runtime_validators():
    """Where the static mirror reports an error, the runtime constructor
    raises — and vice versa for the valid cases the fixture keeps."""
    from repro.topology import Edge, Stage, Topology, config_for

    # literal args go through variables so the repo scan of this test file
    # does not itself trip the topology-config rule it is testing
    bad_scheme, bad_alpha = "nope", 1.5
    assert contracts.validate_config_literal("fish", {"alpha": bad_alpha})
    with pytest.raises(ValueError):
        config_for("fish", alpha=bad_alpha)
    with pytest.raises((KeyError, ValueError)):
        config_for(bad_scheme)
    assert contracts.validate_config_literal("fish", {"alpha": 0.5}) is None

    reserved, zero = "source", 0
    assert contracts.validate_stage_literal(reserved, 4)
    with pytest.raises(ValueError):
        Stage(reserved, 4)
    assert contracts.validate_stage_literal("work", zero)
    with pytest.raises(ValueError):
        Stage("work", zero)
    assert contracts.validate_stage_literal("work", 4) is None

    a = "a"  # indirection: keeps the repo scan of this file itself clean
    assert contracts.validate_edge_literal(a, a)
    with pytest.raises(ValueError):
        Edge(a, a, config_for("sg"))
    assert contracts.validate_edge_literal("source", a) is None

    dup = [a, a]
    assert contracts.validate_topology_literal(dup, [("source", a)])
    with pytest.raises(ValueError):
        Topology(name="dup",
                 stages=(Stage(a, 2), Stage(a, 2)),
                 edges=(Edge("source", a, config_for("sg")),))
    assert contracts.validate_topology_literal(
        ["a", "b"], [("source", "a"), ("a", "b")]) == []
    # fan-in and disconnection are both promoted to pre-run errors
    assert contracts.validate_topology_literal(
        ["a", "b"], [("source", "a"), ("source", "b"), ("a", "b")])
    assert contracts.validate_topology_literal(["a"], [])


# ---------------------------------------------------------------------------
# auditor mechanics (the engine-level budgets live in test_fused_engine)
# ---------------------------------------------------------------------------


def test_trace_budget_guard():
    from repro.analysis.audit import TraceBudget
    from repro.kernels import feed_fused

    with TraceBudget(1):
        feed_fused.TRACE_COUNT += 1
    with pytest.raises(AssertionError, match="traces > budget"):
        with TraceBudget(0, what="guarded block"):
            feed_fused.TRACE_COUNT += 1


def test_auditor_rejects_unknown_sync_context():
    from repro.analysis.audit import EdgeAuditor

    class _Stub:
        begin_feed = run_segment = flush_pane = host_sync = \
            refresh_membership = staticmethod(lambda *a, **k: None)

    with EdgeAuditor(_Stub()) as aud:
        with pytest.raises(ValueError, match="unknown sync context"):
            with aud.expect("metrics"):
                pass


# ---------------------------------------------------------------------------
# CLI gate: red on an injected violation, green when clean
# ---------------------------------------------------------------------------


def _write_violation(tmp_path: Path) -> Path:
    bad = tmp_path / "injected.py"
    bad.write_text(
        "import numpy as np\n"
        "x = np.random.rand(4)\n")
    return bad


def test_cli_red_on_injected_violation(tmp_path, capsys):
    bad = _write_violation(tmp_path)
    rc = analysis_main([str(bad), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "unseeded-rng" in out and "injected.py:2" in out


def test_cli_green_on_clean_file(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert analysis_main([str(ok), "--no-baseline"]) == 0


def test_cli_baseline_cycle(tmp_path, capsys):
    bad = _write_violation(tmp_path)
    base = tmp_path / "base.json"
    assert analysis_main([str(bad), "--write-baseline", str(base)]) == 0
    data = json.loads(base.read_text())
    assert data["accepted"][0]["why"].startswith("TODO")
    # an unjustified baseline is rejected outright
    assert analysis_main([str(bad), "--baseline", str(base)]) == 1
    data["accepted"][0]["why"] = "intentional global-RNG test double"
    base.write_text(json.dumps(data))
    assert analysis_main([str(bad), "--baseline", str(base)]) == 0
    # a second instance of the same fingerprint is new again
    bad.write_text(bad.read_text() + "y = np.random.rand(8)\n")
    assert analysis_main([str(bad), "--baseline", str(base)]) == 1


def test_cli_json_artifact(tmp_path):
    bad = _write_violation(tmp_path)
    report = tmp_path / "findings.json"
    rc = analysis_main([str(bad), "--no-baseline", "--json", str(report),
                        "--quiet"])
    assert rc == 1
    data = json.loads(report.read_text())
    assert data["new"] == data["total"] == 1
    (entry,) = data["findings"]
    assert entry["rule"] == "unseeded-rng" and entry["new"]


def test_cli_usage_errors(tmp_path):
    assert analysis_main([str(tmp_path / "missing.py")]) == 2
    assert analysis_main(["--rules", "not-a-rule",
                          str(tmp_path)]) == 2
