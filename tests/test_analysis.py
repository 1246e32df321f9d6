"""repro.analysis: AST rules must trip on bad fixtures, jaxpr checks
must catch injected violations, the baseline must round-trip, and the
repo itself must be clean (DESIGN.md §2.9).

The fixture modules are written to tmp_path on purpose: the analyzer's
CI gate lints ``src/repro``/``benchmarks``/``examples`` but *not*
``tests/``, precisely so that violation fixtures can exist here.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import astlint, baseline as _baseline, jaxprs
from repro.analysis.cli import SCAN_ROOTS
from repro.analysis.findings import Finding, render_json, render_text

REPO = Path(__file__).resolve().parents[1]


def _lint_src(tmp_path, source, only=None):
    mod = tmp_path / "fixture.py"
    mod.write_text(textwrap.dedent(source))
    findings, n = astlint.lint_paths([mod], root=tmp_path, only=only)
    assert n == 1
    return findings


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Layer 1: each AST rule trips on a synthetic bad module
# ---------------------------------------------------------------------------


def test_rng_global_trips(tmp_path):
    findings = _lint_src(tmp_path, """
        import numpy as np
        x = np.random.rand(4)
        rng = np.random.default_rng()
        ok = np.random.default_rng(42)
    """)
    hits = [f for f in findings if f.rule == "rng-global"]
    assert {f.line for f in hits} == {3, 4}, findings
    assert all(f.is_error for f in hits)


def test_rng_global_stdlib_random(tmp_path):
    findings = _lint_src(tmp_path, """
        import random
        x = random.random()
    """)
    assert "rng-global" in _rules(findings)


def test_rng_in_fold_trips_even_when_seeded(tmp_path):
    findings = _lint_src(tmp_path, """
        import time
        import numpy as np
        import jax

        def fold(xs):
            def step(carry, op):
                r = np.random.default_rng(0).normal()
                t = time.time()
                return carry, op
            return jax.lax.scan(step, 0.0, xs)
    """)
    hits = [f for f in findings if f.rule == "rng-in-fold"]
    assert {f.line for f in hits} == {8, 9}, findings


def test_rng_in_fold_sees_lambda_bodies(tmp_path):
    findings = _lint_src(tmp_path, """
        import datetime
        import jax
        out = jax.lax.fori_loop(
            0, 4, lambda i, c: c + datetime.datetime.now().microsecond, 0)
    """)
    assert "rng-in-fold" in _rules(findings)


def test_engine_dispatch_trips_outside_registry(tmp_path):
    findings = _lint_src(tmp_path, """
        def pick(engine):
            if engine == "scan":
                return 1
            return engine in ("prefix", "squaring")
    """)
    hits = [f for f in findings if f.rule == "engine-dispatch"]
    assert len(hits) == 2, findings


def test_engine_dispatch_allowed_in_registry_module(tmp_path):
    api = tmp_path / "src" / "repro" / "core" / "api.py"
    api.parent.mkdir(parents=True)
    api.write_text('def pick(engine):\n    return engine == "scan"\n')
    findings, _ = astlint.lint_paths([api], root=tmp_path)
    assert "engine-dispatch" not in _rules(findings)


def test_shim_internal_trips(tmp_path):
    findings = _lint_src(tmp_path, """
        from repro.core.sim import ssd_bandwidth_mb_s
        from repro.core import trace

        def go():
            a = ssd_bandwidth_mb_s()
            b = trace.simulate()
            return a, b
    """)
    hits = [f for f in findings if f.rule == "shim-internal"]
    assert {f.line for f in hits} == {6, 7}, findings
    assert any("Simulator.run" in f.message for f in hits)


def test_host_in_fold_trips(tmp_path):
    findings = _lint_src(tmp_path, """
        import numpy as np
        import jax

        def fold(xs):
            def step(carry, op):
                v = float(carry)
                w = carry.item()
                u = np.asarray(op)
                return carry, op
            return jax.lax.scan(step, 0.0, xs)
    """)
    hits = [f for f in findings if f.rule == "host-in-fold"]
    assert {f.line for f in hits} == {7, 8, 9}, findings


def test_host_ops_fine_outside_folds(tmp_path):
    findings = _lint_src(tmp_path, """
        import numpy as np
        def summarise(end):
            return float(end), np.asarray(end)
    """)
    assert findings == []


def test_only_filter_restricts_rules(tmp_path):
    findings = _lint_src(tmp_path, """
        import numpy as np
        x = np.random.rand(4)
        def pick(engine):
            return engine == "scan"
    """, only={"engine-dispatch"})
    assert _rules(findings) == {"engine-dispatch"}


def test_rule_catalog_complete():
    assert set(astlint.registered_rules()) == {
        "rng-global", "rng-in-fold", "engine-dispatch",
        "shim-internal", "host-in-fold"}


# ---------------------------------------------------------------------------
# Layer 2: jaxpr checks on injected fake engines
# ---------------------------------------------------------------------------


class _FakeEngine:
    def __init__(self, folds):
        self._folds = folds

    def canonical_folds(self, sim):
        folds = self._folds
        if isinstance(folds, Exception):
            raise folds
        return folds


def test_jaxpr_dtype_catches_f64_promoting_engine():
    import jax.numpy as jnp
    import numpy as np

    def bad(x):
        # np.float64 scalar: demoted silently under the default config,
        # promotes the whole fold to f64 once x64 is enabled.
        return x * np.float64(2.0)

    folds, findings = jaxprs.collect_engine_folds(
        engines={"fake": _FakeEngine(
            {"bad": (bad, (jnp.ones((3,), jnp.float32),))})},
        sim=object())
    assert [f.key for f in folds] == ["fake/bad"]
    hits = [f for f in findings if f.rule == "jaxpr-dtype"]
    assert hits and "enable_x64" in hits[0].message


def test_jaxpr_rng_catches_in_fold_randomness():
    import jax

    def bad(key):
        return jax.random.uniform(key, (3,))

    _, findings = jaxprs.collect_engine_folds(
        engines={"fake": _FakeEngine(
            {"rng": (bad, (jax.random.PRNGKey(0),))})},
        sim=object())
    assert "jaxpr-rng" in _rules(findings)


def test_jaxpr_hook_missing_is_an_error():
    _, findings = jaxprs.collect_engine_folds(
        engines={"fake": _FakeEngine(NotImplementedError("no hook"))},
        sim=object())
    hits = [f for f in findings if f.rule == "jaxpr-hook"]
    assert hits and hits[0].path == "engine:fake"


def test_jaxpr_host_optout_is_recorded_not_traced():
    folds, findings = jaxprs.collect_engine_folds(
        engines={"fake": _FakeEngine(None)}, sim=object())
    assert findings == []
    assert folds[0].host and folds[0].n_primitives == 0


# ---------------------------------------------------------------------------
# Baseline round-trip
# ---------------------------------------------------------------------------


def _fold(key, n, host=False):
    engine, _, label = key.partition("/")
    return jaxprs.EngineFold(engine=engine, label=label or "host",
                             n_primitives=n, primitive_counts={},
                             host=host)


def test_baseline_round_trip(tmp_path):
    import jax

    path = tmp_path / "baseline.json"
    folds = [_fold("scan/end_time", 100), _fold("oracle/host", 0, True)]
    doc = _baseline.save_baseline(folds, path)
    assert doc["jax"] == jax.__version__
    loaded = _baseline.load_baseline(path)
    assert loaded == json.loads(path.read_text())
    assert _baseline.check_budgets(folds, loaded) == []


def test_baseline_budget_regression_and_improvement(tmp_path):
    base = {"jax": __import__("jax").__version__,
            "budgets": {"scan/end_time": 100}, "host_engines": []}
    over = _baseline.check_budgets([_fold("scan/end_time", 120)], base)
    assert [f.severity for f in over] == ["error"]
    under = _baseline.check_budgets([_fold("scan/end_time", 80)], base)
    assert [f.severity for f in under] == ["info"]
    within = _baseline.check_budgets([_fold("scan/end_time", 108)], base)
    assert within == []


def test_baseline_missing_fold_and_stale_entry(tmp_path):
    base = {"jax": __import__("jax").__version__,
            "budgets": {"gone/end_time": 50}, "host_engines": []}
    findings = _baseline.check_budgets([_fold("new/end_time", 10)], base)
    by_rule = {(f.path, f.severity) for f in findings}
    assert ("new/end_time", "error") in by_rule     # unbudgeted fold
    assert ("gone/end_time", "info") in by_rule     # stale entry


def test_baseline_jax_mismatch_downgrades_to_info():
    """A baseline from another jax version no longer downgrades a
    regression: the gate fires on the installed version alike."""
    base = {"jax": "0.0.0", "budgets": {"scan/end_time": 100},
            "host_engines": []}
    findings = _baseline.check_budgets([_fold("scan/end_time", 200)], base)
    assert [f.is_error for f in findings] == [True]


def test_no_baseline_is_an_error():
    findings = _baseline.check_budgets([_fold("scan/end_time", 1)], None)
    assert [f.is_error for f in findings] == [True]


# ---------------------------------------------------------------------------
# Findings / report plumbing
# ---------------------------------------------------------------------------


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError):
        Finding(rule="r", path="p", line=1, message="m", severity="warn")


def test_render_text_and_json_agree():
    fs = [Finding(rule="r", path="b.py", line=2, message="m"),
          Finding(rule="r", path="a.py", line=1, message="m",
                  severity="info")]
    text = render_text(fs, n_files=2, n_engines=0)
    assert text.splitlines()[0].startswith("a.py:1")      # sorted
    assert "1 error(s), 1 info note(s)" in text
    doc = json.loads(render_json(fs, n_files=2, n_engines=0))
    assert (doc["errors"], doc["infos"]) == (1, 1)
    assert len(doc["findings"]) == 2


# ---------------------------------------------------------------------------
# CLI: exit codes and repo cleanliness
# ---------------------------------------------------------------------------


def _fixture_tree(tmp_path, source):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(textwrap.dedent(source))
    return tmp_path


def test_cli_fails_on_bad_tree_names_the_rule(tmp_path, capsys):
    from repro.analysis.cli import main

    root = _fixture_tree(tmp_path, """
        import numpy as np
        x = np.random.rand(4)
    """)
    code = main(["--check", "--no-jaxpr", "--root", str(root)])
    out = capsys.readouterr().out
    assert code == 1
    assert "[rng-global]" in out


def test_cli_passes_on_clean_tree(tmp_path, capsys):
    from repro.analysis.cli import main

    root = _fixture_tree(tmp_path, "x = 1\n")
    code = main(["--check", "--no-jaxpr", "--root", str(root)])
    assert code == 0
    assert "0 error(s)" in capsys.readouterr().out


def test_cli_json_output(tmp_path, capsys):
    from repro.analysis.cli import main

    root = _fixture_tree(tmp_path, "import random\nx = random.random()\n")
    code = main(["--check", "--json", "--no-jaxpr", "--root", str(root)])
    doc = json.loads(capsys.readouterr().out)
    assert code == 1 and doc["errors"] == 1
    assert doc["findings"][0]["rule"] == "rng-global"


def test_module_entry_point_runs():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--help"],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0 and "--baseline" in out.stdout


def test_repo_ast_layer_is_clean():
    paths = [REPO / sub for sub in SCAN_ROOTS if (REPO / sub).exists()]
    findings, n_files = astlint.lint_paths(paths, root=REPO)
    assert n_files > 50
    assert [f.format() for f in findings] == []


# ---------------------------------------------------------------------------
# The real registry: full jaxpr-layer pass (the regression pin for the
# weak-f64 fixes in sim.py's squaring table and chunk-fold energy path)
# ---------------------------------------------------------------------------


def test_repo_jaxpr_layer_covers_all_engines_and_is_clean():
    from repro.core import api

    folds, findings = jaxprs.collect_engine_folds()
    assert [f.format() for f in findings] == []
    covered = {f.engine for f in folds}
    assert covered == set(api.registered_engines())
    traced = {f.key for f in folds if not f.host}
    assert {"scan/end_time", "scan/dispatch", "prefix/end_time",
            "squaring/end_time", "pallas/end_time",
            "streaming/chunk_fold"} <= traced
    # Budgets against the committed baseline must hold as-committed.
    budget = _baseline.check_budgets(
        folds, _baseline.load_baseline())
    assert [f.format() for f in budget if f.is_error] == []


def test_repo_padding_identity_bitwise():
    assert [f.format() for f in jaxprs.check_padding_identity()] == []
