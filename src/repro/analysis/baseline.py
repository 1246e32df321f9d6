"""Committed per-engine primitive-count budgets (DESIGN.md §2.9).

``baseline.json`` (next to this module) records, per canonical fold,
the total jaxpr equation count measured at commit time.  The
``jaxpr-budget`` rule compares fresh counts against it:

* fold missing from the baseline (new engine / new hook) — **error**:
  run ``python -m repro.analysis --baseline`` and commit the result;
* count grew beyond ``+10%`` — **error**: a compile-size regression
  (an accidental unroll, a lost fusion) fails CI loudly;
* count shrank below ``-10%`` — **info**: an improvement worth
  locking in with a baseline refresh;
* baseline entry with no live fold — **info**: stale entry.

The file also records the jax version it was measured under, so a
refresh after a jax upgrade shows in its diff; the gate fires on every
version alike.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

from repro.analysis.findings import Finding
from repro.analysis.jaxprs import EngineFold

DEFAULT_BASELINE = Path(__file__).with_name("baseline.json")

#: relative growth/shrink tolerance before the budget rule fires
BUDGET_TOLERANCE = 0.10


def save_baseline(folds: list[EngineFold],
                  path: Path = DEFAULT_BASELINE) -> dict:
    import jax

    doc = {
        "jax": jax.__version__,
        "budgets": {f.key: f.n_primitives for f in folds if not f.host},
        "host_engines": sorted(f.engine for f in folds if f.host),
    }
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return doc


def load_baseline(path: Path = DEFAULT_BASELINE) -> dict | None:
    if not path.exists():
        return None
    return json.loads(path.read_text())


def check_budgets(folds: list[EngineFold],
                  baseline: dict | None) -> list[Finding]:
    if baseline is None:
        return [Finding(
            rule="jaxpr-budget", path=str(DEFAULT_BASELINE), line=0,
            message="no committed baseline; run `python -m "
                    "repro.analysis --baseline` and commit the result")]

    findings: list[Finding] = []
    budgets = dict(baseline.get("budgets", {}))
    hosts = set(baseline.get("host_engines", []))
    for fold in folds:
        if fold.host:
            if fold.engine not in hosts:
                findings.append(Finding(
                    rule="jaxpr-budget", path=fold.key, line=0,
                    message=f"host engine {fold.engine!r} not recorded "
                            "in baseline (run --baseline)"))
            continue
        budget = budgets.pop(fold.key, None)
        if budget is None:
            findings.append(Finding(
                rule="jaxpr-budget", path=fold.key, line=0,
                message=f"fold not in baseline ({fold.n_primitives} "
                        "primitives measured); run --baseline"))
            continue
        hi = math.ceil(budget * (1 + BUDGET_TOLERANCE))
        lo = math.floor(budget * (1 - BUDGET_TOLERANCE))
        if fold.n_primitives > hi:
            findings.append(Finding(
                rule="jaxpr-budget", path=fold.key, line=0,
                message=f"primitive count {fold.n_primitives} exceeds "
                        f"budget {budget} (+{BUDGET_TOLERANCE:.0%} = "
                        f"{hi}): compile-size regression"))
        elif fold.n_primitives < lo:
            findings.append(Finding(
                rule="jaxpr-budget", path=fold.key, line=0,
                severity="info",
                message=f"primitive count {fold.n_primitives} is below "
                        f"budget {budget} (-{BUDGET_TOLERANCE:.0%}): "
                        "improvement — refresh the baseline to lock in"))
    for key in sorted(budgets):
        findings.append(Finding(
            rule="jaxpr-budget", path=key, line=0, severity="info",
            message="baseline entry has no live fold (stale); "
                    "run --baseline"))
    return findings
