"""tmlint — repo-specific static analysis for tendermint-tpu (ISSUE 8).

The codebase runs on invariants that generic linters cannot see: exactly
one dispatch-owner thread may touch the device (ops/pipeline.py), futures
must resolve to host-OWNED verdict memory (the PR-7 donation-aliasing bug
class), simnet must stay replay-exact (no wall clock / global RNG /
unordered-set scheduling in simnet/ and consensus/), the columnar hot
path must stay columnar, and locks follow a fixed discipline. tmlint
turns each of those hard-won bug classes into a mechanical AST pass so it
can never regress silently.

Usage:
    python -m tools.tmlint [paths...] [--json] [--baseline FILE]
    python -m tools.tmlint --write-baseline      # refresh LINT_BASELINE.json

Suppression:
    x = np.asarray(dev)   # tmlint: disable=donation-aliasing — <why>
A comment-only line suppresses the NEXT line too; a suppression on a
`def` line covers the whole function body. `# tmlint: fallback` on a
`def` line is shorthand for disable=hot-path-purity (a documented
object-path / pure-python fallback block). `# tmlint: disable-file=<rule>`
anywhere suppresses the rule for the whole file.

Baseline: grandfathered findings live in LINT_BASELINE.json (fingerprints
are line-number independent, keyed on rule + path + source text), so the
tree gates on NEW findings only. The tier-1 test asserts the gate.

Adding a pass: subclass `core.Rule`, implement `visit(ctx)` yielding
`core.Finding`s, and register it in `rules.ALL_RULES`. Fixture tests in
tests/test_tmlint.py take a positive, a negative, a suppressed, and a
baselined snippet per rule.
"""

from .core import (  # noqa: F401
    Finding,
    Rule,
    fingerprint_findings,
    load_baseline,
    run_paths,
    run_source,
    write_baseline,
)
from .rules import ALL_RULES  # noqa: F401

__all__ = [
    "ALL_RULES",
    "Finding",
    "Rule",
    "fingerprint_findings",
    "load_baseline",
    "run_paths",
    "run_source",
    "write_baseline",
]
