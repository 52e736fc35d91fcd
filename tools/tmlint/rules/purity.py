"""hot-path-purity — per-signature Python loops in the columnar modules.

PRs 2/4 moved the commit-verify hot path to columnar-from-decode: one
GIL-released fused call (or grouped numpy) per BATCH, never per
signature. The three modules below are the columnar core; a `for` loop
that walks signatures one Python iteration at a time (or grows a list
with per-element .append) re-introduces exactly the per-tuple cost those
PRs removed — at 10k signatures that is the difference between ~0.3 ms
and ~15 ms of GIL-held host time per commit.

What counts as per-element (and gets flagged):
  - `for i in range(len(x))` / `range(n)` / `range(self.n)` / `range(x.n)`
  - `for ... in enumerate(...)`
  - `for ... in entries` / `...iter_entries()` / `...to_entries()`

Grouped loops (over np.unique lengths, flag groups, blocks of jobs) are
the DESIGN — a handful of iterations regardless of batch size — and do
not match. Sanctioned object-path fallbacks are marked `# tmlint:
fallback` on the def line (shorthand for disable=hot-path-purity over the
function body); new fallbacks must be marked the same way.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding, Rule
from . import func_name

MODULES = frozenset({
    "tendermint_tpu/ops/entry_block.py",
    "tendermint_tpu/ops/commit_prep.py",
    "tendermint_tpu/wire/canonical.py",
})

_ENTRY_NAMES = {"entries"}
_ENTRY_CALLS = {"iter_entries", "to_entries", "enumerate"}
_N_NAMES = {"n"}


def _is_per_element_iter(it: ast.AST) -> bool:
    if isinstance(it, ast.Call):
        name = func_name(it)
        if name in _ENTRY_CALLS:
            return True
        if name == "range" and len(it.args) == 1:
            a = it.args[0]
            if isinstance(a, ast.Call) and func_name(a) == "len":
                return True
            if isinstance(a, ast.Name) and a.id in _N_NAMES:
                return True
            if isinstance(a, ast.Attribute) and a.attr in _N_NAMES:
                return True
        return False
    if isinstance(it, ast.Name) and it.id in _ENTRY_NAMES:
        return True
    return False


class HotPathPurityRule(Rule):
    name = "hot-path-purity"
    description = (
        "no per-signature Python for-loops / per-element appends in the "
        "columnar hot-path modules outside fallback-marked blocks"
    )

    def applies_to(self, relpath: str) -> bool:
        return relpath in MODULES

    def visit(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.For):
                continue
            if _is_per_element_iter(node.iter):
                yield ctx.finding(
                    self.name, node,
                    "per-element Python loop in a columnar hot-path module "
                    "— vectorize (grouped numpy / fused native call) or "
                    "mark the block `# tmlint: fallback` if it is a "
                    "documented object-path fallback",
                )
