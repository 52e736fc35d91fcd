"""tmlint rule registry + shared AST helpers.

Each pass lives in its own module and encodes ONE invariant the repo has
already paid for in a real bug or a hard design rule (see each module's
docstring for the incident it guards). Register new passes in ALL_RULES.
"""

from __future__ import annotations

import ast
from typing import Optional

from ..core import Rule  # noqa: F401  (re-export for subclass authors)


def func_name(call: ast.Call) -> str:
    """Terminal callee name: `a.b.c(...)` -> 'c', `f(...)` -> 'f'."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def receiver_name(call: ast.Call) -> str:
    """Immediate receiver of an attribute call: `a.b.c(...)` -> 'b',
    `np.asarray(...)` -> 'np', plain `f(...)` -> ''."""
    f = call.func
    if isinstance(f, ast.Attribute):
        v = f.value
        if isinstance(v, ast.Attribute):
            return v.attr
        if isinstance(v, ast.Name):
            return v.id
    return ""


def dotted(node: ast.AST) -> str:
    """Best-effort dotted path of a Name/Attribute chain ('' otherwise)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def iter_functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


from .determinism import SimnetDeterminismRule  # noqa: E402
from .fleet import FleetTransportRule  # noqa: E402
from .ingress import IngressDisciplineRule  # noqa: E402
from .donation import DonationAliasingRule  # noqa: E402
from .locks import LockDisciplineRule  # noqa: E402
from .purity import HotPathPurityRule  # noqa: E402
from .device import DeviceOwnershipRule  # noqa: E402

ALL_RULES = [
    DonationAliasingRule(),
    IngressDisciplineRule(),
    DeviceOwnershipRule(),
    FleetTransportRule(),
    SimnetDeterminismRule(),
    HotPathPurityRule(),
    LockDisciplineRule(),
]

RULES_BY_NAME = {r.name: r for r in ALL_RULES}
