"""lock-discipline — bare acquisitions and unauditable thread targets.

Two shapes this repo has been burned by:

1. Bare `lock.acquire()` as a statement. A `with lock:` block releases on
   every exit path; a bare acquire leaks the lock on any exception
   between acquire and release (the PR-1 metrics self-deadlock was this
   family). Semaphores are exempt — the pipeline's depth semaphore is
   deliberately acquired and released on DIFFERENT threads (dispatcher /
   resolver), which a context manager cannot express; receivers with
   "sem" in the name do not match. Cross-method Lock/Unlock APIs that
   mirror the Go reference (mempool.Mempool.Lock) carry an explicit
   suppression with justification.

2. `threading.Thread(target=...)` where the target is a lambda (nothing
   to audit) or, outside the device whitelist, a same-module function
   whose body calls device entry points — a thread that would touch the
   device without being the dispatch-owner. The runtime twin of this
   check is devcheck's device-thread assertion.

3. `fut.result()` under a mutex (ISSUE 13): a `.result()` call inside a
   `with <...mtx...>:` block parks the lock across a device round-trip.
   If the thread that completes that future ever needs the same lock
   (the ingress completer finishing CheckTx needs the mempool's `_mtx`),
   that's a deadlock, and even when it isn't, every other lock client
   stalls for a full device RTT. Scoped to receivers whose name contains
   "mtx" — the repo's convention for state mutexes — so coordination
   locks built FOR result-collection (pipeline.py's `done_lock`) don't
   false-positive. Wait on futures outside the lock, or hand completion
   to a dedicated thread (mempool/ingress.py's completer).

4. Dispatch `submit()` under a mutex (ISSUE 15): submitting to the
   shared verifier can BLOCK on the pipeline's depth semaphore when the
   device queue is full, so a `<verifier>.submit(...)` inside a
   `with <...mtx...>:` block parks the state mutex across the
   dispatcher's backpressure — and the verdict callback that would
   relieve it usually needs that same lock (the vote accumulator's
   window mutex, the mempool's `_mtx`). The vote-ingress submit path is
   the reference shape: stage under `_mtx`, pop the window, release,
   THEN submit (consensus/vote_ingress.py's `_flush_window`). Scoped to
   verifier-ish receivers ("verifier"/"ingress" in the name, the `_v`
   handle convention, or a `shared_verifier()`/`_ensure_verifier()`
   chain) so executor pools (`prep_pool.submit`) stay out of scope.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator

from ..core import FileContext, Finding, Rule
from . import func_name, receiver_name
from .device import ENTRY_POINTS, WHITELIST


def _terminal_receiver(call: ast.Call) -> str:
    """self._mtx.acquire() -> '_mtx' (the attr nearest the call)."""
    if isinstance(call.func, ast.Attribute):
        inner = call.func.value
        if isinstance(inner, ast.Attribute):
            return inner.attr
        if isinstance(inner, ast.Name):
            return inner.id
    return ""


def _ctx_name(expr: ast.AST) -> str:
    """`with self._mtx:` / `with mtx:` -> the lock's terminal name."""
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return ""


# shape-4 scoping: which `.submit()` receivers count as a pipeline
# dispatch (vs. an executor pool, which is non-blocking bookkeeping)
_DISPATCH_RECEIVER_SUBSTR = ("verifier", "ingress")
_DISPATCH_RECEIVER_EXACT = ("_v", "v")
_DISPATCH_CHAIN_CALLS = ("shared_verifier", "_ensure_verifier")


def _is_dispatch_submit(call: ast.Call) -> bool:
    """`<verifier-ish>.submit(...)` — including the repo's
    `self._ensure_verifier().submit(...)` / `shared_verifier().submit(...)`
    lazy-handle chains, whose immediate receiver is a Call, not a Name."""
    if func_name(call) != "submit":
        return False
    recv = receiver_name(call)
    if recv:
        low = recv.lower()
        return (any(s in low for s in _DISPATCH_RECEIVER_SUBSTR)
                or recv in _DISPATCH_RECEIVER_EXACT)
    if isinstance(call.func, ast.Attribute) and isinstance(
            call.func.value, ast.Call):
        return func_name(call.func.value) in _DISPATCH_CHAIN_CALLS
    return False


def _walk_same_frame(nodes) -> Iterator[ast.AST]:
    """Walk statements WITHOUT descending into nested function/lambda
    bodies — code in a `def` inside a `with` block runs later, on some
    other thread's frame, not under this lock."""
    stack = list(nodes)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


class LockDisciplineRule(Rule):
    name = "lock-discipline"
    description = (
        "locks are acquired via context managers (semaphores exempt); "
        "thread targets must be auditable and device-clean"
    )

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith("tendermint_tpu/")

    # -- helpers ---------------------------------------------------------

    @staticmethod
    def _local_functions(tree: ast.AST) -> Dict[str, ast.AST]:
        fns: Dict[str, ast.AST] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, node)
        return fns

    @staticmethod
    def _touches_device(fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and func_name(node) in ENTRY_POINTS:
                return True
        return False

    # -- visit -----------------------------------------------------------

    def visit(self, ctx: FileContext) -> Iterator[Finding]:
        local_fns = self._local_functions(ctx.tree)
        whitelisted = ctx.path in WHITELIST
        for node in ast.walk(ctx.tree):
            # 1) bare `x.acquire()` as a statement
            if (isinstance(node, ast.Expr)
                    and isinstance(node.value, ast.Call)
                    and func_name(node.value) == "acquire"):
                recv = _terminal_receiver(node.value)
                if "sem" not in recv.lower():
                    yield ctx.finding(
                        self.name, node,
                        f"bare `{recv or '<expr>'}.acquire()` — use "
                        f"`with {recv or 'lock'}:` so every exit path "
                        f"releases (cross-thread handoffs are what "
                        f"semaphores are for)",
                    )
            # 3) `fut.result()` while holding a state mutex
            if isinstance(node, ast.With):
                lock = ""
                for item in node.items:
                    name = _ctx_name(item.context_expr)
                    if "mtx" in name.lower():
                        lock = name
                        break
                if lock:
                    for sub in _walk_same_frame(node.body):
                        if (isinstance(sub, ast.Call)
                                and func_name(sub) == "result"):
                            yield ctx.finding(
                                self.name, sub,
                                f"`.result()` inside `with {lock}:` parks "
                                f"the mutex across a future's round-trip — "
                                f"deadlock bait if the completing thread "
                                f"needs {lock}; wait outside the lock or "
                                f"complete on a dedicated thread",
                            )
                        # 4) dispatch submit while holding the mutex
                        elif (isinstance(sub, ast.Call)
                                and _is_dispatch_submit(sub)):
                            yield ctx.finding(
                                self.name, sub,
                                f"pipeline `submit()` inside `with {lock}:` "
                                f"— submit blocks on the dispatcher's depth "
                                f"semaphore under backpressure, parking "
                                f"{lock} until the device drains; stage "
                                f"under the lock, release, then submit "
                                f"(see consensus/vote_ingress.py "
                                f"_flush_window)",
                            )
            # 2) thread targets
            if isinstance(node, ast.Call) and func_name(node) == "Thread":
                if receiver_name(node) not in ("threading", ""):
                    continue
                target = None
                for kw in node.keywords:
                    if kw.arg == "target":
                        target = kw.value
                if target is None:
                    continue
                if isinstance(target, ast.Lambda):
                    yield ctx.finding(
                        self.name, node,
                        "thread target is a lambda — name the function so "
                        "its lock/device behavior is auditable",
                    )
                elif not whitelisted and isinstance(target, ast.Name):
                    fn = local_fns.get(target.id)
                    if fn is not None and self._touches_device(fn):
                        yield ctx.finding(
                            self.name, node,
                            f"thread target `{target.id}` calls device entry "
                            f"points outside the dispatcher whitelist — "
                            f"only ops/pipeline.py's dispatch-owner thread "
                            f"may touch the device",
                        )
