"""devcheck — runtime invariant checkers for the device pipeline (ISSUE 8).

The runtime twin of tools/tmlint: where tmlint flags call SITES, devcheck
asserts the invariants while the pipeline actually runs. Env-gated —
``TM_TPU_DEVCHECK=1`` (or ``devcheck.enable()`` from a test) turns it on;
off (the default) every hook is a single boolean check, no allocation, no
locking, so production paths pay nothing.

Three checkers:

1. **Device-thread assertions** — the dispatch-owner thread (the ONLY
   thread allowed to touch the device) claims ownership via
   ``claim_device()``; the launch/transfer/table-upload entry points call
   ``note_device_touch()``, which raises (and records) when any OTHER
   thread reaches them. ``exempt()`` marks the sanctioned direct paths
   (oversized-batch fallback, warmup) so they do not false-positive.

2. **Lock-order cycle detector** — ``devcheck.lock(name)`` /
   ``rlock(name)`` wrap the coalescer/dispatcher/resolver/metrics locks
   when devcheck is on at CREATION time (plain ``threading.Lock`` when
   off — zero overhead). Each acquisition records an edge held→acquired
   in a process-wide lock-ORDER graph keyed by lock *name* (order classes,
   not instances); the first edge that closes a cycle raises with the
   offending path. A cycle in the order graph is a deadlock waiting for
   the right interleaving, even if this run never hit it.

3. **Write-after-resolve canary** — the resolver registers every verdict
   array it delivers (``canary_register``) with a byte snapshot;
   subsequent sweeps (next resolve, pool-slot release, pipeline close)
   verify the delivered bytes are still identical. A future resolved with
   a zero-copy view of a donated XLA buffer — the PR-7 bug — trips the
   canary the moment a later launch recycles the page. On slot release
   the checker also best-effort poisons the slot's device buffers
   (backends that expose writable host views get 0xAB scribbles, making
   any lingering alias detectable immediately; backends that do not still
   get the byte-stability verification).

Violations are recorded in a process-wide list (``violations()``) and —
for the device and lock checkers, where the failing stack IS the bug —
also raised as ``DevcheckViolation`` at the offending call site. The
canary records without raising (the mutation is detected asynchronously,
on a thread that did nothing wrong); drive ``check()`` from tests.

Test seams: ``TM_TPU_INJECT_LINTBUG=alias|owner`` re-introduces the PR-7
readback aliasing / a resolver-thread device touch inside ops/pipeline.py
(mirroring simnet's ``--inject-bug``), so tier-1 proves each checker
actually fires (tests/test_devcheck.py).

Stdlib + numpy only; importable without jax.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Set

import numpy as np

_ON = os.environ.get("TM_TPU_DEVCHECK", "") == "1"

_mtx = threading.Lock()  # guards all devcheck global state below
_violations: List[dict] = []
_counts: Dict[str, int] = {"device_touches": 0, "lock_acquires": 0,
                           "canary_checks": 0, "canary_registered": 0,
                           "span_opens": 0}
_device_owners: Set[int] = set()
_lock_edges: Dict[str, Set[str]] = {}
_tls = threading.local()  # .held: list of lock names; .exempt: int depth
# unbalanced-span canary (ISSUE 10): thread ident -> open span names, in
# nesting order. Fed by observability.trace._Span when devcheck is armed;
# span_check() asserts every stack drained (tracer close, pipeline close).
_open_spans: Dict[int, List[str]] = {}

_CANARY_RING = 64
_canaries: "OrderedDict[int, tuple]" = OrderedDict()  # id -> (tag, arr, snap)


class DevcheckViolation(RuntimeError):
    """A devcheck invariant failed; the message carries the context."""


# ---------------------------------------------------------------------------
# enable / disable / reporting


def enabled() -> bool:
    return _ON


def enable(reset: bool = False) -> None:
    """Turn the checkers on (tests; production uses TM_TPU_DEVCHECK=1 so
    import-time lock creation is instrumented too)."""
    global _ON
    if reset:
        reset_state()
    _ON = True


def disable() -> None:
    global _ON
    _ON = False


def reset_state() -> None:
    with _mtx:
        _violations.clear()
        _device_owners.clear()
        _lock_edges.clear()
        _canaries.clear()
        _open_spans.clear()
        for k in _counts:
            _counts[k] = 0


def _violate(kind: str, message: str) -> dict:
    rec = {
        "kind": kind,
        "message": message,
        "thread": threading.current_thread().name,
    }
    with _mtx:
        _violations.append(rec)
    return rec


def violations() -> List[dict]:
    with _mtx:
        return list(_violations)


def check() -> None:
    """Raise if any violation has been recorded (test teardown hook)."""
    v = violations()
    if v:
        lines = "\n".join(f"  [{r['kind']}] {r['message']} "
                          f"(thread {r['thread']})" for r in v)
        raise DevcheckViolation(f"{len(v)} devcheck violation(s):\n{lines}")


def report() -> dict:
    """JSON-embeddable snapshot (tools/simnet_run.py --devcheck)."""
    with _mtx:
        return {
            "enabled": _ON,
            "violations": list(_violations),
            "counts": dict(_counts),
            "lock_order_edges": int(sum(len(v) for v in _lock_edges.values())),
            "open_spans": int(sum(len(s) for s in _open_spans.values())),
        }


def _bump(key: str) -> None:
    with _mtx:
        _counts[key] += 1


# ---------------------------------------------------------------------------
# 1) device-thread assertions


def claim_device(name: str = "") -> None:
    """The dispatch-owner thread claims the device. Multiple verifiers may
    each claim (one dispatcher per instance); any NON-claimed thread
    reaching a device entry point afterwards is a violation."""
    if not _ON:
        return
    with _mtx:
        _device_owners.add(threading.get_ident())


def clear_device() -> None:
    with _mtx:
        _device_owners.clear()


def unclaim_device(idents) -> None:
    """Drop specific thread idents from the owner set — a closing
    verifier retires its dispatcher's claim so (a) later standalone
    direct use stays legal and (b) OS thread-ident reuse cannot hand a
    dead owner's pass to an arbitrary new thread. Safe with devcheck
    off (the set is empty)."""
    with _mtx:
        _device_owners.difference_update(idents)


class _Exempt:
    def __enter__(self):
        _tls.exempt = getattr(_tls, "exempt", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.exempt -= 1
        return False


def exempt() -> _Exempt:
    """Context manager marking a sanctioned direct device path (oversized
    fallback, warmup) on the current thread."""
    return _Exempt()


def note_device_touch(what: str) -> None:
    """Assert the current thread may touch the device. No-op until a
    dispatcher has claimed ownership (standalone/direct use stays legal);
    afterwards only owner threads and exempt() scopes pass."""
    if not _ON:
        return
    _bump("device_touches")
    if getattr(_tls, "exempt", 0):
        return
    with _mtx:
        owners = set(_device_owners)
    if not owners:
        return
    ident = threading.get_ident()
    if ident not in owners:
        rec = _violate(
            "device-ownership",
            f"{what}: device touched from thread "
            f"{threading.current_thread().name!r} (ident {ident}) but the "
            f"device is owned by dispatcher ident(s) {sorted(owners)} — "
            f"exactly ONE dispatch-owner thread may launch/transfer",
        )
        raise DevcheckViolation(rec["message"])


# ---------------------------------------------------------------------------
# 1b) unbalanced-span canary (ISSUE 10 satellite)
#
# observability.trace._Span reports every enter/exit here when devcheck is
# armed; span_check() (tracer close, pipeline close) asserts that every
# thread's stack drained. A span left open — an early return or exception
# path that dodged the `with` discipline, or a hand-called __enter__ —
# corrupts the flame-graph nesting every summary trusts, silently.


def span_opened(name: str) -> None:
    if not _ON:
        return
    ident = threading.get_ident()
    with _mtx:
        _counts["span_opens"] += 1
        _open_spans.setdefault(ident, []).append(name)


def span_closed(name: str) -> None:
    """Pop the most recent matching open span. Unconditional on the live
    flag like DevLock.release: disabling devcheck mid-span must not leave
    a stale entry that later reads as a leak."""
    if not _open_spans:
        # nothing was ever pushed (devcheck never armed): skip the lock —
        # this keeps the tracing-enabled/devcheck-off path allocation- and
        # contention-free (the racy read only ever skips when empty)
        return
    ident = threading.get_ident()
    with _mtx:
        stack = _open_spans.get(ident)
        if not stack:
            return
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == name:
                del stack[i]
                break
        if not stack:
            _open_spans.pop(ident, None)


def span_check(where: str, only_exited: bool = False) -> None:
    """Assert no span is left open (tracer `close()`, verifier close).
    `only_exited=True` restricts the check to threads that are no longer
    alive — the right scope for a component close() racing unrelated
    live threads legitimately mid-span (a span on a DEAD thread can
    never be closed, so it is always a leak). Raises with the per-thread
    leftovers; only the REPORTED entries are cleared, so a live thread's
    in-progress bookkeeping is never corrupted and one leak does not
    re-report at every subsequent checkpoint."""
    if not _ON:
        return
    names = {t.ident: t.name for t in threading.enumerate()}
    with _mtx:
        leftover = {
            i: list(s)
            for i, s in _open_spans.items()
            if s and not (only_exited and i in names)
        }
        for i in leftover:
            _open_spans.pop(i, None)
    if not leftover:
        return
    detail = "; ".join(
        f"{names.get(i, 'exited-thread')}({i}): {s}"
        for i, s in sorted(leftover.items())
    )
    rec = _violate(
        "unbalanced-span",
        f"{sum(len(s) for s in leftover.values())} span(s) left open at "
        f"{where} — every span must close on the thread that opened it "
        f"({detail})",
    )
    raise DevcheckViolation(rec["message"])


# ---------------------------------------------------------------------------
# 2) lock-order cycle detector


def _held() -> list:
    h = getattr(_tls, "held", None)
    if h is None:
        h = _tls.held = []
    return h


def _reaches(src: str, dst: str, edges: Dict[str, Set[str]]) -> Optional[list]:
    """DFS path src -> dst in the order graph, or None."""
    stack = [(src, [src])]
    seen = set()
    while stack:
        node, path = stack.pop()
        if node == dst:
            return path
        if node in seen:
            continue
        seen.add(node)
        for nxt in edges.get(node, ()):
            stack.append((nxt, path + [nxt]))
    return None


def _note_intent(name: str) -> Optional[list]:
    """Record the prospective order edge BEFORE the blocking acquire and
    return the cycle path if this edge closes one (None otherwise).
    Intent-time recording is what lets a CONTESTED inversion be reported
    instead of hanging: edge insertion + cycle check serialize under
    _mtx, so of two threads deadlocking each other at first contact, the
    second one's check must see the first one's edge and raise before
    ever blocking."""
    _bump("lock_acquires")
    held = _held()
    if not held or held[-1] == name:
        return None
    holder = held[-1]
    with _mtx:
        fwd = _lock_edges.setdefault(holder, set())
        new_edge = name not in fwd
        fwd.add(name)
        return _reaches(name, holder, _lock_edges) if new_edge else None


def _note_released(name: str) -> None:
    held = _held()
    # release order may differ from acquire order (handoffs); remove the
    # most recent matching entry
    for i in range(len(held) - 1, -1, -1):
        if held[i] == name:
            del held[i]
            return


def _redepth() -> dict:
    d = getattr(_tls, "redepth", None)
    if d is None:
        d = _tls.redepth = {}
    return d


class DevLock:
    """A named threading.Lock/RLock wrapper feeding the order graph.
    Supports the full lock protocol (with-statement, Condition wrapping,
    timeout/blocking acquire). Reentrant acquisitions of the same RLock
    do not re-record (per-thread depth counter, so the stack pop pairs
    with the OUTERMOST acquire).

    Stack bookkeeping is deliberately NOT gated on the live _ON flag at
    release time: a test disabling devcheck between an acquire and its
    release must still pop the armed-time push, or the stale entry
    manufactures phantom order edges (and false cycles) for every later
    acquisition on that thread."""

    def __init__(self, name: str, reentrant: bool = False):
        self.name = name
        self._l = threading.RLock() if reentrant else threading.Lock()
        self._reentrant = reentrant

    def acquire(self, blocking: bool = True, timeout: float = -1):
        """The order edge is recorded (and the cycle check runs) BEFORE
        the blocking acquire — a contested AB/BA inversion raises on one
        of the two threads instead of wedging both with no diagnostic.

        On a detected cycle: try a NON-blocking acquire first. If it
        succeeds the violation raises with the lock HELD (what both a
        bare acquire() caller and Condition._acquire_restore — cv.wait's
        re-acquire, whose enclosing `with cv:` later releases — expect);
        if the lock is contended, that IS the live deadlock, and the
        violation raises WITHOUT the lock (hanging is the alternative).
        The exception's `lock_held` attribute says which happened;
        __enter__ uses it to release only what was taken."""
        if _ON:
            if self._reentrant and _redepth().get(self.name, 0) > 0:
                ok = self._l.acquire(blocking, timeout)
                if ok:
                    _redepth()[self.name] += 1
                return ok  # re-entry: no new order edge, no push
            back = _note_intent(self.name)
            if back is not None:
                got = self._l.acquire(False)
                rec = _violate(
                    "lock-order",
                    f"acquiring {self.name!r} while holding {back[-1]!r} "
                    f"closes a cycle in the lock-order graph: "
                    f"{' -> '.join(back)} -> {self.name} — a deadlock "
                    f"under the right interleaving"
                    + ("" if got else " (lock contended: a LIVE deadlock "
                                      "was avoided; lock NOT acquired)"),
                )
                e = DevcheckViolation(rec["message"])
                e.lock_held = bool(got)
                raise e
        ok = self._l.acquire(blocking, timeout)
        if ok and _ON:
            if self._reentrant:
                _redepth()[self.name] = 1
            _held().append(self.name)
        return ok

    def release(self) -> None:
        if self._reentrant:
            d = _redepth()
            n = d.get(self.name, 0)
            if n > 1:
                d[self.name] = n - 1
                self._l.release()
                return
            d.pop(self.name, None)
        _note_released(self.name)  # unconditional: pairs any armed push
        self._l.release()

    def __enter__(self):
        try:
            self.acquire()  # tmlint: disable=lock-discipline — this IS the context manager
        except DevcheckViolation as e:
            # __exit__ never runs when __enter__ raises — release here
            # (when the violation path actually took the lock) or the
            # reported POTENTIAL deadlock becomes a real one
            if getattr(e, "lock_held", True):
                self._l.release()
            raise
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self) -> bool:  # Lock protocol completeness
        locked = getattr(self._l, "locked", None)
        return locked() if locked is not None else False


def lock(name: str):
    """A lock for `name`: instrumented when devcheck is on at creation
    time, a plain threading.Lock otherwise (zero overhead off)."""
    return DevLock(name) if _ON else threading.Lock()


def rlock(name: str):
    return DevLock(name, reentrant=True) if _ON else threading.RLock()


# ---------------------------------------------------------------------------
# 3) write-after-resolve canary


def canary_register(arr, tag: str = "") -> None:
    """Snapshot a delivered verdict array; later sweeps verify the bytes
    never change. Ring-bounded (the last _CANARY_RING resolutions)."""
    if not _ON or not isinstance(arr, np.ndarray):
        return
    snap = arr.tobytes()
    with _mtx:
        _counts["canary_registered"] += 1
        _canaries[id(arr)] = (tag, arr, snap)
        while len(_canaries) > _CANARY_RING:
            _canaries.popitem(last=False)


def canary_sweep(where: str) -> int:
    """Verify every registered verdict array is byte-stable. Returns the
    number of violations found (each registered once, then dropped).
    Records without raising — the sweeping thread is not the culprit."""
    if not _ON:
        return 0
    with _mtx:
        items = list(_canaries.items())
    bad = []
    for key, (tag, arr, snap) in items:
        _bump("canary_checks")
        try:
            now = arr.tobytes()
        except Exception:  # noqa: BLE001 — a freed buffer IS the finding
            now = None
        if now != snap:
            bad.append(key)
            _violate(
                "write-after-resolve",
                f"verdict array ({tag}) mutated AFTER resolution "
                f"(detected at {where}) — a future was resolved with a "
                f"non-owning view of a recycled device buffer (the PR-7 "
                f"donation-aliasing class); resolve with np.array/.copy()",
            )
    if bad:
        with _mtx:
            for k in bad:
                _canaries.pop(k, None)
    return len(bad)


def canary_clear() -> None:
    with _mtx:
        _canaries.clear()


def on_slot_release(arrays) -> None:
    """Pool-slot return hook: sweep the canaries, then poison the slot's
    buffers where the backend exposes writable host views (0xAB scribble)
    so any alias still pointing at them fails the NEXT sweep loudly."""
    if not _ON:
        return
    canary_sweep("pool.release")
    if not arrays:
        return
    for a in arrays:
        if isinstance(a, np.ndarray):
            continue  # host array passthrough — may be shared, never poison
        try:
            v = np.asarray(a)
            if v.flags.writeable:
                v.fill(0xAB)
        except Exception:  # noqa: BLE001 — poisoning is best-effort
            pass


# ---------------------------------------------------------------------------
# injected-bug seams (tests only; mirrors simnet's --inject-bug pattern)


def inject_lintbug(kind: str) -> bool:
    """True when TM_TPU_INJECT_LINTBUG names this seam AND devcheck is
    armed. The devcheck gate is load-bearing: the seams deliberately
    corrupt verdicts / touch the device cross-thread, so a stale env
    export with the checkers off must stay inert. Read per call so tests
    can flip it via monkeypatch.setenv without reimporting."""
    return _ON and os.environ.get("TM_TPU_INJECT_LINTBUG", "") == kind
