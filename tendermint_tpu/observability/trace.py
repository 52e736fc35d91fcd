"""Low-overhead span tracer for the commit-verify hot path.

Design constraints (ISSUE 1):

- **~zero cost when disabled.** `span()` checks one module-global's
  `enabled` attribute and returns a shared null context manager before any
  clock read happens; no strings are formatted, no dicts are stored.
- **Thread-safe ring buffer.** Records are fixed-size tuples written under
  a lock into a preallocated ring; the buffer never grows, old spans are
  overwritten (wraparound), and recording is O(1) per span. Spans are
  recorded per *batch* or per commit (host prep, device dispatch, device
  wait), never per signature. The lock is NOT uncontended: a commit's
  caller writes ~25 records of its own, so 32 callers block-syncing
  (with the tracer on) meet at it; it is held for two stores, and a
  thread that loses it waits inside whatever span it was recording in.
- **Nested spans.** Nesting falls out of the `with` discipline: a child's
  [start, end) interval is contained in its parent's on the same thread,
  which is exactly how Chrome-trace/Perfetto reconstruct the flame graph
  from "X" (complete) events sharing a tid.
- **Chrome-trace export.** `export_chrome()` emits the Trace Event Format
  JSON (`{"traceEvents": [...]}`) loadable in chrome://tracing or
  https://ui.perfetto.dev; `dump(path)` writes it to disk (the node's
  OnStop flushes through this so a SIGTERM run leaves a complete file).

Causal cross-node tracing (ISSUE 10):

- **Flow events.** A span may carry a correlation id (`flow=` + a
  `flow_phase` of "s"/"t"/"f" — start/step/finish); `export_chrome()`
  emits matching Trace Event Format flow events bound to the slice, so a
  vote's journey (gossip send → deliver → verify dispatch) renders as a
  clickable arrow chain in Perfetto. `next_flow()` allocates process-wide
  ids (offset above 2^32 so they never collide with a simulation's own
  deterministic per-clock flow counters).
- **Per-node tracer instances.** `SpanTracer(node=..., now=..., epoch=...)`
  stamps every exported event with a per-node pid (+ a `process_name`
  metadata event) and reads time from an injected clock — simnet gives
  each simulated node a tracer on the shared virtual clock, so one merged
  trace aligns every node on the same (virtual) timebase.
- **Merging.** `merge_traces([doc, ...])` re-keys pids and concatenates
  event streams into ONE Chrome-trace document; flow ids are preserved
  verbatim so cross-document chains stay linked.

One clock with the profiler (ISSUE 26): while the process-wide tracer is
on, a `with` span also opens a `jax.profiler.TraceAnnotation` of the same
name, so a `jax.profiler` capture holds the program's spans on the host
plane of the same `.xplane.pb` as the device's `XLA Ops`, on the
profiler's clock. Looked up lazily and only if `jax` is already imported:
this module never imports it. Tracers on an injected clock (simnet's
per-node ones) are excluded — their time is not the profiler's.

Per-thread span args (`set_thread_args`): a thread working on one unit
(the dispatcher's stages on one launch) names it once and every span it
records until the next call carries the id, so the records of one launch
join across threads without threading the id through every call.

Enable via config (`[instrumentation] tracing = true`), env
(`TM_TPU_TRACE=1`), or `configure(enabled=True)`.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..libs import devcheck as _devcheck

_PID = os.getpid()

# A record is (name, start_s, end_s, tid, args_or_None); start/end are
# readings of the tracer's clock (perf_counter by default) against the
# tracer's epoch. Flow correlation rides INSIDE args under the reserved
# keys "flow" (int id) and "flow_phase" ("s"|"t"|"f") so the tuple shape
# — and every 5-tuple consumer — stays stable.
_Record = Tuple[str, float, float, int, Optional[dict]]

# Process-wide flow-id allocator for the wall-clock tracer. Offset far
# above any simulation's per-SimClock counter (which starts at 1) so a
# merged trace never aliases two unrelated chains onto one id.
_FLOW_BASE = 1 << 32
_flow_counter = itertools.count(_FLOW_BASE + 1)


def next_flow() -> int:
    """Allocate a process-unique flow (correlation) id."""
    return next(_flow_counter)


def set_flow_domain(domain: int) -> None:
    """Re-base this process's flow allocator into a disjoint id range.

    The verification fleet (ISSUE 18) merges traces from MANY processes
    — client nodes and the fleet host — into one flight-recorder view;
    each process calls this once at startup (TM_TPU_FLEET_FLOW_DOMAIN)
    with a distinct small integer so allocated flow ids can never alias
    across the merge. Domain 0 is the default base. Flows CONTINUED
    from a wire frame keep the originator's id — that is the point: the
    chain client-submit → fleet-recv → verdict shares one id, and this
    partition guarantees the fleet's own locally-started flows stay out
    of every client's range.
    """
    global _flow_counter
    base = _FLOW_BASE + (int(domain) & 0xFFFF) * (1 << 24)
    _flow_counter = itertools.count(base + 1)

# Per-node tracers get small deterministic pids well away from real OS
# pids; assignment order is the tracer construction order.
_node_pid_mtx = threading.Lock()
_node_pids: Dict[int, int] = {}  # id(tracer) -> pid
_NODE_PID_BASE = 10_000_000

# The process-wide tracer's ring: a hub150 commit writes ~28 records, so
# a 5 s stretch at 3 ms a commit is ~47k (ISSUE 26). A 2 MB list of None
# until spans are written; ~40 MB only when full and on.
DEFAULT_CAPACITY = 262144

_annotation_cls = None


def _profiler_annotation():
    """jax.profiler.TraceAnnotation once `jax` is imported, else None."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls


class SpanTracer:
    """Ring-buffered span recorder. One process-wide wall-clock instance
    (TRACER); per-node instances (simnet) carry a node name and an
    injected clock."""

    def __init__(self, capacity: int = 16384, node: Optional[str] = None,
                 now: Optional[Callable[[], float]] = None,
                 epoch: Optional[float] = None):
        self.enabled = False
        self.node = node
        # only the wall clock is the profiler's clock
        self._annotate = now is None
        self._now = now if now is not None else time.perf_counter
        self._thread_args = threading.local()
        # inbound-flow register: a delivery driver parks the active flow
        # id here so downstream spans (consensus.verify_dispatch) can
        # continue the chain; single-threaded drivers only
        self.flow: Optional[int] = None
        self._cap = max(int(capacity), 16)
        self._buf: List[Optional[_Record]] = [None] * self._cap
        self._n = 0  # monotonic write index; wraps over _cap
        self._mtx = threading.Lock()
        self._epoch = float(epoch) if epoch is not None else self._now()

    # -- recording -----------------------------------------------------

    def record(self, name: str, start: float, end: float,
               args: Optional[dict] = None, flow: Optional[int] = None,
               flow_phase: Optional[str] = None,
               tid: Optional[int] = None) -> None:
        """Record one completed span (clock start/end). `flow`/`flow_phase`
        attach a correlation id under the reserved args keys. `tid` files
        the span under another thread than the recording one: a wait is
        its waiter's, whoever learns when it ended."""
        ambient = getattr(self._thread_args, "args", None)
        if ambient:
            args = {**ambient, **args} if args else ambient
        if flow is not None:
            args = dict(args) if args else {}
            args["flow"] = int(flow)
            args["flow_phase"] = flow_phase or "t"
        rec = (name, start, end,
               tid if tid is not None else threading.get_ident(), args)
        with self._mtx:
            self._buf[self._n % self._cap] = rec
            self._n += 1

    def record_all(self, spans) -> None:
        """record() for several completed spans of the calling thread,
        each (name, start, end, args): one look at the thread's args and
        one hold of the lock for all of them. How intervals timed
        elsewhere (the native module's GIL-free sections) enter the ring
        after the fact."""
        ambient = getattr(self._thread_args, "args", None)
        tid = threading.get_ident()
        recs = [(name, start, end, tid,
                 ({**ambient, **args} if args else ambient) if ambient
                 else args)
                for name, start, end, args in spans]
        with self._mtx:
            for rec in recs:
                self._buf[self._n % self._cap] = rec
                self._n += 1

    def span(self, name: str, flow: Optional[int] = None,
             flow_phase: Optional[str] = None, **args) -> object:
        """Context manager recording on THIS tracer (per-node instances);
        same disabled-path contract as the module-level span()."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args or None, flow, flow_phase)

    def set_thread_args(self, **args) -> None:
        """Args every span THIS thread records from now on carries (a
        span's own args win); no args clears them. Call only under an
        `enabled` check: with tracing off nothing reads them."""
        self._thread_args.args = args or None

    def flow_point(self, name: str, flow: Optional[int],
                   phase: str = "t", **args) -> None:
        """Record an instant (zero-duration) event carrying a flow id —
        how one coalesced batch fans a step/finish out to many chains."""
        if not self.enabled or flow is None:
            return
        t = self._now()
        self.record(name, t, t, args or None, flow=flow, flow_phase=phase)

    def configure(self, enabled: Optional[bool] = None,
                  capacity: Optional[int] = None) -> None:
        with self._mtx:
            if capacity is not None and int(capacity) != self._cap:
                self._cap = max(int(capacity), 16)
                self._buf = [None] * self._cap
                self._n = 0
        if enabled is not None:
            self.enabled = bool(enabled)

    def close(self) -> None:
        """Retire the tracer: under TM_TPU_DEVCHECK=1 assert every span
        opened on every thread was closed (the unbalanced-span canary —
        a leaked span skews every summary that trusts nesting)."""
        _devcheck.span_check(f"tracer.close({self.node or 'global'})")

    def clear(self) -> None:
        with self._mtx:
            self._buf = [None] * self._cap
            self._n = 0

    # -- reading -------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self._cap

    @property
    def recorded_total(self) -> int:
        """Total spans ever recorded (>= len(events()) after wraparound)."""
        return self._n

    def events(self) -> List[_Record]:
        """Retained records, oldest first."""
        with self._mtx:
            if self._n <= self._cap:
                return [r for r in self._buf[: self._n] if r is not None]
            head = self._n % self._cap
            return [r for r in self._buf[head:] + self._buf[:head]
                    if r is not None]

    def _pid(self) -> int:
        if self.node is None:
            return _PID
        with _node_pid_mtx:
            pid = _node_pids.get(id(self))
            if pid is None:
                pid = _NODE_PID_BASE + len(_node_pids) + 1
                _node_pids[id(self)] = pid
            return pid

    def export_chrome(self) -> dict:
        """Trace Event Format dict (chrome://tracing / Perfetto JSON).
        Spans carrying a flow id additionally emit the matching flow
        event ("s"/"t"/"f", binding-point "e" on finish) at the slice's
        start timestamp, so Perfetto draws the causal arrows."""
        evs = []
        epoch = self._epoch
        pid = self._pid()
        for name, start, end, tid, args in self.events():
            ts = (start - epoch) * 1e6   # microseconds
            ev = {
                "name": name,
                "cat": "tendermint_tpu",
                "ph": "X",
                "pid": pid,
                "tid": tid,
                "ts": ts,
                "dur": (end - start) * 1e6,
            }
            if args:
                ev["args"] = args
                fid = args.get("flow")
                if fid is not None:
                    ph = args.get("flow_phase", "t")
                    if ph not in ("s", "t", "f"):
                        ph = "t"
                    fev = {
                        "name": "flow", "cat": "flow", "ph": ph,
                        "id": int(fid), "pid": pid, "tid": tid, "ts": ts,
                    }
                    if ph == "f":
                        fev["bp"] = "e"  # bind to the enclosing slice
                    evs.append(fev)
            evs.append(ev)
        evs.sort(key=lambda e: e["ts"])
        if self.node is not None:
            evs.insert(0, {
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": self.node},
            })
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        """Write the Chrome-trace JSON to `path` (returns the path)."""
        return dump_doc(self.export_chrome(), path)

    def summary(self) -> Dict[str, dict]:
        return summarize_events(self.export_chrome())


class _Span:
    """Active span: records on exit. Only built when tracing is enabled."""

    __slots__ = ("_tr", "_name", "_args", "_flow", "_phase", "_t0", "_ann",
                 "_also")

    def __init__(self, tracer: "SpanTracer", name: str, args: Optional[dict],
                 flow: Optional[int] = None, phase: Optional[str] = None):
        self._tr = tracer
        self._name = name
        self._args = args
        self._flow = flow
        self._phase = phase
        self._also = None

    def note(self, **args) -> None:
        """Args learned inside the span (a bucket the body chose)."""
        self._args = {**self._args, **args} if self._args else args

    def also(self, name: str) -> None:
        """A second name learned inside the span (how its body ended): the
        interval is recorded under both, for readers that select spans by
        name and cannot see args."""
        self._also = name

    def __enter__(self) -> "_Span":
        if _devcheck.enabled():
            _devcheck.span_opened(self._name)
        cls = _profiler_annotation() if self._tr._annotate else None
        self._ann = cls(self._name).__enter__() if cls is not None else None
        self._t0 = self._tr._now()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        t1 = tr._now()
        tr.record(self._name, self._t0, t1, self._args,
                  flow=self._flow, flow_phase=self._phase)
        if self._also is not None:
            tr.record(self._also, self._t0, t1, self._args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        # unconditional (like DevLock.release): devcheck disabled between
        # enter and exit must still pop the armed-time push. The inject
        # seam leaks ONLY this bookkeeping (the span still records) so
        # the close()-time canary demonstrably fires.
        if not _devcheck.inject_lintbug("span"):
            _devcheck.span_closed(self._name)
        return False


class _NullSpan:
    """Disabled-path context manager: shared, allocation-free."""

    __slots__ = ()

    def note(self, **args) -> None:
        pass

    def also(self, name: str) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()

TRACER = SpanTracer(
    int(os.environ.get("TM_TPU_TRACE_BUFFER", str(DEFAULT_CAPACITY))))
if os.environ.get("TM_TPU_TRACE", "0") not in ("", "0"):
    TRACER.enabled = True


def span(name: str, flow: Optional[int] = None,
         flow_phase: Optional[str] = None, **args) -> object:
    """Context manager recording `name` with optional args (and an
    optional flow correlation id) on the process-wide TRACER.

    The disabled path returns a shared null object after a single attribute
    check — hot-path call sites need no `if` of their own (though sites
    that build expensive kwargs should still guard on `TRACER.enabled`).
    """
    if not TRACER.enabled:
        return _NULL_SPAN
    return _Span(TRACER, name, args or None, flow, flow_phase)


def configure(enabled: Optional[bool] = None,
              capacity: Optional[int] = None) -> None:
    TRACER.configure(enabled=enabled, capacity=capacity)


# ---------------------------------------------------------------------------
# Summaries (shared by tools/trace_report.py, bench.py, and /dump_trace)
# ---------------------------------------------------------------------------


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    k = (len(sorted_vals) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = k - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def summarize_events(trace_doc: dict) -> Dict[str, dict]:
    """Per-span-name stats over a Chrome-trace dict: count, total/p50/p95/
    p99 ms. The `_wall` pseudo-entry carries the trace's wall-clock extent
    and its event count. (What the device did is in the profiler's trace,
    not in host spans.)"""
    evs = trace_doc.get("traceEvents", [])
    by_name: Dict[str, List[float]] = {}
    t_min, t_max = float("inf"), float("-inf")
    for ev in evs:
        if ev.get("ph") != "X":
            continue
        dur = float(ev.get("dur", 0.0))
        ts = float(ev.get("ts", 0.0))
        by_name.setdefault(ev["name"], []).append(dur)
        t_min = min(t_min, ts)
        t_max = max(t_max, ts + dur)
    out: Dict[str, dict] = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        out[name] = {
            "count": len(durs),
            "total_ms": sum(durs) / 1e3,
            "p50_ms": _percentile(durs, 0.50) / 1e3,
            "p95_ms": _percentile(durs, 0.95) / 1e3,
            "p99_ms": _percentile(durs, 0.99) / 1e3,
        }
    wall_us = (t_max - t_min) if evs and t_max > t_min else 0.0
    out["_wall"] = {"wall_ms": wall_us / 1e3, "events": len(evs)}
    return out


def dump_doc(doc: dict, path: str) -> str:
    """Atomically write a trace document as JSON: tmp file + rename, so a
    SIGTERM mid-dump never leaves a truncated file at the advertised
    path. Shared by SpanTracer.dump, simnet_run --trace and trace_report
    --out."""
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh)
    os.replace(tmp, path)
    return path


def merge_traces(docs: Sequence[dict],
                 labels: Optional[Sequence[str]] = None) -> dict:
    """Merge several Chrome-trace documents into ONE (ISSUE 10): pids are
    re-keyed per source document (collision-proof), `process_name`
    metadata survives (or is synthesized from `labels`), and flow ids are
    preserved VERBATIM — a flow started in one document and finished in
    another stays a single causal chain. Documents must share a timebase
    for the timeline to be meaningful (simnet's per-node tracers all read
    the same virtual clock)."""
    merged: List[dict] = []
    meta: List[dict] = []
    next_pid = 1
    for i, doc in enumerate(docs):
        label = labels[i] if labels is not None and i < len(labels) else None
        evs = doc.get("traceEvents", [])
        named = {
            ev.get("pid")
            for ev in evs
            if ev.get("ph") == "M" and ev.get("name") == "process_name"
        }
        pid_map: Dict[object, int] = {}
        for ev in evs:
            old = ev.get("pid", 0)
            new = pid_map.get(old)
            if new is None:
                new = pid_map[old] = next_pid
                next_pid += 1
            ev2 = dict(ev)
            ev2["pid"] = new
            if ev2.get("ph") == "M":
                meta.append(ev2)
            else:
                merged.append(ev2)
        for old, new in sorted(pid_map.items(), key=lambda kv: kv[1]):
            if old not in named:
                meta.append({
                    "name": "process_name", "ph": "M", "pid": new, "tid": 0,
                    "args": {"name": label or f"proc{new}"},
                })
    merged.sort(key=lambda e: e.get("ts", 0.0))
    return {"traceEvents": meta + merged, "displayTimeUnit": "ms"}


def flow_chains(trace_doc: dict) -> Dict[int, List[dict]]:
    """Group a document's flow-carrying slices by flow id, each chain
    ordered (phase-aware: "s" first, "f" last, ties by ts). The merged-
    trace acceptance check — and the tests — read chains through this
    instead of re-parsing the event soup."""
    order = {"s": 0, "t": 1, "f": 2}
    chains: Dict[int, List[dict]] = {}
    for ev in trace_doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        fid = args.get("flow")
        if fid is None:
            continue
        chains.setdefault(int(fid), []).append(ev)
    for evs in chains.values():
        evs.sort(key=lambda e: (order.get((e.get("args") or {}).get(
            "flow_phase", "t"), 1), e.get("ts", 0.0)))
    return chains
