"""One ingress fabric — the shared windowed-accumulator engine (ISSUE 17).

PRs 11/12/14/15 put every serving workload on the device pipeline, and
each grew its own near-identical windowed accumulator: light
single-flight, mempool batch/window, replay range fuse, vote
micro-windows — four flush threads, four fallback paths, four
poisoned-window isolation schemes. This module is the consolidation:
ONE engine that owns the window open/flush lifecycle (one scheduler
thread, one completer thread for the whole process), EntryBlock
assembly and submission to the shared AsyncBatchVerifier at each lane's
QoS priority, poisoned-window isolation with retryability, the
fallback-to-host contract, and per-lane labeled metrics. A workload
keeps only a `LaneSpec` — window policy, priority tier, host-stage
check and verdict-apply callbacks — and the engine does the rest.

Windows are ADAPTIVE and SLO-AWARE (`AdaptiveWindow`): under flood a
lane's window deepens (more amortization per device launch — the
2302.00418 batch economics applied at admission); when traffic thins it
shrinks below its base so a lone request is not taxed the full window;
and a lane's p99 latency budget bounds the effective window so the
flush fires BEFORE the budget is exhausted (deadline-aware flush).
Explicitly-configured lanes (constructor args, every existing bench and
test call site) keep fixed windows unless TM_TPU_INGRESS_ADAPTIVE=1 —
determinism by default where determinism was promised.

Threading contracts the engine preserves from the per-lane era:

* Scheduler flushes stage under the engine mutex, RELEASE it, then
  submit — verifier submission never happens under a lock (the tmlint
  lock-discipline shape).
* A lane may ask for completer-thread delivery (`use_completer`): its
  verdict delivery and host verification run on the engine's completer
  thread, never the pipeline resolver. The mempool needs this —
  consensus holds the mempool lock across update()→recheck while
  waiting on PIPELINE futures (resolved by the resolver, which never
  takes that lock), so completion work that takes the mempool lock must
  live on a different thread. The completer only ever takes workload
  locks that their owners release without waiting on the completer —
  verdict futures are resolved here, pipeline futures never are.
* A lane with `use_completer=False` (votes) delivers straight from the
  resolver done-callback: its apply callback is enqueue-only by
  contract.
* Stepped lanes (simnet) are never touched by the scheduler: nothing
  flushes until `flush_pending()` — flush points stay a pure function
  of message arrival, so cluster runs stay replay-exact.

Error policy, per window (the four schemes, now one):

* pre-submit failure (EntryBlock build or verifier.submit raised):
  `submit_error_to_host=True` lanes host-verify the window instead
  (votes — the host path is always available); others deliver the
  error to exactly that window's items (mempool — futures raise).
* post-submit DispatchError: poisons ONLY its own window — the items
  are handed back with the error, the lane and every later window keep
  flowing.
* post-submit remote death (ISSUE 18): an error marked
  `fallback_to_host` (a fleet verifier's FleetUnavailable) host-verifies
  the window through `host_fn` instead of poisoning — zero lost items
  while the remote backend rejoins.

Knobs (lane-keyed, replacing the per-workload sprawl — old names are
honored with a DeprecationWarning): TM_TPU_INGRESS_<LANE>_BATCH,
TM_TPU_INGRESS_<LANE>_WINDOW_MS, TM_TPU_INGRESS_<LANE>_BUDGET_MS,
TM_TPU_INGRESS_<LANE>_ADAPTIVE, and the global TM_TPU_INGRESS_ADAPTIVE.

This module imports neither jax nor the pipeline at module level: the
controller and engine mechanics are testable in a jax-free interpreter
(tests/test_ingress_fabric.py), and lanes resolve their verifier
lazily exactly like the accumulators they replaced.
"""

from __future__ import annotations

import os
import queue
import threading
import time
import warnings
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# QoS tiers — mirror ops/pipeline.py (asserted equal by the test suite;
# duplicated so the engine stays importable without numpy/jax).
PRIORITY_CONSENSUS = 0
PRIORITY_REPLAY = 1
PRIORITY_INGRESS = 2

# flush causes fed to the controller
CAUSE_FULL = "full"          # a window hit the batch target
CAUSE_TIMER = "timer"        # the base window elapsed
CAUSE_DEADLINE = "deadline"  # the SLO budget bound the window
CAUSE_MANUAL = "manual"      # flush_now()
CAUSE_STEPPED = "stepped"    # flush_pending() in stepped mode
CAUSE_CLOSE = "close"        # final drain on lane close

# Per-lane defaults: base batch/window (the pre-fabric knob defaults,
# unchanged) and the p99 budget the deadline-aware flush respects.
# Consensus votes carry the paper's 5 ms hot-path budget; the others
# are configurable via TM_TPU_INGRESS_<LANE>_BUDGET_MS.
LANE_DEFAULTS: Dict[str, Dict[str, float]] = {
    "mempool": {"batch": 256, "window_ms": 4.0, "budget_ms": 25.0},
    "votes": {"batch": 128, "window_ms": 2.0, "budget_ms": 5.0},
    "light": {"batch": 64, "window_ms": 0.0, "budget_ms": 20.0},
    "replay": {"batch": 512, "window_ms": 0.0, "budget_ms": 0.0},
}

_warned_legacy: set = set()


def _warn_legacy(old: str, new: str) -> None:
    if old in _warned_legacy:
        return
    _warned_legacy.add(old)
    warnings.warn(
        f"{old} is deprecated; use {new} (lane-keyed ingress knobs)",
        DeprecationWarning, stacklevel=3,
    )


def env_setting(new: str, old: Optional[str] = None) -> Optional[str]:
    """Read a lane-keyed TM_TPU_INGRESS_* env knob, honoring its legacy
    per-workload name with a one-time DeprecationWarning."""
    v = os.environ.get(new)
    if v is not None:
        return v
    if old is not None:
        v = os.environ.get(old)
        if v is not None:
            _warn_legacy(old, new)
            return v
    return None


@dataclass
class LaneConfig:
    """Resolved knobs for one lane (see resolve_lane_config)."""

    batch: int
    window_ms: float
    budget_ms: Optional[float]
    adaptive: bool


def resolve_lane_config(
    lane: str,
    batch: Optional[int] = None,
    window_ms: Optional[float] = None,
    budget_ms: Optional[float] = None,
    adaptive: Optional[bool] = None,
    legacy_batch: Optional[str] = None,
    legacy_window: Optional[str] = None,
) -> LaneConfig:
    """Resolve one lane's knobs: explicit args > TM_TPU_INGRESS_<LANE>_*
    > legacy env names (DeprecationWarning) > LANE_DEFAULTS.

    Adaptivity defaults ON only when both batch and window came from
    env/defaults: a caller that pinned them (every bench column, every
    parity test, the soak harness) promised determinism and keeps it.
    TM_TPU_INGRESS_<LANE>_ADAPTIVE / TM_TPU_INGRESS_ADAPTIVE override
    either way."""
    d = LANE_DEFAULTS.get(lane, {"batch": 256, "window_ms": 4.0,
                                 "budget_ms": 0.0})
    up = lane.upper()
    explicit = batch is not None or window_ms is not None
    if batch is None:
        v = env_setting(f"TM_TPU_INGRESS_{up}_BATCH", legacy_batch)
        batch = int(v) if v is not None else int(d["batch"])
    if window_ms is None:
        v = env_setting(f"TM_TPU_INGRESS_{up}_WINDOW_MS", legacy_window)
        window_ms = float(v) if v is not None else float(d["window_ms"])
    if adaptive is None:
        v = env_setting(f"TM_TPU_INGRESS_{up}_ADAPTIVE") or env_setting(
            "TM_TPU_INGRESS_ADAPTIVE")
        adaptive = (v == "1") if v is not None else not explicit
    if budget_ms is None:
        v = env_setting(f"TM_TPU_INGRESS_{up}_BUDGET_MS")
        if v is not None:
            budget_ms = float(v)
        else:
            # the default SLO budget engages only with adaptivity: a
            # caller that pinned batch/window (benches, parity tests)
            # gets EXACTLY the flush timing it pinned
            budget_ms = float(d.get("budget_ms") or 0.0) if adaptive else 0.0
    return LaneConfig(batch=max(int(batch), 1),
                      window_ms=max(float(window_ms), 0.0),
                      budget_ms=(float(budget_ms) or None),
                      adaptive=bool(adaptive))


class AdaptiveWindow:
    """SLO-aware window controller — pure state machine, no clocks.

    Feeds: `on_flush(depth, cause)` per flush cycle and
    `note_service(ms)` per completed device window. Outputs:
    `batch_target()` (current size trigger) and `effective_window_ms()`
    (current time trigger). Policy:

    * deepen under flood — a FULL flush at the current target grows the
      window ×1.5 and the target ×2 (throughput: more signatures per
      device launch), up to 8× the configured base;
    * shrink when idle — SHRINK_PATIENCE consecutive timer flushes each
      carrying ≤¼ of the target halve both, down to ¼ window / base
      batch (latency: a lone request is not taxed a flood-depth window;
      the patience is hysteresis — one jitter-thinned flush mid-flood
      must not collapse a window the next burst will need);
    * deadline-aware — the effective window never exceeds
      `budget_ms - 2×(service-time EWMA)`: the flush fires early enough
      that submit + device service still fit the lane's p99 budget.

    `adaptive=False` freezes the base batch/window (existing call sites
    that pinned their knobs) but keeps the deadline bound when a budget
    is set — SLO awareness is not optional, adaptivity is.
    """

    GROW_WINDOW = 1.5
    GROW_BATCH = 2
    SHRINK = 0.5
    IDLE_FRACTION = 0.25
    SHRINK_PATIENCE = 2   # consecutive idle flushes before shrinking
    SPAN = 8.0            # max window / base window (and batch cap ×8)
    ALPHA = 0.3           # service-time EWMA weight
    SAFETY = 2.0          # budget headroom in service-time multiples

    def __init__(self, batch: int, window_ms: float,
                 budget_ms: Optional[float] = None,
                 adaptive: bool = True):
        self.base_batch = max(int(batch), 1)
        self.base_window_ms = max(float(window_ms), 0.0)
        self.budget_ms = float(budget_ms) if budget_ms else None
        self.adaptive = bool(adaptive)
        self.min_window_ms = self.base_window_ms / 4.0
        self.max_window_ms = self.base_window_ms * self.SPAN
        self.batch_cap = int(self.base_batch * self.SPAN)
        self.batch = self.base_batch
        self.window_ms = self.base_window_ms
        self.service_ewma_ms = 0.0
        self.deadline_bound = False   # last effective window was budget-clamped
        self.grows = 0
        self.shrinks = 0
        self.deadline_flushes = 0
        self._idle_streak = 0

    def batch_target(self) -> int:
        return self.batch

    def effective_window_ms(self) -> float:
        """The live time trigger: the adaptive window, clamped so flush +
        expected device service still fit inside the lane's budget."""
        w = self.window_ms
        if self.budget_ms is not None:
            lim = self.budget_ms - self.SAFETY * self.service_ewma_ms
            lim = max(lim, self.min_window_ms)
            if lim < w:
                self.deadline_bound = True
                return lim
        self.deadline_bound = False
        return w

    def note_service(self, ms: float) -> None:
        if ms < 0.0:
            return
        if self.service_ewma_ms == 0.0:
            self.service_ewma_ms = ms
        else:
            self.service_ewma_ms += self.ALPHA * (ms - self.service_ewma_ms)

    def on_flush(self, depth: int, cause: str) -> None:
        if cause == CAUSE_DEADLINE:
            self.deadline_flushes += 1
        if not self.adaptive or cause in (CAUSE_MANUAL, CAUSE_STEPPED,
                                          CAUSE_CLOSE):
            return
        if cause == CAUSE_FULL and depth >= self.batch:
            self._idle_streak = 0
            grew = False
            if self.batch < self.batch_cap:
                self.batch = min(self.batch * self.GROW_BATCH,
                                 self.batch_cap)
                grew = True
            if self.window_ms < self.max_window_ms:
                self.window_ms = min(self.window_ms * self.GROW_WINDOW,
                                     self.max_window_ms)
                grew = True
            if grew:
                self.grows += 1
        elif cause in (CAUSE_TIMER, CAUSE_DEADLINE):
            if depth <= max(self.batch * self.IDLE_FRACTION, 1.0):
                self._idle_streak += 1
                if self._idle_streak < self.SHRINK_PATIENCE:
                    return
                shrank = False
                if self.batch > self.base_batch:
                    self.batch = max(int(self.batch * self.SHRINK),
                                     self.base_batch)
                    shrank = True
                if self.window_ms > self.min_window_ms:
                    self.window_ms = max(self.window_ms * self.SHRINK,
                                         self.min_window_ms)
                    shrank = True
                if shrank:
                    self.shrinks += 1
            else:
                self._idle_streak = 0


@dataclass
class LaneSpec:
    """Everything lane-specific the engine needs — a workload IS this
    spec plus its host-stage check and verdict-apply callbacks.

    deliver(items, verdicts, err) receives the window's IngressItems in
    submission order; verdicts is None iff err is set. It runs on the
    completer thread when `use_completer`, else on the flusher/resolver
    thread — and must be enqueue-only in the latter case."""

    name: str                                  # metric label + registry key
    priority: int = PRIORITY_INGRESS
    batch: int = 256
    window_ms: float = 4.0
    budget_ms: Optional[float] = None
    adaptive: bool = False
    stepped: bool = False
    full_by_window: bool = False   # size trigger per keyed window (votes)
                                   # vs total lane depth (mempool)
    device_threshold: int = 0      # windows below this host-verify
                                   # (unless TM_TPU_FORCE_DEVICE=1)
    use_completer: bool = False    # deliver + host_fn on completer thread
    submit_error_to_host: bool = False  # pre-submit failure → host verify
    closed_msg: str = "ingress lane is closed"
    # None → ops.pipeline.shared_verifier(). Anything submit()-shaped
    # plugs in here — including fleet.client.FleetClient, which routes
    # the lane's flushed windows over the wire to a remote device fleet
    # (ISSUE 18). A remote verifier signals post-submit death by failing
    # futures with an error whose `fallback_to_host` attr is true: such
    # windows host-verify via host_fn (counted remote_fallbacks) instead
    # of poisoning. Pre-submit raises ride submit_error_to_host as ever.
    verifier: Any = None
    # callbacks (None where a lane has no use for the seam)
    entries_fn: Optional[Callable[[Any], Tuple[bytes, bytes, bytes]]] = None
    route_fn: Optional[Callable[[Any], bool]] = None   # True → device lane
    attach_fn: Optional[Callable[[Any, Any, List[Any]], None]] = None
    flow_fn: Optional[Callable[[Any], Optional[int]]] = None
    trace_fn: Optional[Callable[[List[Any], int], None]] = None
    host_fn: Optional[Callable[[List[Any]], Sequence[bool]]] = None
    deliver: Optional[Callable[
        [List["IngressItem"], Optional[Sequence[bool]],
         Optional[BaseException]], None]] = None
    observer: Any = None           # legacy metric mirror (duck-typed)


class IngressItem:
    """One queued submission riding a window."""

    __slots__ = ("item", "future", "t_enq")

    def __init__(self, item: Any, t_enq: float, want_future: bool = False):
        self.item = item
        self.future: Optional[Future] = Future() if want_future else None
        self.t_enq = t_enq


def _observe(obs: Any, method: str, *args) -> None:
    """Call an optional legacy-metric mirror — observability never fatal."""
    if obs is None:
        return
    fn = getattr(obs, method, None)
    if fn is None:
        return
    try:
        fn(*args)
    except Exception:  # noqa: BLE001
        pass


class Lane:
    """One registered workload on the engine. Created via
    IngressEngine.register(spec); all mutable window state is guarded by
    the ENGINE mutex (one scheduler means one lock suffices)."""

    def __init__(self, engine: "IngressEngine", spec: LaneSpec):
        self.engine = engine
        self.spec = spec
        self.ctrl = AdaptiveWindow(spec.batch, spec.window_ms,
                                   budget_ms=spec.budget_ms,
                                   adaptive=spec.adaptive)
        self._v = spec.verifier
        self._v_hooked = False
        # window state — engine-mutex guarded
        self._windows: Dict[Any, List[IngressItem]] = {}
        self._inwindow: set = set()
        self._depth = 0
        self._t_first = 0.0
        self._force = False            # flush_now / window<=0 / full
        self._manual = False           # the force came from flush_now
        self._inflight = 0             # submitted, verdict not delivered
        self._host_inflight = 0        # parked on the completer queue
        self._closed = False
        # counters (read via stats(); labeled metrics mirror them)
        self.batches = 0
        self.sigs = 0
        self.host_lane_sigs = 0        # route_fn-directed host items
        self.window_dups = 0
        self.sync_fallbacks = 0
        self.preempted = 0
        self.dispatch_errors = 0
        self.remote_fallbacks = 0      # remote verifier died post-submit
        self.blocks = 0                # whole-block passthrough submits
        self._wait_ms_sum = 0.0
        self._flush_t0: Dict[int, float] = {}   # inflight window → t_submit

    # -- wiring -----------------------------------------------------------

    def _verifier(self):
        if self._v is None:
            from . import pipeline as _pl

            self._v = _pl.shared_verifier()
        if not self._v_hooked:
            self._v_hooked = True
            hook = getattr(self._v, "add_preempt_hook", None)
            if hook is not None:
                hook(self._note_preempt)
        return self._v

    def _note_preempt(self, n: int) -> None:
        self.preempted += n
        self.engine._m_preempt(self.spec.name, n)
        _observe(self.spec.observer, "preempt", n)

    # -- submission -------------------------------------------------------

    def submit(self, item: Any, key: Any = None,
               dedup_key: Any = None, t_enq: Optional[float] = None,
               want_future: bool = False) -> Optional[Future]:
        """Queue one item into the window keyed by `key`. Returns a
        per-item Future when `want_future` (resolved by deliver());
        returns None on an in-window duplicate drop."""
        if self._closed:
            raise RuntimeError(self.spec.closed_msg)
        it = IngressItem(item, t_enq or time.perf_counter(), want_future)
        eng = self.engine
        with eng._mtx:
            if dedup_key is not None:
                if dedup_key in self._inwindow:
                    self.window_dups += 1
                    return None
                self._inwindow.add(dedup_key)
            win = self._windows.get(key)
            if win is None:
                win = self._windows[key] = []
            if not self._depth:
                self._t_first = it.t_enq
            win.append(it)
            self._depth += 1
            depth = self._depth
            size = len(win) if self.spec.full_by_window else depth
            full = (size >= self.ctrl.batch_target()
                    or self.ctrl.effective_window_ms() <= 0.0)
            if full and not self.spec.stepped:
                self._force = True
        eng._m_depth(self.spec.name, depth)
        _observe(self.spec.observer, "depth", depth)
        if not self.spec.stepped:
            eng._kick()
        return it.future

    def submit_block(self, block, flow: Optional[int] = None,
                     priority: Optional[int] = None,
                     count: bool = True):
        """Whole-block passthrough (light header stages, mempool recheck,
        replay fused ranges): submit straight to the lane's verifier at
        its QoS tier, count it, return the PIPELINE future — resolved on
        the resolver thread, safe to wait on while holding workload
        locks that deliver() would need. `count=False` keeps the block
        out of the lane's batches/sigs counters (mempool recheck, whose
        legacy stats never counted recheck traffic)."""
        if priority is None:
            priority = self.spec.priority
        if priority == PRIORITY_CONSENSUS:
            # CONSENSUS is the pipeline's default tier — omit the kwarg
            # so narrow duck-typed verifiers (submit(entries, flow=None))
            # keep working
            fut = self._verifier().submit(block, flow=flow)
        else:
            fut = self._verifier().submit(block, flow=flow,
                                          priority=priority)
        n = len(block)
        if count:
            with self.engine._mtx:
                self.blocks += 1
                self.sigs += n
        self.engine._m_block(self.spec.name, n)
        return fut

    def flush_now(self) -> None:
        if self.spec.stepped:
            self.flush_pending()
            return
        with self.engine._mtx:
            self._force = True
            self._manual = True
        self.engine._kick()

    def flush_pending(self) -> bool:
        """Stepped-mode flush point: host-verify every open window in
        submission order and apply inline on the CALLER's thread.
        Returns True when anything flushed."""
        taken = self._take()
        if not taken:
            return False
        for _key, items in taken:
            self._note_flush(items)
            self._host(items, fallback=True)
        self.ctrl.on_flush(sum(len(i) for _, i in taken), CAUSE_STEPPED)
        return True

    # -- flush machinery (engine-driven) ----------------------------------

    def _take(self) -> List[Tuple[Any, List[IngressItem]]]:
        with self.engine._mtx:
            taken = list(self._windows.items())
            self._windows = {}
            self._inwindow.clear()
            self._depth = 0
            self._t_first = 0.0
            self._force = False
            self._manual = False
        return taken

    def _classify_locked(self, now: float) -> Optional[str]:
        """Under the engine mutex: is this lane due, and why? None when
        not due; the scheduler flushes due lanes after releasing."""
        if self._closed or self.spec.stepped or not self._depth:
            return None
        if self._force:
            if self._manual:
                return CAUSE_MANUAL
            return CAUSE_FULL
        w_ms = self.ctrl.effective_window_ms()
        if now - self._t_first >= w_ms / 1e3:
            return CAUSE_DEADLINE if self.ctrl.deadline_bound else CAUSE_TIMER
        return None

    def _deadline_locked(self) -> Optional[float]:
        if self._closed or self.spec.stepped or not self._depth:
            return None
        if self._force:
            return 0.0
        return self._t_first + self.ctrl.effective_window_ms() / 1e3

    def _note_flush(self, items: List[IngressItem]) -> None:
        now = time.perf_counter()
        wait_ms = max(
            (now - min((it.t_enq or now) for it in items)) * 1e3, 0.0)
        with self.engine._mtx:
            self.batches += 1
            self.sigs += len(items)
            self._wait_ms_sum += wait_ms
        self.engine._m_flush(self.spec.name, len(items), wait_ms)
        _observe(self.spec.observer, "flush", len(items), wait_ms)

    def _flush(self, cause: str) -> None:
        """Take and dispatch every open window. Runs on the scheduler
        thread (or the closing thread for the final drain) with NO lock
        held — staging happened in _take()."""
        taken = self._take()
        if not taken:
            return
        total = 0
        for key, items in taken:
            total += len(items)
            self._note_flush(items)
            if self.spec.route_fn is not None:
                dev = [it for it in items if self.spec.route_fn(it.item)]
                host = [it for it in items
                        if not self.spec.route_fn(it.item)]
            else:
                dev, host = items, []
            if host:
                with self.engine._mtx:
                    self.host_lane_sigs += len(host)
                self.engine._m_host_lane(self.spec.name, len(host))
                self._host(host, fallback=False)
            if dev:
                self._flush_device(key, dev)
        self.ctrl.on_flush(total, cause)
        self.engine._m_window(self.spec.name, self.ctrl)
        _observe(self.spec.observer, "depth", 0)

    def _flush_device(self, key: Any, items: List[IngressItem]) -> None:
        spec = self.spec
        force = os.environ.get("TM_TPU_FORCE_DEVICE", "0") == "1"
        if len(items) < spec.device_threshold and not force:
            self._host(items, fallback=True)
            return
        t0 = time.perf_counter()
        try:
            from .entry_block import EntryBlock

            block = EntryBlock.from_entries(
                [spec.entries_fn(it.item) for it in items])
            if spec.attach_fn is not None:
                spec.attach_fn(block, key, [it.item for it in items])
            flow = None
            if spec.flow_fn is not None:
                flow = next((f for f in (spec.flow_fn(it.item)
                                         for it in items)
                             if f is not None), None)
            if flow is not None and spec.trace_fn is not None:
                spec.trace_fn([it.item for it in items], flow)
            with self.engine._mtx:
                self._inflight += 1
            fut = self._verifier().submit(block, flow=flow,
                                          priority=spec.priority)
        except Exception as e:  # noqa: BLE001 — window isolation:
            # engine absent/closed or a build failure hits exactly this
            # window; only post-submit DispatchErrors poison futures
            with self.engine._mtx:
                self._inflight = max(self._inflight - 1, 0)
            if spec.submit_error_to_host:
                self._host(items, fallback=True)
            else:
                self._deliver(items, None, e)
            return
        self._flush_t0[id(fut)] = t0
        if spec.use_completer:
            # done-callback runs on the pipeline resolver: ONLY enqueue —
            # the completer owns any work that may take workload locks
            fut.add_done_callback(
                lambda f, b=items: self.engine._cq_put(
                    ("device", self, b, f)))
        else:
            fut.add_done_callback(
                lambda f, b=items: self._complete_device(b, f,
                                                         dec_first=True))

    def _complete_device(self, items: List[IngressItem], fut,
                         dec_first: bool = False) -> None:
        if dec_first:
            with self.engine._mtx:
                self._inflight = max(self._inflight - 1, 0)
        t0 = self._flush_t0.pop(id(fut), None)
        if t0 is not None:
            self.ctrl.note_service((time.perf_counter() - t0) * 1e3)
        err = fut.exception()
        if err is not None:
            # graceful degradation (ISSUE 18): a remote verifier that
            # died AFTER submit marks its error fallback_to_host (duck-
            # typed — fleet.client.FleetUnavailable; ingress never
            # imports fleet). The window host-verifies instead of
            # poisoning: zero lost items, and the lane keeps flowing
            # while the client rejoins.
            if (getattr(err, "fallback_to_host", False)
                    and self.spec.host_fn is not None):
                with self.engine._mtx:
                    self.remote_fallbacks += 1
                self.engine._m_remote_fallback(self.spec.name)
                _observe(self.spec.observer, "remote_fallback")
                try:
                    # fallback=False: remote_fallbacks is the counter
                    # here, not sync_fallbacks (disjoint classifications)
                    self._host(items, fallback=False)
                    return
                except Exception as e:  # noqa: BLE001 — fallback failed
                    self._count_dispatch_error()
                    self._deliver(items, None, e)
                    return
            # poisoned window: exactly these items fail; the lane and
            # every later window keep flowing (items left the dedup set
            # at stage time, so a retry re-enters cleanly)
            self._count_dispatch_error()
            self._deliver(items, None, err)
            return
        try:
            verdicts = [bool(v) for v in fut.result()]
            self._deliver(items, verdicts, None)
        except Exception as e:  # noqa: BLE001 — a delivery failure is
            # handed back like a dispatch failure, never swallowed
            self._count_dispatch_error()
            self._deliver(items, None, e)

    def _count_dispatch_error(self) -> None:
        with self.engine._mtx:
            self.dispatch_errors += 1
        self.engine._m_dispatch_error(self.spec.name)
        _observe(self.spec.observer, "dispatch_error")

    def _host(self, items: List[IngressItem], fallback: bool) -> None:
        """Host-verify one window — inline, or parked on the completer
        queue for use_completer lanes. `fallback` distinguishes the sync
        fallback (sub-threshold / stepped / engine absent) from
        route_fn-directed host-lane traffic."""
        if fallback:
            with self.engine._mtx:
                self.sync_fallbacks += 1
            self.engine._m_sync_fallback(self.spec.name)
            _observe(self.spec.observer, "sync_fallback")
        if self.spec.use_completer:
            with self.engine._mtx:
                self._host_inflight += 1
            self.engine._cq_put(("host", self, items, None))
        else:
            self._run_host(items)

    def _run_host(self, items: List[IngressItem]) -> None:
        verdicts = self.spec.host_fn([it.item for it in items])
        self._deliver(items, verdicts, None)

    def _deliver(self, items: List[IngressItem],
                 verdicts: Optional[Sequence[bool]],
                 err: Optional[BaseException]) -> None:
        if self.spec.deliver is not None:
            self.spec.deliver(items, verdicts, err)

    # -- lifecycle / introspection ----------------------------------------

    def stats(self) -> dict:
        with self.engine._mtx:
            depth = self._depth
        return {
            "queue_depth": depth,
            "batches": self.batches,
            "sigs": self.sigs,
            "host_lane_sigs": self.host_lane_sigs,
            "window_dups": self.window_dups,
            "sync_fallbacks": self.sync_fallbacks,
            "batch_wait_ms_avg": (
                self._wait_ms_sum / self.batches if self.batches else 0.0
            ),
            "preemptions": self.preempted,
            "dispatch_errors": self.dispatch_errors,
            "remote_fallbacks": self.remote_fallbacks,
            "blocks": self.blocks,
            "max_batch": self.ctrl.batch_target(),
            "window_ms": self.ctrl.window_ms,
            "budget_ms": self.ctrl.budget_ms or 0.0,
            "adaptive": self.ctrl.adaptive,
            "stepped": self.spec.stepped,
            "window_grows": self.ctrl.grows,
            "window_shrinks": self.ctrl.shrinks,
            "deadline_flushes": self.ctrl.deadline_flushes,
        }

    def close(self, timeout: float = 10.0) -> None:
        """Drain and retire the lane: flush open windows on the calling
        thread, then wait for every in-flight verdict to deliver. The
        engine (shared, process-wide) keeps running for other lanes."""
        with self.engine._mtx:
            if self._closed:
                return
            self._closed = True
        self._flush(CAUSE_CLOSE)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self.engine._mtx:
                if self._inflight == 0 and self._host_inflight == 0:
                    break
            time.sleep(0.005)
        self.engine._unregister(self)


class IngressEngine:
    """The fabric: ONE flush scheduler and ONE completer thread serving
    every registered lane (threads start lazily, on first need). Lanes
    may carry different verifiers — tests and multi-node sims register
    private-verifier lanes on the same engine."""

    def __init__(self):
        self._mtx = threading.Lock()
        self._lanes: List[Lane] = []
        self._wake = threading.Event()
        self._cq: "queue.Queue" = queue.Queue()
        self._sched: Optional[threading.Thread] = None
        self._cthread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._metrics = None

    # -- registration -----------------------------------------------------

    def register(self, spec: LaneSpec) -> Lane:
        lane = Lane(self, spec)
        with self._mtx:
            self._lanes.append(lane)
        if not spec.stepped:
            self._ensure_scheduler()
        if spec.use_completer:
            self._ensure_completer()
        self._m_window(spec.name, lane.ctrl)
        return lane

    def _unregister(self, lane: Lane) -> None:
        with self._mtx:
            if lane in self._lanes:
                self._lanes.remove(lane)

    # -- threads ----------------------------------------------------------

    def _ensure_scheduler(self) -> None:
        with self._mtx:
            if self._sched is None or not self._sched.is_alive():
                self._sched = threading.Thread(
                    target=self._scheduler, daemon=True,
                    name="ingress-fabric-flush")
                self._sched.start()

    def _ensure_completer(self) -> None:
        with self._mtx:
            if self._cthread is None or not self._cthread.is_alive():
                self._cthread = threading.Thread(
                    target=self._completer, daemon=True,
                    name="ingress-fabric-complete")
                self._cthread.start()

    def _kick(self) -> None:
        self._wake.set()

    def _cq_put(self, item) -> None:
        self._ensure_completer()
        self._cq.put(item)

    def _scheduler(self) -> None:
        while not self._stop.is_set():
            due: List[Tuple[Lane, str]] = []
            nxt: Optional[float] = None
            with self._mtx:
                lanes = list(self._lanes)
            now = time.perf_counter()
            with self._mtx:
                for lane in lanes:
                    cause = lane._classify_locked(now)
                    if cause is not None:
                        due.append((lane, cause))
                        continue
                    dl = lane._deadline_locked()
                    if dl is not None:
                        nxt = dl if nxt is None else min(nxt, dl)
            for lane, cause in due:
                try:
                    lane._flush(cause)
                except Exception:  # noqa: BLE001 — a lane's flush bug
                    # must not stall the other lanes' scheduler
                    pass
            if due:
                continue
            if nxt is None:
                self._wake.wait(0.05)
            else:
                self._wake.wait(min(max(nxt - now, 0.0), 0.05))
            self._wake.clear()

    def _completer(self) -> None:
        while True:
            item = self._cq.get()
            if item is None:
                break
            kind, lane, items, fut = item
            try:
                if kind == "device":
                    lane._complete_device(items, fut)
                else:
                    lane._run_host(items)
            except Exception:  # noqa: BLE001 — one lane's completion
                # bug must not kill the shared completer
                pass
            finally:
                with self._mtx:
                    if kind == "device":
                        lane._inflight = max(lane._inflight - 1, 0)
                    else:
                        lane._host_inflight = max(
                            lane._host_inflight - 1, 0)

    def close(self, timeout: float = 10.0) -> None:
        """Stop the engine threads (used by tests owning a private
        engine; the process-wide shared engine is never closed)."""
        self._stop.set()
        self._wake.set()
        if self._sched is not None:
            self._sched.join(timeout=timeout)
        self._cq.put(None)
        if self._cthread is not None:
            self._cthread.join(timeout=timeout)

    # -- labeled metrics (satellite 1) ------------------------------------

    def _m(self):
        if self._metrics is None:
            try:
                from ..libs import metrics as _m

                self._metrics = _m.ingress_metrics()
            except Exception:  # noqa: BLE001 — observability never fatal
                return None
        return self._metrics

    def _m_depth(self, lane: str, depth: int) -> None:
        m = self._m()
        if m is not None:
            try:
                m.queue_depth.set(depth, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_flush(self, lane: str, n: int, wait_ms: float) -> None:
        m = self._m()
        if m is not None:
            try:
                m.batches.inc(1, lane=lane)
                m.sigs.inc(n, lane=lane)
                m.batch_wait_ms.observe(wait_ms, lane=lane)
                m.queue_depth.set(0, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_host_lane(self, lane: str, n: int) -> None:
        m = self._m()
        if m is not None:
            try:
                m.host_lane_sigs.inc(n, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_sync_fallback(self, lane: str) -> None:
        m = self._m()
        if m is not None:
            try:
                m.sync_fallbacks.inc(1, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_remote_fallback(self, lane: str) -> None:
        m = self._m()
        if m is not None:
            try:
                m.remote_fallbacks.inc(1, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_dispatch_error(self, lane: str) -> None:
        m = self._m()
        if m is not None:
            try:
                m.dispatch_errors.inc(1, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_preempt(self, lane: str, n: int) -> None:
        m = self._m()
        if m is not None:
            try:
                m.preemptions.inc(n, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_block(self, lane: str, n: int) -> None:
        m = self._m()
        if m is not None:
            try:
                m.blocks.inc(1, lane=lane)
                m.sigs.inc(n, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    def _m_window(self, lane: str, ctrl: AdaptiveWindow) -> None:
        m = self._m()
        if m is not None:
            try:
                m.window_ms.set(ctrl.window_ms, lane=lane)
                m.batch_target.set(ctrl.batch_target(), lane=lane)
                m.deadline_flushes.inc(0, lane=lane)
            except Exception:  # noqa: BLE001
                pass

    # -- introspection ----------------------------------------------------

    def lanes(self) -> List[Lane]:
        with self._mtx:
            return list(self._lanes)

    def stats(self) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for lane in self.lanes():
            out[lane.spec.name] = lane.stats()
        return out


class BlockFuser:
    """The replay range fuse, engine-owned: pack per-height EntryBlocks
    into lane submissions of at most `cap` signatures. add() concludes a
    chunk when the next block would overflow; flush() concludes the
    tail. Each concluded chunk is ONE verifier command; `on_chunk(fut,
    parts)` receives the pipeline future plus (tag, offset, length)
    per packed block so the caller can slice verdicts back out."""

    def __init__(self, lane: Lane, cap: int,
                 on_chunk: Callable[[Any, List[Tuple[Any, int, int]]], None],
                 flow: Optional[int] = None):
        self.lane = lane
        self.cap = max(int(cap), 1)
        self.on_chunk = on_chunk
        self.flow = flow
        self._blocks: List[Any] = []
        self._parts: List[Tuple[Any, int, int]] = []
        self._n = 0

    def add(self, tag: Any, block) -> None:
        n = len(block)
        if self._n and self._n + n > self.cap:
            self.flush()
        self._blocks.append(block)
        self._parts.append((tag, self._n, n))
        self._n += n

    def flush(self) -> None:
        if not self._blocks:
            return
        from .entry_block import EntryBlock

        fused = (self._blocks[0] if len(self._blocks) == 1
                 else EntryBlock.concat(self._blocks))
        parts = self._parts
        self._blocks, self._parts, self._n = [], [], 0
        fut = self.lane.submit_block(fused, flow=self.flow)
        self.on_chunk(fut, parts)


# ---------------------------------------------------------------------------
# process-wide engine
# ---------------------------------------------------------------------------

_shared_mtx = threading.Lock()
_shared: Optional[IngressEngine] = None


def shared_engine() -> IngressEngine:
    """THE process-wide fabric — every lane in the process shares its
    one scheduler and one completer (multi-node sims included: lanes
    carry their own verifiers, the threads are common infrastructure)."""
    global _shared
    with _shared_mtx:
        if _shared is None:
            _shared = IngressEngine()
        return _shared
