"""Async device verification pipeline — overlap host prep with device work.

SURVEY.md §7 hard-part 4 and the reference's pipelined sync shape
(internal/blocksync/pool.go:127 parallel requesters feeding a sequential
verify/apply loop): verification batches are submitted to a single worker
thread that dispatches the jitted kernel asynchronously (JAX dispatch
returns before the device finishes) and only blocks on a result when the
pipeline is `depth` batches deep — so batch N's host prep (sign-bytes
construction, limb packing) runs while batch N-1 executes on device, and
the device never waits on the host between batches.

Consumers:
- blocksync reactor: speculative pre-verification of the next block's
  commit while the current block runs through ABCI apply.
- light client header sync: verify_headers_pipelined — BASELINE config #5
  (pipelined 1k-header verify).
"""

from __future__ import annotations

import functools
import heapq
import itertools
import logging
import os
import queue
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..libs import devcheck as _devcheck
from ..observability import trace as _trace
from ..types.validation import ErrNotEnoughVotingPowerSigned
from . import backend as _backend
from . import device_pool as _dpool
from . import mesh as _mesh
from .entry_block import EntryBlock, as_block, block_concat

_span = _trace.span

_log = logging.getLogger("tendermint_tpu.ops.pipeline")


@functools.lru_cache(maxsize=1)
def _d2h_async_supported() -> bool:
    """One-time capability probe (ISSUE 7 satellite): do this backend's
    device arrays support copy_to_host_async()? Probed once at engine
    init and logged — the old code wrapped every batch's call in a bare
    `except AttributeError: pass`, so a missing capability silently cost
    a blocking readback per batch with nothing in the logs."""
    import jax

    # a device_put that throws here is a dead device, not a missing
    # capability: it raises to whoever is building the verifier
    arr = jax.device_put(np.zeros(1, dtype=np.uint8))
    supported = callable(getattr(arr, "copy_to_host_async", None))
    if supported:
        _log.debug("device arrays support copy_to_host_async; verdict "
                   "readback overlaps compute")
    else:
        _log.warning(
            "device arrays lack copy_to_host_async(); verdict readback "
            "will block on materialization (one blocking readback per batch)"
        )
    return supported


def _rlc_width_arg(rlc_entries, bucket: int) -> dict:
    """The `m` a launch's spans carry: the lane width of an RLC launch of
    this bucket, nothing for the per-signature kernels or with the
    tracer off."""
    if rlc_entries is None or not _trace.TRACER.enabled:
        return {}
    from . import pallas_rlc

    return {"m": pallas_rlc.lane_width(bucket)}


class _Readback:
    """Structured async verdict readback (ISSUE 7 tentpole piece 4): the
    launched device result plus its D2H copy, started at construction
    when the backend supports it — so the copy rides behind the batches
    still computing. The resolver drains it via wait(); the depth
    semaphore keeps bounding launched-but-unresolved batches exactly as
    before."""

    __slots__ = ("dev",)

    def __init__(self, dev, start_async: bool):
        self.dev = dev
        # a launch closure may hand back a host array (the BLS lane's
        # two-launch protocol reduces residues host-side and returns the
        # verdict-code row as numpy) — nothing left to copy back then
        if start_async and hasattr(dev, "copy_to_host_async"):
            dev.copy_to_host_async()

    def wait(self) -> np.ndarray:
        # _resolve applies the owndata guard (copies before delivery);
        # wait() itself hands back the raw materialization
        return np.asarray(self.dev)  # tmlint: disable=donation-aliasing — consumer copies


_alias_scratch: dict = {}


def _alias_view(arr: np.ndarray) -> np.ndarray:
    """TM_TPU_INJECT_LINTBUG=alias (test seam, ISSUE 8): re-introduce the
    PR-7 readback-aliasing bug DETERMINISTICALLY on any backend — the
    verdict is delivered as a view of one per-shape scratch buffer that
    the next batch's resolve overwrites, exactly the recycled-donated-
    page mechanics devcheck's write-after-resolve canary must catch."""
    key = (arr.shape, str(arr.dtype))
    buf = _alias_scratch.get(key)
    if buf is None:
        buf = _alias_scratch[key] = np.empty_like(arr)
    np.copyto(buf, arr)
    return buf[:]  # non-owning view of the shared scratch


# QoS priority classes (ISSUE 13/14): the dispatcher is multi-tenant —
# consensus commit batches share it with blocksync replay ranges and
# mempool CheckTx superbatches. Lower value = more urgent. A pending
# CONSENSUS batch overtakes every queued REPLAY range and INGRESS
# superbatch (never an in-flight launch), so neither a rejoining node's
# catch-up flood nor a tx flood can push commit verification to the back
# of the line. REPLAY sits above INGRESS: catch-up is a node-liveness
# workload, user-tx ingress is best-effort.
PRIORITY_CONSENSUS = 0
PRIORITY_REPLAY = 1
PRIORITY_INGRESS = 2

# lane label per priority class — the queue_wait_seconds histogram and
# lane_counts() speak the same vocabulary (ISSUE 16)
_LANE_NAMES = {
    PRIORITY_CONSENSUS: "consensus",
    PRIORITY_REPLAY: "replay",
    PRIORITY_INGRESS: "ingress",
}


class _PriorityQueue:
    """Priority-ordered hand-off queue (ISSUE 13): items pop in
    (priority, arrival) order — arrival sequence preserves FIFO within a
    class, so this degrades to the old plain Queue when every producer
    uses one priority. Reordering happens strictly while an item is
    QUEUED: once the consumer picks a batch up (an in-flight transfer or
    launch) it is never revoked. The None close sentinel is delivered
    only after the heap drains, preserving the plain-Queue shutdown
    contract. `on_bypass(n)` — called outside the internal lock — reports
    how many queued lower-priority items a new arrival overtook: the
    preemption-visibility hook feeding `checktx_preemptions`."""

    def __init__(self, on_bypass=None):
        self._heap: list = []
        self._ctr = itertools.count()
        self._cv = threading.Condition(threading.Lock())
        self._closed = False
        self.on_bypass = on_bypass

    def put(self, item, priority: int = 0) -> None:
        if item is None:
            with self._cv:
                self._closed = True
                self._cv.notify_all()
            return
        with self._cv:
            bypassed = sum(1 for p, _, _ in self._heap if p > priority)
            heapq.heappush(self._heap, (priority, next(self._ctr), item))
            self._cv.notify()
        if bypassed and self.on_bypass is not None:
            try:
                self.on_bypass(bypassed)
            except Exception:  # noqa: BLE001 — observability never fatal
                pass

    def get(self, timeout: Optional[float] = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while not self._heap:
                if self._closed:
                    return None
                if deadline is None:
                    self._cv.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise queue.Empty
                self._cv.wait(remaining)
            return heapq.heappop(self._heap)[2]

    def get_nowait(self):
        with self._cv:
            if not self._heap:
                raise queue.Empty
            return heapq.heappop(self._heap)[2]

    def empty(self) -> bool:
        with self._cv:
            return not self._heap

    def qsize(self) -> int:
        with self._cv:
            return len(self._heap)

    def best_priority(self) -> Optional[int]:
        """Priority of the most-urgent queued item (None when empty) —
        the dispatcher's preemption probe while parked on the depth
        semaphore with a lower-urgency batch in hand."""
        with self._cv:
            return self._heap[0][0] if self._heap else None


class DispatchError(RuntimeError):
    """A batch failed on the dispatch-owner thread (host prep, epoch-table
    upload, or kernel launch). Carries the epoch/bucket context of the
    failing batch (bucket 0 when the failure precedes bucket planning) so
    a caller holding many futures can attribute the failure; the original
    exception rides as __cause__. The dispatcher itself survives — only
    the poisoned batch's futures fail."""

    def __init__(self, msg: str, *, bucket: int = 0,
                 epoch_key: Optional[bytes] = None):
        ek = epoch_key.hex()[:16] if epoch_key else None
        super().__init__(
            f"{msg} (bucket={bucket}, epoch={ek or 'uncached'})"
        )
        self.bucket = bucket
        self.epoch_key = epoch_key


class _Job:
    __slots__ = ("entries", "future", "flow", "flow_owned",
                 "priority", "seq", "t_submit", "t_take", "tid")

    def __init__(self, entries: EntryBlock,
                 priority: int = PRIORITY_CONSENSUS, seq: int = 0):
        self.entries = entries
        self.future: Future = Future()
        # flow correlation id (ISSUE 10): allocated at submit() when the
        # tracer is live, threaded through the coalesced batch so the
        # dispatch/verdict instants chain back to the submitting caller.
        # flow_owned=False (ISSUE 11) marks a CONTINUED caller flow (the
        # light service's RPC-arrival → verdict chain): the verdict
        # instant then steps ("t") instead of finishing ("f") so the
        # caller owns the chain's terminal event.
        self.flow: Optional[int] = None
        self.flow_owned = True
        # QoS class + submission sequence (ISSUE 13): seq keeps ordering
        # FIFO within a class and lets the mesh packer count how many
        # earlier-arrived INGRESS jobs a CONSENSUS job overtook
        self.priority = priority
        self.seq = seq
        # submit() instant and thread / taken-off-the-intake-queue
        # instant, stamped only while the tracer is on:
        # pipeline.queue_wait.intake
        self.t_submit = 0.0
        self.t_take = 0.0
        self.tid = 0


def resolved_at(future: Future) -> Tuple[float, int]:
    """(instant the resolver completed `future`, its launch id) for a
    future resolved while the tracer was on, else (0.0, 0): what the
    caller's ops.pipeline_wait.wake span starts from."""
    return getattr(future, "_tm_resolved", (0.0, 0))


class AsyncBatchVerifier:
    """Coalescing pipeline over the device engine with a SINGLE
    dispatch-owner thread.

    submit(entries) returns a Future resolving to the (n,) bool validity
    array; entries may be an EntryBlock (handed downstream BY REFERENCE —
    the zero-copy commit path) or a (pub, msg, sig) tuple list (converted
    once at this boundary).

    Thread layout (exactly ONE thread issues transfers and launches, and
    it never blocks on anything but the device):

      coalescer   drains submit()s, fuses jobs into device batches,
                  farms host prep out to a small pool
      dispatcher  the ONLY thread that launches kernels / issues device
                  transfers; pulls prepared args FIFO off a queue, so
                  callers and prep threads never convoy on the device
      resolver    blocks on device results (np.asarray) and completes
                  futures — device waits never delay the next launch

    `depth` bounds launched-but-unresolved batches (device memory;
    2 = classic double buffering) via a semaphore between dispatcher and
    resolver. `pool_depth` (default depth + 1, env TM_TPU_POOL_DEPTH)
    bounds transferred-but-unresolved input-buffer sets per compiled
    layout (ops/device_pool.py) — one deeper than the launch bound so
    batch k+1's H2D copy can issue while the pipeline is full.

    `mesh_lanes` >= 1 (default: TM_TPU_MESH, see ops/mesh.py) switches
    the coalescer into MESH-DISPATCHER mode (ISSUE 9): queued jobs are
    bin-packed into per-shard lanes of one (lanes x lane_bucket)
    superbatch per launch — same-epoch jobs share a lane, short lanes
    pad with identity rows, verdicts demux per job on readback. The
    dispatcher/resolver stages are UNCHANGED: a superbatch transfers,
    launches (sharded over the mesh when jax.shard_map + devices allow,
    simulated lanes otherwise) and reads back through the same
    single-owner overlap machinery as a single-device batch."""

    def __init__(self, depth: int = 3, pool_depth: Optional[int] = None,
                 mesh_lanes: Optional[int] = None):
        self._depth = max(depth, 1)
        self._mesh_lanes = (
            _mesh.lanes_from_env() if mesh_lanes is None
            else max(int(mesh_lanes), 0)
        )
        if pool_depth is None:
            pool_depth = int(
                os.environ.get("TM_TPU_POOL_DEPTH", self._depth + 1)
            )
        self._pool = _dpool.DeviceBufferPool(pool_depth)
        self._d2h_async = _d2h_async_supported()
        # job intake is priority-ordered too (ISSUE 13): a commit
        # submitted behind a backlog of queued ingress windows reaches
        # the coalescer first instead of waiting out the whole backlog
        self._q = _PriorityQueue()
        # QoS preemption visibility (ISSUE 13): total lower-priority
        # batches bypassed while queued, plus caller hooks (the mempool
        # ingress accumulator feeds MempoolMetrics.checktx_preemptions)
        self.preempted_total = 0
        self._preempt_mtx = threading.Lock()
        self._preempt_hooks: List = []
        # per-lane intake accounting (ISSUE 15): the CONSENSUS class is
        # now multi-producer — commit batches AND live-vote ingress
        # windows share it — so lane counters are the only way /status
        # can show votes actually cross-coalescing through the QoS lanes
        self._lane_mtx = threading.Lock()
        self._lane_submitted = {
            PRIORITY_CONSENSUS: 0, PRIORITY_REPLAY: 0, PRIORITY_INGRESS: 0,
        }
        # declared-origin attribution (ISSUE 18): fleet-server submits
        # carry each remote client's lane name; same mutex as lane counts
        self._origin_submitted: Dict[str, int] = {}
        # (spans, prep_future, t_enqueue, priority, launch) | None sentinel —
        # priority-ordered so a pending consensus batch overtakes queued
        # ingress superbatches (never an in-flight launch)
        self._dispatch_q = _PriorityQueue(on_bypass=self._note_preempt)
        # resolve order is priority-ordered too: with batches of both
        # classes in flight, the consensus verdict materializes first
        # instead of queuing behind ingress readbacks
        self._resolve_q = _PriorityQueue()
        self._job_seq = itertools.count()
        # one id per coalesced batch, allotted by the coalescer and carried
        # in the queue items: the spans of one launch join on it
        self._launch_seq = itertools.count(1)
        self._stopped = threading.Event()
        self._sem = threading.Semaphore(self._depth)
        # QoS reserved lane (ISSUE 13): INGRESS batches may occupy at
        # most depth-1 of the launch slots, so a consensus commit never
        # queues behind a device pipeline filled wall-to-wall with tx
        # superbatches — its depth wait is ~0 instead of a full readback.
        # Degenerate depth=1 disables the reservation (guarded at use).
        self._ing_sem = threading.Semaphore(max(self._depth - 1, 1))
        self._mtx = _devcheck.lock("pipeline.inflight")
        self._inflight = 0
        # thread idents that ever launched a kernel — asserted single-
        # element by tests/test_commit_block.py::TestDispatchOwnerThread
        # (the device-ownership invariant)
        self.dispatch_thread_idents: set = set()
        self._thread = threading.Thread(
            target=self._worker_mesh if self._mesh_lanes else self._worker,
            daemon=True, name="verify-coalesce",
        )
        self._dispatch_thread = threading.Thread(
            target=self._dispatcher, daemon=True, name="verify-dispatch"
        )
        self._resolve_thread = threading.Thread(
            target=self._resolver, daemon=True, name="verify-resolve"
        )
        self._thread.start()
        self._dispatch_thread.start()
        self._resolve_thread.start()

    def add_preempt_hook(self, fn) -> None:
        """Register fn(n_bypassed) — called whenever queued lower-priority
        batches are overtaken by a higher-priority arrival (dispatch-queue
        bypass or mesh-pack reorder)."""
        self._preempt_hooks.append(fn)

    def _note_preempt(self, n: int) -> None:
        with self._preempt_mtx:
            self.preempted_total += n
        for fn in list(self._preempt_hooks):
            try:
                fn(n)
            except Exception:  # noqa: BLE001 — observability never fatal
                pass

    def submit(self, entries, flow: Optional[int] = None,
               priority: int = PRIORITY_CONSENSUS,
               origin: Optional[str] = None) -> Future:
        """`origin` names WHO submitted (ISSUE 18: the fleet server
        passes each client's wire-declared lane) — pure attribution for
        origin_counts(); scheduling ignores it."""
        if self._stopped.is_set():
            raise RuntimeError("verifier is closed")
        t_submit = time.perf_counter() if _trace.TRACER.enabled else 0.0
        block = as_block(entries)
        max_b = _backend.scheme_cap(block.scheme, _backend.max_coalesce())
        if self._mesh_lanes:
            # mesh mode packs WHOLE jobs into lanes — chunk oversized
            # submissions at the lane capacity so every chunk fits one
            max_b = min(max_b, _mesh.lane_cap())
        if len(block) > max_b:
            return self._submit_chunked(block, max_b, flow, priority,
                                        origin=origin)
        job = _Job(block, priority=int(priority),
                   seq=next(self._job_seq))
        if _trace.TRACER.enabled:
            job.t_submit = t_submit or time.perf_counter()
            job.tid = threading.get_ident()
            if flow is not None:
                # continue the CALLER's flow (ISSUE 11: the light
                # service chains RPC arrival → epoch-group → mesh_pack →
                # verdict through the pipeline); the caller emits the
                # finish, so this submit and the verdict both step
                job.flow = int(flow)
                job.flow_owned = False
                _trace.TRACER.flow_point(
                    "pipeline.submit", job.flow, "t", n=len(block)
                )
            else:
                job.flow = _trace.next_flow()
                _trace.TRACER.flow_point(
                    "pipeline.submit", job.flow, "s", n=len(block)
                )
        with self._lane_mtx:
            self._lane_submitted[
                min(job.priority, PRIORITY_INGRESS)
            ] = self._lane_submitted.get(
                min(job.priority, PRIORITY_INGRESS), 0
            ) + 1
            if origin is not None:
                self._origin_submitted[origin] = (
                    self._origin_submitted.get(origin, 0) + 1
                )
        self._q.put(job, priority=job.priority)
        _backend._ops_m().pipeline_queue_depth.set(self._q.qsize())
        return job.future

    def lane_counts(self) -> dict:
        """Jobs accepted per QoS class since start — keys 'consensus'
        (commit batches + live-vote windows), 'replay', 'ingress'."""
        with self._lane_mtx:
            return {
                "consensus": self._lane_submitted[PRIORITY_CONSENSUS],
                "replay": self._lane_submitted[PRIORITY_REPLAY],
                "ingress": self._lane_submitted[PRIORITY_INGRESS],
            }

    def origin_counts(self) -> dict:
        """Jobs accepted per declared origin (ISSUE 18: fleet clients'
        lane names). Empty until someone submits with origin=."""
        with self._lane_mtx:
            return dict(self._origin_submitted)

    def _submit_chunked(self, block: EntryBlock, max_b: int,
                        flow: Optional[int] = None,
                        priority: int = PRIORITY_CONSENSUS,
                        origin: Optional[str] = None) -> Future:
        """An oversized job rides as zero-copy slices through the normal
        queue (the dispatcher stays the only device-touching thread; the
        old path ran a chunked synchronous fallback on the worker) and
        re-aggregates into one future."""
        futs: List[Future] = []
        i = 0
        while i < len(block):
            futs.append(
                self.submit(block[i : i + max_b], flow=flow,
                            priority=priority, origin=origin)
            )
            i += max_b
        agg: Future = Future()
        done_lock = threading.Lock()

        def _combine(_f) -> None:
            with done_lock:
                if agg.done() or not all(f.done() for f in futs):
                    return
                try:
                    parts = [np.asarray(f.result()) for f in futs]
                except Exception as e:  # noqa: BLE001
                    agg.set_exception(e)
                    return
                agg.set_result(np.concatenate(parts))

        for f in futs:
            f.add_done_callback(_combine)
        return agg

    def close(self) -> None:
        self._stopped.set()
        self._thread.join(timeout=5)
        self._dispatch_thread.join(timeout=5)
        self._resolve_thread.join(timeout=5)
        # retire this verifier's device claim (no-op set op when devcheck
        # never armed) — stale idents would outlaw later direct use and
        # can be recycled by the OS onto unrelated threads
        _devcheck.unclaim_device(self.dispatch_thread_idents)
        if _devcheck.enabled():
            _devcheck.canary_sweep("pipeline.close")
            # scoped to EXITED threads: the pipeline's own joined threads
            # can only have leaks left, while an unrelated live thread
            # (consensus mid-verify_dispatch, or a dispatch thread that
            # outlived join's timeout on a stalled device call) is
            # legitimately mid-span and must not false-positive
            _devcheck.span_check("pipeline.close", only_exited=True)

    # -- worker ----------------------------------------------------------

    @staticmethod
    def _prepare(entries):
        """Host prep only (runs on the prep pool — CPU-heavy, largely
        GIL-releasing: native SHA-512 challenges, numpy packing).

        Returns (kernel_fn, args, rlc_entries, bucket): rlc_entries is
        None for the per-signature kernels; for the RLC fast-accept kernel
        it is the entry list _resolve needs to expand lane verdicts to
        per-sig verdicts (and re-verify rejected lanes for blame). bucket
        is the padded device batch size (signature lanes) for metric
        labels. Which kernel, and its argument layout, is
        backend.select_kernel's business."""
        n = len(entries)
        scheme = getattr(entries, "scheme", "ed25519")
        with _span("pipeline.prep", n=n) as sp:
            res = _backend.select_kernel(entries)
            if _trace.TRACER.enabled:
                # what was chosen, for whoever reads the span
                sp.note(
                    bucket=res[3],
                    cached=int(_backend.warm_epoch(entries) is not None),
                    **({"scheme": scheme} if scheme != "ed25519" else {}),
                    **_rlc_width_arg(res[2], res[3]),
                )
        _backend._note_device_batch(n, res[3], scheme=scheme)
        return res

    @classmethod
    def _prepare_timed(cls, entries, launch: int = 0):
        """_prepare plus its own completion timestamp — returned IN the
        future's value so the dispatcher's queue-wait measurement cannot
        race the done-callback machinery. `launch` names the batch for
        the spans a prep-pool thread records (the coalescer's own thread
        already carries it)."""
        if launch and _trace.TRACER.enabled:
            _trace.TRACER.set_thread_args(launch=launch)
        return cls._prepare(entries), time.perf_counter()

    @staticmethod
    def _prepare_mesh(block, plan):
        """Host prep for a mesh superbatch (ISSUE 9): delegate to
        ops/mesh.prepare_superbatch — same return contract as _prepare
        plus the per-arg transfer shardings (None on simulated lanes).
        Pad accounting uses the plan's LIVE count so pad_waste metrics
        see the identity rows the packer added."""
        with _span("pipeline.prep", n=plan.live, bucket=plan.bucket,
                   lanes=plan.n_lanes,
                   cached=int(getattr(block, "epoch_key", None) is not None),
                   schemes=len(plan.schemes())):
            res = _mesh.prepare_superbatch(block, plan)
        # prep timing histograms are recorded inside prepare_batch*; the
        # dispatch counters note the LIVE rows against the full bucket
        schemes = plan.schemes()
        _backend._note_device_batch(
            plan.live, plan.bucket,
            scheme=schemes[0] if len(schemes) == 1 else "")
        return res

    @classmethod
    def _prepare_mesh_timed(cls, block, plan, launch: int = 0):
        if launch and _trace.TRACER.enabled:
            _trace.TRACER.set_thread_args(launch=launch)
        return cls._prepare_mesh(block, plan), time.perf_counter()

    @staticmethod
    def _resolve(spans, dev, rlc_entries=None, t_dispatch: float = 0.0,
                 bucket: int = 0, launch: int = 0) -> None:
        tracing = _trace.TRACER.enabled
        t_wait_end = 0.0
        try:
            with _span("pipeline.device_wait"):
                # dev is a _Readback from the dispatcher (async D2H copy
                # already in flight) or a bare device array from direct
                # callers — both materialize here
                if tracing:
                    # the same materialisation, split: until the launch's
                    # result is ready, then the device->host copy
                    raw = dev.dev if isinstance(dev, _Readback) else dev
                    with _span("pipeline.device_wait.kernel"):
                        if hasattr(raw, "block_until_ready"):
                            raw.block_until_ready()
                    with _span("pipeline.device_wait.readback",
                               bytes=int(getattr(raw, "nbytes", 0))):
                        arr = np.asarray(raw)  # tmlint: disable=donation-aliasing — copied below
                else:
                    arr = dev.wait() if isinstance(dev, _Readback) else np.asarray(dev)
            if tracing:
                t_wait_end = time.perf_counter()
            if not arr.flags.owndata:
                # np.asarray of a device array is a zero-copy VIEW of the
                # XLA output buffer on the CPU backend. Under donation the
                # output aliases a donated input page, and once the jax
                # handles drop that page is recycled and overwritten by a
                # later batch — mutating verdicts already delivered to
                # callers. Futures must resolve to host-OWNED memory; the
                # verdict row is ≤ bucket bytes, so the copy is free.
                arr = np.array(arr, copy=True)
            if t_dispatch:
                # dispatch-to-materialized: the device+transfer time this
                # batch actually cost the pipeline
                _backend._ops_m().device_seconds.observe(
                    time.perf_counter() - t_dispatch,
                    bucket=str(bucket or arr.shape[-1]),
                )
            if arr.ndim == 2:  # pallas output is (1, N) / (1, lanes)
                arr = arr[0].astype(bool)
            if rlc_entries is not None:
                from . import pallas_rlc

                # one verdict per lane: the launch's width is its bucket
                # over its lanes (select_kernel's contract)
                arr = pallas_rlc.expand_lanes(
                    arr, rlc_entries, bucket // len(arr))
            if _devcheck.inject_lintbug("alias"):
                # AFTER the 2-D/RLC reductions (they mint fresh owned
                # arrays that would neutralize the seam): the DELIVERED
                # verdict becomes the recycled-scratch view
                arr = _alias_view(arr)
            if _devcheck.enabled():
                # canary: earlier batches' delivered verdicts must still
                # be byte-stable now; this batch's verdict row registers
                # for the NEXT sweep (resolve / slot release / close)
                _devcheck.canary_sweep("pipeline.resolve")
                _devcheck.canary_register(
                    arr, tag=f"bucket={bucket or arr.shape[-1]}"
                )
        except Exception as e:  # noqa: BLE001
            for job, _, _ in spans:
                job.future.set_exception(e)
            return
        # verdict delivery is pure numpy slicing: one view per job out of
        # the batch verdict array — no per-entry Python anywhere between
        # the device result and the caller's future
        for job, off, n in spans:
            if tracing:
                # stamped BEFORE completion: the woken caller reads it
                job.future._tm_resolved = (time.perf_counter(), launch)
            job.future.set_result(arr[off : off + n])
        if tracing:
            _trace.TRACER.record("pipeline.resolve", t_wait_end,
                                 time.perf_counter(), {"jobs": len(spans)})
        if _trace.TRACER.enabled:
            for job, _off, n in spans:
                if getattr(job, "flow", None) is not None:
                    _trace.TRACER.flow_point(
                        "pipeline.verdict", job.flow,
                        "f" if getattr(job, "flow_owned", True) else "t",
                        n=n,
                    )

    @staticmethod
    def _trace_taken(job: _Job, launch: int) -> float:
        """Tracer on, a batch's first job in hand: names the launch for
        every span this thread records until the next batch, stamps a job
        fresh off the intake queue (a held one was stamped when drained)
        and returns the instant pipeline.coalesce starts from."""
        _trace.TRACER.set_thread_args(launch=launch)
        now = time.perf_counter()
        if not job.t_take:
            job.t_take = now
        return now

    @staticmethod
    def _trace_coalesced(jobs, t0: float, sigs: int, bucket: int) -> None:
        """Tracer on, the batch handed to the dispatcher: each job's wait
        on the intake queue (submit -> taken off it), then the coalescing
        itself (drain, linger, bucket-fit, concat, inline prep). The wait
        is filed under the SUBMITTING thread, inside its blocking span:
        on this thread it would lie over the previous batch's prep and
        hide that work from whoever reads the thread's innermost span."""
        rec = _trace.TRACER.record
        for j in jobs:
            if j.t_submit and j.t_take:
                rec("pipeline.queue_wait.intake", j.t_submit, j.t_take,
                    tid=j.tid)
        rec("pipeline.coalesce", t0, time.perf_counter(),
            {"jobs": len(jobs), "sigs": sigs, "bucket": bucket})

    def _worker(self) -> None:
        """Coalescer: many small commits (e.g. 128-signature headers
        during header sync) fuse into ONE device batch up to the max
        bucket, so a stream of small jobs costs one launch, not one
        launch each.

        Host prep runs on a small thread pool so batch N+1's packing/
        hashing overlaps batch N's prep AND the device kernel; prepared
        batches are handed to the dispatch-owner thread in FIFO order via
        the dispatch queue. This thread never touches the device."""
        from concurrent.futures import ThreadPoolExecutor

        prep_pool = ThreadPoolExecutor(3, thread_name_prefix="verify-prep")
        hold: Optional[_Job] = None
        max_b = _backend.max_coalesce()
        # QoS fuse cap (ISSUE 13): INGRESS-class rounds fuse only up to
        # this many entries. Every non-preemptible stage a fused batch
        # passes through — host prep, readback post-processing — scales
        # with batch size, so an unbounded ingress fuse turns into
        # head-of-line latency for the consensus class even with every
        # queue priority-ordered. Consensus rounds keep the full bucket.
        ing_cap = int(os.environ.get("TM_TPU_INGRESS_FUSE", "1024"))
        # REPLAY fuses to the full bucket by default (ISSUE 14): range
        # batching IS the catch-up win, and the preemption points below
        # bound the head-of-line cost for consensus either way.
        rep_cap = int(os.environ.get("TM_TPU_REPLAY_FUSE", str(max_b)))
        m = _backend._ops_m()
        try:
            while True:
                job = hold
                hold = None
                if job is None:
                    try:
                        job = self._q.get(timeout=0.05)
                    except queue.Empty:
                        if self._stopped.is_set() and self._q.empty():
                            break
                        continue
                launch = next(self._launch_seq)
                tracing = _trace.TRACER.enabled
                if tracing:
                    t_c0 = self._trace_taken(job, launch)
                jobs = [job]
                total = len(job.entries)
                # epoch-key gate: only jobs sharing a (non-None) epoch
                # key fuse — a mixed-key concat would drop the gather
                # indices and push the whole fused batch onto the
                # uncached prep (EntryBlock.concat's fallback). A
                # differing-key job is held for the NEXT batch, exactly
                # like a bucket-overflow job.
                key0 = job.entries.epoch_key
                # scheme gate (ISSUE 19): cross-scheme concat RAISES in
                # EntryBlock.concat (rows would hit the wrong kernel) —
                # a differing-scheme job is held like a differing key
                scheme0 = getattr(job.entries, "scheme", "ed25519")
                # coalescing window: while the device pipeline is busy a
                # short linger costs nothing (the dispatch would queue
                # anyway) and fuses straggler jobs into bigger batches.
                # 8 ms: value not measured on this machine
                busy = self._inflight > 0 or self._dispatch_q.qsize() > 0
                deadline = time.monotonic() + 0.008 if busy else 0.0
                cap = _backend.scheme_cap(scheme0, max_b)
                if job.priority <= PRIORITY_CONSENSUS:
                    limit = cap
                elif job.priority <= PRIORITY_REPLAY:
                    limit = min(cap, rep_cap)
                else:
                    limit = min(cap, ing_cap)
                while total < limit:
                    try:
                        nxt = self._q.get_nowait()
                    except queue.Empty:
                        wait = deadline - time.monotonic()
                        if wait <= 0:
                            break
                        try:
                            with _span("pipeline.queue_wait.linger"):
                                nxt = self._q.get(timeout=wait)
                        except queue.Empty:
                            break
                    if tracing:
                        nxt.t_take = time.perf_counter()
                    if (
                        total + len(nxt.entries) > limit
                        or nxt.entries.epoch_key != key0
                        or getattr(nxt.entries, "scheme", "ed25519")
                        != scheme0
                    ):
                        hold = nxt
                        break
                    jobs.append(nxt)
                    total += len(nxt.entries)
                # bucket-fit: kernel buckets are quantized, so a total
                # just past a bucket pays that bucket's FULL padding in
                # device time and host prep — peel trailing jobs back
                # while doing so lands the batch in a smaller bucket
                # with less waste
                while len(jobs) > 1 and hold is None:
                    b = _backend.quantized_bucket(total, scheme0)
                    if b - total <= max(b // 8, 1024):
                        break
                    shorter = _backend.quantized_bucket(
                        total - len(jobs[-1].entries), scheme0
                    )
                    if shorter >= b:
                        break
                    hold = jobs.pop()
                    total -= len(hold.entries)
                m.pipeline_coalesced_jobs.observe(len(jobs))
                spans = []
                off = 0
                for j in jobs:
                    spans.append((j, off, len(j.entries)))
                    off += len(j.entries)
                # columnar coalescing: one concatenate per column instead
                # of a per-signature list-extend; a single-job dispatch
                # passes its block through BY IDENTITY (zero copies).
                # block_concat dispatches on block type — the scheme gate
                # above keeps a window homogeneous (AggBlocks carry
                # scheme "bls12381"), so agg commits coalesce with agg
                # commits only.
                entries = (
                    jobs[0].entries
                    if len(jobs) == 1
                    else block_concat([j.entries for j in jobs])
                )
                # a fused batch inherits the most urgent class of its
                # jobs: a consensus job fused with ingress stragglers
                # lifts the whole batch rather than riding behind it
                pri = min(j.priority for j in jobs)
                if pri <= PRIORITY_CONSENSUS:
                    # consensus prep runs INLINE: the prep pool is a FIFO,
                    # so a commit's (small) prep submitted behind queued
                    # ingress-superbatch preps would wait out every one of
                    # them — the same inversion the priority queues fix,
                    # one layer down. Inline prep hands the dispatcher an
                    # already-resolved future; overlap with the in-flight
                    # kernel is preserved (this thread isn't the
                    # dispatcher), only drain-ahead is given up, and a
                    # consensus round is small enough not to miss it.
                    fut = Future()
                    try:
                        fut.set_result(self._prepare_timed(entries))
                    except BaseException as e:  # noqa: BLE001
                        fut.set_exception(e)
                else:
                    fut = prep_pool.submit(
                        self._prepare_timed, entries, launch
                    )
                self._dispatch_q.put(
                    (spans, fut, time.perf_counter(), pri, launch),
                    priority=pri,
                )
                if tracing:
                    self._trace_coalesced(
                        jobs, t_c0, total,
                        _backend.quantized_bucket(total, scheme0)
                    )
                m.dispatch_queue_depth.set(self._dispatch_q.qsize())
                m.pipeline_queue_depth.set(self._q.qsize())
        finally:
            self._dispatch_q.put(None)
            prep_pool.shutdown(wait=False)

    def _worker_mesh(self) -> None:
        """Mesh-dispatcher coalescer (ISSUE 9 tentpole): drain queued
        jobs up to the full mesh capacity (lanes x lane capacity), then
        bin-pack them into single-epoch lanes of ONE superbatch launch
        (ops/mesh.pack_jobs). Unlike the single-lane worker there is no
        epoch-key gate on draining — differing epochs land in different
        LANES of the same launch instead of serializing into separate
        launches. Jobs that fit no lane are held for the next superbatch
        (the bucket-overflow hold, generalized). This thread never
        touches the device; the dispatcher/resolver stages downstream
        are shared with the single-lane mode unchanged."""
        from concurrent.futures import ThreadPoolExecutor

        prep_pool = ThreadPoolExecutor(3, thread_name_prefix="verify-prep")
        held: List[_Job] = []
        max_lanes = self._mesh_lanes
        m = _backend._ops_m()
        try:
            while True:
                jobs = held
                held = []
                if not jobs:
                    try:
                        jobs = [self._q.get(timeout=0.05)]
                    except queue.Empty:
                        if self._stopped.is_set() and self._q.empty():
                            break
                        continue
                launch = next(self._launch_seq)
                tracing = _trace.TRACER.enabled
                if tracing:
                    t_c0 = self._trace_taken(jobs[0], launch)
                # cap re-read per superbatch: submit() reads it per call,
                # so a knob change mid-run must not strand a job that was
                # legal when it was accepted
                cap = _mesh.lane_cap()
                total = sum(len(j.entries) for j in jobs)
                budget = max_lanes * cap
                # same coalescing-window rationale as _worker: while the
                # pipeline is busy a short linger fuses stragglers into
                # fuller lanes for free
                busy = self._inflight > 0 or self._dispatch_q.qsize() > 0
                deadline = time.monotonic() + 0.008 if busy else 0.0
                while total < budget:
                    try:
                        nxt = self._q.get_nowait()
                    except queue.Empty:
                        wait = deadline - time.monotonic()
                        if wait <= 0:
                            break
                        try:
                            with _span("pipeline.queue_wait.linger"):
                                nxt = self._q.get(timeout=wait)
                        except queue.Empty:
                            break
                    if tracing:
                        nxt.t_take = time.perf_counter()
                    jobs.append(nxt)
                    total += len(nxt.entries)
                # Coalescer survival invariant (the dispatcher's PR-6
                # rule extended to the new packing stage): a poisoned
                # pack fails ONLY the drained jobs' futures — the worker
                # thread itself never dies on a batch's account.
                # QoS reorder (ISSUE 13): pack order is (priority, seq)
                # order, so a CONSENSUS commit drained in the same window
                # as queued INGRESS superjobs packs — and launches — ahead
                # of every one of them. `preempted` counts the ingress
                # jobs that arrived earlier but were ordered behind (or
                # pushed to the hold list by) this window's consensus
                # work; an already-launched superbatch is never revoked.
                jobs.sort(key=lambda j: (j.priority, j.seq))
                min_pri = min(j.priority for j in jobs)
                hi_seq = max(
                    j.seq for j in jobs if j.priority == min_pri
                )
                preempted = sum(
                    1 for j in jobs
                    if j.priority > min_pri and j.seq < hi_seq
                )
                try:
                    plan, held = _mesh.pack_jobs(jobs, max_lanes, cap)
                    if not plan.lanes:
                        # nothing live: empty submissions resolve right
                        # here, no launch
                        for j in plan.empty_jobs:
                            if not j.future.done():
                                j.future.set_result(
                                    np.zeros(0, dtype=bool)
                                )
                        continue
                    m.pipeline_coalesced_jobs.observe(
                        sum(len(l.jobs) for l in plan.lanes)
                    )
                    with _span("pipeline.mesh_pack", lanes=plan.n_lanes,
                               lane_bucket=plan.lane_bucket,
                               live=plan.live, pad=plan.pad,
                               preempted=preempted):
                        block, spans = _mesh.build_superblock(plan)
                    if preempted:
                        self._note_preempt(preempted)
                    m.mesh_lane_occupancy.set(plan.occupancy())
                    m.mesh_pad_waste_ratio.set(plan.pad_ratio())
                    fut = prep_pool.submit(
                        self._prepare_mesh_timed, block, plan, launch
                    )
                except Exception as e:  # noqa: BLE001 — pack isolation
                    self._fail_spans(
                        [(j, 0, len(j.entries)) for j in jobs],
                        self._wrap_dispatch_err(
                            "mesh pack failed", e, 0,
                            [(j, 0, 0) for j in jobs],
                        ),
                    )
                    held = []
                    continue
                self._dispatch_q.put(
                    (spans, fut, time.perf_counter(), min_pri, launch),
                    priority=min_pri,
                )
                if tracing:
                    self._trace_coalesced(
                        [j for j, _, _ in spans], t_c0, plan.live, plan.bucket
                    )
                m.dispatch_queue_depth.set(self._dispatch_q.qsize())
                m.pipeline_queue_depth.set(self._q.qsize())
        finally:
            self._dispatch_q.put(None)
            prep_pool.shutdown(wait=False)

    def _dispatcher(self) -> None:
        """The dispatch-owner: the ONLY thread that touches the device —
        it issues the host->device transfers AND launches the kernels,
        interleaved as two stages of one loop (ISSUE 7 tentpole): batch
        k+1's `device_put` is issued BEFORE blocking on the depth
        semaphore, so its H2D copy rides behind kernel k's compute
        instead of serializing in front of its own launch. Timeline at
        steady state:

            transfer k+1  ||  kernel k  ||  readback k-1 (resolver)

        Prepared batches arrive FIFO; `pipeline.transfer` records the
        copy issue (with hidden=1 when a kernel was in flight — the
        transfer_overlap_ratio source) and `pipeline.queue_wait` now
        records PURE depth backpressure (transferred-to-launched), so
        span_summary separates wait from device time (`pipeline.dispatch`).
        The buffer pool bounds transferred-but-unresolved input sets and
        counts recycled vs minted slots."""
        m = _backend._ops_m()
        # occupancy/overlap are WINDOWED (reset every ~2s): a cumulative-
        # since-start average would read near zero forever after a long
        # idle stretch, hiding device saturation from /status
        busy = _dpool.WindowedRatio(m.dispatch_busy_ratio, wall=True)
        overlap = _dpool.WindowedRatio(m.transfer_overlap_ratio, wall=False)
        t_free = 0.0  # this thread's last launch: a hand-off starts no earlier
        while True:
            try:
                item = self._dispatch_q.get(timeout=2.0)
            except queue.Empty:
                # idle tick: decay both windows so the gauges read ~0
                # when no traffic flows instead of sticking at the last
                # busy/overlap value
                busy.tick()
                overlap.tick()
                continue
            if item is None:
                self._resolve_q.put(None)
                break
            if item[0] == "xfered":
                # a batch this loop already transferred, then requeued to
                # let a higher-priority arrival overtake it at the depth
                # block (ISSUE 13) — its pool slot and device buffers
                # carry over; it re-enters directly at the launch stage
                (_tag, spans, f, dev_args, rlc_entries, bucket,
                 xslot, t_enq, pri, t_xfer_done, launch) = item
                fut = None
            else:
                spans, fut, t_enq = item[:3]
                pri = item[3] if len(item) > 3 else PRIORITY_CONSENSUS
                launch = item[4] if len(item) > 4 else 0
                xslot = None
                t_xfer_done = 0.0
            if _trace.TRACER.enabled:
                _trace.TRACER.set_thread_args(launch=launch)
            # Dispatcher survival invariant: NOTHING a single batch does —
            # prep failure, metrics accounting, the transfer, epoch-table
            # upload inside the kernel closure, the launch itself — may
            # kill or wedge this thread. A poisoned batch fails ONLY its
            # own futures (wrapped in DispatchError with epoch/bucket
            # context) and the loop moves to the next item with the depth
            # semaphore AND its pool slot intact (sem_held/slot track
            # both so even the last-resort handler leaks neither).
            sem_held = False
            ing_held = False
            slot = xslot
            if fut is not None:
                bucket = 0
            try:
                m.dispatch_queue_depth.set(self._dispatch_q.qsize())
                if fut is not None:
                    # QoS preemption point A (ISSUE 13): the PREP wait.
                    # Host prep of a fused ingress superbatch can run tens
                    # of ms; nothing device-side is held yet, so when a
                    # higher-priority batch queues up behind this wait the
                    # untouched item requeues as-is and the urgent one is
                    # served first.
                    requeued = False
                    prep_err = None
                    while True:
                        try:
                            prep, t_ready = fut.result(timeout=0.002)
                            break
                        except _FutTimeout:
                            best = self._dispatch_q.best_priority()
                            if best is not None and best < pri:
                                self._dispatch_q.put(
                                    (spans, fut, t_enq, pri, launch),
                                    priority=pri,
                                )
                                self._note_preempt(1)
                                requeued = True
                                break
                        except Exception as e:  # noqa: BLE001 — prep
                            prep_err = e
                            break
                    if requeued:
                        continue
                    if prep_err is not None:
                        self._fail_spans(spans, self._wrap_dispatch_err(
                            "batch prep failed", prep_err, 0, spans))
                        continue
                    # mesh preps append per-arg transfer shardings as
                    # a 5th element (lane-per-device placement);
                    # classic preps stay 4-tuples
                    shardings = prep[4] if len(prep) > 4 else None
                    f, args, rlc_entries, bucket = prep[:4]
                    try:
                        # transfer accounting: host bytes this launch
                        # ships, averaged over the commits fused into it —
                        # the gauge a warm epoch cache visibly shrinks
                        # (/status)
                        m.h2d_bytes_per_commit.set(
                            _backend.h2d_arg_bytes(args) / max(len(spans), 1)
                        )
                    except Exception:  # noqa: BLE001 — never fatal
                        pass
                    self.dispatch_thread_idents.add(threading.get_ident())
                    # devcheck device ownership (ISSUE 8): this thread
                    # claims the device; any transfer/upload from another
                    # thread now asserts (no-op when TM_TPU_DEVCHECK off)
                    _devcheck.claim_device("verify-dispatch")
                    # -- stage 1: transfer (before the depth block) ------
                    try:
                        slot = self._pool.acquire(
                            _dpool.layout_key(bucket, args),
                            abort=self._stopped.is_set,
                        )
                        hidden = self._inflight > 0
                        t_x0 = time.perf_counter()
                        if _trace.TRACER.enabled:
                            # the hand-off to this thread (and the pool
                            # slot): prepared, enqueued and this thread
                            # free -> transfer. Behind a busy dispatcher
                            # the batch's wait is the earlier launch's spans
                            _trace.TRACER.record(
                                "pipeline.queue_wait.dispatch",
                                max(t_enq, t_ready, t_free), t_x0,
                            )
                        # positional call when unsharded: test doubles
                        # (and any older transfer impl) keep their
                        # (args)-only signature working
                        with _span("pipeline.transfer", bucket=bucket,
                                   hidden=int(hidden)):
                            if shardings is None:
                                dev_args = _dpool.transfer(args)
                            else:
                                dev_args = _dpool.transfer(
                                    args, shardings=shardings
                                )
                        t_x1 = time.perf_counter()
                        if slot is not None:
                            slot.arrays = dev_args
                        overlap.add(
                            t_x1 - t_x0 if hidden else 0.0, t_x1 - t_x0
                        )
                        busy.add(t_x1 - t_x0)
                    except Exception as e:  # noqa: BLE001
                        self._pool.release(slot)
                        slot = None
                        self._fail_spans(spans, self._wrap_dispatch_err(
                            "batch transfer failed", e, bucket, spans))
                        continue
                    # -- stage 2: launch (behind the depth semaphore) ----
                    t_xfer_done = time.perf_counter()
                    t_enq = max(t_enq, t_ready)
                # QoS preemption point B (ISSUE 13): while parked here with
                # a lower-urgency batch in hand, a queued higher-priority
                # batch may overtake — this batch requeues WITH its
                # transferred state (pool slot + device buffers), so the
                # consensus commit's wait shrinks to in-flight launches
                # only, never the whole transferred backlog. An in-flight
                # launch is never revoked. INGRESS batches additionally
                # pass through the reserved-lane semaphore first, leaving
                # one launch slot the tx flood can never fill.
                requeued = False
                if pri > PRIORITY_CONSENSUS and self._depth > 1:
                    # test seam (ISSUE 16, gated like the alias/owner
                    # seams): with the "starve" lintbug armed the
                    # reserved-lane semaphore is broken for ingress —
                    # its acquire never succeeds, so tx batches park
                    # here forever while consensus/replay keep
                    # overtaking. The soak harness must catch this via
                    # its ingress-admission SLO, not by luck.
                    starved = (pri >= PRIORITY_INGRESS
                               and _devcheck.inject_lintbug("starve"))
                    while starved or not self._ing_sem.acquire(timeout=0.002):
                        if starved:
                            time.sleep(0.002)
                        if self._stopped.is_set():
                            # shutdown while parked: fail the batch and
                            # return its slot instead of wedging close()
                            self._pool.release(slot)
                            slot = None
                            self._fail_spans(
                                spans, self._wrap_dispatch_err(
                                    "pipeline stopped while queued",
                                    RuntimeError("shutdown"), bucket, spans))
                            requeued = True
                            break
                        best = self._dispatch_q.best_priority()
                        if best is not None and best < pri:
                            self._dispatch_q.put(
                                ("xfered", spans, f, dev_args, rlc_entries,
                                 bucket, slot, t_enq, pri, t_xfer_done,
                                 launch),
                                priority=pri,
                            )
                            slot = None  # rode along with the item
                            self._note_preempt(1)
                            requeued = True
                            break
                    ing_held = not requeued
                if not requeued:
                    while not self._sem.acquire(timeout=0.002):
                        best = self._dispatch_q.best_priority()
                        if best is not None and best < pri:
                            self._dispatch_q.put(
                                ("xfered", spans, f, dev_args, rlc_entries,
                                 bucket, slot, t_enq, pri, t_xfer_done,
                                 launch),
                                priority=pri,
                            )
                            slot = None  # ownership rode along
                            if ing_held:
                                self._ing_sem.release()
                                ing_held = False
                            self._note_preempt(1)
                            requeued = True
                            break
                if requeued:
                    continue
                sem_held = True
                t0 = time.perf_counter()
                # per-QoS-lane queue wait (ISSUE 16): the scrapeable
                # counterpart of the queue_wait span — ingress starvation
                # shows up here as a fat ingress tail, visible to /status
                # and the soak sampler without tracing enabled
                m.queue_wait_seconds.observe(
                    max(t0 - max(t_enq, t_xfer_done), 0.0),
                    lane=_LANE_NAMES.get(min(pri, PRIORITY_INGRESS),
                                         "ingress"),
                )
                if _trace.TRACER.enabled:
                    _trace.TRACER.record(
                        "pipeline.queue_wait",
                        max(t_enq, t_xfer_done), t0,
                        {"bucket": bucket},
                    )
                try:
                    with _span("pipeline.dispatch", bucket=bucket,
                               **_rlc_width_arg(rlc_entries, bucket)):
                        dev = f(*dev_args)
                    if _trace.TRACER.enabled:
                        # one launch serves many coalesced jobs: step each
                        # job's flow through the dispatch instant so every
                        # chain passes through this batch's slice
                        for _j, _, _ in spans:
                            if getattr(_j, "flow", None) is not None:
                                _trace.TRACER.flow_point(
                                    "pipeline.dispatch.flow", _j.flow, "t",
                                    bucket=bucket,
                                )
                    # start the device->host copy NOW: an async copy
                    # rides behind the compute so the later wait() in
                    # _resolve finds the bytes already host-side.
                    # Capability probed ONCE at init
                    # (_d2h_async_supported) — no silent per-batch except.
                    with _span("pipeline.dispatch.readback_start"):
                        rb = _Readback(dev, self._d2h_async)
                except Exception as e:  # noqa: BLE001
                    # epoch-table upload (lazy, inside the cached-kernel
                    # closure) or the launch itself blew up: release the
                    # depth slot + buffer slot and fail this batch alone
                    self._sem.release()
                    sem_held = False
                    if ing_held:
                        self._ing_sem.release()
                        ing_held = False
                    self._pool.release(slot)
                    slot = None
                    self._fail_spans(spans, self._wrap_dispatch_err(
                        "kernel dispatch failed", e, bucket, spans))
                    continue
                with self._mtx:
                    self._inflight += 1
                    m.pipeline_inflight.set(self._inflight)
                t_free = now = time.perf_counter()
                busy.add(now - t0)
                self._resolve_q.put(
                    (spans, rb, rlc_entries, now, bucket, slot, ing_held,
                     launch),
                    priority=pri,
                )
                sem_held = False  # resolver now owns the release
                ing_held = False  # (both semaphores and the pool slot)
                slot = None
            except Exception as e:  # noqa: BLE001 — last-resort isolation
                if sem_held:
                    self._sem.release()
                if ing_held:
                    self._ing_sem.release()
                self._pool.release(slot)
                self._fail_spans(spans, self._wrap_dispatch_err(
                    "dispatch bookkeeping failed", e, bucket, spans))

    @staticmethod
    def _wrap_dispatch_err(msg, e, bucket, spans) -> "DispatchError":
        _backend._ops_m().dispatch_errors.inc()
        err = DispatchError(
            f"{msg}: {e!r}",
            bucket=bucket,
            epoch_key=getattr(spans[0][0].entries, "epoch_key", None)
            if spans else None,
        )
        err.__cause__ = e
        return err

    @staticmethod
    def _fail_spans(spans, err: BaseException) -> None:
        for j, _, _ in spans:
            if not j.future.done():
                j.future.set_exception(err)

    def _resolver(self) -> None:
        """Completes futures: blocks on device materialization so neither
        the coalescer nor the dispatch-owner ever waits on a result. Also
        returns each batch's buffer-pool slot — the input buffers' flight
        ends when the verdicts are read back (or the batch fails)."""
        m = _backend._ops_m()
        t_free = 0.0  # this thread's last resolve: a hand-off starts no earlier
        while True:
            item = self._resolve_q.get()
            if item is None:
                break
            spans, rb, rlc_entries, t_dispatch, bucket, slot = item[:6]
            ing_held = item[6] if len(item) > 6 else False
            launch = item[7] if len(item) > 7 else 0
            tracing = _trace.TRACER.enabled
            if tracing:
                _trace.TRACER.set_thread_args(launch=launch)
                _trace.TRACER.record("pipeline.queue_wait.resolve",
                                     max(t_dispatch, t_free),
                                     time.perf_counter())
            if _devcheck.inject_lintbug("owner"):
                # test seam (ISSUE 8): touch the device from the resolver
                # thread — devcheck's ownership assertion must fire
                try:
                    _dpool.transfer((np.zeros(1, dtype=np.uint8),))
                except _devcheck.DevcheckViolation:
                    pass  # recorded; the injected run continues
            try:
                self._resolve(spans, rb, rlc_entries, t_dispatch, bucket,
                              launch)
            finally:
                self._pool.release(slot)
                with self._mtx:
                    self._inflight -= 1
                    m.pipeline_inflight.set(self._inflight)
                self._sem.release()
                if ing_held:
                    self._ing_sem.release()
            if tracing:
                t_free = time.perf_counter()


_shared: Optional[AsyncBatchVerifier] = None
_shared_mtx = threading.Lock()


def shared_verifier() -> AsyncBatchVerifier:
    """Process-wide pipeline instance (device submission is serialized
    through one thread regardless of how many reactors use it)."""
    global _shared
    with _shared_mtx:
        if _shared is None:
            _shared = AsyncBatchVerifier()
        return _shared


# ---------------------------------------------------------------------------
# Commit-level helpers: host-side entry construction mirrors
# types/validation.go:152 verifyCommitBatch, device path per signature.
# ---------------------------------------------------------------------------


def commit_entries(
    chain_id: str, vals, commit, voting_power_needed: int
) -> Tuple[EntryBlock, int]:
    """Build the columnar EntryBlock for a commit's for-block signatures
    (index lookup, early-stop past 2/3 like validation.go:152 with
    countAllSignatures=false). Returns (block, tallied_power). Raises on
    structural problems (bad counts, short power).

    The sign bytes come back as ONE contiguous buffer + offset table
    (Commit.vote_sign_bytes_block) and ride by reference all the way to
    the kernel prep — no per-signature PyBytes or tuples. Callers that
    need tuples can block.to_entries().

    Columnar commits (CommitBlock from wire decode, or built+cached on
    first use) with all-ed25519 validator columns take the FUSED path:
    selection, tally, sign-bytes and gather in one call (native when
    built; the GIL is given up from 1 024 selected rows)."""
    from . import commit_prep as _cp

    with _span("pipeline.commit_prep_fused", n=len(commit.signatures)):
        fused = _cp.prep_commit_from(
            commit,
            vals,
            chain_id,
            voting_power_needed,
            _cp.MODE_SELECT_COMMIT_ONLY | _cp.MODE_EARLY_STOP,
        )
    if fused is not None:
        sel, tallied, blk = fused
        if blk is None:
            raise ErrNotEnoughVotingPowerSigned(
                got=tallied, needed=voting_power_needed
            )
        return blk, tallied
    return commit_entries_legacy(chain_id, vals, commit, voting_power_needed)


def commit_entries_legacy(
    chain_id: str, vals, commit, voting_power_needed: int
) -> Tuple[EntryBlock, int]:
    """The PR-2 columnar path, object-walking selection + per-stage
    composition: the fallback for non-columnar commits/valsets, and the
    baseline the fused path is held equal to (tools/prep_bench.py
    --fused, tests/test_commit_block.py)."""
    idxs = []
    tallied = 0
    for idx, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        idxs.append(idx)
        tallied += vals.validators[idx].voting_power
        if tallied > voting_power_needed:
            break
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(got=tallied, needed=voting_power_needed)
    sigs = commit.signatures
    if any(len(sigs[i].signature) != 64 for i in idxs):
        raise ValueError("invalid signature length")
    buf, offsets = commit.vote_sign_bytes_block(chain_id, idxs)
    n = len(idxs)
    idx_arr = val_idx = np.asarray(idxs, dtype=np.int32)
    cols = vals.ed25519_columns()
    epoch_key = None
    scheme = "ed25519"
    pub_aux = None
    if cols is not None:
        # columnar valset, non-columnar commit: gather the cached pub
        # rows instead of re-joining pub_key.bytes() per commit (the
        # column build + key-type proof already ran once per epoch), and
        # carry the epoch metadata so warm epochs skip shipping pubs
        pub = cols[0][idx_arr]
        from . import epoch_cache as _epoch

        epoch_key, val_idx = _epoch.table_rows(vals, idx_arr)
    elif (scols := vals.secp256k1_columns()) is not None:
        # all-secp256k1 committee (ISSUE 19): gather the 33-byte SEC1
        # rows and route the block through the scheme lane — the prefix
        # column splits off so downstream columns stay 32-wide
        raw = scols[0][idx_arr]
        from . import epoch_cache as _epoch

        pub_aux = np.ascontiguousarray(raw[:, 0])
        pub = np.ascontiguousarray(raw[:, 1:])
        scheme = "secp256k1"
        epoch_key, val_idx = _epoch.table_rows(vals, idx_arr)
    else:
        pub_b = b"".join(vals.validators[i].pub_key.bytes() for i in idxs)
        if len(pub_b) != 32 * n:
            # a wrong-size key (e.g. secp256k1 in an ed25519 set) must
            # surface as the error the per-entry path raised, not a
            # reshape failure
            raise TypeError("pubkey is not ed25519")
        pub = np.frombuffer(pub_b, dtype=np.uint8).reshape(n, 32)
    sig = np.frombuffer(
        b"".join(sigs[i].signature for i in idxs), dtype=np.uint8
    ).reshape(n, 64)
    return EntryBlock(pub, sig, buf, offsets,
                      val_idx=val_idx, epoch_key=epoch_key,
                      scheme=scheme, pub_aux=pub_aux), tallied


def verify_commits_pipelined(
    chain_id: str,
    jobs: Sequence[Tuple[object, object, int, object]],
    verifier: Optional[AsyncBatchVerifier] = None,
) -> List[Optional[str]]:
    """jobs: (vals, block_id, height, commit) per header. All host prep
    and device batches flow through the pipeline; returns one entry per
    job — None on success or an error string.

    The per-job semantics match verify_commit_light (types/validation.go
    :59): basic val/commit binding, then +2/3 of `vals` must have signed
    `block_id` at `height` with valid signatures.
    """
    from ..types.validation import _verify_basic_vals_and_commit

    v = verifier or shared_verifier()
    errors: List[Optional[str]] = [None] * len(jobs)

    # The whole job list is known upfront, so entries are packed into
    # FULL max-bucket device batches here instead of relying on the
    # worker's opportunistic coalescing: per-job submission races the
    # worker's queue drain and leaves undersized dispatches.
    # A job's signatures may straddle two batches; verdicts re-aggregate
    # per job below. NOTE this intentionally layers over the worker's own
    # span machinery (_worker packs STREAMED submissions; this packs a
    # KNOWN-size job list) — each full chunk passes through the worker
    # 1:1, so the worker's spans are trivial for this path.
    max_b = _backend.BUCKETS[-1]
    futures: List[Future] = []
    job_spans: List[list] = [[] for _ in jobs]  # (future_idx, off, n)
    cur: list = []  # EntryBlocks (or zero-copy slices of them)
    cur_n = 0
    cur_spans: list = []  # (job_idx, off_in_batch, n)

    def _flush() -> None:
        nonlocal cur, cur_n, cur_spans
        if not cur:
            return
        fi = len(futures)
        futures.append(v.submit(EntryBlock.concat(cur)))
        for job_i, off, n in cur_spans:
            job_spans[job_i].append((fi, off, n))
        cur, cur_n, cur_spans = [], 0, []

    for i, (vals, block_id, height, commit) in enumerate(jobs):
        try:
            _verify_basic_vals_and_commit(vals, commit, height, block_id)
            needed = vals.total_voting_power() * 2 // 3
            entries, _ = commit_entries(chain_id, vals, commit, needed)
        except (ValueError, RuntimeError) as e:
            errors[i] = str(e)
            continue
        # scheme gate (ISSUE 19): a batch concats only same-scheme
        # blocks (EntryBlock.concat raises across schemes) — flush the
        # running batch before a job that switches scheme
        scheme_i = getattr(entries, "scheme", "ed25519")
        if cur and getattr(cur[0], "scheme", "ed25519") != scheme_i:
            _flush()
        pos = 0
        while pos < len(entries):
            take = min(len(entries) - pos, max_b - cur_n)
            cur_spans.append((i, cur_n, take))
            # a job straddling two device batches rides as a zero-copy
            # slice of its block — no per-signature re-packing
            cur.append(entries[pos : pos + take])
            cur_n += take
            pos += take
            if cur_n >= max_b:
                _flush()
    _flush()

    results: List[object] = []
    for fut in futures:
        try:
            results.append(np.asarray(fut.result(timeout=300)))
        except Exception as e:  # noqa: BLE001
            results.append(e)
    for i in range(len(jobs)):
        if errors[i] is not None:
            continue
        pos_in_job = 0
        for fi, off, n in job_spans[i]:
            r = results[fi]
            if isinstance(r, Exception):
                errors[i] = str(r)
                break
            # _resolve already normalized pallas output to a 1-D array
            seg = np.asarray(r[off : off + n]).astype(bool)
            if not seg.all():
                # report the signature index WITHIN this job's entries
                # (validation.go:242-248 blame assignment), not the lane
                # of the packed multi-job device batch
                bad = pos_in_job + int(np.argmin(seg))
                errors[i] = f"wrong signature (entry {bad})"
                break
            pos_in_job += n
    return errors


def verify_headers_pipelined(
    chain_id: str,
    trusted_header,
    headers: Sequence[Tuple[object, object]],
) -> None:
    """Pipelined ADJACENT header-chain verification (BASELINE config #5:
    light/verifier.go VerifyAdjacent's checks over a fetched range, with
    all commit signature batches overlapped on the device).

    headers: ordered [(signed_header, validator_set), ...] starting at
    trusted_header.height + 1, strictly adjacent. Raises ValueError on the
    first failure (host continuity checks first — they are cheap — then
    the pipelined signature verdicts in order)."""
    from ..types.block import BlockID

    prev = trusted_header
    jobs = []
    for sh, vals in headers:
        if sh.header.height != prev.header.height + 1:
            raise ValueError(
                f"headers must be adjacent: {sh.header.height} after {prev.header.height}"
            )
        sh.validate_basic(chain_id)
        if sh.header.validators_hash != vals.hash():
            raise ValueError(
                f"header {sh.header.height} validators_hash does not match supplied set"
            )
        if sh.header.validators_hash != prev.header.next_validators_hash:
            raise ValueError(
                f"header {sh.header.height} validators_hash breaks continuity"
            )
        jobs.append(
            (
                vals,
                BlockID(
                    hash=sh.commit.block_id.hash,
                    part_set_header=sh.commit.block_id.part_set_header,
                ),
                sh.header.height,
                sh.commit,
            )
        )
        prev = sh
    errors = verify_commits_pipelined(chain_id, jobs)
    for (sh, _), err in zip(headers, errors):
        if err is not None:
            raise ValueError(f"header {sh.header.height}: {err}")
