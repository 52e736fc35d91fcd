"""Per-shape device input-buffer pool for the overlapped dispatcher.

ISSUE 7 tentpole piece (1): the dispatch-owner thread used to hand the
jitted kernel bare numpy arrays, so every launch implicitly minted fresh
device allocations for the batch inputs and the H2D copy serialized in
front of the kernel inside the launch call. The pool makes the input
buffers an explicit, bounded resource:

- a **slot** is one in-flight batch's set of device input buffers for one
  compiled layout (bucket + per-array shapes/dtypes). Acquiring a slot
  bounds how many batch input sets may be alive on the device at once
  (double/triple buffering, ``TM_TPU_POOL_DEPTH``); releasing it — after
  the batch resolves, or fails — recycles the allocation for the next
  batch.
- ``transfer()`` issues the actual ``jax.device_put`` of a prepared
  argument tuple. The dispatcher calls it for batch k+1 *before* blocking
  on the depth semaphore, so the copy rides behind kernel k's compute
  instead of serializing in front of its own launch.
- with buffer **donation** on (``ops/ed25519_verify.jitted_verify(donate
  =True)`` and friends), the transferred arrays are donated to XLA at
  launch — their pages return to the allocator the moment the kernel has
  consumed them, so the next slot's ``device_put`` reuses the same
  allocation instead of growing the arena. JAX has no host-writes-into-
  existing-device-buffer API; donation + a bounded slot set IS the
  recycled-allocation steady state.

Epoch tables (ops/epoch_cache.py) never pass through the pool: they are
persistent device residents resolved inside the kernel closures and are
explicitly excluded from every kernel's ``donate_argnums``.

``buffer_pool_hits``/``buffer_pool_misses`` (OpsMetrics): a hit recycles
a previously-minted slot, a miss mints a new one. A steady-state stream
over one bucket shows misses == pool depth (warmup) and hits thereafter.
NOTE what these observe: the HOST-side bounded-slot invariant (in-flight
input sets per layout, and that error paths return slots) — the page
recycling itself happens inside XLA under donation and is not visible
from Python. ``tools/prep_bench.py --overlap`` gates the slot bound plus
the dispatcher's span order; at the default ``pool_depth = depth + 1``
the acquire path never blocks (the launch semaphore is the tighter
bound) — blocking engages when TM_TPU_POOL_DEPTH is set below that,
which throttles the transfer stage itself.

Pure bookkeeping + lazy jax: importable without jax (the pool is built at
pipeline init, which already sits behind the device stack, but tests
exercise the accounting standalone).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

try:
    from ..libs import devcheck as _devcheck
    from ..observability.trace import TRACER as _TRACER, span as _span
except ImportError:  # pragma: no cover — standalone file load (tests on
    # crypto-less containers exec this module by path, outside the
    # package); devcheck is stdlib+numpy so it loads the same way. The
    # tracer is not loaded that way: no spans there.
    _TRACER = None
    import importlib.util as _ilu
    import os as _os

    _dc_path = _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)),
        _os.pardir, "libs", "devcheck.py",
    )
    _dc_spec = _ilu.spec_from_file_location(
        "_tm_tpu_devcheck_standalone", _dc_path
    )
    _devcheck = _ilu.module_from_spec(_dc_spec)
    _dc_spec.loader.exec_module(_devcheck)

LayoutKey = Tuple


def layout_key(bucket: int, args) -> LayoutKey:
    """Compiled-layout key for a prepared argument tuple: the bucket plus
    every host array's (shape, dtype). Distinct preps (cached/uncached,
    per-signature, RLC) of the same bucket get distinct keys —
    a slot only ever recycles buffers of identical layout."""
    return (bucket,) + tuple(
        (a.shape, a.dtype.str) for a in args if isinstance(a, np.ndarray)
    )


def transfer(args, shardings=None) -> tuple:
    """Issue the H2D copy of a prepared argument tuple: ``device_put``
    every host array (jax Arrays — none on current paths, but e.g. a
    pre-resolved table — pass through untouched). Returns the tuple with
    device arrays in place of numpy ones. The call returns once the
    copies are *enqueued*; completion ordering against the kernel's reads
    is the runtime's job.

    ``shardings`` (ISSUE 9: mesh superbatches) is an optional per-arg
    sequence of jax Shardings — each array's copy is placed
    lane-per-device across the dispatcher's mesh instead of on the
    default device, so batch k+1's distributed H2D rides behind mesh
    kernel k exactly like the single-device overlap path."""
    # devcheck device assertion (ISSUE 8): transfers are device touches —
    # once a dispatcher has claimed the device, only it may issue them
    _devcheck.note_device_touch("device_pool.transfer")
    import jax

    if shardings is None:
        shardings = (None,) * len(args)
    elif len(shardings) != len(args):
        raise ValueError(
            f"{len(args)} args but {len(shardings)} transfer shardings"
        )
    # one span per put: a launch's transfer cost is per operation, not
    # per byte (the dispatcher's thread args name the launch); h2d_ops
    # counts the same operations with the tracer off
    traced = _TRACER is not None and _TRACER.enabled
    out = []
    puts = 0
    for a, s in zip(args, shardings):
        if not isinstance(a, np.ndarray):
            out.append(a)
            continue
        puts += 1
        if traced:
            with _span("pipeline.transfer.put", bytes=a.nbytes):
                out.append(jax.device_put(a, s))
        else:
            out.append(jax.device_put(a, s))
    _ops().h2d_ops.inc(puts)
    return tuple(out)


class PoolSlot:
    """One in-flight batch's input-buffer set. ``arrays`` pins the
    transferred device arrays for the slot's flight (leak tests introspect
    it); release clears it so nothing outlives the batch."""

    __slots__ = ("key", "arrays")

    def __init__(self, key: LayoutKey):
        self.key = key
        self.arrays: Optional[tuple] = None


class DeviceBufferPool:
    """Bounded per-layout slot pool (thread-safe).

    ``acquire`` blocks while ``depth`` slots of the SAME layout are in
    flight — that is the transfer-side backpressure bound, one deeper
    than the launch semaphore so batch k+1's copy can start while the
    pipeline is otherwise full. ``abort`` (a callable) lets a shutting-
    down dispatcher bail out of the wait."""

    def __init__(self, depth: int = 3):
        self.depth = max(int(depth), 1)
        self._mtx = _devcheck.lock("pool.slots")
        self._cv = threading.Condition(self._mtx)
        self._free: Dict[LayoutKey, List[PoolSlot]] = {}
        self._minted: Dict[LayoutKey, int] = {}
        self._in_flight = 0

    def acquire(self, key: LayoutKey,
                abort: Optional[Callable[[], bool]] = None,
                _metrics=None) -> Optional[PoolSlot]:
        """A slot for `key`: recycled when one is free (hit), minted while
        under depth (miss), else blocks until a release. Returns None only
        when `abort()` goes true while waiting."""
        m = _metrics if _metrics is not None else _ops()
        with self._cv:
            while True:
                free = self._free.get(key)
                if free:
                    slot = free.pop()
                    self._in_flight += 1
                    if m is not None:
                        m.buffer_pool_hits.inc()
                    return slot
                if self._minted.get(key, 0) < self.depth:
                    self._minted[key] = self._minted.get(key, 0) + 1
                    self._in_flight += 1
                    if m is not None:
                        m.buffer_pool_misses.inc()
                    return PoolSlot(key)
                if abort is not None and abort():
                    return None
                self._cv.wait(timeout=0.1)

    def release(self, slot: Optional[PoolSlot]) -> None:
        """Return a slot (idempotence is the caller's job — the dispatcher
        nulls its reference on handoff). None is a no-op so error paths
        can release unconditionally."""
        if slot is None:
            return
        if _devcheck.enabled():
            # write-after-resolve canary: the slot's flight is over — all
            # previously delivered verdicts must still be byte-stable,
            # and the returned device buffers get poisoned where the
            # backend exposes writable host views
            _devcheck.on_slot_release(slot.arrays)
        slot.arrays = None
        with self._cv:
            self._in_flight -= 1
            self._free.setdefault(slot.key, []).append(slot)
            self._cv.notify()

    # -- introspection (leak tests, /status, the --overlap gate) ---------

    def in_flight(self) -> int:
        with self._mtx:
            return self._in_flight

    def stats(self) -> dict:
        with self._mtx:
            return {
                "depth": self.depth,
                "in_flight": self._in_flight,
                "layouts": len(self._minted),
                "minted": int(sum(self._minted.values())),
                "free": int(sum(len(v) for v in self._free.values())),
            }


_ops_cached = None


def _ops():
    global _ops_cached
    if _ops_cached is None:
        from ..libs import metrics as _metrics

        _ops_cached = _metrics.ops_metrics()
    return _ops_cached


class WindowedRatio:
    """Windowed num/den ratio pushed to a gauge, reset every ~`window`
    seconds (ISSUE 7 satellite: the dispatcher carried three inline
    copies of this accounting for `dispatch_busy_ratio`).

    wall=True: occupancy mode — the denominator is wall-clock elapsed
    since the window opened (busy seconds / elapsed). wall=False: the
    caller accumulates both terms (e.g. hidden transfer time / total
    transfer time). `tick()` is the idle heartbeat: it rolls the window
    so the gauge decays toward the current (quiet) window instead of
    sticking at the last busy value."""

    def __init__(self, gauge, window: float = 2.0, wall: bool = True):
        self._g = gauge
        self._window = window
        self._wall = wall
        self._start = time.perf_counter()
        self._num = 0.0
        self._den = 0.0

    def _publish(self, now: float) -> None:
        if self._wall:
            elapsed = now - self._start
            # occupancy needs a minimum measurement base: a sample
            # landing right after a roll would divide by near-zero
            # elapsed and clamp the gauge to 1.0 on an idle device —
            # hold the previous value until the window has substance
            if elapsed >= min(self._window * 0.05, 0.05):
                self._g.set(min(self._num / elapsed, 1.0))
        elif self._den > 0:
            self._g.set(min(self._num / self._den, 1.0))

    def _roll(self, now: float) -> None:
        if now - self._start >= self._window:
            self._start, self._num, self._den = now, 0.0, 0.0

    def add(self, num: float, den: float = 0.0) -> None:
        now = time.perf_counter()
        # accumulate into the CURRENT window and publish before rolling:
        # a sample that closes a window genuinely spans it, and counting
        # it against the full elapsed window (then resetting) cannot
        # clamp the gauge to 1.0 the way crediting it to a zero-length
        # fresh window would. Stale pre-idle accumulators are not merged
        # in practice because the owner tick()s through idle stretches,
        # rolling the window long before the next sample lands.
        self._num += num
        self._den += den
        self._publish(now)
        self._roll(now)

    def tick(self) -> None:
        now = time.perf_counter()
        if now - self._start >= self._window:
            if not self._wall and self._den == 0:
                # ratio mode with an empty window: nothing flowed, so the
                # gauge decays to 0 (den==0 makes _publish a no-op)
                self._g.set(0.0)
            else:
                self._publish(now)
            self._start, self._num, self._den = now, 0.0, 0.0
