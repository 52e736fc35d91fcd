"""FleetClient: the node-side end of the verification fleet.

Duck-typed as a pipeline verifier — ``submit(entries, flow=None,
priority=0) -> Future`` resolving to an (n,) bool verdict array — so it
plugs straight into the ingress fabric's ``LaneSpec.verifier`` seam: a
lane routes its flushed windows over the wire instead of into the local
engine, and nothing else about the lane changes.

Health + graceful degradation contract (the load-bearing part):

* Every request carries a deadline (``TM_TPU_FLEET_TIMEOUT_MS``). A
  timeout or any socket error marks the fleet DOWN: all in-flight
  futures fail with ``FleetUnavailable`` and further ``submit()`` calls
  raise it immediately — no queueing behind a dead fleet, no stall.
* ``FleetUnavailable.fallback_to_host`` is the duck-typed marker the
  ingress completer checks: windows that died post-submit host-verify
  through the lane's existing ``host_fn`` instead of poisoning; a
  pre-submit raise rides the lane's ``submit_error_to_host`` path. The
  ingress fabric never imports this module.
* While down, a rejoin thread redials every ``TM_TPU_FLEET_REJOIN_MS``;
  on success the client is UP again and the next window rides the
  fleet. RTT is tracked as an EWMA and exported via FleetMetrics.

A server-side verification failure (ERROR frame, code DISPATCH) is NOT
a fleet failure: the future fails with ``RemoteDispatchError`` — which
deliberately lacks the fallback marker — so it poisons exactly that
window, mirroring a local DispatchError.
"""

from __future__ import annotations

import itertools
import os
import socket
import threading
import time
from concurrent.futures import Future
from typing import Dict, Optional, Tuple

import numpy as np

from ..libs.metrics import fleet_metrics
from ..observability.trace import TRACER
from . import wire

_DEF_TIMEOUT_MS = 5000.0
_DEF_REJOIN_MS = 500.0
_EWMA_ALPHA = 0.2


def _env_ms(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class FleetUnavailable(RuntimeError):
    """The fleet is down (timeout / socket error / not yet joined).

    ``fallback_to_host`` is the duck-typed contract with ops/ingress.py:
    a lane whose in-flight window fails with an error carrying this
    marker host-verifies the window instead of poisoning it.
    """

    fallback_to_host = True


class RemoteDispatchError(RuntimeError):
    """The fleet answered with an ERROR frame: the verifier on the far
    side raised for this request. Poisons only this window — no host
    fallback (the same work would likely fail locally too)."""

    def __init__(self, message: str, code: int = wire.ERR_DISPATCH):
        super().__init__(message)
        self.code = code


class FleetClient:
    """One node's connection to a fleet host.

    ``lane`` is declared per-submit via the LaneSpec seam's wrapper (or
    defaults to the client ``name``) and rides the wire so the server's
    per-lane counters and the cross-node coalescer see who sent what.
    """

    def __init__(self, addr: Tuple[str, int], name: str = "node",
                 lane: str = "", timeout_ms: Optional[float] = None,
                 rejoin_ms: Optional[float] = None,
                 connect: bool = True):
        self._addr = addr
        self.name = name
        self._lane = lane or name
        self._timeout_s = (
            timeout_ms if timeout_ms is not None
            else _env_ms("TM_TPU_FLEET_TIMEOUT_MS", _DEF_TIMEOUT_MS)
        ) / 1000.0
        self._rejoin_s = (
            rejoin_ms if rejoin_ms is not None
            else _env_ms("TM_TPU_FLEET_REJOIN_MS", _DEF_REJOIN_MS)
        ) / 1000.0
        self._target = "%s:%d" % addr
        self._m = fleet_metrics()
        self._mtx = threading.Lock()
        # serializes whole-frame writes: two threads flushing windows
        # concurrently must not interleave their iovecs on the stream
        self._send_mtx = threading.Lock()
        self._sock: Optional[socket.socket] = None
        self._epoch = 0  # bumps on every disconnect; stale threads exit
        self._pending: Dict[int, Tuple[Future, float]] = {}
        self._next_req = itertools.count(1)
        self._closed = threading.Event()
        self._rejoining = False
        self._rtt_ewma_s: Optional[float] = None
        self.rejoins = 0
        self.fallbacks = 0
        self.timeouts = 0
        self._m.client_connected.set(0, target=self._target)
        if connect:
            try:
                self._connect_locked_entry()
            except OSError:
                self._schedule_rejoin()

    # -- public surface ------------------------------------------------

    @property
    def connected(self) -> bool:
        with self._mtx:
            return self._sock is not None

    def rtt_ewma_ms(self) -> Optional[float]:
        with self._mtx:
            return self._rtt_ewma_s * 1000.0 if self._rtt_ewma_s else None

    def stats(self) -> dict:
        with self._mtx:
            return {
                "target": self._target,
                "connected": self._sock is not None,
                "rtt_ewma_ms": (
                    self._rtt_ewma_s * 1000.0 if self._rtt_ewma_s else None
                ),
                "pending": len(self._pending),
                "rejoins": self.rejoins,
                "fallbacks": self.fallbacks,
                "timeouts": self.timeouts,
            }

    def submit(self, entries, flow: Optional[int] = None,
               priority: int = 0) -> Future:
        """Verifier-shaped submit: ship the block to the fleet, return a
        Future resolving to the (n,) bool verdict array. Raises
        FleetUnavailable immediately while degraded."""
        from ..ops.entry_block import as_block
        block = as_block(entries)
        with self._mtx:
            if self._closed.is_set():
                raise FleetUnavailable("fleet client closed")
            sock = self._sock
            if sock is None:
                self.fallbacks += 1
                self._m.client_fallbacks.inc(target=self._target)
                raise FleetUnavailable(
                    f"fleet {self._target} is down (rejoining)")
            rid = next(self._next_req)
            fut: Future = Future()
            self._pending[rid] = (fut, time.monotonic())
        iov = wire.encode_submit(rid, block, flow=flow or 0,
                                 priority=priority, lane=self._lane)
        TRACER.flow_point("fleet.client.send", flow, "t",
                          target=self._target, n=len(block))
        self._m.client_requests.inc(target=self._target)
        try:
            with self._send_mtx:
                wire.send_frame(sock, iov)
        except OSError as e:
            self._mark_down(f"send failed: {e}")
            # _mark_down already failed `fut` along with everything else
        return fut

    def close(self) -> None:
        self._closed.set()
        self._mark_down("client closed")

    # -- connection lifecycle -----------------------------------------

    def _connect_locked_entry(self, rejoin: bool = False) -> None:
        """Dial and install a fresh connection (raises OSError). A
        rejoin is counted under the lock that installs the socket, so no
        reader sees `connected` with the redial that made it uncounted."""
        sock = socket.create_connection(self._addr, timeout=self._timeout_s)
        sock.settimeout(None)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        with self._mtx:
            # clear the rejoin flag HERE, atomically with installing the
            # socket: if this connection dies instantly, the reader's
            # _mark_down -> _schedule_rejoin must see rejoining=False or
            # nobody ever redials again
            self._rejoining = False
            if self._closed.is_set():
                sock.close()
                return
            self._sock = sock
            self._epoch += 1
            epoch = self._epoch
            self.rejoins += rejoin
        self._m.client_connected.set(1, target=self._target)
        threading.Thread(target=self._read_loop, args=(sock, epoch),
                         name=f"fleet-client-read-{self.name}",
                         daemon=True).start()
        threading.Thread(target=self._watchdog, args=(epoch,),
                         name=f"fleet-client-watch-{self.name}",
                         daemon=True).start()

    def _mark_down(self, reason: str) -> None:
        with self._mtx:
            sock, self._sock = self._sock, None
            dead = list(self._pending.values())
            self._pending.clear()
            self._epoch += 1
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        self._m.client_connected.set(0, target=self._target)
        if dead:
            self.fallbacks += len(dead)
            self._m.client_fallbacks.inc(len(dead), target=self._target)
        err = FleetUnavailable(f"fleet {self._target} unavailable: {reason}")
        for fut, _t in dead:
            if not fut.done():
                fut.set_exception(err)
        if not self._closed.is_set():
            self._schedule_rejoin()

    def _schedule_rejoin(self) -> None:
        with self._mtx:
            if self._rejoining or self._closed.is_set():
                return
            self._rejoining = True
        threading.Thread(target=self._rejoin_loop,
                         name=f"fleet-client-rejoin-{self.name}",
                         daemon=True).start()

    def _rejoin_loop(self) -> None:
        while not self._closed.is_set():
            time.sleep(self._rejoin_s)
            if self._closed.is_set():
                break
            try:
                self._connect_locked_entry(rejoin=True)
            except OSError:
                continue
            self._m.client_rejoins.inc(target=self._target)
            return  # flag already cleared inside _connect_locked_entry
        with self._mtx:
            self._rejoining = False

    # -- reader + watchdog --------------------------------------------

    def _read_loop(self, sock: socket.socket, epoch: int) -> None:
        decoder = wire.FrameDecoder()
        while True:
            try:
                data = sock.recv(1 << 20)
            except OSError:
                data = b""
            if not data:
                with self._mtx:
                    stale = epoch != self._epoch
                if not stale:
                    self._mark_down("connection lost")
                return
            try:
                payloads = decoder.feed(data)
                frames = [wire.parse_frame(p) for p in payloads]
            except wire.WireError as e:
                with self._mtx:
                    stale = epoch != self._epoch
                if not stale:
                    self._mark_down(f"bad frame from fleet: {e}")
                return
            for frame in frames:
                self._dispatch_reply(frame)

    def _dispatch_reply(self, frame: wire.Frame) -> None:
        if isinstance(frame, wire.VerdictFrame):
            with self._mtx:
                ent = self._pending.pop(frame.request_id, None)
                if ent is not None:
                    rtt = time.monotonic() - ent[1]
                    if self._rtt_ewma_s is None:
                        self._rtt_ewma_s = rtt
                    else:
                        self._rtt_ewma_s += _EWMA_ALPHA * (rtt - self._rtt_ewma_s)
                    self._m.client_rtt_ewma_ms.set(
                        self._rtt_ewma_s * 1000.0, target=self._target)
            if ent is not None:
                fut = ent[0]
                if not fut.done():
                    fut.set_result(np.asarray(frame.verdicts, dtype=bool))
            return
        if isinstance(frame, wire.ErrorFrame):
            with self._mtx:
                ent = self._pending.pop(frame.request_id, None)
            if ent is not None:
                fut = ent[0]
                if not fut.done():
                    fut.set_exception(
                        RemoteDispatchError(frame.message, frame.code))
            # request_id 0 = connection-scoped error (malformed echo /
            # version skew report); nothing pending to fail
            return
        # a SUBMIT from the server makes no sense; ignore

    def _watchdog(self, epoch: int) -> None:
        tick = max(0.005, min(0.05, self._timeout_s / 4.0))
        while not self._closed.is_set():
            time.sleep(tick)
            now = time.monotonic()
            with self._mtx:
                if epoch != self._epoch:
                    return  # connection was replaced; a new watchdog runs
                expired = [
                    rid for rid, (_f, t0) in self._pending.items()
                    if now - t0 > self._timeout_s
                ]
            if expired:
                self.timeouts += len(expired)
                self._m.client_timeouts.inc(len(expired), target=self._target)
                # a stuck fleet is indistinguishable from a dead one:
                # degrade the whole connection (fails ALL pending) and
                # let the rejoin loop probe for recovery
                self._mark_down(f"{len(expired)} request(s) timed out")
                return


class LoopbackSession:
    """Socket-free client session over a LoopbackFleetHost (simnet).

    Synchronous and deterministic: encode → framing → host.handle →
    framing → decode, exercising the full wire path with no threads or
    wall clock. A killed host raises FleetUnavailable exactly like the
    real client's degraded mode."""

    def __init__(self, host, name: str = "node", lane: str = ""):
        self._host = host
        self.name = name
        self._lane = lane or name
        self._next_req = itertools.count(1)
        self.requests = 0
        self.fallbacks = 0

    def submit_block(self, block, *, flow: int = 0, priority: int = 0):
        rid = next(self._next_req)
        iov = wire.encode_submit(rid, block, flow=flow, priority=priority,
                                 lane=self._lane)
        data = b"".join(bytes(b) for b in iov)
        payloads = wire.FrameDecoder().feed(data)
        self.requests += 1
        try:
            reply_bytes = self._host.handle(payloads[0])
        except ConnectionError as e:
            self.fallbacks += 1
            raise FleetUnavailable(str(e)) from None
        reply = wire.parse_frame(wire.FrameDecoder().feed(reply_bytes)[0])
        if isinstance(reply, wire.ErrorFrame):
            raise RemoteDispatchError(reply.message, reply.code)
        assert isinstance(reply, wire.VerdictFrame) and reply.request_id == rid
        return np.asarray(reply.verdicts, dtype=bool)
