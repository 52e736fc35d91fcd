"""FleetServer: the network-facing end of the verification fleet.

Accepts wire frames over TCP, rebuilds EntryBlocks, and submits them to
an AsyncBatchVerifier at the client-declared QoS tier — so same-epoch
blocks from DIFFERENT nodes land in the same coalescer window and
cross-node coalesce into mesh lanes exactly like same-process callers.
Verdicts stream back in COMPLETION order (not submit order): each reply
carries the request_id so the client demuxes, and the submit frame's
flow id is continued through ``TRACER.flow_point`` so a flight-recorder
chain spans client-node → fleet → verdict.

Threading: one accept thread; per connection one reader thread and one
writer thread joined by an outbox queue. Verdict futures complete on
the verifier's resolver thread — the done-callback only ENQUEUES the
encoded reply, so the resolver never blocks on socket I/O and the
pipeline's lock discipline is preserved.

Failure containment mirrors the wire's error classes: a malformed or
version-skewed frame earns an ERROR reply and the connection lives on;
an oversize length prefix kills (only) that connection; a verifier
exception (DispatchError et al.) earns an ERROR frame with code
ERR_DISPATCH for just that request.
"""

from __future__ import annotations

import queue
import socket
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from ..libs.metrics import fleet_metrics
from ..observability.trace import TRACER
from . import wire

_PRIORITY_MAX = 2  # ingress — the lowest QoS tier the wire can name


class FleetServer:
    """Serve EntryBlock verification to remote nodes over the fleet wire.

    ``verifier`` is any object with ``submit(entries, flow=None,
    priority=0) -> Future`` (AsyncBatchVerifier-shaped). When None it is
    resolved lazily to ``ops.pipeline.shared_verifier()`` on the first
    accepted frame — constructing a FleetServer never spins up jax.
    """

    def __init__(self, addr: Tuple[str, int] = ("127.0.0.1", 0),
                 verifier=None):
        self._verifier = verifier
        self._m = fleet_metrics()
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(addr)
        self._lsock.listen(64)
        self._stopped = threading.Event()
        self._conn_mtx = threading.Lock()
        self._conns: Dict[int, "_Conn"] = {}
        self._next_conn = 0
        self._accept_thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------

    @property
    def addr(self) -> Tuple[str, int]:
        return self._lsock.getsockname()[:2]

    def start(self) -> "FleetServer":
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="fleet-accept", daemon=True
        )
        self._accept_thread.start()
        return self

    def stop(self) -> None:
        """Stop accepting and abort every live connection (simulates a
        fleet-host crash as far as clients can tell)."""
        if self._stopped.is_set():
            return
        self._stopped.set()
        # a blocked accept() is not reliably woken by close() on Linux:
        # poke the listener with a throwaway dial so the accept thread
        # observes _stopped and exits instead of eating the join timeout
        try:
            socket.create_connection(self.addr, timeout=1.0).close()
        except OSError:
            pass
        try:
            self._lsock.close()
        except OSError:
            pass
        with self._conn_mtx:
            conns = list(self._conns.values())
        for c in conns:
            c.abort()
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5.0)

    def stats(self) -> dict:
        with self._conn_mtx:
            return {
                "addr": "%s:%d" % self.addr if not self._stopped.is_set() else "",
                "connections": len(self._conns),
                "stopped": self._stopped.is_set(),
            }

    # -- internals -----------------------------------------------------

    def _resolve_verifier(self):
        if self._verifier is None:
            from ..ops.pipeline import shared_verifier
            self._verifier = shared_verifier()
        return self._verifier

    def _accept_loop(self) -> None:
        while not self._stopped.is_set():
            try:
                sock, _peer = self._lsock.accept()
            except OSError:
                return  # listener closed
            with self._conn_mtx:
                if self._stopped.is_set():
                    sock.close()
                    return
                cid = self._next_conn
                self._next_conn += 1
                conn = _Conn(self, cid, sock)
                self._conns[cid] = conn
            self._m.server_connections.set(len(self._conns))
            conn.start()

    def _drop_conn(self, cid: int) -> None:
        with self._conn_mtx:
            self._conns.pop(cid, None)
            n = len(self._conns)
        self._m.server_connections.set(n)


class _Conn:
    """One accepted client connection: reader + writer thread pair."""

    def __init__(self, server: FleetServer, cid: int, sock: socket.socket):
        self._server = server
        self._cid = cid
        self._sock = sock
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._outbox: "queue.Queue[Optional[bytes]]" = queue.Queue()
        self._closed = threading.Event()
        self._m = server._m

    def start(self) -> None:
        threading.Thread(
            target=self._read_loop, name=f"fleet-read-{self._cid}", daemon=True
        ).start()
        threading.Thread(
            target=self._write_loop, name=f"fleet-write-{self._cid}", daemon=True
        ).start()

    def abort(self) -> None:
        if self._closed.is_set():
            return
        self._closed.set()
        self._outbox.put(None)
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        self._server._drop_conn(self._cid)

    # -- reader --------------------------------------------------------

    def _read_loop(self) -> None:
        decoder = wire.FrameDecoder()
        try:
            while not self._closed.is_set():
                try:
                    data = self._sock.recv(1 << 20)
                except OSError:
                    return
                if not data:
                    return
                try:
                    payloads = decoder.feed(data)
                except wire.OversizeFrame as e:
                    # framing lost — reply best-effort, then close THIS
                    # connection; the server itself stays up
                    self._m.server_frames_rejected.inc(reason="oversize")
                    self._outbox.put(wire.encode_error(0, wire.ERR_OVERSIZE, str(e)))
                    return
                for payload in payloads:
                    self._handle_payload(payload)
        finally:
            self.abort()

    def _handle_payload(self, payload: bytes) -> None:
        try:
            frame = wire.parse_frame(payload)
        except wire.VersionSkew as e:
            self._m.server_frames_rejected.inc(reason="version")
            self._outbox.put(wire.encode_error(0, wire.ERR_VERSION, str(e)))
            return
        except wire.WireError as e:
            # recoverable: the length prefix framed the junk, so the
            # stream is still in sync — reject the frame, keep the conn
            self._m.server_frames_rejected.inc(reason="malformed")
            self._outbox.put(wire.encode_error(0, wire.ERR_MALFORMED, str(e)))
            return
        if not isinstance(frame, wire.SubmitFrame):
            self._m.server_frames_rejected.inc(reason="malformed")
            self._outbox.put(wire.encode_error(
                0, wire.ERR_MALFORMED, f"server expects SUBMIT, got kind "
                f"{type(frame).__name__}"))
            return
        self._submit(frame)

    def _submit(self, frame: wire.SubmitFrame) -> None:
        lane = frame.lane or "unlabeled"
        self._m.server_frames_accepted.inc(lane=lane)
        self._m.server_sigs.inc(len(frame.block), lane=lane)
        flow = frame.flow or None
        TRACER.flow_point("fleet.server.recv", flow, "t",
                          lane=lane, n=len(frame.block))
        priority = min(max(int(frame.priority), 0), _PRIORITY_MAX)
        request_id = frame.request_id
        try:
            verifier = self._server._resolve_verifier()
            try:
                fut = verifier.submit(frame.block, flow=flow,
                                      priority=priority, origin=lane)
            except TypeError:
                # duck-typed verifiers predating the origin= kwarg
                fut = verifier.submit(frame.block, flow=flow,
                                      priority=priority)
        except Exception as e:  # submit itself failed (closed, bad block)
            self._m.server_dispatch_errors.inc()
            self._outbox.put(wire.encode_error(
                request_id, wire.ERR_DISPATCH, str(e)))
            return

        def _done(f, _rid=request_id, _flow=flow):
            # Runs on the verifier's resolver thread: enqueue only —
            # never touch the socket here.
            try:
                verdicts = np.asarray(f.result(), dtype=bool)
            except Exception as e:
                self._m.server_dispatch_errors.inc()
                self._outbox.put(wire.encode_error(
                    _rid, wire.ERR_DISPATCH, str(e)))
                return
            TRACER.flow_point("fleet.server.verdict", _flow, "t",
                              n=int(verdicts.shape[0]))
            self._m.server_verdicts_streamed.inc()
            self._outbox.put(wire.encode_verdicts(_rid, verdicts))

        fut.add_done_callback(_done)

    # -- writer --------------------------------------------------------

    def _write_loop(self) -> None:
        while True:
            buf = self._outbox.get()
            if buf is None:
                return
            try:
                self._sock.sendall(buf)
            except OSError:
                self.abort()
                return


class LoopbackFleetHost:
    """A socket-free fleet host for deterministic (simnet) runs.

    Drives the SAME wire encode/parse code as the real server — so the
    serialization path is exercised and the tmlint fleet-transport rule
    keeps all wire calls inside fleet modules — but handles each frame
    synchronously: ``handle(payload) -> reply frame bytes``. The
    verifier here is any callable ``(EntryBlock, priority) -> (n,) bool
    array`` (simnet supplies a deterministic checker; no threads, no
    sockets, no wall clock).
    """

    def __init__(self, verify_fn):
        self._verify_fn = verify_fn
        self.killed = False
        self.frames_accepted = 0
        self.frames_rejected = 0
        self.sigs = 0
        self.by_priority: Dict[int, int] = {}

    def kill(self) -> None:
        self.killed = True

    def revive(self) -> None:
        self.killed = False

    def handle(self, payload: bytes) -> bytes:
        if self.killed:
            raise ConnectionError("fleet host is down")
        try:
            frame = wire.parse_frame(payload)
        except wire.WireError as e:
            self.frames_rejected += 1
            code = (wire.ERR_VERSION if isinstance(e, wire.VersionSkew)
                    else wire.ERR_MALFORMED)
            return wire.encode_error(0, code, str(e))
        if not isinstance(frame, wire.SubmitFrame):
            self.frames_rejected += 1
            return wire.encode_error(0, wire.ERR_MALFORMED,
                                     "host expects SUBMIT")
        self.frames_accepted += 1
        self.sigs += len(frame.block)
        pr = min(max(int(frame.priority), 0), _PRIORITY_MAX)
        self.by_priority[pr] = self.by_priority.get(pr, 0) + 1
        try:
            verdicts = np.asarray(self._verify_fn(frame.block, pr), dtype=bool)
        except Exception as e:
            return wire.encode_error(frame.request_id, wire.ERR_DISPATCH,
                                     str(e))
        return wire.encode_verdicts(frame.request_id, verdicts)
