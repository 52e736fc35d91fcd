"""FleetServer/FleetClient service behavior over real sockets (ISSUE 18).

The verifier here is a STUB (futures the test resolves by hand), so
these tests pin the transport contract itself — completion-order
verdict streaming, QoS/flow/lane preservation into the submit seam,
malformed-frame containment (ERROR reply, connection lives), oversize
containment (connection dies, server lives), dispatch-error class
(RemoteDispatchError, no host fallback) vs. fleet-death class
(FleetUnavailable, host fallback), deadline → degrade → rejoin — with
no jax, no kernels and no crypto wheel in the loop.
"""

import socket
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

try:
    from tendermint_tpu.fleet import wire
except ModuleNotFoundError:
    # importing tendermint_tpu.ops (EntryBlock's package) pulls the
    # crypto stack; without the cryptography wheel this module re-runs
    # in a purepy subprocess via test_fleet_isolated.py
    pytest.skip(
        "ops stack unavailable (runs via test_fleet_isolated.py)",
        allow_module_level=True,
    )
from tendermint_tpu.fleet.client import (  # noqa: E402
    FleetClient,
    FleetUnavailable,
    RemoteDispatchError,
)
from tendermint_tpu.fleet.server import FleetServer  # noqa: E402
from tendermint_tpu.ops.entry_block import EntryBlock  # noqa: E402


def make_block(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return EntryBlock(
        rng.randint(0, 256, (n, 32), dtype=np.uint8),
        rng.randint(0, 256, (n, 64), dtype=np.uint8),
        bytes(rng.randint(0, 256, 8 * n, dtype=np.uint8)),
        np.arange(0, 8 * (n + 1), 8, dtype=np.int64),
    )


class StubVerifier:
    """AsyncBatchVerifier-shaped: records every submit, hands back a
    Future the TEST resolves — so completion order is test-controlled."""

    def __init__(self):
        self.calls = []  # (block, flow, priority, origin, future)
        self._mtx = threading.Lock()
        self._arrived = threading.Condition(self._mtx)

    def submit(self, entries, flow=None, priority=0, origin=None):
        fut = Future()
        with self._arrived:
            self.calls.append((entries, flow, priority, origin, fut))
            self._arrived.notify_all()
        return fut

    def wait_calls(self, n, timeout=10.0):
        with self._arrived:
            ok = self._arrived.wait_for(lambda: len(self.calls) >= n,
                                        timeout=timeout)
        assert ok, f"server never dispatched {n} submit(s)"
        return self.calls[:n]


class RaisingVerifier:
    def submit(self, entries, flow=None, priority=0, origin=None):
        raise RuntimeError("verifier rejects: synthetic dispatch failure")


@pytest.fixture
def stub_rig():
    stub = StubVerifier()
    srv = FleetServer(verifier=stub).start()
    cli = FleetClient(srv.addr, name="svc", lane="svc-lane",
                      timeout_ms=60_000, rejoin_ms=50)
    yield stub, srv, cli
    cli.close()
    srv.stop()


class TestVerdictStreaming:
    def test_completion_order_not_submit_order(self, stub_rig):
        stub, _srv, cli = stub_rig
        futs = [cli.submit(make_block(n), flow=100 + n, priority=0)
                for n in (2, 3, 4)]
        calls = stub.wait_calls(3)
        # resolve in REVERSE submit order; each client future must still
        # get ITS verdicts (request_id demux), last-submitted first
        for i, (blk, _f, _p, _o, fut) in reversed(list(enumerate(calls))):
            fut.set_result(np.arange(len(blk)) % 2 == i % 2)
        for i, f in enumerate(futs):
            got = f.result(timeout=10)
            assert got.shape == (i + 2,)
            assert np.array_equal(got, np.arange(i + 2) % 2 == i % 2)

    def test_qos_flow_lane_preserved_into_submit_seam(self, stub_rig):
        stub, _srv, cli = stub_rig
        cli.submit(make_block(3), flow=777, priority=2)
        (blk, flow, priority, origin, fut) = stub.wait_calls(1)[0]
        assert (len(blk), flow, priority, origin) == (3, 777, 2, "svc-lane")
        fut.set_result(np.ones(3, dtype=bool))

    def test_out_of_range_priority_clamped(self, stub_rig):
        stub, _srv, cli = stub_rig
        cli.submit(make_block(2), priority=99)
        assert stub.wait_calls(1)[0][2] == 2  # clamped to ingress
        stub.calls[0][4].set_result(np.ones(2, dtype=bool))


class TestFailureContainment:
    def _raw_conn(self, addr):
        s = socket.create_connection(addr, timeout=10)
        s.settimeout(10)
        return s

    def _read_frame(self, sock):
        dec = wire.FrameDecoder()
        while True:
            data = sock.recv(1 << 16)
            assert data, "server closed before replying"
            payloads = dec.feed(data)
            if payloads:
                return wire.parse_frame(payloads[0])

    def test_malformed_then_valid_on_same_connection(self, stub_rig):
        stub, srv, _cli = stub_rig
        s = self._raw_conn(srv.addr)
        try:
            junk = b"NOPE" + b"\x00" * 30
            s.sendall(wire._LEN.pack(len(junk)) + junk)
            err = self._read_frame(s)
            assert isinstance(err, wire.ErrorFrame)
            assert err.code == wire.ERR_MALFORMED
            # ... and the SAME connection still serves a valid frame
            blk = make_block(2)
            for part in wire.encode_submit(5, blk, lane="raw"):
                s.sendall(bytes(part))
            stub.wait_calls(1)[0][4].set_result(np.ones(2, dtype=bool))
            ok = self._read_frame(s)
            assert isinstance(ok, wire.VerdictFrame)
            assert ok.request_id == 5 and bool(ok.verdicts.all())
        finally:
            s.close()

    def test_version_skew_earns_version_error(self, stub_rig):
        _stub, srv, _cli = stub_rig
        s = self._raw_conn(srv.addr)
        try:
            raw = b"".join(bytes(b) for b in wire.encode_submit(
                1, make_block(2)))
            payload = bytearray(raw[4:])
            payload[4:6] = (99).to_bytes(2, "little")
            s.sendall(wire._LEN.pack(len(payload)) + bytes(payload))
            err = self._read_frame(s)
            assert isinstance(err, wire.ErrorFrame)
            assert err.code == wire.ERR_VERSION
        finally:
            s.close()

    def test_oversize_kills_connection_not_server(self, stub_rig):
        stub, srv, cli = stub_rig
        s = self._raw_conn(srv.addr)
        try:
            s.sendall(wire._LEN.pack(1 << 31) + b"x" * 16)
            # the poisoned connection must die...
            deadline = time.monotonic() + 10
            closed = False
            while time.monotonic() < deadline:
                try:
                    if s.recv(1 << 16) == b"":
                        closed = True
                        break
                except OSError:
                    closed = True
                    break
            assert closed, "oversize prefix must kill the connection"
        finally:
            s.close()
        # ... while the server keeps serving: the long-lived client
        # still round-trips, and a brand-new connection is accepted
        f = cli.submit(make_block(2), flow=1)
        stub.wait_calls(1)[0][4].set_result(np.zeros(2, dtype=bool))
        assert not f.result(timeout=10).any()
        s2 = self._raw_conn(srv.addr)
        s2.close()

    def test_dispatch_error_poisons_only_that_request(self):
        srv = FleetServer(verifier=RaisingVerifier()).start()
        cli = FleetClient(srv.addr, name="derr", timeout_ms=60_000)
        try:
            f = cli.submit(make_block(2), flow=9)
            with pytest.raises(RemoteDispatchError,
                               match="synthetic dispatch failure"):
                f.result(timeout=10)
            # no host-fallback marker: a remote verifier raise is not a
            # fleet failure
            assert not getattr(RemoteDispatchError, "fallback_to_host",
                               False)
            assert cli.connected, "dispatch error must not degrade"
        finally:
            cli.close()
            srv.stop()

    def test_future_exception_streams_error_frame(self, stub_rig):
        stub, _srv, cli = stub_rig
        f = cli.submit(make_block(3))
        stub.wait_calls(1)[0][4].set_exception(
            RuntimeError("batch exploded late"))
        with pytest.raises(RemoteDispatchError, match="batch exploded"):
            f.result(timeout=10)


class TestDegradeAndRejoin:
    def test_timeout_degrades_with_fallback_marker(self):
        stub = StubVerifier()
        srv = FleetServer(verifier=stub).start()
        cli = FleetClient(srv.addr, name="slow", timeout_ms=200,
                          rejoin_ms=10_000)
        try:
            f = cli.submit(make_block(2), flow=3)
            stub.wait_calls(1)  # dispatched, but never resolved
            with pytest.raises(FleetUnavailable) as ei:
                f.result(timeout=10)
            assert ei.value.fallback_to_host is True
            assert cli.stats()["timeouts"] == 1
            # degraded: immediate-raise mode, no queueing behind a corpse
            with pytest.raises(FleetUnavailable):
                cli.submit(make_block(2))
        finally:
            cli.close()
            srv.stop()

    def test_server_stop_fails_pending_and_client_rejoins(self):
        stub = StubVerifier()
        srv = FleetServer(verifier=stub).start()
        port = srv.addr[1]
        cli = FleetClient(srv.addr, name="rj", timeout_ms=60_000,
                          rejoin_ms=50)
        try:
            f = cli.submit(make_block(2), flow=4)
            stub.wait_calls(1)
            srv.stop()  # crash: in-flight must fail with the marker
            with pytest.raises(FleetUnavailable):
                f.result(timeout=10)
            # restart on the same port; the rejoin loop redials
            stub2 = StubVerifier()
            srv = FleetServer(addr=("127.0.0.1", port),
                              verifier=stub2).start()
            deadline = time.monotonic() + 30
            while not cli.connected and time.monotonic() < deadline:
                time.sleep(0.02)
            assert cli.connected and cli.stats()["rejoins"] >= 1
            f2 = cli.submit(make_block(3), flow=5)
            stub2.wait_calls(1)[0][4].set_result(np.ones(3, dtype=bool))
            assert f2.result(timeout=10).all()
        finally:
            cli.close()
            srv.stop()


class TestLaneSpecSeam:
    """The tentpole's (c): a FleetClient IS a lane verifier. A lane's
    flushed windows ride the wire; post-submit fleet death host-verifies
    the window via host_fn (remote_fallbacks — zero lost items, no
    poison); while degraded, pre-submit raises ride
    submit_error_to_host; after a rejoin the next window rides the
    fleet again. The ingress fabric never imports fleet — the contract
    is the duck-typed fallback_to_host marker."""

    def test_lane_degrades_and_rejoins_through_fleet_backend(self):
        from tendermint_tpu.ops import ingress as ing

        stub = StubVerifier()
        srv = FleetServer(verifier=stub).start()
        port = srv.addr[1]
        cli = FleetClient(srv.addr, name="lane", lane="fleet-lane",
                          timeout_ms=60_000, rejoin_ms=50)
        host_runs = []

        def entries_fn(item):
            i = item["i"]
            return (bytes([i]) * 32, bytes([i]) * 8, bytes([i]) * 64)

        def host_fn(items):  # receives the raw payloads, unwrapped
            host_runs.append([it["i"] for it in items])
            return [True] * len(items)

        def deliver(items, verdicts, err):
            for it in items:
                if it.future is None or it.future.done():
                    continue
                if err is not None:
                    it.future.set_exception(err)
                else:
                    it.future.set_result(list(verdicts))

        eng = ing.IngressEngine()
        lane = eng.register(ing.LaneSpec(
            name="fleet-lane", priority=2, batch=4, window_ms=50.0,
            submit_error_to_host=True, verifier=cli,
            entries_fn=entries_fn, host_fn=host_fn, deliver=deliver))
        try:
            # 1) healthy: a full window flushes over the wire at the
            # lane's QoS tier, verdicts come back through deliver()
            futs = [lane.submit({"i": i}, want_future=True)
                    for i in range(4)]
            blk, _fl, prio, origin, sfut = stub.wait_calls(1)[0]
            assert (len(blk), prio, origin) == (4, 2, "fleet-lane")
            sfut.set_result(np.array([True, False, True, True]))
            assert futs[0].result(timeout=10) == [True, False, True, True]

            # 2) post-submit death: window reaches the fleet, then the
            # host dies — the window must HOST-verify, not poison
            futs2 = [lane.submit({"i": 10 + i}, want_future=True)
                     for i in range(4)]
            stub.wait_calls(2)  # the frame crossed the wire
            srv.stop()
            assert futs2[0].result(timeout=10) == [True] * 4
            assert host_runs == [[10, 11, 12, 13]]
            assert lane.remote_fallbacks == 1
            assert lane.dispatch_errors == 0, "fallback must not poison"

            # 3) degraded: pre-submit FleetUnavailable rides the
            # submit_error_to_host path (disjoint counter classes)
            futs3 = [lane.submit({"i": 20 + i}, want_future=True)
                     for i in range(4)]
            assert futs3[0].result(timeout=10) == [True] * 4
            assert host_runs[-1] == [20, 21, 22, 23]
            assert lane.sync_fallbacks >= 1
            assert lane.remote_fallbacks == 1

            # 4) fleet returns on the same port: the client rejoins and
            # the NEXT window rides remote again
            stub2 = StubVerifier()
            srv = FleetServer(addr=("127.0.0.1", port),
                              verifier=stub2).start()
            deadline = time.monotonic() + 30
            while not cli.connected and time.monotonic() < deadline:
                time.sleep(0.02)
            assert cli.connected
            futs4 = [lane.submit({"i": 30 + i}, want_future=True)
                     for i in range(4)]
            blk4 = stub2.wait_calls(1)[0]
            assert len(blk4[0]) == 4
            blk4[4].set_result(np.ones(4, dtype=bool))
            assert futs4[0].result(timeout=10) == [True] * 4
            assert len(host_runs) == 2, "post-rejoin windows ride remote"
        finally:
            eng.close()
            cli.close()
            srv.stop()


class TestStatsSurface:
    def test_client_and_server_stats_keys(self, stub_rig):
        stub, srv, cli = stub_rig
        f = cli.submit(make_block(2), flow=8)
        stub.wait_calls(1)[0][4].set_result(np.ones(2, dtype=bool))
        f.result(timeout=10)
        cs = cli.stats()
        assert set(cs) >= {"target", "connected", "rtt_ewma_ms", "pending",
                           "rejoins", "fallbacks", "timeouts"}
        assert cs["connected"] and cs["pending"] == 0
        assert cs["rtt_ewma_ms"] is not None and cs["rtt_ewma_ms"] > 0
        assert cli.rtt_ewma_ms() == cs["rtt_ewma_ms"]
        ss = srv.stats()
        assert ss["connections"] >= 1 and not ss["stopped"]

    def test_fleet_stats_snapshot_covers_both_ends(self, stub_rig):
        from tendermint_tpu.libs.metrics import fleet_stats

        stub, _srv, cli = stub_rig
        f = cli.submit(make_block(2), flow=8)
        stub.wait_calls(1)[0][4].set_result(np.ones(2, dtype=bool))
        f.result(timeout=10)
        snap = fleet_stats()
        assert set(snap) == {"client", "server"}
        tgt = cli.stats()["target"]
        assert snap["client"]["connected"].get(tgt) == 1
        assert snap["client"]["requests"].get(tgt, 0) >= 1
        assert snap["server"]["frames_accepted"].get("svc-lane", 0) >= 1
        assert snap["server"]["verdicts_streamed"] >= 1
