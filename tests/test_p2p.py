"""P2P stack: secret connection, mconnection multiplexing, router over
memory and TCP transports, and a 4-validator TCP localnet committing
blocks through the consensus reactor (SURVEY.md §7 stage 5)."""

import queue
import socket
import threading
import time

import pytest

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.p2p import (
    ChannelDescriptor,
    MConnTransport,
    NodeKey,
    PeerAddress,
    PeerManager,
    Router,
    SecretConnection,
    new_memory_network,
    MemoryTransport,
)
from tendermint_tpu.p2p.key import node_id_from_pubkey


def _sock_pair():
    a, b = socket.socketpair()

    class S:
        def __init__(self, s):
            self._s = s

        def read(self, n):
            try:
                return self._s.recv(n)
            except OSError:
                return b""

        def write(self, data):
            self._s.sendall(data)

        def close(self):
            self._s.close()

    return S(a), S(b)


class TestSecretConnection:
    def test_handshake_and_transfer(self):
        ka = ed25519.gen_priv_key(bytes([1]) * 32)
        kb = ed25519.gen_priv_key(bytes([2]) * 32)
        sa, sb = _sock_pair()
        out = {}

        def server():
            out["b"] = SecretConnection(sb, kb)

        t = threading.Thread(target=server)
        t.start()
        ca = SecretConnection(sa, ka)
        t.join(timeout=5)
        cb = out["b"]
        assert ca.remote_pubkey.bytes() == kb.pub_key().bytes()
        assert cb.remote_pubkey.bytes() == ka.pub_key().bytes()
        # data both ways, > 1 frame
        payload = b"x" * 3000
        ca.write(payload)
        got = b""
        while len(got) < 3000:
            got += cb.read_frame()
        assert got == payload
        cb.write(b"pong")
        assert ca.read_frame() == b"pong"

    def test_tampered_frame_rejected(self):
        ka = ed25519.gen_priv_key(bytes([3]) * 32)
        kb = ed25519.gen_priv_key(bytes([4]) * 32)
        sa, sb = _sock_pair()
        out = {}
        t = threading.Thread(target=lambda: out.update(b=SecretConnection(sb, kb)))
        t.start()
        ca = SecretConnection(sa, ka)
        t.join(timeout=5)
        # write garbage directly to the underlying socket
        sa.write(b"\x00" * 1044)
        with pytest.raises(Exception):
            out["b"].read_frame()


class TestRouterMemory:
    def test_two_node_channel_roundtrip(self):
        hub = new_memory_network()
        keys = [NodeKey.generate(bytes([i + 1]) * 32) for i in range(2)]
        ids = [k.node_id for k in keys]
        desc = ChannelDescriptor(id=7)
        routers = []
        chans = []
        for i in range(2):
            t = MemoryTransport(hub, ids[i], keys[i].pub_key)
            pm = PeerManager(ids[i])
            r = Router(t, pm, ids[i])
            chans.append(r.open_channel(desc))
            routers.append(r)
            r.start()
        # node0 dials node1 (memory transport addresses are node ids)
        routers[0]._pm.add_address(PeerAddress(ids[1], ids[1]))
        deadline = time.time() + 5
        while time.time() < deadline and not routers[0].connected():
            time.sleep(0.05)
        assert ids[1] in routers[0].connected()
        chans[0].send(ids[1], b"hello")
        env = chans[1].receive(timeout=5)
        assert env.message == b"hello" and env.from_id == ids[0]
        chans[1].broadcast(b"reply")
        env2 = chans[0].receive(timeout=5)
        assert env2.message == b"reply"
        for r in routers:
            r.stop()


class TestRouterTCP:
    def test_tcp_transport_router(self):
        keys = [NodeKey.generate(bytes([i + 10]) * 32) for i in range(2)]
        ids = [k.node_id for k in keys]
        desc = ChannelDescriptor(id=9)
        transports = [MConnTransport(k.priv_key, [desc]) for k in keys]
        for t in transports:
            t.listen("127.0.0.1:0")
        routers, chans = [], []
        for i in range(2):
            pm = PeerManager(ids[i])
            r = Router(transports[i], pm, ids[i])
            chans.append(r.open_channel(desc))
            routers.append(r)
            r.start()
        routers[0]._pm.add_address(PeerAddress(ids[1], transports[1].listen_addr))
        deadline = time.time() + 10
        while time.time() < deadline and not routers[0].connected():
            time.sleep(0.05)
        assert ids[1] in routers[0].connected()
        big = bytes(range(256)) * 40  # > 1 mconn packet
        chans[0].send(ids[1], big)
        env = chans[1].receive(timeout=5)
        assert env.message == big
        for r in routers:
            r.stop()


class TestConsensusOverTCP:
    def test_four_validator_tcp_localnet(self):
        from tests.test_consensus import FAST, make_node
        from tendermint_tpu.consensus.reactor import ALL_DESCS, ConsensusReactor

        sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
        node_keys = [NodeKey.generate(bytes([i + 50]) * 32) for i in range(4)]
        nodes, stores, routers, reactors = [], [], [], []
        transports = []
        for i in range(4):
            cs, bstore, _ = make_node(sks, i)
            t = MConnTransport(node_keys[i].priv_key, ALL_DESCS)
            t.listen("127.0.0.1:0")
            pm = PeerManager(node_keys[i].node_id)
            r = Router(t, pm, node_keys[i].node_id)
            reactor = ConsensusReactor(cs, r)
            nodes.append(cs)
            stores.append(bstore)
            routers.append(r)
            reactors.append(reactor)
            transports.append(t)
        # full mesh
        for i in range(4):
            for j in range(4):
                if i != j:
                    routers[i]._pm.add_address(
                        PeerAddress(node_keys[j].node_id, transports[j].listen_addr)
                    )
        for r in routers:
            r.start()
        for re in reactors:
            re.start()
        # wait for connectivity
        deadline = time.time() + 10
        while time.time() < deadline and any(len(r.connected()) < 3 for r in routers):
            time.sleep(0.1)
        for n in nodes:
            n.start()
        try:
            for n in nodes:
                n.wait_for_height(2, timeout=90)
        finally:
            for n in nodes:
                n.stop()
            for re in reactors:
                re.stop()
            for r in routers:
                r.stop()
        hashes = [s.load_block(2).hash() for s in stores]
        assert all(h == hashes[0] for h in hashes), "nodes diverged over TCP"


class TestPeerLifecycle:
    """peermanager.go:27-60 eviction/upgrade machinery + pqueue.go
    priority routing + flowrate limiting."""

    def test_errored_peer_evicted_and_banned(self):
        from tendermint_tpu.p2p.peermanager import EVICT_SCORE

        pm = PeerManager("self", ban_duration=5.0)
        pm.add_address(PeerAddress("bad", "bad"))
        assert pm.accepted("bad")
        for _ in range(-EVICT_SCORE):
            pm.errored("bad", ValueError("garbage"))
        assert pm.evict_next() == "bad"
        pm.disconnected("bad")
        # banned: neither dialable nor re-admittable until the ban lapses
        assert pm.is_banned("bad")
        assert pm.dial_next() is None
        assert not pm.accepted("bad")

    def test_upgrade_displaces_worst_peer(self):
        pm = PeerManager("self", max_connected=2)
        assert pm.accepted("a") and pm.accepted("b")
        # "a" misbehaves a little (score -2, above eviction threshold)
        pm.errored("a", ValueError("x"), weight=2)
        # a better candidate arrives while full: admitted, "a" queued
        assert pm.accepted("c")
        assert sorted(pm.connected_peers()) == ["a", "b", "c"]
        assert pm.evict_next() == "a"

    def test_persistent_peer_never_evicted(self):
        pm = PeerManager("self")
        pm.add_address(PeerAddress("p", "p"), persistent=True)
        assert pm.accepted("p")
        for _ in range(50):
            pm.errored("p", ValueError("x"))
        assert pm.evict_next() is None

    def test_address_book_gc(self):
        pm = PeerManager("self", max_peers=10)
        for i in range(15):
            pm.add_address(PeerAddress(f"n{i}", f"n{i}"))
        assert pm.prune_addresses() == 5
        assert len(pm.peers()) == 10

    def test_router_evicts_garbage_peer_and_gossip_stays_flat(self):
        """A peer that misbehaves repeatedly is dropped by the router's
        eviction pump while a healthy peer's high-priority traffic keeps
        flowing."""
        from tendermint_tpu.p2p.peermanager import EVICT_SCORE

        hub = new_memory_network()
        keys = [NodeKey.generate(bytes([i + 41]) * 32) for i in range(3)]
        ids = [k.node_id for k in keys]
        hi = ChannelDescriptor(id=0x22, priority=6)  # vote gossip
        routers, chans = [], []
        for i in range(3):
            t = MemoryTransport(hub, ids[i], keys[i].pub_key)
            pm = PeerManager(ids[i])
            r = Router(t, pm, ids[i])
            chans.append(r.open_channel(hi))
            routers.append(r)
            r.start()
        routers[0]._pm.add_address(PeerAddress(ids[1], ids[1]))
        routers[0]._pm.add_address(PeerAddress(ids[2], ids[2]))
        deadline = time.time() + 5
        while time.time() < deadline and len(routers[0].connected()) < 2:
            time.sleep(0.05)
        assert len(routers[0].connected()) == 2
        # peer 2 keeps sending garbage -> errored until eviction
        for _ in range(-EVICT_SCORE + 2):
            routers[0]._pm.errored(ids[2], ValueError("garbage"))
        deadline = time.time() + 5
        while time.time() < deadline and ids[2] in routers[0].connected():
            time.sleep(0.05)
        assert ids[2] not in routers[0].connected()
        # healthy peer still delivers promptly
        t0 = time.time()
        chans[0].send(ids[1], b"vote")
        env = chans[1].receive(timeout=5)
        assert env.message == b"vote" and time.time() - t0 < 1.0
        for r in routers:
            r.stop()

    def test_priority_channel_wins_per_peer_queue(self):
        """pqueue semantics: with a peer's low-priority queue stuffed, a
        high-priority message still goes out ahead of the backlog."""
        from tendermint_tpu.p2p.router import _PeerQueue

        lo = ChannelDescriptor(id=0x40, priority=1, send_queue_capacity=50)
        hi = ChannelDescriptor(id=0x22, priority=6, send_queue_capacity=50)
        pq = _PeerQueue({lo.id: lo, hi.id: hi})
        for i in range(50):
            assert pq.put(lo.id, b"bulk%d" % i)
        assert not pq.put(lo.id, b"overflow")  # bounded: drops, not blocks
        assert pq.dropped == 1
        assert pq.put(hi.id, b"vote")
        ch, msg = pq.pop(timeout=1)
        assert ch == hi.id and msg == b"vote"  # vote jumps the bulk backlog
        ch, _ = pq.pop(timeout=1)
        assert ch == lo.id

    def test_flowrate_limited_connection(self):
        """flowrate cap: pushing ~30 kB through a 50 kB/s-limited
        MConnection takes >= ~0.4s and the monitor sees the rate."""
        import socket as _socket

        from tendermint_tpu.p2p.conn.mconnection import MConnection
        from tendermint_tpu.p2p.transport import _SockStream

        a, b = _socket.socketpair()
        got = []
        done = threading.Event()

        def on_recv(ch, msg):
            got.append(msg)
            if len(got) == 30:
                done.set()

        descs = [ChannelDescriptor(id=1, send_queue_capacity=64)]
        ma = MConnection(_SockStream(a), descs, lambda c, m: None,
                         lambda e: None, send_rate=50_000)
        mb = MConnection(_SockStream(b), descs, on_recv, lambda e: None)
        ma.start()
        mb.start()
        t0 = time.time()
        for i in range(30):
            assert ma.send(1, bytes(1000))
        # generous deadline: nominal is ~0.6s, but a loaded CI host can
        # starve the writer thread well past 10s (observed full-suite flake)
        assert done.wait(30)
        dt = time.time() - t0
        assert dt >= 0.35, f"30kB at 50kB/s finished too fast: {dt:.2f}s"
        # the sender counts a packet AFTER writing it, so the 30th message
        # can reach on_recv before its bytes reach the monitor
        deadline = time.time() + 5
        while ma.send_monitor.total() < 30_000 and time.time() < deadline:
            time.sleep(0.01)
        assert ma.send_monitor.total() >= 30_000
        ma.stop()
        mb.stop()


class TestConnTracker:
    """internal/p2p/conn_tracker.go: per-IP inbound connection caps."""

    def test_per_ip_cap(self):
        from tendermint_tpu.p2p.transport import ConnTracker

        t = ConnTracker(max_per_ip=2)
        assert t.add("10.0.0.1") and t.add("10.0.0.1")
        assert not t.add("10.0.0.1")  # cap
        assert t.add("10.0.0.2")  # a different IP is unaffected
        t.remove("10.0.0.1")
        assert t.add("10.0.0.1")
        assert t.count("10.0.0.1") == 2

    def test_tcp_transport_enforces_cap(self):
        import socket as _socket
        import time as _time

        from tendermint_tpu.p2p import NodeKey
        from tendermint_tpu.p2p.transport import MConnTransport

        nk = NodeKey.generate(bytes([61]) * 32)
        t = MConnTransport(nk.priv_key, [ChannelDescriptor(id=1)],
                           max_conns_per_ip=1)
        t.listen("127.0.0.1:0")
        host, _, port = t.listen_addr.rpartition(":")
        # first raw connection occupies the slot (no handshake completes,
        # but the tracker slot is held while the handshake thread runs)
        s1 = _socket.create_connection((host, int(port)))
        _time.sleep(0.3)
        # second connection from the same IP must be closed by the cap
        s2 = _socket.create_connection((host, int(port)))
        s2.settimeout(2)
        try:
            data = s2.recv(1)
            assert data == b"", "expected immediate close by conn tracker"
        except (ConnectionResetError, _socket.timeout):
            pass  # reset also acceptable
        finally:
            s1.close()
            s2.close()
            t.close()
