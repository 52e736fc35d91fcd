"""Node composition + RPC integration: a 2-validator TCP localnet built by
make_node, driven end-to-end over JSON-RPC (broadcast_tx_commit →
abci_query), plus handshake/replay restart behavior."""

import time

import pytest

from tendermint_tpu.abci import KVStoreApplication
from tendermint_tpu.config import Config, ConsensusConfig
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.node import make_node
from tendermint_tpu.p2p import NodeKey
from tendermint_tpu.privval import FilePV
from tendermint_tpu.rpc import HTTPClient
from tendermint_tpu.types import Timestamp
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tests.test_consensus import FAST

CHAIN = "node-chain"


def _make_config(i):
    cfg = Config()
    cfg.base.home = ""  # memdb
    cfg.base.db_backend = "memdb"
    cfg.consensus = FAST
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = f"tcp://127.0.0.1:0"
    return cfg


@pytest.fixture
def two_node_net():
    sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(2)]
    doc_json = GenesisDoc(
        chain_id=CHAIN,
        genesis_time=Timestamp(seconds=1_700_000_000),
        validators=[
            GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10) for sk in sks
        ],
    ).to_json()
    nodes = []
    for i in range(2):
        cfg = _make_config(i)
        node = make_node(
            cfg,
            app=KVStoreApplication(),
            genesis=GenesisDoc.from_json(doc_json),
            priv_validator=FilePV(sks[i]),
            node_key=NodeKey.generate(bytes([i + 60]) * 32),
            with_rpc=True,
        )
        nodes.append(node)
    # wire persistent peers after listen addrs exist
    from tendermint_tpu.p2p import PeerAddress

    for i, n in enumerate(nodes):
        other = nodes[1 - i]
        n.router._pm.add_address(
            PeerAddress(other.node_id, other.router._transport.listen_addr),
            persistent=True,
        )
    for n in nodes:
        n.start()
    yield nodes
    for n in nodes:
        n.stop()


class TestNodeRPC:
    def test_end_to_end_tx_flow(self, two_node_net):
        nodes = two_node_net
        nodes[0].wait_for_height(2, timeout=60)
        rpc = HTTPClient(nodes[0].rpc_server.listen_addr)

        st = rpc.status()
        assert st["node_info"]["network"] == CHAIN
        assert int(st["sync_info"]["latest_block_height"]) >= 2

        # ISSUE 18: the verification-fleet section is always present —
        # all-zero counter reads when no fleet exists, never a dial
        fl = st["fleet"]
        assert set(fl) >= {"client", "server"}
        assert set(fl["client"]) >= {
            "connected", "rtt_ewma_ms", "requests",
            "timeouts", "fallbacks", "rejoins",
        }
        assert set(fl["server"]) >= {
            "connections", "frames_accepted", "frames_rejected",
            "sigs", "verdicts_streamed", "dispatch_errors",
        }

        res = rpc.broadcast_tx_commit(b"rpckey=rpcval")
        assert res["deliver_tx"]["code"] == 0
        height = int(res["height"])
        assert height > 0

        # query on the SECOND node: the tx must have replicated
        nodes[1].wait_for_height(height, timeout=60)
        rpc2 = HTTPClient(nodes[1].rpc_server.listen_addr)
        q = rpc2.abci_query(path="/key", data=b"rpckey")
        import base64

        assert base64.b64decode(q["response"]["value"]) == b"rpcval"

        # block/commit/validators surface
        blk = rpc.block(height)
        assert int(blk["block"]["header"]["height"]) == height
        cm = rpc.commit(max(1, height - 1))
        assert cm["canonical"] is True
        vals = rpc.validators(1)
        assert int(vals["total"]) == 2
        tx_res = rpc.tx(__import__("hashlib").sha256(b"rpckey=rpcval").digest(), prove=True)
        assert int(tx_res["height"]) == height

    def test_net_info_and_misc_endpoints(self, two_node_net):
        nodes = two_node_net
        nodes[0].wait_for_height(1, timeout=60)
        rpc = HTTPClient(nodes[0].rpc_server.listen_addr)
        assert rpc.health() == {}
        ni = rpc.net_info()
        assert int(ni["n_peers"]) >= 1
        gen = rpc.genesis()
        assert gen["genesis"]["chain_id"] == CHAIN
        ai = rpc.abci_info()
        assert "kvstore" in ai["response"]["version"]
        bc = rpc.call("blockchain")
        assert int(bc["last_height"]) >= 1
        ucp = rpc.call("consensus_params")
        assert int(ucp["consensus_params"]["block"]["max_bytes"]) > 0

    def test_thread_dump_endpoint(self, two_node_net):
        """/thread_dump: the goroutine-dump equivalent `debug kill`
        captures — unsafe-gated (stack traces leak internals), and must
        include the consensus receive routine's stack when enabled."""
        nodes = two_node_net
        nodes[0].wait_for_height(1, timeout=60)
        rpc = HTTPClient(nodes[0].rpc_server.listen_addr)
        # gated off by default
        with pytest.raises(Exception):
            rpc.call("thread_dump")
        nodes[0].config.rpc.unsafe = True
        try:
            td = rpc.call("thread_dump")
            assert int(td["n_threads"]) >= 2
            stacks = "".join(s for t in td["threads"] for s in t["stack"])
            assert "_receive_routine" in stacks
        finally:
            nodes[0].config.rpc.unsafe = False


def test_node_stop_returns_while_peer_still_sends(two_node_net):
    """Node.stop() stops consensus BEFORE its reactors, so the reactor
    keeps feeding a queue nobody drains: stop() must not wait on it."""
    import queue
    import threading

    from tendermint_tpu.consensus.ticker import TimeoutInfo

    node_a, node_b = two_node_net
    node_a.wait_for_height(2, timeout=60)
    cs = node_b.consensus
    held = threading.Event()

    def hold(msg, peer_id):  # the receive thread sits here until _quit
        held.set()
        cs.quit_event.wait(60)

    cs._handle_msg = hold
    assert held.wait(30), "peer sent nothing"
    stale = (TimeoutInfo(0.0, 0, 0, 1), "")
    try:
        while True:  # top the queue up behind node_a's own gossip
            cs._queue.put_nowait(stale)
    except queue.Full:
        pass
    assert node_a.consensus.is_running()  # the peer is still sending
    stopper = threading.Thread(target=node_b.stop, daemon=True)
    stopper.start()
    stopper.join(15)
    assert not stopper.is_alive(), "Node.stop() blocked on a flooded queue"


class TestHandshakeReplay:
    def test_app_restart_replays_blocks(self):
        """Kill the app (fresh instance), restart node: handshake replays
        committed blocks into the app (replay.go ReplayBlocks)."""
        sk = ed25519.gen_priv_key(bytes([5]) * 32)
        doc_json = GenesisDoc(
            chain_id=CHAIN,
            genesis_time=Timestamp(seconds=1_700_000_000),
            validators=[GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)],
        ).to_json()
        cfg = _make_config(0)
        cfg.p2p.laddr = "none"
        node = make_node(
            cfg,
            app=KVStoreApplication(),
            genesis=GenesisDoc.from_json(doc_json),
            priv_validator=FilePV(sk),
            node_key=NodeKey.generate(bytes([77]) * 32),
        )
        node.start()
        node.mempool.check_tx(b"persist=1")
        node.wait_for_height(3, timeout=60)
        node.stop()
        stored_height = node.block_store.height()

        # "restart": same stores, FRESH app instance at height 0
        from tendermint_tpu.consensus.replay import Handshaker
        from tendermint_tpu.abci import LocalClient
        from tendermint_tpu.abci import types as abci_t

        fresh_app = KVStoreApplication()
        conn = LocalClient(fresh_app)
        state = node.state_store.load()
        hs = Handshaker(node.state_store, state, node.block_store, node.genesis)
        new_state = hs.handshake(conn)
        assert hs.n_blocks_replayed >= stored_height - 1
        info = conn.info(abci_t.RequestInfo())
        assert info.last_block_height >= stored_height - 1
        # the replayed app has the tx
        q = conn.query(abci_t.RequestQuery(data=b"persist", path="/key"))
        assert q.value == b"1"


class TestNodeStartupModes:
    """node.go:217-247,323-343 startup-mode selection: a fresh node with
    statesync configured restores from a peer's snapshot, backfills, and
    switches to consensus; blocksync hands off to consensus when caught
    up (covered via TCP e2e in test_e2e_proc)."""

    def test_statesync_node_restores_and_joins(self):
        import time

        from tendermint_tpu.abci import KVStoreApplication
        from tendermint_tpu.config import Config
        from tendermint_tpu.consensus.state import ConsensusState  # noqa: F401
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.node import make_node
        from tendermint_tpu.p2p import MemoryTransport, NodeKey, PeerAddress, new_memory_network
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
        from tendermint_tpu.wire.canonical import Timestamp

        hub = new_memory_network()
        sk = ed25519.gen_priv_key(bytes([77]) * 32)
        doc = GenesisDoc(
            chain_id="ss-node-chain",
            genesis_time=Timestamp(seconds=1_700_000_000),
            validators=[GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)],
        )

        def node_cfg():
            cfg = Config()
            cfg.base.home = ""
            cfg.base.db_backend = "memdb"
            from tests.test_consensus import FAST

            cfg.consensus = FAST
            cfg.p2p.laddr = ""
            cfg.rpc.laddr = ""
            return cfg

        # validator node producing snapshots
        nk_a = NodeKey.generate(bytes([78]) * 32)
        from tendermint_tpu.privval import FilePV

        node_a = make_node(
            node_cfg(),
            # generous retention: the FAST test chain outruns the default
            # keep-3 window before the syncing node can fetch chunks
            app=KVStoreApplication(snapshot_interval=2, snapshot_keep=100),
            genesis=doc,
            priv_validator=FilePV(sk),
            node_key=nk_a,
            transport=MemoryTransport(hub, nk_a.node_id, nk_a.pub_key),
        )
        node_a.start()
        try:
            node_a.wait_for_height(6, timeout=60)
            # trust root: a snapshot height the serving node can prove
            snaps = node_a.proxy_app.list_snapshots().snapshots
            assert snaps
            snap_h = max(
                s.height for s in snaps
                if s.height + 2 <= node_a.block_store.height()
            )
            trust = node_a.statesync_reactor._load_local_light_block(snap_h)

            # fresh statesyncing node
            nk_b = NodeKey.generate(bytes([79]) * 32)
            cfg_b = node_cfg()
            cfg_b.statesync.enable = True
            cfg_b.statesync.trust_height = snap_h
            cfg_b.statesync.trust_hash = trust.hash().hex()
            cfg_b.statesync.discovery_time_ms = 1500
            node_b = make_node(
                cfg_b,
                app=KVStoreApplication(),
                genesis=doc,
                node_key=nk_b,
                transport=MemoryTransport(hub, nk_b.node_id, nk_b.pub_key),
            )
            node_b.router._pm.add_address(PeerAddress(nk_a.node_id, nk_a.node_id))
            node_a.router._pm.add_address(PeerAddress(nk_b.node_id, nk_b.node_id))
            node_b.start()
            try:
                deadline = time.time() + 90
                while time.time() < deadline:
                    if node_b.consensus.committed_state.last_block_height > snap_h:
                        break
                    time.sleep(0.2)
                st = node_b.consensus.committed_state
                assert st.last_block_height >= snap_h, (
                    st.last_block_height, snap_h
                )
                # discriminate REAL statesync from a consensus-catchup
                # fallback: only the sync path plants the params
                # checkpoint at the restored snapshot height (the syncer
                # picks the NEWEST advertised snapshot, at/above snap_h)
                restored_h = st.last_height_consensus_params_changed
                assert restored_h >= snap_h, (
                    "node fell back to consensus catchup instead of "
                    "restoring a snapshot"
                )
                # the restored header was planted in the block store
                assert node_b.block_store.load_block_meta(restored_h) is not None
            finally:
                node_b.stop()
        finally:
            node_a.stop()


def test_openapi_spec_covers_route_table():
    """rpc/openapi parity: the spec documents every mounted route (and
    nothing that isn't mounted, modulo the websocket pseudo-path)."""
    import os
    import re

    from tendermint_tpu.rpc.core import ROUTES, UNSAFE_ROUTES

    spec_path = os.path.join(
        os.path.dirname(__file__), "..", "tendermint_tpu", "rpc", "openapi.yaml"
    )
    text = open(spec_path).read()
    paths = set(re.findall(r"^  /([a-z_]+):", text, re.M))
    expected = set(ROUTES) | set(UNSAFE_ROUTES) | {"websocket"}
    assert paths == expected, (paths ^ expected)


def test_seed_node_pex_discovery():
    """node.go:428 makeSeedNode: a seed-mode node runs only the p2p layer
    (pex + address book). Two validators that each know ONLY the seed must
    discover each other through it and produce blocks together."""
    from tendermint_tpu.config import MODE_SEED
    from tendermint_tpu.p2p import PeerAddress

    sks = [ed25519.gen_priv_key(bytes([i + 31]) * 32) for i in range(2)]
    doc_json = GenesisDoc(
        chain_id=CHAIN,
        genesis_time=Timestamp(seconds=1_700_000_000),
        validators=[
            GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)
            for sk in sks
        ],
    ).to_json()

    seed_cfg = _make_config(9)
    seed_cfg.base.mode = MODE_SEED
    seed_cfg.p2p.pex = True
    seed = make_node(
        seed_cfg,
        app=KVStoreApplication(),
        genesis=GenesisDoc.from_json(doc_json),
        priv_validator=None,
        node_key=NodeKey.generate(bytes([91]) * 32),
        with_rpc=False,
    )
    assert seed.consensus_reactor is None  # seed runs no consensus gossip
    assert seed.pex_reactor is not None

    vals = []
    for i in range(2):
        cfg = _make_config(i)
        cfg.p2p.pex = True
        node = make_node(
            cfg,
            app=KVStoreApplication(),
            genesis=GenesisDoc.from_json(doc_json),
            priv_validator=FilePV(sks[i]),
            node_key=NodeKey.generate(bytes([i + 93]) * 32),
            with_rpc=False,
        )
        vals.append(node)
    # validators know ONLY the seed; the seed knows both (as a bootstrap
    # would after they dial in)
    for n in vals:
        n.router._pm.add_address(
            PeerAddress(seed.node_id, seed.router._transport.listen_addr),
            persistent=True,
        )
        seed.router._pm.add_address(
            PeerAddress(n.node_id, n.router._transport.listen_addr)
        )
    try:
        seed.start()
        for n in vals:
            n.start()
        # consensus requires the two validators to find EACH OTHER via
        # pex address exchange through the seed (2/3 of power = both)
        vals[0].wait_for_height(3, timeout=90)
        vals[1].wait_for_height(3, timeout=90)
        assert any(
            pid == vals[1].node_id for pid in vals[0].router.connected()
        ), "validators never learned each other's address via pex"
    finally:
        for n in vals:
            n.stop()
        seed.stop()
