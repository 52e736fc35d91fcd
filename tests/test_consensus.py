"""Consensus engine tests.

Mirrors the reference's in-process multi-validator harness
(internal/consensus/common_test.go, SURVEY.md §4): single-validator chain
producing blocks against kvstore, then a 4-validator net wired through the
broadcast seam (no network) — the "multi-node without a cluster" pattern.
"""

import threading
import time

import pytest

from tendermint_tpu.abci import KVStoreApplication, LocalClient
from tendermint_tpu.config import ConsensusConfig
from tendermint_tpu.consensus import ConsensusState, WAL, WALMessage
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.db import MemDB
from tendermint_tpu.eventbus import EventBus
from tendermint_tpu.mempool import TxMempool
from tendermint_tpu.privval import FilePV
from tendermint_tpu.state import make_genesis_state
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store import BlockStore
from tendermint_tpu.types import Timestamp
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

CHAIN_ID = "cs-chain"

FAST = ConsensusConfig(
    timeout_propose_ms=400,
    timeout_propose_delta_ms=100,
    timeout_prevote_ms=200,
    timeout_prevote_delta_ms=100,
    timeout_precommit_ms=200,
    timeout_precommit_delta_ms=100,
    timeout_commit_ms=50,
    skip_timeout_commit=True,
)


def make_node(sks, idx, wal_path=None, tx_source=None, proxy=None):
    """One in-process consensus node for validator idx."""
    doc = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=Timestamp(seconds=1_700_000_000),
        validators=[
            GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10) for sk in sks
        ],
    )
    state = make_genesis_state(doc)
    app = KVStoreApplication()
    proxy = proxy or LocalClient(app)
    sstore = StateStore(MemDB())
    sstore.save(state)
    bstore = BlockStore(MemDB())
    mp = TxMempool(LocalClient(app))
    if tx_source:
        for tx in tx_source:
            mp.check_tx(tx)
    bus = EventBus()
    ex = BlockExecutor(sstore, proxy, mempool=mp, block_store=bstore, event_bus=bus)
    wal = WAL(wal_path) if wal_path else None
    pv = FilePV(sks[idx]) if idx is not None else None
    cs = ConsensusState(
        FAST, state, ex, bstore, mempool=mp, event_bus=bus, wal=wal, priv_validator=pv
    )
    return cs, bstore, app


class TestSingleValidator:
    def test_one_validator_chain_produces_blocks(self):
        sk = ed25519.gen_priv_key(bytes([1]) * 32)
        cs, bstore, app = make_node([sk], 0, tx_source=[b"a=1", b"b=2"])
        cs.start()
        try:
            cs.wait_for_height(3, timeout=30)
        finally:
            cs.stop()
        assert bstore.height() >= 3
        b1 = bstore.load_block(1)
        assert b1.header.chain_id == CHAIN_ID
        b2 = bstore.load_block(2)
        # height-2 commit carries height-1 signatures
        assert b2.last_commit.height == 1
        assert len(b2.last_commit.signatures) == 1
        # txs from the mempool were included in some block
        all_txs = [tx for h in range(1, bstore.height() + 1) for tx in bstore.load_block(h).data.txs]
        assert b"a=1" in all_txs and b"b=2" in all_txs

    def test_wal_replay_restarts_cleanly(self, tmp_path):
        sk = ed25519.gen_priv_key(bytes([2]) * 32)
        wal_path = str(tmp_path / "cs.wal")
        cs, bstore, _ = make_node([sk], 0, wal_path=wal_path)
        cs.start()
        try:
            cs.wait_for_height(2, timeout=30)
        finally:
            cs.stop()
        # WAL contains end-height markers
        wal = WAL(wal_path)
        ends = [m.end_height for m in wal.iter_messages() if m.end_height is not None]
        assert 0 in ends and 1 in ends and 2 in ends


def wire_nodes(nodes):
    """Device each node's own proposals/parts/votes to every other node —
    the test stand-in for the consensus reactor's gossip."""
    from tendermint_tpu.consensus import BlockPartMessage, ProposalMessage, VoteMessage

    def make_hook(src_idx):
        def hook(msg):
            for j, n in enumerate(nodes):
                if j == src_idx:
                    continue
                if isinstance(msg, ProposalMessage):
                    n.set_proposal(msg.proposal, peer_id=f"n{src_idx}")
                elif isinstance(msg, BlockPartMessage):
                    n.add_block_part(msg.height, msg.round, msg.part, peer_id=f"n{src_idx}")
                elif isinstance(msg, VoteMessage):
                    n.add_vote_msg(msg.vote, peer_id=f"n{src_idx}")

        return hook

    for i, n in enumerate(nodes):
        n.broadcast_hooks.append(make_hook(i))


class TestMultiValidator:
    def test_four_validator_net_commits_blocks(self):
        sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
        nodes = []
        stores = []
        for i in range(4):
            cs, bstore, _ = make_node(sks, i)
            nodes.append(cs)
            stores.append(bstore)
        wire_nodes(nodes)
        for n in nodes:
            n.start()
        try:
            for n in nodes:
                n.wait_for_height(3, timeout=60)
        finally:
            for n in nodes:
                n.stop()
        hashes = [s.load_block(3).hash() for s in stores]
        assert all(h == hashes[0] for h in hashes), "nodes diverged"
        # commits carry signatures from (at least quorum of) the 4 validators
        b3 = stores[0].load_block(3)
        non_absent = [cs for cs in b3.last_commit.signatures if not cs.is_absent()]
        assert len(non_absent) >= 3

    def test_net_survives_one_silent_node(self):
        """3 of 4 validators online still commit (BFT liveness, f=1)."""
        sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
        nodes = []
        stores = []
        for i in range(3):  # node 3 never starts
            cs, bstore, _ = make_node(sks, i)
            nodes.append(cs)
            stores.append(bstore)
        wire_nodes(nodes)
        for n in nodes:
            n.start()
        try:
            for n in nodes:
                n.wait_for_height(2, timeout=60)
        finally:
            for n in nodes:
                n.stop()
        assert stores[0].load_block(2).hash() == stores[1].load_block(2).hash()


class TestStopWhileFlooded:
    """stop() has one signal, _quit: a full queue can neither hold
    stop() nor keep a producer blocked after it (the flooded state-synced
    node of tests/test_node_rpc.py, made deterministic)."""

    @staticmethod
    def _flooded_node():
        """A started node whose receive thread sits in a handler until
        _quit is set, behind a queue filled to maxsize."""
        from tendermint_tpu.types.vote import Vote

        sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(2)]
        cs, _, _ = make_node(sks, None)
        held = threading.Event()

        def hold(msg, peer_id):
            held.set()
            cs.quit_event.wait(60)

        cs._handle_msg = hold
        cs.start()
        vote = Vote(height=1, validator_address=sks[1].pub_key().address())
        cs.add_vote_msg(vote, peer_id="flood")
        assert held.wait(10)
        for _ in range(cs._queue.maxsize):
            cs.add_vote_msg(vote, peer_id="flood")
        assert cs._queue.full()
        return cs, vote

    def test_stop_returns_on_a_full_queue(self):
        cs, _ = self._flooded_node()
        stopper = threading.Thread(target=cs.stop, daemon=True)
        stopper.start()
        stopper.join(2)
        assert not stopper.is_alive(), "stop() blocked on the full queue"
        assert not cs._thread.is_alive()

    def test_stop_releases_a_blocked_producer(self):
        cs, vote = self._flooded_node()
        producer = threading.Thread(
            target=cs.add_vote_msg, args=(vote, "flood"), daemon=True)
        producer.start()
        producer.join(0.5)
        assert producer.is_alive(), "a full queue must hold its producer back"
        stopper = threading.Thread(target=cs.stop, daemon=True)
        stopper.start()
        producer.join(1)
        assert not producer.is_alive(), "producer still blocked after stop()"
        stopper.join(2)
        assert not stopper.is_alive()
