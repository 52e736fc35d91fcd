"""Light client + blocksync tests.

Light: sequential + skipping verification against a real produced chain,
witness divergence detection, backwards verification.
Blocksync: a fresh node catches up from a peer over the memory transport,
verifying every block on the batch path (SURVEY.md §7 stage 6).
"""

import time

import pytest

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.db import MemDB
from tendermint_tpu.light import (
    Client,
    LightStore,
    NodeBackedProvider,
    TrustOptions,
    verify_adjacent,
)
from tendermint_tpu.light.client import ErrLightClientAttack
from tendermint_tpu.p2p import (
    MemoryTransport,
    NodeKey,
    PeerAddress,
    PeerManager,
    Router,
    new_memory_network,
)
from tendermint_tpu.types import SignedHeader, Timestamp
from tests.test_consensus import FAST, make_node


@pytest.fixture(scope="module")
def produced_chain():
    """A 1-validator chain run to height >= 5, exposing node internals."""
    sk = ed25519.gen_priv_key(bytes([7]) * 32)
    cs, bstore, _ = make_node([sk], 0)
    cs.start()
    try:
        cs.wait_for_height(5, timeout=60)
    finally:
        cs.stop()
    return cs, bstore


def _provider(cs, bstore):
    return NodeBackedProvider(bstore, cs._block_exec.store)


class TestLightClient:
    def _client(self, cs, bstore, sequential=False, witnesses=None):
        prov = _provider(cs, bstore)
        lb1 = prov.light_block(1)
        return Client(
            chain_id="cs-chain",
            trust_options=TrustOptions(period=1e9, height=1, hash=lb1.hash()),
            primary=prov,
            witnesses=witnesses if witnesses is not None else [prov],
            store=LightStore(MemDB()),
            sequential=sequential,
        )

    def test_sequential_verification(self, produced_chain):
        cs, bstore = produced_chain
        c = self._client(cs, bstore, sequential=True)
        lb = c.verify_light_block_at_height(4)
        assert lb.height == 4
        # all intermediate headers are now trusted
        assert c.trusted_light_block(2) is not None
        assert c.trusted_light_block(3) is not None

    def test_skipping_verification(self, produced_chain):
        cs, bstore = produced_chain
        c = self._client(cs, bstore)
        lb = c.verify_light_block_at_height(5)
        assert lb.height == 5

    def test_backwards_verification(self, produced_chain):
        cs, bstore = produced_chain
        prov = _provider(cs, bstore)
        lb4 = prov.light_block(4)
        c = Client(
            chain_id="cs-chain",
            trust_options=TrustOptions(period=1e9, height=4, hash=lb4.hash()),
            primary=prov,
            witnesses=[prov],
            store=LightStore(MemDB()),
        )
        lb2 = c.verify_light_block_at_height(2)
        assert lb2.height == 2

    def test_witness_divergence_detected(self, produced_chain):
        cs, bstore = produced_chain
        prov = _provider(cs, bstore)

        class EvilWitness(NodeBackedProvider):
            armed = False  # honest during client init (the root cross-check)

            def light_block(self, height):
                lb = super().light_block(height)
                if not self.armed:
                    return lb
                from dataclasses import replace

                evil_header = replace(lb.signed_header.header, app_hash=b"\x66" * 32)
                return type(lb)(
                    signed_header=SignedHeader(
                        header=evil_header, commit=lb.signed_header.commit
                    ),
                    validators=lb.validators,
                )

        evil = EvilWitness(bstore, cs._block_exec.store)
        c = self._client(cs, bstore, witnesses=[evil])
        evil.armed = True
        # the witness can't sustain its forged header (its commit signs the
        # real one), so it is removed and cross-referencing fails
        # (detector.go:88-101); the sustained-forgery attack path is covered
        # in tests/test_light_attack.py
        from tendermint_tpu.light.client import ErrFailedHeaderCrossReferencing

        with pytest.raises(ErrFailedHeaderCrossReferencing):
            c.verify_light_block_at_height(3)
        assert c._witnesses == []

    def test_expired_trust_rejected(self, produced_chain):
        cs, bstore = produced_chain
        c = self._client(cs, bstore)
        # "now" far in the future: trusted header expired
        future = Timestamp(seconds=2**35, nanos=0)
        from tendermint_tpu.light.verifier import ErrOldHeaderExpired

        with pytest.raises(ErrOldHeaderExpired):
            c.verify_light_block_at_height(5, now=future)


class TestBlockSync:
    def test_fresh_node_catches_up(self, produced_chain):
        from tendermint_tpu.blocksync import BLOCKSYNC_DESC, BlockSyncReactor
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.store import BlockStore
        from tendermint_tpu.abci import KVStoreApplication, LocalClient
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

        cs, src_store = produced_chain

        hub = new_memory_network()
        keys = [NodeKey.generate(bytes([i + 30]) * 32) for i in range(2)]
        routers = []
        for i in range(2):
            t = MemoryTransport(hub, keys[i].node_id, keys[i].pub_key)
            pm = PeerManager(keys[i].node_id)
            r = Router(t, pm, keys[i].node_id)
            routers.append(r)

        # node 0: serves the produced chain
        serving = BlockSyncReactor(
            routers[0], src_store, cs._block_exec, cs.committed_state
        )

        # node 1: fresh from genesis
        sk = ed25519.gen_priv_key(bytes([7]) * 32)
        doc = GenesisDoc(
            chain_id="cs-chain",
            genesis_time=Timestamp(seconds=1_700_000_000),
            validators=[GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)],
        )
        genesis = make_genesis_state(doc)
        sstore = StateStore(MemDB())
        sstore.save(genesis)
        fresh_store = BlockStore(MemDB())
        ex = BlockExecutor(sstore, LocalClient(KVStoreApplication()), block_store=fresh_store)
        caught = []
        syncing = BlockSyncReactor(
            routers[1], fresh_store, ex, genesis, on_caught_up=lambda s: caught.append(s)
        )

        routers[0]._pm.add_address(PeerAddress(keys[1].node_id, keys[1].node_id))
        for r in routers:
            r.start()
        serving.start()
        syncing.start()
        target = src_store.height() - 1  # can't verify the tip without a next block
        deadline = time.time() + 30
        try:
            # the pool reports caught-up AFTER the last block is stored:
            # wait for the report too, not only for the height
            while time.time() < deadline and not (
                    fresh_store.height() >= target and caught):
                time.sleep(0.1)
        finally:
            serving.stop()
            syncing.stop()
            for r in routers:
                r.stop()
        assert fresh_store.height() >= target
        for h in range(1, target + 1):
            assert fresh_store.load_block(h).hash() == src_store.load_block(h).hash()
        assert caught, "on_caught_up was not reported"


class TestLightClientAPI:
    """client.go public-surface parity: VerifyHeader, height accessors,
    witness management, init-time witness cross-check."""

    def _client(self, cs, bstore, witnesses=None):
        prov = _provider(cs, bstore)
        lb1 = prov.light_block(1)
        return Client(
            chain_id="cs-chain",
            trust_options=TrustOptions(period=1e9, height=1, hash=lb1.hash()),
            primary=prov,
            witnesses=witnesses if witnesses is not None else [prov],
            store=LightStore(MemDB()),
        ), prov

    def test_verify_header_and_accessors(self, produced_chain):
        cs, bstore = produced_chain
        c, prov = self._client(cs, bstore)
        assert c.chain_id() == "cs-chain"
        assert c.primary() is prov
        assert c.last_trusted_height() == 1
        assert c.first_trusted_height() == 1
        hdr3 = prov.light_block(3).signed_header.header
        c.verify_header(hdr3)  # fetches + verifies through the primary
        assert c.last_trusted_height() >= 3
        # re-verifying a trusted header is a no-op; a mismatching one errors
        c.verify_header(hdr3)
        from dataclasses import replace

        import pytest as _pytest

        forged = replace(hdr3, app_hash=b"\x13" * 32)
        with _pytest.raises(ValueError):
            c.verify_header(forged)

    def test_witness_management(self, produced_chain):
        cs, bstore = produced_chain
        c, prov = self._client(cs, bstore)
        extra = _provider(cs, bstore)
        c.add_provider(extra)
        assert len(c.witnesses()) == 2
        c.remove_witnesses([0])
        assert c.witnesses() == [extra]
        import pytest as _pytest

        with _pytest.raises(RuntimeError):
            c.remove_witnesses([0])  # cannot remove all witnesses
        c.cleanup()
        assert c.last_trusted_height() == -1

    def test_init_conflicting_witness_rejected(self, produced_chain):
        """compareFirstHeaderWithWitnesses: a witness serving a different
        root header aborts client construction."""
        from dataclasses import replace

        import pytest as _pytest

        from tendermint_tpu.light.client import ErrLightClientAttack
        from tendermint_tpu.light.provider import LightBlock

        cs, bstore = produced_chain
        prov = _provider(cs, bstore)

        class ConflictingWitness(type(prov)):
            def light_block(self, height):
                lb = super().light_block(height)
                return LightBlock(
                    signed_header=SignedHeader(
                        header=replace(
                            lb.signed_header.header, app_hash=b"\x31" * 32
                        ),
                        commit=lb.signed_header.commit,
                    ),
                    validators=lb.validators,
                )

        evil = ConflictingWitness(bstore, cs._block_exec.store)
        lb1 = prov.light_block(1)
        with _pytest.raises(ErrLightClientAttack):
            Client(
                chain_id="cs-chain",
                trust_options=TrustOptions(period=1e9, height=1, hash=lb1.hash()),
                primary=prov,
                witnesses=[evil],
                store=LightStore(MemDB()),
            )
