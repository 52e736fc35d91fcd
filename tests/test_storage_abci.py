"""Storage (db, block store) and ABCI (codec, clients, server, kvstore)."""

import threading

import pytest

from tendermint_tpu.abci import (
    ABCIServer,
    KVStoreApplication,
    LocalClient,
    PersistentKVStoreApplication,
    SocketClient,
)
from tendermint_tpu.abci import types as abci
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.crypto.encoding import pubkey_from_proto, pubkey_to_proto
from tendermint_tpu.db import MemDB, PrefixDB, SQLiteDB
from tendermint_tpu.store import BlockStore
from tendermint_tpu.types import (
    Block,
    Commit,
    CommitSig,
    Data,
    Header,
    Timestamp,
    BLOCK_ID_FLAG_COMMIT,
)
from tendermint_tpu.types.block import BlockID, PartSetHeader
from tendermint_tpu.types.part_set import PartSet


class TestDB:
    @pytest.mark.parametrize("make", [MemDB, lambda: SQLiteDB(":memory:")])
    def test_ordered_kv(self, make):
        db = make()
        for k in [b"b", b"a", b"c", b"ab"]:
            db.set(k, b"v" + k)
        assert db.get(b"a") == b"va"
        assert db.get(b"missing") is None
        keys = [k for k, _ in db.iterator()]
        assert keys == [b"a", b"ab", b"b", b"c"]
        assert [k for k, _ in db.iterator(b"ab", b"c")] == [b"ab", b"b"]
        assert [k for k, _ in db.reverse_iterator()] == [b"c", b"b", b"ab", b"a"]
        db.delete(b"b")
        assert db.get(b"b") is None
        db.write_batch([("set", b"x", b"1"), ("delete", b"a", None)])
        assert db.get(b"x") == b"1" and db.get(b"a") is None

    def test_prefix_db(self):
        base = MemDB()
        p1, p2 = PrefixDB(base, b"a/"), PrefixDB(base, b"b/")
        p1.set(b"k", b"1")
        p2.set(b"k", b"2")
        assert p1.get(b"k") == b"1" and p2.get(b"k") == b"2"
        assert [kv for kv in p1.iterator()] == [(b"k", b"1")]


def _make_chain_block(height, last_commit=None):
    header = Header(
        chain_id="t",
        height=height,
        validators_hash=b"\x01" * 32,
        next_validators_hash=b"\x01" * 32,
        consensus_hash=b"\x02" * 32,
        proposer_address=b"\x04" * 20,
    )
    b = Block(header=header, data=Data(txs=[b"tx-%d" % height]), last_commit=last_commit)
    b.fill_header()
    return b


def _commit_for(block, parts):
    bid = BlockID(hash=block.hash(), part_set_header=parts.header())
    return Commit(
        height=block.header.height,
        round=0,
        block_id=bid,
        signatures=[
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=b"\x07" * 20,
                timestamp=Timestamp(seconds=4),
                signature=b"\x08" * 64,
            )
        ],
    )


class TestBlockStore:
    def test_save_load_prune(self):
        bs = BlockStore(MemDB())
        assert bs.height() == 0 and bs.base() == 0
        last_commit = None
        blocks = []
        for h in range(1, 6):
            b = _make_chain_block(h, last_commit)
            parts = PartSet.from_data(b.encode())
            seen = _commit_for(b, parts)
            bs.save_block(b, parts, seen)
            last_commit = seen
            blocks.append(b)
        assert bs.height() == 5 and bs.base() == 1 and bs.size() == 5
        lb = bs.load_block(3)
        assert lb.header == blocks[2].header
        assert bs.load_block_by_hash(blocks[2].hash()).header == blocks[2].header
        assert bs.load_block_meta(2).header == blocks[1].header
        assert bs.load_block_commit(4) is not None  # block 5's LastCommit
        assert bs.load_seen_commit().height == 5
        # out-of-order save rejected
        with pytest.raises(ValueError):
            bs.save_block(_make_chain_block(9), PartSet.from_data(b"z"), _commit_for(blocks[0], PartSet.from_data(b"z")))
        pruned = bs.prune_blocks(4)
        assert pruned == 3
        assert bs.base() == 4
        assert bs.load_block(2) is None


class TestABCICodec:
    def test_request_response_roundtrip(self):
        req = abci.RequestBeginBlock(
            hash=b"\x01" * 32,
            header=b"hdrbytes",
            last_commit_info=abci.LastCommitInfo(
                round=2,
                votes=[
                    abci.VoteInfo(
                        validator=abci.ABCIValidator(address=b"\x02" * 20, power=10),
                        signed_last_block=True,
                    )
                ],
            ),
        )
        payload = abci.enc_request_payload("begin_block", req)
        framed = abci.write_message(abci.encode_request("begin_block", payload))
        msg, n = abci.read_message(framed)
        assert n == len(framed)
        kind, p2 = abci.decode_request(msg)
        assert kind == "begin_block"
        rt = abci.dec_request_payload(kind, p2)
        assert rt == req

        resp = abci.ResponseCheckTx(code=0, gas_wanted=5, priority=7, sender="s")
        enc = abci.enc_response_payload("check_tx", resp)
        rt2 = abci.dec_response_payload("check_tx", enc)
        assert rt2 == resp


class TestKVStore:
    def test_local_client_flow(self):
        app = KVStoreApplication()
        cli = LocalClient(app)
        assert cli.info(abci.RequestInfo()).last_block_height == 0
        assert cli.check_tx(abci.RequestCheckTx(tx=b"a=1")).is_ok()
        cli.begin_block(abci.RequestBeginBlock())
        assert cli.deliver_tx(abci.RequestDeliverTx(tx=b"a=1")).is_ok()
        cli.end_block(abci.RequestEndBlock(height=1))
        c = cli.commit()
        assert c.data  # app hash
        q = cli.query(abci.RequestQuery(data=b"a", path="/key"))
        assert q.value == b"1"

    def test_socket_client_server(self):
        app = KVStoreApplication()
        srv = ABCIServer("tcp://127.0.0.1:0", app)
        srv.start()
        cli = SocketClient(srv.address)
        try:
            assert cli.echo("hello") == "hello"
            assert cli.info(abci.RequestInfo()).version.startswith("kvstore")
            # pipelined delivers
            futs = [cli.deliver_tx_async(abci.RequestDeliverTx(tx=b"k%d=v" % i)) for i in range(20)]
            cli.flush()
            assert all(f.result(timeout=5).is_ok() for f in futs)
            cli.end_block(abci.RequestEndBlock(height=1))
            cli.commit()
            assert cli.query(abci.RequestQuery(data=b"k7", path="/key")).value == b"v"
        finally:
            cli.close()
            srv.stop()

    def test_persistent_kvstore_validator_updates(self):
        from tendermint_tpu.abci.kvstore import make_validator_tx

        app = PersistentKVStoreApplication()
        pk = ed25519.gen_priv_key(bytes([1]) * 32).pub_key()
        app.init_chain(
            abci.RequestInitChain(
                validators=[abci.ValidatorUpdate(pub_key=pubkey_to_proto(pk), power=10)]
            )
        )
        app.begin_block(abci.RequestBeginBlock())
        pk2 = ed25519.gen_priv_key(bytes([2]) * 32).pub_key()
        r = app.deliver_tx(
            abci.RequestDeliverTx(tx=make_validator_tx(pk2.bytes(), 7))
        )
        assert r.is_ok()
        eb = app.end_block(abci.RequestEndBlock(height=1))
        assert len(eb.validator_updates) == 1
        assert pubkey_from_proto(eb.validator_updates[0].pub_key).bytes() == pk2.bytes()
        vals = app.validators()
        assert len(vals) == 2


class TestABCICli:
    """abci-cli parity (abci/cmd/abci-cli): batch-style commands against
    a socket kvstore server."""

    def test_cli_commands_roundtrip(self, capsys):
        from tendermint_tpu.abci import cli as abci_cli
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.abci.server import ABCIServer

        srv = ABCIServer("tcp://127.0.0.1:0", KVStoreApplication())
        srv.start()
        addr = srv._address
        try:
            assert abci_cli.main(["--address", addr, "echo", "hello"]) == 0
            assert abci_cli.main(["--address", addr, "info"]) == 0
            assert (
                abci_cli.main(["--address", addr, "deliver_tx", '"abc=def"']) == 0
            )
            assert abci_cli.main(["--address", addr, "commit"]) == 0
            assert abci_cli.main(["--address", addr, "query", '"abc"']) == 0
            out = capsys.readouterr().out
            assert "hello" in out
            assert "value" in out
            # hex form of the same tx (stringOrHexToBytes)
            hex_tx = "0x" + b"k2=v2".hex()
            assert abci_cli.main(["--address", addr, "deliver_tx", hex_tx]) == 0
            # bad arg form errors
            assert abci_cli.main(["--address", addr, "deliver_tx", "bare"]) == 1
        finally:
            srv.stop()

    def test_cli_batch_mode(self, capsys, monkeypatch):
        import io

        from tendermint_tpu.abci import cli as abci_cli
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.abci.server import ABCIServer

        srv = ABCIServer("tcp://127.0.0.1:0", KVStoreApplication())
        srv.start()
        addr = srv._address
        try:
            monkeypatch.setattr(
                "sys.stdin",
                io.StringIO('deliver_tx "bk=bv"\ncommit\nquery "bk"\n'),
            )
            assert abci_cli.main(["--address", addr, "batch"]) == 0
            out = capsys.readouterr().out
            assert "-> commit" in out and "-> query" in out
        finally:
            srv.stop()


class TestABCIUnknownOneof:
    def test_unknown_request_and_response_kinds_fail_loudly(self):
        """A foreign app speaking an ABCI
        method this framework does not implement must produce a loud
        error, not a silently dropped message."""
        import pytest

        from tendermint_tpu.abci.types import decode_request, decode_response
        from tendermint_tpu.wire.proto import ProtoWriter

        w = ProtoWriter()
        w.write_message(99, b"\x0a\x01x", always=True)  # no such oneof
        with pytest.raises(ValueError, match="unknown ABCI request"):
            decode_request(w.bytes())
        with pytest.raises(ValueError, match="unknown ABCI response"):
            decode_response(w.bytes())
        with pytest.raises(ValueError, match="empty"):
            decode_request(b"")
