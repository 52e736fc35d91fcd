"""Auxiliary subsystems: indexer + tx_search, rollback, inspect mode,
CLI commands, fail-point injection, pubsub queries, bit arrays."""

import json
import os
import subprocess
import sys

import pytest

from tendermint_tpu.db import MemDB
from tendermint_tpu.libs.bits import BitArray
from tendermint_tpu.libs.pubsub import Query


class TestQueryLanguage:
    def test_conditions(self):
        q = Query("tm.event='Tx' AND tx.height>5 AND app.key CONTAINS 'ab'")
        assert q.matches({"tm.event": ["Tx"], "tx.height": ["6"], "app.key": ["xaby"]})
        assert not q.matches({"tm.event": ["Tx"], "tx.height": ["5"], "app.key": ["xaby"]})
        assert not q.matches({"tm.event": ["NewBlock"], "tx.height": ["9"], "app.key": ["ab"]})
        assert Query("tx.hash EXISTS").matches({"tx.hash": ["AA"]})
        assert not Query("tx.hash EXISTS").matches({"other": ["AA"]})

    def test_invalid_query(self):
        with pytest.raises(ValueError):
            Query("this is !! not a query ==")


class TestBitArray:
    def test_ops(self):
        a = BitArray(10)
        a.set_index(2, True)
        a.set_index(7, True)
        b = BitArray(10)
        b.set_index(7, True)
        assert a.get_index(2) and not a.get_index(3)
        assert a.sub(b).get_true_indices() == [2]
        assert a.or_(b).num_true_bits() == 2
        assert a.and_(b).get_true_indices() == [7]
        assert a.not_().num_true_bits() == 8
        rt = BitArray.decode(a.encode())
        assert rt == a
        idx, ok = a.pick_random()
        assert ok and idx in (2, 7)


class TestIndexer:
    def test_index_and_search(self):
        from tendermint_tpu.abci import types as abci
        from tendermint_tpu.indexer import KVSink
        from tendermint_tpu.types.tx import tx_hash

        sink = KVSink(MemDB())
        res = abci.ResponseDeliverTx(code=0)
        sink.index_tx(
            5, 0, b"tx-a", res,
            {"tm.event": ["Tx"], "app.creator": ["alice"], "tx.height": ["5"]},
        )
        sink.index_tx(
            6, 1, b"tx-b", res,
            {"tm.event": ["Tx"], "app.creator": ["bob"], "tx.height": ["6"]},
        )
        rec = sink.get_tx(tx_hash(b"tx-a"))
        assert rec["height"] == 5
        hits = sink.search_txs("app.creator='alice'")
        assert len(hits) == 1 and hits[0]["tx"] == b"tx-a".hex()
        hits = sink.search_txs("tm.event='Tx' AND tx.height>5")
        assert len(hits) == 1 and hits[0]["height"] == 6
        sink.index_block(5, {"block.height": ["5"]})
        sink.index_block(6, {"block.height": ["6"]})
        assert sink.search_blocks("block.height='6'") == [6]

    def test_indexer_service_end_to_end(self):
        """Indexer wired to a real running chain via the eventbus."""
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.indexer import IndexerService, KVSink
        from tests.test_consensus import make_node

        sk = ed25519.gen_priv_key(bytes([9]) * 32)
        cs, bstore, _ = make_node([sk], 0, tx_source=[b"idx=1"])
        sink = KVSink(MemDB())
        svc = IndexerService([sink], cs._event_bus)
        svc.start()
        cs.start()
        try:
            cs.wait_for_height(2, timeout=30)
        finally:
            cs.stop()
            svc.stop()
        from tendermint_tpu.types.tx import tx_hash

        rec = sink.get_tx(tx_hash(b"idx=1"))
        assert rec is not None and rec["code"] == 0


class TestRollback:
    def test_rollback_one_height(self):
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.state.rollback import rollback_state
        from tests.test_consensus import make_node

        sk = ed25519.gen_priv_key(bytes([3]) * 32)
        cs, bstore, _ = make_node([sk], 0)
        cs.start()
        try:
            cs.wait_for_height(4, timeout=30)
        finally:
            cs.stop()
        sstore = cs._block_exec.store
        before = sstore.load()
        h = before.last_block_height
        if bstore.height() == h + 1:
            # stopped mid-apply: block persisted, state not yet. The
            # reference returns the CURRENT state unchanged
            # (rollback.go:24-29) — no state to roll back.
            new_h, app_hash = rollback_state(sstore, bstore)
            assert new_h == h
            assert app_hash == before.app_hash
            assert sstore.load().last_block_height == h
            # the normal-shutdown case must still roll back: re-run after
            # pretending the tail block was applied is not possible here,
            # so verify via the invariant error path instead
        else:
            assert bstore.height() == h
            new_h, app_hash = rollback_state(sstore, bstore)
            assert new_h == h - 1
            after = sstore.load()
            assert after.last_block_height == h - 1
            meta = bstore.load_block_meta(h)
            assert app_hash == meta.header.app_hash

    def test_rollback_mid_apply_returns_current_state(self):
        """blockstore one ahead of statestore (crash between save_block
        and state save) — rollback is a no-op returning the current state
        (rollback.go:24-29); a larger divergence is an invariant error."""
        from types import SimpleNamespace

        from tendermint_tpu.state.rollback import rollback_state

        state = SimpleNamespace(last_block_height=7, app_hash=b"\xaa" * 32)

        class SS:
            def load(self):
                return state

        class BS:
            def __init__(self, h):
                self._h = h

            def height(self):
                return self._h

        assert rollback_state(SS(), BS(8)) == (7, b"\xaa" * 32)
        with pytest.raises(RuntimeError, match="not one below or equal"):
            rollback_state(SS(), BS(9))


class TestInspect:
    def test_inspect_serves_indexer_rpcs_from_dead_node_dir(self, tmp_path):
        """internal/inspect/rpc/rpc.go:48-66: kill a
        node, run inspect over its DATA DIR (sqlite stores + tx_index
        sink), find a tx by hash and by event query, and block_search."""
        import json
        import urllib.request

        from tendermint_tpu.abci import KVStoreApplication
        from tendermint_tpu.config import Config
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.db import backend as db_backend
        from tendermint_tpu.inspect import Inspector
        from tendermint_tpu.node import make_node
        from tendermint_tpu.p2p import NodeKey
        from tendermint_tpu.privval import FilePV
        from tendermint_tpu.rpc import HTTPClient
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.store import BlockStore
        from tendermint_tpu.types import Timestamp
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
        from tests.test_node_rpc import FAST

        sk = ed25519.gen_priv_key(bytes([8]) * 32)
        doc = GenesisDoc(
            chain_id="inspect-chain",
            genesis_time=Timestamp(seconds=1_700_000_000),
            validators=[
                GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)
            ],
        )
        cfg = Config()
        cfg.base.home = str(tmp_path)
        cfg.base.db_backend = "sqlite"
        cfg.consensus = FAST
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        node = make_node(
            cfg,
            app=KVStoreApplication(),
            genesis=doc,
            priv_validator=FilePV(sk),
            node_key=NodeKey.generate(bytes([42]) * 32),
            with_rpc=True,
        )
        node.start()
        try:
            rpc = HTTPClient(node.rpc_server.listen_addr)
            res = rpc.call("broadcast_tx_commit", tx="696e73703d6b6579")  # insp=key
            assert int(res["deliver_tx"]["code"]) == 0
            tx_hash_hex = res["hash"]
            height = int(res["height"])
            node.wait_for_height(height + 1, timeout=30)
        finally:
            node.stop()

        # the node is dead; inspect opens the same data dir from disk
        insp = Inspector(
            cfg,
            doc,
            StateStore(db_backend("sqlite", cfg.base.db_path("state"))),
            BlockStore(db_backend("sqlite", cfg.base.db_path("blockstore"))),
        )
        insp.start()
        try:
            rpc = HTTPClient(insp.listen_addr)
            # tx by hash
            got = rpc.call("tx", hash=tx_hash_hex)
            assert got["hash"].lower() == tx_hash_hex.lower()
            assert int(got["height"]) == height
            # tx by event query through the persisted index sink
            hits = rpc.call("tx_search", query="app.creator='Cosmoshi Netowoko'")
            assert int(hits["total_count"]) >= 1
            assert any(t["hash"].lower() == tx_hash_hex.lower() for t in hits["txs"])
            # block_search over the same sink
            blocks = rpc.call("block_search", query=f"block.height={height}")
            assert any(
                int(b["block"]["header"]["height"]) == height
                for b in blocks["blocks"]
            )
            # routes outside the inspect table are refused cleanly
            # (internal/inspect/rpc/rpc.go Routes)
            from tendermint_tpu.rpc.core import RPCError

            with pytest.raises(RPCError) as ei:
                rpc.call("broadcast_tx_sync", tx="00")
            assert ei.value.code == -32601
            # ...including over the websocket upgrade (the route gate
            # must not be bypassable by switching transports)
            from tendermint_tpu.rpc.client import WSClient

            ws = WSClient(insp.listen_addr)
            try:
                with pytest.raises(RPCError) as ei2:
                    ws.call("broadcast_tx_sync", {"tx": "00"})
                assert ei2.value.code == -32601
                got_h = ws.call("block", {"height": height})
                assert int(got_h["block"]["header"]["height"]) == height
            finally:
                ws.close()
        finally:
            insp.stop()

    def test_inspect_serves_store_rpcs(self):
        from tendermint_tpu.config import default_config
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.inspect import Inspector
        from tendermint_tpu.rpc import HTTPClient
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
        from tendermint_tpu.types import Timestamp
        from tests.test_consensus import make_node

        sk = ed25519.gen_priv_key(bytes([4]) * 32)
        cs, bstore, _ = make_node([sk], 0)
        cs.start()
        try:
            cs.wait_for_height(3, timeout=30)
        finally:
            cs.stop()
        cfg = default_config("")
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        doc = GenesisDoc(
            chain_id="cs-chain",
            genesis_time=Timestamp(seconds=1_700_000_000),
            validators=[GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)],
        )
        insp = Inspector(cfg, doc, cs._block_exec.store, bstore)
        insp.start()
        try:
            rpc = HTTPClient(insp.listen_addr)
            blk = rpc.block(2)
            assert int(blk["block"]["header"]["height"]) == 2
            vals = rpc.validators(1)
            assert int(vals["total"]) == 1
        finally:
            insp.stop()


class TestCLI:
    def test_init_and_keys(self, tmp_path):
        from tendermint_tpu.cli import main

        home = str(tmp_path / "home")
        assert main(["--home", home, "init", "validator", "--chain-id", "cli-test"]) == 0
        assert os.path.exists(os.path.join(home, "config", "genesis.json"))
        assert os.path.exists(os.path.join(home, "config", "priv_validator_key.json"))
        assert os.path.exists(os.path.join(home, "config", "config.toml"))
        # idempotent re-init keeps the same key
        with open(os.path.join(home, "config", "node_key.json")) as fh:
            nk1 = json.load(fh)["id"]
        assert main(["--home", home, "init", "validator"]) == 0
        with open(os.path.join(home, "config", "node_key.json")) as fh:
            assert json.load(fh)["id"] == nk1

    def test_testnet_generation(self, tmp_path):
        from tendermint_tpu.cli import main
        from tendermint_tpu.config import Config

        out = str(tmp_path / "net")
        assert main(["testnet", "--v", "3", "--o", out, "--chain-id", "net-test"]) == 0
        for i in range(3):
            cfg = Config.load(os.path.join(out, f"node{i}", "config", "config.toml"))
            assert cfg.p2p.persistent_peers.count("@") == 3
        g0 = open(os.path.join(out, "node0", "config", "genesis.json")).read()
        g1 = open(os.path.join(out, "node1", "config", "genesis.json")).read()
        assert g0 == g1
        assert json.loads(g0)["chain_id"] == "net-test"

    def test_unsafe_reset(self, tmp_path):
        from tendermint_tpu.cli import main

        home = str(tmp_path / "home")
        main(["--home", home, "init", "validator"])
        marker = os.path.join(home, "data", "junk.db")
        open(marker, "w").write("x")
        assert main(["--home", home, "unsafe-reset-all"]) == 0
        assert not os.path.exists(marker)


class TestSQLSink:
    """psql sink parity (internal/state/indexer/sink/psql + schema.sql)
    over DB-API — exercised here on sqlite3; production plugs a psycopg2
    connection factory."""

    def _sink(self):
        import sqlite3

        from tendermint_tpu.indexer.sql_sink import SQLSink

        return SQLSink(lambda: sqlite3.connect(":memory:"), "sql-chain")

    def test_blocks_txs_events_roundtrip(self):
        sink = self._sink()
        sink.index_block(1, {"block.proposer": ["aa"]})

        class _R:
            code = 0

        sink.index_tx(1, 0, b"tx-1", _R(), {"transfer.to": ["alice"]})
        sink.index_tx(1, 1, b"tx-2", _R(), {"transfer.to": ["bob"]})
        # idempotent re-index (same block/index)
        sink.index_tx(1, 1, b"tx-2", _R(), {"transfer.to": ["bob"]})
        assert sink.tx_count() == 2
        from tendermint_tpu.types.tx import tx_hash

        found = sink.find_tx_hashes_by_event("transfer.to", "alice")
        assert found == [tx_hash(b"tx-1").hex().upper()]
        sink.close()

    def test_multi_block_unique_constraint(self):
        sink = self._sink()
        for h in (1, 2, 3):
            sink.index_block(h, {"k.a": [str(h)]})
            sink.index_block(h, {"k.b": [str(h)]})  # same height, more events
        cur = sink._conn.cursor()
        cur.execute("SELECT COUNT(*) FROM blocks")
        assert cur.fetchone()[0] == 3
        sink.close()


class TestWALTools:
    def test_wal2json_json2wal_roundtrip(self, tmp_path, capsys):
        """scripts/wal2json + json2wal parity: binary -> JSON lines ->
        binary reproduces the byte-identical CRC-framed WAL."""
        import json as _json
        import struct
        import zlib

        from tendermint_tpu import cli
        from tendermint_tpu.consensus.wal import WAL, WALMessage, _encode_record

        wal_path = tmp_path / "wal"
        msgs = [
            WALMessage(end_height=3),
            WALMessage(timeout=(1000, 4, 0, 1)),
            WALMessage(msg_kind="vote", msg_payload=b"\x01\x02\xff", peer_id="p1"),
            WALMessage(msg_kind="block_part", msg_payload=b"\x00" * 40, peer_id=""),
        ]
        with open(wal_path, "wb") as fh:
            for m in msgs:
                body = _encode_record(m)
                crc = zlib.crc32(body) & 0xFFFFFFFF
                fh.write(struct.pack(">II", crc, len(body)) + body)
        orig = wal_path.read_bytes()

        assert cli.main(["wal2json", str(wal_path)]) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 4
        assert _json.loads(lines[0]) == {"end_height": 3}
        assert _json.loads(lines[2])["msg"]["kind"] == "vote"

        json_path = tmp_path / "wal.json"
        json_path.write_text("\n".join(lines) + "\n")
        out_path = tmp_path / "wal2"
        assert cli.main(["json2wal", str(out_path), "--input", str(json_path)]) == 0
        assert out_path.read_bytes() == orig
        # and it decodes back to the same records
        assert [m.end_height for m in WAL._iter_file(str(out_path))] == [
            m.end_height for m in msgs
        ]


class TestTimeLimit:
    """The harness's per-test limit (tests/conftest.py), driven through a
    pytest of its own: a hang costs the test that hung and nothing else."""

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    HUNG = (
        "import queue\n"
        "import pytest\n"
        "@pytest.fixture(scope='module')\n"
        "def flooded():\n"
        "    q = queue.Queue(1)\n"
        "    q.put(1)\n"
        "    return q\n"
        "@pytest.mark.time_limit(1)\n"
        "def test_blocks_on_a_full_queue(flooded):\n"
        "    flooded.put(2)\n"
        "def test_runs_after_the_hang():\n"
        "    pass\n"
    )
    TOO_MUCH = (
        "import pytest\n"
        "@pytest.mark.time_limit(601)\n"
        "def test_asks_for_too_much():\n"
        "    pass\n"
    )

    def _pytest(self, tmp_path, source):
        (tmp_path / "test_it.py").write_text(source)
        return subprocess.run(
            [sys.executable, "-m", "pytest", "-p", "tests.conftest",
             "-c", "pyproject.toml", "--rootdir", str(tmp_path),
             "-p", "no:cacheprovider", "-v", str(tmp_path / "test_it.py")],
            cwd=self.REPO, capture_output=True, text=True, timeout=90,
        )

    def test_a_blocked_test_fails_at_its_limit_and_the_next_one_runs(self, tmp_path):
        r = self._pytest(tmp_path, self.HUNG)
        out = r.stdout + r.stderr
        assert r.returncode == 1, out
        assert "test_blocks_on_a_full_queue FAILED" in out, out
        assert "exceeded its time limit of 1 s" in out, out
        # the blocked frame, in the failure's traceback and in the dump
        assert "flooded.put(2)" in out, out
        assert 'queue.py", line' in out and "in put" in out, out
        assert "test_runs_after_the_hang PASSED" in out, out
        assert "1 failed, 1 passed" in out, out

    def test_a_limit_above_the_maximum_is_refused_at_collection(self, tmp_path):
        r = self._pytest(tmp_path, self.TOO_MUCH)
        out = r.stdout + r.stderr
        assert r.returncode == 4, out  # pytest's usage-error exit code
        assert "time_limit above 600 s" in out, out
        assert "test_asks_for_too_much" in out, out
