"""TLS on the JSON-RPC server and clients.

Reference parity: rpc/jsonrpc/server/http_server.go ServeTLS — the same
handler tree (HTTP JSON-RPC + the /websocket upgrade) served over TLS when
the config names a cert/key pair; clients pin the CA.
"""

import datetime
import json
import ssl
import urllib.request

import pytest

from tendermint_tpu.config import Config
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.node import make_node
from tendermint_tpu.abci import KVStoreApplication
from tendermint_tpu.privval import FilePV
from tendermint_tpu.p2p import NodeKey
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.wire.canonical import Timestamp
from tests.test_node_rpc import CHAIN, FAST


def _self_signed_cert(tmp_path):
    """Generate a self-signed localhost certificate (test CA == leaf)."""
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "127.0.0.1")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (
        x509.CertificateBuilder()
        .subject_name(name)
        .issuer_name(name)
        .public_key(key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(
            x509.SubjectAlternativeName(
                [x509.DNSName("localhost"),
                 x509.IPAddress(__import__("ipaddress").ip_address("127.0.0.1"))]
            ),
            critical=False,
        )
        .sign(key, hashes.SHA256())
    )
    cert_path = tmp_path / "rpc.crt"
    key_path = tmp_path / "rpc.key"
    cert_path.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    key_path.write_bytes(
        key.private_bytes(
            serialization.Encoding.PEM,
            serialization.PrivateFormat.TraditionalOpenSSL,
            serialization.NoEncryption(),
        )
    )
    return str(cert_path), str(key_path)


@pytest.fixture
def tls_node(tmp_path):
    cert, key = _self_signed_cert(tmp_path)
    sk = ed25519.gen_priv_key(bytes([9]) * 32)
    doc = GenesisDoc(
        chain_id=CHAIN,
        genesis_time=Timestamp(seconds=1_700_000_000),
        validators=[GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)],
    )
    cfg = Config()
    cfg.base.home = ""
    cfg.base.db_backend = "memdb"
    cfg.consensus = FAST
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.tls_cert_file = cert
    cfg.rpc.tls_key_file = key
    node = make_node(
        cfg,
        app=KVStoreApplication(),
        genesis=doc,
        priv_validator=FilePV(sk),
        node_key=NodeKey.generate(bytes([77]) * 32),
        with_rpc=True,
    )
    node.start()
    try:
        yield node, cert
    finally:
        node.stop()


class TestRPCOverTLS:
    def test_https_rpc_and_plaintext_rejected(self, tls_node):
        from tendermint_tpu.rpc.client import HTTPClient

        node, ca = tls_node
        assert node.rpc_server.tls
        addr = node.rpc_server.listen_addr
        node.wait_for_height(1, timeout=60)

        c = HTTPClient(f"https://{addr}", ca_file=ca)
        st = c.status()
        assert int(st["sync_info"]["latest_block_height"]) >= 1
        assert c.health() == {}

        # an unpinned default context must REFUSE the self-signed cert
        with pytest.raises(Exception):
            urllib.request.urlopen(f"https://{addr}/health", timeout=10)

        # plaintext HTTP against the TLS listener cannot produce a result
        with pytest.raises(Exception):
            with urllib.request.urlopen(f"http://{addr}/health", timeout=10) as r:
                json.loads(r.read())

    def test_wss_subscribe(self, tls_node):
        from tendermint_tpu.rpc.client import WSClient

        node, ca = tls_node
        node.wait_for_height(1, timeout=60)
        c = WSClient(f"wss://{node.rpc_server.listen_addr}", ca_file=ca)
        try:
            st = c.call("status")
            assert int(st["sync_info"]["latest_block_height"]) >= 1
            c.subscribe("tm.event='NewBlock'")
            ev = c.next_event(timeout=30)
            assert ev["query"] == "tm.event='NewBlock'"
        finally:
            c.close()

    def test_wss_refuses_unpinned(self, tls_node):
        from tendermint_tpu.rpc.client import WSClient

        node, _ = tls_node
        with pytest.raises(ssl.SSLError):
            WSClient(f"wss://{node.rpc_server.listen_addr}")
