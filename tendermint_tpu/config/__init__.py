"""Configuration tree.

Reference parity: config/config.go — Base/RPC/P2P/Mempool/StateSync/
Consensus/TxIndex/Instrumentation sections with the reference's defaults
(consensus timeouts config.go:956-962), TOML load/save via stdlib tomllib
+ a minimal writer, node modes validator/full/seed.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, asdict
from typing import List, Optional

MODE_FULL = "full"
MODE_VALIDATOR = "validator"
MODE_SEED = "seed"


@dataclass
class BaseConfig:
    """config.go BaseConfig."""

    home: str = ""
    chain_id: str = ""
    moniker: str = "anonymous"
    mode: str = MODE_VALIDATOR
    db_backend: str = "sqlite"
    db_dir: str = "data"
    genesis_file: str = "config/genesis.json"
    node_key_file: str = "config/node_key.json"
    abci: str = "socket"
    proxy_app: str = "tcp://127.0.0.1:26658"
    filter_peers: bool = False

    def genesis_path(self) -> str:
        return os.path.join(self.home, self.genesis_file)

    def node_key_path(self) -> str:
        return os.path.join(self.home, self.node_key_file)

    def db_path(self, name: str) -> str:
        return os.path.join(self.home, self.db_dir, f"{name}.db")


@dataclass
class PrivValidatorConfig:
    """config.go PrivValidatorConfig."""

    key_file: str = "config/priv_validator_key.json"
    state_file: str = "data/priv_validator_state.json"
    listen_addr: str = ""

    def key_path(self, home: str) -> str:
        return os.path.join(home, self.key_file)

    def state_path(self, home: str) -> str:
        return os.path.join(home, self.state_file)


@dataclass
class RPCConfig:
    laddr: str = "tcp://127.0.0.1:26657"
    cors_allowed_origins: List[str] = field(default_factory=list)
    unsafe: bool = False
    max_open_connections: int = 900
    max_subscription_clients: int = 100
    max_subscriptions_per_client: int = 5
    timeout_broadcast_tx_commit_ms: int = 10000
    max_body_bytes: int = 1000000
    max_header_bytes: int = 1 << 20
    # TLS: both set -> the RPC server serves HTTPS/WSS
    # (rpc/jsonrpc/server/http_server.go ServeTLS; config.go TLSCertFile).
    # Relative paths resolve under <home>/config/.
    tls_cert_file: str = ""
    tls_key_file: str = ""
    pprof_laddr: str = ""


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    persistent_peers: str = ""
    bootstrap_peers: str = ""
    max_connections: int = 64
    max_incoming_connection_attempts: int = 100
    flush_throttle_timeout_ms: int = 100
    max_packet_msg_payload_size: int = 1400
    send_rate: int = 5120000
    recv_rate: int = 5120000
    pex: bool = True
    private_peer_ids: str = ""
    allow_duplicate_ip: bool = False
    handshake_timeout_ms: int = 20000
    dial_timeout_ms: int = 3000


@dataclass
class MempoolConfig:
    recheck: bool = True
    broadcast: bool = True
    size: int = 5000
    max_txs_bytes: int = 1073741824  # 1GB
    cache_size: int = 10000
    keep_invalid_txs_in_cache: bool = False
    max_tx_bytes: int = 1048576  # 1MB
    ttl_duration_ms: int = 0
    ttl_num_blocks: int = 0


@dataclass
class StateSyncConfig:
    enable: bool = False
    rpc_servers: List[str] = field(default_factory=list)
    trust_height: int = 0
    trust_hash: str = ""
    trust_period_ms: int = 168 * 3600 * 1000  # 1 week
    discovery_time_ms: int = 15000
    chunk_request_timeout_ms: int = 15000
    fetchers: int = 4


@dataclass
class BlockSyncConfig:
    enable: bool = True
    version: str = "v0"


@dataclass
class ConsensusConfig:
    """config.go:922-962 — timeouts in milliseconds."""

    wal_file: str = "data/cs.wal/wal"
    timeout_propose_ms: int = 3000
    timeout_propose_delta_ms: int = 500
    timeout_prevote_ms: int = 1000
    timeout_prevote_delta_ms: int = 500
    timeout_precommit_ms: int = 1000
    timeout_precommit_delta_ms: int = 500
    timeout_commit_ms: int = 1000
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval_ms: int = 0
    peer_gossip_sleep_duration_ms: int = 100
    peer_query_maj23_sleep_duration_ms: int = 2000
    double_sign_check_height: int = 0

    # timeout helpers (config.go Propose/Prevote/Precommit/Commit methods)
    def propose_timeout(self, round_: int) -> float:
        return (self.timeout_propose_ms + self.timeout_propose_delta_ms * round_) / 1000.0

    def prevote_timeout(self, round_: int) -> float:
        return (self.timeout_prevote_ms + self.timeout_prevote_delta_ms * round_) / 1000.0

    def precommit_timeout(self, round_: int) -> float:
        return (self.timeout_precommit_ms + self.timeout_precommit_delta_ms * round_) / 1000.0

    def commit_timeout(self) -> float:
        return self.timeout_commit_ms / 1000.0

    def wal_path(self, home: str) -> str:
        return os.path.join(home, self.wal_file)


@dataclass
class TxIndexConfig:
    indexer: List[str] = field(default_factory=lambda: ["kv"])
    psql_conn: str = ""


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    max_open_connections: int = 3
    namespace: str = "tendermint"
    # Span tracing (observability.trace): off by default — the tracer's
    # disabled path is a single attribute check on the hot path. When on,
    # spans land in a fixed-size ring buffer served by the /dump_trace RPC
    # and (if trace_dump_path is set, resolved under <home>) flushed as a
    # Chrome-trace JSON file on node stop. TM_TPU_TRACE=1 also enables.
    tracing: bool = False
    trace_buffer_size: int = 262144
    trace_dump_path: str = ""


@dataclass
class Config:
    """config.go:61-74 — the full tree."""

    base: BaseConfig = field(default_factory=BaseConfig)
    priv_validator: PrivValidatorConfig = field(default_factory=PrivValidatorConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    blocksync: BlockSyncConfig = field(default_factory=BlockSyncConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(default_factory=InstrumentationConfig)

    def validate_basic(self) -> None:
        if self.base.mode not in (MODE_FULL, MODE_VALIDATOR, MODE_SEED):
            raise ValueError(f"unknown mode: {self.base.mode}")
        if self.mempool.size < 0:
            raise ValueError("mempool size can't be negative")

    def ensure_dirs(self) -> None:
        for sub in ("config", "data"):
            os.makedirs(os.path.join(self.base.home, sub), exist_ok=True)

    # -- TOML -----------------------------------------------------------

    def save(self, path: Optional[str] = None) -> None:
        path = path or os.path.join(self.base.home, "config", "config.toml")
        with open(path, "w") as fh:
            fh.write(_to_toml(self))

    @classmethod
    def load(cls, path: str) -> "Config":
        try:
            import tomllib
        except ModuleNotFoundError:  # Python < 3.11
            import tomli as tomllib

        with open(path, "rb") as fh:
            data = tomllib.load(fh)
        cfg = cls()
        for section_name, section in data.items():
            tgt = getattr(cfg, section_name, None)
            if tgt is None or not isinstance(section, dict):
                continue
            for k, v in section.items():
                if hasattr(tgt, k):
                    setattr(tgt, k, v)
        return cfg


def _toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return str(v)
    if isinstance(v, list):
        return "[" + ", ".join(_toml_value(x) for x in v) + "]"
    return '"' + str(v).replace("\\", "\\\\").replace('"', '\\"') + '"'


def _to_toml(cfg: Config) -> str:
    out = []
    for section_name, section in asdict(cfg).items():
        out.append(f"[{section_name}]")
        for k, v in section.items():
            out.append(f"{k} = {_toml_value(v)}")
        out.append("")
    return "\n".join(out)


def default_config(home: str) -> Config:
    cfg = Config()
    cfg.base.home = home
    return cfg
