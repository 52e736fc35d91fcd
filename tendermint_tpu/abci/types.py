"""ABCI message types + wire codec.

Reference parity: abci/types/types.pb.go (tendermint.abci package).
Request/Response are proto oneofs; the socket transport frames each
message with a uvarint length prefix (abci/types/messages.go
WriteMessage/ReadMessage).

Field-surface contract: only the fields the
framework and example apps touch are modeled as dataclasses; everything
round-trips through the deterministic proto codec in wire/proto.py.
Concretely:
- Unknown fields INSIDE a message are ignored on decode — standard
  proto3 semantics, identical to what the reference's generated codec
  does — and are therefore NOT re-emitted on re-encode. ABCI messages
  are never round-tripped through this codec on behalf of a third
  party (each side encodes its own structs), so no wire data is lost.
- Unknown Request/Response ONEOF kinds (an ABCI method this framework
  does not implement) are rejected loudly (ValueError) instead of being
  silently dropped — see decode_request/decode_response.
- The modeled surface covers every field the v0.35 framework reads or
  writes on each message (consensus, mempool, query, snapshot
  connections), cross-checked against abci/types/types.pb.go usage in
  the reference's node/consensus/mempool/statesync packages.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dfield
from typing import List, Optional, Tuple

from ..wire.canonical import Timestamp, encode_timestamp
from ..wire.proto import (
    ProtoWriter,
    decode_message,
    field_bytes,
    field_int,
    field_repeated_bytes,
    marshal_delimited,
    to_signed32,
    to_signed64,
    unmarshal_delimited,
)

CODE_TYPE_OK = 0

# CheckTxType enum
CHECK_TX_TYPE_NEW = 0
CHECK_TX_TYPE_RECHECK = 1

# ResponseOfferSnapshot.Result / ResponseApplySnapshotChunk.Result enums
OFFER_SNAPSHOT_UNKNOWN = 0
OFFER_SNAPSHOT_ACCEPT = 1
OFFER_SNAPSHOT_ABORT = 2
OFFER_SNAPSHOT_REJECT = 3
OFFER_SNAPSHOT_REJECT_FORMAT = 4
OFFER_SNAPSHOT_REJECT_SENDER = 5

APPLY_SNAPSHOT_CHUNK_UNKNOWN = 0
APPLY_SNAPSHOT_CHUNK_ACCEPT = 1
APPLY_SNAPSHOT_CHUNK_ABORT = 2
APPLY_SNAPSHOT_CHUNK_RETRY = 3
APPLY_SNAPSHOT_CHUNK_RETRY_SNAPSHOT = 4
APPLY_SNAPSHOT_CHUNK_REJECT_SNAPSHOT = 5

# EvidenceType enum
EVIDENCE_TYPE_UNKNOWN = 0
EVIDENCE_TYPE_DUPLICATE_VOTE = 1
EVIDENCE_TYPE_LIGHT_CLIENT_ATTACK = 2


def _decode_ts(raw: bytes) -> Timestamp:
    f = decode_message(raw)
    return Timestamp(
        seconds=to_signed64(field_int(f, 1)), nanos=to_signed32(field_int(f, 2))
    )


@dataclass
class EventAttribute:
    key: str = ""
    value: str = ""
    index: bool = False

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_string(1, self.key)
        w.write_string(2, self.value)
        w.write_varint(3, 1 if self.index else 0)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "EventAttribute":
        f = decode_message(data)
        return cls(
            key=field_bytes(f, 1).decode("utf-8", "replace"),
            value=field_bytes(f, 2).decode("utf-8", "replace"),
            index=bool(field_int(f, 3)),
        )


@dataclass
class Event:
    type: str = ""
    attributes: List[EventAttribute] = dfield(default_factory=list)

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_string(1, self.type)
        for a in self.attributes:
            w.write_message(2, a.encode(), always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Event":
        f = decode_message(data)
        return cls(
            type=field_bytes(f, 1).decode("utf-8", "replace"),
            attributes=[EventAttribute.decode(raw) for raw in field_repeated_bytes(f, 2)],
        )


@dataclass
class ValidatorUpdate:
    """abci.ValidatorUpdate: pub_key (tendermint.crypto.PublicKey) + power."""

    pub_key: bytes  # encoded PublicKey message
    power: int = 0

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_message(1, self.pub_key, always=True)
        w.write_varint(2, self.power)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "ValidatorUpdate":
        f = decode_message(data)
        return cls(pub_key=field_bytes(f, 1), power=to_signed64(field_int(f, 2)))


@dataclass
class ABCIValidator:
    """abci.Validator: address + power (no pubkey)."""

    address: bytes = b""
    power: int = 0

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_bytes(1, self.address)
        w.write_varint(3, self.power)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "ABCIValidator":
        f = decode_message(data)
        return cls(address=field_bytes(f, 1), power=to_signed64(field_int(f, 3)))


@dataclass
class VoteInfo:
    validator: ABCIValidator = dfield(default_factory=ABCIValidator)
    signed_last_block: bool = False

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_message(1, self.validator.encode(), always=True)
        w.write_varint(2, 1 if self.signed_last_block else 0)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "VoteInfo":
        f = decode_message(data)
        return cls(
            validator=ABCIValidator.decode(field_bytes(f, 1)),
            signed_last_block=bool(field_int(f, 2)),
        )


@dataclass
class LastCommitInfo:
    round: int = 0
    votes: List[VoteInfo] = dfield(default_factory=list)

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.round)
        for v in self.votes:
            w.write_message(2, v.encode(), always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "LastCommitInfo":
        f = decode_message(data)
        return cls(
            round=to_signed32(field_int(f, 1)),
            votes=[VoteInfo.decode(raw) for raw in field_repeated_bytes(f, 2)],
        )


@dataclass
class ABCIEvidence:
    """abci.Evidence (misbehavior report to the app)."""

    type: int = EVIDENCE_TYPE_UNKNOWN
    validator: ABCIValidator = dfield(default_factory=ABCIValidator)
    height: int = 0
    time: Timestamp = dfield(default_factory=Timestamp.zero)
    total_voting_power: int = 0

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.type)
        w.write_message(2, self.validator.encode(), always=True)
        w.write_varint(3, self.height)
        w.write_message(4, encode_timestamp(self.time), always=True)
        w.write_varint(5, self.total_voting_power)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "ABCIEvidence":
        f = decode_message(data)
        return cls(
            type=field_int(f, 1),
            validator=ABCIValidator.decode(field_bytes(f, 2)),
            height=to_signed64(field_int(f, 3)),
            time=_decode_ts(field_bytes(f, 4)),
            total_voting_power=to_signed64(field_int(f, 5)),
        )


@dataclass
class Snapshot:
    height: int = 0
    format: int = 0
    chunks: int = 0
    hash: bytes = b""
    metadata: bytes = b""

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.height)
        w.write_varint(2, self.format)
        w.write_varint(3, self.chunks)
        w.write_bytes(4, self.hash)
        w.write_bytes(5, self.metadata)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Snapshot":
        f = decode_message(data)
        return cls(
            height=field_int(f, 1),
            format=field_int(f, 2),
            chunks=field_int(f, 3),
            hash=field_bytes(f, 4),
            metadata=field_bytes(f, 5),
        )


# --------------------------------------------------------------------------
# Requests


@dataclass
class RequestInfo:
    version: str = ""
    block_version: int = 0
    p2p_version: int = 0
    abci_version: str = ""


@dataclass
class RequestInitChain:
    time: Timestamp = dfield(default_factory=Timestamp.zero)
    chain_id: str = ""
    consensus_params: Optional[bytes] = None  # encoded ConsensusParams
    validators: List[ValidatorUpdate] = dfield(default_factory=list)
    app_state_bytes: bytes = b""
    initial_height: int = 0


@dataclass
class RequestQuery:
    data: bytes = b""
    path: str = ""
    height: int = 0
    prove: bool = False


@dataclass
class RequestBeginBlock:
    hash: bytes = b""
    header: bytes = b""  # encoded types.Header
    last_commit_info: LastCommitInfo = dfield(default_factory=LastCommitInfo)
    byzantine_validators: List[ABCIEvidence] = dfield(default_factory=list)


@dataclass
class RequestCheckTx:
    tx: bytes = b""
    type: int = CHECK_TX_TYPE_NEW


@dataclass
class RequestDeliverTx:
    tx: bytes = b""


@dataclass
class RequestEndBlock:
    height: int = 0


@dataclass
class RequestOfferSnapshot:
    snapshot: Optional[Snapshot] = None
    app_hash: bytes = b""


@dataclass
class RequestLoadSnapshotChunk:
    height: int = 0
    format: int = 0
    chunk: int = 0


@dataclass
class RequestApplySnapshotChunk:
    index: int = 0
    chunk: bytes = b""
    sender: str = ""


# --------------------------------------------------------------------------
# Responses


@dataclass
class ResponseInfo:
    data: str = ""
    version: str = ""
    app_version: int = 0
    last_block_height: int = 0
    last_block_app_hash: bytes = b""


@dataclass
class ResponseInitChain:
    consensus_params: Optional[bytes] = None
    validators: List[ValidatorUpdate] = dfield(default_factory=list)
    app_hash: bytes = b""


@dataclass
class ResponseQuery:
    code: int = 0
    log: str = ""
    info: str = ""
    index: int = 0
    key: bytes = b""
    value: bytes = b""
    proof_ops: Optional[bytes] = None  # encoded crypto.ProofOps
    height: int = 0
    codespace: str = ""

    def is_ok(self) -> bool:
        return self.code == CODE_TYPE_OK


@dataclass
class ResponseBeginBlock:
    events: List[Event] = dfield(default_factory=list)


@dataclass
class ResponseCheckTx:
    code: int = 0
    data: bytes = b""
    log: str = ""
    info: str = ""
    gas_wanted: int = 0
    gas_used: int = 0
    events: List[Event] = dfield(default_factory=list)
    codespace: str = ""
    sender: str = ""
    priority: int = 0
    mempool_error: str = ""

    def is_ok(self) -> bool:
        return self.code == CODE_TYPE_OK


@dataclass
class ResponseDeliverTx:
    code: int = 0
    data: bytes = b""
    log: str = ""
    info: str = ""
    gas_wanted: int = 0
    gas_used: int = 0
    events: List[Event] = dfield(default_factory=list)
    codespace: str = ""

    def is_ok(self) -> bool:
        return self.code == CODE_TYPE_OK


@dataclass
class ResponseEndBlock:
    validator_updates: List[ValidatorUpdate] = dfield(default_factory=list)
    consensus_param_updates: Optional[bytes] = None
    events: List[Event] = dfield(default_factory=list)


@dataclass
class ResponseCommit:
    data: bytes = b""
    retain_height: int = 0


@dataclass
class ResponseListSnapshots:
    snapshots: List[Snapshot] = dfield(default_factory=list)


@dataclass
class ResponseOfferSnapshot:
    result: int = OFFER_SNAPSHOT_UNKNOWN


@dataclass
class ResponseLoadSnapshotChunk:
    chunk: bytes = b""


@dataclass
class ResponseApplySnapshotChunk:
    result: int = APPLY_SNAPSHOT_CHUNK_UNKNOWN
    refetch_chunks: List[int] = dfield(default_factory=list)
    reject_senders: List[str] = dfield(default_factory=list)


# --------------------------------------------------------------------------
# Request/Response oneof wire codec (for the socket transport)

_REQ_FIELDS = {
    "echo": 1, "flush": 2, "info": 3, "init_chain": 4, "query": 5,
    "begin_block": 6, "check_tx": 7, "deliver_tx": 8, "end_block": 9,
    "commit": 10, "list_snapshots": 11, "offer_snapshot": 12,
    "load_snapshot_chunk": 13, "apply_snapshot_chunk": 14,
}
_REQ_BY_NUM = {v: k for k, v in _REQ_FIELDS.items()}

_RESP_FIELDS = {
    "exception": 1, "echo": 2, "flush": 3, "info": 4, "init_chain": 5,
    "query": 6, "begin_block": 7, "check_tx": 8, "deliver_tx": 9,
    "end_block": 10, "commit": 11, "list_snapshots": 12,
    "offer_snapshot": 13, "load_snapshot_chunk": 14,
    "apply_snapshot_chunk": 15,
}
_RESP_BY_NUM = {v: k for k, v in _RESP_FIELDS.items()}


def encode_request(kind: str, payload: bytes) -> bytes:
    w = ProtoWriter()
    w.write_message(_REQ_FIELDS[kind], payload, always=True)
    return w.bytes()


def decode_request(data: bytes) -> Tuple[str, bytes]:
    f = decode_message(data)
    unknown = []
    for num, vals in f.items():
        kind = _REQ_BY_NUM.get(num)
        if kind is not None:
            return kind, vals[-1][1]
        unknown.append(num)
    if unknown:
        # a request carrying ONLY methods this framework does not
        # implement must fail LOUDLY, not be silently dropped (a foreign
        # app would otherwise get no reply and hang its connection);
        # unknown fields NEXT TO a known oneof are skipped (proto3)
        raise ValueError(f"unknown ABCI request oneof field(s) {unknown}")
    raise ValueError("empty ABCI request")


def encode_response(kind: str, payload: bytes) -> bytes:
    w = ProtoWriter()
    w.write_message(_RESP_FIELDS[kind], payload, always=True)
    return w.bytes()


def decode_response(data: bytes) -> Tuple[str, bytes]:
    f = decode_message(data)
    unknown = []
    for num, vals in f.items():
        kind = _RESP_BY_NUM.get(num)
        if kind is not None:
            return kind, vals[-1][1]
        unknown.append(num)
    if unknown:
        raise ValueError(f"unknown ABCI response oneof field(s) {unknown}")
    raise ValueError("empty ABCI response")


def write_message(msg: bytes) -> bytes:
    """Length-delimited framing (abci/types/messages.go WriteMessage)."""
    return marshal_delimited(msg)


def read_message(buf: bytes) -> Tuple[bytes, int]:
    return unmarshal_delimited(buf)


# -- payload codecs (request) ----------------------------------------------


def enc_request_payload(kind: str, req) -> bytes:
    w = ProtoWriter()
    if kind == "echo":
        w.write_string(1, req)
    elif kind in ("flush", "commit", "list_snapshots"):
        pass
    elif kind == "info":
        w.write_string(1, req.version)
        w.write_varint(2, req.block_version)
        w.write_varint(3, req.p2p_version)
        w.write_string(4, req.abci_version)
    elif kind == "init_chain":
        w.write_message(1, encode_timestamp(req.time), always=True)
        w.write_string(2, req.chain_id)
        w.write_message(3, req.consensus_params)
        for v in req.validators:
            w.write_message(4, v.encode(), always=True)
        w.write_bytes(5, req.app_state_bytes)
        w.write_varint(6, req.initial_height)
    elif kind == "query":
        w.write_bytes(1, req.data)
        w.write_string(2, req.path)
        w.write_varint(3, req.height)
        w.write_varint(4, 1 if req.prove else 0)
    elif kind == "begin_block":
        w.write_bytes(1, req.hash)
        w.write_message(2, req.header, always=True)
        w.write_message(3, req.last_commit_info.encode(), always=True)
        for e in req.byzantine_validators:
            w.write_message(4, e.encode(), always=True)
    elif kind == "check_tx":
        w.write_bytes(1, req.tx)
        w.write_varint(2, req.type)
    elif kind == "deliver_tx":
        w.write_bytes(1, req.tx)
    elif kind == "end_block":
        w.write_varint(1, req.height)
    elif kind == "offer_snapshot":
        if req.snapshot is not None:
            w.write_message(1, req.snapshot.encode(), always=True)
        w.write_bytes(2, req.app_hash)
    elif kind == "load_snapshot_chunk":
        w.write_varint(1, req.height)
        w.write_varint(2, req.format)
        w.write_varint(3, req.chunk)
    elif kind == "apply_snapshot_chunk":
        w.write_varint(1, req.index)
        w.write_bytes(2, req.chunk)
        w.write_string(3, req.sender)
    else:
        raise ValueError(f"unknown request kind {kind}")
    return w.bytes()


def dec_request_payload(kind: str, data: bytes):
    f = decode_message(data)
    if kind == "echo":
        return field_bytes(f, 1).decode("utf-8", "replace")
    if kind in ("flush", "commit", "list_snapshots"):
        return None
    if kind == "info":
        return RequestInfo(
            version=field_bytes(f, 1).decode(),
            block_version=field_int(f, 2),
            p2p_version=field_int(f, 3),
            abci_version=field_bytes(f, 4).decode(),
        )
    if kind == "init_chain":
        return RequestInitChain(
            time=_decode_ts(field_bytes(f, 1)),
            chain_id=field_bytes(f, 2).decode(),
            consensus_params=field_bytes(f, 3) if 3 in f else None,
            validators=[ValidatorUpdate.decode(raw) for raw in field_repeated_bytes(f, 4)],
            app_state_bytes=field_bytes(f, 5),
            initial_height=to_signed64(field_int(f, 6)),
        )
    if kind == "query":
        return RequestQuery(
            data=field_bytes(f, 1),
            path=field_bytes(f, 2).decode(),
            height=to_signed64(field_int(f, 3)),
            prove=bool(field_int(f, 4)),
        )
    if kind == "begin_block":
        return RequestBeginBlock(
            hash=field_bytes(f, 1),
            header=field_bytes(f, 2),
            last_commit_info=LastCommitInfo.decode(field_bytes(f, 3)),
            byzantine_validators=[ABCIEvidence.decode(raw) for raw in field_repeated_bytes(f, 4)],
        )
    if kind == "check_tx":
        return RequestCheckTx(tx=field_bytes(f, 1), type=field_int(f, 2))
    if kind == "deliver_tx":
        return RequestDeliverTx(tx=field_bytes(f, 1))
    if kind == "end_block":
        return RequestEndBlock(height=to_signed64(field_int(f, 1)))
    if kind == "offer_snapshot":
        return RequestOfferSnapshot(
            snapshot=Snapshot.decode(field_bytes(f, 1)) if 1 in f else None,
            app_hash=field_bytes(f, 2),
        )
    if kind == "load_snapshot_chunk":
        return RequestLoadSnapshotChunk(
            height=field_int(f, 1), format=field_int(f, 2), chunk=field_int(f, 3)
        )
    if kind == "apply_snapshot_chunk":
        return RequestApplySnapshotChunk(
            index=field_int(f, 1),
            chunk=field_bytes(f, 2),
            sender=field_bytes(f, 3).decode(),
        )
    raise ValueError(f"unknown request kind {kind}")


# -- payload codecs (response) ---------------------------------------------


def enc_response_payload(kind: str, resp) -> bytes:
    w = ProtoWriter()
    if kind == "exception":
        w.write_string(1, resp)
    elif kind == "echo":
        w.write_string(1, resp)
    elif kind == "flush":
        pass
    elif kind == "info":
        w.write_string(1, resp.data)
        w.write_string(2, resp.version)
        w.write_varint(3, resp.app_version)
        w.write_varint(4, resp.last_block_height)
        w.write_bytes(5, resp.last_block_app_hash)
    elif kind == "init_chain":
        w.write_message(1, resp.consensus_params)
        for v in resp.validators:
            w.write_message(2, v.encode(), always=True)
        w.write_bytes(3, resp.app_hash)
    elif kind == "query":
        w.write_varint(1, resp.code)
        w.write_string(3, resp.log)
        w.write_string(4, resp.info)
        w.write_varint(5, resp.index)
        w.write_bytes(6, resp.key)
        w.write_bytes(7, resp.value)
        w.write_message(8, resp.proof_ops)
        w.write_varint(9, resp.height)
        w.write_string(10, resp.codespace)
    elif kind == "begin_block":
        for e in resp.events:
            w.write_message(1, e.encode(), always=True)
    elif kind in ("check_tx", "deliver_tx"):
        w.write_varint(1, resp.code)
        w.write_bytes(2, resp.data)
        w.write_string(3, resp.log)
        w.write_string(4, resp.info)
        w.write_varint(5, resp.gas_wanted)
        w.write_varint(6, resp.gas_used)
        for e in resp.events:
            w.write_message(7, e.encode(), always=True)
        w.write_string(8, resp.codespace)
        if kind == "check_tx":
            w.write_string(9, resp.sender)
            w.write_varint(10, resp.priority)
            w.write_string(11, resp.mempool_error)
    elif kind == "end_block":
        for v in resp.validator_updates:
            w.write_message(1, v.encode(), always=True)
        w.write_message(2, resp.consensus_param_updates)
        for e in resp.events:
            w.write_message(3, e.encode(), always=True)
    elif kind == "commit":
        w.write_bytes(2, resp.data)
        w.write_varint(3, resp.retain_height)
    elif kind == "list_snapshots":
        for s in resp.snapshots:
            w.write_message(1, s.encode(), always=True)
    elif kind == "offer_snapshot":
        w.write_varint(1, resp.result)
    elif kind == "load_snapshot_chunk":
        w.write_bytes(1, resp.chunk)
    elif kind == "apply_snapshot_chunk":
        w.write_varint(1, resp.result)
        for c in resp.refetch_chunks:
            w.write_varint(2, c, always=True)
        for s in resp.reject_senders:
            w.write_string(3, s, always=True)
    else:
        raise ValueError(f"unknown response kind {kind}")
    return w.bytes()


def dec_response_payload(kind: str, data: bytes):
    f = decode_message(data)
    if kind == "exception":
        return field_bytes(f, 1).decode("utf-8", "replace")
    if kind == "echo":
        return field_bytes(f, 1).decode("utf-8", "replace")
    if kind == "flush":
        return None
    if kind == "info":
        return ResponseInfo(
            data=field_bytes(f, 1).decode(),
            version=field_bytes(f, 2).decode(),
            app_version=field_int(f, 3),
            last_block_height=to_signed64(field_int(f, 4)),
            last_block_app_hash=field_bytes(f, 5),
        )
    if kind == "init_chain":
        return ResponseInitChain(
            consensus_params=field_bytes(f, 1) if 1 in f else None,
            validators=[ValidatorUpdate.decode(raw) for raw in field_repeated_bytes(f, 2)],
            app_hash=field_bytes(f, 3),
        )
    if kind == "query":
        return ResponseQuery(
            code=field_int(f, 1),
            log=field_bytes(f, 3).decode(),
            info=field_bytes(f, 4).decode(),
            index=to_signed64(field_int(f, 5)),
            key=field_bytes(f, 6),
            value=field_bytes(f, 7),
            proof_ops=field_bytes(f, 8) if 8 in f else None,
            height=to_signed64(field_int(f, 9)),
            codespace=field_bytes(f, 10).decode(),
        )
    if kind == "begin_block":
        return ResponseBeginBlock(events=[Event.decode(raw) for raw in field_repeated_bytes(f, 1)])
    if kind in ("check_tx", "deliver_tx"):
        cls = ResponseCheckTx if kind == "check_tx" else ResponseDeliverTx
        resp = cls(
            code=field_int(f, 1),
            data=field_bytes(f, 2),
            log=field_bytes(f, 3).decode(),
            info=field_bytes(f, 4).decode(),
            gas_wanted=to_signed64(field_int(f, 5)),
            gas_used=to_signed64(field_int(f, 6)),
            events=[Event.decode(raw) for raw in field_repeated_bytes(f, 7)],
            codespace=field_bytes(f, 8).decode(),
        )
        if kind == "check_tx":
            resp.sender = field_bytes(f, 9).decode()
            resp.priority = to_signed64(field_int(f, 10))
            resp.mempool_error = field_bytes(f, 11).decode()
        return resp
    if kind == "end_block":
        return ResponseEndBlock(
            validator_updates=[ValidatorUpdate.decode(raw) for raw in field_repeated_bytes(f, 1)],
            consensus_param_updates=field_bytes(f, 2) if 2 in f else None,
            events=[Event.decode(raw) for raw in field_repeated_bytes(f, 3)],
        )
    if kind == "commit":
        return ResponseCommit(
            data=field_bytes(f, 2), retain_height=to_signed64(field_int(f, 3))
        )
    if kind == "list_snapshots":
        return ResponseListSnapshots(
            snapshots=[Snapshot.decode(raw) for raw in field_repeated_bytes(f, 1)]
        )
    if kind == "offer_snapshot":
        return ResponseOfferSnapshot(result=field_int(f, 1))
    if kind == "load_snapshot_chunk":
        return ResponseLoadSnapshotChunk(chunk=field_bytes(f, 1))
    if kind == "apply_snapshot_chunk":
        return ResponseApplySnapshotChunk(
            result=field_int(f, 1),
            refetch_chunks=[v for _, v in f.get(2, [])],
            reject_senders=[raw.decode() for raw in field_repeated_bytes(f, 3)],
        )
    raise ValueError(f"unknown response kind {kind}")
