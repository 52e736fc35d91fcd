"""Block, Header, Commit, CommitSig, BlockID — core chain data types.

Reference parity: types/block.go. Hashing is bit-exact:
- Header.hash: merkle root over 14 proto-encoded fields (block.go:448-483)
- Commit.hash: merkle root over proto-encoded CommitSigs (block.go:732-751)
- Data.hash: merkle root over raw txs (types/tx.go Txs.Hash)
- cdcEncode wrappers (types/encoding_helper.go): gogotypes
  {String,Int64,Bytes}Value with the value in field 1; empty -> nil leaf.
"""

from __future__ import annotations

from collections.abc import MutableSequence
from dataclasses import dataclass, field, replace
from typing import List, Optional

from ..crypto import merkle, tmhash
from ..wire import canonical as _canon
from ..wire.canonical import GO_ZERO_TIME_SECONDS, Timestamp
from ..wire.proto import (
    WT_BYTES,
    WT_VARINT,
    ProtoWriter,
    decode_message,
    field_bytes,
    field_int,
    field_repeated_bytes,
    iter_fields,
    to_signed32,
    to_signed64,
)

MAX_HEADER_BYTES = 626  # types/block.go:570
BLOCK_ID_FLAG_ABSENT = 1
BLOCK_ID_FLAG_COMMIT = 2
BLOCK_ID_FLAG_NIL = 3

MAX_SIGNATURE_SIZE = 64  # ed25519/sr25519; secp256k1 is <= 72 (types/vote.go:24)


def cdc_encode_string(s: str) -> bytes:
    if not s:
        return b""
    w = ProtoWriter()
    w.write_string(1, s)
    return w.bytes()


def cdc_encode_int64(v: int) -> bytes:
    if not v:
        return b""
    w = ProtoWriter()
    w.write_varint(1, v)
    return w.bytes()


def cdc_encode_bytes(b: bytes) -> bytes:
    if not b:
        return b""
    w = ProtoWriter()
    w.write_bytes(1, b)
    return w.bytes()


@dataclass(frozen=True)
class Version:
    """Consensus version (proto/tendermint/version, version/version.go)."""

    block: int = 11  # version.BlockProtocol (version/version.go:25)
    app: int = 0

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.block)
        w.write_varint(2, self.app)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Version":
        f = decode_message(data)
        return cls(block=field_int(f, 1), app=field_int(f, 2))


@dataclass(frozen=True)
class PartSetHeader:
    total: int = 0
    hash: bytes = b""

    def is_zero(self) -> bool:
        return self.total == 0 and not self.hash

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.total)
        w.write_bytes(2, self.hash)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "PartSetHeader":
        f = decode_message(data)
        return cls(total=field_int(f, 1), hash=field_bytes(f, 2))

    def validate_basic(self) -> None:
        if self.hash and len(self.hash) != tmhash.SIZE:
            raise ValueError("wrong PartSetHeader hash size")


@dataclass(frozen=True)
class BlockID:
    hash: bytes = b""
    part_set_header: PartSetHeader = field(default_factory=PartSetHeader)

    def is_zero(self) -> bool:
        return not self.hash and self.part_set_header.is_zero()

    def is_complete(self) -> bool:
        """ValidateBasic-completeness (types/block.go:1153): hash and part
        set header both fully set."""
        return (
            len(self.hash) == tmhash.SIZE
            and self.part_set_header.total > 0
            and len(self.part_set_header.hash) == tmhash.SIZE
        )

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_bytes(1, self.hash)
        w.write_message(2, self.part_set_header.encode(), always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "BlockID":
        f = decode_message(data)
        return cls(
            hash=field_bytes(f, 1),
            part_set_header=PartSetHeader.decode(field_bytes(f, 2)),
        )

    def canonical(self) -> Optional[_canon.CanonicalBlockID]:
        """types/canonical.go CanonicalizeBlockID: nil for the zero ID."""
        if self.is_zero():
            return None
        return _canon.CanonicalBlockID(
            hash=self.hash,
            part_set_header=_canon.CanonicalPartSetHeader(
                total=self.part_set_header.total,
                hash=self.part_set_header.hash,
            ),
        )

    def validate_basic(self) -> None:
        if self.hash and len(self.hash) != tmhash.SIZE:
            raise ValueError("wrong BlockID hash size")
        self.part_set_header.validate_basic()

    def key(self) -> bytes:
        """Map key (types/block.go BlockID.Key)."""
        return self.hash + self.part_set_header.encode()


ZERO_BLOCK_ID = BlockID()


@dataclass(frozen=True)
class Header:
    """types/block.go:370-412."""

    version: Version = field(default_factory=Version)
    chain_id: str = ""
    height: int = 0
    time: Timestamp = field(default_factory=Timestamp.zero)
    last_block_id: BlockID = field(default_factory=BlockID)
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    validators_hash: bytes = b""
    next_validators_hash: bytes = b""
    consensus_hash: bytes = b""
    app_hash: bytes = b""
    last_results_hash: bytes = b""
    evidence_hash: bytes = b""
    proposer_address: bytes = b""
    # memoized merkle root: the class is FROZEN so the 14-leaf tree can
    # never change under a live instance, and init=False makes
    # dataclasses.replace() re-default the memo to None (a forged-header
    # copy must never inherit the original's hash). compare=False keeps
    # __eq__/__hash__ on the real fields.
    _hash_memo: Optional[bytes] = field(
        default=None, init=False, repr=False, compare=False
    )

    def hash(self) -> bytes:
        """Merkle root of proto-encoded fields (types/block.go:448-483).
        Returns b"" when the header is incomplete (nil in Go)."""
        if not self.validators_hash:
            return b""
        h = self._hash_memo
        if h is not None:
            return h
        h = merkle.hash_from_byte_slices(
            [
                self.version.encode(),
                cdc_encode_string(self.chain_id),
                cdc_encode_int64(self.height),
                _canon.encode_timestamp(self.time),
                self.last_block_id.encode(),
                cdc_encode_bytes(self.last_commit_hash),
                cdc_encode_bytes(self.data_hash),
                cdc_encode_bytes(self.validators_hash),
                cdc_encode_bytes(self.next_validators_hash),
                cdc_encode_bytes(self.consensus_hash),
                cdc_encode_bytes(self.app_hash),
                cdc_encode_bytes(self.last_results_hash),
                cdc_encode_bytes(self.evidence_hash),
                cdc_encode_bytes(self.proposer_address),
            ]
        )
        object.__setattr__(self, "_hash_memo", h)
        return h

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_message(1, self.version.encode(), always=True)
        w.write_string(2, self.chain_id)
        w.write_varint(3, self.height)
        w.write_message(4, _canon.encode_timestamp(self.time), always=True)
        w.write_message(5, self.last_block_id.encode(), always=True)
        w.write_bytes(6, self.last_commit_hash)
        w.write_bytes(7, self.data_hash)
        w.write_bytes(8, self.validators_hash)
        w.write_bytes(9, self.next_validators_hash)
        w.write_bytes(10, self.consensus_hash)
        w.write_bytes(11, self.app_hash)
        w.write_bytes(12, self.last_results_hash)
        w.write_bytes(13, self.evidence_hash)
        w.write_bytes(14, self.proposer_address)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Header":
        f = decode_message(data)
        ts_f = decode_message(field_bytes(f, 4))
        return cls(
            version=Version.decode(field_bytes(f, 1)),
            chain_id=field_bytes(f, 2).decode("utf-8"),
            height=to_signed64(field_int(f, 3)),
            time=Timestamp(
                seconds=to_signed64(field_int(ts_f, 1)),
                nanos=to_signed32(field_int(ts_f, 2)),
            ),
            last_block_id=BlockID.decode(field_bytes(f, 5)),
            last_commit_hash=field_bytes(f, 6),
            data_hash=field_bytes(f, 7),
            validators_hash=field_bytes(f, 8),
            next_validators_hash=field_bytes(f, 9),
            consensus_hash=field_bytes(f, 10),
            app_hash=field_bytes(f, 11),
            last_results_hash=field_bytes(f, 12),
            evidence_hash=field_bytes(f, 13),
            proposer_address=field_bytes(f, 14),
        )

    def validate_basic(self) -> None:
        """types/block.go:413-446."""
        if len(self.chain_id) > 50:
            raise ValueError("chain_id is too long")
        if self.height < 0:
            raise ValueError("negative Height")
        if self.height == 0:
            raise ValueError("zero Height")
        self.last_block_id.validate_basic()
        for name, h in (
            ("last_commit_hash", self.last_commit_hash),
            ("data_hash", self.data_hash),
            ("evidence_hash", self.evidence_hash),
            ("last_results_hash", self.last_results_hash),
            ("validators_hash", self.validators_hash),
            ("next_validators_hash", self.next_validators_hash),
            ("consensus_hash", self.consensus_hash),
        ):
            if h and len(h) != tmhash.SIZE:
                raise ValueError(f"wrong {name} size")
        if self.proposer_address and len(self.proposer_address) != tmhash.TRUNCATED_SIZE:
            raise ValueError("invalid proposer_address size")


@dataclass(frozen=True)
class CommitSig:
    """types/block.go:590-700."""

    block_id_flag: int = BLOCK_ID_FLAG_ABSENT
    validator_address: bytes = b""
    timestamp: Timestamp = field(default_factory=Timestamp.zero)
    signature: bytes = b""

    @classmethod
    def absent(cls) -> "CommitSig":
        return cls(block_id_flag=BLOCK_ID_FLAG_ABSENT)

    def for_block(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_COMMIT

    def is_absent(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """The vote's BlockID implied by the flag (types/block.go:685-700)."""
        if self.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            return BlockID()
        if self.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            return commit_block_id
        if self.block_id_flag == BLOCK_ID_FLAG_NIL:
            return BlockID()
        raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.block_id_flag)
        w.write_bytes(2, self.validator_address)
        w.write_message(3, _canon.encode_timestamp(self.timestamp), always=True)
        w.write_bytes(4, self.signature)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "CommitSig":
        f = decode_message(data)
        ts_f = decode_message(field_bytes(f, 3))
        return cls(
            block_id_flag=field_int(f, 1),
            validator_address=field_bytes(f, 2),
            timestamp=Timestamp(
                seconds=to_signed64(field_int(ts_f, 1)),
                nanos=to_signed32(field_int(ts_f, 2)),
            ),
            signature=field_bytes(f, 4),
        )

    def validate_basic(self) -> None:
        """types/block.go:702-741."""
        if self.block_id_flag not in (
            BLOCK_ID_FLAG_ABSENT,
            BLOCK_ID_FLAG_COMMIT,
            BLOCK_ID_FLAG_NIL,
        ):
            raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")
        if self.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            if self.validator_address:
                raise ValueError("validator address is present for absent CommitSig")
            if not self.timestamp.is_zero():
                raise ValueError("time is present for absent CommitSig")
            if self.signature:
                raise ValueError("signature is present for absent CommitSig")
        else:
            if len(self.validator_address) != tmhash.TRUNCATED_SIZE:
                raise ValueError("expected ValidatorAddress size")
            if not self.signature:
                raise ValueError("signature is missing")
            if len(self.signature) > MAX_SIGNATURE_SIZE:
                raise ValueError("signature is too big")


class CommitSigs(MutableSequence):
    """`commit.signatures` backed by a columnar CommitBlock
    (ops/entry_block.py): the columns are the source of truth from wire
    decode onward, and CommitSig OBJECTS are materialized lazily, one per
    accessed index, as views over them. The verify hot path
    (types/validation.py fused branch) reads the columns directly and
    never triggers materialization.

    Mutation (setitem/delitem/insert) first materializes every lane into
    a plain object list and DETACHES the columns — the mutated list is
    then the truth and the owning Commit rebuilds its block on demand —
    so list semantics (including the tests' in-place signature tampering)
    are preserved exactly."""

    __slots__ = ("_block", "_items")

    def __init__(self, block):
        self._block = block
        self._items: list = [None] * len(block)

    # -- lazy view ------------------------------------------------------

    def _materialize(self, i: int) -> CommitSig:
        cs = self._items[i]
        if cs is None:
            b = self._block
            flag = int(b.flags[i])
            if flag == BLOCK_ID_FLAG_ABSENT:
                cs = CommitSig(block_id_flag=flag)
            else:
                cs = CommitSig(
                    block_id_flag=flag,
                    validator_address=b.addr[i].tobytes(),
                    timestamp=Timestamp(
                        int(b.ts_seconds[i]), int(b.ts_nanos[i])
                    ),
                    signature=b.sig[i].tobytes(),
                )
            self._items[i] = cs
        return cs

    def _detach(self) -> None:
        """Materialize everything and drop the columns (mutation path)."""
        if self._block is None:
            return
        for i in range(len(self._items)):
            self._materialize(i)
        self._block = None

    def block(self):
        """The backing CommitBlock, or None once mutated."""
        return self._block

    # -- sequence protocol ---------------------------------------------

    def __len__(self) -> int:
        return len(self._items)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [
                self._materialize(j)
                for j in range(*i.indices(len(self._items)))
            ]
        if self._items[i] is None:  # also validates the index
            if i < 0:
                i += len(self._items)
            return self._materialize(i)
        return self._items[i]

    def __setitem__(self, i, value) -> None:
        self._detach()
        self._items[i] = value

    def __delitem__(self, i) -> None:
        self._detach()
        del self._items[i]

    def insert(self, i, value) -> None:
        self._detach()
        self._items.insert(i, value)

    def __eq__(self, other) -> bool:
        if isinstance(other, CommitSigs):
            return list(self) == list(other)
        if isinstance(other, list):
            return list(self) == other
        return NotImplemented

    def __repr__(self) -> str:
        return repr(list(self))


def _commit_sig_columns(sigs) -> Optional[object]:
    """Build a CommitBlock from CommitSig objects — the path for commits
    assembled in-process (consensus MakeCommit, tests). Returns None when
    any lane deviates from the canonical shape (wrong-size address or
    signature, unknown flag, absent lane with data): those commits keep
    the object path and its exact error behavior."""
    import numpy as np

    from ..ops.entry_block import CommitBlock

    n = len(sigs)
    if n == 0:
        return None
    flags = []
    sig_chunks = []
    addr_chunks = []
    secs = []
    nanos = []
    for cs in sigs:
        f = cs.block_id_flag
        if f == BLOCK_ID_FLAG_ABSENT:
            if (
                cs.validator_address
                or cs.signature
                or not cs.timestamp.is_zero()
            ):
                return None
            sig_chunks.append(_ZERO64)
            addr_chunks.append(_ZERO20)
        elif f in (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL):
            if len(cs.validator_address) != 20 or len(cs.signature) != 64:
                return None
            sig_chunks.append(cs.signature)
            addr_chunks.append(cs.validator_address)
        else:
            return None
        flags.append(f)
        secs.append(cs.timestamp.seconds)
        nanos.append(cs.timestamp.nanos)
    return CommitBlock(
        flags=np.array(flags, dtype=np.uint8),
        val_idx=np.arange(n, dtype=np.int32),
        sig=np.frombuffer(b"".join(sig_chunks), dtype=np.uint8).reshape(
            n, 64
        ),
        ts_seconds=np.array(secs, dtype=np.int64),
        ts_nanos=np.array(nanos, dtype=np.int32),
        addr=np.frombuffer(b"".join(addr_chunks), dtype=np.uint8).reshape(
            n, 20
        ),
    )


_ZERO64 = bytes(64)
_ZERO20 = bytes(20)

_KNOWN_FLAGS = (BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL)


class _NonCanonical(Exception):
    """Wire record deviates from the canonical CommitSig shape."""


def _decode_sig_record(raw: bytes):
    """One CommitSig wire record -> (flag, addr, secs, nanos, sig),
    canonical-shape-checked. Raises _NonCanonical on ANY deviation
    (unknown/duplicate fields, wrong wire types, non-canonical lane
    shape) — the caller falls back to CommitSig.decode per record, which
    reproduces the object path's exact tolerance and errors."""
    flag = 0
    addr = b""
    sig = b""
    ts_raw = None
    seen = 0
    for f, wt, val in iter_fields(raw):
        bit = 1 << f
        if seen & bit:
            raise _NonCanonical
        seen |= bit
        if f == 1 and wt == WT_VARINT:
            flag = val
        elif f == 2 and wt == WT_BYTES:
            addr = val
        elif f == 3 and wt == WT_BYTES:
            ts_raw = val
        elif f == 4 and wt == WT_BYTES:
            sig = val
        else:
            raise _NonCanonical
    secs = 0
    nanos = 0
    if ts_raw is not None:
        seen_ts = 0
        for f, wt, val in iter_fields(ts_raw):
            if wt != WT_VARINT or f not in (1, 2) or seen_ts & (1 << f):
                raise _NonCanonical
            seen_ts |= 1 << f
            if f == 1:
                secs = to_signed64(val)
            else:
                nanos = to_signed32(val)
    if flag == BLOCK_ID_FLAG_ABSENT:
        if addr or sig or secs != GO_ZERO_TIME_SECONDS or nanos != 0:
            raise _NonCanonical
    elif flag in (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL):
        if len(addr) != 20 or len(sig) != 64:
            raise _NonCanonical
    else:
        raise _NonCanonical
    return flag, addr, secs, nanos, sig


def _decode_commit_sigs(raws: List[bytes]):
    """Decode a commit's signature records COLUMNAR-FIRST: one pass fills
    CommitBlock columns and the result is a lazy CommitSigs view. Any
    non-canonical record falls the whole commit back to plain CommitSig
    objects (identical to the pre-columnar decode).

    This walk, with _decode_sig_record under it, is the SPECIFICATION of
    the canonical shape and of everything off it (tolerance, fallback,
    exception types and messages). The fast path — native/tm_native.cpp
    commit_decode_columns, tried first by Commit.decode — takes a subset
    of the canonical inputs, must give these columns for it, and hands
    every other input back here."""
    n = len(raws)
    if n == 0:
        return []
    try:
        rows = [_decode_sig_record(raw) for raw in raws]
    except (_NonCanonical, ValueError):
        return [CommitSig.decode(raw) for raw in raws]
    import numpy as np

    from ..ops.entry_block import CommitBlock

    block = CommitBlock(
        flags=np.fromiter((r[0] for r in rows), dtype=np.uint8, count=n),
        val_idx=np.arange(n, dtype=np.int32),
        sig=np.frombuffer(
            b"".join(r[4] or _ZERO64 for r in rows), dtype=np.uint8
        ).reshape(n, 64),
        ts_seconds=np.fromiter(
            (r[2] for r in rows), dtype=np.int64, count=n
        ),
        ts_nanos=np.fromiter((r[3] for r in rows), dtype=np.int32, count=n),
        addr=np.frombuffer(
            b"".join(r[1] or _ZERO20 for r in rows), dtype=np.uint8
        ).reshape(n, 20),
    )
    return CommitSigs(block)


_OPS = None


def _native_commit_columns(data):
    """The native parse of a Commit's wire bytes (Commit.decode's fast
    path): its column tuple, or None where the module is absent or the
    input is off the canonical shape. Counts the commit under the path
    that decodes it."""
    global _OPS
    from .. import native as _native

    cols = _native.columns("commit_decode_columns", data)
    if _OPS is None:
        from ..libs import metrics as _metrics

        _OPS = _metrics.ops_metrics()
    _OPS.commit_decodes.inc(path="python" if cols is None else "native")
    return cols


def _commit_sigs_from_columns(n, flags, sig, secs, nanos, addr):
    """commit_decode_columns' buffers as `signatures`: the CommitBlock
    _decode_commit_sigs builds (same dtypes and shapes; read-only views
    of the buffers), behind a lazy CommitSigs view."""
    if n == 0:
        return []
    import numpy as np

    from ..ops.entry_block import CommitBlock

    return CommitSigs(
        CommitBlock(
            flags=np.frombuffer(flags, dtype=np.uint8),
            val_idx=np.arange(n, dtype=np.int32),
            sig=np.frombuffer(sig, dtype=np.uint8).reshape(n, 64),
            ts_seconds=np.frombuffer(secs, dtype=np.int64),
            ts_nanos=np.frombuffer(nanos, dtype=np.int32),
            addr=np.frombuffer(addr, dtype=np.uint8).reshape(n, 20),
        )
    )


@dataclass
class Commit:
    """types/block.go:744-830."""

    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    signatures: List[CommitSig] = field(default_factory=list)

    _hash: Optional[bytes] = field(default=None, repr=False, compare=False)
    _sb_tpl: Optional[dict] = field(default=None, repr=False, compare=False)

    def __setattr__(self, name, value):
        # reassigning `signatures` invalidates the signature-dependent
        # hash — the tests' wholesale `commit.signatures = [...]`
        # replacement stays correct
        object.__setattr__(self, name, value)
        if name == "signatures":
            object.__setattr__(self, "_hash", None)

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [cs.encode() for cs in self.signatures]
            )
        return self._hash

    def size(self) -> int:
        return len(self.signatures)

    def sign_bytes_template(self, chain_id: str, flag: int) -> tuple:
        """(prefix, suffix) canonical-vote template for a BlockIDFlag —
        only the timestamp differs across a commit's signatures for a
        given flag, so the constant fields are encoded once per
        (chain_id, flag) and reused. The columnar fused prep
        (ops/commit_prep.py) composes every lane's sign bytes from these
        templates plus the timestamp columns."""
        if self._sb_tpl is None:
            self._sb_tpl = {}
        key = (chain_id, flag)
        tpl = self._sb_tpl.get(key)
        if tpl is None:
            # the vote's BlockID implied by the flag (CommitSig.block_id):
            # the commit's for COMMIT, the zero BlockID for ABSENT/NIL
            if flag == BLOCK_ID_FLAG_COMMIT:
                bid = self.block_id
            elif flag in (BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_NIL):
                bid = BlockID()
            else:
                raise ValueError(f"unknown BlockIDFlag: {flag}")
            tpl = _canon.canonical_vote_template(
                chain_id=chain_id,
                msg_type=_canon.SIGNED_MSG_TYPE_PRECOMMIT,
                height=self.height,
                round_=self.round,
                block_id=bid.canonical(),
            )
            self._sb_tpl[key] = tpl
        return tpl

    def vote_sign_bytes(self, chain_id: str, idx: int) -> bytes:
        """Canonical sign bytes of the vote at idx (types/block.go:816-819).

        Only the timestamp differs across a commit's signatures (for a
        given BlockIDFlag), so the constant fields are encoded once per
        (chain_id, flag) and reused — the 10k-signature batch path walks
        this for every lane."""
        cs = self.signatures[idx]
        tpl = self.sign_bytes_template(chain_id, cs.block_id_flag)
        return _canon.compose_vote_sign_bytes(tpl, cs.timestamp)

    def commit_block(self):
        """The commit's columnar CommitBlock (ops/entry_block.py), or
        None when the signatures deviate from the canonical shape.

        Wire-decoded commits carry their block from decode (the
        signatures list is a lazy CommitSigs view over it — zero cost
        here, and mutating the view detaches it, so the columns can
        never go stale). Commits assembled from objects in-process
        build columns FRESH on every call — deliberately uncached:
        `commit.signatures[i] = ...` on a plain list has no hook, so a
        cache here would let a mutated (tampered) signature verify
        against the pre-mutation bytes. The object build is a single
        O(n) pass (~4 ms at 10k lanes); the wire path — the hot one —
        never pays it."""
        sigs = self.signatures
        if isinstance(sigs, CommitSigs):
            blk = sigs.block()
            if blk is not None:
                return blk
        return _commit_sig_columns(sigs)

    def vote_sign_bytes_many(self, chain_id: str, idxs) -> list:
        """Batch form of vote_sign_bytes: one native compose call for all
        requested lanes (the pure-Python composer is ~27us/sig, which was
        the host bottleneck of pipelined header sync at 128 vals/header).
        Falls back to the per-index path without the native module or for
        mixed BlockIDFlags."""
        idxs = list(idxs)
        if len(idxs) >= 8:
            flag = self.signatures[idxs[0]].block_id_flag
            if all(self.signatures[i].block_id_flag == flag for i in idxs):
                from ..native import load as _load_native

                native = _load_native()
                if native is not None and hasattr(native, "vote_sign_bytes_batch"):
                    # materialize the (chain_id, flag) template via the
                    # single-lane path once
                    self.vote_sign_bytes(chain_id, idxs[0])
                    prefix, suffix = self._sb_tpl[(chain_id, flag)]
                    import struct as _struct

                    times = b"".join(
                        _struct.pack(
                            "<qq",
                            self.signatures[i].timestamp.seconds,
                            self.signatures[i].timestamp.nanos,
                        )
                        for i in idxs
                    )
                    return native.vote_sign_bytes_batch(prefix, suffix, times)
        return [self.vote_sign_bytes(chain_id, i) for i in idxs]

    def vote_sign_bytes_block(self, chain_id: str, idxs) -> tuple:
        """Buffer-writing variant of vote_sign_bytes_many: every requested
        lane's sign bytes composed into ONE contiguous buffer + an
        (len(idxs)+1,) int64 offset table — the columnar EntryBlock msgs
        form (ops/entry_block.py). The native composer fills the buffer in
        a single GIL-released call; the pure-Python fallback is
        byte-identical (wire/canonical.compose_vote_sign_bytes_block)."""
        import numpy as np

        idxs = list(idxs)
        n = len(idxs)
        if n == 0:
            return b"", np.zeros(1, dtype=np.int64)
        flag = self.signatures[idxs[0]].block_id_flag
        if all(self.signatures[i].block_id_flag == flag for i in idxs):
            # materialize the (chain_id, flag) template via the
            # single-lane path once
            self.vote_sign_bytes(chain_id, idxs[0])
            prefix, suffix = self._sb_tpl[(chain_id, flag)]
            from ..native import load as _load_native

            native = _load_native()
            if native is not None and hasattr(
                native, "vote_sign_bytes_batch_buf"
            ):
                import struct as _struct

                times = b"".join(
                    _struct.pack(
                        "<qq",
                        self.signatures[i].timestamp.seconds,
                        self.signatures[i].timestamp.nanos,
                    )
                    for i in idxs
                )
                buf, offs = native.vote_sign_bytes_batch_buf(
                    prefix, suffix, times
                )
                return buf, np.frombuffer(offs, dtype=np.int64)
            return _canon.compose_vote_sign_bytes_block(
                (prefix, suffix),
                [self.signatures[i].timestamp for i in idxs],
            )
        # mixed BlockIDFlags (never a single commit's for-block set, but
        # the API allows it): per-index compose, one join
        chunks = [self.vote_sign_bytes(chain_id, i) for i in idxs]
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(c) for c in chunks], out=offsets[1:])
        return b"".join(chunks), offsets

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.height)
        w.write_varint(2, self.round)
        w.write_message(3, self.block_id.encode(), always=True)
        for cs in self.signatures:
            w.write_message(4, cs.encode(), always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Commit":
        """Columnar-from-decode: canonical-shaped signature records parse
        straight into CommitBlock columns (ONE pass, no CommitSig or
        Timestamp objects); `signatures` is a lazy view over them. A
        non-canonical commit decodes to plain objects as before.

        Two paths, one result. The FAST path is native/tm_native.cpp
        commit_decode_columns: the whole message walked once with the GIL
        released, every record parsed and shape-checked on every call,
        nothing kept between calls. It answers None for any input off the
        canonical shape (and is absent under TM_TPU_NO_NATIVE or a failed
        build); then the Python walk below runs — the SPECIFICATION, which
        alone decides tolerance, the plain-CommitSig fallback and every
        exception. ops_stats() counts the commits each path decoded
        (commit_decode_native / commit_decode_python)."""
        cols = _native_commit_columns(data)
        if cols is not None:
            height, round_, block_id_raw, n, flags, sig, secs, nanos, addr = cols
            return cls(
                height=height,
                round=round_,
                block_id=BlockID.decode(block_id_raw),
                signatures=_commit_sigs_from_columns(
                    n, flags, sig, secs, nanos, addr
                ),
            )
        f = decode_message(data)
        sigs = _decode_commit_sigs(field_repeated_bytes(f, 4))
        return cls(
            height=to_signed64(field_int(f, 1)),
            round=to_signed32(field_int(f, 2)),
            block_id=BlockID.decode(field_bytes(f, 3)),
            signatures=sigs,
        )

    def validate_basic(self) -> None:
        """types/block.go:779-800."""
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if self.height >= 1:
            if self.block_id.is_zero():
                raise ValueError("commit cannot be for nil block")
            if not self.signatures:
                raise ValueError("no signatures in commit")
            for i, cs in enumerate(self.signatures):
                try:
                    cs.validate_basic()
                except ValueError as e:
                    raise ValueError(f"wrong CommitSig #{i}: {e}") from e


BLS_AGG_SIGNATURE_SIZE = 96  # compressed G2 (min-pubkey BLS12-381)


@dataclass
class AggregatedCommit:
    """BLS12-381 aggregated commit (ISSUE 20): the committee's V
    per-validator precommit signatures collapse into ONE compressed G2
    aggregate plus a signer bitmap — 96 bytes + ceil(V/8) on the wire
    instead of V x (64-byte signature + address + timestamp). This is
    the committee-scale wire diet of "Performance of EdDSA and BLS
    Signatures in Committee-Based Consensus" (arXiv 2302.00418).

    Every signer signs the SAME canonical precommit: the per-signature
    timestamp is dropped (Timestamp.zero() in the canonical vote), which
    is exactly what makes the signatures aggregatable — EdDSA commits
    carry per-signature timestamps, so each validator signs a DIFFERENT
    message and nothing aggregates. The zero-timestamp tradeoff (no
    median-time from commits) is the paper's documented cost.

    Wire framing (local extension — upstream tendermint has no
    aggregated commit message):

        1 height (varint)   2 round (varint)   3 block_id (message)
        4 signature (bytes) 5 signers (BitArray message)
    """

    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    signature: bytes = b""
    signers: Optional["BitArray"] = None  # libs/bits.BitArray

    def sign_bytes(self, chain_id: str) -> bytes:
        """The ONE message every signer signed: the canonical precommit
        with the zero timestamp."""
        tpl = _canon.canonical_vote_template(
            chain_id=chain_id,
            msg_type=_canon.SIGNED_MSG_TYPE_PRECOMMIT,
            height=self.height,
            round_=self.round,
            block_id=self.block_id.canonical(),
        )
        return _canon.compose_vote_sign_bytes(tpl, Timestamp.zero())

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_varint(1, self.height)
        w.write_varint(2, self.round)
        w.write_message(3, self.block_id.encode(), always=True)
        w.write_bytes(4, self.signature)
        if self.signers is not None:
            w.write_message(5, self.signers.encode(), always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "AggregatedCommit":
        from ..libs.bits import BitArray

        f = decode_message(data)
        signers = None
        if 5 in f:
            signers = BitArray.decode(field_bytes(f, 5))
        return cls(
            height=to_signed64(field_int(f, 1)),
            round=to_signed32(field_int(f, 2)),
            block_id=BlockID.decode(field_bytes(f, 3)),
            signature=field_bytes(f, 4),
            signers=signers,
        )

    def validate_basic(self) -> None:
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if self.height >= 1:
            if self.block_id.is_zero():
                raise ValueError("commit cannot be for nil block")
            if len(self.signature) != BLS_AGG_SIGNATURE_SIZE:
                raise ValueError(
                    "aggregate signature is "
                    f"{len(self.signature)} bytes, want "
                    f"{BLS_AGG_SIGNATURE_SIZE}"
                )
            if self.signers is None or self.signers.size() == 0:
                raise ValueError("no signer bitmap in aggregated commit")


@dataclass
class Data:
    """Block transactions (types/block.go Data)."""

    txs: List[bytes] = field(default_factory=list)
    _hash: Optional[bytes] = field(default=None, repr=False, compare=False)

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(list(self.txs))
        return self._hash

    def encode(self) -> bytes:
        w = ProtoWriter()
        for tx in self.txs:
            w.write_bytes(1, tx, always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Data":
        f = decode_message(data)
        return cls(txs=field_repeated_bytes(f, 1))


@dataclass
class Block:
    """types/block.go:37-67 (evidence carried as raw encoded list for now;
    typed evidence lands with types/evidence.py)."""

    header: Header = field(default_factory=Header)
    data: Data = field(default_factory=Data)
    evidence: List[bytes] = field(default_factory=list)  # encoded Evidence msgs
    last_commit: Optional[Commit] = None

    def hash(self) -> bytes:
        return self.header.hash()

    def hash_evidence(self) -> bytes:
        return merkle.hash_from_byte_slices(list(self.evidence))

    def fill_header(self) -> None:
        """types/block.go:108-124: populate derived header hashes."""
        h = self.header
        updates = {}
        if not h.last_commit_hash and self.last_commit is not None:
            updates["last_commit_hash"] = self.last_commit.hash()
        if not h.data_hash:
            updates["data_hash"] = self.data.hash()
        if not h.evidence_hash:
            updates["evidence_hash"] = self.hash_evidence()
        if updates:
            self.header = replace(h, **updates)

    def encode(self) -> bytes:
        w = ProtoWriter()
        w.write_message(1, self.header.encode(), always=True)
        w.write_message(2, self.data.encode(), always=True)
        ev = ProtoWriter()
        for e in self.evidence:
            ev.write_message(1, e, always=True)
        w.write_message(3, ev.bytes(), always=True)
        if self.last_commit is not None:
            w.write_message(4, self.last_commit.encode())
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Block":
        f = decode_message(data)
        ev_f = decode_message(field_bytes(f, 3))
        return cls(
            header=Header.decode(field_bytes(f, 1)),
            data=Data.decode(field_bytes(f, 2)),
            evidence=field_repeated_bytes(ev_f, 1),
            last_commit=Commit.decode(field_bytes(f, 4)) if 4 in f else None,
        )

    def validate_basic(self) -> None:
        """types/block.go:69-106."""
        self.header.validate_basic()
        if self.last_commit is not None:
            self.last_commit.validate_basic()
            if self.header.last_commit_hash != self.last_commit.hash():
                raise ValueError("wrong last_commit_hash")
        elif self.header.height > 1:
            raise ValueError("nil LastCommit")
        if self.header.data_hash != self.data.hash():
            raise ValueError("wrong data_hash")
        if self.header.evidence_hash != self.hash_evidence():
            raise ValueError("wrong evidence_hash")


@dataclass(frozen=True)
class SignedHeader:
    """Header + the commit that signed it (types/block.go:833-890)."""

    header: Optional[Header] = None
    commit: Optional[Commit] = None

    def encode(self) -> bytes:
        """tendermint.types.SignedHeader: 1 header, 2 commit."""
        w = ProtoWriter()
        if self.header is not None:
            w.write_message(1, self.header.encode(), always=True)
        if self.commit is not None:
            w.write_message(2, self.commit.encode(), always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "SignedHeader":
        """A field the bytes lack decodes to None (a nil pointer in Go),
        which validate_basic names; the commit rides Commit.decode's
        native column pass."""
        f = decode_message(data)
        return cls(
            header=Header.decode(field_bytes(f, 1)) if 1 in f else None,
            commit=Commit.decode(field_bytes(f, 2)) if 2 in f else None,
        )

    def validate_basic(self, chain_id: str) -> None:
        if self.header is None:
            raise ValueError("missing header")
        if self.commit is None:
            raise ValueError("missing commit")
        self.header.validate_basic()
        self.commit.validate_basic()
        if self.header.chain_id != chain_id:
            raise ValueError(
                f"header belongs to another chain {self.header.chain_id!r}, not {chain_id!r}"
            )
        if self.header.height != self.commit.height:
            raise ValueError("header and commit height mismatch")
        if self.header.hash() != self.commit.block_id.hash:
            raise ValueError("commit signs a header other than this one")
