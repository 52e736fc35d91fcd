"""The yardstick's own wire format: a Tendermint v0.35 commit as proto3
bytes and the canonical precommit sign-bytes, written out here so that
the benchmark's inputs do not depend on the program's encoders.

Layout (proto/tendermint/types/types.proto, canonical.proto; gogoproto
emission: ascending fields, zero scalars omitted, non-nullable embedded
messages always emitted, negative varints as 10-byte two's complement):

  Commit        1 height(varint) 2 round(varint) 3 block_id(msg) 4* sigs
  BlockID       1 hash 2 part_set_header(msg){1 total(varint) 2 hash}
  CommitSig     1 flag(varint) 2 validator_address 3 timestamp(msg) 4 sig
  Timestamp     1 seconds(varint) 2 nanos(varint)
  CanonicalVote 1 type 2 height(sfixed64) 3 round(sfixed64) 4 block_id
                5 timestamp(ALWAYS) 6 chain_id — uvarint length-prefixed

tests/benchmark holds these against the program's Commit.encode() and
Vote.sign_bytes() byte for byte.
"""

from __future__ import annotations

FLAG_ABSENT = 1
FLAG_COMMIT = 2
PRECOMMIT = 2
GO_ZERO_SECONDS = -62135596800  # Go's zero time.Time as a proto Timestamp
_U64 = (1 << 64) - 1


def uvarint(v: int) -> bytes:
    v &= _U64
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _varint(field: int, v: int) -> bytes:
    return b"" if v == 0 else uvarint(field << 3) + uvarint(v)


def _bytes(field: int, b: bytes, always: bool = False) -> bytes:
    if not b and not always:
        return b""
    return uvarint((field << 3) | 2) + uvarint(len(b)) + b


def timestamp(seconds: int, nanos: int = 0) -> bytes:
    return _varint(1, seconds) + _varint(2, nanos)


def block_id(digest: bytes, total: int = 1) -> bytes:
    """A BlockID whose part-set header carries the same digest; the
    canonical form has the same bytes (both headers are non-nullable)."""
    psh = _varint(1, total) + _bytes(2, digest)
    return _bytes(1, digest) + _bytes(2, psh, always=True)


def commit_sig(flag: int, address: bytes, seconds: int, nanos: int,
               sig: bytes) -> bytes:
    return (_varint(1, flag) + _bytes(2, address)
            + _bytes(3, timestamp(seconds, nanos), always=True)
            + _bytes(4, sig))


ABSENT_SIG = commit_sig(FLAG_ABSENT, b"", GO_ZERO_SECONDS, 0, b"")


def commit(height: int, digest: bytes, sig_records) -> bytes:
    """sig_records: encoded CommitSig messages, in validator order."""
    parts = [_varint(1, height), _bytes(3, block_id(digest), always=True)]
    parts += [_bytes(4, r, always=True) for r in sig_records]
    return b"".join(parts)


def sign_bytes_template(chain_id: str, height: int, digest: bytes):
    """(prefix, suffix) of a round-0 precommit's CanonicalVote: only the
    timestamp (field 5) differs between a commit's signatures."""
    prefix = (_varint(1, PRECOMMIT)
              + uvarint((2 << 3) | 1) + (height & _U64).to_bytes(8, "little")
              + _bytes(4, block_id(digest), always=True))
    return prefix, _bytes(6, chain_id.encode())


def sign_bytes(template, seconds: int, nanos: int = 0) -> bytes:
    prefix, suffix = template
    body = (prefix + _bytes(5, timestamp(seconds, nanos), always=True)
            + suffix)
    return uvarint(len(body)) + body
