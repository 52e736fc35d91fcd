"""Canonical sign-bytes construction.

Reference parity: types/canonical.go (CanonicalizeVote/Proposal/BlockID),
proto/tendermint/types/canonical.proto, and the generated marshalers in
canonical.pb.go:370-567. The resulting byte strings are what validators
ed25519-sign; they must match the reference bit-for-bit.

Encoded layout (gogoproto emission rules, see wire/proto.py docstring):
  CanonicalVote:     1 type(varint) 2 height(sfixed64) 3 round(sfixed64)
                     4 block_id(msg, nil-omitted) 5 timestamp(msg, ALWAYS)
                     6 chain_id(string)
  CanonicalProposal: 1 type 2 height 3 round 4 pol_round(varint)
                     5 block_id 6 timestamp(ALWAYS) 7 chain_id
  CanonicalBlockID:  1 hash(bytes) 2 part_set_header(msg, ALWAYS)
  CanonicalPartSetHeader: 1 total(varint) 2 hash(bytes)
  Timestamp:         1 seconds(varint int64) 2 nanos(varint int32)

The whole message is uvarint length-prefixed (types/vote.go:93-95,
protoio MarshalDelimited) — kept for hardware-signer compatibility.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

from .proto import ProtoWriter, marshal_delimited

# SignedMsgType enum (proto/tendermint/types/types.pb.go:70-87)
SIGNED_MSG_TYPE_UNKNOWN = 0
SIGNED_MSG_TYPE_PREVOTE = 1
SIGNED_MSG_TYPE_PRECOMMIT = 2
SIGNED_MSG_TYPE_PROPOSAL = 32

# Go's zero time.Time (0001-01-01T00:00:00Z) as a proto Timestamp.
GO_ZERO_TIME_SECONDS = -62135596800


class Timestamp(NamedTuple):
    """google.protobuf.Timestamp value; Go zero time is the zero() value."""

    seconds: int = GO_ZERO_TIME_SECONDS
    nanos: int = 0

    @classmethod
    def zero(cls) -> "Timestamp":
        return cls(GO_ZERO_TIME_SECONDS, 0)

    def is_zero(self) -> bool:
        return self.seconds == GO_ZERO_TIME_SECONDS and self.nanos == 0


def encode_timestamp(ts: Timestamp) -> bytes:
    w = ProtoWriter()
    w.write_varint(1, ts.seconds)
    w.write_varint(2, ts.nanos)
    return w.bytes()


class CanonicalPartSetHeader(NamedTuple):
    total: int
    hash: bytes


class CanonicalBlockID(NamedTuple):
    hash: bytes
    part_set_header: CanonicalPartSetHeader


def encode_canonical_part_set_header(psh: CanonicalPartSetHeader) -> bytes:
    w = ProtoWriter()
    w.write_varint(1, psh.total)
    w.write_bytes(2, psh.hash)
    return w.bytes()


def encode_canonical_block_id(bid: CanonicalBlockID) -> bytes:
    w = ProtoWriter()
    w.write_bytes(1, bid.hash)
    # part_set_header is gogoproto non-nullable: always emitted
    w.write_message(2, encode_canonical_part_set_header(bid.part_set_header), always=True)
    return w.bytes()


def canonical_vote_sign_bytes(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id: Optional[CanonicalBlockID],
    timestamp: Timestamp,
) -> bytes:
    """VoteSignBytes (types/vote.go:84-101): delimited CanonicalVote.

    block_id must already be canonicalized: None iff the vote's BlockID is
    zero (types/canonical.go:18-34). Implemented via the template split so
    there is exactly one encoder for the cached and direct paths."""
    return compose_vote_sign_bytes(
        canonical_vote_template(chain_id, msg_type, height, round_, block_id),
        timestamp,
    )


def canonical_vote_template(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id: Optional[CanonicalBlockID],
) -> tuple:
    """Split the CanonicalVote encoding around its only per-signature field
    (the timestamp, field 5): (prefix = fields 1-4, suffix = field 6).
    compose_vote_sign_bytes(tpl, ts) == canonical_vote_sign_bytes(...) for
    every timestamp — a commit's 10k sign-bytes share one template
    (types/block.go:816-819 rebuilds the whole message per signature; the
    batch path here amortizes everything but the timestamp)."""
    w = ProtoWriter()
    w.write_varint(1, msg_type)
    w.write_sfixed64(2, height)
    w.write_sfixed64(3, round_)
    if block_id is not None:
        w.write_message(4, encode_canonical_block_id(block_id), always=True)
    prefix = w.bytes()
    w2 = ProtoWriter()
    w2.write_string(6, chain_id)
    return prefix, w2.bytes()


def compose_vote_sign_bytes(tpl: tuple, timestamp: Timestamp) -> bytes:
    prefix, suffix = tpl
    w = ProtoWriter()
    w.write_message(5, encode_timestamp(timestamp), always=True)
    return marshal_delimited(prefix + w.bytes() + suffix)


_U64 = (1 << 64) - 1


def _compose_one(prefix: bytes, suffix: bytes, ts: "Timestamp") -> bytes:
    """One record of the block composer's layout (scalar reference)."""
    from .proto import encode_uvarint

    tb = b""
    if ts.seconds:
        tb = b"\x08" + encode_uvarint(ts.seconds & _U64)
    if ts.nanos:
        tb += b"\x10" + encode_uvarint(ts.nanos & _U64)
    body = prefix + b"\x2a" + encode_uvarint(len(tb)) + tb + suffix
    return encode_uvarint(len(body)) + body


def _uvarint_len(v):
    """(n,) uint64 -> per-value uvarint byte length (numpy)."""
    import numpy as np

    length = np.ones(v.shape, dtype=np.int64)
    for k in range(1, 10):
        length += v >= np.uint64(1 << (7 * k))
    return length


def compose_vote_sign_bytes_block(tpl: tuple, timestamps) -> tuple:
    """Batch compose_vote_sign_bytes into ONE contiguous buffer: returns
    (buf, offsets) where buf[offsets[i]:offsets[i+1]] is the i-th vote's
    sign bytes — the EntryBlock msgs form (ops/entry_block.py), so the
    verify path never materializes per-signature PyBytes.

    Byte-identical to the per-call composer (differentially tested)."""
    import numpy as np

    prefix, suffix = tpl
    n = len(timestamps)
    if n and n < 64:
        offsets = np.zeros(n + 1, dtype=np.int64)
        chunks = [_compose_one(prefix, suffix, ts) for ts in timestamps]
        np.cumsum([len(c) for c in chunks], out=offsets[1:])
        return b"".join(chunks), offsets
    secs = np.fromiter(
        (ts.seconds for ts in timestamps), dtype=np.int64, count=n
    )
    nanos = np.fromiter(
        (ts.nanos for ts in timestamps), dtype=np.int64, count=n
    )
    return compose_vote_sign_bytes_cols(tpl, secs, nanos)


def compose_vote_sign_bytes_cols(
    tpl: tuple, secs_col, nanos_col, with_groups: bool = False
) -> tuple:
    """Column-input composer: (seconds (n,) int64, nanos (n,) int-like)
    arrays in, (buf, offsets) out — byte-identical to the per-call
    composer. The columnar commit path (ops/commit_prep.py) feeds the
    CommitBlock timestamp columns straight in, so no Timestamp objects
    exist anywhere between wire decode and the kernel.

    Records vary only in the two timestamp varints, so rows group by
    their (seconds-length, nanos-length) layout — a handful of groups per
    commit — and each group composes as one broadcast + vectorized varint
    fill instead of n ProtoWriter walks (~7x at 10k signatures). When
    every row shares one layout (the common case), the record matrix IS
    the output buffer — no scatter at all.

    with_groups=True appends a [(rows, (g, rec_len) uint8 array)] list so
    a caller merging two compositions into lane order (the fused prep's
    COMMIT and NIL groups) can reuse the 2-D record matrices; the buffer
    then comes back as a 1-D uint8 ndarray (no bytes copy) instead of
    bytes."""
    import numpy as np

    prefix, suffix = tpl
    n = len(secs_col)
    offsets = np.zeros(n + 1, dtype=np.int64)
    if n == 0:
        return (b"", offsets, []) if with_groups else (b"", offsets)
    secs = np.ascontiguousarray(secs_col, dtype=np.int64).view(np.uint64)
    nanos = np.ascontiguousarray(nanos_col, dtype=np.int64).view(np.uint64)
    # per-row field layout: 0 length = field omitted (proto3 zero-skip)
    s_len = np.where(secs != 0, _uvarint_len(secs), 0)
    n_len = np.where(nanos != 0, _uvarint_len(nanos), 0)
    tn = (s_len != 0) * (1 + s_len) + (n_len != 0) * (1 + n_len)
    p_len, x_len = len(prefix), len(suffix)
    body_len = p_len + 2 + tn + x_len  # 0x2a + 1-byte uvarint(tn) + fields
    hdr_len = _uvarint_len(body_len.view(np.uint64))
    rec_len = hdr_len + body_len
    np.cumsum(rec_len, out=offsets[1:])
    pre_arr = np.frombuffer(prefix, dtype=np.uint8)
    suf_arr = np.frombuffer(suffix, dtype=np.uint8)

    def _fill_varint(dst, col, v, width):
        for j in range(width):
            b = (v >> np.uint64(7 * j)) & np.uint64(0x7F)
            if j < width - 1:
                b = b | np.uint64(0x80)
            dst[:, col + j] = b
        return col + width

    def _fill_group(rows):
        i0 = rows[0]
        sl, nl, hl = int(s_len[i0]), int(n_len[i0]), int(hdr_len[i0])
        rl, bl, t0 = int(rec_len[i0]), int(body_len[i0]), int(tn[i0])
        g = len(rows)
        # a commit's votes land within the same second (or two), so a
        # group's seconds column is usually ONE value: compose a single
        # template row, broadcast it, and fill only the varying varint
        # columns — one big write instead of ~15 per-column passes
        const_secs = g > 1 and sl and bool(
            (secs[rows] == secs[rows[0]]).all()
        )
        if const_secs:
            row = np.empty((1, rl), dtype=np.uint8)
            col = _fill_varint(row, 0, np.uint64(bl), hl)
            row[:, col : col + p_len] = pre_arr
            col += p_len
            row[:, col] = 0x2A
            row[:, col + 1] = t0
            col += 2
            row[:, col] = 0x08
            col = _fill_varint(row, col + 1, secs[rows[:1]], sl)
            n_col = col
            if nl:
                row[:, col] = 0x10
                col = _fill_varint(row, col + 1, nanos[rows[:1]], nl)
            row[:, col:] = suf_arr
            arr = np.empty((g, rl), dtype=np.uint8)
            arr[:] = row
            if nl:
                _fill_varint(arr, n_col + 1, nanos[rows], nl)
            return arr
        arr = np.empty((g, rl), dtype=np.uint8)
        col = _fill_varint(arr, 0, np.uint64(bl), hl)
        arr[:, col : col + p_len] = pre_arr
        col += p_len
        arr[:, col] = 0x2A
        arr[:, col + 1] = t0
        col += 2
        if sl:
            arr[:, col] = 0x08
            col = _fill_varint(arr, col + 1, secs[rows], sl)
        if nl:
            arr[:, col] = 0x10
            col = _fill_varint(arr, col + 1, nanos[rows], nl)
        arr[:, col:] = suf_arr
        return arr

    key = (s_len * 1024 + n_len * 16 + hdr_len).astype(np.int64)
    uniq = np.unique(key)
    groups = []
    if uniq.size == 1:
        rows = np.arange(n)
        arr = _fill_group(rows)
        if with_groups:
            groups.append((rows, arr))
            return arr.reshape(-1), offsets, groups
        return arr.tobytes(), offsets
    out = np.zeros(int(offsets[-1]), dtype=np.uint8)
    for k in uniq:
        rows = np.nonzero(key == k)[0]
        arr = _fill_group(rows)
        out[offsets[rows][:, None] + np.arange(arr.shape[1])] = arr
        groups.append((rows, arr))
    if with_groups:
        return out, offsets, groups
    return out.tobytes(), offsets


def canonical_proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id: Optional[CanonicalBlockID],
    timestamp: Timestamp,
) -> bytes:
    """ProposalSignBytes (types/proposal.go): delimited CanonicalProposal."""
    w = ProtoWriter()
    w.write_varint(1, SIGNED_MSG_TYPE_PROPOSAL)
    w.write_sfixed64(2, height)
    w.write_sfixed64(3, round_)
    w.write_varint(4, pol_round)
    if block_id is not None:
        w.write_message(5, encode_canonical_block_id(block_id), always=True)
    w.write_message(6, encode_timestamp(timestamp), always=True)
    w.write_string(7, chain_id)
    return marshal_delimited(w.bytes())
