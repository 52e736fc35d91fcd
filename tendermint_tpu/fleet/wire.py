"""Fleet wire format: length-prefixed columnar EntryBlock frames.

EntryBlocks are already columnar (pub (n,32) u8, sig (n,64) u8, one
contiguous msgs buffer + (n+1,) i64 offsets), so serialization is
near-free: the encoder emits an iovec of header bytes plus raw
memoryviews over the numpy columns — zero copies on the send side.
The decoder rebuilds the block with ``np.frombuffer`` over slices of
the received payload (read-only views, one copy per frame at the
socket boundary, which is unavoidable).

Frame layout (all little-endian):

    u32 payload_len | payload

    payload := MAGIC("TMFL") u16 version u8 kind u8 flags | body

SUBMIT body (kind=1):

    u64 request_id | u64 flow (0 = none) | u8 priority | u8 meta_flags
    | u16 lane_len | lane utf-8
    | u32 n | u64 msgs_len
    | pub n*32 | sig n*64 | offsets (n+1)*8 i64 | msgs
    | [if meta_flags & FLAG_EPOCH:  u16 ek_len | epoch_key | val_idx n*4 i32]

VERDICT body (kind=2):   u64 request_id | u32 n | n bytes of 0/1
ERROR body   (kind=3):   u64 request_id | u8 code | u16 msg_len | msg utf-8

Error classes:

* ``WireError`` — malformed payload. Recoverable: the 4-byte length
  prefix still framed the junk, so the connection survives and the
  peer answers with an ERROR frame.
* ``VersionSkew`` — well-framed but from a different protocol version.
  Recoverable the same way (code ERR_VERSION).
* ``OversizeFrame`` — the length prefix exceeds ``max_frame``. Framing
  can no longer be trusted, so the *connection* must close — but only
  the connection; the server stays up.
* ``TruncatedFrame`` — EOF mid-frame (peer died). Connection-fatal.
"""

from __future__ import annotations

import os
import struct
from typing import Iterator, List, NamedTuple, Optional, Sequence, Union

import numpy as np

from ..ops.entry_block import EntryBlock

MAGIC = b"TMFL"
VERSION = 1

KIND_SUBMIT = 1
KIND_VERDICT = 2
KIND_ERROR = 3

ERR_MALFORMED = 1
ERR_VERSION = 2
ERR_DISPATCH = 3
ERR_OVERSIZE = 4
ERR_CLOSED = 5

FLAG_EPOCH = 1  # meta_flags bit0: epoch_key + val_idx tail present

_LEN = struct.Struct("<I")
_HDR = struct.Struct("<4sHBB")           # magic, version, kind, flags
_SUBMIT_META = struct.Struct("<QQBBH")   # request_id, flow, priority, meta_flags, lane_len
_SUBMIT_SHAPE = struct.Struct("<IQ")     # n, msgs_len
_VERDICT_META = struct.Struct("<QI")     # request_id, n
_ERROR_META = struct.Struct("<QBH")      # request_id, code, msg_len
_EK_LEN = struct.Struct("<H")

_DEF_MAX_FRAME = 64 * 1024 * 1024


def max_frame_bytes() -> int:
    """Hard per-frame ceiling (``TM_TPU_FLEET_MAX_FRAME``, default 64 MiB)."""
    try:
        v = int(os.environ.get("TM_TPU_FLEET_MAX_FRAME", _DEF_MAX_FRAME))
    except ValueError:
        v = _DEF_MAX_FRAME
    return max(4096, v)


class WireError(ValueError):
    """Malformed frame payload; the connection survives (framing intact)."""


class VersionSkew(WireError):
    """Frame from an incompatible protocol version."""

    def __init__(self, got: int):
        super().__init__(f"fleet wire version skew: got v{got}, speak v{VERSION}")
        self.got = got


class OversizeFrame(WireError):
    """Length prefix exceeds max_frame — framing lost, connection must close."""


class TruncatedFrame(WireError):
    """EOF arrived mid-frame (peer died with bytes in flight)."""


class SubmitFrame(NamedTuple):
    request_id: int
    flow: int          # 0 = no flow
    priority: int
    lane: str
    block: EntryBlock


class VerdictFrame(NamedTuple):
    request_id: int
    verdicts: np.ndarray  # (n,) bool


class ErrorFrame(NamedTuple):
    request_id: int
    code: int
    message: str


Frame = Union[SubmitFrame, VerdictFrame, ErrorFrame]


def _col_bytes(arr: np.ndarray) -> memoryview:
    # Contiguous little-endian bytes over a column, copy-free when the
    # array is already C-contiguous (EntryBlock columns always are).
    a = np.ascontiguousarray(arr)
    if a.dtype.byteorder == ">":  # pragma: no cover - no BE hosts in CI
        a = a.astype(a.dtype.newbyteorder("<"))
    if a.size == 0:  # zero-size views can't be cast flat
        return memoryview(b"")
    return memoryview(a).cast("B")


def encode_submit(
    request_id: int,
    block: EntryBlock,
    *,
    flow: int = 0,
    priority: int = 0,
    lane: str = "",
) -> List[Union[bytes, memoryview]]:
    """Encode an EntryBlock SUBMIT frame as an iovec (zero-copy columns).

    Returns a list of buffers suitable for ``socket.sendmsg`` or
    sequential ``sendall``; the numpy columns are passed through as
    memoryviews without copying.
    """
    n = len(block)
    lane_b = lane.encode("utf-8")
    if len(lane_b) > 0xFFFF:
        raise WireError("lane name too long")
    msgs_buf, offs = block.msgs_contiguous()
    msgs_len = len(msgs_buf)

    has_epoch = block.epoch_key is not None and block.val_idx is not None
    meta_flags = FLAG_EPOCH if has_epoch else 0

    iov: List[Union[bytes, memoryview]] = []
    head = (
        _HDR.pack(MAGIC, VERSION, KIND_SUBMIT, 0)
        + _SUBMIT_META.pack(request_id, flow, priority, meta_flags, len(lane_b))
        + lane_b
        + _SUBMIT_SHAPE.pack(n, msgs_len)
    )
    payload_len = (
        len(head) + n * 32 + n * 64 + (n + 1) * 8 + msgs_len
    )
    ek_b = b""
    if has_epoch:
        ek_b = bytes(block.epoch_key)
        if len(ek_b) > 0xFFFF:
            raise WireError("epoch_key too long")
        payload_len += _EK_LEN.size + len(ek_b) + n * 4
    if payload_len > max_frame_bytes():
        raise OversizeFrame(
            f"encoded frame {payload_len}B exceeds max_frame {max_frame_bytes()}B"
        )

    iov.append(_LEN.pack(payload_len) + head)
    iov.append(_col_bytes(block.pub))
    iov.append(_col_bytes(block.sig))
    iov.append(_col_bytes(offs.astype("<i8", copy=False)))
    iov.append(memoryview(msgs_buf) if not isinstance(msgs_buf, memoryview) else msgs_buf)
    if has_epoch:
        iov.append(_EK_LEN.pack(len(ek_b)) + ek_b)
        iov.append(_col_bytes(block.val_idx.astype("<i4", copy=False)))
    return iov


def encode_verdicts(request_id: int, verdicts: np.ndarray) -> bytes:
    v = np.asarray(verdicts).astype(np.uint8, copy=False).reshape(-1)
    payload = (
        _HDR.pack(MAGIC, VERSION, KIND_VERDICT, 0)
        + _VERDICT_META.pack(request_id, v.shape[0])
        + v.tobytes()
    )
    return _LEN.pack(len(payload)) + payload


def encode_error(request_id: int, code: int, message: str) -> bytes:
    msg_b = message.encode("utf-8")[:0xFFFF]
    payload = (
        _HDR.pack(MAGIC, VERSION, KIND_ERROR, 0)
        + _ERROR_META.pack(request_id, code, len(msg_b))
        + msg_b
    )
    return _LEN.pack(len(payload)) + payload


def _need(payload: bytes, off: int, n: int, what: str) -> None:
    if off + n > len(payload):
        raise WireError(f"truncated {what}: need {n}B at {off}, have {len(payload)}")


def parse_frame(payload: bytes) -> Frame:
    """Parse one complete frame payload (length prefix already stripped).

    Raises WireError / VersionSkew on malformed input; both are
    per-frame recoverable because framing came from the length prefix.
    """
    _need(payload, 0, _HDR.size, "header")
    magic, version, kind, _flags = _HDR.unpack_from(payload, 0)
    if magic != MAGIC:
        raise WireError(f"bad magic {magic!r}")
    if version != VERSION:
        raise VersionSkew(version)
    off = _HDR.size

    if kind == KIND_SUBMIT:
        _need(payload, off, _SUBMIT_META.size, "submit meta")
        request_id, flow, priority, meta_flags, lane_len = _SUBMIT_META.unpack_from(
            payload, off
        )
        off += _SUBMIT_META.size
        _need(payload, off, lane_len, "lane name")
        try:
            lane = payload[off : off + lane_len].decode("utf-8")
        except UnicodeDecodeError as e:
            raise WireError(f"lane name not utf-8: {e}") from None
        off += lane_len
        _need(payload, off, _SUBMIT_SHAPE.size, "submit shape")
        n, msgs_len = _SUBMIT_SHAPE.unpack_from(payload, off)
        off += _SUBMIT_SHAPE.size

        _need(payload, off, n * 32, "pub column")
        pub = np.frombuffer(payload, dtype=np.uint8, count=n * 32, offset=off)
        pub = pub.reshape(n, 32)
        off += n * 32
        _need(payload, off, n * 64, "sig column")
        sig = np.frombuffer(payload, dtype=np.uint8, count=n * 64, offset=off)
        sig = sig.reshape(n, 64)
        off += n * 64
        _need(payload, off, (n + 1) * 8, "offsets column")
        offsets = np.frombuffer(payload, dtype="<i8", count=n + 1, offset=off)
        off += (n + 1) * 8
        _need(payload, off, msgs_len, "msgs buffer")
        msgs = payload[off : off + msgs_len]
        off += msgs_len

        if offsets[0] != 0:
            raise WireError(f"offsets[0] = {int(offsets[0])}, want 0")
        if int(offsets[-1]) != msgs_len:
            raise WireError(
                f"offsets[-1] = {int(offsets[-1])} != msgs_len {msgs_len}"
            )
        if n and np.any(np.diff(offsets) < 0):
            raise WireError("offsets not non-decreasing")

        epoch_key: Optional[bytes] = None
        val_idx: Optional[np.ndarray] = None
        if meta_flags & FLAG_EPOCH:
            _need(payload, off, _EK_LEN.size, "epoch_key length")
            (ek_len,) = _EK_LEN.unpack_from(payload, off)
            off += _EK_LEN.size
            _need(payload, off, ek_len, "epoch_key")
            epoch_key = payload[off : off + ek_len]
            off += ek_len
            _need(payload, off, n * 4, "val_idx column")
            val_idx = np.frombuffer(payload, dtype="<i4", count=n, offset=off)
            off += n * 4
        if off != len(payload):
            raise WireError(f"{len(payload) - off}B of trailing junk")

        block = EntryBlock(
            pub=pub,
            sig=sig,
            msgs=msgs,
            offsets=offsets.astype(np.int64, copy=False),
            epoch_key=epoch_key,
            val_idx=(
                val_idx.astype(np.int32, copy=False) if val_idx is not None else None
            ),
        )
        return SubmitFrame(request_id, flow, priority, lane, block)

    if kind == KIND_VERDICT:
        _need(payload, off, _VERDICT_META.size, "verdict meta")
        request_id, n = _VERDICT_META.unpack_from(payload, off)
        off += _VERDICT_META.size
        _need(payload, off, n, "verdict bytes")
        v = np.frombuffer(payload, dtype=np.uint8, count=n, offset=off)
        off += n
        if off != len(payload):
            raise WireError(f"{len(payload) - off}B of trailing junk")
        return VerdictFrame(request_id, v.astype(bool))

    if kind == KIND_ERROR:
        _need(payload, off, _ERROR_META.size, "error meta")
        request_id, code, msg_len = _ERROR_META.unpack_from(payload, off)
        off += _ERROR_META.size
        _need(payload, off, msg_len, "error message")
        try:
            msg = payload[off : off + msg_len].decode("utf-8")
        except UnicodeDecodeError as e:
            raise WireError(f"error message not utf-8: {e}") from None
        off += msg_len
        if off != len(payload):
            raise WireError(f"{len(payload) - off}B of trailing junk")
        return ErrorFrame(request_id, code, msg)

    raise WireError(f"unknown frame kind {kind}")


class FrameDecoder:
    """Incremental stream → complete frame payloads.

    Feed arbitrary byte chunks; get back complete payloads (length
    prefix stripped). Tolerates any fragmentation. Raises
    ``OversizeFrame`` when a length prefix exceeds the cap — after
    that the stream's framing cannot be trusted and the connection
    must close.
    """

    def __init__(self, max_frame: Optional[int] = None):
        self._buf = bytearray()
        self._max = max_frame if max_frame is not None else max_frame_bytes()

    def feed(self, data: bytes) -> List[bytes]:
        self._buf.extend(data)
        out: List[bytes] = []
        while True:
            if len(self._buf) < _LEN.size:
                break
            (plen,) = _LEN.unpack_from(self._buf, 0)
            if plen > self._max:
                raise OversizeFrame(
                    f"frame length {plen}B exceeds max_frame {self._max}B"
                )
            if len(self._buf) < _LEN.size + plen:
                break
            out.append(bytes(self._buf[_LEN.size : _LEN.size + plen]))
            del self._buf[: _LEN.size + plen]
        return out

    def eof(self) -> None:
        """Signal end-of-stream; raises if a partial frame was pending."""
        if self._buf:
            raise TruncatedFrame(
                f"EOF with {len(self._buf)}B of partial frame buffered"
            )

    @property
    def pending(self) -> int:
        return len(self._buf)


def send_frame(sock, iov: Sequence[Union[bytes, memoryview]]) -> None:
    """Write one encoded frame (iovec or single buffer) to a socket."""
    if isinstance(iov, (bytes, bytearray, memoryview)):
        sock.sendall(iov)
        return
    if not hasattr(sock, "sendmsg"):
        for b in iov:
            sock.sendall(b)
        return
    # One syscall per round when the platform supports scatter-gather
    # (Linux always does); loop handles rare partial sends.
    bufs = [b if isinstance(b, memoryview) else memoryview(b) for b in iov]
    while bufs:
        sent = sock.sendmsg(bufs)
        while sent:
            if sent >= len(bufs[0]):
                sent -= len(bufs[0])
                bufs.pop(0)
            else:
                bufs[0] = bufs[0][sent:]
                sent = 0


def iter_frames(decoder: FrameDecoder, data: bytes) -> Iterator[Frame]:
    """Convenience: feed + parse in one step (used by loopback paths)."""
    for payload in decoder.feed(data):
        yield parse_frame(payload)
