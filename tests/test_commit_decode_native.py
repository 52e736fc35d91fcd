"""Commit.decode's two paths (ISSUE 27): the native single-pass parser
(native/tm_native.cpp commit_decode_columns) against the Python walk that
specifies it. Canonical commits must give the same columns by both; every
deviant input must make the native pass answer None and leave Commit.decode
with exactly what the Python walk gives alone — the same objects, or the
same exception type and message. Plus the two ops_stats() counters, the
GIL release and a wide speed-ratio guard."""

import json
import os
import random
import subprocess
import sys
import threading
import time
from unittest import mock

import numpy as np
import pytest

from tendermint_tpu import native as native_mod
from tendermint_tpu.libs.metrics import ops_stats
from tendermint_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    CommitSigs,
    PartSetHeader,
)
from tendermint_tpu.wire.canonical import GO_ZERO_TIME_SECONDS, Timestamp
from tendermint_tpu.wire.proto import encode_uvarint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COLUMNS = ("flags", "val_idx", "sig", "ts_seconds", "ts_nanos", "addr")
U64 = (1 << 64) - 1

BLOCK_ID = BlockID(
    hash=b"\x11" * 32,
    part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32),
)


# -- the two paths ----------------------------------------------------------


def _native_columns(data):
    return native_mod.load().commit_decode_columns(data)


def _python_decode(data):
    """Commit.decode as it runs without the module: the specification."""
    with mock.patch.object(native_mod, "load", lambda: None):
        return Commit.decode(data)


def _outcome(decode, data):
    try:
        return ("ok", decode(data))
    except Exception as e:  # noqa: BLE001 - the type is what is compared
        return ("err", type(e), str(e))


def _assert_same_commit(a, b):
    assert (a.height, a.round, a.block_id) == (b.height, b.round, b.block_id)
    assert type(a.signatures) is type(b.signatures)
    if isinstance(a.signatures, CommitSigs):
        ba, bb = a.signatures.block(), b.signatures.block()
        for col in COLUMNS:
            x, y = getattr(ba, col), getattr(bb, col)
            assert x.dtype == y.dtype, col
            assert x.shape == y.shape, col
            assert np.array_equal(x, y), col
    assert a.signatures == b.signatures


def _assert_same_outcome(data):
    got, want = _outcome(Commit.decode, data), _outcome(_python_decode, data)
    assert got[0] == want[0], (got, want)
    if got[0] == "ok":
        _assert_same_commit(got[1], want[1])
    else:
        assert got[1:] == want[1:]


# -- canonical commits, through the repo's own encoder ----------------------


def _sig(i, flag=BLOCK_ID_FLAG_COMMIT, ts=None):
    if flag == BLOCK_ID_FLAG_ABSENT:
        return CommitSig.absent()
    rng = random.Random(i)
    return CommitSig(
        block_id_flag=flag,
        validator_address=rng.randbytes(20),
        timestamp=ts or Timestamp(1_700_000_000 + i, (i * 7919) % 10**9),
        signature=rng.randbytes(64),
    )


def _mixed_flag(i):
    if i % 11 == 3:
        return BLOCK_ID_FLAG_ABSENT
    if i % 13 == 5:
        return BLOCK_ID_FLAG_NIL
    return BLOCK_ID_FLAG_COMMIT


def _commit(sigs, height=7, round_=0, block_id=BLOCK_ID):
    return Commit(
        height=height, round=round_, block_id=block_id, signatures=sigs
    ).encode()


def _timestamp_commit(ts):
    return _commit(
        [_sig(0), _sig(1, ts=ts), _sig(2, BLOCK_ID_FLAG_NIL, ts=ts), _sig(3)]
    )


CANONICAL = {
    "150_all_commit": lambda: _commit([_sig(i) for i in range(150)]),
    "10000_all_commit": lambda: _commit([_sig(i) for i in range(10_000)]),
    "150_absent_nil_mixed": lambda: _commit(
        [_sig(i, _mixed_flag(i)) for i in range(150)]
    ),
    "10000_absent_nil_mixed": lambda: _commit(
        [_sig(i, _mixed_flag(i)) for i in range(10_000)]
    ),
    "all_absent": lambda: _commit(
        [_sig(i, BLOCK_ID_FLAG_ABSENT) for i in range(5)]
    ),
    "one_lane": lambda: _commit([_sig(0)]),
    "empty_signature_list": lambda: _commit([]),
    "zero_block_id": lambda: _commit([_sig(0)], block_id=BlockID()),
    "ts_zero": lambda: _timestamp_commit(Timestamp(0, 0)),
    "ts_negative": lambda: _timestamp_commit(Timestamp(-1, 5)),
    "ts_negative_nanos": lambda: _timestamp_commit(Timestamp(9, -3)),
    "ts_go_zero_time_on_commit_lane": lambda: _timestamp_commit(
        Timestamp(GO_ZERO_TIME_SECONDS, 0)
    ),
    "ts_nanos_0": lambda: _timestamp_commit(Timestamp(1_700_000_000, 0)),
    "ts_nanos_999999999": lambda: _timestamp_commit(
        Timestamp(1_700_000_000, 999_999_999)
    ),
    "ts_int64_min": lambda: _timestamp_commit(Timestamp(-(1 << 63), 0)),
    "ts_int64_max": lambda: _timestamp_commit(
        Timestamp((1 << 63) - 1, (1 << 31) - 1)
    ),
    **{
        # the largest seconds a k-byte varint holds (k = 10: a negative)
        f"ts_seconds_{k}_varint_bytes": (
            lambda k=k: _timestamp_commit(
                Timestamp((1 << (7 * k)) - 1 if k < 10 else -2, 1)
            )
        )
        for k in range(1, 11)
    },
    "height_0_round_0": lambda: _commit([_sig(0)], height=0, round_=0),
    "height_int64_max": lambda: _commit([_sig(0)], height=(1 << 63) - 1),
    "height_int64_min": lambda: _commit([_sig(0)], height=-(1 << 63)),
    "height_minus_1": lambda: _commit([_sig(0)], height=-1),
    "round_int32_max": lambda: _commit([_sig(0)], round_=(1 << 31) - 1),
    "round_int32_min": lambda: _commit([_sig(0)], round_=-(1 << 31)),
    "round_minus_1": lambda: _commit([_sig(0)], round_=-1),
}


@pytest.mark.native_required
@pytest.mark.parametrize("case", sorted(CANONICAL))
def test_canonical_commit_decodes_alike_by_both_paths(case):
    data = CANONICAL[case]()
    cols = _native_columns(data)
    assert cols is not None, "the native pass refused a canonical commit"
    assert cols[3] == len(cols[4]) == len(cols[5]) // 64 == len(cols[8]) // 20
    got, want = Commit.decode(data), _python_decode(data)
    _assert_same_commit(got, want)
    assert got.encode() == data
    assert want.encode() == data


# -- hand-built wire, for what the encoder never writes ---------------------

ADDR = bytes(range(100, 120))
SIG = bytes(range(64))


def _tag(field, wire_type):
    return encode_uvarint((field << 3) | wire_type)


def _varint(field, value):
    return _tag(field, 0) + encode_uvarint(value & U64)


def _bytes(field, value):
    return _tag(field, 2) + encode_uvarint(len(value)) + value


def _ts(seconds=1_700_000_000, nanos=5):
    return (_varint(1, seconds) if seconds else b"") + (
        _varint(2, nanos) if nanos else b""
    )


def _record(flag=2, addr=ADDR, ts=None, sig=SIG):
    return (
        (_varint(1, flag) if flag else b"")
        + (_bytes(2, addr) if addr else b"")
        + _bytes(3, _ts() if ts is None else ts)
        + (_bytes(4, sig) if sig else b"")
    )


ABSENT_RECORD = _record(flag=1, addr=b"", ts=_ts(GO_ZERO_TIME_SECONDS, 0), sig=b"")
HEIGHT, ROUND, BID = _varint(1, 7), _varint(2, 1), _bytes(3, BLOCK_ID.encode())
RECORDS = _bytes(4, _record()) + _bytes(4, ABSENT_RECORD) + _bytes(4, _record(flag=3))
VALID = HEIGHT + ROUND + BID + RECORDS


def _with_record(record):
    return HEIGHT + ROUND + BID + _bytes(4, _record()) + _bytes(4, record)


def test_hand_built_wire_is_what_the_encoder_writes():
    commit = Commit(
        height=7,
        round=1,
        block_id=BLOCK_ID,
        signatures=[
            CommitSig(BLOCK_ID_FLAG_COMMIT, ADDR, Timestamp(1_700_000_000, 5), SIG),
            CommitSig.absent(),
            CommitSig(BLOCK_ID_FLAG_NIL, ADDR, Timestamp(1_700_000_000, 5), SIG),
        ],
    )
    assert commit.encode() == VALID


# canonical for both paths though the encoder never writes it: no encode
# round trip to hold, the two decodes must still agree
OFF_ENCODER = {
    "empty_message": b"",
    "height_only": HEIGHT,
    "no_block_id": HEIGHT + ROUND + RECORDS,
    "zero_height_written": _tag(1, 0) + b"\x00" + ROUND + BID + RECORDS,
    "non_minimal_height_varint": _tag(1, 0) + b"\x87\x80\x00" + BID + RECORDS,
    "non_minimal_record_length": HEIGHT + BID + _tag(4, 2) + b"\xe4\x00" + _record(),
    # _decode_sig_record takes a record's fields in any order
    "record_fields_reversed": HEIGHT + BID + _bytes(
        4, _bytes(4, SIG) + _bytes(3, _ts()) + _bytes(2, ADDR) + _varint(1, 2)
    ),
    "timestamp_fields_reversed": HEIGHT + BID + _bytes(
        4, _record(flag=3, ts=_varint(2, 5) + _varint(1, 9))
    ),
    "absent_with_empty_address_and_signature_written": HEIGHT + BID + _bytes(
        4, _varint(1, 1) + _bytes(2, b"") + _bytes(3, _ts(GO_ZERO_TIME_SECONDS, 0)) + _bytes(4, b"")
    ),
    "commit_lane_without_timestamp_field": HEIGHT + BID + _bytes(
        4, _varint(1, 2) + _bytes(2, ADDR) + _bytes(4, SIG)
    ),
}


@pytest.mark.native_required
@pytest.mark.parametrize("case", sorted(OFF_ENCODER))
def test_canonical_wire_the_encoder_never_writes(case):
    data = OFF_ENCODER[case]
    assert _native_columns(data) is not None
    _assert_same_commit(Commit.decode(data), _python_decode(data))


OVERLONG_11 = b"\x80" * 10 + b"\x01"  # an 11-byte varint
TEN_BYTES_70_BITS = b"\xff" * 9 + b"\x7f"  # Python reads 70 bits of it
TEN_BYTES_65_BITS = b"\x80" * 9 + b"\x02"

DEVIANT = {
    # outer: unknown, duplicate, out of order
    "outer_unknown_field_appended": VALID + _varint(5, 9),
    "outer_unknown_field_before_records": HEIGHT + ROUND + BID + _bytes(6, b"xy") + RECORDS,
    "outer_unknown_field_first": _varint(9, 1) + VALID,
    "outer_duplicate_height": HEIGHT + _varint(1, 8) + ROUND + BID + RECORDS,
    "outer_duplicate_round": HEIGHT + ROUND + _varint(2, 3) + BID + RECORDS,
    "outer_duplicate_block_id": HEIGHT + ROUND + BID + _bytes(3, b"") + RECORDS,
    "outer_round_before_height": ROUND + HEIGHT + BID + RECORDS,
    "outer_block_id_before_round": HEIGHT + BID + ROUND + RECORDS,
    "outer_record_before_block_id": HEIGHT + ROUND + _bytes(4, _record()) + BID,
    "outer_height_after_records": ROUND + BID + RECORDS + HEIGHT,
    "outer_block_id_between_records": HEIGHT + ROUND + _bytes(4, _record()) + BID + _bytes(4, _record()),
    "outer_non_minimal_tag": b"\x88\x00\x07" + ROUND + BID + RECORDS,
    "outer_field_number_0": b"\x00\x01" + VALID,
    # outer: wrong wire types
    "outer_height_fixed64": _tag(1, 1) + bytes(8) + ROUND + BID + RECORDS,
    "outer_height_fixed32": _tag(1, 5) + b"\x07\x00\x00\x00" + ROUND + BID + RECORDS,
    "outer_height_bytes": _bytes(1, b"\x07") + ROUND + BID + RECORDS,
    "outer_round_fixed32": HEIGHT + _tag(2, 5) + b"\x01\x00\x00\x00" + BID + RECORDS,
    "outer_round_bytes": HEIGHT + _bytes(2, b"") + BID + RECORDS,
    "outer_block_id_varint": HEIGHT + ROUND + _varint(3, 1) + RECORDS,
    "outer_block_id_fixed64": HEIGHT + ROUND + _tag(3, 1) + bytes(8) + RECORDS,
    "outer_record_varint": HEIGHT + ROUND + BID + _varint(4, 1),
    "outer_record_fixed32_among_records": HEIGHT + ROUND + BID + _bytes(4, _record()) + _tag(4, 5) + bytes(4),
    "outer_group_wire_type": HEIGHT + _tag(2, 3) + BID + RECORDS,
    # varints
    "height_varint_11_bytes": _tag(1, 0) + OVERLONG_11 + ROUND + BID + RECORDS,
    "height_varint_70_bits": _tag(1, 0) + TEN_BYTES_70_BITS + ROUND + BID + RECORDS,
    "height_varint_65_bits": _tag(1, 0) + TEN_BYTES_65_BITS + ROUND + BID + RECORDS,
    "round_varint_70_bits": HEIGHT + _tag(2, 0) + TEN_BYTES_70_BITS + BID + RECORDS,
    "record_length_varint_11_bytes": HEIGHT + ROUND + BID + _tag(4, 2) + OVERLONG_11,
    "record_length_past_the_end": HEIGHT + ROUND + BID + _tag(4, 2) + b"\xff\xff\xff\xff\x0f",
    "record_length_2_to_the_63": HEIGHT + ROUND + BID + _tag(4, 2) + b"\x80" * 9 + b"\x01",
    "flag_varint_70_bits": _with_record(_tag(1, 0) + TEN_BYTES_70_BITS + _record(flag=0)),
    "ts_seconds_varint_70_bits": _with_record(_record(ts=_tag(1, 0) + TEN_BYTES_70_BITS)),
    "ts_nanos_varint_11_bytes": _with_record(_record(ts=_tag(2, 0) + OVERLONG_11)),
    # trailing bytes
    "trailing_zero_byte": VALID + b"\x00",
    "trailing_ff": VALID + b"\xff",
    "trailing_half_a_record": VALID + _bytes(4, _record())[:40],
    "trailing_tag_only": VALID + b"\x22",
    # records off the canonical shape
    "record_empty": _with_record(b""),
    "record_address_19": _with_record(_record(addr=ADDR[:19])),
    "record_address_21": _with_record(_record(addr=ADDR + b"\x01")),
    "record_no_address": _with_record(_record(addr=b"")),
    "record_signature_63": _with_record(_record(sig=SIG[:63])),
    "record_signature_65": _with_record(_record(sig=SIG + b"\x01")),
    "record_no_signature": _with_record(_record(sig=b"")),
    "record_flag_omitted": _with_record(_record(flag=0)),
    "record_flag_0_written": _with_record(_varint(1, 0) + _record(flag=0)),
    "record_flag_4": _with_record(_record(flag=4)),
    "record_flag_2_to_the_32_plus_2": _with_record(_record(flag=(1 << 32) + 2)),
    "absent_with_address": _with_record(_record(flag=1, ts=_ts(GO_ZERO_TIME_SECONDS, 0), sig=b"")),
    "absent_with_signature": _with_record(_record(flag=1, addr=b"", ts=_ts(GO_ZERO_TIME_SECONDS, 0))),
    "absent_with_unix_zero_time": _with_record(_record(flag=1, addr=b"", ts=b"", sig=b"")),
    "absent_with_nanos": _with_record(_record(flag=1, addr=b"", ts=_ts(GO_ZERO_TIME_SECONDS, 1), sig=b"")),
    "absent_with_a_real_time": _with_record(_record(flag=1, addr=b"", sig=b"")),
    "record_duplicate_flag": _with_record(_varint(1, 2) + _record()),
    "record_duplicate_address": _with_record(_record() + _bytes(2, ADDR)),
    "record_duplicate_timestamp": _with_record(_record() + _bytes(3, _ts())),
    "record_duplicate_signature": _with_record(_record() + _bytes(4, SIG)),
    "record_unknown_field": _with_record(_record() + _varint(5, 1)),
    "record_field_number_0": _with_record(_record() + b"\x00\x00"),
    "record_flag_as_bytes": _with_record(_bytes(1, b"\x02") + _record(flag=0)),
    "record_address_as_varint": _with_record(_varint(1, 2) + _varint(2, 5) + _bytes(3, _ts()) + _bytes(4, SIG)),
    "record_timestamp_as_varint": _with_record(_varint(1, 2) + _bytes(2, ADDR) + _varint(3, 5) + _bytes(4, SIG)),
    "record_signature_as_fixed64": _with_record(_varint(1, 2) + _bytes(2, ADDR) + _bytes(3, _ts()) + _tag(4, 1) + bytes(8)),
    "record_signature_past_the_record": _with_record(_varint(1, 2) + _bytes(2, ADDR) + _bytes(3, _ts()) + _tag(4, 2) + b"\x40" + SIG[:60]),
    "ts_unknown_field": _with_record(_record(ts=_ts() + _varint(3, 1))),
    "ts_duplicate_seconds": _with_record(_record(ts=_varint(1, 5) + _ts())),
    "ts_duplicate_nanos": _with_record(_record(ts=_ts() + _varint(2, 6))),
    "ts_seconds_as_fixed64": _with_record(_record(ts=_tag(1, 1) + bytes(8))),
    "ts_nanos_as_bytes": _with_record(_record(ts=_bytes(2, b"\x05"))),
    "ts_truncated_varint": _with_record(_record(ts=b"\x08\x80")),
    "ts_tag_only": _with_record(_record(ts=b"\x10")),
}

# one cut in each region of the message, by the byte it lands in
_R0 = len(HEIGHT + ROUND + BID) + 2  # first byte of the first record's body
TRUNCATION = {
    "cut_in_height": 1,
    "cut_after_round_tag": len(HEIGHT) + 1,
    "cut_in_block_id_length": len(HEIGHT + ROUND) + 1,
    "cut_in_block_id": len(HEIGHT + ROUND) + 20,
    "cut_after_record_tag": _R0 - 1,
    "cut_in_flag": _R0 + 1,
    "cut_in_address": _R0 + 10,
    "cut_in_timestamp": _R0 + 2 + 22 + 4,
    "cut_in_signature": _R0 + 2 + 22 + 12 + 30,
    "cut_in_absent_record": len(VALID) - len(_bytes(4, _record(flag=3))) - 5,
    "cut_last_byte": len(VALID) - 1,
}
DEVIANT.update({name: VALID[:cut] for name, cut in TRUNCATION.items()})


@pytest.mark.native_required
@pytest.mark.parametrize("case", sorted(DEVIANT))
def test_deviant_input_is_left_to_the_python_path(case):
    data = DEVIANT[case]
    assert _native_columns(data) is None
    _assert_same_outcome(data)


@pytest.mark.native_required
def test_every_truncation_of_a_valid_commit():
    # a cut on a field boundary leaves a shorter valid commit (columns);
    # a cut anywhere else must read None; both must match the Python path
    boundaries = 0
    for cut in range(len(VALID)):
        data = VALID[:cut]
        boundaries += _native_columns(data) is not None
        _assert_same_outcome(data)
    assert boundaries == 6  # empty, after 1, 2, 3 and each of two records


@pytest.mark.native_required
@pytest.mark.parametrize(
    "data",
    [bytearray(VALID), memoryview(VALID), "not bytes", None, 7],
    ids=["bytearray", "memoryview", "str", "None", "int"],
)
def test_native_pass_answers_none_for_what_is_not_bytes(data):
    assert _native_columns(data) is None


@pytest.mark.native_required
def test_bytearray_input_decodes_as_the_python_path_decodes_it():
    _assert_same_outcome(bytearray(VALID))


def _mutate(rng, data):
    buf = bytearray(data)
    for _ in range(rng.choice((1, 1, 1, 2, 3))):
        kind = rng.random()
        at = rng.randrange(len(buf))
        if kind < 0.6:
            buf[at] ^= 1 << rng.randrange(8)
        elif kind < 0.75:
            buf[at] = rng.choice((0x00, 0x7F, 0x80, 0xFF, 0x08, 0x12, 0x1A, 0x22))
        elif kind < 0.9:
            del buf[at]
        else:
            buf.insert(at, rng.randrange(256))
    return bytes(buf)


@pytest.mark.native_required
@pytest.mark.parametrize("seed", [27, 2027, 0x7FFFFFFF, 2**31 + 9])
def test_byte_flip_fuzz_keeps_the_two_paths_equal(seed):
    rng = random.Random(seed)
    valid = _commit([_sig(i, _mixed_flag(i)) for i in range(24)], height=seed)
    taken = refused = 0
    for _ in range(1500):
        data = _mutate(rng, valid)
        if _native_columns(data) is None:
            refused += 1
        else:
            taken += 1
        _assert_same_outcome(data)
    # flips inside a signature or an address stay canonical; flips in the
    # framing do not: a fuzz that never saw one side proves nothing
    assert taken > 100 and refused > 100, (taken, refused)


# -- counters ----------------------------------------------------------------


def _decode_counts():
    stats = ops_stats()
    return stats["commit_decode_native"], stats["commit_decode_python"]


@pytest.mark.native_required
def test_counters_move_by_one_on_the_path_taken():
    native0, python0 = _decode_counts()
    Commit.decode(VALID)
    assert _decode_counts() == (native0 + 1, python0)
    Commit.decode(DEVIANT["outer_unknown_field_appended"])
    assert _decode_counts() == (native0 + 1, python0 + 1)
    with pytest.raises(ValueError):
        Commit.decode(DEVIANT["trailing_ff"])
    assert _decode_counts() == (native0 + 1, python0 + 2)
    _python_decode(VALID)
    assert _decode_counts() == (native0 + 1, python0 + 3)


_NO_NATIVE_SCRIPT = """
import json, sys
from tendermint_tpu import native
from tendermint_tpu.libs.metrics import ops_stats
from tendermint_tpu.types.block import Commit, CommitSigs
data = bytes.fromhex(sys.argv[1])
commit = Commit.decode(data)
stats = ops_stats()
print(json.dumps({
    "module": native.load() is not None,
    "native": stats["commit_decode_native"],
    "python": stats["commit_decode_python"],
    "columnar": isinstance(commit.signatures, CommitSigs),
    "roundtrip": commit.encode() == data,
    "height": commit.height,
}))
"""


def test_without_the_module_only_the_python_counter_moves():
    env = dict(os.environ, TM_TPU_NO_NATIVE="1", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", _NO_NATIVE_SCRIPT, VALID.hex()],
        capture_output=True,
        env=env,
        cwd=REPO,
        timeout=60,
    )
    assert r.returncode == 0, (r.stderr or b"").decode(errors="replace")[-3000:]
    out = json.loads(r.stdout.decode().strip().splitlines()[-1])
    assert out == {
        "module": False,
        "native": 0,
        "python": 1,
        "columnar": True,
        "roundtrip": True,
        "height": 7,
    }


# -- the GIL and the speed ratio ---------------------------------------------


@pytest.fixture(scope="module")
def wire_10k():
    return _commit([_sig(i) for i in range(10_000)])


def _spin_rate(seconds):
    """Iterations a second of a pure-Python loop on this thread."""
    n = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        for _ in range(1000):
            n += 1
    return n / (time.perf_counter() - t0)


@pytest.mark.native_required
def test_decoding_10k_lanes_leaves_the_gil_to_a_python_thread(wire_10k):
    stop = threading.Event()
    decoded = [0]

    def decode_loop():
        while not stop.is_set():
            Commit.decode(wire_10k)
            decoded[0] += 1

    best = 0.0
    for _ in range(3):  # a busy machine can starve either reading: best of 3
        unloaded = _spin_rate(0.4)
        decoded[0] = 0
        stop.clear()
        worker = threading.Thread(target=decode_loop, daemon=True)
        worker.start()
        try:
            loaded = _spin_rate(0.4)
        finally:
            stop.set()
            worker.join(timeout=30)
        assert not worker.is_alive()
        assert decoded[0] >= 1, "the decoding thread never finished a commit"
        best = max(best, loaded / unloaded)
        if best >= 0.5:
            break
    # two pure-Python threads share the GIL about evenly, so the Python
    # walk leaves a spinner half its rate at most; the native walk holds
    # the GIL only to build five buffers
    assert best >= 0.5, f"the spinner kept {best:.2f} of its unloaded rate"


def _best_seconds(fn, data, repeats):
    # CPU seconds of this thread (the native walk runs on it, GIL or not):
    # sibling test workers preempting either walk cannot move the ratio
    best = float("inf")
    for _ in range(repeats):
        t0 = time.thread_time()
        fn(data)
        best = min(best, time.thread_time() - t0)
    return best


@pytest.mark.native_required
def test_native_decode_is_at_least_5x_the_python_walk(wire_10k):
    # a ratio on the same bytes in the same process, not a wall-clock
    # budget: ~150x on the sandbox's CPU when written
    _assert_same_commit(Commit.decode(wire_10k), _python_decode(wire_10k))
    native_s = _best_seconds(Commit.decode, wire_10k, 7)
    python_s = _best_seconds(_python_decode, wire_10k, 3)
    assert python_s >= 5 * native_s, (python_s, native_s)
