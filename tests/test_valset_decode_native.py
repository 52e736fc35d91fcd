"""ValidatorSet.decode's two paths (ISSUE 33): the native single-pass parser
(native/tm_native.cpp valset_decode_columns) against the Python walk that
specifies it. Canonical all-ed25519 sets must give the same set by both —
every field of every validator, the proposer, the power total, the hash and
the ed25519 columns; every deviant input must make the native pass answer
None and leave ValidatorSet.decode with exactly what the Python walk gives
alone — the same set, or the same exception type and message. Plus the two
ops_stats() counters and the GIL release. No wall-clock gate."""

import json
import os
import random
import subprocess
import sys
import threading
import time
import types
from unittest import mock

import numpy as np
import pytest

from tendermint_tpu import native as native_mod
from tendermint_tpu.crypto import bls12381, ed25519, secp256k1, sr25519
from tendermint_tpu.crypto.encoding import pubkey_to_proto
from tendermint_tpu.libs.metrics import ops_stats
from tendermint_tpu.types.validator_set import (
    MAX_TOTAL_VOTING_POWER,
    Validator,
    ValidatorSet,
)
from tendermint_tpu.wire.proto import encode_uvarint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
U64 = (1 << 64) - 1


# -- the two paths ----------------------------------------------------------


def _native_columns(data):
    return native_mod.load().valset_decode_columns(data)


def _python_decode(data):
    """ValidatorSet.decode as it runs without the module: the specification."""
    with mock.patch.object(native_mod, "load", lambda: None):
        return ValidatorSet.decode(data)


def _outcome(decode, data):
    try:
        return ("ok", decode(data))
    except Exception as e:  # noqa: BLE001 - the type is what is compared
        return ("err", type(e), str(e))


def _fields(v):
    return (
        bytes(v.address),
        type(v.pub_key),
        v.pub_key.bytes(),
        v.voting_power,
        v.proposer_priority,
    )


def _assert_same_set(a, b):
    assert len(a.validators) == len(b.validators)
    for x, y in zip(a.validators, b.validators):
        assert _fields(x) == _fields(y)
    assert a.validators == b.validators
    assert _fields(a.proposer) == _fields(b.proposer)
    # the proposer is an object of its own by both paths, never a row
    assert all(a.proposer is not v for v in a.validators)
    assert a.total_voting_power() == b.total_voting_power()
    assert a.hash() == b.hash()
    ca, cb = a.ed25519_columns(), b.ed25519_columns()
    assert (ca is None) == (cb is None)
    if ca is not None:
        for x, y in zip(ca, cb):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(x, y)
    assert a.encode() == b.encode()


def _assert_same_outcome(data):
    got = _outcome(ValidatorSet.decode, data)
    want = _outcome(_python_decode, data)
    assert got[0] == want[0], (got, want)
    if got[0] == "ok":
        _assert_same_set(got[1], want[1])
    else:
        assert got[1:] == want[1:]
    return got[0]


# -- canonical sets, through the repo's own encoder -------------------------


def _key(i):
    return ed25519.PubKey(random.Random(i).randbytes(32))


def _val(i, power=100, priority=0, key=None):
    pk = key or _key(i)
    return Validator(pk.address(), pk, power, priority)


def _set(vals, proposer=0):
    """Wire bytes of a set; `proposer` is a row number or a Validator."""
    p = vals[proposer] if isinstance(proposer, int) else proposer
    return ValidatorSet(validators=list(vals), proposer=p).encode()


def _wide(n):
    rng = random.Random(n)
    return [
        _val(i, rng.randrange(1, 1 << 40), rng.randrange(-(1 << 62), 1 << 62))
        for i in range(n)
    ]


CANONICAL = {
    "1_validator": lambda: _set([_val(0)]),
    "2_validators": lambda: _set([_val(0), _val(1)]),
    "100_validators": lambda: _set([_val(i) for i in range(100)]),
    "100_validators_wide": lambda: _set(_wide(100), proposer=57),
    "10000_validators": lambda: _set([_val(i) for i in range(10_000)]),
    "10000_validators_wide": lambda: _set(_wide(10_000), proposer=9_999),
    **{
        f"power_{name}": (lambda p=p: _set([_val(0), _val(1, power=p), _val(2)]))
        for name, p in {
            "0": 0,
            "1": 1,
            "127": 127,
            "128": 128,
            "2_to_the_59": 1 << 59,
            "max_total_less_200": MAX_TOTAL_VOTING_POWER - 200,
        }.items()
    },
    "all_powers_0": lambda: _set([_val(i, power=0) for i in range(3)]),
    **{
        f"priority_{name}": (
            lambda p=p: _set([_val(0, priority=p), _val(1, priority=-p)], proposer=1)
        )
        for name, p in {
            "1": 1,
            "minus_1": -1,
            "int64_max": (1 << 63) - 1,
            "int64_min_plus_1": -(1 << 63) + 1,
            "2_to_the_35": 1 << 35,
        }.items()
    },
    "priority_int64_min": lambda: _set([_val(0, priority=-(1 << 63)), _val(1)]),
    "proposer_first": lambda: _set([_val(i) for i in range(7)], proposer=0),
    "proposer_last": lambda: _set([_val(i) for i in range(7)], proposer=6),
    "proposer_not_in_the_set": lambda: _set(
        [_val(i) for i in range(4)], proposer=_val(99, power=5, priority=-3)
    ),
    "proposer_with_another_priority_than_its_row": lambda: _set(
        [_val(i) for i in range(4)], proposer=_val(2, priority=77)
    ),
    "same_key_twice": lambda: _set([_val(0), _val(0), _val(1)]),
    "address_of_another_key": lambda: _set(
        [_val(0), Validator(_key(5).address(), _key(1), 9, 9)]
    ),
}


@pytest.mark.native_required
@pytest.mark.parametrize("case", sorted(CANONICAL))
def test_canonical_set_decodes_alike_by_both_paths(case):
    data = CANONICAL[case]()
    cols = _native_columns(data)
    assert cols is not None, "the native pass refused a canonical set"
    n = cols[0]
    assert [len(c) for c in cols[1:7]] == [20 * n, 32 * n, 8 * n, 8 * n, 20, 32]
    got, want = ValidatorSet.decode(data), _python_decode(data)
    _assert_same_set(got, want)
    assert got.encode() == data
    # the columns the decode kept are the ones the objects would give
    kept = got.ed25519_columns()
    got._ed_cols = None
    for x, y in zip(kept, got.ed25519_columns()):
        assert x.dtype == y.dtype and np.array_equal(x, y)


# a set the native pass takes and the checks that follow it refuse, with
# the walk's own exception: the power total is recomputed by both paths
TAKEN_THEN_REFUSED = {
    "power_2_to_the_62": lambda: _set([_val(0), _val(1, power=1 << 62)]),
    "power_sum_past_the_maximum": lambda: _set(
        [_val(0, power=MAX_TOTAL_VOTING_POWER), _val(1, power=1)]
    ),
    "power_sum_past_int64": lambda: _set(
        [_val(i, power=(1 << 63) - 1) for i in range(3)]
    ),
}


@pytest.mark.native_required
@pytest.mark.parametrize("case", sorted(TAKEN_THEN_REFUSED))
def test_power_total_is_recomputed_after_the_native_pass(case):
    data = TAKEN_THEN_REFUSED[case]()
    assert _native_columns(data) is not None
    got = _outcome(ValidatorSet.decode, data)
    assert got[:2] == ("err", OverflowError)
    assert "total voting power exceeds max" in got[2]
    assert _assert_same_outcome(data) == "err"


# -- hand-built wire, for what the encoder never writes ---------------------

ADDR = bytes(range(100, 120))
KEY = bytes(range(32))


def _tag(field, wire_type):
    return encode_uvarint((field << 3) | wire_type)


def _varint(field, value):
    return _tag(field, 0) + encode_uvarint(value & U64)


def _bytes(field, value):
    return _tag(field, 2) + encode_uvarint(len(value)) + value


ED_KEY = _bytes(1, KEY)


def _record(addr=ADDR, key=ED_KEY, power=10, priority=-4):
    return (
        (_bytes(1, addr) if addr is not None else b"")
        + (_bytes(2, key) if key is not None else b"")
        + (_varint(3, power) if power else b"")
        + (_varint(4, priority) if priority else b"")
    )


V1 = _bytes(1, _record())
V2 = _bytes(1, _record(addr=ADDR[::-1], key=_bytes(1, KEY[::-1]), power=1 << 40, priority=0))
V3 = _bytes(1, _record(power=0, priority=(1 << 63) - 1))
PROPOSER = _bytes(2, _record())
VALID = V1 + V2 + V3 + PROPOSER


def _with_record(record):
    return V1 + _bytes(1, record) + PROPOSER


def test_hand_built_wire_is_what_the_encoder_writes():
    k1, k2 = ed25519.PubKey(KEY), ed25519.PubKey(KEY[::-1])
    vals = [
        Validator(ADDR, k1, 10, -4),
        Validator(ADDR[::-1], k2, 1 << 40, 0),
        Validator(ADDR, k1, 0, (1 << 63) - 1),
    ]
    assert ValidatorSet(validators=vals, proposer=vals[0]).encode() == VALID


# canonical for both paths though the encoder never writes it: the two
# decodes must agree, and the native pass takes these
OFF_ENCODER_TAKEN = {
    "proposer_first": PROPOSER + V1 + V2 + V3,
    "proposer_between_validators": V1 + PROPOSER + V2,
    "total_voting_power_present_and_right": VALID + _varint(3, 10 + (1 << 40)),
    "total_voting_power_present_and_wrong": VALID + _varint(3, 7),
    "total_voting_power_negative": V1 + _varint(3, -1) + PROPOSER,
    "total_voting_power_first": _varint(3, 1 << 62) + VALID,
    "record_fields_reversed": PROPOSER + _bytes(
        1, _varint(4, 3) + _varint(3, 5) + _bytes(2, ED_KEY) + _bytes(1, ADDR)
    ),
    "zero_power_and_priority_written": PROPOSER + _bytes(
        1, _bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(3, 0) + b"\x00" + _tag(4, 0) + b"\x00"
    ),
    "non_minimal_power_varint": PROPOSER + _bytes(
        1, _bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(3, 0) + b"\x8a\x80\x00"
    ),
    "non_minimal_record_length": PROPOSER + _tag(1, 2) + bytes([0x80 | len(_record()), 0]) + _record(),
    "power_omitted": _with_record(_record(power=0)),
}

# ... and these it may take or leave: equal result either way
OFF_ENCODER = {
    **OFF_ENCODER_TAKEN,
    "outer_unknown_field_appended": VALID + _varint(5, 9),
    "outer_unknown_bytes_field_first": _bytes(7, b"xy") + VALID,
    "record_unknown_field": _with_record(_record() + _varint(5, 1)),
    "pub_key_unknown_field_after_the_key": _with_record(
        _record(key=ED_KEY + _varint(9, 1))
    ),
    "total_voting_power_twice": VALID + _varint(3, 1) + _varint(3, 2),
    "total_voting_power_as_fixed64": VALID + _tag(3, 1) + bytes(8),
    "total_voting_power_as_bytes": VALID + _bytes(3, b"\x01"),
    "proposer_twice_the_last_counts": V1 + PROPOSER + _bytes(2, _record(power=77)),
    "record_power_twice_the_last_counts": _with_record(_record() + _varint(3, 3)),
    "record_address_twice_the_last_counts": _with_record(_bytes(1, ADDR[::-1]) + _record()),
    "record_key_twice_the_last_counts": _with_record(_record() + _bytes(2, _bytes(1, KEY[::-1]))),
    "pub_key_ed25519_twice_the_last_counts": _with_record(
        _record(key=_bytes(1, KEY[::-1]) + ED_KEY)
    ),
    "power_as_fixed64": _with_record(
        _bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(3, 1) + (9).to_bytes(8, "little")
    ),
    "priority_as_fixed32": _with_record(
        _bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(4, 5) + (9).to_bytes(4, "little")
    ),
    "non_minimal_outer_tag": b"\x8a\x00" + V1[1:] + PROPOSER,
}


@pytest.mark.native_required
@pytest.mark.parametrize("case", sorted(OFF_ENCODER))
def test_canonical_wire_the_encoder_never_writes(case):
    data = OFF_ENCODER[case]
    if case in OFF_ENCODER_TAKEN:
        assert _native_columns(data) is not None
    assert _assert_same_outcome(data) == "ok"


def _other_key(mod, size, i=1):
    return _bytes(
        {secp256k1: 2, sr25519: 3, bls12381: 4}[mod],
        random.Random(i).randbytes(size),
    )


SECP_KEY = _other_key(secp256k1, 33)
SR_KEY = _other_key(sr25519, 32)
BLS_KEY = _other_key(bls12381, 48)

OVERLONG_11 = b"\x80" * 10 + b"\x01"  # an 11-byte varint
TEN_BYTES_70_BITS = b"\xff" * 9 + b"\x7f"  # Python reads 70 bits of it
TEN_BYTES_65_BITS = b"\x80" * 9 + b"\x02"

DEVIANT = {
    # other members of the PublicKey oneof keep the walk
    "all_secp256k1": _bytes(1, _record(key=SECP_KEY)) + _bytes(2, _record(key=SECP_KEY)),
    "all_sr25519": _bytes(1, _record(key=SR_KEY)) + _bytes(2, _record(key=SR_KEY)),
    "all_bls12381": _bytes(1, _record(key=BLS_KEY)) + _bytes(2, _record(key=BLS_KEY)),
    "mixed_one_secp256k1_row": _with_record(_record(key=SECP_KEY)),
    "mixed_one_sr25519_row": _with_record(_record(key=SR_KEY)),
    "mixed_one_bls12381_row": _with_record(_record(key=BLS_KEY)),
    "mixed_secp256k1_proposer": V1 + _bytes(2, _record(key=SECP_KEY)),
    "pub_key_ed25519_and_secp256k1": _with_record(_record(key=ED_KEY + SECP_KEY)),
    "pub_key_sr25519_then_ed25519": _with_record(_record(key=SR_KEY + ED_KEY)),
    # keys and addresses of another size
    "key_31_bytes": _with_record(_record(key=_bytes(1, KEY[:31]))),
    "key_33_bytes": _with_record(_record(key=_bytes(1, KEY + b"\x01"))),
    "key_empty": _with_record(_record(key=_bytes(1, b""))),
    "key_non_minimal_length": _with_record(_record(key=_tag(1, 2) + b"\xa0\x00" + KEY)),
    "secp256k1_key_32_bytes": _with_record(_record(key=_bytes(2, KEY))),
    "pub_key_message_empty": _with_record(_record(key=b"")),
    "pub_key_unknown_oneof_member": _with_record(_record(key=_bytes(5, KEY))),
    "pub_key_ed25519_as_varint": _with_record(_record(key=_varint(1, 5))),
    "pub_key_truncated_inside": _with_record(_record(key=ED_KEY[:20])),
    "pub_key_missing": _with_record(_record(key=None)),
    "address_19_bytes": _with_record(_record(addr=ADDR[:19])),
    "address_21_bytes": _with_record(_record(addr=ADDR + b"\x01")),
    "address_empty": _with_record(_record(addr=b"")),
    "address_missing": _with_record(_record(addr=None)),
    "proposer_address_19_bytes": V1 + _bytes(2, _record(addr=ADDR[:19])),
    "proposer_key_31_bytes": V1 + _bytes(2, _record(key=_bytes(1, KEY[:31]))),
    "proposer_key_missing": V1 + _bytes(2, _record(key=None)),
    "proposer_message_empty": V1 + _bytes(2, b""),
    # what validate_basic refuses
    "proposer_missing": V1 + V2,
    "empty_message": b"",
    "empty_set_proposer_only": PROPOSER,
    "empty_set_total_only": _varint(3, 5),
    "negative_power": _with_record(_record(power=-1)),
    "negative_power_int64_min": _with_record(_record(power=-(1 << 63))),
    "negative_proposer_power": V1 + _bytes(2, _record(power=-5)),
    "validator_message_empty": V1 + _bytes(1, b"") + PROPOSER,
    # wrong wire types
    "validator_as_varint": V1 + _varint(1, 5) + PROPOSER,
    "validator_as_fixed64": V1 + _tag(1, 1) + bytes(8) + PROPOSER,
    "proposer_as_varint": V1 + _varint(2, 5),
    "proposer_as_fixed32": V1 + _tag(2, 5) + bytes(4),
    "address_as_varint": _with_record(_varint(1, 5) + _bytes(2, ED_KEY)),
    "key_as_varint": _with_record(_bytes(1, ADDR) + _varint(2, 5)),
    "power_as_bytes": _with_record(_bytes(1, ADDR) + _bytes(2, ED_KEY) + _bytes(3, b"\x05")),
    "priority_as_bytes": _with_record(_bytes(1, ADDR) + _bytes(2, ED_KEY) + _bytes(4, b"")),
    "outer_group_wire_type": V1 + _tag(3, 3) + PROPOSER,
    "record_group_wire_type": _with_record(_record() + _tag(5, 4)),
    "outer_field_number_0": b"\x00\x01" + VALID,
    "record_field_number_0": _with_record(_record() + b"\x00\x00"),
    # varints
    "power_varint_11_bytes": _with_record(_bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(3, 0) + OVERLONG_11),
    "power_varint_70_bits": _with_record(_bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(3, 0) + TEN_BYTES_70_BITS),
    "power_varint_65_bits": _with_record(_bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(3, 0) + TEN_BYTES_65_BITS),
    "priority_varint_70_bits": _with_record(_bytes(1, ADDR) + _bytes(2, ED_KEY) + _tag(4, 0) + TEN_BYTES_70_BITS),
    "total_voting_power_varint_70_bits": VALID + _tag(3, 0) + TEN_BYTES_70_BITS,
    "total_voting_power_varint_11_bytes": VALID + _tag(3, 0) + OVERLONG_11,
    "record_length_varint_11_bytes": V1 + PROPOSER + _tag(1, 2) + OVERLONG_11,
    "record_length_past_the_end": V1 + PROPOSER + _tag(1, 2) + b"\xff\xff\xff\xff\x0f",
    "record_length_2_to_the_63": V1 + PROPOSER + _tag(1, 2) + b"\x80" * 9 + b"\x01",
    "key_length_past_the_record": _with_record(_bytes(1, ADDR) + _tag(2, 2) + b"\x30" + ED_KEY),
    # trailing bytes
    "trailing_zero_byte": VALID + b"\x00",
    "trailing_ff": VALID + b"\xff",
    "trailing_half_a_validator": VALID + V2[:30],
    "trailing_tag_only": VALID + b"\x0a",
}


@pytest.mark.native_required
@pytest.mark.parametrize("case", sorted(DEVIANT))
def test_deviant_input_is_left_to_the_python_path(case):
    data = DEVIANT[case]
    assert _native_columns(data) is None
    _assert_same_outcome(data)


# the walk's own words, so a second author of any of them shows here
WALK_SAYS = {
    "proposer_missing": (ValueError, "proposer failed validate basic: nil"),
    "empty_message": (ValueError, "validator set is nil or empty"),
    "empty_set_proposer_only": (ValueError, "validator set is nil or empty"),
    "negative_power": (ValueError, "invalid validator #1: validator has negative voting power"),
    "negative_proposer_power": (ValueError, "validator has negative voting power"),
    "address_19_bytes": (ValueError, "invalid validator #1: validator address is the wrong size"),
    "proposer_address_19_bytes": (ValueError, "validator address is the wrong size"),
    "key_31_bytes": (ValueError, "ed25519 pubkey must be 32 bytes"),
    "key_33_bytes": (ValueError, "ed25519 pubkey must be 32 bytes"),
    "pub_key_missing": (ValueError, "unknown or empty PublicKey oneof"),
    "secp256k1_key_32_bytes": (ValueError, "secp256k1 pubkey must be 33 bytes"),
    "trailing_ff": (ValueError, "truncated uvarint"),
    "power_varint_11_bytes": (ValueError, "uvarint overflow"),
    "validator_as_varint": (ValueError, "repeated field 1: expected bytes, got wire type 0"),
    "power_as_bytes": (ValueError, "field 3: expected scalar, got length-delimited"),
    "outer_group_wire_type": (ValueError, "unsupported wire type 3"),
}


@pytest.mark.parametrize("case", sorted(WALK_SAYS))
def test_refusals_carry_the_walks_own_type_and_message(case):
    want_type, want_msg = WALK_SAYS[case]
    got = _outcome(ValidatorSet.decode, DEVIANT[case])
    assert got == ("err", want_type, want_msg)


@pytest.mark.parametrize(
    "case",
    ["all_secp256k1", "all_sr25519", "all_bls12381", "mixed_one_secp256k1_row",
     "mixed_one_sr25519_row", "mixed_one_bls12381_row", "mixed_secp256k1_proposer"],
)
def test_sets_of_other_key_types_decode_by_the_walk(case):
    vs = ValidatorSet.decode(DEVIANT[case])
    rows = {type(v.pub_key) for v in vs.validators}
    assert (rows | {type(vs.proposer.pub_key)}) - {ed25519.PubKey}
    # the columns cover the rows, not the proposer
    assert (vs.ed25519_columns() is None) == bool(rows - {ed25519.PubKey})
    assert vs.encode() == DEVIANT[case]
    for v in vs.validators:
        assert pubkey_to_proto(v.pub_key) in DEVIANT[case]


@pytest.mark.native_required
@pytest.mark.parametrize(
    "layout,boundaries",
    [(VALID, 0), (PROPOSER + V1 + V2 + V3, 2)],
    ids=["as_the_encoder_writes", "proposer_first"],
)
def test_every_truncation_of_a_valid_set(layout, boundaries):
    # a cut on a field boundary past the proposer and one validator leaves
    # a shorter valid set (columns); a cut anywhere else must read None;
    # both must match the Python path
    taken = 0
    for cut in range(len(layout)):
        data = layout[:cut]
        taken += _native_columns(data) is not None
        _assert_same_outcome(data)
    assert taken == boundaries


@pytest.mark.native_required
@pytest.mark.parametrize(
    "data",
    [bytearray(VALID), memoryview(VALID), "not bytes", None, 7],
    ids=["bytearray", "memoryview", "str", "None", "int"],
)
def test_native_pass_answers_none_for_what_is_not_bytes(data):
    assert _native_columns(data) is None


@pytest.mark.parametrize("kind", [bytearray, memoryview], ids=["bytearray", "memoryview"])
def test_other_buffers_decode_as_the_python_path_decodes_them(kind):
    assert _assert_same_outcome(kind(VALID)) == "ok"
    _assert_same_set(ValidatorSet.decode(kind(VALID)), ValidatorSet.decode(VALID))


def _mutate(rng, data):
    buf = bytearray(data)
    for _ in range(rng.choice((1, 1, 1, 2, 3))):
        kind = rng.random()
        at = rng.randrange(len(buf))
        if kind < 0.6:
            buf[at] ^= 1 << rng.randrange(8)
        elif kind < 0.75:
            buf[at] = rng.choice((0x00, 0x7F, 0x80, 0xFF, 0x0A, 0x12, 0x18, 0x20))
        elif kind < 0.9:
            del buf[at]
        else:
            buf.insert(at, rng.randrange(256))
    return bytes(buf)


@pytest.mark.native_required
@pytest.mark.parametrize("seed", [33, 2033, 0x7FFFFFFF, 2**31 + 9])
def test_byte_flip_fuzz_keeps_the_two_paths_equal(seed):
    rng = random.Random(seed)
    valid = _set(_wide(12), proposer=seed % 12)
    taken = refused = 0
    for _ in range(1500):
        data = _mutate(rng, valid)
        if _native_columns(data) is None:
            refused += 1
        else:
            taken += 1
        _assert_same_outcome(data)
    # flips inside a key or an address stay canonical; flips in the framing
    # do not: a fuzz that never saw one side proves nothing
    assert taken > 100 and refused > 100, (taken, refused)


# -- counters ----------------------------------------------------------------


def _decode_counts():
    stats = ops_stats()
    return stats["valset_decode_native"], stats["valset_decode_python"]


@pytest.mark.native_required
def test_counters_move_by_one_on_the_path_taken():
    native0, python0 = _decode_counts()
    ValidatorSet.decode(VALID)
    assert _decode_counts() == (native0 + 1, python0)
    ValidatorSet.decode(DEVIANT["all_sr25519"])
    assert _decode_counts() == (native0 + 1, python0 + 1)
    with pytest.raises(ValueError):
        ValidatorSet.decode(DEVIANT["trailing_ff"])
    assert _decode_counts() == (native0 + 1, python0 + 2)
    _python_decode(VALID)
    assert _decode_counts() == (native0 + 1, python0 + 3)


def test_a_module_without_the_function_reads_as_absent():
    # native/_build is not tracked: a stale .so built before this entry
    # point must send every set down the walk, never raise
    stale = types.SimpleNamespace(commit_decode_columns=lambda data: None)
    native0, python0 = _decode_counts()
    with mock.patch.object(native_mod, "load", lambda: stale):
        got = ValidatorSet.decode(VALID)
    assert _decode_counts() == (native0, python0 + 1)
    _assert_same_set(got, _python_decode(VALID))


_NO_NATIVE_SCRIPT = """
import json, sys
from tendermint_tpu import native
from tendermint_tpu.libs.metrics import ops_stats
from tendermint_tpu.types.validator_set import ValidatorSet
data = bytes.fromhex(sys.argv[1])
vs = ValidatorSet.decode(data)
cols = vs.ed25519_columns()
stats = ops_stats()
print(json.dumps({
    "module": native.load() is not None,
    "native": stats["valset_decode_native"],
    "python": stats["valset_decode_python"],
    "roundtrip": vs.encode() == data,
    "hash": vs.hash().hex(),
    "total": vs.total_voting_power(),
    "columns": [list(cols[0].shape), cols[1].tolist()],
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
    here = ValidatorSet.decode(VALID)
    assert out == {
        "module": False,
        "native": 0,
        "python": 1,
        "roundtrip": True,
        "hash": here.hash().hex(),
        "total": 10 + (1 << 40),
        "columns": [[3, 32], [10, 1 << 40, 0]],
    }


# -- the GIL -----------------------------------------------------------------


@pytest.fixture(scope="module")
def wire_10k():
    return _set([_val(i) for i in range(10_000)])


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
def test_parsing_10k_validators_leaves_the_gil_to_a_python_thread(wire_10k):
    parse = native_mod.load().valset_decode_columns
    assert parse(wire_10k)[0] == 10_000
    stop = threading.Event()
    parsed = [0]

    def parse_loop():
        while not stop.is_set():
            parse(wire_10k)
            parsed[0] += 1

    best = 0.0
    for _ in range(3):  # a busy machine can starve either reading: best of 3
        unloaded = _spin_rate(0.4)
        parsed[0] = 0
        stop.clear()
        worker = threading.Thread(target=parse_loop, daemon=True)
        worker.start()
        try:
            loaded = _spin_rate(0.4)
        finally:
            stop.set()
            worker.join(timeout=30)
        assert not worker.is_alive()
        assert parsed[0] >= 1, "the parsing thread never finished a set"
        best = max(best, loaded / unloaded)
        if best >= 0.5:
            break
    # two pure-Python threads share the GIL about evenly, so a walk that
    # held it would leave the spinner half its rate at most; the native
    # walk holds the GIL only to build six buffers
    assert best >= 0.5, f"the spinner kept {best:.2f} of its unloaded rate"
