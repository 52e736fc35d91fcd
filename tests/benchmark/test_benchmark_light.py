"""The light-client cell's yardstick against the program on the CPU: the
chain builder's wire bytes, Merkle hashes and sign-bytes are the program's
byte for byte on a 10-validator, 12-header chain (the sequential path, no
kernel compiles); its plain reference says what light.verifier.verify_adjacent
says on every block built to fail; LightBlock.decode is the inverse of
encode and refuses cut bytes; and the driver, on a 100-validator chain
through the device path with the epoch cache on, returns the signature
counts the program's counter shows and passes its own check."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import lightchain, reference_light, spec, wire  # noqa: E402

TINY = {"name": "tiny", "validators": 10, "voting_power": 100,
        "chain_id": "bench-tiny", "headers": 12, "keys_replaced_per_height": 1,
        "block_interval_s": 60, "trusting_period_s": 86400,
        "max_clock_drift_s": 10}
FX100 = dict(TINY, name="fx100", validators=100, chain_id="bench-fx100",
             headers=7)
SEED = 2 ** 31 + 5
CASE_IDS = [f"{what}@{h}" for h in (11, 12) for what in lightchain.CASES]


@pytest.fixture(scope="module")
def tiny():
    """(builder's records, pool, program's decoded light blocks, now)."""
    from tendermint_tpu.light.provider import LightBlock
    from tendermint_tpu.wire.canonical import Timestamp

    _keys, blocks = lightchain.chain(TINY, SEED)
    pool = lightchain.build(TINY, SEED)
    return (blocks, pool, [LightBlock.decode(w) for w in pool.blocks],
            Timestamp(*pool.now))


def test_wire_bytes_hashes_and_sign_bytes_are_the_programs(tiny):
    blocks, pool, decoded, _now = tiny
    assert len(blocks) == len(decoded) == 12
    for blk, lb, raw in zip(blocks, decoded, pool.blocks):
        assert lb.encode() == raw == lightchain.light_block_wire(blk)
        sh = lb.signed_header
        assert sh.header.hash() == lightchain.header_hash(blk.header) \
            == blk.block_hash == sh.commit.block_id.hash
        assert lb.validators.hash() == lightchain.valset_hash(blk.vals) \
            == sh.header.validators_hash
        assert [v.address for v in lb.validators.validators] == \
            [v.address for v in blk.vals]
        tpl = wire.sign_bytes_template(TINY["chain_id"], blk.height,
                                       blk.block_hash)
        for idx in (0, 4, 9):
            seconds, nanos, sig = blk.sigs[idx]
            assert sh.commit.vote_sign_bytes(TINY["chain_id"], idx) == \
                wire.sign_bytes(tpl, seconds, nanos)
            assert sh.commit.signatures[idx].signature == sig
    for prev, nxt in zip(decoded, decoded[1:]):
        a, b = prev.signed_header.header, nxt.signed_header.header
        assert a.next_validators_hash == b.validators_hash
        assert b.last_block_id.hash == a.hash()
        old = {v.address for v in prev.validators.validators}
        new = {v.address for v in nxt.validators.validators}
        assert len(old - new) == len(new - old) == 1, "one key a height"


def test_same_seed_same_bytes_and_the_pool_cache_round_trips(tiny, tmp_path):
    _blocks, pool, _decoded, _now = tiny
    first = lightchain.pool(str(tmp_path), TINY, SEED)
    again = lightchain.pool(str(tmp_path), TINY, SEED)
    assert first.built and not again.built
    assert again.blocks == pool.blocks == first.blocks
    assert [[(c.what, c.wire, c.expect) for c in g] for g in again.blame] == \
           [[(c.what, c.wire, c.expect) for c in g] for g in pool.blame]
    assert (again.now, again.trusting_period_s) == (pool.now, 86400)
    assert lightchain.build(TINY, SEED + 1).blocks[0] != pool.blocks[0]


@pytest.mark.parametrize("case", range(10), ids=CASE_IDS)
def test_the_reference_says_what_verify_adjacent_says(tiny, case):
    from tendermint_tpu.light import verifier
    from tendermint_tpu.light.provider import LightBlock

    _blocks, pool, decoded, now = tiny
    c = [c for group in pool.blame for c in group][case]
    assert c.what == CASE_IDS[case]
    lb = LightBlock.decode(c.wire)
    try:
        verifier.verify_adjacent(
            decoded[c.height - 2].signed_header, lb.signed_header,
            lb.validators, float(pool.trusting_period_s), now,
            float(pool.max_clock_drift_s))
        got = None
    except Exception as e:  # noqa: BLE001 — the verdict IS the error
        got = (type(e).__name__, str(e))
    assert got == c.expect
    kind = c.what.split("@")[0]
    if kind == "forged_past":
        assert got is None, "past the early stop nothing is looked at"
    else:
        assert got[0] == "ErrInvalidHeader"
        assert {"forged_within": "wrong signature (#",
                "departed_key": "wrong signature (#",
                "swapped_valset": "expected new header validators (",
                "starved": "insufficient voting power: got 600, needed more "
                           "than 666"}[kind] in got[1]


@pytest.mark.parametrize("what,change,says", [
    ("not_adjacent", dict(height=5), "headers must be adjacent in height"),
    ("expired", dict(now=(1_700_000_000 + 60 * 3 + 86400 + 1, 0)),
     "old header has expired at Timestamp(seconds=1700086581, nanos=0)"),
    ("from_the_future", dict(drift=-10 ** 6),
     "new header has a time from the future (max clock drift exceeded)"),
])
def test_the_reference_orders_the_header_checks_as_the_program(tiny, what,
                                                               change, says):
    from tendermint_tpu.light import verifier
    from tendermint_tpu.wire.canonical import Timestamp

    blocks, pool, decoded, _now = tiny
    h = change.get("height", 4)
    now = change.get("now", pool.now)
    drift = change.get("drift", pool.max_clock_drift_s)
    want = reference_light.verify_adjacent(
        blocks[2], blocks[h - 1], blocks[h - 1].vals, pool.trusting_period_s,
        now, drift)
    with pytest.raises(ValueError) as e:
        verifier.verify_adjacent(
            decoded[2].signed_header, decoded[h - 1].signed_header,
            decoded[h - 1].validators, float(pool.trusting_period_s),
            Timestamp(*now), float(drift))
    assert (type(e.value).__name__, str(e.value)) == want
    assert want[1] == says


# -- LightBlock.decode ------------------------------------------------------------


def test_decode_is_the_inverse_of_encode(tiny):
    from tendermint_tpu.light.provider import LightBlock
    from tendermint_tpu.types.block import SignedHeader

    _blocks, pool, decoded, _now = tiny
    lb = decoded[3]
    again = LightBlock.decode(lb.encode())
    assert again.signed_header == lb.signed_header
    assert again.validators.validators == lb.validators.validators
    assert again.validators.proposer == lb.validators.proposer
    assert again.height == 4 and again.hash() == lb.hash()
    sh = SignedHeader.decode(lb.signed_header.encode())
    assert sh == lb.signed_header and sh.encode() == lb.signed_header.encode()
    # the commit came through the native column pass, as Commit.decode's
    assert sh.commit.commit_block() is not None
    # a signed header with a part missing decodes to what validate_basic names
    with pytest.raises(ValueError, match="missing commit"):
        SignedHeader.decode(SignedHeader(header=sh.header).encode()) \
            .validate_basic(TINY["chain_id"])


@pytest.mark.parametrize("cut", [0, 1, 2, 300, 700, 1500, 1728, 2000, -1])
def test_decode_refuses_truncated_bytes(tiny, cut):
    from tendermint_tpu.light.provider import LightBlock

    raw = tiny[1].blocks[5]
    with pytest.raises(ValueError):
        LightBlock.decode(raw[:cut])


# -- the driver, through the device path ---------------------------------------------


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import jax

    from tendermint_tpu.ops import epoch_cache

    driver = spec.load_driver(os.path.join(ROOT, "benchmark"),
                              "light_adjacent_from_wire")
    root = str(tmp_path_factory.mktemp("checkout"))
    epoch_cache.reset(depth=8)
    try:
        yield driver.session_class()(FX100, SEED, root, jax.devices(),
                                     lambda m: None)
    finally:
        epoch_cache.reset()


@pytest.mark.time_limit(600)
def test_the_driver_counts_the_signatures_the_device_verified(session):
    s = session
    assert s.n_sigs == 67 and s.n_pool == 6
    c0 = s.counters()
    lines = []
    s.warm({"generator": {"kind": "closed_loop", "callers": 1}}, lines.append)
    assert s.setup["compile_s"] >= 0 and "trace_lower_s" in s.setup
    c1 = s.counters()
    assert sum(s.request(i) for i in range(s.n_pool)) == 6 * 67
    c2 = s.counters()
    # warm-up walked the chain once, the loop again: 67 a request, on the
    # device, one launch each
    for a, b in ((c0, c1), (c1, c2)):
        assert b["sigs_verified_device"] - a["sigs_verified_device"] == 6 * 67
        assert b["launches"] - a["launches"] == 6
        assert b["sigs_verified_host"] == a["sigs_verified_host"]
    # first sight built a table; the five sets after it mapped onto it, one
    # key appended each; the second walk found every set's hash again
    assert (c1["epoch_tables_built"] - c0["epoch_tables_built"],
            c1["epoch_tables_shared"] - c0["epoch_tables_shared"],
            c1["epoch_rows_patched"] - c0["epoch_rows_patched"]) == (1, 5, 5)
    assert c2["epoch_cache_hits"] - c1["epoch_cache_hits"] == 6
    assert c2["epoch_cache_misses"] == c1["epoch_cache_misses"]
    assert c1["sigs_per_request"] == 67


@pytest.mark.time_limit(600)
def test_the_drivers_check_passes_and_fails_on_a_wrong_verdict(session,
                                                               monkeypatch):
    s = session
    assert s.check() == []
    real = s._verify

    def accept_all(*a):
        try:
            real(*a)
        except ValueError:
            pass

    monkeypatch.setattr(s, "_verify", accept_all)
    bad = s.check()
    assert len(bad) == 4 and all("raised None" in b for b in bad), bad
    stats = s._ops_stats
    monkeypatch.setattr(s, "_verify", real)
    monkeypatch.setattr(
        s, "_ops_stats",
        lambda: dict(stats(), dispatch_errors=stats()["dispatch_errors"] + 1))
    assert any("dispatch_errors moved" in b for b in s.check())


def test_the_driver_refuses_a_program_without_the_entry_point(monkeypatch):
    from tendermint_tpu.light import provider

    driver = spec.load_driver(os.path.join(ROOT, "benchmark"),
                              "light_adjacent_from_wire")
    monkeypatch.delattr(provider.LightBlock, "decode")
    with pytest.raises(SystemExit, match="no LightBlock.decode"):
        driver.open(FX100, SEED, ROOT, 1, lambda m: None)
