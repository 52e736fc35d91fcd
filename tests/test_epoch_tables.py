"""Validator sets that share a device table of public-key rows (PR 32):
a set never seen maps, key by key, onto a resident table and appends what
the table lacks. Verdict rows and blame strings are held against the
uncached kernel, the sequential host path and the pure-Python oracle
(crypto/_edwards.py) over seeded churn; a departed key whose row is still
resident never verifies; a table value handed to a launch is never written;
blocks of different sets fuse exactly when they name one table.

Sizes stay in the vp=128 / bucket-128 shape class tests/test_epoch_cache.py
compiles, so this file traces nothing of its own but the patch program."""

import hashlib
import random
import threading

import numpy as np
import pytest

try:
    from tendermint_tpu.crypto import ed25519
except ModuleNotFoundError:
    pytest.skip("ed25519 backend unavailable", allow_module_level=True)

from tendermint_tpu.crypto import _edwards
from tendermint_tpu.libs import metrics as _metrics
from tendermint_tpu.ops import _testing, backend, epoch_cache, pipeline
from tendermint_tpu.ops import ed25519_verify as ev
from tendermint_tpu.types import Vote, validation
from tendermint_tpu.types.block import (
    BLOCK_ID_FLAG_COMMIT,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.types.vote import PRECOMMIT_TYPE
from tendermint_tpu.wire.canonical import Timestamp

CHAIN_ID = "epoch-tables-test"
BID = BlockID(hash=b"\x11" * 32,
              part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
TS = Timestamp(seconds=1_700_000_000)
N = 80           # 80 keys in a 128-row table: 47 free rows


@pytest.fixture(autouse=True)
def _fresh_cache():
    epoch_cache.reset(depth=8)
    yield
    epoch_cache.reset()


def _sk(i: int):
    return ed25519.gen_priv_key(hashlib.sha256(b"tables-key-%d" % i).digest())


SKS = {}


def _vset(members: dict) -> ValidatorSet:
    """members: key index -> voting power."""
    for i in members:
        SKS.setdefault(i, _sk(i))
    return ValidatorSet.new([Validator.new(SKS[i].pub_key(), p)
                             for i, p in members.items()])


def _by_pub():
    return {sk.pub_key().bytes(): sk for sk in SKS.values()}


def _commit(vset, height=7, bad=(), signer=None) -> Commit:
    """Every validator of the set signs, in the set's order; `bad` rows
    carry a broken signature, `signer` maps a row to the key that signs
    there instead of the row's own."""
    by_pub, sigs = _by_pub(), []
    for i, val in enumerate(vset.validators):
        v = Vote(type=PRECOMMIT_TYPE, height=height, round=0, block_id=BID,
                 timestamp=TS, validator_address=val.address,
                 validator_index=i)
        sk = (signer or {}).get(i) or by_pub[val.pub_key.bytes()]
        sig = sk.sign(v.sign_bytes(CHAIN_ID))
        if i in bad:
            sig = sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]
        sigs.append(CommitSig(block_id_flag=BLOCK_ID_FLAG_COMMIT,
                              validator_address=val.address, timestamp=TS,
                              signature=sig))
    return Commit.decode(Commit(height=height, round=0, block_id=BID,
                                signatures=sigs).encode())


def _block(vset, commit, stop_after=None):
    """The commit's EntryBlock as the commit path builds it, with the
    reference's conclude: every lane, or — the light client's early stop —
    the first lanes whose power passes `stop_after`."""
    if stop_after is not None:
        return validation.prepare_commit_batch(
            CHAIN_ID, vset, commit, stop_after,
            validation._ignore_not_for_block, validation._count_all, False,
            True)
    return validation.prepare_commit_batch(
        CHAIN_ID, vset, commit, vset.total_voting_power() - 1,
        validation._ignore_absent, validation._count_for_block, True, True)


def _rows(blk):
    """(cached verdicts, uncached verdicts, oracle verdicts) of one block."""
    ep = epoch_cache.lookup(blk)
    assert ep is not None, "the block names no resident table"
    bucket = backend._bucket_for(len(blk))
    cached = np.asarray(backend.cached_kernel(ep)(
        *backend.prepare_batch_cached(blk, bucket, ep)))[: len(blk)]
    plain = np.asarray(ev.jitted_verify()(
        *backend.prepare_batch(blk, bucket)))[: len(blk)]
    oracle = np.array([_edwards.verify_zip215(*blk.entry(i))
                       for i in range(len(blk))])
    return cached.astype(bool), plain.astype(bool), oracle


def _sequential_error(vset, commit):
    try:
        validation._verify_commit_single(
            CHAIN_ID, vset, commit, vset.total_voting_power() - 1,
            validation._ignore_absent, validation._count_for_block, True, True)
    except ValueError as e:
        return str(e)
    return None


def _concluded(conclude, row):
    try:
        conclude(row)
    except ValueError as e:
        return str(e)
    return None


def _ops():
    return _metrics.ops_metrics()


# -- seeded churn: every kind of change, against three references ---------------


KINDS = ("replace", "add", "remove", "power", "reorder", "many")


def _churn(rng, kind: str, members: dict, fresh: list) -> None:
    """One change of the set, in place."""
    live = sorted(members)
    if kind == "replace":
        for k in rng.sample(live, rng.randrange(1, 3)):
            members[fresh.pop()] = members.pop(k)
    elif kind == "add":
        members[fresh.pop()] = 100
    elif kind == "remove":
        members.pop(rng.choice(live))
    elif kind == "power":          # same order: the one power all share
        p = rng.randrange(50, 150)
        for k in live:
            members[k] = p
    elif kind == "reorder":        # power decides the order of the set
        for k in rng.sample(live, 5):
            members[k] = rng.randrange(1, 1000)
    else:
        for k in rng.sample(live, 20):
            members[fresh.pop()] = members.pop(k)


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("seed", [11, 12])
def test_churn_gives_the_uncached_paths_verdicts_and_blame(seed):
    rng = random.Random(seed)
    members = {i: 100 for i in range(N)}
    fresh = list(range(1000 * seed + 500, 1000 * seed, -1))
    m = _ops()
    b0 = m.epoch_tables_built.total()
    _block(_vset(members), _commit(_vset(members)))     # first sight: cold
    tables = set()
    # every kind of change twice and two more of many keys at once, in an
    # order the seed draws: 80 and more new keys, so the 47 free rows run out
    schedule = list(KINDS) * 2 + ["many", "many"]
    rng.shuffle(schedule)
    for step, kind in enumerate(schedule):
        _churn(rng, kind, members, fresh)
        vset = _vset(members)
        n = len(vset.validators)
        bad = tuple(rng.sample(range(n), rng.choice([0, 1, 1, 2])))
        commit = _commit(vset, height=8 + step, bad=bad)
        blk, conclude = _block(vset, commit)
        if blk.epoch_key is None:       # a full table: this commit is cold,
            blk, conclude = _block(vset, commit)     # the next is a hit
        tables.add(blk.epoch_key)
        ep = epoch_cache.lookup(blk)
        # the lanes gather their own keys from the table, and never its pad
        assert (ep.pub_rows[blk.val_idx] == blk.pub).all()
        assert blk.val_idx.max() < ep.vp - 1
        cached, plain, oracle = _rows(blk)
        assert np.array_equal(cached, plain) and np.array_equal(cached, oracle)
        assert sorted(np.flatnonzero(~cached)) == sorted(bad)
        said = _concluded(conclude, cached)
        assert said == _sequential_error(vset, commit)
        assert (said is None) == (not bad)
        if bad:
            assert said.startswith(f"wrong signature (#{min(bad)}): ")
    # a table filled up and a fresh one was built behind a cold commit
    assert len(tables) >= 2 and m.epoch_tables_built.total() - b0 >= 2
    assert m.epoch_tables_shared.total() > 0


def test_power_only_and_reorder_append_nothing():
    members = {i: 100 for i in range(N)}
    _block(_vset(members), _commit(_vset(members)))
    m = _ops()
    p0, s0 = m.epoch_rows_patched.total(), m.epoch_tables_shared.total()
    members.update({3: 700, 5: 1, 40: 350})
    vset = _vset(members)
    blk, _ = _block(vset, _commit(vset))
    assert blk.epoch_key is not None
    assert epoch_cache.lookup(blk).n_rows == N
    assert (m.epoch_rows_patched.total() - p0,
            m.epoch_tables_shared.total() - s0) == (0, 1)
    assert list(blk.val_idx) != list(range(N)), "the set's order changed"


def test_too_few_shared_keys_or_no_room_builds_a_fresh_table():
    m = _ops()
    a = _vset({i: 100 for i in range(N)})
    assert epoch_cache.table_rows(a, np.arange(N, dtype=np.int32))[0] is None
    b0 = m.epoch_tables_built.total()
    # 39 of 80 keys are rows of the table: under half
    few = _vset({**{i: 100 for i in range(41, N)},
                 **{3000 + i: 100 for i in range(41)}})
    assert epoch_cache.table_rows(few, np.arange(N, dtype=np.int32))[0] is None
    # 40 of 80: half, and the 40 new keys fit its 47 free rows
    members = {**{i: 100 for i in range(40, N)},
               **{4000 + i: 100 for i in range(40)}}
    half = _vset(members)
    key, _idx = epoch_cache.table_rows(half, np.arange(N, dtype=np.int32))
    assert key[:32] == a.hash()
    # 8 more do not fit the 7 rows left (row vp-1 stays the pad lane's)
    for i in range(8):
        members[5000 + i] = members.pop(4000 + i)
    full = _vset(members)
    assert epoch_cache.table_rows(full, np.arange(N, dtype=np.int32))[0] is None
    assert m.epoch_tables_built.total() - b0 == 2
    ep = epoch_cache.cache().get(key)
    assert ep.n_rows == 120
    assert (ep.pub_rows[120:-1] == epoch_cache._FREE_ENC).all(), \
        "free rows stay rows that reject"
    assert (ep.pub_rows[-1] == epoch_cache._IDENT_ENC).all()


def test_eviction_at_depth_8_takes_the_tables_sets_with_it():
    m = _ops()
    e0 = m.epoch_cache_evictions.total()
    first = _vset({i: 100 for i in range(10)})
    later = _vset({**{i: 100 for i in range(1, 10)}, 9000: 100})
    epoch_cache.note_valset(first)
    name = epoch_cache.table_rows(later, np.arange(10, dtype=np.int32))[0]
    assert name[:32] == first.hash()
    for t in range(1, 9):          # eight more tables that share no key
        assert epoch_cache.note_valset(
            _vset({100 * t + i: 100 for i in range(10)})) is None
    assert len(epoch_cache.cache()) == 8
    assert m.epoch_cache_evictions.total() - e0 == 1
    assert epoch_cache.cache().get(name) is None
    # both sets of the evicted table are strangers again
    h0 = m.epoch_cache_hits.total()
    assert epoch_cache.table_rows(later, np.arange(10, dtype=np.int32))[0] is None
    assert m.epoch_cache_hits.total() == h0


# -- a departed key ---------------------------------------------------------------


def test_a_departed_key_never_verifies_in_its_successors_row():
    members = {i: 100 for i in range(N)}
    old = _vset(members)
    validation.verify_commit_light(CHAIN_ID, old, BID, 7, _commit(old))
    left = 17
    power = members.pop(left)
    for k in range(7000, 7100):     # a joining key whose row is inside
        new = _vset({**members, k: power})     # the light early stop
        joined = next(i for i, v in enumerate(new.validators)
                      if v.pub_key.bytes() == SKS[k].pub_key().bytes())
        if joined < (2 * N) // 3:
            break
    forged = _commit(new, height=8, signer={joined: SKS[left]})
    with pytest.raises(ValueError) as cached_err:
        validation.verify_commit_light(CHAIN_ID, new, BID, 8, forged)
    blk, _ = _block(new, forged)
    ep = epoch_cache.lookup(blk)
    assert ep is not None and ep.key[:32] == old.hash(), \
        "served from the old table"
    resident = ep.pub_rows[: ep.n_rows].tobytes()
    assert SKS[left].pub_key().bytes() in [
        resident[32 * r: 32 * r + 32] for r in range(ep.n_rows)]
    assert str(cached_err.value).startswith(f"wrong signature (#{joined}): ")
    with pytest.raises(ValueError) as seq_err:
        validation._verify_commit_single(
            CHAIN_ID, new, forged, new.total_voting_power() * 2 // 3,
            validation._ignore_absent, validation._count_for_block, False, True)
    assert str(cached_err.value) == str(seq_err.value)
    # and the honest commit of the new set accepts from the same table
    validation.verify_commit_light(CHAIN_ID, new, BID, 8, _commit(new, height=8))


# -- a name means one content ------------------------------------------------------


def _evict_and_build_again(first, name):
    """Eight tables that share no key push `name` out of the LRU; then the
    set that built it comes by again (cold, then warm). Its new table."""
    for t in range(1, 9):
        assert epoch_cache.note_valset(
            _vset({9000 + 100 * t + i: 100 for i in range(10)})) is None
    assert epoch_cache.cache().get(name) is None
    assert epoch_cache.note_valset(first) is None
    again = epoch_cache.note_valset(first)
    assert again[:32] == name[:32] and again != name, \
        "a table built again is another table, under another name"
    return epoch_cache.cache().get(again)


@pytest.mark.time_limit(600)
def test_a_stale_block_of_a_table_built_again_rides_the_uncached_path():
    """REVIEW, PR 32: a block in flight names its table and rows past the
    first set's. The table is evicted and the same first set builds one
    again, where those rows are free. The stale name must find nothing,
    the block ride the uncached path and give the sequential verdict."""
    members = {i: 100 for i in range(N)}
    first = _vset(members)
    _block(first, _commit(first))                   # cold: builds the table
    members[7400] = members.pop(0)
    later = _vset(members)
    commit = _commit(later, height=8, bad=(5,))
    stale, conclude = _block(later, commit)         # mapped: row N appended
    name = stale.epoch_key
    assert name[:32] == first.hash() and stale.val_idx.max() == N
    fresh = _evict_and_build_again(first, name)
    assert fresh.n_rows == N, "the joined key's row is free there"
    assert epoch_cache.lookup(stale) is None
    assert epoch_cache.lookup(stale[:60]) is None
    m = _ops()
    dev0 = m.sigs_verified.value(path="device")
    v = pipeline.AsyncBatchVerifier()
    try:
        row = np.asarray(v.submit(stale).result(timeout=300))
    finally:
        v.close()
    assert m.sigs_verified.value(path="device") - dev0 == len(stale)
    assert list(np.flatnonzero(~row)) == [5]
    said = _concluded(conclude, row)
    assert said == _sequential_error(later, commit)
    assert said.startswith("wrong signature (#5): ")
    # the set asked again maps onto the new table and gathers its own keys
    blk, _ = _block(later, commit)
    assert blk.epoch_key == fresh.key and fresh.n_rows == N + 1
    assert (fresh.pub_rows[blk.val_idx] == blk.pub).all()


def test_a_stale_blocks_name_does_not_fuse_with_the_new_tables():
    """The coalescer's gate and concat compare names: a block of the old
    table and one of the table built again keep no common name."""
    from tendermint_tpu.ops.entry_block import EntryBlock

    members = {i: 100 for i in range(N)}
    first = _vset(members)
    _block(first, _commit(first))
    old, _ = _block(first, _commit(first))
    _evict_and_build_again(first, old.epoch_key)
    new, _ = _block(first, _commit(first))
    assert new.epoch_key != old.epoch_key
    both = EntryBlock.concat([old[:8], new[:8]])
    assert both.epoch_key is None and both.val_idx is None


@pytest.mark.time_limit(600)
def test_a_gather_from_a_free_row_rejects_the_forgery_the_identity_accepts():
    """ZIP-215 takes the identity as a public key, under which R = [s]B
    verifies for anyone — so a row that waits for a key must not hold it.
    Every layout of a free row says 'no point'; the pad lane's row stays
    the identity."""
    s = 12345
    sig = _edwards.compress(_edwards.scalar_mult(s, _edwards.BASE)) + \
        s.to_bytes(32, "little")
    ident = bytes(epoch_cache._IDENT_ENC)
    assert _edwards.verify_zip215(ident, b"anything", sig), \
        "the forgery the identity row would accept"
    assert _edwards.decompress(bytes(epoch_cache._FREE_ENC)) is None
    a = _vset({i: 100 for i in range(N)})
    _block(a, _commit(a))
    blk, _ = _block(a, _commit(a))
    ep = epoch_cache.lookup(blk)
    from tendermint_tpu.ops.entry_block import EntryBlock

    forged = EntryBlock.from_entries([(ident, b"anything", sig)] * 3)
    forged.epoch_key = ep.key
    # a free row, the last free row, and the pad lane's own row
    forged.val_idx = np.array([N, ep.vp - 2, ep.vp - 1], dtype=np.int32)
    bucket = backend._bucket_for(len(forged))
    row = np.asarray(backend.cached_kernel(ep)(
        *backend.prepare_batch_cached(forged, bucket, ep)))[:3]
    assert list(row.astype(bool)) == [False, False, True]
    _coords, ok = ep.coords_tables()
    ok = np.asarray(ok)[0]
    assert ok[:N].all() and not ok[N: ep.vp - 1].any() and ok[ep.vp - 1]
    # a patch leaves the rows still free as they were
    b = _vset({**{i: 100 for i in range(1, N)}, 7500: 100})
    _block(b, _commit(b, height=8))
    _coords, ok = ep.coords_tables()
    ok = np.asarray(ok)[0]
    assert ok[: N + 1].all() and not ok[N + 1: ep.vp - 1].any() \
        and ok[ep.vp - 1]


# -- a table value is never written ------------------------------------------------


def test_a_patch_makes_a_new_array_value_and_keeps_the_pad_row():
    members = {i: 100 for i in range(N)}
    a = _vset(members)
    blk_a, _ = _block(a, _commit(a))
    blk_a, _ = _block(a, _commit(a))
    ep = epoch_cache.lookup(blk_a)
    limbs0, sign0 = ep.xla_tables()
    before = np.asarray(limbs0).copy()
    members[7100] = members.pop(3)
    b = _vset(members)
    blk_b, _ = _block(b, _commit(b, height=8))
    assert blk_b.epoch_key == blk_a.epoch_key and ep.n_rows == N + 1
    limbs1, _sign1 = ep.xla_tables()
    assert limbs1 is not limbs0
    assert np.array_equal(np.asarray(limbs0), before), \
        "the value a launch in flight holds was written"
    after = np.asarray(limbs1)
    assert np.array_equal(after[:N], before[:N])
    assert not np.array_equal(after[N], before[N]), "the new key's row"
    assert np.array_equal(after[N + 1:], before[N + 1:])
    assert np.array_equal(after[ep.vp - 1], backend._pack_le_limbs(
        epoch_cache._IDENT_ENC[None, :])[0])
    # the old set still verifies from the patched table, the new one too
    for blk in (blk_a, blk_b):
        cached, plain, _ = _rows(blk)
        assert cached.all() and plain.all()


@pytest.mark.time_limit(600)
def test_host_decompression_of_appended_keys_is_the_devices():
    """coords_columns (crypto/_edwards.py on the host) against the traced
    routine that builds a whole table: the same points mod p and the same
    ok flags, on honest keys and on ZIP-215's edges."""
    p = _edwards.P
    keys = [_sk(i).pub_key().bytes() for i in range(3)] + [
        (1).to_bytes(32, "little"),                 # the identity
        (1 | 1 << 255).to_bytes(32, "little"),      # x = 0 with the sign set
        (p + 3).to_bytes(32, "little"),             # y >= p, taken mod p
        (2).to_bytes(32, "little"),                 # no such point
        (7).to_bytes(32, "little"),
    ]
    rows = np.frombuffer(b"".join(keys), np.uint8).reshape(8, 32)
    dev_coords, dev_ok = (np.asarray(a) for a in epoch_cache._coords_fn()(
        np.ascontiguousarray(rows.T)))
    ok, cols = epoch_cache.coords_columns(rows, 7)   # the last: a pad row
    cols = cols.reshape(4 * 32, 8)

    def value(col, c):
        return sum(int(col[32 * c + i]) << (13 * i) for i in range(20)) % p

    assert list(ok[:7]) == list(dev_ok[0, :7]) and ok[7] == 1
    assert list(ok[:7]) == [1, 1, 1, 1, 1, 1, 0]
    for j in range(6):
        assert [value(cols[:, j], c) for c in range(4)] == \
               [value(dev_coords[:, j], c) for c in range(4)], j
        assert cols[:, j].max() < 1 << 13 and not cols[20:32, j].any()
    ident = [0, 1, 1, 0]
    for j in (3, 6, 7):     # the identity, a refused key, a pad row
        assert [value(cols[:, j], c) for c in range(4)] == ident


def test_launches_in_flight_across_a_patch_keep_their_verdicts():
    members = {i: 100 for i in range(N)}
    sets, blocks = [], []
    _block(_vset(members), _commit(_vset(members)))
    for step in range(4):
        members[7200 + step] = members.pop(step)
        vset = _vset(members)
        sets.append(vset)
        blocks.append(_block(vset, _commit(vset, height=9 + step,
                                           bad=(step + 20,)))[0])
    assert len({b.epoch_key for b in blocks}) == 1
    v = pipeline.AsyncBatchVerifier(depth=2)
    try:
        futs = [v.submit(b[:60]) for b in blocks]     # none waited for
        for step, f in enumerate(futs):
            row = np.asarray(f.result(timeout=300))
            assert list(np.flatnonzero(~row)) == [step + 20]
    finally:
        v.close()


# -- fusing: one table, one launch --------------------------------------------------


def _fused_batches(monkeypatch, blocks):
    """Submits `blocks` while the coalescer is held inside the prep of a
    job of no table (which nothing fuses with), so that they wait in the
    queue together; returns what reached _prepare after it as
    (epoch_key, lanes)."""
    seen = []
    orig = pipeline.AsyncBatchVerifier._prepare
    gate = threading.Event()

    def spy(entries):
        if entries.epoch_key is None:
            gate.wait(10)            # the first job: hold the coalescer
        else:
            seen.append((entries.epoch_key, len(entries)))
        return orig(entries)

    monkeypatch.setattr(pipeline.AsyncBatchVerifier, "_prepare",
                        staticmethod(spy))
    first = blocks[0][:8]
    first.epoch_key = first.val_idx = None
    v = pipeline.AsyncBatchVerifier()
    try:
        futs = [v.submit(b) for b in [first] + blocks]
        gate.set()
        rows = [np.asarray(f.result(timeout=300)) for f in futs]
        _testing.drain_pool(v._pool)
    finally:
        v.close()
    assert all(r.all() for r in rows)
    return seen


def test_sets_of_one_table_fuse_and_sets_of_two_do_not(monkeypatch):
    members = {i: 100 for i in range(N)}
    a = _vset(members)
    _block(a, _commit(a))
    members[7300] = members.pop(0)
    b = _vset(members)
    other = _vset({8000 + i: 100 for i in range(N)})
    _block(other, _commit(other))
    stop = 100 * 59                 # each block stops at 60 lanes
    blk_a, _ = _block(a, _commit(a, height=8), stop)
    blk_b, _ = _block(b, _commit(b, height=9), stop)
    blk_o, _ = _block(other, _commit(other, height=8), stop)
    assert blk_a.epoch_key == blk_b.epoch_key
    assert blk_a.epoch_key[:32] == a.hash()
    assert blk_o.epoch_key[:32] == other.hash()
    assert len(blk_a) == len(blk_b) == 60
    d0 = _ops().sigs_verified.value(path="device")
    seen = _fused_batches(monkeypatch, [blk_a, blk_b])
    # two callers, two sets, one table: one launch carries both
    assert seen == [(blk_a.epoch_key, 120)], seen
    assert _ops().sigs_verified.value(path="device") - d0 == 8 + 120
    seen = _fused_batches(monkeypatch, [blk_a, blk_o])
    # two tables: the gate holds the second back for a launch of its own
    assert seen == [(blk_a.epoch_key, 60), (blk_o.epoch_key, 60)], seen
