"""Valset epoch cache (ISSUE 5): LRU hit/miss/evict + invalidation
semantics, EntryBlock epoch metadata through slice/concat/coalescing,
device-unpack vs host-pack parity, and cached-vs-uncached verdict/blame
bit-parity on the XLA kernels (pallas/RLC cached kernels are covered by
the slow interpret tests at the bottom)."""

import numpy as np
import pytest

try:
    from tendermint_tpu.crypto import ed25519
except ModuleNotFoundError:
    # No cryptography wheel in this container. Do NOT flip
    # TM_TPU_PUREPY_CRYPTO here (env leaks into later-collected modules);
    # test_epoch_cache_isolated.py re-runs this module in a subprocess
    # with the fallback enabled instead.
    pytest.skip(
        "ed25519 backend unavailable (runs via test_epoch_cache_isolated.py)",
        allow_module_level=True,
    )

from tendermint_tpu.libs import metrics as _metrics
from tendermint_tpu.ops import backend, epoch_cache, pipeline
from tendermint_tpu.ops import ed25519_verify as ev
from tendermint_tpu.ops.entry_block import EntryBlock
from tendermint_tpu.types import Vote, validation
from tendermint_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.types.vote import PRECOMMIT_TYPE
from tendermint_tpu.wire.canonical import Timestamp

CHAIN_ID = "epoch-cache-test"


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Every test starts with an ENABLED, empty cache and leaves the
    process on the environment default (disabled on CPU unless
    TM_TPU_EPOCH_CACHE is set) so other modules see no behavior change."""
    epoch_cache.reset(depth=4)
    yield
    epoch_cache.reset()


def _block_id():
    return BlockID(
        hash=b"\x11" * 32,
        part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32),
    )


def _signed_commit(n, height=7, bad=(), nil=(), absent=(), power=None,
                   first=0):
    """A REAL signed commit over n validators (index-aligned set); sets
    of different `first` (16 apart) share no key."""
    sks = [ed25519.gen_priv_key(bytes([first + i + 1]) * 32)
           for i in range(n)]
    vals = [
        Validator.new(sk.pub_key(), (power or [100] * n)[i])
        for i, sk in enumerate(sks)
    ]
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    bid = _block_id()
    ts = Timestamp(seconds=1_700_000_000)
    sigs = []
    for i, sk in enumerate(sks):
        if i in absent:
            sigs.append(CommitSig.absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT
        v = Vote(
            type=PRECOMMIT_TYPE, height=height, round=0,
            block_id=BlockID() if i in nil else bid,
            timestamp=ts, validator_address=vals[i].address,
            validator_index=i,
        )
        sig = (
            b"\x01" * 64 if i in bad else sk.sign(v.sign_bytes(CHAIN_ID))
        )
        sigs.append(
            CommitSig(
                block_id_flag=flag, validator_address=vals[i].address,
                timestamp=ts, signature=sig,
            )
        )
    commit = Commit(height=height, round=0, block_id=bid, signatures=sigs)
    return vset, commit, bid, sks


def _ops():
    return _metrics.ops_metrics()


# ---------------------------------------------------------------------------
# Cache core: hit/miss/evict, keying, invalidation
# ---------------------------------------------------------------------------


class TestEpochCacheCore:
    def test_cold_then_warm(self):
        vset, commit, _, _ = _signed_commit(6)
        key1 = epoch_cache.note_valset(vset)
        assert key1 is None  # first sight: cold, registers only
        key2 = epoch_cache.note_valset(vset)
        # second sight: warm; a table's name is the hash of the set that
        # built it and a serial of its own
        assert key2[:32] == vset.hash() and len(key2) == 40
        ep = epoch_cache.cache().get(key2)
        assert ep is not None
        assert ep.n_vals == 6
        assert ep.vp >= ep.n_vals + 1
        assert ep.vp & (ep.vp - 1) == 0  # power of two

    def test_hit_miss_evict_counters(self):
        m = _ops()
        h0, m0, e0 = (
            m.epoch_cache_hits.total(),
            m.epoch_cache_misses.total(),
            m.epoch_cache_evictions.total(),
        )
        # five sets that share no key: nothing maps, each builds a table
        sets = [_signed_commit(4 + i, first=16 * i)[0] for i in range(5)]
        for vs in sets:
            assert epoch_cache.note_valset(vs) is None  # 5 misses
        # depth=4: registering the 5th evicted the 1st (LRU)
        assert m.epoch_cache_misses.total() - m0 == 5
        assert m.epoch_cache_evictions.total() - e0 == 1
        assert epoch_cache.note_valset(sets[4]) is not None  # hit
        assert m.epoch_cache_hits.total() - h0 == 1
        # the evicted set is cold again
        assert epoch_cache.note_valset(sets[0]) is None
        assert m.epoch_cache_misses.total() - m0 == 6

    def test_lru_ordering(self):
        sets = [_signed_commit(4 + i, first=16 * i)[0] for i in range(4)]
        for vs in sets:
            epoch_cache.note_valset(vs)
        # touch the oldest so it is no longer the LRU victim
        assert epoch_cache.note_valset(sets[0]) is not None
        epoch_cache.note_valset(_signed_commit(12, first=64)[0])  # evicts sets[1]
        assert epoch_cache.note_valset(sets[0]) is not None
        assert epoch_cache.note_valset(sets[1]) is None  # was evicted

    def test_power_change_is_a_new_set_of_the_same_table(self):
        m = _ops()
        vset, _, _, sks = _signed_commit(5)
        epoch_cache.note_valset(vset)
        key_a = epoch_cache.note_valset(vset)
        assert key_a is not None
        m0, s0, p0, b0 = (m.epoch_cache_misses.total(),
                          m.epoch_tables_shared.total(),
                          m.epoch_rows_patched.total(),
                          m.epoch_tables_built.total())
        vset.update_with_change_set(
            [Validator.new(sks[0].pub_key(), 999)]
        )
        # _update_with_change_set cleared _hash and _ed_cols: the changed
        # set has a NEW hash (a miss) and a new order, but its keys are
        # the table's: it maps onto it and appends nothing
        assert vset.hash() != key_a
        key_b, idx = epoch_cache.table_rows(vset, np.arange(5, dtype=np.int32))
        assert key_b == key_a
        ep = epoch_cache.cache().get(key_a)
        assert (ep.pub_rows[idx] == vset.ed25519_columns()[0]).all()
        assert ep.n_rows == 5 and len(epoch_cache.cache()) == 1
        assert (m.epoch_cache_misses.total() - m0,
                m.epoch_tables_shared.total() - s0,
                m.epoch_rows_patched.total() - p0,
                m.epoch_tables_built.total() - b0) == (1, 1, 0, 0)
        # a caller that would attach its own set rows is told "uncached"
        assert list(idx) != list(range(5))
        assert epoch_cache.note_valset(vset) is None

    def test_membership_change_appends_a_row(self):
        m = _ops()
        vset, _, _, _ = _signed_commit(5)
        epoch_cache.note_valset(vset)
        key_a = epoch_cache.note_valset(vset)
        p0 = m.epoch_rows_patched.total()
        new_sk = ed25519.gen_priv_key(b"\x77" * 32)
        vset.update_with_change_set([Validator.new(new_sk.pub_key(), 50)])
        assert vset.hash() != key_a
        key_b, idx = epoch_cache.table_rows(vset, np.arange(6, dtype=np.int32))
        assert key_b == key_a  # the same table, one key appended
        ep = epoch_cache.cache().get(key_a)
        assert ep.n_vals == 5 and ep.n_rows == 6
        assert (ep.pub_rows[idx] == vset.ed25519_columns()[0]).all()
        assert m.epoch_rows_patched.total() - p0 == 1
        assert len(epoch_cache.cache()) == 1  # no table of its own

    def test_non_ed25519_set_not_cached(self):
        class FakeKey:
            def bytes(self):
                return b"\x00" * 32

            def address(self):
                return b"\x00" * 20

        vset, _, _, _ = _signed_commit(3)
        vset.validators[1].pub_key = FakeKey()
        vset._ed_cols = None
        vset._hash = None
        epoch_cache.note_valset(vset)
        assert epoch_cache.note_valset(vset) is None  # never warm

    def test_disabled_cache(self):
        epoch_cache.reset(depth=0)
        vset, _, _, _ = _signed_commit(3)
        assert epoch_cache.note_valset(vset) is None
        assert epoch_cache.note_valset(vset) is None
        assert epoch_cache.cache() is None

    def test_copy_shares_epoch(self):
        vset, _, _, _ = _signed_commit(4)
        epoch_cache.note_valset(vset)
        c = vset.copy()
        # copy preserves (pub, power): same hash, same (warm) epoch
        assert epoch_cache.note_valset(c)[:32] == vset.hash()


# ---------------------------------------------------------------------------
# EntryBlock epoch metadata: slices, concat, coalescer fallback
# ---------------------------------------------------------------------------


def _meta_block(n, key, base=0):
    pub = np.arange(n * 32, dtype=np.uint8).reshape(n, 32)
    sig = np.zeros((n, 64), dtype=np.uint8)
    offs = np.arange(n + 1, dtype=np.int64) * 3
    return EntryBlock(
        pub, sig, b"abc" * n, offs,
        val_idx=np.arange(base, base + n, dtype=np.int32), epoch_key=key,
    )


class TestEntryBlockEpochMeta:
    def test_slice_preserves(self):
        b = _meta_block(6, b"K" * 32)
        s = b[2:5]
        assert s.epoch_key == b"K" * 32
        assert list(s.val_idx) == [2, 3, 4]

    def test_concat_same_key(self):
        a = _meta_block(3, b"K" * 32)
        b = _meta_block(2, b"K" * 32, base=7)
        c = EntryBlock.concat([a, b])
        assert c.epoch_key == b"K" * 32
        assert list(c.val_idx) == [0, 1, 2, 7, 8]

    def test_concat_mixed_key_falls_back(self):
        a = _meta_block(3, b"K" * 32)
        b = _meta_block(2, b"L" * 32)
        c = EntryBlock.concat([a, b])
        assert c.epoch_key is None and c.val_idx is None

    def test_concat_missing_key_falls_back(self):
        a = _meta_block(3, b"K" * 32)
        b = _meta_block(2, None)
        c = EntryBlock.concat([a, b])
        assert c.epoch_key is None and c.val_idx is None

    def test_coalescer_never_fuses_mixed_epochs(self, monkeypatch):
        """Jobs with differing epoch keys must reach _prepare in
        separate batches (the dispatch-level face of the mixed-valset
        fallback)."""
        seen = []
        orig = pipeline.AsyncBatchVerifier._prepare

        def spy(entries):
            seen.append((entries.epoch_key, len(entries)))
            return orig(entries)

        monkeypatch.setattr(
            pipeline.AsyncBatchVerifier, "_prepare", staticmethod(spy)
        )
        v = pipeline.AsyncBatchVerifier()
        try:
            sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
            blocks = []
            for key in (b"A" * 32, b"A" * 32, b"B" * 32):
                ents = [
                    (sk.pub_key().bytes(), b"m", sk.sign(b"m")) for sk in sks
                ]
                blk = EntryBlock.from_entries(ents)
                blk.val_idx = np.arange(4, dtype=np.int32)
                blk.epoch_key = key
                blocks.append(blk)
            futs = [v.submit(b) for b in blocks]
            for f in futs:
                assert np.asarray(f.result(timeout=120)).all()
        finally:
            v.close()
        assert seen, "no batches dispatched"
        # every dispatched batch carries ONE epoch key — fused batches of
        # mixed keys would show epoch_key=None with 8+ entries
        for key, n in seen:
            assert key in (b"A" * 32, b"B" * 32)


# ---------------------------------------------------------------------------
# Device unpack vs host pack parity (the on-device prologue)
# ---------------------------------------------------------------------------


class TestDeviceUnpackParity:
    def test_limbs_and_bits(self):
        rng = np.random.RandomState(9)
        enc = rng.randint(0, 256, (37, 32), dtype=np.uint8)
        import jax.numpy as jnp

        limbs_dev, sign_dev = ev.unpack_limbs_rows(
            jnp.asarray(enc.astype(np.int32))
        )
        assert np.array_equal(
            np.asarray(limbs_dev), backend._pack_le_limbs(enc)
        )
        assert np.array_equal(
            np.asarray(sign_dev), (enc[:, 31] >> 7).astype(np.int32)
        )
        scal = enc.copy()
        scal[:, 31] &= 0x1F  # < 2^253
        bits_dev = ev.bits253_rows(jnp.asarray(scal.astype(np.int32)))
        assert np.array_equal(np.asarray(bits_dev), backend._bits_253(scal))

    def test_epoch_table_matches_host_pack(self):
        vset, _, _, _ = _signed_commit(5)
        epoch_cache.note_valset(vset)
        key = epoch_cache.note_valset(vset)
        ep = epoch_cache.cache().get(key)
        limbs, sign = ep.xla_tables()
        assert np.array_equal(
            np.asarray(limbs), backend._pack_le_limbs(ep.pub_rows)
        )
        # the pad lane's identity row: limb0 = 1, rest 0, sign 0; the
        # free rows before it hold y = 2, which is no point
        pad = np.asarray(limbs)[ep.n_vals:]
        assert (pad[:-1, 0] == 2).all() and pad[-1, 0] == 1
        assert (pad[:, 1:] == 0).all()


# ---------------------------------------------------------------------------
# Cached vs uncached verdict/blame bit-parity (XLA kernels, CPU)
# ---------------------------------------------------------------------------


def _warm_block(vset, commit, needed):
    dec = Commit.decode(commit.encode())
    assert dec.commit_block() is not None
    blk, _ = pipeline.commit_entries(CHAIN_ID, vset, dec, needed)
    if blk.epoch_key is None:  # first sight was cold
        blk, _ = pipeline.commit_entries(CHAIN_ID, vset, dec, needed)
    assert blk.epoch_key is not None
    return blk


class TestCachedVerdictParity:
    @pytest.mark.parametrize("n,bad,nil,absent", [
        (90, (17,), (), ()),
        (90, (3, 88), (11,), (40,)),
    ])
    def test_host_hash_parity(self, n, bad, nil, absent):
        vset, commit, _, _ = _signed_commit(n, bad=bad, nil=nil,
                                            absent=absent)
        # threshold just under the commit lanes' total power: the
        # early-stop selection keeps EVERY commit lane (bad ones too)
        needed = 100 * (n - len(nil) - len(absent)) - 1
        blk = _warm_block(vset, commit, needed)
        ep = epoch_cache.lookup(blk)
        assert ep is not None
        bucket = backend._bucket_for(len(blk))
        args_u = backend.prepare_batch(blk, bucket)
        res_u = np.asarray(ev.jitted_verify()(*args_u))[: len(blk)]
        args_c = backend.prepare_batch_cached(blk, bucket, ep)
        res_c = np.asarray(
            backend.cached_kernel(ep)(*args_c)
        )[: len(blk)]
        assert np.array_equal(res_u, res_c)
        assert not res_c.all()  # the bad lanes really reject
        # warm-epoch transfer really shrinks (acceptance: <= 0.5x)
        assert backend.h2d_arg_bytes(args_c) <= 0.5 * (
            backend.h2d_arg_bytes(args_u)
        )

    def test_verify_commit_blame_parity_cached_vs_uncached(self):
        n, bad_i = 90, 23
        vset, commit, bid, _ = _signed_commit(n, bad=(bad_i,))
        dec = Commit.decode(commit.encode())
        # uncached pass (cold epoch) — the PR-4 behavior
        epoch_cache.reset(depth=4)
        with pytest.raises(ValueError) as cold_err:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)
        # warm pass: same commit, epoch now resident -> cached kernels
        with pytest.raises(ValueError) as warm_err:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)
        assert str(cold_err.value) == str(warm_err.value)
        assert f"wrong signature (#{bad_i})" in str(warm_err.value)
        m = _ops()
        assert m.epoch_cache_hits.total() >= 1

    def test_verify_commit_accepts_warm(self):
        vset, commit, bid, _ = _signed_commit(80)
        dec = Commit.decode(commit.encode())
        validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)  # cold
        validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)  # warm
        # a light verify on the same epoch stays warm too
        validation.verify_commit_light(CHAIN_ID, vset, bid, 7, dec)

    def test_evicted_epoch_falls_back(self):
        """A key that points at an evicted entry degrades to the uncached
        path (verify still succeeds) — never an error."""
        vset, commit, bid, _ = _signed_commit(70)
        dec = Commit.decode(commit.encode())
        needed = vset.total_voting_power() * 2 // 3
        blk = _warm_block(vset, commit, needed)
        epoch_cache.cache().clear()  # simulate eviction after submit
        assert epoch_cache.lookup(blk) is None
        from tendermint_tpu.ops.pipeline import shared_verifier

        res = np.asarray(
            shared_verifier().submit(blk).result(timeout=300)
        )
        assert res.all()


# ---------------------------------------------------------------------------
# Churn lifecycle (ISSUE 6 satellite): realistic validator-set rotation —
# join + leave through the REAL update_with_change_set path, exactly what
# an EndBlock validator update drives — must cycle the cache through
# cold -> warm -> invalidate -> evict -> re-register, with verdict/blame
# parity on the evicted-fallback path. Sizes stay in the vp=128/bucket-128
# shape class the parity tests above already compiled.
# ---------------------------------------------------------------------------


def _vset_with_sks(n, first_byte=1):
    sks = [ed25519.gen_priv_key(bytes([first_byte + i]) * 32) for i in range(n)]
    vals = [Validator.new(sk.pub_key(), 100) for sk in sks]
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    return vset, {sk.pub_key().bytes(): sk for sk in sks}


def _commit_signed_by(vset, by_pub, height=7, bad=()):
    """A commit signed by the CURRENT set in its CURRENT order (rotation
    re-sorts validators, so indices must be re-derived per epoch)."""
    bid = _block_id()
    ts = Timestamp(seconds=1_700_000_000)
    sigs = []
    for i, val in enumerate(vset.validators):
        v = Vote(
            type=PRECOMMIT_TYPE, height=height, round=0, block_id=bid,
            timestamp=ts, validator_address=val.address, validator_index=i,
        )
        sig = (
            b"\x01" * 64 if i in bad
            else by_pub[val.pub_key.bytes()].sign(v.sign_bytes(CHAIN_ID))
        )
        sigs.append(
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=val.address, timestamp=ts, signature=sig,
            )
        )
    return Commit(height=height, round=0, block_id=bid, signatures=sigs), bid


def _rotate(vset, by_pub, joiner_byte):
    """One churn: a fresh validator joins, the current first leaves —
    the same change-set shape state.execution.update_state applies from
    EndBlock updates (power 0 = removal)."""
    new_sk = ed25519.gen_priv_key(bytes([joiner_byte]) * 32)
    by_pub[new_sk.pub_key().bytes()] = new_sk
    leaver = vset.validators[0]
    vset.update_with_change_set(
        [
            Validator.new(new_sk.pub_key(), 100),
            Validator.new(leaver.pub_key, 0),
        ]
    )


class TestChurnLifecycle:
    def test_rotation_cycles_cold_warm_invalidate_evict_reregister(self):
        epoch_cache.reset(depth=2)
        m = _ops()
        vset, by_pub = _vset_with_sks(90)

        def deltas():
            return (
                m.epoch_cache_hits.total(),
                m.epoch_cache_misses.total(),
                m.epoch_cache_evictions.total(),
            )

        def verify(h):
            commit, bid = _commit_signed_by(vset, by_pub, height=h)
            dec = Commit.decode(commit.encode())
            validation.verify_commit(CHAIN_ID, vset, bid, h, dec)

        h0, m0, e0 = deltas()
        epoch_a = vset.copy()  # pre-rotation snapshot: same hash
        verify(7)  # cold: registers epoch A
        h1, m1, e1 = deltas()
        assert (m1 - m0, e1 - e0) == (1, 0)
        verify(8)  # warm: hits epoch A
        h2, m2, _ = deltas()
        assert h2 - h1 >= 1 and m2 == m1
        key_a = epoch_cache.note_valset(epoch_a)
        assert key_a[:32] == epoch_a.hash()

        _rotate(vset, by_pub, 200)  # epoch B: one key joins, one leaves
        assert vset.hash() != epoch_a.hash()
        verify(9)   # a NEW set hash (miss) that maps onto A's table
        verify(10)  # seen: a hit
        h3, m3, e3 = deltas()
        assert m3 - m2 == 1 and h3 - h2 >= 1 and e3 - e1 == 0
        assert len(epoch_cache.cache()) == 1
        assert epoch_cache.cache().get(key_a).n_rows == 91

        # two committees that share no key with A: depth 2 evicts A
        for first in (100, 200):
            other, _ = _vset_with_sks(8, first_byte=first)
            assert epoch_cache.note_valset(other) is None
        _, m4, e4 = deltas()
        assert m4 - m3 == 2 and e4 - e3 == 1
        assert epoch_cache.cache().get(key_a) is None  # A really evicted

        # re-register: the SAME membership returning after eviction is a
        # fresh cold registration, then warm — in ANOTHER table, under
        # another name: what the old name's later rows held followed from
        # the sets mapped onto it, not from the set that built it
        assert epoch_cache.note_valset(epoch_a) is None       # cold again
        key_a2 = epoch_cache.note_valset(epoch_a)             # warm again
        assert key_a2[:32] == key_a[:32] and key_a2 != key_a
        assert epoch_cache.cache().get(key_a) is None
        _, m5, _ = deltas()
        assert m5 - m4 == 1
        # and B, whose mapping went with the table, maps onto the new one
        verify(11)
        assert epoch_cache.cache().get(key_a2).n_rows == 91

    def test_evicted_epoch_verdict_and_blame_bit_identical(self):
        """The satellite's parity leg: a commit verified WARM (cached
        kernels) and the same commit verified after EVICTION (uncached
        fallback) must produce byte-identical error strings — same
        verdicts, same blamed lane."""
        epoch_cache.reset(depth=4)
        vset, by_pub = _vset_with_sks(90)
        bad_i = 31
        commit, bid = _commit_signed_by(vset, by_pub, height=7, bad=(bad_i,))
        dec = Commit.decode(commit.encode())
        with pytest.raises(ValueError) as cold_err:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)  # cold
        with pytest.raises(ValueError) as warm_err:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)  # cached
        epoch_cache.cache().clear()  # evict everything mid-stream
        with pytest.raises(ValueError) as evicted_err:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)  # fallback
        assert str(cold_err.value) == str(warm_err.value) == str(
            evicted_err.value
        )
        assert "wrong signature (#" in str(evicted_err.value)
        # a GOOD commit from the same (re-registered) epoch verifies warm
        good, gbid = _commit_signed_by(vset, by_pub, height=8)
        gdec = Commit.decode(good.encode())
        validation.verify_commit(CHAIN_ID, vset, gbid, 8, gdec)
        validation.verify_commit(CHAIN_ID, vset, gbid, 8, gdec)


# ---------------------------------------------------------------------------
# Sharded cached path (needs jax.shard_map — absent on this container's
# jax; runs on images that have it, e.g. the TPU driver)
# ---------------------------------------------------------------------------


class TestShardedCached:
    def test_sharded_cached_matches_uncached(self):
        import jax

        try:
            from jax import shard_map  # noqa: F401
        except ImportError:
            pytest.skip("jax.shard_map unavailable on this jax version")
        from tendermint_tpu.ops import sharded

        n_dev = min(8, len(jax.devices()))
        mesh = sharded.make_mesh(n_dev)
        n = 2 * n_dev
        sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
        ents = [
            (sk.pub_key().bytes(), b"shard-%d" % i, sk.sign(b"shard-%d" % i))
            for i, sk in enumerate(sks)
        ]
        ents[3] = (ents[3][0], ents[3][1], b"\x01" * 64)
        powers = [100 + i for i in range(n)]
        blk = EntryBlock.from_entries(ents)
        v_u, t_u, a_u = sharded.verify_commit_sharded(
            blk, powers, mesh, bucket=n
        )
        # warm the epoch and re-run: verify_commit_sharded auto-dispatches
        # to the cached variant (replicated table, per-shard gather)
        key = b"E" * 32
        epoch_cache.cache().note(key, blk.pub.copy())
        got = epoch_cache.cache().note(key, blk.pub.copy())
        assert got is not None
        blk.val_idx = np.arange(n, dtype=np.int32)
        blk.epoch_key = got[0].key
        assert epoch_cache.lookup(blk) is not None
        v_c, t_c, a_c = sharded.verify_commit_sharded(
            blk, powers, mesh, bucket=n
        )
        assert np.array_equal(v_u, v_c)
        assert t_u == t_c and a_u == a_c
        assert not v_c[3] and not a_c


# ---------------------------------------------------------------------------
# Pallas cached kernels (interpret mode: minutes per grid — slow-marked;
# the TPU driver image runs them compiled)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestCachedPallasInterpret:
    def _blk(self, n):
        sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
        ents = [
            (sk.pub_key().bytes(), b"m%d" % i, sk.sign(b"m%d" % i))
            for i, sk in enumerate(sks)
        ]
        ents[min(3, n - 1)] = (ents[min(3, n - 1)][0], b"m", b"\x01" * 64)
        blk = EntryBlock.from_entries(ents)
        ep = epoch_cache.EpochEntry(b"k" * 32, blk.pub.copy())
        blk.val_idx = np.arange(n, dtype=np.int32)
        blk.epoch_key = b"k" * 32
        return blk, ep

    def test_rlc_cached_parity(self, monkeypatch):
        from tendermint_tpu.ops import pallas_rlc as pr

        monkeypatch.setenv("TM_TPU_RLC_SEED", "7")
        monkeypatch.setenv("TM_TPU_RLC_SEED_UNSAFE", "1")
        blk, ep = self._blk(6)
        bucket, g, b, m = pr.plan_bucket(len(blk))
        lanes_u = pr.verify_rlc_compact(
            *pr.prepare_rlc(blk, bucket, m), m, block=b, interpret=True
        )
        dev = pr.rlc_cached_fn(ep, m, g, b, True)(
            *pr.prepare_rlc_cached(blk, bucket, ep, m)
        )
        lanes_c = np.asarray(dev)[0].astype(bool)
        assert np.array_equal(lanes_u, lanes_c)
        assert np.array_equal(
            pr.expand_lanes(lanes_u, blk, m), pr.expand_lanes(lanes_c, blk, m)
        )

    def test_compact_cached_parity(self):
        from tendermint_tpu.ops import pallas_verify as pv

        blk, ep = self._blk(8)
        res_u = pv.verify_compact(
            *pv.prepare_compact(blk, 8), block=8, interpret=True
        )
        res_c = pv.verify_compact_cached(
            pv.prepare_compact_cached(blk, 8, ep), ep, block=8,
            interpret=True,
        )
        assert np.array_equal(res_u, res_c)
        assert not res_c.all()
