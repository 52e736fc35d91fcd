"""Columnar-from-decode commit path (ISSUE 4): CommitBlock <-> CommitSig
lazy-view parity, fused commit prep differential (numpy fallback vs
native vs the object paths — verdicts, tally, blame, absent/nil flags),
EntryBlock RAM columns, and the pipeline's single dispatch-owner
thread."""

import threading

import numpy as np
import pytest

try:
    from tendermint_tpu.crypto import ed25519
except ModuleNotFoundError:
    # No cryptography wheel in this container. Do NOT flip
    # TM_TPU_PUREPY_CRYPTO here (env leaks into later-collected modules);
    # test_commit_block_isolated.py re-runs this module in a subprocess
    # with the fallback enabled instead.
    pytest.skip(
        "ed25519 backend unavailable (runs via test_commit_block_isolated.py)",
        allow_module_level=True,
    )

from tendermint_tpu.ops import backend, commit_prep as cp
from tendermint_tpu.ops import pipeline as pl
from tendermint_tpu.ops.entry_block import CommitBlock, EntryBlock
from tendermint_tpu.types import validation
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
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.wire.canonical import Timestamp

CHAIN_ID = "commit-block-test"


def _block_id():
    return BlockID(
        hash=b"\x11" * 32,
        part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32),
    )


def _signed_commit(n, height=7, bad=(), nil=(), absent=(), power=None):
    """A REAL signed commit over n validators (index-aligned set)."""
    sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
    vals = [
        Validator.new(sk.pub_key(), (power or [100] * n)[i])
        for i, sk in enumerate(sks)
    ]
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    bid = _block_id()
    sigs = []
    for i, sk in enumerate(sks):
        if i in absent:
            sigs.append(CommitSig.absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT
        ts = Timestamp(seconds=1_700_000_000, nanos=i + 1)
        commit_stub = Commit(height=height, round=0, block_id=bid)
        tpl = commit_stub.sign_bytes_template(CHAIN_ID, flag)
        from tendermint_tpu.wire.canonical import compose_vote_sign_bytes

        sb = compose_vote_sign_bytes(tpl, ts)
        sig = sk.sign(sb)
        if i in bad:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        sigs.append(
            CommitSig(
                block_id_flag=flag,
                validator_address=sk.pub_key().address(),
                timestamp=ts,
                signature=sig,
            )
        )
    return vset, bid, Commit(height=height, round=0, block_id=bid,
                             signatures=sigs)


def _random_commit(n, seed=0, nil=(), absent=()):
    """Structurally-valid commit with random (invalid) signatures — for
    prep-stage differentials where validity doesn't matter."""
    rng = np.random.RandomState(seed)
    vals = []
    sigs = []
    for i in range(n):
        pk = ed25519.PubKey(rng.randint(0, 256, 32, dtype=np.uint8).tobytes())
        vals.append(Validator.new(pk, 50 + (i % 7)))
        if i in absent:
            sigs.append(CommitSig.absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT
        sigs.append(
            CommitSig(
                block_id_flag=flag,
                validator_address=pk.address(),
                timestamp=Timestamp(
                    seconds=1_700_000_000 + (i % 3), nanos=(i * 37) % 1000
                ),
                signature=rng.randint(0, 256, 64, dtype=np.uint8).tobytes(),
            )
        )
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    return vset, Commit(height=42, round=1, block_id=_block_id(),
                        signatures=sigs)


class TestCommitSigsView:
    def test_decode_is_columnar_and_lazy(self):
        _, commit = _random_commit(40, nil=(3, 9), absent=(5,))
        dec = Commit.decode(commit.encode())
        assert isinstance(dec.signatures, CommitSigs)
        assert dec.commit_block() is not None
        # lazy: only the accessed index materializes
        _ = dec.signatures[7]
        mat = [x is not None for x in dec.signatures._items]
        assert mat[7] and sum(mat) == 1

    def test_view_parity_with_object_decode(self):
        _, commit = _random_commit(60, nil=(1, 2), absent=(4, 44))
        enc = commit.encode()
        dec = Commit.decode(enc)
        assert list(dec.signatures) == list(commit.signatures)
        assert dec.signatures == list(commit.signatures)
        assert dec.encode() == enc
        assert dec.hash() == commit.hash()
        assert dec == commit

    def test_mutation_detaches_columns(self):
        _, commit = _random_commit(10)
        dec = Commit.decode(commit.encode())
        cs = dec.signatures[2]
        dec.signatures[2] = CommitSig(
            block_id_flag=cs.block_id_flag,
            validator_address=cs.validator_address,
            timestamp=cs.timestamp,
            signature=b"\x07" * 64,
        )
        assert dec.signatures.block() is None
        blk = dec.commit_block()  # rebuilt from the mutated objects
        assert blk is not None
        assert blk.sig[2].tobytes() == b"\x07" * 64

    def test_reassignment_invalidates_block_and_hash(self):
        _, commit = _random_commit(8)
        dec = Commit.decode(commit.encode())
        h0 = dec.hash()
        blk0 = dec.commit_block()
        assert blk0 is not None
        dec.signatures = [CommitSig.absent()] * 8
        blk1 = dec.commit_block()  # rebuilt from the new list
        assert blk1 is not blk0
        assert (blk1.flags == 1).all()
        assert dec.hash() != h0

    def test_in_place_mutation_of_plain_list_never_sees_stale_columns(self):
        # commit_block() must NOT cache object-built columns: a plain
        # list's `signatures[i] = ...` has no hook, so a cache would let
        # a tampered signature verify against pre-mutation bytes
        _, commit = _random_commit(6)
        blk0 = commit.commit_block()
        assert blk0 is not None
        cs = commit.signatures[2]
        commit.signatures[2] = CommitSig(
            block_id_flag=cs.block_id_flag,
            validator_address=cs.validator_address,
            timestamp=cs.timestamp,
            signature=b"\xff" * 64,
        )
        blk1 = commit.commit_block()
        assert blk1.sig[2].tobytes() == b"\xff" * 64

    def test_detached_view_second_mutation_never_sees_stale_columns(self):
        _, commit = _random_commit(6)
        dec = Commit.decode(commit.encode())
        cs = dec.signatures[1]

        def forged(sig_byte):
            return CommitSig(
                block_id_flag=cs.block_id_flag,
                validator_address=cs.validator_address,
                timestamp=cs.timestamp,
                signature=bytes([sig_byte]) * 64,
            )

        dec.signatures[1] = forged(0xAA)  # detaches the view
        assert dec.commit_block().sig[1].tobytes() == b"\xaa" * 64
        dec.signatures[1] = forged(0xBB)  # second mutation, view already
        assert dec.commit_block().sig[1].tobytes() == b"\xbb" * 64

    def test_non_canonical_wire_falls_back_to_objects(self):
        # an absent CommitSig carrying a signature is invalid-but-
        # decodable; the columnar form cannot represent it, so decode
        # must keep plain objects (and validate_basic still rejects it)
        from tendermint_tpu.wire.proto import ProtoWriter

        w = ProtoWriter()
        w.write_varint(1, 7)
        w.write_message(2, _block_id().encode(), always=True)
        bad_cs = CommitSig(
            block_id_flag=BLOCK_ID_FLAG_ABSENT,
            signature=b"\x01" * 64,
            timestamp=Timestamp(seconds=1, nanos=0),
        )
        # build via encode(): absent-with-signature still encodes
        commit = Commit(height=7, round=0, block_id=_block_id(),
                        signatures=[bad_cs, CommitSig.absent()])
        dec = Commit.decode(commit.encode())
        assert not isinstance(dec.signatures, CommitSigs)
        assert dec.commit_block() is None
        with pytest.raises(ValueError):
            dec.validate_basic()

    def test_commit_block_rejects_non_canonical_objects(self):
        _, commit = _random_commit(4)
        sigs = list(commit.signatures)
        cs = sigs[1]
        sigs[1] = CommitSig(
            block_id_flag=cs.block_id_flag,
            validator_address=cs.validator_address,
            timestamp=cs.timestamp,
            signature=b"\x01" * 63,  # wrong length
        )
        commit.signatures = sigs
        assert commit.commit_block() is None


def _native_prep_inputs(vset, commit):
    """(tm_native, CommitBlock, the set's columns, COMMIT template, NIL
    template) of a commit as decoded from its wire bytes; skips where the
    module was built without the fused prep."""
    from tendermint_tpu.native import load as _load_native

    native = _load_native()
    if not hasattr(native, "commit_prep_fused"):
        pytest.skip("tm_native built without commit_prep_fused")
    dec = Commit.decode(commit.encode())
    return (native, dec.commit_block(), vset.ed25519_columns(),
            dec.sign_bytes_template(CHAIN_ID, BLOCK_ID_FLAG_COMMIT),
            dec.sign_bytes_template(CHAIN_ID, BLOCK_ID_FLAG_NIL))


class TestFusedPrepDifferential:
    @pytest.mark.parametrize("mode", [
        0,
        cp.MODE_COUNT_FOR_BLOCK,
        cp.MODE_SELECT_COMMIT_ONLY | cp.MODE_EARLY_STOP,
        cp.MODE_SELECT_COMMIT_ONLY | cp.MODE_COUNT_FOR_BLOCK
        | cp.MODE_EARLY_STOP,
    ])
    def test_numpy_matches_object_sign_bytes(self, mode):
        vset, commit = _random_commit(120, nil=(0, 7, 33), absent=(5, 60))
        dec = Commit.decode(commit.encode())
        cb = dec.commit_block()
        cols = vset.ed25519_columns()
        pc = dec.sign_bytes_template(CHAIN_ID, BLOCK_ID_FLAG_COMMIT)
        pn = dec.sign_bytes_template(CHAIN_ID, BLOCK_ID_FLAG_NIL)
        needed = vset.total_voting_power() * 2 // 3
        sel, tallied, blk = cp._prep_commit_numpy(
            cb, cols[0], cols[1], pc[0], pn[0], pc[1], needed, mode
        )
        assert blk is not None
        # per-lane parity with the object-path sign bytes + columns
        for j in range(len(sel)):
            i = int(sel[j])
            assert blk.msg(j) == dec.vote_sign_bytes(CHAIN_ID, i)
            assert blk.pub[j].tobytes() == vset.validators[i].pub_key.bytes()
            assert blk.sig[j].tobytes() == dec.signatures[i].signature

    @pytest.mark.native_required
    @pytest.mark.parametrize("mode", [
        0,
        cp.MODE_SELECT_COMMIT_ONLY,
        cp.MODE_COUNT_FOR_BLOCK,
        cp.MODE_EARLY_STOP,
        cp.MODE_SELECT_COMMIT_ONLY | cp.MODE_EARLY_STOP,
        cp.MODE_COUNT_FOR_BLOCK | cp.MODE_EARLY_STOP,
    ])
    def test_native_matches_numpy(self, mode):
        vset, commit = _random_commit(150, nil=(2, 9, 77), absent=(1, 80))
        # edge-case timestamps: zero seconds, negative nanos, zero nanos
        sigs = list(commit.signatures)
        for i, ts in ((3, Timestamp(0, 5)), (4, Timestamp(9, -3)),
                      (6, Timestamp(12, 0))):
            cs = sigs[i]
            sigs[i] = CommitSig(
                block_id_flag=cs.block_id_flag,
                validator_address=cs.validator_address,
                timestamp=ts,
                signature=cs.signature,
            )
        commit.signatures = sigs
        _native, cb, cols, pc, pn = _native_prep_inputs(vset, commit)
        for thr in (100, vset.total_voting_power() * 2 // 3, 10 ** 12):
            a = cp.prep_commit(cb, cols[0], cols[1], pc[0], pn[0],
                               pc[1], thr, mode)
            b = cp._prep_commit_numpy(cb, cols[0], cols[1], pc[0],
                                      pn[0], pc[1], thr, mode)
            assert np.array_equal(a[0], b[0])
            assert a[1] == b[1]
            assert (a[2] is None) == (b[2] is None)
            if a[2] is None:
                continue
            assert np.array_equal(a[2].pub, b[2].pub)
            assert np.array_equal(a[2].sig, b[2].sig)
            assert np.array_equal(a[2].offsets, b[2].offsets)
            assert bytes(a[2].msgs) == bytes(b[2].msgs)

    @pytest.mark.native_required
    @pytest.mark.parametrize("n", [150, 1023, 1024, 1025, 1026, 4096])
    def test_native_matches_numpy_on_both_sides_of_the_release_floor(self, n):
        """The fused prep keeps the GIL through its third section under
        1 024 SELECTED rows and gives it up from there on (ISSUE 38): the
        six outputs are the numpy fallback's byte for byte on both sides,
        and the two of the early return (the tally fails) too."""
        vset, commit = _random_commit(n, seed=n, nil=(2, 9, 77),
                                      absent=(1, 80))
        native, cb, cols, pc, pn = _native_prep_inputs(vset, commit)
        args = cp._contiguous(cb, cols[0], cols[1])
        for thr in (vset.total_voting_power() * 2 // 3, 10 ** 12):
            res = native.commit_prep_fused(*args[1:], pc[0], pn[0], pc[1],
                                           thr, 0)
            held = [h for *_t, h in native.last_sections()]
            sel, tallied, blk = cp._prep_commit_numpy(
                cb, cols[0], cols[1], pc[0], pn[0], pc[1], thr, 0)
            assert res[0] == sel.tobytes() and res[1] == tallied
            assert sel.shape[0] == n - 2
            if thr == 10 ** 12:
                assert blk is None and len(res) == 2 and held == [True]
                continue
            assert held == [True, True, n - 2 < 1024]
            assert res[2] == blk.pub.tobytes()
            assert res[3] == blk.sig.tobytes()
            assert res[4] == bytes(blk.msgs)
            assert res[5] == np.asarray(blk.offsets, np.int64).tobytes()

    def test_commit_entries_fused_matches_legacy(self):
        vset, commit = _random_commit(90, absent=(4,))
        dec = Commit.decode(commit.encode())
        needed = vset.total_voting_power() * 2 // 3
        blk_f, tallied_f = pl.commit_entries(CHAIN_ID, vset, dec, needed)
        blk_l, tallied_l = pl.commit_entries_legacy(
            CHAIN_ID, vset, commit, needed
        )
        assert tallied_f == tallied_l
        assert np.array_equal(blk_f.pub, blk_l.pub)
        assert np.array_equal(blk_f.sig, blk_l.sig)
        assert np.array_equal(blk_f.offsets, np.asarray(blk_l.offsets))
        assert bytes(blk_f.msgs) == bytes(blk_l.msgs)

    def test_not_enough_power_parity(self):
        vset, commit = _random_commit(10, absent=tuple(range(2, 10)))
        dec = Commit.decode(commit.encode())
        needed = vset.total_voting_power() * 2 // 3
        with pytest.raises(validation.ErrNotEnoughVotingPowerSigned) as e1:
            pl.commit_entries(CHAIN_ID, vset, dec, needed)
        with pytest.raises(validation.ErrNotEnoughVotingPowerSigned) as e2:
            pl.commit_entries_legacy(CHAIN_ID, vset, commit, needed)
        assert str(e1.value) == str(e2.value)


class TestVerifyCommitFused:
    def test_valid_commit_verifies_via_fused_path(self, monkeypatch):
        vset, bid, commit = _signed_commit(6, nil=(4,))
        dec = Commit.decode(commit.encode())
        calls = []
        orig = cp._fused  # the four stages, under prep_commit_from's spans

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(cp, "_fused", spy)
        validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)
        assert calls, "fused prep was not taken for a columnar commit"

    def test_blame_parity_fused_vs_object_path(self, monkeypatch):
        vset, bid, commit = _signed_commit(6, bad=(3,))
        dec = Commit.decode(commit.encode())
        with pytest.raises(ValueError) as e_fused:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)
        # force the object path: no validator columns
        monkeypatch.setattr(ValidatorSet, "ed25519_columns", lambda self: None)
        with pytest.raises(ValueError) as e_obj:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, commit)
        assert str(e_fused.value) == str(e_obj.value)
        assert "wrong signature (#3)" in str(e_fused.value)

    def test_light_path_parity(self, monkeypatch):
        vset, bid, commit = _signed_commit(8, bad=(6,), absent=(1,))
        dec = Commit.decode(commit.encode())
        with pytest.raises(ValueError) as e_fused:
            validation.verify_commit_light(CHAIN_ID, vset, bid, 7, dec)
        monkeypatch.setattr(ValidatorSet, "ed25519_columns", lambda self: None)
        with pytest.raises(ValueError) as e_obj:
            validation.verify_commit_light(CHAIN_ID, vset, bid, 7, commit)
        assert str(e_fused.value) == str(e_obj.value)

    def test_light_early_stop_skips_trailing_bad_sig(self):
        # with equal powers, 2/3 is crossed before the last lane: the
        # light path must accept without ever verifying the bad tail
        # signature (countAllSignatures=false semantics)
        vset, bid, commit = _signed_commit(9, bad=(8,))
        dec = Commit.decode(commit.encode())
        validation.verify_commit_light(CHAIN_ID, vset, bid, 7, dec)

    def test_not_enough_power_error_parity(self, monkeypatch):
        vset, bid, commit = _signed_commit(6, absent=(1, 2, 3, 4))
        dec = Commit.decode(commit.encode())
        with pytest.raises(validation.ErrNotEnoughVotingPowerSigned) as e1:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, dec)
        monkeypatch.setattr(ValidatorSet, "ed25519_columns", lambda self: None)
        with pytest.raises(validation.ErrNotEnoughVotingPowerSigned) as e2:
            validation.verify_commit(CHAIN_ID, vset, bid, 7, commit)
        assert str(e1.value) == str(e2.value)


class TestEntryBlockConcat:
    def test_concat_single_block_passes_through_by_identity(self):
        vset, commit = _random_commit(8, seed=3)
        dec = Commit.decode(commit.encode())
        needed = vset.total_voting_power() * 2 // 3
        blk, _ = pl.commit_entries(CHAIN_ID, vset, dec, needed)
        assert EntryBlock.concat([blk]) is blk
        assert EntryBlock.concat([EntryBlock.empty(), blk]) is blk


class TestDispatchOwnerThread:
    def _entries(self, n, tag=0, bad=()):
        out = []
        for i in range(n):
            sk = ed25519.gen_priv_key(bytes([tag + 1]) * 31 + bytes([i + 1]))
            m = b"own-%d-%d" % (tag, i)
            s = sk.sign(m)
            if i in bad:
                s = s[:-1] + bytes([s[-1] ^ 1])
            out.append((sk.pub_key().bytes(), m, s))
        return out

    def test_exactly_one_thread_issues_device_dispatches(self):
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            futs = []
            threads = []
            # concurrent submitters: the device-ownership invariant must
            # hold regardless of caller concurrency
            def submit_from_thread(t):
                futs.append(v.submit(self._entries(6, tag=t)))

            for t in range(6):
                th = threading.Thread(target=submit_from_thread, args=(t,))
                th.start()
                threads.append(th)
            for th in threads:
                th.join()
            for f in list(futs):
                assert np.asarray(f.result(timeout=120)).all()
        finally:
            v.close()
        assert len(v.dispatch_thread_idents) == 1
        (ident,) = v.dispatch_thread_idents
        assert ident == v._dispatch_thread.ident
        assert ident != threading.get_ident()

    def test_single_job_passthrough_to_prepare(self, monkeypatch):
        seen = []
        orig = pl.AsyncBatchVerifier._prepare

        def spy(entries):
            seen.append(entries)
            return orig(entries)

        monkeypatch.setattr(pl.AsyncBatchVerifier, "_prepare",
                            staticmethod(spy))
        from tendermint_tpu.ops.entry_block import as_block

        blk = as_block(self._entries(5))
        v = pl.AsyncBatchVerifier(depth=1)
        try:
            res = v.submit(blk).result(timeout=120)
            assert res.all()
        finally:
            v.close()
        assert any(e is blk for e in seen), (
            "single-job dispatch must hand the submitted EntryBlock "
            "through by identity (zero-copy)"
        )

    def test_oversized_submit_splits_and_reaggregates(self, monkeypatch):
        monkeypatch.setattr(backend, "max_coalesce", lambda: 8)
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            ents = self._entries(20, bad=(13,))
            res = np.asarray(v.submit(ents).result(timeout=120))
        finally:
            v.close()
        assert res.shape == (20,)
        assert not res[13] and res.sum() == 19

    def test_dispatch_gauges_exported(self):
        from tendermint_tpu.libs.metrics import ops_stats

        v = pl.AsyncBatchVerifier(depth=1)
        try:
            assert v.submit(self._entries(4)).result(timeout=120).all()
        finally:
            v.close()
        stats = ops_stats()
        assert "dispatch_queue_depth" in stats
        assert "dispatch_busy_ratio" in stats
        assert 0.0 <= stats["dispatch_busy_ratio"] <= 1.0

    def test_queue_wait_span_recorded(self):
        from tendermint_tpu.observability import trace as _trace

        _trace.TRACER.clear()
        _trace.configure(enabled=True)
        try:
            v = pl.AsyncBatchVerifier(depth=1)
            try:
                assert v.submit(self._entries(4)).result(timeout=120).all()
            finally:
                v.close()
            names = {e[0] for e in _trace.TRACER.events()}
        finally:
            _trace.configure(enabled=False)
            _trace.TRACER.clear()
        assert "pipeline.queue_wait" in names
        assert "pipeline.dispatch" in names
