"""Columnar EntryBlock path: tuple <-> block parity (args, verdicts,
blame) across the prep/kernel stack, coalescing straddle, native-absent
fallbacks, and the RLC env-knob hardening (ISSUE 2 satellites)."""

import os

import numpy as np
import pytest

try:
    from tendermint_tpu.crypto import ed25519
except ModuleNotFoundError:
    # No cryptography wheel in this container. Do NOT flip
    # TM_TPU_PUREPY_CRYPTO here: the env leaks into every later-collected
    # module and unlocks slow OpenSSL-dependent e2e failures.
    # test_entry_block_isolated.py re-runs this module in a subprocess
    # with the fallback enabled instead.
    pytest.skip(
        "ed25519 backend unavailable (runs via test_entry_block_isolated.py)",
        allow_module_level=True,
    )
from tendermint_tpu.ops import backend
from tendermint_tpu.ops import pallas_rlc
from tendermint_tpu.ops import pipeline as pl
from tendermint_tpu.ops.entry_block import EntryBlock, as_block


def _entries(n, tag=0, bad=(), msg_len=None):
    out = []
    for i in range(n):
        sk = ed25519.gen_priv_key(bytes([tag + 1]) * 31 + bytes([i + 1]))
        m = b"eb-%d-%d" % (tag, i)
        if msg_len:
            m = m.ljust(msg_len, b"x")
        s = sk.sign(m)
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
        out.append((sk.pub_key().bytes(), m, s))
    return out


def _no_native(monkeypatch):
    import tendermint_tpu.native as native

    monkeypatch.setattr(native, "load", lambda: None)


class TestEntryBlock:
    def test_roundtrip_and_shapes(self):
        ents = _entries(5)
        blk = EntryBlock.from_entries(ents)
        assert len(blk) == 5
        assert blk.pub.shape == (5, 32) and blk.sig.shape == (5, 64)
        assert blk.to_entries() == ents
        assert blk.entry(3) == ents[3]
        assert blk.msg(2) == ents[2][1]

    def test_as_block_passthrough(self):
        blk = EntryBlock.from_entries(_entries(3))
        assert as_block(blk) is blk
        assert as_block([]).n == 0

    def test_slicing_is_zero_copy_and_correct(self):
        ents = _entries(7)
        blk = EntryBlock.from_entries(ents)
        sub = blk[2:5]
        assert sub.to_entries() == ents[2:5]
        assert sub.pub.base is not None  # numpy view, not a copy
        # nested slice of a slice
        assert sub[1:3].to_entries() == ents[3:5]
        # full + empty slices
        assert blk[:].to_entries() == ents
        assert len(blk[4:4]) == 0

    def test_concat(self):
        a, b, c = (_entries(3, tag=t) for t in range(3))
        blk = EntryBlock.concat(
            [EntryBlock.from_entries(a), EntryBlock.from_entries(b),
             EntryBlock.from_entries(c)]
        )
        assert blk.to_entries() == a + b + c
        # concat of slices (the coalescing straddle shape)
        blk2 = EntryBlock.concat(
            [EntryBlock.from_entries(a)[1:3], EntryBlock.from_entries(b)[0:2]]
        )
        assert blk2.to_entries() == a[1:3] + b[0:2]
        assert len(EntryBlock.concat([])) == 0

    def test_length_validation(self):
        with pytest.raises(ValueError, match="triples"):
            EntryBlock.from_entries([(b"\x00" * 31, b"m", b"\x00" * 64)])
        with pytest.raises(ValueError, match="triples"):
            EntryBlock.from_entries([(b"\x00" * 32, b"m", b"\x00" * 63)])

    def test_non_monotonic_offsets_rejected(self):
        # a decreasing offset table would wrap to a huge size_t length in
        # the GIL-released native consumers — must be rejected up front
        with pytest.raises(ValueError, match="non-decreasing"):
            EntryBlock(
                np.zeros((2, 32), dtype=np.uint8),
                np.zeros((2, 64), dtype=np.uint8),
                b"x" * 10,
                np.array([0, 8, 4], dtype=np.int64),
            )

    def test_commit_entries_rejects_wrong_size_key(self):
        from tests.test_types import CHAIN_ID, build_commit

        _, vset, _, commit = build_commit(n=4, height=6, round_=0)

        class FakeKey:
            def bytes(self):
                return b"\x00" * 33

        v = vset.validators[1]
        vset.validators[1] = type(v)(
            address=v.address, pub_key=FakeKey(), voting_power=v.voting_power,
            proposer_priority=v.proposer_priority,
        )
        with pytest.raises(TypeError, match="not ed25519"):
            pl.commit_entries(
                CHAIN_ID, vset, commit, vset.total_voting_power() * 2 // 3
            )


class TestSignBytesBlock:
    def test_block_matches_many_and_single(self):
        from tests.test_types import CHAIN_ID, build_commit

        _, vset, _, commit = build_commit(n=6, height=9, round_=0)
        idxs = list(range(6))
        ref = [commit.vote_sign_bytes(CHAIN_ID, i) for i in idxs]
        assert commit.vote_sign_bytes_many(CHAIN_ID, idxs) == ref
        buf, offs = commit.vote_sign_bytes_block(CHAIN_ID, idxs)
        got = [bytes(buf[offs[i] : offs[i + 1]]) for i in range(6)]
        assert got == ref

    def test_block_pure_python_fallback_parity(self, monkeypatch):
        from tests.test_types import CHAIN_ID, build_commit

        _, vset, _, commit = build_commit(n=6, height=9, round_=0)
        idxs = list(range(6))
        buf_n, offs_n = commit.vote_sign_bytes_block(CHAIN_ID, idxs)
        _no_native(monkeypatch)
        commit._sb_tpl = None
        buf_p, offs_p = commit.vote_sign_bytes_block(CHAIN_ID, idxs)
        assert bytes(buf_n) == bytes(buf_p)
        assert np.array_equal(offs_n, offs_p)

    def test_vectorized_composer_differential(self):
        """Grouped numpy composer == per-call ProtoWriter composer across
        varint length boundaries and proto3 zero-skips."""
        from tendermint_tpu.wire import canonical as C

        tpl = C.canonical_vote_template(
            chain_id="eb-chain", msg_type=C.SIGNED_MSG_TYPE_PRECOMMIT,
            height=77, round_=1, block_id=None,
        )
        cases = [0, 1, 127, 128, 16383, 16384, 2**31 - 1, 2**40,
                 C.GO_ZERO_TIME_SECONDS, 1_700_000_000]
        tss = [C.Timestamp(s, nn) for s in cases for nn in cases]
        # pad above the n >= 64 vectorized-path threshold
        tss = tss + tss
        ref = [C.compose_vote_sign_bytes(tpl, ts) for ts in tss]
        buf, offs = C.compose_vote_sign_bytes_block(tpl, tss)
        got = [buf[offs[i] : offs[i + 1]] for i in range(len(tss))]
        assert got == ref


class TestPrepParity:
    """Identical kernel argument tuples from tuple lists and EntryBlocks,
    with and without the native module (native-absent fallback parity)."""

    @pytest.mark.parametrize("use_native", [True, False])
    @pytest.mark.parametrize("prep", ["prepare_batch", "prepare_compact"])
    def test_args_match(self, monkeypatch, prep, use_native):
        if not use_native:
            _no_native(monkeypatch)
        elif __import__("tendermint_tpu.native", fromlist=["load"]).load() is None:
            pytest.skip("native module unavailable")
        ents = _entries(11, bad=(2,))
        blk = EntryBlock.from_entries(ents)
        if prep == "prepare_compact":
            from tendermint_tpu.ops import pallas_verify

            fn = pallas_verify.prepare_compact
        else:
            fn = getattr(backend, prep)
        a = fn(ents, 16)
        b = fn(blk, 16)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize("M", pallas_rlc.WIDTHS)
    @pytest.mark.parametrize("use_native", [True, False])
    def test_prepare_rlc_args_match(self, monkeypatch, use_native, M):
        if not use_native:
            _no_native(monkeypatch)
        elif __import__("tendermint_tpu.native", fromlist=["load"]).load() is None:
            pytest.skip("native module unavailable")
        # deterministic z so tuple and block runs draw identical
        # coefficients (CPU backend: seed is honored)
        monkeypatch.setenv("TM_TPU_RLC_SEED", "7")
        ents = _entries(2 * M + 1, bad=(1,))
        bucket = ((len(ents) + M - 1) // M + 1) * M  # one padding lane
        a = pallas_rlc.prepare_rlc(ents, bucket, M)
        b = pallas_rlc.prepare_rlc(EntryBlock.from_entries(ents), bucket, M)
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize("M", pallas_rlc.WIDTHS)
    def test_expand_lanes_blame_parity(self, M):
        ents = _entries(2 * M, bad=(1, M + 1))
        lane_valid = np.array([False, False])
        per_tuple = pallas_rlc.expand_lanes(lane_valid, ents, M)
        per_block = pallas_rlc.expand_lanes(
            lane_valid, EntryBlock.from_entries(ents), M
        )
        assert np.array_equal(per_tuple, per_block)
        expected = np.ones(2 * M, dtype=bool)
        expected[[1, M + 1]] = False
        assert np.array_equal(per_block, expected)


class TestKernelVerdictParity:
    def test_xla_verify_batch_tuple_vs_block(self):
        """Same verdicts and blame lanes through the XLA kernel on CPU
        for both representations."""
        ents = _entries(70, bad=(3, 41))
        ref = backend.verify_batch(ents)
        got = backend.verify_batch(EntryBlock.from_entries(ents))
        assert np.array_equal(ref, got)
        assert not got[3] and not got[41] and got.sum() == 68

    def test_device_verifier_add_block(self):
        bv = backend.Ed25519DeviceBatchVerifier(force_device=True)
        ents = _entries(70, bad=(5,))
        bv.add_block(
            EntryBlock.from_entries(ents),
            keys=[ed25519.PubKey(pk) for pk, _, _ in ents],
        )
        ok, valid = bv.verify()
        assert not ok and valid[5] is False and sum(valid) == 69

    def test_add_block_rejects_wrong_key_type(self):
        bv = backend.Ed25519DeviceBatchVerifier()
        with pytest.raises(TypeError, match="not ed25519"):
            bv.add_block(EntryBlock.from_entries(_entries(2)), keys=[object(), object()])


class TestCoalescingStraddle:
    def test_job_straddles_two_device_batches(self, monkeypatch):
        """A pipelined job whose signatures split across two coalesced
        device batches re-aggregates per-job verdicts (and blame indices
        WITHIN the job) correctly."""
        from tests.test_types import CHAIN_ID, build_commit

        monkeypatch.setattr(backend, "BUCKETS", (16,))
        jobs = []
        # commit_entries early-stops past 2/3: 10 validators x 100 power
        # -> 7 entries per job. With max_b=16, job 2's entries split 2+5
        # across the first and second device batches; the tampered lane
        # (entry 5 of job 2) lands in the SECOND batch segment.
        commits = [build_commit(n=10, height=40 + i, round_=0) for i in range(3)]
        for i, (_, vset, bid, commit) in enumerate(commits):
            if i == 2:
                cs = commit.signatures[5]
                sig = cs.signature[:-1] + bytes([cs.signature[-1] ^ 1])
                commit.signatures[5] = type(cs)(
                    block_id_flag=cs.block_id_flag,
                    validator_address=cs.validator_address,
                    timestamp=cs.timestamp,
                    signature=sig,
                )
            jobs.append((vset, bid, 40 + i, commit))
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            errors = pl.verify_commits_pipelined(CHAIN_ID, jobs, verifier=v)
        finally:
            v.close()
        assert errors[0] is None and errors[1] is None
        assert errors[2] is not None and "entry 5" in errors[2]

    def test_worker_coalesces_blocks(self, monkeypatch):
        monkeypatch.setattr(backend, "max_coalesce", lambda: 16)
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            futs = [
                v.submit(EntryBlock.from_entries(
                    _entries(6, tag=t, bad=(2,) if t == 1 else ())
                ))
                for t in range(4)
            ]
            results = [f.result(timeout=120) for f in futs]
        finally:
            v.close()
        for t, res in enumerate(results):
            assert res.shape == (6,)
            if t == 1:
                assert not res[2] and res.sum() == 5
            else:
                assert res.all()

    def test_idle_worker_wakes_promptly(self):
        import time

        v = pl.AsyncBatchVerifier(depth=2)
        try:
            time.sleep(0.3)  # let the worker go idle (event wait path)
            t0 = time.monotonic()
            res = v.submit(_entries(4)).result(timeout=60)
            assert res.all()
        finally:
            t0 = time.monotonic()
            v.close()
            assert time.monotonic() - t0 < 2.0  # close() sets the wake event


class TestRlcEnvHardening:
    def test_rlc_buckets_respect_cap(self):
        assert pallas_rlc.RLC_BUCKETS == tuple(sorted(pallas_rlc.RLC_BUCKETS))
        assert pallas_rlc.RLC_BUCKETS[-1] == pallas_rlc.MAX_SIGS
        # every multi-block bucket runs at the widest lane
        step = pallas_rlc.WIDTHS[-1] * pallas_rlc.BLOCK_LANES
        assert all(b % step == 0 and b <= pallas_rlc.MAX_SIGS
                   and pallas_rlc.lane_width(b) == pallas_rlc.WIDTHS[-1]
                   for b in pallas_rlc.RLC_BUCKETS)

    def test_plan_bucket_never_exceeds_cap(self):
        for n in (1, 511, 512, 513, 10240, pallas_rlc.MAX_SIGS,
                  pallas_rlc.MAX_SIGS + 1):
            bucket, g, block, m = pallas_rlc.plan_bucket(n)
            assert bucket <= pallas_rlc.MAX_SIGS
            assert g % block == 0 and bucket == g * m

    def test_max_sigs_validated_at_import(self):
        import subprocess
        import sys

        env = dict(os.environ, TM_TPU_RLC_MAX_SIGS="1000",
                   JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-c", "import tendermint_tpu.ops.pallas_rlc"],
            capture_output=True, env=env, timeout=120,
        )
        assert r.returncode != 0
        assert b"TM_TPU_RLC_MAX_SIGS" in r.stderr

    def test_seed_refused_on_tpu_backend(self, monkeypatch):
        import warnings

        monkeypatch.setenv("TM_TPU_RLC_SEED", "5")
        monkeypatch.delenv("TM_TPU_RLC_SEED_UNSAFE", raising=False)
        from tendermint_tpu.ops.engine import engine

        monkeypatch.setattr(pallas_rlc.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pallas_rlc, "_seed_refused", False)
        engine.cache_clear()  # the platform decision is made once: redo it
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                z1 = pallas_rlc._gen_z(64)
                z2 = pallas_rlc._gen_z(64)
        finally:
            monkeypatch.undo()
            engine.cache_clear()
        assert any("TM_TPU_RLC_SEED ignored" in str(x.message) for x in w)
        # seed ignored: draws are CSPRNG, not the deterministic stream
        assert not np.array_equal(z1, z2)

    def test_seed_honored_off_tpu_and_with_override(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_RLC_SEED", "5")
        z1 = pallas_rlc._gen_z(32)
        z2 = pallas_rlc._gen_z(32)
        assert np.array_equal(z1, z2)  # cpu backend: deterministic ok
        monkeypatch.setattr(pallas_rlc.jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("TM_TPU_RLC_SEED_UNSAFE", "1")
        z3 = pallas_rlc._gen_z(32)
        assert np.array_equal(z1, z3)


class TestReplayConsoleStep:
    def _playback(self, handler, height=10):
        """A Playback shell around a stub consensus state — step() logic
        only, no stores/WAL."""
        from types import SimpleNamespace

        from tendermint_tpu.consensus.replay_console import Playback

        pb = Playback.__new__(Playback)
        pb.warnings = []
        pb.count = 0
        rs = SimpleNamespace(height=height)
        pb.cs = SimpleNamespace(
            rs=rs,
            _handle_timeout=handler,
            _set_proposal=handler,
            _add_proposal_block_part=handler,
            _try_add_vote=lambda v, p: handler(v),
        )
        return pb

    def _rec(self, **kw):
        from types import SimpleNamespace

        base = dict(end_height=None, timeout=None, msg_kind=None,
                    msg_payload=b"", peer_id="p")
        base.update(kw)
        return SimpleNamespace(**base)

    def test_corrupt_record_warns(self, capsys):
        pb = self._playback(lambda *a: None)
        pb._records = [self._rec(msg_kind="vote", msg_payload=b"\xff\x00garbage")]
        assert pb.step(1) == 1
        assert len(pb.warnings) == 1 and "vote" in pb.warnings[0]
        assert "replay:" in capsys.readouterr().err

    def test_stale_height_skips_silently(self):
        def boom(*a):
            raise ValueError("stale")

        pb = self._playback(boom, height=10)
        pb._records = [self._rec(timeout=(1000, 3, 0, 1))]  # height 3 < 10
        assert pb.step(1) == 1
        assert pb.warnings == []

    def test_current_height_failure_warns(self):
        def boom(*a):
            raise RuntimeError("handler rejected")

        pb = self._playback(boom, height=10)
        pb._records = [self._rec(timeout=(1000, 10, 0, 1))]
        assert pb.step(1) == 1
        assert len(pb.warnings) == 1 and "handler rejected" in pb.warnings[0]


@pytest.mark.slow
class TestInterpretKernels:
    """Pallas kernels in interpret mode — slow on the CPU image (minutes
    per grid); run on the TPU driver image or with -m slow."""

    def test_pallas_interpret_parity(self):
        from tendermint_tpu.ops import pallas_verify

        ents = _entries(8, bad=(2,))
        a = pallas_verify.prepare_compact(ents, 8)
        b = pallas_verify.prepare_compact(EntryBlock.from_entries(ents), 8)
        ra = pallas_verify.verify_compact(*a, block=8, interpret=True)
        rb = pallas_verify.verify_compact(*b, block=8, interpret=True)
        assert np.array_equal(ra, rb)
        assert not ra[2] and ra.sum() == 7

    def test_rlc_interpret_parity(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_RLC_SEED", "3")
        M = 4
        ents = _entries(2 * M, bad=(1,))
        ra = pallas_rlc.verify_batch_rlc(ents, interpret=True)
        rb = pallas_rlc.verify_batch_rlc(
            EntryBlock.from_entries(ents), interpret=True
        )
        assert np.array_equal(ra, rb)
        assert not ra[1] and ra.sum() == 2 * M - 1
