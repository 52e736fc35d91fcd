"""Async device verification pipeline (SURVEY.md §7 hard-part 4 /
BASELINE config #5): double-buffered batch submission, pipelined commit
verification, pipelined adjacent-header verification, and the blocksync
speculative pre-verify path."""

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.libs import devcheck
from tendermint_tpu.ops import pipeline as pl
from tests.test_types import CHAIN_ID, build_commit, make_validators


@pytest.fixture(autouse=True)
def _devcheck_armed():
    """ISSUE 8: the whole pipeline suite runs with the runtime invariant
    checkers on — device-thread assertions, lock-order cycle detection,
    and the write-after-resolve canary. Any violation fails the test
    that caused it at teardown."""
    devcheck.enable(reset=True)
    yield
    try:
        devcheck.check()
    finally:
        devcheck.reset_state()
        devcheck.disable()


def _entries(n, tag=0, bad=()):
    out = []
    for i in range(n):
        sk = ed25519.gen_priv_key(bytes([tag + 1]) * 31 + bytes([i + 1]))
        m = b"pipe-%d-%d" % (tag, i)
        s = sk.sign(m)
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
        out.append((sk.pub_key().bytes(), m, s))
    return out


class TestAsyncBatchVerifier:
    def test_overlapped_batches_resolve_in_order(self):
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            futs = [v.submit(_entries(8, tag=t, bad=(3,) if t == 2 else ())) for t in range(5)]
            results = [f.result(timeout=120) for f in futs]
        finally:
            v.close()
        for t, res in enumerate(results):
            assert res.shape == (8,)
            if t == 2:
                assert not res[3] and res.sum() == 7
            else:
                assert res.all()

    def test_shared_verifier_is_singleton(self):
        assert pl.shared_verifier() is pl.shared_verifier()

    def test_poisoned_job_fails_alone_dispatcher_survives(self, monkeypatch):
        """ISSUE 6 satellite: a job whose kernel launch (or lazy
        epoch-table upload — same code path: inside the prepared callable
        on the dispatch-owner thread) raises must fail ONLY its own
        future, with epoch/bucket context, and the dispatcher must keep
        serving later jobs."""
        real_prepare = pl.AsyncBatchVerifier._prepare
        POISON_N = 3  # poisoned jobs are 3 entries long, healthy ones differ

        def prep(entries):
            f, args, rlc, bucket = real_prepare(entries)
            if len(entries) == POISON_N:
                def boom(*_a):
                    raise RuntimeError("epoch table upload exploded")

                return boom, args, rlc, bucket
            return f, args, rlc, bucket

        monkeypatch.setattr(
            pl.AsyncBatchVerifier, "_prepare", staticmethod(prep)
        )
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            bad = v.submit(_entries(POISON_N, tag=9))
            with pytest.raises(pl.DispatchError) as ei:
                bad.result(timeout=120)
            assert "bucket=" in str(ei.value) and "epoch=" in str(ei.value)
            assert isinstance(ei.value.__cause__, RuntimeError)
            # the dispatcher must still be alive and serving
            assert v._dispatch_thread.is_alive()
            good = v.submit(_entries(8, tag=10))
            res = good.result(timeout=120)
            assert res.shape == (8,) and res.all()
            # and a second poisoned job again fails only itself
            bad2 = v.submit(_entries(POISON_N, tag=11))
            with pytest.raises(pl.DispatchError):
                bad2.result(timeout=120)
            good2 = v.submit(_entries(5, tag=12))
            assert good2.result(timeout=120).all()
            assert v._dispatch_thread.is_alive()
            assert v._resolve_thread.is_alive()
        finally:
            v.close()


class TestPipelinedCommits:
    def test_verify_commits_pipelined_mixed(self):
        jobs = []
        # 3 good commits + 1 with a tampered signature
        commits = [build_commit(n=4, height=10 + i, round_=0) for i in range(4)]
        for i, (sks, vset, block_id, commit) in enumerate(commits):
            if i == 2:
                cs = commit.signatures[1]
                sig = cs.signature[:-1] + bytes([cs.signature[-1] ^ 1])
                commit.signatures[1] = type(cs)(
                    block_id_flag=cs.block_id_flag,
                    validator_address=cs.validator_address,
                    timestamp=cs.timestamp,
                    signature=sig,
                )
            jobs.append((vset, block_id, 10 + i, commit))
        errors = pl.verify_commits_pipelined(CHAIN_ID, jobs)
        assert errors[0] is None and errors[1] is None and errors[3] is None
        assert errors[2] is not None and "signature" in errors[2]

    def test_not_enough_power_reported(self):
        sks, vset, block_id, commit = build_commit(n=4, height=5, round_=0)
        # keep only one signature: power 100/400 < 2/3
        from tendermint_tpu.types.block import CommitSig

        commit.signatures = [
            commit.signatures[0],
            CommitSig.absent(), CommitSig.absent(), CommitSig.absent(),
        ]
        errors = pl.verify_commits_pipelined(CHAIN_ID, [(vset, block_id, 5, commit)])
        assert errors[0] is not None and "power" in errors[0].lower()


class TestPipelinedHeaders:
    def _make_chain(self, n_headers, n_vals=4):
        """A synthetic adjacent header chain signed by one validator set."""
        from dataclasses import replace

        from tendermint_tpu.types import SignedHeader
        from tendermint_tpu.types.block import BlockID, Header, PartSetHeader, Version
        from tendermint_tpu.types.vote import PRECOMMIT_TYPE
        from tendermint_tpu.types.vote_set import VoteSet
        from tendermint_tpu.wire.canonical import Timestamp
        from tests.test_types import sign_vote

        sks, vset = make_validators(n_vals)
        headers = []
        prev_hash = b"\x00" * 32
        shs = []
        for h in range(1, n_headers + 2):
            hdr = Header(
                version=Version(block=11, app=0),
                chain_id=CHAIN_ID,
                height=h,
                time=Timestamp(seconds=1_600_000_000 + h),
                last_block_id=BlockID(
                    hash=prev_hash,
                    part_set_header=PartSetHeader(total=1, hash=prev_hash),
                ) if h > 1 else BlockID(),
                validators_hash=vset.hash(),
                next_validators_hash=vset.hash(),
                consensus_hash=b"\x01" * 32,
                app_hash=b"",
                proposer_address=vset.validators[0].address,
            )
            bid = BlockID(
                hash=hdr.hash(),
                part_set_header=PartSetHeader(total=1, hash=hdr.hash()),
            )
            vs = VoteSet(CHAIN_ID, h, 0, PRECOMMIT_TYPE, vset)
            for sk in sks:
                vs.add_vote(sign_vote(sk, vset, PRECOMMIT_TYPE, h, 0, bid))
            shs.append((SignedHeader(header=hdr, commit=vs.make_commit()), vset))
            prev_hash = hdr.hash()
        return shs

    def test_adjacent_range_pipelined(self):
        shs = self._make_chain(6)
        trusted = shs[0][0]
        pl.verify_headers_pipelined(CHAIN_ID, trusted, shs[1:])

    def test_adjacent_range_detects_broken_continuity(self):
        shs = self._make_chain(4)
        trusted = shs[0][0]
        # skip one header -> not adjacent
        with pytest.raises(ValueError, match="adjacent"):
            pl.verify_headers_pipelined(CHAIN_ID, trusted, shs[2:])

    def test_adjacent_range_detects_bad_signature(self):
        shs = self._make_chain(4)
        trusted = shs[0][0]
        sh, vset = shs[2]
        cs = sh.commit.signatures[0]
        sh.commit.signatures[0] = type(cs)(
            block_id_flag=cs.block_id_flag,
            validator_address=cs.validator_address,
            timestamp=cs.timestamp,
            signature=cs.signature[:-1] + bytes([cs.signature[-1] ^ 1]),
        )
        with pytest.raises(ValueError, match="signature|power"):
            pl.verify_headers_pipelined(CHAIN_ID, trusted, shs[1:])


class TestBlocksyncSpeculation:
    def test_fresh_node_catches_up_with_speculative_verify(self, monkeypatch):
        """The blocksync pipelined path: force the speculation gate open so
        every block's commit pre-verifies through the device pipeline."""
        from tendermint_tpu.ops import backend as _backend

        monkeypatch.setattr(_backend, "DEVICE_THRESHOLD", 0)
        import tests.test_light_blocksync as tlb

        # reuse the existing blocksync e2e with the speculation gate open,
        # building the source chain inline (same as its produced_chain fixture)
        inst = tlb.TestBlockSync()
        sk = ed25519.gen_priv_key(bytes([7]) * 32)
        cs, bstore, _ = tlb.make_node([sk], 0)
        cs.start()
        try:
            cs.wait_for_height(5, timeout=60)
        finally:
            cs.stop()
        inst.test_fresh_node_catches_up((cs, bstore))
