"""Device-batched live-vote ingress (ISSUE 15): the split add_vote —
host-stage check_vote then verdict-stage apply_vote_verdict — must be
byte-identical (exception type AND string) to the sequential path for
EVERY error add_vote can raise: forged signature, conflicting votes
(block-vs-block and nil-vs-block equivocation, with identical evidence
votes), non-deterministic signatures, wrong height/round/type, bad
index/address, exact duplicates, and the HeightVoteSet unwanted-round
budget. Plus the accumulator itself: memo-hit short-circuit, stepped
deterministic flushing, DispatchError poisoned-window isolation (the
round still completes via the per-vote fallback, devcheck armed), the
PeerState HasVoteBits OR-learn, and the simnet replay-exactness of a
cluster running with ingress on.

Needs a working ed25519 signer: with the `cryptography` wheel the module
runs directly; without it, tests/test_vote_ingress_isolated.py re-runs
it in a subprocess under TM_TPU_PUREPY_CRYPTO=1.
"""

import importlib.util
import os
import sys
import threading
import time

import pytest

if importlib.util.find_spec("cryptography") is None and not os.environ.get(
    "TM_TPU_PUREPY_CRYPTO"
):
    pytest.skip(
        "needs an ed25519 signer (cryptography wheel or the isolated runner)",
        allow_module_level=True,
    )

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tendermint_tpu.consensus import vote_ingress as vi  # noqa: E402
from tendermint_tpu.consensus.peer_state import PeerState  # noqa: E402
from tendermint_tpu.consensus.types import (  # noqa: E402
    ErrGotVoteFromUnwantedRound,
    HeightVoteSet,
)
from tendermint_tpu.crypto import ed25519 as ed  # noqa: E402
from tendermint_tpu.libs.bits import BitArray  # noqa: E402
from tendermint_tpu.ops import epoch_cache as _epoch  # noqa: E402
from tendermint_tpu.ops import pipeline as pl  # noqa: E402
from tendermint_tpu.types import (  # noqa: E402
    BlockID,
    PartSetHeader,
    Timestamp,
    Validator,
    ValidatorSet,
    Vote,
    VoteSet,
)
from tendermint_tpu.types.vote import (  # noqa: E402
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
)
from tendermint_tpu.types.vote_set import (  # noqa: E402
    ErrVoteConflictingVotes,
    ErrVoteInvalidSignature,
    ErrVoteInvalidValidatorIndex,
    ErrVoteNonDeterministicSignature,
    ErrVoteUnexpectedStep,
)

CHAIN_ID = "vote-ingress-test"
HEIGHT = 10


def make_validators(n):
    pairs = []
    for i in range(n):
        sk = ed.gen_priv_key(bytes([i + 1]) * 32)
        pairs.append((sk, Validator.new(sk.pub_key(), 100)))
    vset = ValidatorSet.new([v for _, v in pairs])
    by_addr = {v.address: sk for sk, v in pairs}
    return [by_addr[v.address] for v in vset.validators], vset


def make_block_id(tag=b"\x01"):
    return BlockID(
        hash=tag * 32, part_set_header=PartSetHeader(total=1, hash=tag * 32)
    )


def sign_vote(sk, vset, vote_type, height, round_, block_id, idx=None):
    addr = sk.pub_key().address()
    if idx is None:
        idx, _ = vset.get_by_address(addr)
    vote = Vote(
        type=vote_type,
        height=height,
        round=round_,
        block_id=block_id,
        timestamp=Timestamp(seconds=1_600_000_000, nanos=0),
        validator_address=addr,
        validator_index=idx,
    )
    sig = sk.sign(vote.sign_bytes(CHAIN_ID))
    return Vote(**{**vote.__dict__, "signature": sig})


def fresh_sets():
    """Two independent-but-identical VoteSets: one driven sequentially,
    one through the split check/verdict path."""
    sks, vset = make_validators(4)
    seq = VoteSet(CHAIN_ID, HEIGHT, 0, PREVOTE_TYPE, vset)
    bat = VoteSet(CHAIN_ID, HEIGHT, 0, PREVOTE_TYPE, vset)
    return sks, vset, seq, bat


def batched_add(vs: VoteSet, vote: Vote):
    """The ingress path against ONE VoteSet: host check, real signature
    verify (what the device lane computes), verdict application."""
    chk = vs.check_vote(vote)
    if chk is None:
        return False
    valid = chk.pub_key.verify_signature(
        vote.sign_bytes(vs.chain_id), vote.signature
    )
    return vs.apply_vote_verdict(vote, valid)


def both_raise(seq_vs, bat_vs, vote, exc_type):
    """Drive the same vote down both paths; the exceptions must match in
    TYPE and STRING — the parity contract."""
    with pytest.raises(exc_type) as e_seq:
        seq_vs.add_vote(vote)
    with pytest.raises(exc_type) as e_bat:
        batched_add(bat_vs, vote)
    assert type(e_seq.value) is type(e_bat.value)
    assert str(e_seq.value) == str(e_bat.value)
    return e_seq.value, e_bat.value


class TestVoteSetParity:
    def test_valid_vote_parity(self):
        sks, vset, seq, bat = fresh_sets()
        v = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0, make_block_id())
        assert seq.add_vote(v) is True
        assert batched_add(bat, v) is True
        assert seq.bit_array().get_index(0)
        assert bat.bit_array().get_index(0)

    def test_forged_signature_parity(self):
        sks, vset, seq, bat = fresh_sets()
        v = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0, make_block_id())
        bad = bytearray(v.signature)
        bad[0] ^= 0x5A
        forged = Vote(**{**v.__dict__, "signature": bytes(bad)})
        both_raise(seq, bat, forged, ErrVoteInvalidSignature)

    def test_conflicting_votes_parity_and_evidence(self):
        sks, vset, seq, bat = fresh_sets()
        a = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0,
                      make_block_id(b"\x0a"))
        b = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0,
                      make_block_id(b"\x0b"))
        assert seq.add_vote(a) and batched_add(bat, a)
        es, eb = both_raise(seq, bat, b, ErrVoteConflictingVotes)
        # the evidence votes — what DuplicateVoteEvidence is built from —
        # must be identical too
        assert es.vote_a == eb.vote_a and es.vote_b == eb.vote_b
        assert es.vote_a == a and es.vote_b == b

    def test_nil_vs_block_equivocation_parity(self):
        sks, vset, seq, bat = fresh_sets()
        nil = sign_vote(sks[1], vset, PREVOTE_TYPE, HEIGHT, 0, BlockID())
        blk = sign_vote(sks[1], vset, PREVOTE_TYPE, HEIGHT, 0,
                        make_block_id(b"\x0c"))
        assert seq.add_vote(nil) and batched_add(bat, nil)
        es, eb = both_raise(seq, bat, blk, ErrVoteConflictingVotes)
        assert es.vote_a == eb.vote_a == nil
        assert es.vote_b == eb.vote_b == blk

    def test_non_deterministic_signature_parity(self):
        sks, vset, seq, bat = fresh_sets()
        v = sign_vote(sks[2], vset, PREVOTE_TYPE, HEIGHT, 0, make_block_id())
        assert seq.add_vote(v) and batched_add(bat, v)
        twiddled = bytearray(v.signature)
        twiddled[-1] ^= 0x01
        v2 = Vote(**{**v.__dict__, "signature": bytes(twiddled)})
        both_raise(seq, bat, v2, ErrVoteNonDeterministicSignature)

    def test_duplicate_returns_false_on_both_paths(self):
        sks, vset, seq, bat = fresh_sets()
        v = sign_vote(sks[3], vset, PREVOTE_TYPE, HEIGHT, 0, make_block_id())
        assert seq.add_vote(v) and batched_add(bat, v)
        assert seq.add_vote(v) is False
        # the host stage already answers a duplicate: check_vote is None
        assert bat.check_vote(v) is None
        assert batched_add(bat, v) is False

    def test_wrong_height_round_type_parity(self):
        sks, vset, seq, bat = fresh_sets()
        for h, r, t in ((HEIGHT + 1, 0, PREVOTE_TYPE),
                        (HEIGHT, 3, PREVOTE_TYPE),
                        (HEIGHT, 0, PRECOMMIT_TYPE)):
            v = sign_vote(sks[0], vset, t, h, r, make_block_id())
            both_raise(seq, bat, v, ErrVoteUnexpectedStep)

    def test_bad_index_parity(self):
        sks, vset, seq, bat = fresh_sets()
        v = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0,
                      make_block_id(), idx=-1)
        both_raise(seq, bat, v, ErrVoteInvalidValidatorIndex)
        v2 = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0,
                       make_block_id(), idx=99)
        both_raise(seq, bat, v2, ErrVoteInvalidValidatorIndex)


class TestHeightVoteSetParity:
    def test_unwanted_round_budget_parity(self):
        sks, vset = make_validators(4)
        seq = HeightVoteSet(CHAIN_ID, HEIGHT, vset)
        bat = HeightVoteSet(CHAIN_ID, HEIGHT, vset)

        def hv_batched(hvs, vote, peer):
            chk = hvs.check_vote(vote, peer)
            if chk is None:
                return False
            valid = chk.pub_key.verify_signature(
                vote.sign_bytes(CHAIN_ID), vote.signature
            )
            return hvs.apply_vote_verdict(vote, peer, valid)

        # two catchup rounds fit the per-peer budget...
        for r in (5, 7):
            v = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, r,
                          make_block_id())
            assert seq.add_vote(v, "peer-a") is True
            assert hv_batched(bat, v, "peer-a") is True
        # ...the third raises the same error on both paths
        v3 = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 9,
                       make_block_id())
        with pytest.raises(ErrGotVoteFromUnwantedRound) as e_seq:
            seq.add_vote(v3, "peer-a")
        with pytest.raises(ErrGotVoteFromUnwantedRound) as e_bat:
            bat.check_vote(v3, "peer-a")
        assert str(e_seq.value) == str(e_bat.value)

    def test_verdict_for_vanished_round_falls_back(self):
        sks, vset = make_validators(4)
        hvs = HeightVoteSet(CHAIN_ID, HEIGHT, vset)
        v = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0, make_block_id())
        chk = hvs.check_vote(v, "p")
        assert chk is not None
        # the height advanced underneath the in-flight verdict
        hvs.reset(HEIGHT, vset)
        assert hvs.apply_vote_verdict(v, "p", True) is True
        assert hvs.prevotes(0).bit_array().get_index(v.validator_index)


class _Collector:
    """Apply callback standing in for ConsensusState._on_vote_verdicts:
    records outcomes; on a window error re-drives each vote through the
    sequential per-vote path (the consensus fallback contract)."""

    def __init__(self, vote_set=None):
        self.vs = vote_set
        self.applied = []  # (round, val_idx, verdict-or-"err")
        self.errors = []
        self.done = threading.Event()
        self.want = 0

    def __call__(self, batch, verdicts, error):
        for i, p in enumerate(batch):
            if error is not None:
                self.errors.append(type(error).__name__)
                if self.vs is not None:
                    self.vs.add_vote(p.vote)  # per-vote fallback
                self.applied.append((p.vote.round, p.vote.validator_index,
                                     "err"))
            else:
                ok = bool(verdicts[i])
                if self.vs is not None and ok:
                    self.vs.apply_vote_verdict(p.vote, True)
                self.applied.append((p.vote.round, p.vote.validator_index,
                                     ok))
        if len(self.applied) >= self.want:
            self.done.set()


def _pend(vote, sk, peer="p"):
    return vi.PendingVote(vote, peer, sk.pub_key().bytes(),
                          vote.sign_bytes(CHAIN_ID),
                          t_enq=time.perf_counter())


class TestAccumulator:
    def test_memo_hit_short_circuits(self):
        """A memoized (pub, msg, sig) verdict applies immediately —
        no window, no flush — and the memo_hits counter advances."""
        sks, vset = make_validators(4)
        v = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0, make_block_id())
        real = ed.verify_zip215_fast

        class Memo:
            def __init__(self):
                self.cache = {}

            def __call__(self, pub, msg, sig):
                return real(pub, msg, sig)

        memo = Memo()
        pend = _pend(v, sks[0])
        memo.cache[(pend.pub, pend.msg, v.signature)] = True
        ed.verify_zip215_fast = memo
        col = _Collector()
        col.want = 1
        acc = vi.VoteIngress(col, stepped=True)
        try:
            acc.submit(pend, vset)
            assert col.done.wait(1.0)
            assert col.applied == [(0, v.validator_index, True)]
            assert acc.stats()["memo_hits"] == 1
            assert acc.stats()["batches"] == 0  # never windowed
        finally:
            acc.close()
            ed.verify_zip215_fast = real

    def test_stepped_flush_is_deterministic(self):
        """Stepped mode: nothing flushes until flush_pending(); then
        every open window applies inline in submission order — twice
        over, the apply order is identical."""

        def run():
            sks, vset = make_validators(4)
            col = _Collector()
            acc = vi.VoteIngress(col, stepped=True)
            try:
                for r in range(2):
                    for i, sk in enumerate(sks):
                        v = sign_vote(sk, vset, PREVOTE_TYPE, HEIGHT, r,
                                      make_block_id())
                        acc.submit(_pend(v, sk, peer=f"p{i}"), vset)
                assert col.applied == []  # stepped: no eager flush
                assert acc.flush_pending() is True
                assert acc.flush_pending() is False  # drained
                return list(col.applied)
            finally:
                acc.close()

        first, second = run(), run()
        assert first == second
        assert len(first) == 8
        assert all(ok is True for _, _, ok in first)

    def test_in_window_duplicate_dropped(self):
        sks, vset = make_validators(4)
        v = sign_vote(sks[0], vset, PREVOTE_TYPE, HEIGHT, 0, make_block_id())
        col = _Collector()
        acc = vi.VoteIngress(col, stepped=True)
        try:
            acc.submit(_pend(v, sks[0], peer="p1"), vset)
            acc.submit(_pend(v, sks[0], peer="p2"), vset)  # re-gossip copy
            assert acc.stats()["window_dups"] == 1
            acc.flush_pending()
            assert len(col.applied) == 1
        finally:
            acc.close()

    def test_poisoned_window_fails_alone_round_completes(self):
        """Devcheck armed: prep blows up for exactly one window size —
        that window's votes fall back to the per-vote sequential path,
        neighbouring windows are untouched, and the VoteSet still
        reaches +2/3. No devcheck violations along the way."""
        from tendermint_tpu.libs import devcheck

        _epoch.reset(8)
        sks, vset = make_validators(9)
        vs = VoteSet(CHAIN_ID, HEIGHT, 0, PREVOTE_TYPE, vset)
        bid = make_block_id()
        votes = [sign_vote(sk, vset, PREVOTE_TYPE, HEIGHT, 0, bid)
                 for sk in sks]
        poison_n = 5
        real = pl.AsyncBatchVerifier._prepare

        def poisoned(entries, *args, **kw):
            n = (len(entries.entries) if hasattr(entries, "entries")
                 else len(entries))
            if n == poison_n:
                raise RuntimeError("injected poison")
            return real(entries, *args, **kw)

        was_on = devcheck.enabled()
        devcheck.enable(reset=True)
        pl.AsyncBatchVerifier._prepare = staticmethod(poisoned)
        v = pl.AsyncBatchVerifier(depth=2)
        col = _Collector(vote_set=vs)
        col.want = 9
        # giant window: only explicit flush_now() submits, so each wave
        # below is exactly one device window
        acc = vi.VoteIngress(col, verifier=v, max_batch=256,
                             window_ms=60_000.0)
        try:
            for vt, sk in zip(votes[:4], sks[:4]):  # healthy window
                acc.submit(_pend(vt, sk), vset)
            acc.flush_now()
            deadline = time.time() + 60
            while len(col.applied) < 4 and time.time() < deadline:
                time.sleep(0.01)
            for vt, sk in zip(votes[4:], sks[4:]):  # poisoned window (5)
                acc.submit(_pend(vt, sk), vset)
            acc.flush_now()
            assert col.done.wait(60)
            assert acc.stats()["dispatch_errors"] == 1
            assert col.errors and all(e == "DispatchError"
                                      for e in col.errors)
            # the poisoned window fell back per-vote: every vote landed
            _, ok = vs.two_thirds_majority()
            assert ok, "round did not complete through the fallback"
            assert vs.bit_array().size() == 9
            assert all(vs.bit_array().get_index(i) for i in range(9))
            assert not devcheck.violations()
        finally:
            acc.close()
            v.close()
            pl.AsyncBatchVerifier._prepare = real
            if not was_on:
                devcheck.disable()

    def test_engine_absent_falls_back_to_host(self):
        """A window that cannot even be SUBMITTED host-verifies instead
        of erroring (sync_fallbacks counted) — byte-identical verdicts."""
        sks, vset = make_validators(4)

        class DeadVerifier:
            def submit(self, *a, **k):
                raise RuntimeError("engine is closed")

        col = _Collector()
        col.want = 4
        acc = vi.VoteIngress(col, verifier=DeadVerifier(), max_batch=256,
                             window_ms=60_000.0)
        try:
            for sk in sks:
                v = sign_vote(sk, vset, PREVOTE_TYPE, HEIGHT, 0,
                              make_block_id())
                acc.submit(_pend(v, sk), vset)
            acc.flush_now()
            assert col.done.wait(30)
            assert acc.stats()["sync_fallbacks"] >= 1
            assert all(ok is True for _, _, ok in col.applied)
        finally:
            acc.close()


class TestHasVoteBits:
    def test_or_learn_semantics(self):
        ps = PeerState("p")
        ps.apply_new_round_step(3, 0, 4, -1)
        ps.ensure_vote_bit_arrays(3, 5)
        bits = BitArray(5)
        bits.set_index(1, True)
        bits.set_index(3, True)
        ps.apply_has_vote_bits(3, 0, PREVOTE_TYPE, bits)
        assert ps.prs.prevotes.get_index(1)
        assert ps.prs.prevotes.get_index(3)
        # a later summary ORs in — earlier learned bits survive
        more = BitArray(5)
        more.set_index(0, True)
        ps.apply_has_vote_bits(3, 0, PREVOTE_TYPE, more)
        assert all(ps.prs.prevotes.get_index(i) for i in (0, 1, 3))
        assert not ps.prs.prevotes.get_index(2)

    def test_wrong_height_ignored(self):
        ps = PeerState("p")
        ps.apply_new_round_step(3, 0, 4, -1)
        ps.ensure_vote_bit_arrays(3, 5)
        bits = BitArray(5)
        bits.set_index(0, True)
        ps.apply_has_vote_bits(7, 0, PREVOTE_TYPE, bits)
        assert not ps.prs.prevotes.get_index(0)

    def test_last_commit_summary_learned(self):
        # peer at height 4: a summary for height 3 precommits lands in
        # its last-commit bits (the height+1 route)
        ps = PeerState("p")
        ps.apply_new_round_step(4, 0, 1, 2)
        bits = BitArray(4)
        bits.set_index(2, True)
        ps.apply_has_vote_bits(3, 2, PRECOMMIT_TYPE, bits)
        assert ps.prs.last_commit is not None
        assert ps.prs.last_commit.get_index(2)


@pytest.mark.slow
class TestSimnetIngress:
    def test_replay_exact_with_ingress(self):
        """4-node partition+heal smoke with the stepped accumulator
        attached on every node: a 2/2 split stalls quorum, heals, and
        two identical-seed runs still produce identical fingerprints,
        votes actually window (batches observed), and invariants hold."""
        from tendermint_tpu.simnet import Cluster
        from tendermint_tpu.simnet.faults import partition_heal_schedule

        def run():
            c = Cluster(
                n_nodes=4, seed=29, vote_ingress=True,
                faults=partition_heal_schedule(4, at_height=3,
                                               duration=2.0),
            )
            rep = c.run_to_height(6, max_virtual_s=600.0)
            fp = c.fingerprint()
            batches = sum(
                n.cs.vote_ingress.stats()["batches"] for n in c.nodes
                if n.cs is not None and n.cs.vote_ingress is not None
            )
            c.stop()
            return rep, fp, batches

        r1, fp1, b1 = run()
        r2, fp2, b2 = run()
        assert r1.ok and r2.ok, (r1.reason, r2.reason)
        assert not r1.violations and not r2.violations
        assert fp1 == fp2
        assert b1 == b2
        assert b1 > 0, "votes never windowed through the accumulator"


class TestWindowsOnASharedTable:
    """PR 32: a validator set that maps onto another set's device table
    (ops/epoch_cache.py) attaches the TABLE's rows, and two sets of one
    table at one height never share a window."""

    def test_a_mapped_sets_window_carries_the_tables_rows(self):
        from tendermint_tpu.ops.entry_block import EntryBlock
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet

        _epoch.reset(8)
        try:
            sks, vset = make_validators(6)
            joiner = make_validators(7)[0][6]
            later = ValidatorSet.new(
                [Validator.new(sk.pub_key(), 10) for sk in sks[1:] + [joiner]])
            acc = vi.VoteIngress(_Collector(), stepped=True)
            try:
                assert acc._window_key(HEIGHT, vset) == (HEIGHT, id(vset))  # cold
                k_old = acc._window_key(HEIGHT + 1, vset)
                k_new = acc._window_key(HEIGHT + 1, later)
            finally:
                acc.close()
            assert k_old[1] == k_new[1], "one table"
            assert k_old[1][:32] == vset.hash()
            assert k_old != k_new and hash(k_old) != hash(k_new)
            table = _epoch.cache().get(k_old[1])
            assert table.n_rows == 7
            cols = later.ed25519_columns()[0]
            assert (table.pub_rows[k_new[2].rows] == cols).all()
            # a window of the later set's validators 0, 3, 5
            bid = make_block_id()
            by_addr = {sk.pub_key().address(): sk for sk in sks + [joiner]}
            batch = []
            for i in (0, 3, 5):
                sk = by_addr[later.validators[i].address]
                batch.append(_pend(sign_vote(sk, later, PREVOTE_TYPE,
                                             HEIGHT + 1, 0, bid), sk))
            blk = EntryBlock.from_entries(
                [(p.pub, p.msg, p.vote.signature) for p in batch])
            vi.VoteIngress._attach(blk, k_new, batch)
            assert blk.epoch_key == k_old[1]
            assert (table.pub_rows[blk.val_idx] == blk.pub).all()
            assert list(blk.val_idx) != [0, 3, 5], "not the set's own rows"
        finally:
            _epoch.reset()

    def test_a_windows_cached_name_goes_stale_with_its_table(self):
        """The window key is cached for the height; when the table is
        evicted and built again meanwhile, the cached (name, rows) must
        lead nowhere: the window's block rides the uncached path."""
        from tendermint_tpu.ops.entry_block import EntryBlock
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet

        _epoch.reset(1)
        try:
            sks, vset = make_validators(6)
            joiner = make_validators(7)[0][6]
            later = ValidatorSet.new(
                [Validator.new(sk.pub_key(), 10) for sk in sks[1:] + [joiner]])
            _epoch.note_valset(vset)
            acc = vi.VoteIngress(_Collector(), stepped=True)
            try:
                key = acc._window_key(HEIGHT, later)      # mapped: row 6
                assert int(key[2].rows.max()) == 6
                stranger = ValidatorSet.new([Validator.new(
                    ed.gen_priv_key(bytes([200 + i]) * 32).pub_key(), 100)
                    for i in range(6)])
                assert _epoch.note_valset(stranger) is None   # depth 1: evicts
                assert _epoch.note_valset(vset) is None       # built again
                assert _epoch.note_valset(vset) != key[1]
                assert acc._window_key(HEIGHT, later) == key, "cached"
            finally:
                acc.close()
            bid = make_block_id()
            batch = [_pend(sign_vote(joiner, later, PREVOTE_TYPE, HEIGHT, 0,
                                     bid), joiner)]
            blk = EntryBlock.from_entries(
                [(p.pub, p.msg, p.vote.signature) for p in batch])
            vi.VoteIngress._attach(blk, key, batch)
            assert blk.epoch_key == key[1] and list(blk.val_idx) == [6]
            assert _epoch.lookup(blk) is None
        finally:
            _epoch.reset()
