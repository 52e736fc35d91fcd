"""Types layer tests: hashing, wire round-trips, proposer rotation,
vote sets, and commit verification through both host and device paths.

Mirrors the reference's test strategy for types/ (SURVEY.md §4):
validator_set_test.go proposer-rotation cases, vote_set_test.go quorum
cases, block_test.go hashing/ValidateBasic."""

import pytest

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.types import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    Block,
    BlockID,
    Commit,
    CommitSig,
    Data,
    Fraction,
    Header,
    PartSetHeader,
    Timestamp,
    Validator,
    ValidatorSet,
    Vote,
    VoteSet,
    ErrNotEnoughVotingPowerSigned,
    ErrVoteConflictingVotes,
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    verify_commit,
    verify_commit_light,
    verify_commit_light_trusting,
)
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.vote import vote_from_commit_sig

CHAIN_ID = "test-chain"


def make_validators(n, power=None):
    """n deterministic validators; returns (privkeys, ValidatorSet)."""
    pairs = []
    for i in range(n):
        sk = ed25519.gen_priv_key(bytes([i + 1]) * 32)
        pairs.append((sk, Validator.new(sk.pub_key(), power[i] if power else 100)))
    vset = ValidatorSet.new([v for _, v in pairs])
    # key privkeys by address so they follow the set's sort order
    by_addr = {v.address: sk for sk, v in pairs}
    return [by_addr[v.address] for v in vset.validators], vset


def sign_vote(sk, vset, vote_type, height, round_, block_id, ts=None):
    addr = sk.pub_key().address()
    idx, _ = vset.get_by_address(addr)
    vote = Vote(
        type=vote_type,
        height=height,
        round=round_,
        block_id=block_id,
        timestamp=ts or Timestamp(seconds=1_600_000_000, nanos=0),
        validator_address=addr,
        validator_index=idx,
    )
    sig = sk.sign(vote.sign_bytes(CHAIN_ID))
    return Vote(**{**vote.__dict__, "signature": sig})


def make_block_id(tag=b"\x01"):
    return BlockID(
        hash=tag * 32, part_set_header=PartSetHeader(total=1, hash=tag * 32)
    )


class TestBlockHashing:
    def test_header_hash_deterministic_and_field_sensitive(self):
        h = Header(
            chain_id=CHAIN_ID,
            height=5,
            time=Timestamp(seconds=100, nanos=5),
            validators_hash=b"\x01" * 32,
            next_validators_hash=b"\x02" * 32,
            consensus_hash=b"\x03" * 32,
            app_hash=b"app",
            proposer_address=b"\x04" * 20,
        )
        h2 = Header(**{**h.__dict__, "height": 6})
        assert h.hash() != h2.hash()
        assert len(h.hash()) == 32
        assert Header(chain_id=CHAIN_ID, height=5).hash() == b""  # no valhash

    def test_header_wire_roundtrip(self):
        h = Header(
            chain_id=CHAIN_ID,
            height=7,
            time=Timestamp(seconds=123, nanos=456),
            last_block_id=make_block_id(),
            validators_hash=b"\x01" * 32,
            proposer_address=b"\x04" * 20,
        )
        assert Header.decode(h.encode()) == h

    def test_commit_hash_and_roundtrip(self):
        cs = CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=b"\x05" * 20,
            timestamp=Timestamp(seconds=9),
            signature=b"\x06" * 64,
        )
        commit = Commit(height=3, round=1, block_id=make_block_id(), signatures=[cs])
        assert len(commit.hash()) == 32
        rt = Commit.decode(commit.encode())
        assert rt.height == 3 and rt.round == 1 and rt.signatures == [cs]
        assert rt.block_id == commit.block_id

    def test_block_fill_header_and_validate(self):
        lc = Commit(
            height=1,
            round=0,
            block_id=make_block_id(),
            signatures=[
                CommitSig(
                    block_id_flag=BLOCK_ID_FLAG_COMMIT,
                    validator_address=b"\x05" * 20,
                    timestamp=Timestamp(seconds=9),
                    signature=b"\x06" * 64,
                )
            ],
        )
        b = Block(
            header=Header(
                chain_id=CHAIN_ID,
                height=2,
                validators_hash=b"\x01" * 32,
                next_validators_hash=b"\x01" * 32,
                consensus_hash=b"\x02" * 32,
                proposer_address=b"\x04" * 20,
            ),
            data=Data(txs=[b"tx1", b"tx2"]),
            last_commit=lc,
        )
        b.fill_header()
        b.validate_basic()
        rt = Block.decode(b.encode())
        assert rt.header == b.header
        assert rt.data.txs == [b"tx1", b"tx2"]
        assert rt.last_commit.hash() == lc.hash()


class TestPartSet:
    def test_chunk_proof_reassemble(self):
        data = bytes(range(256)) * 1024  # 256 KiB -> 4 parts
        ps = PartSet.from_data(data)
        assert ps.total() == 4 and ps.is_complete()
        ps2 = PartSet.new_from_header(ps.header())
        # add out of order; duplicates rejected as False
        for idx in (2, 0, 3, 1):
            assert ps2.add_part(ps.get_part(idx))
        assert not ps2.add_part(ps.get_part(1))
        assert ps2.is_complete()
        assert ps2.assemble() == data

    def test_corrupt_part_rejected(self):
        data = b"x" * 200000
        ps = PartSet.from_data(data)
        ps2 = PartSet.new_from_header(ps.header())
        p = ps.get_part(0)
        from tendermint_tpu.types.part_set import Part

        bad = Part(index=0, bytes=p.bytes[:-1] + b"\x00", proof=p.proof)
        with pytest.raises(ValueError):
            ps2.add_part(bad)


class TestValidatorSet:
    def test_sorting_and_hash(self):
        _, vset = make_validators(5, power=[5, 4, 3, 2, 1])
        powers = [v.voting_power for v in vset.validators]
        assert powers == sorted(powers, reverse=True)
        assert len(vset.hash()) == 32

    def test_proposer_rotation_is_fair(self):
        _, vset = make_validators(3, power=[1, 2, 3])
        counts = {}
        vs = vset.copy()
        for _ in range(600):
            p = vs.get_proposer()
            counts[p.address] = counts.get(p.address, 0) + 1
            vs.increment_proposer_priority(1)
        by_power = {v.address: v.voting_power for v in vset.validators}
        # each validator proposes proportionally to voting power (1:2:3)
        for addr, c in counts.items():
            assert abs(c - 100 * by_power[addr]) <= 2, (c, by_power[addr])

    def test_update_with_change_set(self):
        sks, vset = make_validators(3, power=[10, 10, 10])
        tvp = vset.total_voting_power()
        assert tvp == 30
        # bump one validator, remove another, add a new one
        newsk = ed25519.gen_priv_key(bytes([99]) * 32)
        changes = [
            Validator.new(sks[0].pub_key(), 20),
            Validator.new(sks[1].pub_key(), 0),  # removal
            Validator.new(newsk.pub_key(), 5),
        ]
        vset.update_with_change_set(changes)
        assert vset.size() == 3
        assert vset.total_voting_power() == 35
        _, v = vset.get_by_address(sks[0].pub_key().address())
        assert v.voting_power == 20
        assert not vset.has_address(sks[1].pub_key().address())

    def test_from_existing_preserves_priorities(self):
        _, vset = make_validators(4)
        vset.increment_proposer_priority(3)
        rebuilt = ValidatorSet.from_existing([v.copy() for v in vset.validators])
        assert [v.proposer_priority for v in rebuilt.validators] == [
            v.proposer_priority for v in vset.validators
        ]

    def test_wire_roundtrip(self):
        _, vset = make_validators(3)
        rt = ValidatorSet.decode(vset.encode())
        assert rt.hash() == vset.hash()
        assert rt.total_voting_power() == vset.total_voting_power()


def build_commit(n=4, power=None, height=10, round_=1):
    sks, vset = make_validators(n, power=power)
    block_id = make_block_id()
    vote_set = VoteSet(CHAIN_ID, height, round_, PRECOMMIT_TYPE, vset)
    for sk in sks:
        vote_set.add_vote(sign_vote(sk, vset, PRECOMMIT_TYPE, height, round_, block_id))
    return sks, vset, block_id, vote_set.make_commit()


class TestVoteSet:
    def test_quorum_tracking(self):
        sks, vset = make_validators(4)  # 4 x 100 power, quorum = 267
        block_id = make_block_id()
        vs = VoteSet(CHAIN_ID, 10, 0, PREVOTE_TYPE, vset)
        for i, sk in enumerate(sks[:2]):
            assert vs.add_vote(sign_vote(sk, vset, PREVOTE_TYPE, 10, 0, block_id))
        assert not vs.has_two_thirds_majority()
        assert vs.add_vote(sign_vote(sks[2], vset, PREVOTE_TYPE, 10, 0, block_id))
        assert vs.has_two_thirds_majority()
        maj, ok = vs.two_thirds_majority()
        assert ok and maj == block_id
        # duplicate -> False, not an error
        assert not vs.add_vote(sign_vote(sks[2], vset, PREVOTE_TYPE, 10, 0, block_id))

    def test_conflicting_vote_raises_and_is_tracked(self):
        sks, vset = make_validators(4)
        vs = VoteSet(CHAIN_ID, 10, 0, PREVOTE_TYPE, vset)
        a, b = make_block_id(b"\x0a"), make_block_id(b"\x0b")
        assert vs.add_vote(sign_vote(sks[0], vset, PREVOTE_TYPE, 10, 0, a))
        with pytest.raises(ErrVoteConflictingVotes) as ei:
            vs.add_vote(sign_vote(sks[0], vset, PREVOTE_TYPE, 10, 0, b))
        assert ei.value.vote_a.block_id == a
        assert ei.value.vote_b.block_id == b

    def test_wrong_step_and_bad_signature(self):
        sks, vset = make_validators(2)
        vs = VoteSet(CHAIN_ID, 10, 0, PREVOTE_TYPE, vset)
        with pytest.raises(ValueError):
            vs.add_vote(sign_vote(sks[0], vset, PREVOTE_TYPE, 11, 0, make_block_id()))
        good = sign_vote(sks[0], vset, PREVOTE_TYPE, 10, 0, make_block_id())
        bad = Vote(**{**good.__dict__, "signature": b"\x00" * 64})
        with pytest.raises(ValueError):
            vs.add_vote(bad)

    def test_make_commit_includes_nil_and_absent(self):
        sks, vset = make_validators(4)
        block_id = make_block_id()
        vs = VoteSet(CHAIN_ID, 10, 0, PRECOMMIT_TYPE, vset)
        for sk in sks[:3]:
            vs.add_vote(sign_vote(sk, vset, PRECOMMIT_TYPE, 10, 0, block_id))
        # 4th validator votes nil
        vs.add_vote(sign_vote(sks[3], vset, PRECOMMIT_TYPE, 10, 0, BlockID()))
        commit = vs.make_commit()
        flags = [cs.block_id_flag for cs in commit.signatures]
        assert flags.count(BLOCK_ID_FLAG_COMMIT) == 3
        assert commit.block_id == block_id


class TestVerifyCommit:
    def test_verify_commit_host_path(self):
        sks, vset, block_id, commit = build_commit(4)
        verify_commit(CHAIN_ID, vset, block_id, 10, commit)  # no raise
        verify_commit_light(CHAIN_ID, vset, block_id, 10, commit)
        verify_commit_light_trusting(CHAIN_ID, vset, commit, Fraction(1, 3))

    def test_verify_commit_device_path(self, monkeypatch):
        import tendermint_tpu.ops  # noqa: F401 — installs device factory

        monkeypatch.setenv("TM_TPU_FORCE_DEVICE", "1")
        sks, vset, block_id, commit = build_commit(4)
        verify_commit(CHAIN_ID, vset, block_id, 10, commit)

    def test_verify_commit_device_blames_bad_signature(self, monkeypatch):
        import tendermint_tpu.ops  # noqa: F401

        monkeypatch.setenv("TM_TPU_FORCE_DEVICE", "1")
        sks, vset, block_id, commit = build_commit(4)
        bad = CommitSig(
            block_id_flag=commit.signatures[2].block_id_flag,
            validator_address=commit.signatures[2].validator_address,
            timestamp=commit.signatures[2].timestamp,
            signature=b"\x01" * 64,
        )
        commit.signatures[2] = bad
        with pytest.raises(ValueError, match=r"wrong signature \(#2\)"):
            verify_commit(CHAIN_ID, vset, block_id, 10, commit)

    def test_not_enough_power(self):
        sks, vset = make_validators(4)
        block_id = make_block_id()
        vs = VoteSet(CHAIN_ID, 10, 1, PRECOMMIT_TYPE, vset)
        for sk in sks[:3]:
            vs.add_vote(sign_vote(sk, vset, PRECOMMIT_TYPE, 10, 1, block_id))
        commit = vs.make_commit()
        # drop one signature to absent: tallied 200 of 400 < 2/3
        commit.signatures[0] = CommitSig.absent()
        commit.signatures[1] = CommitSig.absent()
        with pytest.raises(ErrNotEnoughVotingPowerSigned):
            verify_commit(CHAIN_ID, vset, block_id, 10, commit)

    def test_commit_height_block_id_mismatch(self):
        sks, vset, block_id, commit = build_commit(4)
        with pytest.raises(ValueError):
            verify_commit(CHAIN_ID, vset, block_id, 11, commit)
        with pytest.raises(ValueError):
            verify_commit(CHAIN_ID, vset, make_block_id(b"\x0f"), 10, commit)

    def test_light_trusting_by_address_lookup(self):
        # trusting path looks up validators by address: use a superset valset
        sks, vset, block_id, commit = build_commit(4)
        extra = ed25519.gen_priv_key(bytes([77]) * 32)
        bigger = ValidatorSet.new(
            [v.copy() for v in vset.validators] + [Validator.new(extra.pub_key(), 100)]
        )
        verify_commit_light_trusting(CHAIN_ID, bigger, commit, Fraction(1, 3))

    def test_vote_roundtrip_and_commit_sig(self):
        sks, vset = make_validators(2)
        v = sign_vote(sks[0], vset, PRECOMMIT_TYPE, 5, 0, make_block_id())
        assert Vote.decode(v.encode()) == v
        cs = v.to_commit_sig()
        assert cs.for_block()
        back = vote_from_commit_sig(cs, v.block_id, 5, 0, v.validator_index)
        assert back.sign_bytes(CHAIN_ID) == v.sign_bytes(CHAIN_ID)


# ---------------------------------------------------------------------------
# PR 32: a validator set and a commit arrive as wire bytes inside every
# light-client request — what decode gives is what the encoder was given,
# and validate_basic walks decoded signatures as it walks objects.
# ---------------------------------------------------------------------------


def _wire_valset(n=7, powers=None, priorities=None):
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    vals = []
    for i in range(n):
        pk = ed25519.gen_priv_key(bytes([40 + i]) * 32).pub_key()
        vals.append(Validator(pk.address(), pk,
                              (powers or [100] * n)[i],
                              (priorities or [0] * n)[i]))
    return ValidatorSet(validators=vals, proposer=vals[0])


class TestValidatorSetFromWire:
    @pytest.mark.parametrize("powers,priorities", [
        (None, None),
        ([1, 2 ** 40, 7, 100, 100, 3, 9], [5, -5, 0, -(2 ** 50), 1, 2, 3]),
    ], ids=["equal", "wide"])
    def test_decode_gives_the_set_its_hash_and_its_columns(self, powers,
                                                           priorities):
        import numpy as np

        from tendermint_tpu.crypto import merkle
        from tendermint_tpu.types.validator_set import ValidatorSet

        src = _wire_valset(powers=powers, priorities=priorities)
        got = ValidatorSet.decode(src.encode())
        assert got.validators == src.validators
        assert got.encode() == src.encode()
        assert got.hash() == merkle.hash_from_byte_slices(
            [v.bytes() for v in src.validators])
        want = src.ed25519_columns()
        cols = got.ed25519_columns()
        assert np.array_equal(cols[0], want[0]) and \
            np.array_equal(cols[1], want[1])

    @pytest.mark.parametrize("mangle", [
        lambda r: r[:57],                              # a short key
        lambda r: r[:30],                              # cut inside the key
    ], ids=["short_key", "truncated"])
    def test_a_mangled_validator_record_is_refused(self, mangle):
        from tendermint_tpu.types.validator_set import Validator

        raw = mangle(_wire_valset(1).validators[0].encode())
        with pytest.raises(ValueError):
            Validator.decode(raw)

    def test_a_changed_set_drops_the_decoded_columns(self):
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet

        got = ValidatorSet.decode(_wire_valset().encode())
        before = got.hash()
        pk = ed25519.gen_priv_key(b"\x66" * 32).pub_key()
        got.update_with_change_set([Validator.new(pk, 5)])
        assert got._ed_cols is None and got.hash() != before
        assert got.ed25519_columns()[0].shape == (8, 32)


class TestCommitValidateBasicFromWire:
    def _commit(self, n=5):
        from tendermint_tpu.types.block import (
            BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader,
        )
        from tendermint_tpu.wire.canonical import Timestamp

        bid = BlockID(hash=b"\x11" * 32,
                      part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
        sigs = [CommitSig.absent()] + [
            CommitSig(block_id_flag=BLOCK_ID_FLAG_COMMIT,
                      validator_address=bytes([i]) * 20,
                      timestamp=Timestamp(seconds=1_700_000_000 + i),
                      signature=bytes([i]) * 64) for i in range(1, n)]
        return Commit(height=3, round=0, block_id=bid, signatures=sigs)

    def test_decoded_columns_pass_as_the_objects_do(self):
        from tendermint_tpu.types.block import Commit, CommitSigs

        dec = Commit.decode(self._commit().encode())
        assert isinstance(dec.signatures, CommitSigs)
        dec.validate_basic()
        self._commit().validate_basic()
        assert list(dec.signatures) == self._commit().signatures

    def test_a_tampered_view_is_walked_again(self):
        from dataclasses import replace

        from tendermint_tpu.types.block import Commit

        dec = Commit.decode(self._commit().encode())
        dec.signatures[2] = replace(dec.signatures[2], signature=b"")
        with pytest.raises(ValueError, match="wrong CommitSig #2: signature is missing"):
            dec.validate_basic()

    def test_objects_and_odd_records_are_walked(self):
        from dataclasses import replace

        from tendermint_tpu.types.block import Commit

        c = self._commit()
        c.signatures[1] = replace(c.signatures[1], validator_address=b"\x01" * 19)
        with pytest.raises(ValueError, match="expected ValidatorAddress size"):
            c.validate_basic()
        # off the canonical shape the decode keeps objects, and the walk
        with pytest.raises(ValueError, match="expected ValidatorAddress size"):
            Commit.decode(c.encode()).validate_basic()


# ---------------------------------------------------------------------------
# PR 33: the set inside a light block is decoded by a native pass where the
# module is there and by the Python walk where it is not — to equal blocks
# and equal verdicts.
# ---------------------------------------------------------------------------

_LIGHT_CHAIN = dict(seed=33, n_headers=4, n_vals=8)

_LIGHT_SCRIPT = """
import json, sys
import chip_smoke
from tendermint_tpu import native
from tendermint_tpu.libs.metrics import ops_stats
from tendermint_tpu.light import verifier
from tendermint_tpu.light.provider import LightBlock
from tendermint_tpu.wire.canonical import Timestamp

wires = chip_smoke.build_churn_chain(**json.loads(sys.argv[1]))
blocks = [LightBlock.decode(w) for w in wires]
# the last step twice: as it is, then under the validator set of the height below
steps = [(k, blocks[k].validators) for k in range(1, len(blocks))]
steps.append((len(blocks) - 1, blocks[-2].validators))
now = Timestamp(seconds=chip_smoke.T0 + len(wires) + 1)
verdicts = []
for k, vals in steps:
    try:
        verifier.verify_adjacent(blocks[k - 1].signed_header,
                                 blocks[k].signed_header, vals, 3600.0, now, 10.0)
        verdicts.append(["ok", ""])
    except Exception as e:
        verdicts.append([type(e).__name__, str(e)])
stats = ops_stats()
print(json.dumps({
    "module": native.load() is not None,
    "native": stats["valset_decode_native"],
    "python": stats["valset_decode_python"],
    "roundtrip": [b.encode() == w for b, w in zip(blocks, wires)],
    "valset_hashes": [b.validators.hash().hex() for b in blocks],
    "verdicts": verdicts,
}))
"""


class TestLightBlockFromWire:
    @staticmethod
    def _wires():
        import chip_smoke

        return chip_smoke.build_churn_chain(**_LIGHT_CHAIN)

    @staticmethod
    def _decode(wire, path):
        from unittest import mock

        from tendermint_tpu import native as native_mod
        from tendermint_tpu.light.provider import LightBlock

        if path == "native":
            return LightBlock.decode(wire)
        with mock.patch.object(native_mod, "load", lambda: None):
            return LightBlock.decode(wire)

    @pytest.mark.parametrize("path", ["native", "walk"])
    def test_decode_of_encode_is_the_block(self, path):
        for wire in self._wires():
            lb = self._decode(wire, path)
            assert lb.encode() == wire
            assert lb.validators.hash() == lb.signed_header.header.validators_hash
            again = self._decode(lb.encode(), path)
            assert again.signed_header == lb.signed_header
            assert again.validators.validators == lb.validators.validators
            assert again.validators.proposer == lb.validators.proposer

    @pytest.mark.native_required
    def test_both_paths_decode_equal_blocks(self):
        from tendermint_tpu.libs.metrics import ops_stats

        for wire in self._wires():
            before = ops_stats()
            a, b = self._decode(wire, "native"), self._decode(wire, "walk")
            after = ops_stats()
            assert [after[k] - before[k] for k in
                    ("valset_decode_native", "valset_decode_python")] == [1, 1]
            assert a.signed_header == b.signed_header
            assert a.validators.validators == b.validators.validators
            assert a.validators.proposer == b.validators.proposer
            assert a.validators.total_voting_power() == \
                b.validators.total_voting_power()
            assert a.validators.hash() == b.validators.hash()
            assert a.encode() == b.encode() == wire

    @pytest.mark.parametrize("no_native", [False, True],
                             ids=["native", "TM_TPU_NO_NATIVE"])
    def test_a_process_decodes_and_verifies_alike_with_and_without_the_module(
            self, no_native):
        import json
        import os
        import subprocess
        import sys

        from tendermint_tpu import native as native_mod

        if not no_native and native_mod.load() is None:
            pytest.skip("tm_native module not built")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("TM_TPU_NO_NATIVE", None)
        if no_native:
            env["TM_TPU_NO_NATIVE"] = "1"
        r = subprocess.run(
            [sys.executable, "-c", _LIGHT_SCRIPT, json.dumps(_LIGHT_CHAIN)],
            capture_output=True, env=env, cwd=repo, timeout=240)
        assert r.returncode == 0, \
            (r.stderr or b"").decode(errors="replace")[-3000:]
        out = json.loads(r.stdout.decode().strip().splitlines()[-1])
        n = _LIGHT_CHAIN["n_headers"]
        wires = self._wires()
        assert out == {
            "module": not no_native,
            "native": 0 if no_native else n,
            "python": n if no_native else 0,
            "roundtrip": [True] * n,
            "valset_hashes": [
                self._decode(w, "walk").validators.hash().hex() for w in wires],
            "verdicts": [["ok", ""]] * (n - 1) + [self._refusal(wires)],
        }

    def _refusal(self, wires):
        """What this process says of the last step under the set of the
        height below: the header's validators_hash refuses it."""
        import chip_smoke
        from tendermint_tpu.light import verifier

        lbs = [self._decode(w, "walk") for w in wires[-2:]]
        now = Timestamp(seconds=chip_smoke.T0 + len(wires) + 1)
        with pytest.raises(ValueError, match="validators") as e:
            verifier.verify_adjacent(
                lbs[0].signed_header, lbs[1].signed_header, lbs[0].validators,
                3600.0, now, 10.0)
        return [type(e.value).__name__, str(e.value)]
