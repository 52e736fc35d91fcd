"""The BLS12-381 aggregation lane (ISSUE 20): oracle, wire type, and
commit-seam integration.

Two layers, same pattern as test_secp_lane.py:

- the pure-Python BLS oracle (crypto/bls12381.py — stdlib-only big-int
  math) and the AggregatedCommit wire type import WITHOUT the
  cryptography wheel, so their unit tests run IN PROCESS in the main
  tier-1 run;
- the validation/kernel seam (types/validation.py pulls the crypto
  package) and the `tools/prep_bench.py --bls` fused-launch +
  blame-parity gate run in SUBPROCESSES with TM_TPU_PUREPY_CRYPTO=1,
  which must never leak into the main pytest process.
"""

import os
import subprocess
import sys

import pytest

from tendermint_tpu.crypto import bls12381 as bls
from tendermint_tpu.libs.bits import BitArray

try:
    # types/__init__ reaches validation -> crypto.batch -> the
    # cryptography wheel; everything below the oracle tests needs it
    from tendermint_tpu.types.block import (
        AggregatedCommit,
        BlockID,
        PartSetHeader,
    )

    _HAVE_CRYPTO = True
except ModuleNotFoundError:
    # No cryptography wheel in this container; the subprocess runner
    # below re-runs this module with TM_TPU_PUREPY_CRYPTO=1 instead.
    _HAVE_CRYPTO = False

needs_crypto = pytest.mark.skipif(
    not _HAVE_CRYPTO,
    reason="crypto backend unavailable (runs via the purepy subprocess "
    "runner)",
)


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bad_g1() -> bytes:
    """Smallest-x on-curve G1 point OUTSIDE the prime subgroup (the
    cofactor is ~2^125, so the first few on-curve x qualify)."""
    x = 1
    while True:
        y = bls.fp_sqrt((x * x * x + bls.B) % bls.P)
        if y is not None and not bls.g1_in_subgroup((x, y)):
            return bls.g1_compress((x, y))
        x += 1


def _bad_g2() -> bytes:
    c = 1
    while True:
        xx = (c, 0)
        y2 = bls.f2_add(bls.f2_mul(xx, bls.f2_sqr(xx)),
                        bls.f2_scalar(bls.XI, bls.B))
        y = bls.f2_sqrt(y2)
        if y is not None and not bls.g2_in_subgroup((xx, y)):
            return bls.g2_compress((xx, y))
        c += 1


class TestOracle:
    def test_compress_roundtrip(self):
        sk = bls.PrivKey(b"\x01" * 32)
        pub = sk.pub_key().bytes()
        assert len(pub) == 48
        pt = bls.g1_decompress(pub)
        assert bls.g1_compress(pt) == pub
        sig = sk.sign(b"msg")
        assert len(sig) == 96
        q = bls.g2_decompress(sig)
        assert bls.g2_compress(q) == sig

    def test_pubkey_status_words(self):
        good = bls.PrivKey(b"\x02" * 32).pub_key().bytes()
        assert bls.pubkey_status(good) == (bls.g1_decompress(good), None)
        assert bls.pubkey_status(b"\xff" * 48)[1] == "malformed"
        inf = bytes([0xC0]) + b"\x00" * 47
        assert bls.pubkey_status(inf)[1] == "identity"
        assert bls.pubkey_status(_bad_g1())[1] == "subgroup"

    def test_signature_status_words(self):
        sig = bls.PrivKey(b"\x03" * 32).sign(b"m")
        assert bls.signature_status(sig)[1] is None
        assert bls.signature_status(b"\xff" * 96)[1] == "malformed"
        inf = bytes([0xC0]) + b"\x00" * 95
        assert bls.signature_status(inf)[1] == "identity"
        assert bls.signature_status(_bad_g2())[1] == "subgroup"

    def test_g1_subgroup_check_is_not_vacuous(self):
        # Regression: g1_mul used to reduce k mod R, turning the
        # subgroup check [R]P == O into [0]P == O — vacuously true for
        # every on-curve point, so non-subgroup pubkeys (which break
        # apk-aggregation soundness) sailed through.
        pub = _bad_g1()
        pt = bls.g1_decompress(pub)
        assert bls.g1_on_curve(pt)
        assert not bls.g1_in_subgroup(pt)
        assert bls.g1_mul(bls.R, pt) is not None

    def test_aggregate_pubkeys_flags_lowest_bad_index(self):
        pubs = [bls.PrivKey(bytes([i + 1]) * 32).pub_key().bytes()
                for i in range(3)]
        apk, bad = bls.aggregate_pubkeys(pubs)
        assert apk is not None and bad is None
        apk2, bad2 = bls.aggregate_pubkeys([pubs[0], _bad_g1(), b"\x00" * 48])
        assert apk2 is None and bad2 == 1

    def test_fast_aggregate_verify_end_to_end(self):
        # ONE full pairing on the brute-force oracle (~seconds): the
        # exhaustive kernel-vs-oracle differential lives in the
        # subprocess gate, not here.
        sks = [bls.PrivKey(bytes([7 + i]) * 32) for i in range(3)]
        msg = b"one vote, one message"
        sig = bls.aggregate([sk.sign(msg) for sk in sks])
        pubs = [sk.pub_key().bytes() for sk in sks]
        assert bls.fast_aggregate_verify(pubs, msg, sig)
        assert not bls.fast_aggregate_verify(pubs[:2], msg, sig)


@needs_crypto
class TestAggregatedCommitWire:
    def _agg(self, n=8, signers=(0, 1, 2, 3, 4, 5)):
        ba = BitArray(n)
        for i in signers:
            ba.set_index(i, True)
        bid = BlockID(hash=b"\x21" * 32,
                      part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
        return AggregatedCommit(height=11, round=2, block_id=bid,
                                signature=b"\x05" * 96, signers=ba)

    def test_proto_roundtrip(self):
        agg = self._agg()
        assert AggregatedCommit.decode(agg.encode()) == agg

    def test_wire_footprint_is_constant_in_signers(self):
        # one signature + a bitmap: adding signers must not add 96-byte
        # rows (the 2302.00418 bandwidth win the lane exists for). The
        # bitmap words are varints, so two extra bits may cost ONE more
        # byte — never another signature row.
        a6 = self._agg(signers=(0, 1, 2, 3, 4, 5))
        a8 = self._agg(signers=tuple(range(8)))
        assert abs(len(a8.encode()) - len(a6.encode())) <= 1

    def test_sign_bytes_identical_across_signers(self):
        # aggregation requires ONE message: the canonical vote is
        # composed with the zero timestamp for every signer
        agg = self._agg()
        sb = agg.sign_bytes("chain")
        assert isinstance(sb, bytes) and len(sb) > 0
        assert sb == self._agg(signers=(2, 5)).sign_bytes("chain")

    def test_validate_basic(self):
        agg = self._agg()
        agg.validate_basic()
        bad = self._agg()
        bad.signature = b"\x05" * 64
        with pytest.raises(ValueError):
            bad.validate_basic()
        neg = self._agg()
        neg.height = -1
        with pytest.raises(ValueError):
            neg.validate_basic()


@needs_crypto
class TestCommitSeam:
    """Sequential verify + prepare/conclude on paths that fail BEFORE
    the pairing (cheap); pairing-path parity is the subprocess gate."""

    def _committee(self, n=4):
        from tendermint_tpu.types import Validator, ValidatorSet

        sks = [bls.PrivKey((40 + i).to_bytes(32, "big")) for i in range(n)]
        vset = ValidatorSet.new([Validator.new(sk.pub_key(), 100)
                                 for sk in sks])
        by = {sk.pub_key().address(): sk for sk in sks}
        return vset, [by[v.address] for v in vset.validators]

    def _agg(self, vset, sks, signers, chain_id="seam"):
        bid = BlockID(hash=b"\x31" * 32,
                      part_set_header=PartSetHeader(total=1, hash=b"\x32" * 32))
        ba = BitArray(len(sks))
        for i in signers:
            ba.set_index(i, True)
        agg = AggregatedCommit(height=3, round=0, block_id=bid, signers=ba)
        msg = agg.sign_bytes(chain_id)
        agg.signature = bls.aggregate([sks[i].sign(msg) for i in signers])
        return bid, agg

    def test_malformed_signature_blame(self):
        from tendermint_tpu.types import validation as V

        vset, sks = self._committee()
        bid, agg = self._agg(vset, sks, [0, 1, 2])
        agg.signature = b"\xff" * 96
        with pytest.raises(ValueError) as ei:
            V.verify_aggregated_commit("seam", vset, bid, 3, agg)
        assert str(ei.value) == (
            f"malformed aggregate signature: {agg.signature.hex().upper()}")

    def test_bitmap_size_mismatch_is_pre_crypto(self):
        from tendermint_tpu.types import validation as V
        from tendermint_tpu.types.validation import ErrInvalidCommitSignatures

        vset, sks = self._committee()
        bid, agg = self._agg(vset, sks, [0, 1, 2])
        agg.signers = BitArray(7)
        for fn in (
            lambda: V.verify_aggregated_commit("seam", vset, bid, 3, agg),
            lambda: V.prepare_aggregated_commit("seam", vset, bid, 3, agg,
                                                k_hint=8),
        ):
            with pytest.raises(ErrInvalidCommitSignatures):
                fn()

    def test_insufficient_power_precedes_crypto(self):
        from tendermint_tpu.types import validation as V
        from tendermint_tpu.types.validator_set import (
            ErrNotEnoughVotingPowerSigned,
        )

        vset, sks = self._committee()
        bid, agg = self._agg(vset, sks, [0])
        agg.signature = b"\xff" * 96  # never reached: tally first
        with pytest.raises(ErrNotEnoughVotingPowerSigned):
            V.verify_aggregated_commit("seam", vset, bid, 3, agg)

    def test_prepare_below_threshold_stays_sync(self):
        from tendermint_tpu.ops import backend
        from tendermint_tpu.types import validation as V

        vset, sks = self._committee()
        bid, agg = self._agg(vset, sks, [0, 1, 2])
        assert backend.BLS_DEVICE_THRESHOLD > 1
        blk, conc = V.prepare_aggregated_commit("seam", vset, bid, 3, agg,
                                                k_hint=1)
        assert blk is None and conc is None

    def test_aggblock_pad_and_concat_rules(self):
        from tendermint_tpu.ops.entry_block import AggBlock, block_concat
        from tendermint_tpu.types import validation as V
        from tendermint_tpu.ops import epoch_cache as _epoch

        _epoch.reset(8)
        vset, sks = self._committee()
        _epoch.note_valset(vset)
        _epoch.note_valset(vset)
        bid, agg = self._agg(vset, sks, [0, 1, 2])
        blk, _ = V.prepare_aggregated_commit("seam", vset, bid, 3, agg,
                                             k_hint=8)
        assert blk is not None and len(blk) == 1
        fused = block_concat([blk, AggBlock.pad(3)])
        assert len(fused) == 4 and fused.epoch_key == blk.epoch_key
        vset2, sks2 = self._committee(n=5)
        bid2, agg2 = self._agg(vset2, sks2, [0, 1, 2, 3])
        _epoch.note_valset(vset2)
        _epoch.note_valset(vset2)
        blk2, _ = V.prepare_aggregated_commit("seam", vset2, bid2, 3, agg2,
                                              k_hint=8)
        with pytest.raises(ValueError):
            block_concat([blk, blk2])  # mixed committees never fuse

    def test_mesh_bls_lane_width_quantizes(self):
        from tendermint_tpu.ops import mesh as ms

        assert ms._lane_width(1, "bls12381", 10240) == 4
        assert ms._lane_width(4, "bls12381", 10240) == 4
        assert ms._lane_width(5, "bls12381", 10240) == 16
        assert ms._lane_width(17, "bls12381", 10240) == 17
        assert ms._lane_width(5, "ed25519", 128) == 128


def _purepy_env():
    from tendermint_tpu.libs import jaxcache

    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    env.pop("TM_TPU_DONATE", None)
    env.pop("TM_TPU_MESH", None)
    jaxcache.set_env(env)
    return env


@pytest.mark.time_limit(600)  # 114-128 s on a cold cache; its two subprocess limits add to 540
def test_bls_isolated_runners():
    """The purepy subprocess re-run of this file (the tier-1 home of
    the crypto-gated seam tests above) and the `prep_bench --bls`
    acceptance gate (fused multi-pairing launch + verdict-code/blame
    parity incl. crafted non-subgroup points, three-lane superbatch,
    zero pool-slot leak), run back to back like the secp runner."""
    if os.environ.get("TM_TPU_BLS_ISOLATED"):
        pytest.skip("already inside the isolated runner")
    have_crypto = _HAVE_CRYPTO
    here = os.path.dirname(os.path.abspath(__file__))
    cmds = {}
    if not have_crypto:  # with the wheel present the seam tests ran direct
        cmds["lane suite"] = (
            [
                sys.executable, "-m", "pytest",
                os.path.join(here, "test_bls_lane_isolated.py"),
                "-q", "-m", "not slow", "-p", "no:cacheprovider",
            ],
            dict(_purepy_env(), TM_TPU_BLS_ISOLATED="1"),
            180,
        )
    cmds["--bls gate"] = (
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--bls",
        ],
        _purepy_env(),
        360,
    )
    fails = []
    for label, (cmd, env, limit) in cmds.items():
        r = subprocess.run(
            cmd,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            cwd=_repo_root(),
            timeout=limit,
        )
        if r.returncode != 0:
            fails.append(f"{label}: rc={r.returncode}\n"
                         f"{(r.stdout or b'').decode(errors='replace')[-3000:]}")
    assert not fails, "\n\n".join(fails)
