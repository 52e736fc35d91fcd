"""sr25519 (schnorrkel/ristretto255) — reference crypto/sr25519 parity."""

import os

import pytest

from tendermint_tpu.crypto import _ristretto as R
from tendermint_tpu.crypto import sr25519
from tendermint_tpu.crypto.encoding import pubkey_from_proto, pubkey_to_proto

# draft-irtf-cfrg-ristretto255 small-multiple test vectors (first 6)
SPEC_MULTIPLES = [
    "0000000000000000000000000000000000000000000000000000000000000000",
    "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
    "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
    "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
    "da80862773358b466ffadfe0b3293ab3d9fd53c5ea6c955358f568322daf6a57",
    "e882b131016b52c1d3337080187cf768423efccbb517bb495ab812c4160ff44e",
]


class TestRistretto:
    def test_spec_small_multiples(self):
        pt = R.IDENTITY
        for i, want_hex in enumerate(SPEC_MULTIPLES):
            assert R.encode(pt) == bytes.fromhex(want_hex), f"multiple {i}"
            pt = R.add(pt, R.BASE)

    def test_decode_rejects_noncanonical(self):
        # non-canonical field element (>= p)
        assert R.decode(b"\xff" * 32) is None
        # negative s (odd)
        bad = bytearray(bytes.fromhex(SPEC_MULTIPLES[1]))
        bad[0] |= 1
        assert R.decode(bytes(bad)) is None

    def test_roundtrip(self):
        for k in (1, 7, 1234567):
            pt = R.scalar_mult(k, R.BASE)
            assert R.equals(R.decode(R.encode(pt)), pt)


class TestSr25519:
    def test_sign_verify(self):
        sk = sr25519.gen_priv_key(bytes(range(32)))
        pk = sk.pub_key()
        sig = sk.sign(b"msg")
        assert sig[63] & 0x80  # schnorrkel v1 marker
        assert pk.verify_signature(b"msg", sig)
        assert not pk.verify_signature(b"other", sig)
        bad = bytearray(sig)
        bad[5] ^= 1
        assert not pk.verify_signature(b"msg", bytes(bad))
        # missing marker bit rejected
        nomark = bytearray(sig)
        nomark[63] &= 0x7F
        assert not pk.verify_signature(b"msg", bytes(nomark))

    def test_randomized_signatures(self):
        sk = sr25519.gen_priv_key(bytes([9]) * 32)
        s1, s2 = sk.sign(b"m"), sk.sign(b"m")
        assert s1 != s2
        assert sk.pub_key().verify_signature(b"m", s1)
        assert sk.pub_key().verify_signature(b"m", s2)

    def test_batch_verifier(self):
        bv = sr25519.BatchVerifier()
        keys = [sr25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
        for i, sk in enumerate(keys):
            bv.add(sk.pub_key(), b"m%d" % i, sk.sign(b"m%d" % i))
        ok, valid = bv.verify()
        assert ok and valid == [True] * 4
        bv2 = sr25519.BatchVerifier()
        bv2.add(keys[0].pub_key(), b"x", keys[0].sign(b"y"))
        ok, valid = bv2.verify()
        assert not ok and valid == [False]

    def test_proto_encoding_roundtrip(self):
        pk = sr25519.gen_priv_key(bytes([3]) * 32).pub_key()
        rt = pubkey_from_proto(pubkey_to_proto(pk))
        assert rt.type() == "sr25519" and rt.bytes() == pk.bytes()

    def test_address(self):
        pk = sr25519.gen_priv_key(bytes([4]) * 32).pub_key()
        assert len(pk.address()) == 20


class TestNativeMerlin:
    """native/tm_native.cpp sr25519_challenges_buf must match the
    pure-Python merlin transcript, reduced mod L (the host half of the
    device lane)."""

    def test_challenges_match_pure_python(self):
        from tendermint_tpu.crypto.sr25519 import (
            SIGNING_CTX,
            _signing_transcript,
            gen_priv_key,
        )
        from tendermint_tpu.native import load

        nat = load()
        if nat is None:
            import pytest

            pytest.skip("no native toolchain")
        sk = gen_priv_key(b"\x31" * 32)
        pub = sk.pub_key().bytes()
        msgs, rss, want = [], [], []
        for i in range(6):
            msg = b"nm-%d" % i + b"y" * (i * 13 % 50)
            sig = sk.sign(msg)
            t = _signing_transcript(msg)
            t.append_message(b"proto-name", b"Schnorr-sig")
            t.append_message(b"sign:pk", pub)
            t.append_message(b"sign:R", sig[:32])
            want.append(t.challenge_bytes(b"sign:c", 64))
            msgs.append(msg)
            rss.append(sig[:32])
        import numpy as np

        from tendermint_tpu.crypto._edwards import L

        offs = np.cumsum([0] + [len(m) for m in msgs]).astype(np.int64)
        got = nat.sr25519_challenges_buf(
            SIGNING_CTX, pub * len(msgs), b"".join(rss), b"".join(msgs),
            offs.tobytes(),
        )
        assert all(
            int.from_bytes(got[32 * i : 32 * (i + 1)], "little")
            == int.from_bytes(want[i], "little") % L
            for i in range(len(msgs))
        )


class TestSr25519Prep:
    def test_prepare_flags(self):
        from tendermint_tpu.crypto.sr25519 import gen_priv_key
        from tendermint_tpu.ops.pallas_sr25519 import packed_views, prepare_sr25519

        sk = gen_priv_key(b"\x32" * 32)
        msg = b"prep"
        sig = sk.sign(msg)
        pub = sk.pub_key().bytes()
        entries = [
            (pub, msg, sig),
            (pub, msg, sig[:63] + bytes([sig[63] & 0x7F])),  # no v1 marker
            (
                pub,
                msg,
                sig[:32]
                + bytes(
                    b | (0x80 if i == 31 else 0)
                    for i, b in enumerate(
                        __import__(
                            "tendermint_tpu.crypto._edwards", fromlist=["L"]
                        ).L.__add__(1).to_bytes(32, "little")
                    )
                ),
            ),  # s = L + 1
            (b"\xff" * 32, msg, sig),  # non-canonical A encoding
        ]
        (packed,) = prepare_sr25519(entries, 8)
        assert packed.shape == (4 * 32 + 3, 8)
        a_t, r_t, s_t, k_t, aok, rok, sok = packed_views(packed)
        assert sok[0, 0] == 1 and aok[0, 0] == 1 and rok[0, 0] == 1
        assert sok[0, 1] == 0  # missing marker
        assert sok[0, 2] == 0  # s >= L
        assert aok[0, 3] == 0  # A >= p
        # padding lanes admissible
        assert sok[0, 4:].all() and aok[0, 4:].all() and rok[0, 4:].all()
        # s had the marker stripped
        assert s_t[31, 0] == sig[63] & 0x7F

    def test_mixed_dispatch_host_lanes(self):
        """verify_mixed partitions by key type and agrees with per-curve
        verification (device lanes off -> host paths)."""
        import os

        from tendermint_tpu.crypto import ed25519, secp256k1, sr25519
        from tendermint_tpu.ops import backend, mixed

        backend.engine.cache_clear()
        prior = os.environ.get("TM_TPU_PALLAS")
        os.environ["TM_TPU_PALLAS"] = "0"
        try:
            entries = []
            ed = ed25519.gen_priv_key(b"\x33" * 32)
            entries.append((ed.pub_key(), b"m1", ed.sign(b"m1")))
            sr = sr25519.gen_priv_key(b"\x34" * 32)
            entries.append((sr.pub_key(), b"m2", sr.sign(b"m2")))
            sc = secp256k1.gen_priv_key()
            entries.append((sc.pub_key(), b"m3", sc.sign(b"m3")))
            bad = sr.sign(b"m4")
            entries.append((sr.pub_key(), b"tampered", bad))
            res = mixed.verify_mixed(entries)
            assert res == [True, True, True, False]
        finally:
            if prior is None:
                del os.environ["TM_TPU_PALLAS"]
            else:
                os.environ["TM_TPU_PALLAS"] = prior
            backend.engine.cache_clear()


class TestSr25519DeviceLaneK1:
    """Always-on coverage for the default-on device lane: the ristretto
    DECODE kernel (K1) runs in interpret mode at a tiny bucket on every
    suite run (~20 s cold compile, cached afterwards), so CPU CI executes
    the sr25519 kernel code the production mixed path enables by default.
    The full-ladder differential below is @slow (compile-heavy)."""

    def test_k1_decode_differential(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        from tendermint_tpu.crypto import _ristretto, sr25519
        from tendermint_tpu.ops import fe_t
        from tendermint_tpu.ops import pallas_sr25519 as ps

        sk = sr25519.gen_priv_key(b"\x07" * 32)
        sig = sk.sign(b"k1")
        pub = sk.pub_key().bytes()
        # lane 1: canonical+even (passes host flags) but NOT on the curve
        # (non-square ratio) — rejection must come from the kernel itself
        bad_enc = (2).to_bytes(32, "little")
        assert _ristretto.decode(bad_enc) is None
        entries = [(pub, b"k1", sig), (bad_enc, b"x", sig)]
        args = ps.packed_views(*ps.prepare_sr25519(entries, 8))
        assert args[4][0, 1] == 1, "bad_enc must pass the host-side flags"

        n = block = 8

        def spec(rows):
            return pl.BlockSpec(
                (rows, block), lambda i: (0, i), memory_space=pltpu.VMEM
            )

        k1 = pl.pallas_call(
            ps._k1r_decode_kernel,
            grid=(1,),
            in_specs=[spec(32)] * 4 + [spec(1), spec(1)],
            out_specs=[spec(8 * 32), spec(2), spec(128), spec(128)],
            out_shape=[
                jax.ShapeDtypeStruct((8 * 32, n), jnp.int32),
                jax.ShapeDtypeStruct((2, n), jnp.int32),
                jax.ShapeDtypeStruct((128, n), jnp.int32),
                jax.ShapeDtypeStruct((128, n), jnp.int32),
            ],
            interpret=True,
        )
        coords, ok, _, _ = jax.jit(k1)(*args[:6])
        ok = np.asarray(ok)
        assert ok[0, 0] == 1 and ok[1, 0] == 1  # A and R of the valid sig
        assert ok[0, 1] == 0  # off-curve A rejected in-kernel

        # lane 0's decoded A must equal the host ristretto oracle
        pt = _ristretto.decode(pub)
        assert pt is not None
        coords = np.asarray(coords)

        def limbs_to_int(rows):
            return sum(int(v) << (fe_t.RADIX * i) for i, v in enumerate(rows)) % fe_t.P

        x = limbs_to_int(coords[0:20, 0])
        y = limbs_to_int(coords[32:52, 0])
        z = limbs_to_int(coords[64:84, 0])
        assert z == 1
        assert (x, y) == (pt[0] % fe_t.P, pt[1] % fe_t.P)


@pytest.mark.slow
class TestSr25519DeviceLane:
    def test_interpret_differential(self):
        import numpy as np

        from tendermint_tpu.crypto import sr25519
        from tendermint_tpu.ops import pallas_sr25519 as ps

        sk = sr25519.gen_priv_key(b"\x01" * 32)
        msg = b"m"
        sig = sk.sign(msg)
        pub = sk.pub_key().bytes()
        entries = [(pub, msg, sig), (pub, b"bad", sig)]
        expect = [sr25519.verify(p, m, s) for p, m, s in entries]
        (packed,) = ps.prepare_sr25519(entries, 8)
        res = np.asarray(
            ps.verify_sr25519_compact(packed, block=8, interpret=True)
        )[0].astype(bool)
        assert res[:2].tolist() == expect
        assert res[2:].all(), "padding lanes (ristretto identity) must verify"
