"""Device engine tests: field arithmetic, kernel parity vs the ZIP-215
oracle, bucketing driver, and the mesh-sharded commit step.

The differential strategy mirrors the reference's CPU↔device plan
(SURVEY.md §7 stage 1): every device result is checked against the
pure-Python oracle (crypto/_edwards), including the ZIP-215 edge cases the
reference inherits from curve25519-voi (small-order points, non-canonical
encodings, s >= L)."""

import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.crypto import _edwards as E  # noqa: E402
from tendermint_tpu.crypto import batch as cbatch  # noqa: E402
from tendermint_tpu.crypto import ed25519  # noqa: E402
from tendermint_tpu.ops import backend, fe  # noqa: E402


class TestFieldArithmetic:
    def _vals(self):
        rng = random.Random(7)
        vals = [0, 1, 2, 19, E.P - 1, E.P, E.P + 1, 2**255 - 1]
        vals += [rng.randrange(0, E.P) for _ in range(12)]
        return vals

    def test_ring_ops(self):
        vals = self._vals()
        rng = random.Random(8)
        others = [rng.randrange(0, E.P) for _ in vals]
        a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in vals]))
        b = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in others]))
        for name, got, want in [
            ("add", fe.add(a, b), [x + y for x, y in zip(vals, others)]),
            ("sub", fe.sub(a, b), [x - y for x, y in zip(vals, others)]),
            ("mul", fe.mul(a, b), [x * y for x, y in zip(vals, others)]),
            ("sq", fe.sq(a), [x * x for x in vals]),
        ]:
            got = [fe.int_from_limbs(g) % E.P for g in np.asarray(got)]
            assert got == [w % E.P for w in want], name

    def test_canon_exact_and_parity(self):
        vals = self._vals()
        a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in vals]))
        b = jnp.asarray(np.stack([fe.limbs_from_int(v + 1) for v in vals]))
        canon = np.asarray(fe.canon(fe.sub(a, b)))
        for row, x in zip(canon, vals):
            assert fe.int_from_limbs(row) == (x - (x + 1)) % E.P
        assert bool(jnp.all(fe.eq(a, a)))
        assert not bool(jnp.any(fe.eq(a, b)))
        par = np.asarray(fe.parity(a))
        assert [int(p) for p in par] == [(v % E.P) & 1 for v in vals]

    def test_exponent_chains(self):
        vals = [2, 19, E.P - 2, random.Random(5).randrange(0, E.P)]
        a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in vals]))
        got = [fe.int_from_limbs(g) % E.P for g in np.asarray(jax.jit(fe.pow22523)(a))]
        assert got == [pow(v, (E.P - 5) // 8, E.P) for v in vals]
        got = [fe.int_from_limbs(g) % E.P for g in np.asarray(jax.jit(fe.invert)(a))]
        assert got == [pow(v, E.P - 2, E.P) for v in vals]


def _edge_entries():
    """Mixed batch exercising every ZIP-215 acceptance/rejection branch."""
    rng = random.Random(11)
    entries = []
    for i in range(6):
        sk = ed25519.gen_priv_key(bytes([i + 1]) * 32)
        msg = b"msg-%d" % i
        entries.append((sk.pub_key().bytes(), msg, sk.sign(msg)))
    sk = ed25519.gen_priv_key(bytes(32))
    msg, pub = b"hello", sk.pub_key().bytes()
    sig = sk.sign(msg)
    bad = bytearray(sig)
    bad[5] ^= 1
    entries.append((pub, msg, bytes(bad)))  # corrupted sig
    entries.append((pub, b"other", sig))  # wrong msg
    badpub = bytearray(pub)
    badpub[3] ^= 1
    entries.append((bytes(badpub), msg, sig))  # corrupted pubkey
    bad_s = bytearray(sig)
    bad_s[32:] = (E.L + 5).to_bytes(32, "little")
    entries.append((pub, msg, bytes(bad_s)))  # s >= L -> reject

    # Small-order A with R = [s]B: cofactored equation accepts for ANY msg.
    small = []
    for y in range(50):
        for sgn in (0, 1):
            enc = bytearray(y.to_bytes(32, "little"))
            enc[31] |= sgn << 7
            pt = E.decompress(bytes(enc))
            if pt is not None and E.is_identity(E.mult_by_cofactor(pt)):
                small.append(bytes(enc))
    assert small
    for enc in small[:3]:
        s = rng.randrange(0, E.L)
        r = E.compress(E.scalar_mult(s, E.BASE))
        entries.append((enc, b"anything", r + s.to_bytes(32, "little")))
    # Non-canonical A encoding (y' = y + p): same point, still accepted.
    for enc in small:
        y = int.from_bytes(enc, "little") & ((1 << 255) - 1)
        if y < 19:
            enc2 = ((y + E.P) | ((enc[31] >> 7) << 255)).to_bytes(32, "little")
            s = rng.randrange(0, E.L)
            r = E.compress(E.scalar_mult(s, E.BASE))
            entries.append((enc2, b"nc", r + s.to_bytes(32, "little")))
    for _ in range(3):
        entries.append((rng.randbytes(32), rng.randbytes(20), rng.randbytes(64)))
    return entries


class TestVerifyKernel:
    def test_parity_vs_oracle(self):
        entries = _edge_entries()
        oracle = [E.verify_zip215(p, m, s) for p, m, s in entries]
        assert any(oracle) and not all(oracle)
        res = backend.verify_batch(entries)
        assert [bool(r) for r in res] == oracle

    def test_empty_and_chunking_shapes(self):
        assert backend.verify_batch([]).shape == (0,)

    def test_batch_verifier_interface(self):
        bv = backend.Ed25519DeviceBatchVerifier(force_device=True)
        sks = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
        for i, sk in enumerate(sks):
            bv.add(sk.pub_key(), b"m%d" % i, sk.sign(b"m%d" % i))
        ok, valid = bv.verify()
        assert ok and valid == [True] * 4
        bv = backend.Ed25519DeviceBatchVerifier(force_device=True)
        bv.add(sks[0].pub_key(), b"x", sks[0].sign(b"y"))
        ok, valid = bv.verify()
        assert not ok and valid == [False]

    def test_dispatch_seam_installs_device_engine(self):
        import tendermint_tpu.ops  # noqa: F401 — installs the factory

        sk = ed25519.gen_priv_key(bytes([9]) * 32)
        bv = cbatch.create_batch_verifier(sk.pub_key())
        assert isinstance(bv, backend.Ed25519DeviceBatchVerifier)


class TestShardedCommit:
    def test_sharded_commit_verifier(self):
        from tendermint_tpu.ops import sharded

        n_dev = min(8, len(jax.devices()))
        mesh = sharded.make_mesh(n_dev)
        entries, powers = [], []
        for i in range(2 * n_dev):
            sk = ed25519.gen_priv_key(bytes([i + 1]) * 32)
            msg = b"commit-%d" % i
            sig = sk.sign(msg)
            if i == 3:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
            entries.append((sk.pub_key().bytes(), msg, sig))
            powers.append(1000 + i)
        valid, tallied, all_valid = sharded.verify_commit_sharded(
            entries, powers, mesh, bucket=2 * n_dev
        )
        want_valid = [i != 3 for i in range(2 * n_dev)]
        assert [bool(v) for v in valid] == want_valid
        assert not all_valid
        assert tallied == sum(p for p, w in zip(powers, want_valid) if w)

    @pytest.mark.time_limit(390)  # 98-124 s on a cold cache
    def test_sharded_pallas_matches_host_oracle(self):
        """The PRODUCTION compact Pallas kernel under
        shard_map (interpret mode, the same traced program Mosaic
        compiles) agrees with the big-int ZIP-215 oracle lane-by-lane,
        with the psum power tally and all-valid reduction correct."""
        from tendermint_tpu.crypto import _edwards as E
        from tendermint_tpu.ops import pallas_verify as pv, sharded

        n_dev = min(8, len(jax.devices()))
        mesh = sharded.make_mesh(n_dev)
        old_block = pv.BLOCK
        pv.BLOCK = 8  # keep the interpreted ladder fast
        try:
            entries, powers = [], []
            for i in range(4 * n_dev):
                sk = ed25519.gen_priv_key(bytes([i + 1]) * 32)
                msg = b"pshard-%d" % i
                sig = sk.sign(msg)
                if i in (3, 17):
                    sig = sig[:-1] + bytes([sig[-1] ^ 1])
                entries.append((sk.pub_key().bytes(), msg, sig))
                powers.append(100 + i)
            valid, tallied, all_valid = sharded.verify_commit_sharded_pallas(
                entries, powers, mesh, bucket=8 * n_dev
            )
            oracle = [E.verify_zip215(p, m, s) for p, m, s in entries]
            assert [bool(v) for v in valid] == oracle
            assert not all_valid
            assert tallied == sum(p for p, ok in zip(powers, oracle) if ok)
        finally:
            pv.BLOCK = old_block

    def test_power_split_roundtrip(self):
        from tendermint_tpu.ops import sharded

        # Domain: up to MaxTotalVotingPower = 2^63/8 (validator_set.go:25).
        vals = [0, 1, 2**16, 2**30 - 1, 2**30, 2**60 - 1, 2**63 // 8]
        sp = sharded.split_power(np.asarray(vals))
        for lanes, v in zip(sp, vals):
            assert sharded.join_power(lanes) == v
        with pytest.raises(ValueError):
            sharded.split_power(np.asarray([2**62]))
        with pytest.raises(ValueError):
            sharded.split_power(np.asarray([-1]))


class TestFreshImportUnderTrace:
    """Regression for the round-2 bench crash: the device-hash kernel was
    the FIRST jax trace in the process, and a lazy `from . import sc`
    inside it materialized module-level jnp constants inside the trace
    (ops/sc.py L_LIMBS leaked as a DynamicJaxprTracer). The fix is
    two-fold: module-scope imports in ops/ed25519_verify.py and numpy
    (trace-immune) module constants; this test reproduces the bench's
    exact import order in a fresh interpreter so a regression fails here
    and not in the driver's bench run."""

    def test_device_hash_kernel_first_trace(self):
        import subprocess
        import sys

        code = (
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import numpy as np\n"
            "from tendermint_tpu.crypto import ed25519\n"
            "from tendermint_tpu.ops import backend\n"
            "sk = ed25519.gen_priv_key(b'\\x07' * 32)\n"
            "msg = b'fresh-trace'\n"
            "entries = [(sk.pub_key().bytes(), msg, sk.sign(msg))]\n"
            "args = backend.prepare_batch_device_hash(entries, 128)\n"
            "kern = backend.ed25519_verify.jitted_verify_device_hash()\n"
            "res = np.asarray(kern(*args))\n"
            "assert bool(res[0]), 'signature must verify'\n"
            "print('OK')\n"
        )
        env = dict(os.environ)
        # a chip belongs to one process: the child stays on the CPU
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=300,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout
