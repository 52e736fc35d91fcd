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
    """Regression for the round-2 bench crash: a kernel that is the FIRST
    jax trace in the process, with a lazy import inside it, materialized
    module-level jnp constants inside the trace (they leaked as
    DynamicJaxprTracers). The fix is two-fold: module-scope imports in
    ops/ed25519_verify.py and numpy (trace-immune) module constants; this
    test reproduces the bench's exact import order in a fresh interpreter
    so a regression fails here and not in the driver's bench run."""

    def test_kernel_first_trace(self):
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
            "args = backend.prepare_batch(entries, 128)\n"
            "kern = backend.ed25519_verify.jitted_verify()\n"
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


# -- backend.select_kernel: the one place an engine is chosen (ISSUE 30) -----


def _rand_block(n, seed=30):
    """Structurally-valid random ed25519 block: choosing and preparing a
    kernel needs no valid signature."""
    from tendermint_tpu.ops.entry_block import EntryBlock

    rng = np.random.RandomState(seed)
    return EntryBlock.from_entries([
        (rng.randint(0, 256, 32, dtype=np.uint8).tobytes(), b"sel-%d" % i,
         rng.randint(0, 256, 64, dtype=np.uint8).tobytes())
        for i in range(n)
    ])


def _choice_block(scheme, warm):
    """A 6-row batch of `scheme`, carrying a registered epoch key when
    `warm` (the cache must have been reset by the caller)."""
    from tendermint_tpu.ops import epoch_cache
    from tendermint_tpu.ops.entry_block import AggBlock, EntryBlock

    n, key = 6, b"\x30" * 32
    rng = np.random.RandomState(3)
    if scheme == "bls12381":
        pub = rng.randint(0, 256, (n, 48), dtype=np.uint8)
        blk = AggBlock.from_commits(
            [(np.ones(n, dtype=bool), b"agg-%d" % i, bytes(96))
             for i in range(n)],
            pub, key if warm else None,
        )
    else:
        blk = _rand_block(n)
        pub = blk.pub
        if scheme == "secp256k1":
            blk = EntryBlock(blk.pub, blk.sig, blk.msgs, blk.offsets,
                             scheme=scheme,
                             pub_aux=np.full(n, 2, dtype=np.uint8))
            pub = np.concatenate([blk.pub_aux[:, None], blk.pub], axis=1)
        if warm:
            blk.val_idx = np.arange(n, dtype=np.int32)
            blk.epoch_key = key
    if warm:
        epoch_cache.cache().note(key, pub, scheme)
        if scheme == "ed25519":    # an ed25519 table has a name of its own
            blk.epoch_key = epoch_cache.cache().note(key, pub)[0].key
    return blk


# (engine family, scheme) -> what a cold and a warm batch must name and
# how many per-batch arguments the prep ships. The scheme lanes do not
# depend on the ed25519 family.
_SCHEME_CHOICE = {
    "secp256k1": (({"prepare_batch_secp", "secp_kernel"}, 7),
                  ({"prepare_batch_secp_cached", "secp_cached_kernel"}, 6)),
    "bls12381": (({"prepare_batch_bls", "bls_kernel"}, 2),) * 2,
}
_ED_CHOICE = {
    "xla": (({"prepare_batch", "jitted_verify"}, 7),
            ({"prepare_batch_cached", "cached_kernel"}, 5)),
    "pallas": (({"prepare_compact", "_jitted_pallas_verify"}, 5),
               ({"prepare_compact_cached", "cached_compact_fn"}, 5)),
    "pallas_rlc": (({"prepare_rlc", "_jitted_rlc_verify"}, 1),
                   ({"prepare_rlc_cached", "rlc_cached_fn"}, 1)),
}
_FAMILY_ENV = {"xla": ("0", "0"), "pallas": ("1", "0"),
               "pallas_rlc": ("1", "1")}


class TestSelectKernel:
    @pytest.fixture
    def family(self, request, monkeypatch):
        """Force an ed25519 engine family the way
        tests/test_pallas_rlc_dispatch.py does."""
        from tendermint_tpu.ops import epoch_cache

        pallas, rlc = _FAMILY_ENV[request.param]
        monkeypatch.setenv("TM_TPU_PALLAS", pallas)
        monkeypatch.setenv("TM_TPU_RLC", rlc)
        backend.engine.cache_clear()
        epoch_cache.reset(depth=4)
        yield request.param
        epoch_cache.reset()  # reads the engine: clear that last
        backend.engine.cache_clear()

    @pytest.fixture
    def named(self, monkeypatch):
        """Records which prep and which kernel factory get named. The
        ed25519 preps run for real (numpy only); the secp256k1 and
        bls12381 ones are python bignum work and return stand-ins of
        their real arity. No kernel is traced, let alone launched."""
        from tendermint_tpu.ops import ed25519_verify as ev
        from tendermint_tpu.ops import pallas_rlc as pr
        from tendermint_tpu.ops import pallas_verify as pv

        seen = set()

        def spy(mod, name, stand_in=None):
            real = getattr(mod, name)

            def call(*a, **kw):
                seen.add(name)
                return stand_in if stand_in is not None else real(*a, **kw)

            monkeypatch.setattr(mod, name, call)

        for mod, names in (
            (backend, ("prepare_batch", "prepare_batch_cached",
                       "cached_kernel", "secp_kernel", "secp_cached_kernel",
                       "bls_kernel")),
            (ev, ("jitted_verify",)),
            (pv, ("prepare_compact", "prepare_compact_cached",
                  "_jitted_pallas_verify", "cached_compact_fn")),
            (pr, ("prepare_rlc", "prepare_rlc_cached", "_jitted_rlc_verify",
                  "rlc_cached_fn")),
        ):
            for name in names:
                spy(mod, name)
        spy(backend, "prepare_batch_secp", (0,) * 7)
        spy(backend, "prepare_batch_secp_cached", (0,) * 6)
        spy(backend, "prepare_batch_bls", (0, 0, 0, 0))
        monkeypatch.setattr(backend, "_bls_bad_rows", lambda pub48: [])
        return seen

    @pytest.mark.parametrize("scheme", ["ed25519", "secp256k1", "bls12381"])
    @pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
    @pytest.mark.parametrize("family", list(_FAMILY_ENV), indirect=True)
    def test_choice(self, family, named, warm, scheme):
        blk = _choice_block(scheme, warm)
        fn, args, rlc_entries, bucket = backend.select_kernel(blk)
        table = _ED_CHOICE[family] if scheme == "ed25519" else (
            _SCHEME_CHOICE[scheme])
        want_names, want_args = table[int(warm)]
        assert named == want_names
        assert len(args) == want_args and callable(fn)
        # lane verdicts, and with them the host's expansion, only for RLC
        is_rlc = family == "pallas_rlc" and scheme == "ed25519"
        assert (rlc_entries is blk) if is_rlc else (rlc_entries is None)
        assert bucket >= len(blk)
        assert (backend.warm_epoch(blk) is not None) == warm

    @pytest.mark.parametrize("family", ["pallas_rlc"], indirect=True)
    def test_lane_pack_takes_the_per_signature_kernel(self, family, named):
        """A lane pack demuxes verdicts by row, which RLC lane verdicts
        cannot: same family, per-signature kernel, forced width."""
        blk = _rand_block(32)
        fn, args, rlc_entries, bucket = backend.select_kernel(
            blk, bucket=32, lanes=2
        )
        assert named == {"prepare_compact", "_jitted_pallas_verify"}
        assert rlc_entries is None and bucket == 32
        assert args[0].shape == (32, 32)  # batch-minor, forced width


def _forged_batch(n, forged, warm):
    """n real signatures with one forged; `warm` registers the signers as
    an epoch and hands the block its gather indices."""
    from tendermint_tpu.ops import epoch_cache
    from tendermint_tpu.ops.entry_block import EntryBlock

    entries = []
    for i in range(n):
        sk = ed25519.gen_priv_key(b"\x31" * 30 + i.to_bytes(2, "big"))
        m = b"one-function-%d" % i
        s = sk.sign(m)
        if i == forged:
            s = s[:-1] + bytes([s[-1] ^ 1])
        entries.append((sk.pub_key().bytes(), m, s))
    blk = EntryBlock.from_entries(entries)
    if warm:
        key = b"\x32" * 32
        epoch_cache.cache().note(key, blk.pub.copy())
        blk.val_idx = np.arange(n, dtype=np.int32)
        blk.epoch_key = epoch_cache.cache().note(key, blk.pub.copy())[0].key
    return blk


class TestOneChoiceThreeCallers:
    @pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
    def test_equal_verdicts_and_blame(self, warm):
        """The dispatcher's _prepare, the direct chunk loop and the mesh's
        one-lane superbatch ask the same function: equal verdicts, equal
        blame index, cold and warm."""
        from tendermint_tpu.libs import devcheck
        from tendermint_tpu.ops import epoch_cache, mesh as ms
        from tendermint_tpu.ops.pipeline import AsyncBatchVerifier

        class _Job:
            def __init__(self, entries):
                self.entries = entries

        n, forged = 40, 17
        epoch_cache.reset(depth=4)
        try:
            blk = _forged_batch(n, forged, warm)
            assert (backend.warm_epoch(blk) is not None) == warm
            with devcheck.exempt():
                fn, args, rlc, _b = AsyncBatchVerifier._prepare(blk)
                assert rlc is None and len(args) == (5 if warm else 7)
                via_prepare = np.asarray(fn(*args))[:n]
                direct = backend._verify_batch_direct(
                    blk, backend.max_coalesce()
                )
                plan, held = ms.pack_jobs([_Job(blk)], 1, 128)
                assert not held
                sblock, spans = ms.build_superblock(plan)
                fn, args, _rlc, _b, shardings = ms.prepare_superbatch(
                    sblock, plan
                )
                assert shardings is None  # one lane: no mesh to place on
                row = np.asarray(fn(*args))
            (_job, off, m), = spans
            via_mesh = row[off:off + m]
        finally:
            epoch_cache.reset()
        want = np.ones(n, dtype=bool)
        want[forged] = False
        for got in (via_prepare, direct, via_mesh):
            got = np.asarray(got).astype(bool)
            assert np.array_equal(got, want)
            assert int(np.argmin(got)) == forged


class TestDeviceHashTwinIsGone:
    @pytest.mark.native_required
    def test_fused_prep_native_equals_numpy_and_has_no_ram_columns(self):
        """tm_native.commit_prep_fused and its numpy twin return the same
        (sel, tallied, block) — four columns a block, no R||A||M stage."""
        from tendermint_tpu.ops import commit_prep as cp
        from tendermint_tpu.ops.entry_block import CommitBlock, EntryBlock

        n = 90
        rng = np.random.RandomState(30)
        flags = np.full(n, cp.FLAG_COMMIT, dtype=np.uint8)
        flags[[3, 41]] = cp.FLAG_NIL
        flags[[7, 60]] = cp.FLAG_ABSENT
        live = flags != cp.FLAG_ABSENT
        sig = rng.randint(0, 256, (n, 64), dtype=np.uint8) * live[:, None]
        cb = CommitBlock(
            flags, np.arange(n, dtype=np.int32), sig.astype(np.uint8),
            (1_700_000_000 + rng.randint(0, 3, n)).astype(np.int64) * live,
            rng.randint(0, 10 ** 9, n).astype(np.int32) * live,
            rng.randint(0, 256, (n, 20), dtype=np.uint8) * live[:, None],
        )
        pub = rng.randint(0, 256, (n, 32), dtype=np.uint8)
        power = rng.randint(1, 100, n).astype(np.int64)
        tpl = (b"\x08\x02\x11commit-prefix", b"\x08\x02\x11nil", b"2\x05chain")
        for mode in (0, cp.MODE_SELECT_COMMIT_ONLY | cp.MODE_EARLY_STOP):
            for thr in (10, int(power.sum()) * 2 // 3, 10 ** 9):
                a = cp.prep_commit(cb, pub, power, *tpl, thr, mode)
                b = cp._prep_commit_numpy(cb, pub, power, *tpl, thr, mode)
                assert np.array_equal(a[0], b[0]) and a[1] == b[1]
                assert (a[2] is None) == (b[2] is None)
                if a[2] is None:
                    continue
                assert np.array_equal(a[2].pub, b[2].pub)
                assert np.array_equal(a[2].sig, b[2].sig)
                assert np.array_equal(a[2].offsets, b[2].offsets)
                assert bytes(a[2].msgs) == bytes(b[2].msgs)
                assert not [x for x in dir(a[2]) if x.startswith("ram")]
        assert not [s for s in EntryBlock.__slots__ if s.startswith("ram")]

    def test_dispatcher_and_mesh_name_no_kernel(self):
        """Structure: ops/pipeline.py and ops/mesh.py name no prep, no
        jitted function and no kernel factory, and read no engine flag —
        they ask backend.select_kernel. The packing layer's own copies of
        the bucket ladders (it imports without the device stack) equal
        the backend's."""
        import ast
        import re

        from tendermint_tpu.ops import mesh as ms

        banned = re.compile(
            r"^(prepare_batch|prepare_compact|prepare_rlc|jitted_|"
            r"_jitted_|rlc_cached_fn$|rlc_launch$|cached_compact_fn$)"
        )
        ops_dir = os.path.dirname(backend.__file__)
        for mod in ("pipeline.py", "mesh.py"):
            tree = ast.parse(open(os.path.join(ops_dir, mod)).read())
            asked = 0
            for node in ast.walk(tree):
                name = getattr(node, "attr", None) or getattr(node, "id", None)
                if not isinstance(name, str):
                    continue
                assert not banned.match(name), (mod, node.lineno, name)
                assert name not in ("pallas", "rlc", "pallas_verify",
                                    "ed25519_verify"), (mod, node.lineno, name)
                if isinstance(node, ast.Attribute) and name == "select_kernel":
                    asked += 1
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    f = node.func
                    callee = getattr(f, "attr", None) or getattr(f, "id", "")
                    assert callee == "select_kernel" or not callee.endswith(
                        "_kernel"), (mod, node.lineno, callee)
            assert asked, mod
        assert ms._BUCKETS == backend.BUCKETS
        assert ms._BLS_LANE_BUCKETS == backend.BLS_BUCKETS
