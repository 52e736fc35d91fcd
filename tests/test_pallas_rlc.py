"""RLC fast-accept kernel (ops.pallas_rlc): differential conformance
against the ZIP-215 oracle, lane-reject fallback blame, scalar-prep
parity (native C vs pure Python), and backend dispatch wiring. The edge
battery, the async pipeline and the sharded program trace shapes of their
own and live in test_pallas_rlc_{edge,dispatch,sharded}.py (tests/_rlc.py).

Runs the real 3-kernel RLC pipeline in interpret mode at tiny buckets —
the same traced program Mosaic compiles on TPU (chip_smoke.py runs it
compiled, at the 10240 and 81920 buckets).
"""

import os

import numpy as np
import pytest

pytest.importorskip("jax")

from tendermint_tpu.ops import backend, pallas_rlc as pr  # noqa: E402
from _rlc import _deterministic_z, _sign_batch  # noqa: E402,F401


class TestRlcKernel:
    @pytest.mark.time_limit(600)  # 191-242 s on a cold cache (first trace + XLA:CPU compile of the bucket-16 shape): the maximum
    def test_valid_batch_with_straddling_padding(self):
        # 14 live sigs in a 16-sig bucket: one lane straddles live/padding
        entries = _sign_batch(14)
        res = pr.verify_batch_rlc(entries, block=4, interpret=True)
        assert res.tolist() == [True] * 14

    def test_lane_reject_falls_back_per_sig(self):
        entries = _sign_batch(14, tamper={6})
        res = pr.verify_batch_rlc(entries, block=4, interpret=True)
        assert res.tolist() == [i != 6 for i in range(14)]

    def test_all_valid_small_order_lane_fast_accepts(self):
        """A lane of entirely-valid small-order signatures must accept
        WITHOUT the fallback: [8]e_j = O for each, so the combination
        [8]acc = O identically (torsion cancels under the cofactor)."""
        ident_pk = (1).to_bytes(32, "little")
        entries = [(ident_pk, b"m%d" % i, bytes(64)) for i in range(pr.M)]
        args = pr.prepare_rlc(entries, 4 * pr.M)  # shape shared with above
        lanes = pr.verify_rlc_compact(*args, block=4, interpret=True)
        assert lanes.tolist() == [True] * 4  # lane 0 small-order, 1-3 padding

    def test_scalar_prep_native_matches_python(self):
        entries = _sign_batch(8)
        from tendermint_tpu.ops.backend import _challenges, _pack_rows
        from tendermint_tpu.native import load as _load_native

        native = _load_native()
        if native is None:
            pytest.skip("native module unavailable")
        pub, r_enc, s_enc = _pack_rows(entries, 8)
        ks = _challenges(r_enc, pub, [m for _, m, _ in entries])
        k_enc = np.frombuffer(ks, dtype=np.uint8).reshape(8, 32)
        z = pr._gen_z(8)
        a = native.ed25519_rlc_scalars(
            s_enc.tobytes(), k_enc.tobytes(), z.tobytes(), pr.M
        )
        b = pr._rlc_scalars_py(s_enc.tobytes(), k_enc.tobytes(), z.tobytes(), pr.M)
        assert a == b

    def test_seeded_z_deterministic(self):
        assert (pr._gen_z(8) == pr._gen_z(8)).all()
        # slot-0 coefficients are fixed at 1 (ignored entries stay zero)
        os.environ.pop("TM_TPU_RLC_SEED", None)
        z1, z2 = pr._gen_z(8), pr._gen_z(8)
        assert (z1[:, 16:] == 0).all()
        assert (z1 != z2).any(), "unseeded z must be random per batch"

    def test_backend_dispatch_uses_rlc(self, monkeypatch):
        """TM_TPU_PALLAS=1 + TM_TPU_RLC=1 routes verify_batch through the
        RLC fast-accept path on the CPU interpret backend."""
        monkeypatch.setenv("TM_TPU_PALLAS", "1")
        monkeypatch.setenv("TM_TPU_RLC", "1")
        # tiny lane blocks so interpret mode stays fast (env var is read
        # at module import; patch the module attribute)
        monkeypatch.setattr(pr, "BLOCK_LANES", 4)
        backend.engine.cache_clear()
        try:
            entries = _sign_batch(10, tamper={3})
            res = backend.verify_batch(entries)
            assert res.tolist() == [i != 3 for i in range(10)]
        finally:
            backend.engine.cache_clear()


def _four_arrays(entries, bucket, ep):
    """The four arrays the warm-epoch prep shipped, one device_put each,
    before they became views of one buffer (PR 29): what the packed
    buffer's sections have to hold, byte for byte."""
    n = len(entries)
    g = bucket // pr.M
    g_live = min((n + pr.M - 1) // pr.M, g)
    live = g_live * pr.M
    _pub, r_enc, scal, s_ok = pr._rlc_host_scalars(entries, live, g_live)
    idx = np.full((bucket,), ep.vp - 1, dtype=np.int32)
    idx[:n] = entries.val_idx
    r_rows = np.zeros((bucket, 32), dtype=np.uint8)
    r_rows[:live] = r_enc
    r_rows[live:, 0] = 1
    scal_rows = np.zeros((g, pr.N_SCAL, 32), dtype=np.uint8)
    scal_rows[:g_live] = scal
    sok_rows = np.ones((g, pr.M), dtype=np.int32)
    sok_rows[:g_live] = s_ok.reshape(g_live, pr.M).astype(np.int32)
    return idx, r_rows, scal_rows, sok_rows


def _warm_block(entries):
    """(EntryBlock with gather indices, its epoch entry): the block as
    the pipeline sees it once the validator set's tables are resident."""
    from tendermint_tpu.ops import epoch_cache
    from tendermint_tpu.ops.entry_block import EntryBlock

    blk = EntryBlock.from_entries(entries)
    # a permutation: gather indices are the commit's order, not 0..n-1
    blk.val_idx = np.random.RandomState(len(entries)).permutation(
        len(entries)).astype(np.int32)
    ep = epoch_cache.EpochEntry(b"k" * 32, blk.pub[np.argsort(blk.val_idx)])
    blk.epoch_key = ep.key
    return blk, ep


class TestPackedLaunchBuffer:
    """The warm-epoch launch ships ONE buffer (ISSUE 29): its sections,
    as the host wrote them and as the jitted prologue splits them, are
    the four arrays of the old layout; the kernels' verdicts follow."""

    @pytest.mark.parametrize("n", [1, 3, 150, 255, 256, 10_000])
    def test_split_equals_the_four_arrays(self, n):
        import jax

        rng = np.random.RandomState(n)
        # unsigned rows: prep hashes and reduces them all the same, and
        # random s halves give s_ok both values (s < L one time in 16)
        blk, ep = _warm_block(
            [(rng.bytes(32), b"rlc-%d" % i, rng.bytes(64)) for i in range(n)])
        bucket, g, _block = pr.plan_bucket(n)
        want = _four_arrays(blk, bucket, ep)
        (packed,) = pr.prepare_rlc_cached(blk, bucket, ep)
        assert packed.ndim == 1 and packed.flags.c_contiguous
        # no padding: the launch ships the bytes it always shipped
        assert packed.nbytes == sum(a.nbytes for a in want) == 104 * bucket
        host = pr.packed_views(packed, bucket)
        dev = jax.jit(pr.split_packed, static_argnums=1)(packed, bucket)
        for name, w, h, d in zip(("idx", "r_rows", "scal_rows", "sok_rows"),
                                 want, host, dev):
            d = np.asarray(d)
            assert h.base is not None and np.shares_memory(h, packed), name
            assert w.dtype == h.dtype == d.dtype, name
            assert w.shape == h.shape == d.shape, name
            assert w.tobytes() == h.tobytes() == d.tobytes(), name
        idx, r_rows, scal_rows, sok_rows = (np.asarray(d) for d in dev)
        live = -(-n // pr.M) * pr.M
        assert (idx[:n] == blk.val_idx).all() and (idx[n:] == ep.vp - 1).all()
        assert (r_rows[live:, 0] == 1).all() and not r_rows[live:, 1:].any()
        assert not scal_rows[live // pr.M:].any()
        assert (sok_rows[live // pr.M:] == 1).all()
        assert n < 16 or 0 < sok_rows[: live // pr.M].sum() < live

    @pytest.mark.time_limit(600)  # first trace of the cached bucket-16 shape
    @pytest.mark.parametrize("tamper", [(), (6,)], ids=["valid", "forged"])
    def test_cached_lanes_equal_uncached(self, tamper):
        blk, ep = _warm_block(_sign_batch(14, tamper=set(tamper)))
        bucket, g, block = pr.plan_bucket(len(blk), 4)
        lanes_u = pr.verify_rlc_compact(
            *pr.prepare_rlc(blk, bucket), block=block, interpret=True)
        dev = pr.rlc_cached_fn(ep, g, block, True)(
            *pr.prepare_rlc_cached(blk, bucket, ep))
        lanes_c = np.asarray(dev)[0].astype(bool)
        assert lanes_c.tolist() == lanes_u.tolist()
        assert lanes_c.tolist() == [True, not tamper, True, True]
        assert pr.expand_lanes(lanes_c, blk).tolist() == [
            i not in tamper for i in range(14)]
