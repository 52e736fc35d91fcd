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
from _rlc import (  # noqa: E402,F401
    CachedSuite, KernelSuite, _deterministic_z, _sign_batch, _warm_block,
)


class TestRlcKernel(KernelSuite):
    M, N, FORGED = 4, 14, 6

    @pytest.mark.parametrize("m", pr.WIDTHS)
    def test_scalar_prep_native_matches_python(self, m):
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
            s_enc.tobytes(), k_enc.tobytes(), z.tobytes(), m
        )
        b = pr._rlc_scalars_py(s_enc.tobytes(), k_enc.tobytes(), z.tobytes(), m)
        assert a == b and len(a) == 32 * (8 // m) * (1 + m)

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
        # at module import; patch the module attribute); 10 signatures in
        # 4-lane blocks are width 4, the shape traced above
        monkeypatch.setattr(pr, "BLOCK_LANES", 4)
        backend.engine.cache_clear()
        try:
            entries = _sign_batch(10, tamper={3})
            res = backend.verify_batch(entries)
            assert res.tolist() == [i != 3 for i in range(10)]
        finally:
            backend.engine.cache_clear()


# what plan_bucket owes a batch of n at the default 128-lane blocks:
# (n, width, bucket)
PLANS = [
    (64, 2, 64), (150, 2, 256), (256, 2, 256), (257, 4, 512),
    (512, 4, 512), (513, 8, 1024), (1_024, 8, 1024), (1_025, 8, 2048),
    (10_000, 8, 10_240), (40_000, 8, 40_960), (81_920, 8, 81_920),
]


class TestLaneWidthRule:
    """The width follows the batch size and nothing else: the narrowest
    whose single block holds the batch, the widest past that."""

    @pytest.mark.parametrize("n,m,bucket", PLANS)
    def test_plan_bucket(self, n, m, bucket):
        got_bucket, g, block, got_m = pr.plan_bucket(n)
        assert (got_m, got_bucket) == (m, bucket)
        # whole lanes, whole blocks: never a truncated grid
        assert got_bucket == g * m >= n
        assert g % block == 0 and 0 < block <= pr.BLOCK_LANES
        assert got_bucket % (m * block) == 0
        # a single block wherever one block can hold the batch
        assert (g == block) == (n <= pr.WIDTHS[-1] * pr.BLOCK_LANES)
        # a bucket has the width of every size it pads
        assert pr.lane_width(got_bucket) == m
        assert pr.plan_bucket(got_bucket) == (got_bucket, g, block, m)

    def test_width_is_monotone_in_n(self):
        widths = [pr.lane_width(n) for n in range(1, 3000)]
        assert widths == sorted(widths) and set(widths) == set(pr.WIDTHS)
        assert pr.plan_bucket(pr.MAX_SIGS + 1)[0] == pr.MAX_SIGS

    @pytest.mark.parametrize("m", [0, 1, 3, 16])
    def test_unknown_width_refused(self, m):
        with pytest.raises(ValueError, match="width"):
            pr.prepare_rlc([], 16 * max(m, 1), m)
        with pytest.raises(ValueError, match="width"):
            pr._jitted_rlc_verify(m, 4, 4, True)

    def test_launches_counted_by_width(self, monkeypatch):
        from tendermint_tpu.libs.metrics import ops_stats

        monkeypatch.setattr(pr, "_jitted_rlc_verify",
                            lambda m, g, *_a, **_k: (m, g))
        before = ops_stats()
        shapes = [pr.rlc_launch(_sign_batch(n), block=4)
                  for n in (3, 5, 9, 20, 21)]
        # (kernel for (m, g), its packed buffer, bucket, m)
        assert [(fn, bucket, m) for fn, _args, bucket, m in shapes] == [
            ((2, 2), 4, 2), ((2, 4), 8, 2), ((4, 4), 16, 4),
            ((8, 4), 32, 8), ((8, 4), 32, 8)]
        after = ops_stats()

        def moved(key):
            return {m: after[key].get(m, 0) - before[key].get(m, 0)
                    for m in ("2", "4", "8")}

        assert moved("rlc_launches_by_width") == {"2": 2, "4": 1, "8": 2}
        assert moved("rlc_sigs_by_width") == {"2": 8, "4": 9, "8": 41}


def _four_arrays(entries, bucket, ep, m):
    """The four arrays a prep shipped, one device_put each, before they
    became views of one buffer: what the packed buffer's sections have
    to hold, byte for byte. The head is the gather indices of a warm
    epoch (ep), the public keys of an uncached launch (ep None)."""
    n = len(entries)
    g = bucket // m
    g_live = min((n + m - 1) // m, g)
    live = g_live * m
    pub, r_enc, raw, z, s_ok = pr._rlc_host_scalars(entries, live, g_live, m)
    scal = pr._scal_rows(raw, z, g_live, m)
    if ep is None:
        head = np.zeros((bucket, 32), dtype=np.uint8)
        head[:live] = pub
        head[live:, 0] = 1
    else:
        head = np.full((bucket,), ep.vp - 1, dtype=np.int32)
        head[:n] = entries.val_idx
    r_rows = np.zeros((bucket, 32), dtype=np.uint8)
    r_rows[:live] = r_enc
    r_rows[live:, 0] = 1
    scal_rows = np.zeros((g, 2 * m, 32), dtype=np.uint8)
    scal_rows[:g_live] = scal
    sok_rows = np.ones((g, m), dtype=np.int32)
    sok_rows[:g_live] = s_ok.reshape(g_live, m).astype(np.int32)
    return head, r_rows, scal_rows, sok_rows


class TestPackedLaunchBuffer(CachedSuite):
    """The warm-epoch launch ships ONE buffer (ISSUE 29): its sections,
    as the host wrote them and as the jitted prologue splits them, are
    the four arrays of the old layout, at every lane width; the kernels'
    verdicts follow (CachedSuite, here at width 4)."""

    M, N, FORGED = 4, 14, 6

    @pytest.mark.parametrize("m", pr.WIDTHS)
    @pytest.mark.parametrize("n", [1, 3, 150, 255, 256, 10_000])
    def test_split_equals_the_four_arrays(self, n, m):
        import jax

        rng = np.random.RandomState(n)
        # unsigned rows: prep hashes and reduces them all the same, and
        # random s halves give s_ok both values (s < L one time in 16)
        blk, ep = _warm_block(
            [(rng.bytes(32), b"rlc-%d" % i, rng.bytes(64)) for i in range(n)])
        # plan_bucket's own bucket where m is its width for n, else the
        # lanes of n at the forced width padded to whole 8-lane groups
        bucket, _g, _block, planned = pr.plan_bucket(n)
        if planned != m:
            bucket = -(-n // (8 * m)) * 8 * m
        want = _four_arrays(blk, bucket, ep, m)
        (packed,) = pr.prepare_rlc_cached(blk, bucket, ep, m)
        assert packed.ndim == 1 and packed.flags.c_contiguous
        # no padding: the launch ships the bytes it always shipped, 104 a
        # slot at any width
        assert packed.nbytes == sum(a.nbytes for a in want) == 104 * bucket
        host = pr.packed_views(packed, bucket, m)
        dev = jax.jit(pr.split_packed, static_argnums=(1, 2))(
            packed, bucket, m)
        for name, w, h, d in zip(("idx", "r_rows", "scal_rows", "sok_rows"),
                                 want, host, dev):
            d = np.asarray(d)
            assert h.base is not None and np.shares_memory(h, packed), name
            assert w.dtype == h.dtype == d.dtype, name
            assert w.shape == h.shape == d.shape, name
            assert w.tobytes() == h.tobytes() == d.tobytes(), name
        idx, r_rows, scal_rows, sok_rows = (np.asarray(d) for d in dev)
        live = -(-n // m) * m
        assert (idx[:n] == blk.val_idx).all() and (idx[n:] == ep.vp - 1).all()
        assert (r_rows[live:, 0] == 1).all() and not r_rows[live:, 1:].any()
        assert not scal_rows[live // m:].any()
        assert (sok_rows[live // m:] == 1).all()
        assert n < 16 or 0 < sok_rows[: live // m].sum() < live

    @pytest.mark.parametrize("m", pr.WIDTHS)
    @pytest.mark.parametrize("n", [1, 3, 150, 255, 256, 10_000])
    def test_uncached_split_equals_the_four_arrays(self, n, m):
        """The uncached launch's buffer, public keys at its head: the
        host's views and the jitted prologue's split are its four arrays,
        132 bytes a slot, and slot_major_args lays them out for the mesh
        as the kernels read them: slot j of lane l in column l."""
        import jax

        rng = np.random.RandomState(n)
        entries = [(rng.bytes(32), b"rlc-%d" % i, rng.bytes(64))
                   for i in range(n)]
        bucket, _g, _block, planned = pr.plan_bucket(n)
        if planned != m:
            bucket = -(-n // (8 * m)) * 8 * m
        g = bucket // m
        want = _four_arrays(entries, bucket, None, m)
        (packed,) = pr.prepare_rlc(entries, bucket, m)
        assert packed.ndim == 1 and packed.dtype == np.int32
        assert packed.nbytes == sum(a.nbytes for a in want) == 132 * bucket
        host = pr.packed_views(packed, bucket, m, pr.PUB_WORDS)
        dev = jax.jit(pr.split_packed, static_argnums=(1, 2, 3))(
            packed, bucket, m, pr.PUB_WORDS)
        for name, w, h, d in zip(("pub_rows", "r_rows", "scal_rows",
                                  "sok_rows"), want, host, dev):
            d = np.asarray(d)
            assert np.shares_memory(h, packed), name
            assert w.dtype == h.dtype == d.dtype, name
            assert w.shape == h.shape == d.shape, name
            assert w.tobytes() == h.tobytes() == d.tobytes(), name
        pub_rows, r_rows, scal_rows, sok_rows = want
        a_t, r_t, scal_t, sok_t = pr.slot_major_args(packed, bucket, m)
        assert a_t.shape == r_t.shape == (m * 32, g)
        assert scal_t.shape == (2 * m * 32, g) and sok_t.shape == (m, g)
        for j in range(m):
            assert (a_t[j * 32:(j + 1) * 32] == pub_rows[j::m].T).all()
            assert (r_t[j * 32:(j + 1) * 32] == r_rows[j::m].T).all()
            assert (sok_t[j] == sok_rows[:, j]).all()
        for q in range(2 * m):
            assert (scal_t[q * 32:(q + 1) * 32] == scal_rows[:, q].T).all()
        live = -(-n // m) * m
        assert (pub_rows[live:, 0] == 1).all() and not pub_rows[live:, 1:].any()
