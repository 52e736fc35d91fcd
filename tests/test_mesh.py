"""Mesh dispatcher (ISSUE 9): lane-packed superbatch verdict/blame
parity against the single-device path, across mixed-epoch and mixed-size
lane packs (including a pure identity-padding lane), on the 1-lane and
2-lane (simulated) mesh — the CPU/tier-1 face of multichip serving. Also
the warn-once shard_map fallback and the mesh observability gauges.

Runs with devcheck armed: the mesh superbatch path must satisfy the
device single-owner assertions and the write-after-resolve canary exactly
like the single-device dispatcher."""

import os

import numpy as np
import pytest

try:
    from tendermint_tpu.crypto import ed25519
except ModuleNotFoundError:
    # No cryptography wheel in this container. Do NOT flip
    # TM_TPU_PUREPY_CRYPTO here (env leaks into later-collected modules);
    # test_mesh_isolated.py re-runs this module in a subprocess with the
    # fallback enabled instead.
    pytest.skip(
        "ed25519 backend unavailable (runs via test_mesh_isolated.py)",
        allow_module_level=True,
    )

from tendermint_tpu.libs import devcheck
from tendermint_tpu.libs.metrics import ops_stats
from tendermint_tpu.ops import backend, epoch_cache, mesh as ms
from tendermint_tpu.ops import pipeline as pl
from tendermint_tpu.ops import sharded
from tendermint_tpu.ops._testing import drain_pool
from tendermint_tpu.ops.entry_block import EntryBlock


@pytest.fixture(autouse=True)
def _devcheck_armed():
    devcheck.enable(reset=True)
    yield
    try:
        devcheck.check()
    finally:
        devcheck.reset_state()
        devcheck.disable()


@pytest.fixture(autouse=True)
def _lane_bucket_128(monkeypatch):
    """Small lanes keep the compiled superbatch shapes at {128, 256} —
    the tier-1 compile budget — and make pack shapes predictable."""
    monkeypatch.setenv("TM_TPU_MESH_LANE_BUCKET", "128")


def _signed(n, tag=0, bad=()):
    out = []
    for i in range(n):
        sk = ed25519.gen_priv_key((tag * 4096 + i + 1).to_bytes(32, "little"))
        m = b"mesh-%d-%d" % (tag, i)
        sig = sk.sign(m) if i not in bad else b"\x07" * 64
        out.append((sk.pub_key().bytes(), m, sig))
    return out


class _J:
    def __init__(self, blk):
        self.entries = blk


def _run_plan(plan):
    """Launch a hand-built plan the way the dispatcher would (direct
    call — no pipeline threads), returning the raw verdict row."""
    from tendermint_tpu.ops import device_pool as dp

    block, spans = ms.build_superblock(plan)
    res = ms.prepare_superbatch(block, plan)
    f, args = res[0], res[1]
    shardings = res[4] if len(res) > 4 else None
    with devcheck.exempt():
        dev = f(*dp.transfer(args, shardings=shardings))
    arr = np.array(dev)
    if arr.ndim == 2:
        arr = arr[0]
    return arr.astype(bool), spans


class TestMeshParity:
    def test_one_lane_mesh_parity_mixed_sizes(self):
        """lanes=1: the mesh packer's (1, bucket) superbatch must be
        verdict-identical to the classic single-device path."""
        jobs = [_signed(96, 1, bad=(3,)), _signed(31, 2), _signed(5, 3)]
        v = pl.AsyncBatchVerifier(depth=2, mesh_lanes=1)
        try:
            futs = [v.submit(j) for j in jobs]
            res = [np.asarray(f.result(timeout=300)) for f in futs]
            drain_pool(v._pool)
            assert v._pool.stats()["in_flight"] == 0
        finally:
            v.close()
        for j, r in zip(jobs, res):
            assert np.array_equal(r, np.asarray(backend.verify_batch(j)))
        assert not res[0][3] and res[0].sum() == 95

    def test_two_lane_pack_parity_and_blame(self):
        """2 simulated lanes, mixed job sizes, tampered rows in two
        different jobs: verdicts and blame indices survive the per-lane
        demux bit-identically."""
        jobs = [
            _signed(96, 10, bad=(17,)),
            _signed(31, 11),
            _signed(128, 12, bad=(0, 127)),
            _signed(64, 13),
            _signed(7, 14),
        ]
        v = pl.AsyncBatchVerifier(depth=2, mesh_lanes=2)
        try:
            futs = [v.submit(j) for j in jobs]
            res = [np.asarray(f.result(timeout=300)) for f in futs]
            drain_pool(v._pool)
            assert v._pool.stats()["in_flight"] == 0
        finally:
            v.close()
        for j, r in zip(jobs, res):
            assert np.array_equal(r, np.asarray(backend.verify_batch(j)))
        assert not res[0][17] and res[0].sum() == 95
        assert not res[2][0] and not res[2][127] and res[2].sum() == 126
        assert res[1].all() and res[3].all() and res[4].all()
        # the verdict rows delivered to callers are owned memory (the
        # PR-7 aliasing rule holds on the mesh path too)
        assert all(r.flags.owndata or r.base.flags.owndata for r in res)

    def test_pure_identity_pad_lane(self):
        """A superbatch whose lane count rounds past its live lanes
        carries a PURE padding lane — verdicts of the live jobs are
        unaffected and the pad lane verifies trivially."""
        blk = EntryBlock.from_entries(_signed(100, 20, bad=(5,)))
        plan, held = ms.pack_jobs([_J(blk)], 2, 128)
        assert not held and len(plan.lanes) == 1
        plan.n_lanes = 2  # force the trailing pure-pad lane
        assert plan.bucket == 256 and plan.pad == 156
        arr, spans = _run_plan(plan)
        assert len(spans) == 1
        job, off, n = spans[0]
        got = arr[off:off + n]
        want = np.asarray(backend.verify_batch(blk))
        assert np.array_equal(got, want)
        assert not got[5] and got.sum() == 99
        # every identity padding row (incl. the whole second lane)
        # verifies trivially
        assert arr[n:].all()

    def test_mixed_epoch_lanes_never_share_a_lane(self):
        """Jobs of two different (warm) epochs plus an uncached job pack
        into single-epoch lanes; the mixed superbatch rides the uncached
        prep and stays verdict-identical per job."""
        epoch_cache.reset(depth=4)
        try:
            e1 = EntryBlock.from_entries(_signed(40, 30))
            e1.epoch_key, e1.val_idx = b"ek-1", np.arange(40, dtype=np.int32)
            e2 = EntryBlock.from_entries(_signed(50, 31, bad=(9,)))
            e2.epoch_key, e2.val_idx = b"ek-2", np.arange(50, dtype=np.int32)
            e3 = EntryBlock.from_entries(_signed(30, 32))
            plan, held = ms.pack_jobs([_J(e1), _J(e2), _J(e3)], 4, 128)
            assert not held
            # e1/e2 differ in key, e3 has none: three distinct lanes
            assert [l.key for l in plan.lanes] == [b"ek-1", b"ek-2", None]
            block, _ = ms.build_superblock(plan)
            # mixed keys: concat drops the epoch metadata -> uncached
            assert block.epoch_key is None
            arr, spans = _run_plan(plan)
            for job, off, n in spans:
                want = np.asarray(backend.verify_batch(job.entries))
                assert np.array_equal(arr[off:off + n], want)
        finally:
            epoch_cache.reset()

    def test_same_warm_epoch_pack_uses_cached_prep(self):
        """A pack whose every lane shares ONE warm epoch preps through
        the gather path (no pubkey-derived arrays ship) and stays
        verdict-identical to the uncached launch of the same rows."""
        epoch_cache.reset(depth=4)
        try:
            entries = _signed(48, 40, bad=(11,))
            pub_col = np.frombuffer(
                b"".join(p for p, _, _ in entries), dtype=np.uint8
            ).reshape(48, 32)
            c = epoch_cache.cache()
            assert c.note(b"mesh-warm", pub_col) is None  # cold register
            warm = c.note(b"mesh-warm", pub_col)  # warm
            assert warm is not None
            name = warm[0].key      # the table's own name

            def jb(lo, hi, tag):
                blk = EntryBlock.from_entries(entries[lo:hi])
                blk.epoch_key = name
                blk.val_idx = np.arange(lo, hi, dtype=np.int32)
                return _J(blk)

            plan, held = ms.pack_jobs([jb(0, 20, 0), jb(20, 48, 1)], 2, 128)
            # same warm key: first-fit shares ONE lane (same-epoch jobs
            # gather from the same table rows)
            assert not held and len(plan.lanes) == 1
            block, _ = ms.build_superblock(plan)
            assert block.epoch_key == name
            res = ms.prepare_superbatch(block, plan)
            args = res[1]
            # cached arg shape: the warm args are (idx, r, s, k, s_ok)
            # — structurally pub-free (the --transfer gate's invariant,
            # mesh face)
            assert len(args) == 5 and args[0].dtype == np.int32
            arr, spans = _run_plan(plan)
            flat = np.zeros(48, dtype=bool)
            for job, off, n in spans:
                flat[job.entries.val_idx] = arr[off:off + n]
            want = np.asarray(backend.verify_batch(
                EntryBlock.from_entries(entries)
            ))
            assert np.array_equal(flat, want)
            assert not flat[11] and flat.sum() == 47
        finally:
            epoch_cache.reset()


class TestMeshObservability:
    def test_gauges_published_and_complementary(self):
        jobs = [_signed(96, 60), _signed(31, 61)]
        v = pl.AsyncBatchVerifier(depth=2, mesh_lanes=2)
        try:
            for f in [v.submit(j) for j in jobs]:
                f.result(timeout=300)
            drain_pool(v._pool)
        finally:
            v.close()
        s = ops_stats()
        occ, pad = s["mesh_lane_occupancy"], s["mesh_pad_waste_ratio"]
        assert 0.0 < occ <= 1.0
        assert occ + pad == pytest.approx(1.0)

    def test_oversized_submit_chunks_at_lane_cap(self):
        """A job bigger than one lane chunk-splits at submit (mesh mode
        packs WHOLE jobs into lanes) and re-aggregates into one future."""
        entries = _signed(200, 70, bad=(150,))
        v = pl.AsyncBatchVerifier(depth=2, mesh_lanes=2)
        try:
            r = np.asarray(v.submit(entries).result(timeout=300))
            drain_pool(v._pool)
        finally:
            v.close()
        assert r.shape == (200,)
        want = np.asarray(backend.verify_batch(entries))
        assert np.array_equal(r, want)
        assert not r[150] and r.sum() == 199
