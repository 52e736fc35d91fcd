"""Overlapped device (ISSUE 7): transfer/compute pipelining in the
dispatch-owner loop, the per-shape device buffer pool, buffer donation
parity (cold + warm epoch, buckets 128/1024), the structured async
verdict readback, and the poisoned-batch buffer-return bookkeeping.

Donation on this container's CPU backend is a no-op with a warning (XLA
CPU ignores donate_argnums) — the parity tests still pin the donated
wrappers' verdict/blame bit-equality and exercise the exact call paths
the TPU backend donates for real."""

import time

import numpy as np
import pytest

try:
    from tendermint_tpu.crypto import ed25519
except ModuleNotFoundError:
    # No cryptography wheel in this container. Do NOT flip
    # TM_TPU_PUREPY_CRYPTO here (env leaks into later-collected modules);
    # test_overlap_isolated.py re-runs this module in a subprocess with
    # the fallback enabled instead.
    pytest.skip(
        "ed25519 backend unavailable (runs via test_overlap_isolated.py)",
        allow_module_level=True,
    )

from tendermint_tpu.libs import devcheck
from tendermint_tpu.observability import trace as _tr
from tendermint_tpu.ops import backend, device_pool, epoch_cache
from tendermint_tpu.ops import ed25519_verify as ev


@pytest.fixture(autouse=True)
def _devcheck_armed():
    """ISSUE 8: the overlap suite runs with the runtime invariant
    checkers on (device assertions, lock-order cycles, write-after-
    resolve canary); a violation fails the offending test at teardown.
    Direct kernel launches by parity tests stay legal — the device
    assertion only gates transfer/table-upload entry points once a
    dispatcher has claimed ownership."""
    devcheck.enable(reset=True)
    yield
    try:
        devcheck.check()
    finally:
        devcheck.reset_state()
        devcheck.disable()
from tendermint_tpu.ops import pipeline as pl
from tendermint_tpu.ops._testing import drain_pool, slow_prepare
from tendermint_tpu.ops.entry_block import EntryBlock

pytestmark = pytest.mark.filterwarnings(
    "ignore:Some donated buffers were not usable"
)

_RNG = np.random.RandomState(42)


def _signed_entries(n, tag=0, bad=()):
    """n REAL (pub, msg, sig) triples, sigs at `bad` indices corrupted."""
    out = []
    for i in range(n):
        sk = ed25519.gen_priv_key(bytes([tag + 1]) * 30 + i.to_bytes(2, "big"))
        m = b"overlap-%d-%d" % (tag, i)
        s = sk.sign(m)
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
        out.append((sk.pub_key().bytes(), m, s))
    return out


def _random_entries(n, tag=0):
    """Structurally-valid random triples — verdict parity between the
    donated and plain wrappers does not need valid signatures."""
    return [
        (
            _RNG.randint(0, 256, 32, dtype=np.uint8).tobytes(),
            b"rnd-%d-%d" % (tag, i),
            _RNG.randint(0, 256, 64, dtype=np.uint8).tobytes(),
        )
        for i in range(n)
    ]


def _warm_epoch(n_vals, n_sigs, bad=()):
    """A direct EpochEntry + warm EntryBlock (val_idx/epoch_key set), the
    shape prepare_batch_cached* consumes — no cache registry involved."""
    sks = [
        ed25519.gen_priv_key(b"\x05" * 30 + i.to_bytes(2, "big"))
        for i in range(n_vals)
    ]
    pub_col = np.frombuffer(
        b"".join(sk.pub_key().bytes() for sk in sks), dtype=np.uint8
    ).reshape(n_vals, 32)
    ep = epoch_cache.EpochEntry(b"\xEE" * 32, pub_col)
    idx = _RNG.randint(0, n_vals, size=n_sigs)
    entries = []
    for j, i in enumerate(idx):
        m = b"warm-%d" % j
        s = sks[i].sign(m)
        if j in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
        entries.append((sks[i].pub_key().bytes(), m, s))
    block = EntryBlock.from_entries(entries)
    block.val_idx = idx.astype(np.int32)
    block.epoch_key = ep.key
    return ep, block


def _assert_verdict_blame_parity(a, b):
    a, b = np.asarray(a).astype(bool), np.asarray(b).astype(bool)
    assert np.array_equal(a, b)
    if not a.all():
        assert int(np.argmin(a)) == int(np.argmin(b))


class TestDonationParity:
    """Donated wrappers are bit-identical to the plain ones — verdicts
    AND blame — and never read a donated input after launch (fresh args
    per call, exactly the pipeline's usage)."""

    @pytest.mark.parametrize("bucket,n", [(128, 100), (1024, 1000)])
    def test_cold_epoch_parity(self, bucket, n):
        entries = (
            _signed_entries(16, tag=1, bad=(3, 7)) + _random_entries(n - 16)
            if bucket == 128
            else _random_entries(n, tag=2)
        )
        block = EntryBlock.from_entries(entries)
        plain = ev.jitted_verify(False)(
            *backend.prepare_batch(block, bucket)
        )
        donated = ev.jitted_verify(True)(
            *backend.prepare_batch(block, bucket)
        )
        _assert_verdict_blame_parity(
            np.asarray(plain)[:n], np.asarray(donated)[:n]
        )

    @pytest.mark.parametrize("bucket,n", [(128, 100), (1024, 1000)])
    def test_warm_epoch_parity(self, bucket, n):
        ep, block = _warm_epoch(100, n, bad=(5,))
        plain = backend.cached_kernel(ep, donate=False)(
            *backend.prepare_batch_cached(block, bucket, ep)
        )
        donated = backend.cached_kernel(ep, donate=True)(
            *backend.prepare_batch_cached(block, bucket, ep)
        )
        p, d = np.asarray(plain)[:n], np.asarray(donated)[:n]
        _assert_verdict_blame_parity(p, d)
        assert not p[5]  # the corrupted lane is blamed on both paths
        # the epoch tables survived the donated launch (donation exempt):
        # a second donated call over fresh args still verifies
        again = backend.cached_kernel(ep, donate=True)(
            *backend.prepare_batch_cached(block, bucket, ep)
        )
        assert np.array_equal(np.asarray(again)[:n], p)

    def test_donated_pipeline_overlapping_batches(self, monkeypatch):
        """ISSUE 7 regression: two (five) overlapping batches with
        DISTINGUISHABLE payloads through a donation-enabled pipeline —
        a donated input buffer read after launch, or a recycled buffer
        leaking between batches, would flip verdicts across batches."""
        monkeypatch.setenv("TM_TPU_DONATE", "1")
        backend.engine.cache_clear()
        try:
            assert backend.engine().donate is True
            v = pl.AsyncBatchVerifier(depth=2)
            try:
                futs = [
                    v.submit(_signed_entries(8, tag=t, bad=(t % 8,)))
                    for t in range(5)
                ]
                donated_res = [f.result(timeout=300) for f in futs]
            finally:
                v.close()
        finally:
            monkeypatch.setenv("TM_TPU_DONATE", "0")
            backend.engine.cache_clear()
        try:
            v2 = pl.AsyncBatchVerifier(depth=2)
            try:
                futs = [
                    v2.submit(_signed_entries(8, tag=t, bad=(t % 8,)))
                    for t in range(5)
                ]
                plain_res = [f.result(timeout=300) for f in futs]
            finally:
                v2.close()
        finally:
            monkeypatch.delenv("TM_TPU_DONATE", raising=False)
            backend.engine.cache_clear()
        for t, (d, p) in enumerate(zip(donated_res, plain_res)):
            d, p = np.asarray(d), np.asarray(p)
            assert d.shape == (8,)
            assert not d[t % 8] and d.sum() == 7, f"batch {t}"
            assert np.array_equal(d, p)


class TestBufferPool:
    def test_poisoned_batch_leaks_no_slots(self, monkeypatch):
        """ISSUE 7 satellite: a kernel-launch failure must return the
        batch's pool slot (and depth permit) — DispatchError carries the
        buffer-return bookkeeping too."""
        real_prepare = pl.AsyncBatchVerifier._prepare
        POISON_N = 3

        def prep(entries):
            f, args, rlc, bucket = real_prepare(entries)
            if len(entries) == POISON_N:
                def boom(*_a):
                    raise RuntimeError("kernel launch exploded")

                return boom, args, rlc, bucket
            return f, args, rlc, bucket

        monkeypatch.setattr(
            pl.AsyncBatchVerifier, "_prepare", staticmethod(prep)
        )
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            for round_ in range(2):
                bad = v.submit(_random_entries(POISON_N, tag=round_))
                with pytest.raises(pl.DispatchError):
                    bad.result(timeout=300)
                good = v.submit(_random_entries(8, tag=10 + round_))
                assert good.result(timeout=300).shape == (8,)
            assert v._dispatch_thread.is_alive()
            drain_pool(v._pool)
            stats = v._pool.stats()
            assert stats["in_flight"] == 0, stats
            assert stats["free"] == stats["minted"], stats
        finally:
            v.close()

    def test_transfer_failure_fails_batch_alone(self, monkeypatch):
        real = device_pool.transfer
        state = {"boom": True}

        def xfer(args):
            if state["boom"]:
                state["boom"] = False
                raise RuntimeError("device transfer exploded")
            return real(args)

        monkeypatch.setattr(pl._dpool, "transfer", xfer)
        v = pl.AsyncBatchVerifier(depth=2)
        try:
            bad = v.submit(_random_entries(4))
            with pytest.raises(pl.DispatchError, match="transfer"):
                bad.result(timeout=300)
            good = v.submit(_random_entries(8, tag=1))
            assert good.result(timeout=300).shape == (8,)
            assert v._dispatch_thread.is_alive()
            # futures complete BEFORE the resolver returns the slot —
            # drain instead of racing the release
            drain_pool(v._pool)
            assert v._pool.in_flight() == 0
        finally:
            v.close()

    def test_pool_reuse_steady_state(self):
        """Same layout streamed repeatedly: the pool mints at most
        `pool_depth` slots, then every acquire recycles."""
        v = pl.AsyncBatchVerifier(depth=2, pool_depth=2)
        try:
            for t in range(6):
                v.submit(_random_entries(96, tag=t)).result(timeout=300)
            drain_pool(v._pool)
            stats = v._pool.stats()
            assert stats["minted"] <= 2 * stats["layouts"], stats
            assert stats["in_flight"] == 0, stats
        finally:
            v.close()


class TestOverlapStructure:
    def test_transfer_overlaps_previous_batch(self, monkeypatch):
        """Span-order proof of the pipelined loop: with a slow (mocked)
        readback and depth 1, batch k+1's transfer is issued before batch
        k resolves, transfers precede their own launch, and the transfer
        stage runs on the single dispatch-owner thread."""
        monkeypatch.setattr(
            pl.AsyncBatchVerifier, "_prepare",
            staticmethod(slow_prepare(pl.AsyncBatchVerifier._prepare, 0.1)),
        )
        monkeypatch.setattr(backend, "max_coalesce", lambda: 96)
        _tr.TRACER.clear()
        _tr.configure(enabled=True)
        v = pl.AsyncBatchVerifier(depth=1, pool_depth=2)
        try:
            v.submit(_random_entries(96, tag=99)).result(timeout=300)
            futs = [v.submit(_random_entries(96, tag=t)) for t in range(4)]
            for f in futs:
                f.result(timeout=300)
        finally:
            _tr.configure(enabled=False)
            v.close()
        xfers, dispatches, waits = [], [], []
        tids = set()
        for name, start, end, tid, args in _tr.TRACER.events():
            if name == "pipeline.transfer":
                xfers.append((start, end, args or {}))
                tids.add(tid)
            elif name == "pipeline.dispatch":
                dispatches.append((start, end))
                tids.add(tid)
            elif name == "pipeline.device_wait":
                waits.append((start, end))
        xfers.sort(), dispatches.sort(), waits.sort()
        assert len(xfers) == len(dispatches) == len(waits) == 5
        xfers, dispatches, waits = xfers[1:], dispatches[1:], waits[1:]
        # split: every batch's transfer closes before its launch opens
        assert all(x[1] <= d[0] for x, d in zip(xfers, dispatches))
        # overlap: transfer k+1 issued before batch k resolved
        overlapped = sum(
            1 for i in range(1, 4) if xfers[i][0] < waits[i - 1][1]
        )
        assert overlapped >= 2, (overlapped, xfers, waits)
        assert sum(1 for x in xfers if x[2].get("hidden")) >= 3
        # device single-owner extends to the transfer stage
        assert tids == v.dispatch_thread_idents == {v._dispatch_thread.ident}

    def test_d2h_capability_probe_cached(self):
        first = pl._d2h_async_supported()
        assert isinstance(first, bool)
        assert pl._d2h_async_supported() is first
        assert pl._d2h_async_supported.cache_info().hits >= 1
        # on this jax, device arrays do expose the async copy
        import jax

        arr = jax.device_put(np.zeros(1, dtype=np.uint8))
        assert first == callable(getattr(arr, "copy_to_host_async", None))

    def test_overlap_metrics_surfaced(self):
        from tendermint_tpu.libs.metrics import ops_stats

        v = pl.AsyncBatchVerifier(depth=2)
        try:
            v.submit(_random_entries(32)).result(timeout=300)
        finally:
            v.close()
        s = ops_stats()
        assert "transfer_overlap_ratio" in s
        assert s["buffer_pool_hits"] + s["buffer_pool_misses"] >= 1


class TestOneTransferPerWarmRlcLaunch:
    def test_warm_and_uncached_launches_are_one_put_each(self, monkeypatch):
        """ISSUE 29: a warm-epoch RLC launch hands device_pool.transfer
        ONE host array, so the dispatcher records one
        `pipeline.transfer.put` inside its `pipeline.transfer` and
        `h2d_ops` rises by 1. So does the uncached launch, its public
        keys in the buffer's head where the warm one has gather indices. Each buffer carries the bytes its launch's four
        arrays did, to the byte. The Pallas
        pipelines are stood in for by all-accepting launches: this is the
        dispatcher's accounting, tests/test_pallas_rlc.py has the kernels."""
        import jax.numpy as jnp

        from tendermint_tpu.libs.metrics import ops_stats
        from tendermint_tpu.ops import pallas_rlc as pr

        def accept_all(_m, g, *_a, **_k):
            return lambda *_args: jnp.ones((1, g), dtype=jnp.int32)

        monkeypatch.setenv("TM_TPU_PALLAS", "1")
        monkeypatch.setenv("TM_TPU_RLC", "1")
        monkeypatch.setattr(pr, "_jitted_rlc_verify", accept_all)
        monkeypatch.setattr(pr, "_jitted_rlc_verify_cached", accept_all)
        monkeypatch.setattr(epoch_cache.EpochEntry, "coords_tables",
                            lambda self: (None, None))
        backend.engine.cache_clear()
        epoch_cache.reset(depth=4)
        n = 150
        ep, warm = _warm_epoch(n, n)
        # registered, then seen again: the epoch is warm for lookup()
        for _ in range(2):
            got = epoch_cache.cache().note(ep.key, ep.pub_rows[:n].copy())
        warm.epoch_key = got[0].key     # the resident table's own name
        cold = EntryBlock.from_entries(warm.to_entries())
        bucket, g, _block, m = pr.plan_bucket(n)

        def launch(v, block):
            """(put spans inside the one transfer span, h2d_ops delta,
            h2d_bytes_per_commit) of one launch of `block`."""
            before = ops_stats()["h2d_ops"]
            _tr.TRACER.clear()
            _tr.configure(enabled=True)
            try:
                assert v.submit(block).result(timeout=300).all()
            finally:
                _tr.configure(enabled=False)
            ev_ = _tr.TRACER.events()
            (xfer,) = [e for e in ev_ if e[0] == "pipeline.transfer"]
            puts = [e for e in ev_ if e[0] == "pipeline.transfer.put"]
            assert all(e[3] == xfer[3] and xfer[1] <= e[1] and e[2] <= xfer[2]
                       for e in puts)
            s = ops_stats()
            return puts, s["h2d_ops"] - before, s["h2d_bytes_per_commit"]

        v = pl.AsyncBatchVerifier(depth=1)
        try:
            puts_w, ops_w, bytes_w = launch(v, warm)
            puts_c, ops_c, bytes_c = launch(v, cold)
        finally:
            v.close()
            epoch_cache.reset()
            backend.engine.cache_clear()
        assert (len(puts_w), ops_w) == (1, 1)
        # idx 4 + r 32 + scal 2m*32/m = 64 + sok 4 bytes a signature
        assert puts_w[0][4]["bytes"] == bytes_w == 104 * bucket
        assert (len(puts_c), ops_c) == (1, 1)
        # pub 32 + r 32 + scal 2m*32/m = 64 + sok 4 bytes a signature: the
        # slot-major a_t, r_t, scal_t and sok_t it used to ship four times
        assert puts_c[0][4]["bytes"] == bytes_c == 132 * bucket == g * (
            2 * m * 32 + 2 * m * 32 + 4 * m)
