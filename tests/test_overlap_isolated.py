"""Tier-1 face of the overlapped device (ISSUE 7).

Two layers, same pattern as test_epoch_cache_isolated.py:

- crypto-free unit tests of the device buffer pool and the windowed-ratio
  accounting (ops/device_pool.py) run IN PROCESS — no cryptography wheel,
  no jax, no kernel compiles;
- the signature-level tests (tests/test_overlap.py) and the
  `tools/prep_bench.py --overlap` span-order/pool-reuse gate run in
  SUBPROCESSES with TM_TPU_PUREPY_CRYPTO=1, which must never leak into
  the main pytest process.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

try:
    from tendermint_tpu.ops import device_pool as dp
except ModuleNotFoundError:
    # The ops package __init__ wires the crypto.batch seam, which needs
    # the cryptography wheel this container lacks. device_pool itself is
    # stdlib+numpy bookkeeping — load the module file directly so the
    # pool/ratio unit tests still run in the main tier-1 process. (The
    # lazy `_ops()` metrics hook is unusable in this mode; every test
    # below passes `_metrics=` explicitly.)
    import importlib.util

    _p = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tendermint_tpu", "ops", "device_pool.py",
    )
    _spec = importlib.util.spec_from_file_location(
        "_tm_tpu_device_pool_standalone", _p
    )
    dp = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(dp)


class _Gauge:
    def __init__(self):
        self.v = None

    def set(self, v):
        self.v = v


class _Counter:
    def __init__(self):
        self.n = 0

    def inc(self, v=1):
        self.n += v


class _Metrics:
    def __init__(self):
        self.buffer_pool_hits = _Counter()
        self.buffer_pool_misses = _Counter()


class TestDeviceBufferPool:
    def test_mint_then_recycle(self):
        pool = dp.DeviceBufferPool(depth=2)
        m = _Metrics()
        key = ((128, 32), "|u1")
        s1 = pool.acquire(key, _metrics=m)
        s2 = pool.acquire(key, _metrics=m)
        assert m.buffer_pool_misses.n == 2 and m.buffer_pool_hits.n == 0
        pool.release(s1)
        s3 = pool.acquire(key, _metrics=m)
        assert s3 is s1  # recycled
        assert m.buffer_pool_hits.n == 1
        pool.release(s2)
        pool.release(s3)
        st = pool.stats()
        assert st == {"depth": 2, "in_flight": 0, "layouts": 1,
                      "minted": 2, "free": 2}

    def test_distinct_layouts_do_not_share_slots(self):
        pool = dp.DeviceBufferPool(depth=1)
        m = _Metrics()
        a = pool.acquire(("a",), _metrics=m)
        b = pool.acquire(("b",), _metrics=m)  # different layout: no block
        assert a.key != b.key
        assert m.buffer_pool_misses.n == 2
        pool.release(a)
        pool.release(b)

    def test_acquire_blocks_at_depth_until_release(self):
        pool = dp.DeviceBufferPool(depth=1)
        m = _Metrics()
        held = pool.acquire(("k",), _metrics=m)
        got = []

        def worker():
            got.append(pool.acquire(("k",), _metrics=m))

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        time.sleep(0.15)
        assert not got  # blocked: depth reached
        pool.release(held)
        t.join(timeout=5)
        assert got and got[0] is held
        pool.release(got[0])
        assert pool.in_flight() == 0

    def test_acquire_abort(self):
        pool = dp.DeviceBufferPool(depth=1)
        m = _Metrics()
        held = pool.acquire(("k",), _metrics=m)
        stop = threading.Event()
        got = []

        def worker():
            got.append(pool.acquire(("k",), abort=stop.is_set, _metrics=m))

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        stop.set()
        t.join(timeout=5)
        assert got == [None]
        pool.release(held)

    def test_release_none_is_noop(self):
        pool = dp.DeviceBufferPool(depth=1)
        pool.release(None)
        assert pool.in_flight() == 0

    def test_layout_key_separates_shapes_and_dtypes(self):
        import numpy as np

        a = (np.zeros((128, 32), np.uint8), np.zeros((128,), np.int32))
        b = (np.zeros((128, 32), np.uint8), np.zeros((128,), np.int64))
        c = (np.zeros((1024, 32), np.uint8), np.zeros((1024,), np.int32))
        k = dp.layout_key
        assert k(128, a) != k(128, b) != k(1024, c)
        assert k(128, a) == k(128, tuple(x.copy() for x in a))
        # non-arrays (e.g. a pre-resolved jax table) don't key
        assert k(128, a + ("not-an-array",)) == k(128, a)


class TestWindowedRatio:
    def test_occupancy_mode(self):
        g = _Gauge()
        r = dp.WindowedRatio(g, window=60.0, wall=True)
        time.sleep(0.05)
        r.add(0.025)  # ~0.025 busy over >=0.05 elapsed
        assert g.v is not None and 0.0 < g.v <= 1.0

    def test_ratio_mode(self):
        g = _Gauge()
        r = dp.WindowedRatio(g, window=60.0, wall=False)
        r.add(1.0, 4.0)
        assert g.v == pytest.approx(0.25)
        r.add(1.0, 0.0)
        assert g.v == pytest.approx(0.5)

    def test_ratio_mode_idle_tick_decays_to_zero(self):
        # an empty ratio window (nothing transferred) must read 0, not
        # stick at the last busy value (den==0 skips normal publish)
        g = _Gauge()
        r = dp.WindowedRatio(g, window=0.05, wall=False)
        r.add(1.0, 2.0)
        assert g.v == pytest.approx(0.5)
        time.sleep(0.08)
        r.tick()  # flushes the residual pre-idle window, resets
        time.sleep(0.06)
        r.tick()  # empty window: decays to 0
        assert g.v == pytest.approx(0.0)

    def test_ratio_mode_add_after_idle_tick_starts_fresh_window(self):
        # the dispatcher tick()s through idle stretches, so a sample
        # landing after idle meets reset accumulators, not the stale
        # pre-idle window
        g = _Gauge()
        r = dp.WindowedRatio(g, window=0.05, wall=False)
        r.add(4.0, 4.0)  # pre-idle: ratio 1.0
        time.sleep(0.08)
        r.tick()         # idle heartbeat rolls the window
        r.add(0.0, 1.0)  # fresh window: 0 hidden of 1
        assert g.v == pytest.approx(0.0)

    def test_occupancy_boundary_sample_cannot_clamp_to_one(self):
        # a 30ms-busy sample arriving after ~0.1s idle closes the window
        # against the FULL elapsed time — the gauge must read the true
        # low occupancy, not 1.0 (crediting the sample to a zero-length
        # fresh window)
        g = _Gauge()
        r = dp.WindowedRatio(g, window=0.05, wall=True)
        time.sleep(0.1)
        r.add(0.03)
        assert g.v == pytest.approx(0.03 / 0.1, rel=0.5)
        r.add(0.005)  # next sample lands in the fresh window
        assert g.v < 1.0

    def test_window_rolls_and_idle_tick_decays(self):
        g = _Gauge()
        r = dp.WindowedRatio(g, window=0.05, wall=True)
        time.sleep(0.01)  # give the window a real measurement base
        r.add(0.04)
        first = g.v
        assert first is not None
        time.sleep(0.08)
        r.tick()  # idle: publish the (quiet) window, reset
        assert g.v <= first
        time.sleep(0.06)
        r.tick()
        assert g.v == pytest.approx(0.0, abs=1e-6)

    def test_ops_stats_exposes_overlap_fields(self):
        from tendermint_tpu.libs.metrics import ops_stats

        s = ops_stats()
        for key in ("transfer_overlap_ratio", "buffer_pool_hits",
                    "buffer_pool_misses"):
            assert key in s


def _purepy_env():
    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    env.pop("TM_TPU_DONATE", None)
    return env


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.time_limit(540)  # re-runs test_overlap.py: 198 s on a cold cache
def test_overlap_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_overlap runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    # TM_TPU_DEVCHECK=1 at process start (ISSUE 8): import-time lock
    # creation (metrics registries, epoch cache) is instrumented too, so
    # the overlap suite's autouse devcheck fixture sees the full lock-
    # order graph, not just locks created after enable()
    env = dict(_purepy_env(), TM_TPU_DEVCHECK="1")
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_overlap.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=env,
        cwd=_repo_root(),
        timeout=510,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_overlap run failed:\n{tail}"


def test_prep_bench_overlap_gate():
    """ISSUE 7 satellite: the --overlap span-order + pool-reuse gate,
    wired into tier-1 through the isolated runner."""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--overlap",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=90,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0, f"--overlap gate failed:\n{out}\n{err[-2000:]}"
