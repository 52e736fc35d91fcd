"""libs/devcheck runtime invariant checkers (ISSUE 8).

Two layers, same pattern as the other _isolated suites:

- unit tests of the checkers themselves (lock-order cycle detection,
  write-after-resolve canary, device ownership, zero-cost-off) run IN
  PROCESS — stdlib + numpy only, no jax, no crypto wheel;
- the injected-bug integration (TM_TPU_INJECT_LINTBUG=alias|owner driven
  through a REAL AsyncBatchVerifier with a mock kernel) needs the ops
  package, which imports the crypto seam — on containers without the
  wheel it re-runs in a purepy subprocess.

The injected-bug tests are the runtime half of the seeded-regression
requirement: re-introduce the PR-7 readback aliasing / a resolver-thread
device touch and assert the matching checker FIRES — proving the canary
and the ownership assertion actually guard their bug class.
"""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from tendermint_tpu.libs import devcheck


@pytest.fixture(autouse=True)
def _fresh_devcheck():
    was_on = devcheck.enabled()
    devcheck.enable(reset=True)
    yield
    devcheck.reset_state()
    if not was_on:
        devcheck.disable()


# ---------------------------------------------------------------------------
# units: lock-order cycle detector


class TestLockOrder:
    def test_consistent_order_is_clean(self):
        a, b = devcheck.DevLock("A"), devcheck.DevLock("B")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert not devcheck.violations()

    def test_cycle_raises_and_records(self):
        a, b = devcheck.DevLock("A"), devcheck.DevLock("B")
        with a:
            with b:
                pass
        with pytest.raises(devcheck.DevcheckViolation) as ei:
            with b:
                with a:
                    pass
        assert "cycle" in str(ei.value)
        assert devcheck.violations()[0]["kind"] == "lock-order"

    def test_three_lock_cycle(self):
        a, b, c = (devcheck.DevLock(n) for n in "ABC")
        with a:
            with b:
                pass
        with b:
            with c:
                pass
        with pytest.raises(devcheck.DevcheckViolation):
            with c:
                with a:
                    pass

    def test_cycle_violation_releases_the_underlying_lock(self):
        # review fix: a raised violation must not leave the raw lock held
        # (the `with` never enters, so __exit__ never releases) — the
        # diagnostic must not CREATE the deadlock it reports
        a, b = devcheck.DevLock("A"), devcheck.DevLock("B")
        with a:
            with b:
                pass
        with pytest.raises(devcheck.DevcheckViolation):
            with b:
                with a:
                    pass
        assert a.acquire(blocking=False), "lock leaked by the violation"
        a.release()

    def test_bare_acquire_cycle_keeps_lock_held_for_caller(self):
        # contract (review fix): a BARE acquire() that raises the cycle
        # violation leaves the lock HELD — Condition._acquire_restore
        # (cv.wait's re-acquire) depends on owning the lock afterwards so
        # the enclosing `with cv:` __exit__ can release it
        a, b = devcheck.DevLock("A"), devcheck.DevLock("B")
        with a:
            with b:
                pass
        assert b.acquire()
        with pytest.raises(devcheck.DevcheckViolation):
            a.acquire()
        probe = []
        t = threading.Thread(
            target=lambda: probe.append(a._l.acquire(blocking=False)),
            daemon=True,
        )
        t.start()
        t.join(timeout=5)
        assert probe == [False], "bare-acquire violation must keep the lock held"
        a.release()
        b.release()

    def test_contested_inversion_raises_instead_of_hanging(self):
        # review fix: edges record at INTENT (before the blocking
        # acquire, serialized under the devcheck mutex), so a first-
        # contact AB/BA deadlock raises on one thread instead of wedging
        # both with no diagnostic
        a, b = devcheck.DevLock("A"), devcheck.DevLock("B")
        barrier = threading.Barrier(2, timeout=5)
        errs = []

        def one(first, second):
            with first:
                barrier.wait()
                try:
                    with second:
                        pass
                except devcheck.DevcheckViolation as e:
                    errs.append(e)

        t1 = threading.Thread(target=one, args=(a, b), daemon=True)
        t2 = threading.Thread(target=one, args=(b, a), daemon=True)
        t1.start()
        t2.start()
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert not t1.is_alive() and not t2.is_alive(), "deadlock wedged"
        assert errs, "the inversion must be reported"
        assert devcheck.violations()[0]["kind"] == "lock-order"

    def test_same_name_nesting_is_not_a_self_cycle(self):
        # two INSTANCES of the same order class (e.g. two epoch entries)
        e1, e2 = devcheck.DevLock("epoch.entry"), devcheck.DevLock("epoch.entry")
        with e1:
            with e2:
                pass
        assert not devcheck.violations()

    def test_rlock_reentry_records_no_edge(self):
        r = devcheck.DevLock("R", reentrant=True)
        with r:
            with r:
                pass
        assert not devcheck.violations()
        assert devcheck.report()["lock_order_edges"] == 0

    def test_rlock_release_pairs_with_outermost_acquire(self):
        # review fix: the inner re-entry release must not pop the outer
        # stack entry — R is still held when X is taken, so R->X records
        r = devcheck.DevLock("R", reentrant=True)
        x = devcheck.DevLock("X")
        with r:
            with r:
                pass
            with x:
                pass
        assert devcheck.report()["lock_order_edges"] == 1

    def test_disable_between_acquire_and_release_pops_stack(self):
        # review fix: release pops unconditionally — disabling devcheck
        # mid-flight must not leave a stale held entry that manufactures
        # phantom order edges (and false cycles) for later tests
        a = devcheck.DevLock("A")
        a.acquire()
        devcheck.disable()
        a.release()
        devcheck.enable()
        b = devcheck.DevLock("B")
        with b:
            pass
        assert devcheck.report()["lock_order_edges"] == 0
        with b:
            with devcheck.DevLock("A"):
                pass  # B->A must be legal: no phantom A->B exists
        assert not devcheck.violations()

    def test_condition_wrapping_devlock(self):
        lk = devcheck.DevLock("cv.lock")
        cv = threading.Condition(lk)
        hit = []

        def waiter():
            with cv:
                cv.wait(timeout=5)
                hit.append(True)

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        import time

        time.sleep(0.1)
        with cv:
            cv.notify()
        t.join(timeout=5)
        assert hit and not devcheck.violations()

    def test_disabled_lock_is_plain(self):
        devcheck.disable()
        try:
            lk = devcheck.lock("x")
            assert not isinstance(lk, devcheck.DevLock)
        finally:
            devcheck.enable()

    def test_enabled_lock_is_instrumented(self):
        assert isinstance(devcheck.lock("x"), devcheck.DevLock)
        assert isinstance(devcheck.rlock("x"), devcheck.DevLock)


# ---------------------------------------------------------------------------
# units: write-after-resolve canary


class TestCanary:
    def test_stable_bytes_pass(self):
        arr = np.arange(16, dtype=np.uint8)
        devcheck.canary_register(arr, tag="t")
        assert devcheck.canary_sweep("here") == 0
        assert not devcheck.violations()

    def test_mutation_is_detected_once(self):
        buf = np.arange(16, dtype=np.uint8)
        view = buf[:]
        assert not view.flags.owndata
        devcheck.canary_register(view, tag="aliased")
        buf[3] ^= 0xFF
        assert devcheck.canary_sweep("sweep1") == 1
        v = devcheck.violations()
        assert v and v[0]["kind"] == "write-after-resolve"
        # entry dropped after detection: no duplicate reports
        assert devcheck.canary_sweep("sweep2") == 0

    def test_ring_bound(self):
        for i in range(200):
            devcheck.canary_register(np.full(4, i, dtype=np.uint8))
        assert devcheck.canary_sweep("x") == 0
        assert devcheck.report()["counts"]["canary_registered"] == 200

    def test_on_slot_release_sweeps(self):
        buf = np.arange(8, dtype=np.uint8)
        devcheck.canary_register(buf[:], tag="slot")
        buf[0] = 99
        devcheck.on_slot_release(())
        assert devcheck.violations()

    def test_non_ndarray_register_is_noop(self):
        devcheck.canary_register("not-an-array")
        assert devcheck.canary_sweep("x") == 0


# ---------------------------------------------------------------------------
# units: device ownership


class TestDeviceOwnership:
    def test_no_owner_means_direct_use_is_legal(self):
        devcheck.note_device_touch("standalone")
        assert not devcheck.violations()

    def test_owner_thread_passes_others_raise(self):
        devcheck.claim_device("me")
        devcheck.note_device_touch("same-thread")  # owner: fine
        err = []

        def intruder():
            try:
                devcheck.note_device_touch("other-thread")
            except devcheck.DevcheckViolation as e:
                err.append(e)

        t = threading.Thread(target=intruder, daemon=True)
        t.start()
        t.join(timeout=5)
        assert err and devcheck.violations()[0]["kind"] == "device-ownership"

    def test_exempt_scope_passes(self):
        devcheck.claim_device("owner")
        ok = []

        def sanctioned():
            with devcheck.exempt():
                devcheck.note_device_touch("warmup")
            ok.append(True)

        t = threading.Thread(target=sanctioned, daemon=True)
        t.start()
        t.join(timeout=5)
        assert ok and not devcheck.violations()

    def test_zero_cost_off(self):
        devcheck.disable()
        try:
            devcheck.claim_device("x")
            devcheck.note_device_touch("y")
            devcheck.canary_register(np.zeros(4, dtype=np.uint8))
            assert devcheck.canary_sweep("z") == 0
            assert devcheck.report()["counts"]["device_touches"] == 0
        finally:
            devcheck.enable()

    def test_check_raises_with_context(self):
        devcheck._violate("test-kind", "test message")
        with pytest.raises(devcheck.DevcheckViolation) as ei:
            devcheck.check()
        assert "test-kind" in str(ei.value)

    def test_unclaim_device_retires_owner(self):
        # review fix: a closing verifier drops its dispatcher ident so
        # later standalone direct use stays legal and a recycled OS
        # thread ident cannot inherit the dead owner's pass
        devcheck.claim_device("me")
        devcheck.unclaim_device({threading.get_ident()})
        devcheck.note_device_touch("after-close")  # no owners: legal
        assert not devcheck.violations()

    def test_inject_seams_require_devcheck_armed(self, monkeypatch):
        # review fix: a stale TM_TPU_INJECT_LINTBUG export with the
        # checkers OFF must stay inert (the seams corrupt verdicts)
        monkeypatch.setenv("TM_TPU_INJECT_LINTBUG", "alias")
        assert devcheck.inject_lintbug("alias")
        devcheck.disable()
        try:
            assert not devcheck.inject_lintbug("alias")
        finally:
            devcheck.enable()


# ---------------------------------------------------------------------------
# injected-bug integration: the REAL pipeline must trip the checkers

try:
    from tendermint_tpu.ops import pipeline as _pl

    _HAVE_OPS = True
except ModuleNotFoundError:
    # no crypto wheel: the purepy subprocess runner below covers these
    _HAVE_OPS = False


class _FakeDev:
    """Mock device result: materializes to a given (owned) verdict row,
    honoring the async-copy protocol so _Readback works unchanged."""

    def __init__(self, a):
        self._a = a

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None):
        return self._a if dtype is None else self._a.astype(dtype)


def _fake_prepare_factory():
    """Per-batch mock kernels — no XLA compile. Batch verdicts DIFFER
    run to run (lane 0 flips on odd batches) so a recycled-scratch alias
    produces a byte delta the canary can see."""
    counter = {"n": 0}

    def fake_prepare(entries):
        n = len(entries)
        i = counter["n"]
        counter["n"] += 1
        verdict = np.ones(n, dtype=np.int32)
        if i % 2:
            verdict[0] = 0
        args = (np.arange(16, dtype=np.uint8),)

        def kern(*dev_args):
            return _FakeDev(verdict)

        return kern, args, None, n

    return fake_prepare


def _mk_entries(n):
    return [(bytes(32), b"m%d" % i, bytes(64)) for i in range(n)]


@pytest.mark.skipif(not _HAVE_OPS, reason="ops package needs the crypto "
                    "wheel (runs via the purepy subprocess below)")
class TestInjectedLintbugs:
    @pytest.fixture(autouse=True)
    def _mock_kernels(self, monkeypatch):
        monkeypatch.setattr(
            _pl.AsyncBatchVerifier, "_prepare",
            staticmethod(_fake_prepare_factory()),
        )
        yield

    def _run_two_batches(self):
        v = _pl.AsyncBatchVerifier(depth=2)
        try:
            r1 = np.array(v.submit(_mk_entries(8)).result(timeout=30),
                          copy=True)
            r2 = np.array(v.submit(_mk_entries(8)).result(timeout=30),
                          copy=True)
        finally:
            v.close()
        return r1, r2

    def test_clean_pipeline_has_no_violations(self):
        self._run_two_batches()
        assert not devcheck.violations()
        counts = devcheck.report()["counts"]
        assert counts["device_touches"] >= 1       # transfers asserted
        assert counts["canary_registered"] >= 1   # verdicts canaried
        assert counts["lock_acquires"] > 0        # locks instrumented

    def test_alias_injection_trips_canary(self, monkeypatch):
        """TM_TPU_INJECT_LINTBUG=alias re-introduces PR-7: verdicts are
        delivered as views of a recycled scratch buffer; the NEXT batch's
        resolve overwrites it and the canary must catch the mutation."""
        monkeypatch.setenv("TM_TPU_INJECT_LINTBUG", "alias")
        self._run_two_batches()
        kinds = [x["kind"] for x in devcheck.violations()]
        assert "write-after-resolve" in kinds, kinds

    def test_owner_injection_trips_device_assertion(self, monkeypatch):
        """TM_TPU_INJECT_LINTBUG=owner makes the RESOLVER thread issue a
        device transfer — the device-ownership assertion must fire."""
        monkeypatch.setenv("TM_TPU_INJECT_LINTBUG", "owner")
        self._run_two_batches()
        kinds = [x["kind"] for x in devcheck.violations()]
        assert "device-ownership" in kinds, kinds


def test_injected_lintbugs_under_purepy_fallback():
    """Containers without the crypto wheel run the integration layer in a
    subprocess with TM_TPU_PUREPY_CRYPTO=1 (which must not leak here)."""
    if _HAVE_OPS:
        pytest.skip("ops importable; TestInjectedLintbugs ran directly")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_devcheck.py"),
            "-q", "-k", "InjectedLintbugs", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=env,
        cwd=os.path.dirname(here),
        timeout=60,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated injected-lintbug run failed:\n{tail}"
