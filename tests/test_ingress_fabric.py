"""Ingress-fabric unit suite (ISSUE 17): controller + engine mechanics.

Everything here runs against fake verifiers — no jax, no crypto wheel,
no pipeline — so the fabric's window policy, knob resolution, QoS
routing, poisoned-window isolation and stepped semantics are pinned in
a plain interpreter. The ADAPTIVE controller's three behaviors are each
pinned explicitly:

* deepen-under-flood — FULL flushes at target grow batch ×2 / window
  ×1.5 up to 8× the base;
* shrink-when-idle — sparse timer flushes halve both back down to the
  base batch / quarter window;
* deadline-aware flush — the effective window is clamped to
  budget − 2×(service EWMA) so flush + device service fit the lane's
  p99 budget.

Cross-lane parity rides along: all four production lane names register
on one private engine and expose the same stats contract.
"""

import importlib.util
import os
import sys
import threading
import time
import warnings
from concurrent.futures import Future

import pytest

if importlib.util.find_spec("cryptography") is None and not os.environ.get(
    "TM_TPU_PUREPY_CRYPTO"
):
    # the fabric itself is crypto-free, but importing tendermint_tpu.ops
    # pulls the crypto chain; the isolated runner
    # (test_ingress_fabric_isolated.py) re-runs this suite under
    # TM_TPU_PUREPY_CRYPTO=1 so tier-1 keeps the coverage
    pytest.skip(
        "cryptography wheel absent; runs via test_ingress_fabric_isolated",
        allow_module_level=True,
    )

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tendermint_tpu.ops import ingress  # noqa: E402
from tendermint_tpu.ops.entry_block import EntryBlock  # noqa: E402


def wait_until(cond, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.002)
    raise AssertionError(f"timed out waiting for {msg}")


def entry(i: int):
    return (bytes([i % 256]) * 32, b"msg-%d" % i, bytes([i % 256]) * 64)


class FakeVerifier:
    """Records submissions; resolves futures per `mode`:
    - "ok": every signature verifies
    - "manual": caller resolves via self.futures
    - "poison_first": first submit raises DispatchError-shaped failure
      post-submit, later submits verify
    - "raise": submit() itself raises (pre-submit failure)
    """

    def __init__(self, mode="ok"):
        self.mode = mode
        self.calls = []          # (n, flow, priority)
        self.futures = []
        self._n = 0

    def submit(self, block, flow=None, priority=None):
        self._n += 1
        if self.mode == "raise":
            raise RuntimeError("verifier rejected submit")
        self.calls.append((len(block), flow, priority))
        fut = Future()
        self.futures.append(fut)
        if self.mode == "ok":
            fut.set_result([True] * len(block))
        elif self.mode == "poison_first" and self._n == 1:
            fut.set_exception(RuntimeError("DispatchError: lost slot"))
        elif self.mode == "poison_first":
            fut.set_result([True] * len(block))
        return fut


class NarrowVerifier:
    """The duck-typed test-double shape the light suite uses: no
    priority parameter at all."""

    def __init__(self):
        self.calls = 0

    def submit(self, block, flow=None):
        self.calls += 1
        fut = Future()
        fut.set_result([True] * len(block))
        return fut


class Sink:
    """Collects deliver() callbacks."""

    def __init__(self):
        self.windows = []        # (items, verdicts, err)
        self.mtx = threading.Lock()

    def __call__(self, items, verdicts, err):
        for i, it in enumerate(items):      # deliver() owns item futures
            if it.future is not None:
                if err is not None:
                    it.future.set_exception(err)
                else:
                    it.future.set_result(verdicts[i])
        with self.mtx:
            self.windows.append(([it.item for it in items], verdicts, err))

    def count(self):
        with self.mtx:
            return sum(len(w[0]) for w in self.windows)


def make_lane(engine, sink, verifier=None, **kw):
    defaults = dict(
        name="test", priority=ingress.PRIORITY_INGRESS, batch=4,
        window_ms=60_000.0, verifier=verifier or FakeVerifier(),
        entries_fn=lambda i: entry(i), deliver=sink,
        host_fn=lambda items: [True] * len(items),
    )
    defaults.update(kw)
    return engine.register(ingress.LaneSpec(**defaults))


@pytest.fixture
def engine():
    eng = ingress.IngressEngine()
    yield eng
    eng.close(timeout=2.0)


# ---------------------------------------------------------------------------
# the adaptive controller


class TestAdaptiveWindow:
    def test_deepen_under_flood(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=2.0)
        for _ in range(16):
            c.on_flush(c.batch_target(), ingress.CAUSE_FULL)
        assert c.batch_target() == 64 * 8          # capped at 8x base
        assert c.window_ms == pytest.approx(2.0 * 8)
        assert c.grows >= 3                        # 64->128->256->512

    def test_partial_full_does_not_grow(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=2.0)
        c.on_flush(10, ingress.CAUSE_FULL)
        assert c.batch_target() == 64 and c.grows == 0

    def test_shrink_when_idle(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=2.0)
        for _ in range(8):
            c.on_flush(c.batch_target(), ingress.CAUSE_FULL)
        assert c.batch_target() > 64
        for _ in range(32):
            c.on_flush(1, ingress.CAUSE_TIMER)
        assert c.batch_target() == 64              # back to base
        assert c.window_ms == pytest.approx(2.0 / 4)   # quarter window
        assert c.shrinks >= 3

    def test_busy_timer_flush_does_not_shrink(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=2.0)
        c.on_flush(40, ingress.CAUSE_TIMER)        # > 1/4 of target
        assert c.shrinks == 0 and c.window_ms == 2.0

    def test_manual_stepped_close_never_adapt(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=2.0)
        for cause in (ingress.CAUSE_MANUAL, ingress.CAUSE_STEPPED,
                      ingress.CAUSE_CLOSE):
            c.on_flush(10_000, cause)
            c.on_flush(1, cause)
        assert c.grows == 0 and c.shrinks == 0
        assert c.batch_target() == 64 and c.window_ms == 2.0

    def test_frozen_when_not_adaptive(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=2.0, adaptive=False)
        c.on_flush(64, ingress.CAUSE_FULL)
        c.on_flush(1, ingress.CAUSE_TIMER)
        assert c.batch_target() == 64 and c.window_ms == 2.0

    def test_deadline_bounds_effective_window(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=4.0, budget_ms=5.0)
        assert c.effective_window_ms() == pytest.approx(4.0)
        assert not c.deadline_bound
        c.note_service(2.0)                        # EWMA seeds at 2ms
        # budget 5 - SAFETY(2) * 2ms = 1ms < base window
        assert c.effective_window_ms() == pytest.approx(1.0)
        assert c.deadline_bound

    def test_deadline_floor_is_min_window(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=4.0, budget_ms=5.0)
        c.note_service(100.0)                      # budget hopeless
        assert c.effective_window_ms() == pytest.approx(4.0 / 4)

    def test_frozen_lane_keeps_deadline_bound(self):
        """SLO awareness is not optional — only adaptivity is."""
        c = ingress.AdaptiveWindow(batch=64, window_ms=4.0, budget_ms=5.0,
                                   adaptive=False)
        c.note_service(2.0)
        assert c.effective_window_ms() == pytest.approx(1.0)

    def test_service_ewma(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=4.0)
        c.note_service(10.0)
        assert c.service_ewma_ms == pytest.approx(10.0)
        c.note_service(0.0)
        assert c.service_ewma_ms == pytest.approx(10.0 * 0.7)
        c.note_service(-1.0)                       # ignored
        assert c.service_ewma_ms == pytest.approx(10.0 * 0.7)

    def test_deadline_flush_counter(self):
        c = ingress.AdaptiveWindow(batch=64, window_ms=4.0, budget_ms=5.0)
        c.on_flush(1, ingress.CAUSE_DEADLINE)
        assert c.deadline_flushes == 1
        # one idle flush is within hysteresis patience — no shrink yet
        assert c.shrinks == 0 and c.window_ms == pytest.approx(4.0)
        # sustained idle deadline flushes DO shrink: deadline pressure
        # with near-empty windows means the window is too deep
        c.on_flush(1, ingress.CAUSE_DEADLINE)
        assert c.deadline_flushes == 2
        assert c.shrinks == 1 and c.window_ms == pytest.approx(2.0)

    def test_shrink_hysteresis_survives_jitter(self):
        """A lone jitter-thinned timer flush mid-flood must not collapse
        the window the next burst needs — the full flush resets the
        idle streak before it reaches SHRINK_PATIENCE."""
        c = ingress.AdaptiveWindow(batch=64, window_ms=2.0)
        c.on_flush(64, ingress.CAUSE_FULL)         # grow to 128
        grown = c.batch_target()
        assert grown > 64
        for _ in range(8):
            c.on_flush(1, ingress.CAUSE_TIMER)     # jitter: streak -> 1
            c.on_flush(c.batch_target(), ingress.CAUSE_FULL)  # flood resumes
        assert c.shrinks == 0
        assert c.batch_target() >= grown
        # a busy (non-idle) timer flush also resets the streak
        c2 = ingress.AdaptiveWindow(batch=64, window_ms=2.0)
        c2.on_flush(64, ingress.CAUSE_FULL)
        c2.on_flush(1, ingress.CAUSE_TIMER)
        c2.on_flush(40, ingress.CAUSE_TIMER)       # > 1/4 target: busy
        c2.on_flush(1, ingress.CAUSE_TIMER)
        assert c2.shrinks == 0


# ---------------------------------------------------------------------------
# knob resolution


class TestResolveLaneConfig:
    def setup_method(self):
        ingress._warned_legacy.clear()

    def test_lane_defaults(self, monkeypatch):
        for k in list(os.environ):
            if k.startswith("TM_TPU_INGRESS"):
                monkeypatch.delenv(k)
        cfg = ingress.resolve_lane_config("votes")
        assert (cfg.batch, cfg.window_ms) == (128, 2.0)
        assert cfg.budget_ms == 5.0                # the paper's hot-path p99
        assert cfg.adaptive

    def test_explicit_args_pin_determinism(self):
        cfg = ingress.resolve_lane_config("votes", batch=32, window_ms=1.0)
        assert (cfg.batch, cfg.window_ms) == (32, 1.0)
        assert not cfg.adaptive
        # default SLO budget only engages with adaptivity: a pinned
        # caller gets EXACTLY the flush timing it pinned
        assert cfg.budget_ms is None

    def test_lane_keyed_env(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_INGRESS_VOTES_BATCH", "99")
        monkeypatch.setenv("TM_TPU_INGRESS_VOTES_WINDOW_MS", "7.5")
        cfg = ingress.resolve_lane_config("votes")
        assert (cfg.batch, cfg.window_ms) == (99, 7.5)
        assert cfg.adaptive                        # env knobs stay adaptive

    def test_legacy_env_honored_with_warning(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_VOTE_BATCH", "48")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cfg = ingress.resolve_lane_config(
                "votes", legacy_batch="TM_TPU_VOTE_BATCH")
        assert cfg.batch == 48
        assert any(issubclass(x.category, DeprecationWarning) for x in w)

    def test_new_name_wins_over_legacy(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_VOTE_BATCH", "48")
        monkeypatch.setenv("TM_TPU_INGRESS_VOTES_BATCH", "96")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cfg = ingress.resolve_lane_config(
                "votes", legacy_batch="TM_TPU_VOTE_BATCH")
        assert cfg.batch == 96
        assert not w                               # no deprecation fired

    def test_adaptive_env_overrides(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_INGRESS_VOTES_ADAPTIVE", "1")
        cfg = ingress.resolve_lane_config("votes", batch=32, window_ms=1.0)
        assert cfg.adaptive
        monkeypatch.setenv("TM_TPU_INGRESS_VOTES_ADAPTIVE", "0")
        cfg = ingress.resolve_lane_config("votes")
        assert not cfg.adaptive

    def test_global_adaptive_env(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_INGRESS_ADAPTIVE", "1")
        cfg = ingress.resolve_lane_config("votes", batch=32, window_ms=1.0)
        assert cfg.adaptive

    def test_budget_env_always_applies(self, monkeypatch):
        monkeypatch.setenv("TM_TPU_INGRESS_VOTES_BUDGET_MS", "7")
        cfg = ingress.resolve_lane_config("votes", batch=32, window_ms=1.0)
        assert cfg.budget_ms == 7.0                # even though pinned


# ---------------------------------------------------------------------------
# QoS tiers mirror the pipeline's


class TestPriorityTiers:
    def test_constants_match_pipeline(self):
        pl = pytest.importorskip("tendermint_tpu.ops.pipeline")
        assert ingress.PRIORITY_CONSENSUS == pl.PRIORITY_CONSENSUS
        assert ingress.PRIORITY_REPLAY == pl.PRIORITY_REPLAY
        assert ingress.PRIORITY_INGRESS == pl.PRIORITY_INGRESS


# ---------------------------------------------------------------------------
# engine mechanics (fake verifier — no pipeline, no jax)


class TestEngineMechanics:
    def test_full_flush_delivers_at_lane_priority(self, engine):
        sink, v = Sink(), FakeVerifier()
        lane = make_lane(engine, sink, verifier=v)
        futs = [lane.submit(i, want_future=True) for i in range(4)]
        wait_until(lambda: sink.count() == 4, msg="full-window delivery")
        assert v.calls == [(4, None, ingress.PRIORITY_INGRESS)]
        assert all(f.done() for f in futs)
        st = lane.stats()
        assert st["batches"] == 1 and st["sigs"] == 4

    def test_consensus_tier_omits_priority_kwarg(self, engine):
        sink, v = Sink(), NarrowVerifier()
        lane = make_lane(engine, sink, verifier=v,
                         priority=ingress.PRIORITY_CONSENSUS)
        block = EntryBlock.from_entries([entry(i) for i in range(3)])
        fut = lane.submit_block(block)
        assert fut.result(timeout=1) == [True, True, True]
        assert v.calls == 1
        assert lane.stats()["blocks"] == 1 and lane.stats()["sigs"] == 3

    def test_timer_flush(self, engine):
        sink = Sink()
        lane = make_lane(engine, sink, window_ms=10.0)
        lane.submit(1)
        wait_until(lambda: sink.count() == 1, msg="timer flush")
        assert lane.stats()["queue_depth"] == 0

    def test_flush_now_and_stale_force(self, engine):
        sink = Sink()
        lane = make_lane(engine, sink)               # 60s window
        lane.submit(1)
        lane.flush_now()
        wait_until(lambda: sink.count() == 1, msg="manual flush")
        # flush_now on an empty lane leaves the force latched: the NEXT
        # submit flushes immediately (the pre-fabric full-event shape)
        lane.flush_now()
        lane.submit(2)
        wait_until(lambda: sink.count() == 2, msg="stale-force flush")

    def test_window_dedup(self, engine):
        sink = Sink()
        lane = make_lane(engine, sink, batch=64)
        assert lane.submit(1, dedup_key="a") is None   # no future asked
        assert lane.submit(1, dedup_key="a") is None   # dropped
        assert lane.stats()["window_dups"] == 1
        lane.flush_now()
        wait_until(lambda: sink.count() == 1, msg="flush")
        lane.submit(1, dedup_key="a")                  # re-enters post-flush
        lane.flush_now()
        wait_until(lambda: sink.count() == 2, msg="re-entry")
        assert lane.stats()["window_dups"] == 1

    def test_poisoned_window_is_isolated(self, engine):
        sink = Sink()
        lane = make_lane(engine, sink, verifier=FakeVerifier("poison_first"))
        for i in range(4):
            lane.submit(i)
        wait_until(lambda: sink.count() == 4, msg="poisoned window")
        for i in range(4, 8):
            lane.submit(i)
        wait_until(lambda: sink.count() == 8, msg="clean window")
        with sink.mtx:
            (w1, w2) = sink.windows
        assert w1[1] is None and isinstance(w1[2], RuntimeError)
        assert w2[1] == [True] * 4 and w2[2] is None
        assert lane.stats()["dispatch_errors"] == 1

    def test_presubmit_error_to_host(self, engine):
        """submit_error_to_host lanes (votes) host-verify the window a
        pre-submit failure orphaned — no dispatch_errors, verdicts real."""
        sink = Sink()
        lane = make_lane(engine, sink, verifier=FakeVerifier("raise"),
                         submit_error_to_host=True)
        for i in range(4):
            lane.submit(i)
        wait_until(lambda: sink.count() == 4, msg="host fallback")
        with sink.mtx:
            (items, verdicts, err) = sink.windows[0]
        assert verdicts == [True] * 4 and err is None
        st = lane.stats()
        assert st["sync_fallbacks"] >= 1 and st["dispatch_errors"] == 0

    def test_presubmit_error_to_futures(self, engine):
        """Lanes without the host contract (mempool) deliver the error
        to exactly that window's futures."""
        sink = Sink()
        lane = make_lane(engine, sink, verifier=FakeVerifier("raise"))
        futs = [lane.submit(i, want_future=False) for i in range(4)]
        del futs
        wait_until(lambda: sink.count() == 4, msg="error delivery")
        with sink.mtx:
            (_, verdicts, err) = sink.windows[0]
        assert verdicts is None and isinstance(err, RuntimeError)
        assert lane.stats()["dispatch_errors"] == 0    # pre-submit, not poison

    def test_device_threshold_host_fallback(self, engine, monkeypatch):
        monkeypatch.delenv("TM_TPU_FORCE_DEVICE", raising=False)
        sink = Sink()
        v = FakeVerifier()
        lane = make_lane(engine, sink, verifier=v, device_threshold=16)
        for i in range(4):
            lane.submit(i)
        lane.flush_now()
        wait_until(lambda: sink.count() == 4, msg="sub-threshold host")
        assert v.calls == []                       # never reached the device
        assert lane.stats()["sync_fallbacks"] == 1

    def test_route_fn_splits_host_lane(self, engine):
        sink, v = Sink(), FakeVerifier()
        host_seen = []

        def host_fn(items):
            host_seen.extend(items)
            return [True] * len(items)

        lane = make_lane(engine, sink, verifier=v,
                         route_fn=lambda i: i % 2 == 0, host_fn=host_fn)
        for i in range(8):
            lane.submit(i)
        lane.flush_now()
        wait_until(lambda: sink.count() == 8, msg="split delivery")
        assert sorted(host_seen) == [1, 3, 5, 7]
        # how many launches carry the 4 routed sigs is the scheduler's
        # timing (a full batch of 4 wakes it mid-loop on a loaded host)
        assert sum(n for n, _flow, _pri in v.calls) == 4
        st = lane.stats()
        assert st["host_lane_sigs"] == 4
        assert st["sync_fallbacks"] == 0           # routed, not fallen back

    def test_stepped_lane_never_scheduler_flushed(self, engine):
        sink = Sink()
        lane = make_lane(engine, sink, stepped=True, window_ms=0.0)
        lane.submit(1)
        lane.submit(2)
        time.sleep(0.15)                           # scheduler ticks ~20x
        assert sink.count() == 0                   # nothing moved
        assert lane.flush_pending() is True        # the ONLY flush point
        assert sink.count() == 2                   # inline, on this thread
        assert lane.flush_pending() is False
        assert lane.stats()["sync_fallbacks"] == 1

    def test_completer_thread_delivery(self, engine):
        sink = Sink()
        lane = make_lane(engine, sink, use_completer=True)
        threads = []
        orig = sink.__call__

        def recording(items, verdicts, err):
            threads.append(threading.current_thread().name)
            orig(items, verdicts, err)

        lane.spec.deliver = recording
        for i in range(4):
            lane.submit(i)
        wait_until(lambda: sink.count() == 4, msg="completer delivery")
        assert threads == ["ingress-fabric-complete"]
        wait_until(lambda: lane._inflight == 0, msg="inflight drain")

    def test_close_drains_and_rejects(self, engine):
        sink = Sink()
        lane = make_lane(engine, sink, closed_msg="lane shut")
        lane.submit(1)
        lane.close(timeout=2.0)
        assert sink.count() == 1                   # final drain flushed it
        with pytest.raises(RuntimeError, match="lane shut"):
            lane.submit(2)
        assert lane not in engine.lanes()

    def test_keyed_windows_flush_separately(self, engine):
        """full_by_window (votes): the size trigger counts the keyed
        window, and each keyed window becomes its own submission."""
        sink, v = Sink(), FakeVerifier()
        lane = make_lane(engine, sink, verifier=v, batch=4,
                         full_by_window=True)
        for i in range(3):
            lane.submit(i, key="h10")
        for i in range(3):
            lane.submit(10 + i, key="h11")         # 6 total, no window full
        time.sleep(0.05)
        assert sink.count() == 0
        lane.submit(3, key="h10")                  # h10 hits 4 -> flush all
        wait_until(lambda: sink.count() == 7, msg="keyed flush")
        assert sorted(c[0] for c in v.calls) == [3, 4]
        assert lane.stats()["batches"] == 2


# ---------------------------------------------------------------------------
# the replay range fuse


class TestBlockFuser:
    def test_packs_to_cap_and_reports_spans(self, engine):
        sink, v = Sink(), FakeVerifier()
        lane = make_lane(engine, sink, verifier=v,
                         priority=ingress.PRIORITY_REPLAY)
        chunks = []
        fuser = ingress.BlockFuser(lane, cap=10,
                                   on_chunk=lambda f, p: chunks.append(p),
                                   flow=42)
        for h in range(3):                         # 4 + 4 + 4 sigs, cap 10
            fuser.add(h, EntryBlock.from_entries(
                [entry(4 * h + i) for i in range(4)]))
        fuser.flush()
        assert [c[0] for c in v.calls] == [8, 4]   # fused pair + tail
        assert all(c[1] == 42 for c in v.calls)
        assert all(c[2] == ingress.PRIORITY_REPLAY for c in v.calls)
        assert chunks == [[(0, 0, 4), (1, 4, 4)], [(2, 0, 4)]]
        assert lane.stats()["blocks"] == 2
        assert lane.stats()["sigs"] == 12

    def test_flush_on_empty_is_noop(self, engine):
        sink, v = Sink(), FakeVerifier()
        lane = make_lane(engine, sink, verifier=v)
        fuser = ingress.BlockFuser(lane, cap=10, on_chunk=lambda f, p: None)
        fuser.flush()
        assert v.calls == []


# ---------------------------------------------------------------------------
# cross-lane parity: the four production lanes share one stats contract


class TestCrossLaneParity:
    LANES = ("mempool", "votes", "light", "replay")

    def test_four_lanes_one_engine_one_contract(self, engine):
        sinks = {}
        for name in self.LANES:
            cfg = ingress.LANE_DEFAULTS[name]
            sinks[name] = Sink()
            make_lane(engine, sinks[name], name=name,
                      batch=int(cfg["batch"]), window_ms=0.0,
                      stepped=name in ("light", "replay"))
        assert sorted(engine.stats()) == sorted(self.LANES)
        keys = None
        for name, st in engine.stats().items():
            if keys is None:
                keys = set(st)
            assert set(st) == keys, f"{name} diverges from the contract"
        for k in ("queue_depth", "batches", "sigs", "sync_fallbacks",
                  "dispatch_errors", "batch_wait_ms_avg", "max_batch",
                  "window_ms", "window_grows", "window_shrinks",
                  "deadline_flushes", "adaptive", "stepped"):
            assert k in keys

    def test_one_scheduler_for_all_lanes(self, engine):
        """The point of the fabric: N lanes, ONE flush thread."""
        sinks = [Sink() for _ in range(4)]
        lanes = [make_lane(engine, s, name=f"lane{i}", window_ms=5.0)
                 for i, s in enumerate(sinks)]
        before = {t.name for t in threading.enumerate()}
        assert sum("ingress-fabric-flush" in n for n in before) == 1
        for lane in lanes:
            lane.submit(1)
        for s in sinks:
            wait_until(lambda s=s: s.count() == 1, msg="per-lane flush")
        after = {t.name for t in threading.enumerate()}
        assert sum("ingress-fabric-flush" in n for n in after) == 1
