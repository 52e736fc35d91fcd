"""Tier-1 simnet coverage for containers without the `cryptography` wheel.

Two layers:
  1. Crypto-free unit tests of the simulation substrate (virtual clock,
     event ordering, link fault model, partitions, fault-schedule
     parsing) — these run in the MAIN pytest process: simnet's
     clock/transport layer imports without any signer.
  2. Subprocess runs of the signer-needing end-to-end suites
     (tests/test_simnet.py and tools/simnet_run.py --smoke) under
     TM_TPU_PUREPY_CRYPTO=1. The env flag must NOT be set in the main
     process — pytest collects all modules in one interpreter and the
     flag would unlock slow OpenSSL-dependent paths suite-wide (same
     pattern as tests/test_entry_block_isolated.py).
"""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from tendermint_tpu.simnet.clock import NodeClock, SimClock
from tendermint_tpu.simnet.faults import (
    Fault,
    parse_faults,
    rotation_schedule,
    smoke_schedule,
)
from tendermint_tpu.simnet.transport import Envelope, LinkConfig, SimNetwork, SimRouter

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class TestSimClock:
    def test_events_fire_in_time_order_with_stable_ties(self):
        clk = SimClock(seed=0, start=0.0)
        order = []
        clk.call_later(2.0, lambda: order.append("b"))
        clk.call_later(1.0, lambda: order.append("a"))
        clk.call_later(2.0, lambda: order.append("c"))  # same time as b: FIFO
        clk.call_later(3.0, lambda: order.append("d"))
        clk.run_until()
        assert order == ["a", "b", "c", "d"]
        assert clk.time() == 3.0

    def test_cancel_and_deadline(self):
        clk = SimClock(seed=0, start=0.0)
        fired = []
        t = clk.call_later(1.0, lambda: fired.append(1))
        clk.call_later(5.0, lambda: fired.append(2))
        t.cancel()
        clk.run_until(deadline=2.0)
        assert fired == []
        assert clk.time() == 2.0
        clk.run_until()
        assert fired == [2]

    def test_callbacks_can_schedule_more_events(self):
        clk = SimClock(seed=0, start=0.0)
        seen = []

        def tick(n):
            seen.append(n)
            if n < 3:
                clk.call_later(1.0, lambda: tick(n + 1))

        clk.call_later(1.0, lambda: tick(0))
        assert clk.run_until(predicate=lambda: len(seen) == 4)
        assert seen == [0, 1, 2, 3]
        assert clk.time() == 4.0

    def test_same_seed_same_rng_stream(self):
        a = [SimClock(seed=5).rng.random() for _ in range(8)]
        b = [SimClock(seed=5).rng.random() for _ in range(8)]
        c = [SimClock(seed=6).rng.random() for _ in range(8)]
        assert a == b
        assert a != c

    def test_node_clock_skew_shifts_reads_not_delays(self):
        clk = SimClock(seed=0, start=100.0)
        nc = NodeClock(clk, skew=2.5)
        assert nc.time() == 102.5
        fired = []
        nc.call_later(1.0, lambda: fired.append(clk.time()))
        clk.run_until()
        assert fired == [101.0]  # delay unaffected by skew


def _net(seed=0, link=None):
    clk = SimClock(seed=seed, start=0.0)
    net = SimNetwork(clk, default_link=link or LinkConfig(latency_s=0.01))
    inboxes = {}
    for nid in ("a", "b", "c"):
        SimRouter(net, nid)
        inboxes[nid] = []
        net.set_receiver(nid, lambda env, n=nid: inboxes[n].append(env))
    return clk, net, inboxes


class TestSimNetwork:
    def test_unicast_and_broadcast_delivery(self):
        clk, net, inboxes = _net()
        net.route("a", Envelope(to_id="b", channel_id=7, message=b"x"))
        net.route("a", Envelope(channel_id=7, message=b"y", broadcast=True))
        clk.run_until()
        assert [e.message for e in inboxes["b"]] == [b"x", b"y"]
        assert [e.message for e in inboxes["c"]] == [b"y"]
        assert inboxes["a"] == []  # broadcast never loops back
        assert net.delivered == 3

    def test_partition_blocks_and_heals(self):
        clk, net, inboxes = _net()
        net.set_partition([["a", "b"], ["c"]])
        net.route("a", Envelope(to_id="c", channel_id=1, message=b"1"))
        net.route("a", Envelope(to_id="b", channel_id=1, message=b"2"))
        clk.run_until()
        assert inboxes["c"] == []
        assert [e.message for e in inboxes["b"]] == [b"2"]
        net.heal_partition()
        net.route("a", Envelope(to_id="c", channel_id=1, message=b"3"))
        clk.run_until()
        assert [e.message for e in inboxes["c"]] == [b"3"]

    def test_partition_eats_in_flight_messages(self):
        clk, net, inboxes = _net()
        net.route("a", Envelope(to_id="c", channel_id=1, message=b"mid-flight"))
        net.set_partition([["a", "b"], ["c"]])  # applied before delivery time
        clk.run_until()
        assert inboxes["c"] == []
        assert net.dropped >= 1

    def test_down_node_sends_and_receives_nothing(self):
        clk, net, inboxes = _net()
        net.set_down("b")
        net.route("a", Envelope(to_id="b", channel_id=1, message=b"x"))
        net.route("b", Envelope(to_id="a", channel_id=1, message=b"y"))
        clk.run_until()
        assert inboxes["b"] == [] and inboxes["a"] == []

    def test_drop_and_duplicate_probabilities(self):
        link = LinkConfig(latency_s=0.001, drop=0.5)
        clk, net, inboxes = _net(seed=1, link=link)
        for i in range(100):
            net.route("a", Envelope(to_id="b", channel_id=1, message=b"%d" % i))
        clk.run_until()
        assert 20 < len(inboxes["b"]) < 80  # ~50 expected, seeded
        link2 = LinkConfig(latency_s=0.001, duplicate=1.0)
        clk2, net2, inboxes2 = _net(seed=2, link=link2)
        net2.route("a", Envelope(to_id="b", channel_id=1, message=b"x"))
        clk2.run_until()
        assert len(inboxes2["b"]) == 2

    def test_bandwidth_cap_serializes_link(self):
        # 1000 bytes at 10_000 B/s -> 0.1s per message of queueing
        link = LinkConfig(latency_s=0.0, bandwidth_bps=10_000)
        clk, net, inboxes = _net(seed=0, link=link)
        times = []
        net.set_receiver("b", lambda env: times.append(clk.time()))
        for _ in range(3):
            net.route("a", Envelope(to_id="b", channel_id=1, message=b"z" * 1000))
        clk.run_until()
        assert len(times) == 3
        assert times[0] == pytest.approx(0.1, abs=1e-6)
        assert times[2] == pytest.approx(0.3, abs=1e-6)

    def test_schedule_digest_tracks_order(self):
        clk, net, _ = _net(seed=3, link=LinkConfig(latency_s=0.01, jitter_s=0.05))
        for i in range(20):
            net.route("a", Envelope(to_id="b", channel_id=1, message=b"%d" % i))
        clk.run_until()
        d1 = net.schedule_digest()
        clk2, net2, _ = _net(seed=3, link=LinkConfig(latency_s=0.01, jitter_s=0.05))
        for i in range(20):
            net2.route("a", Envelope(to_id="b", channel_id=1, message=b"%d" % i))
        clk2.run_until()
        assert net2.schedule_digest() == d1
        clk3, net3, _ = _net(seed=4, link=LinkConfig(latency_s=0.01, jitter_s=0.05))
        for i in range(20):
            net3.route("a", Envelope(to_id="b", channel_id=1, message=b"%d" % i))
        clk3.run_until()
        assert net3.schedule_digest() != d1


class TestFaultSchedules:
    def test_parse_roundtrip_and_validation(self):
        raw = [
            {"kind": "partition", "at_height": 5, "groups": [[0, 1], [2, 3]],
             "duration": 2.0},
            {"kind": "crash", "at_height": 8, "node": 2, "restart_after": 1.0},
            {"kind": "double_sign", "node": 3},
        ]
        faults = parse_faults(raw)
        assert [f.kind for f in faults] == ["partition", "crash", "double_sign"]
        for f in faults:
            f.validate(4)
        with pytest.raises(ValueError):
            parse_faults([{"kind": "crash", "at_height": 1, "node": 0, "bogus": 1}])
        with pytest.raises(ValueError):
            Fault(kind="partition", at_time=0.0).validate(4)

    def test_smoke_schedule_shape(self):
        sched = smoke_schedule(4)
        kinds = [f.kind for f in sched]
        assert kinds == ["partition", "crash"]
        assert sched[0].duration is not None
        assert sched[1].restart_after is not None

    def test_valset_fault_kinds_validate(self):
        Fault(kind="val_join", at_height=5, node=4, power=10).validate(6)
        Fault(kind="val_leave", at_height=5, node=1).validate(6)
        Fault(kind="val_power", at_height=5, node=0, power=7).validate(6)
        with pytest.raises(ValueError, match="power"):
            Fault(kind="val_join", at_height=5, node=4).validate(6)
        with pytest.raises(ValueError, match="power"):
            Fault(kind="val_power", at_height=5, node=0, power=0).validate(6)
        with pytest.raises(ValueError, match="node"):
            Fault(kind="val_join", at_height=5, node=9, power=10).validate(6)

    def test_validation_tightened(self):
        """ISSUE 6 satellite: mutually exclusive triggers, kind-scoped
        optional fields."""
        with pytest.raises(ValueError, match="mutually exclusive"):
            Fault(
                kind="crash", at_height=3, at_time=1.0, node=0,
            ).validate(4)
        with pytest.raises(ValueError, match="restart_after"):
            Fault(
                kind="clock_skew", at_height=3, node=0, restart_after=1.0,
            ).validate(4)
        with pytest.raises(ValueError, match="duration"):
            Fault(
                kind="crash", at_height=3, node=0, duration=1.0,
            ).validate(4)
        with pytest.raises(ValueError, match="power only"):
            Fault(kind="crash", at_height=3, node=0, power=5).validate(4)
        # the valid forms still pass
        Fault(kind="partition", at_height=3, groups=[[0], [1, 2, 3]],
              duration=2.0).validate(4)
        Fault(kind="crash", at_height=3, node=0, restart_after=1.0).validate(4)

    def test_to_dict_minimal_and_roundtrip(self):
        f = Fault(kind="val_join", at_height=5, node=4, power=10)
        d = f.to_dict()
        assert d == {"kind": "val_join", "at_height": 5, "node": 4, "power": 10}
        assert parse_faults([d]) == [f]

    def test_rotation_schedule_membership_and_power_modes(self):
        sched = rotation_schedule(6, 4, every=4, start=3, until=12)
        assert [f.kind for f in sched] == ["val_join", "val_leave"] * 3
        # joiners are standbys first, then cycled-out validators
        assert [f.node for f in sched if f.kind == "val_join"] == [4, 5, 0]
        assert [f.node for f in sched if f.kind == "val_leave"] == [0, 1, 2]
        for f in sched:
            f.validate(6)
        # no standbys -> power churn, each still a structural change
        sched2 = rotation_schedule(4, 4, every=5, start=3, until=13)
        assert all(f.kind == "val_power" for f in sched2)
        assert len({f.power for f in sched2}) == len(sched2)


class TestSearchUnit:
    """Crypto-free layer of the schedule-search engine: generator
    determinism and shrink logic (cluster-backed search runs live in
    tests/test_simnet.py via the subprocess runner)."""

    def test_generators_are_seed_deterministic(self):
        import random

        from tendermint_tpu.simnet.search import GENERATORS

        for name, gen in GENERATORS.items():
            f1, l1 = gen(random.Random(f"{name}:5"), 8, 6)
            f2, l2 = gen(random.Random(f"{name}:5"), 8, 6)
            f3, l3 = gen(random.Random(f"{name}:6"), 8, 6)
            assert [f.to_dict() for f in f1] == [f.to_dict() for f in f2]
            assert l1 == l2
            assert f1, f"{name} generated an empty schedule"
            for f in f1:
                f.validate(8)
            # different seeds must actually explore different schedules
            assert (
                [f.to_dict() for f in f1] != [f.to_dict() for f in f3]
                or l1 != l3
            )

    def test_shrink_drops_irrelevant_faults(self):
        from tendermint_tpu.simnet.search import shrink_schedule

        poison = Fault(kind="crash", at_height=5, node=0, restart_after=1.0)
        noise = [
            Fault(kind="clock_skew", at_height=2, node=1, skew=0.3),
            Fault(kind="partition", at_height=3, groups=[[0], [1, 2, 3]],
                  duration=1.0),
            Fault(kind="double_sign", at_height=4, node=2),
        ]
        sched = [noise[0], poison, noise[1], noise[2]]
        runs = {"n": 0}

        def still_fails(cand):
            runs["n"] += 1
            return poison in cand

        minimal, used = shrink_schedule(sched, still_fails)
        assert minimal == [poison]
        assert used == runs["n"] <= 12

    def test_shrink_respects_budget(self):
        from tendermint_tpu.simnet.search import shrink_schedule

        sched = [
            Fault(kind="clock_skew", at_height=i + 2, node=0, skew=0.1)
            for i in range(6)
        ]
        minimal, used = shrink_schedule(sched, lambda cand: True, max_runs=3)
        assert used <= 3
        assert len(minimal) >= len(sched) - 3

    def test_scenario_emit_load_roundtrip(self, tmp_path):
        from tendermint_tpu.simnet.search import emit_scenario, load_scenario

        failure = {
            "generator": "mixed",
            "seed": 9,
            "reason": "height 10 not reached",
            "minimal": [
                {"kind": "partition", "at_height": 7,
                 "groups": [[0], [1, 2, 3]], "duration": 1.5},
            ],
            "link": dataclasses.asdict(LinkConfig(drop=0.05)),
            "n_nodes": 4,
            "n_validators": 4,
            "height": 10,
        }
        path = emit_scenario(str(tmp_path), failure)
        kw = load_scenario(path)
        assert kw["seed"] == 9 and kw["n_nodes"] == 4
        assert kw["faults"][0].kind == "partition"
        assert kw["link"].drop == 0.05


def _purepy_env():
    return dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")


def test_simnet_suite_under_purepy_fallback():
    """Re-run tests/test_simnet.py in a subprocess where the pure-Python
    signer can be enabled without leaking into this interpreter."""
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_simnet runs directly")
    except ModuleNotFoundError:
        pass
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(HERE, "test_simnet.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=REPO,
        timeout=90,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_simnet run failed:\n{tail}"


def test_mini_search_sweep_green_and_replay_exact():
    """ISSUE 6 satellite: a fixed-seed mini sweep (5 seeds x 2 generators,
    8 nodes, to h>=10) through the search engine must come back green on
    the fixed build, and re-running one (generator, seed) cell must be
    replay-exact — regression-guarding the schedule generators themselves
    (a generator drift would move every downstream search)."""
    code = r"""
import json, sys
from tendermint_tpu.simnet.search import search_schedules
res = search_schedules(
    list(range(5)), generators=("mixed", "churn"), n_nodes=8,
    n_validators=6, height=10, max_virtual_s=180.0, max_wall_s=30.0,
    shrink=False,
)
rerun = search_schedules(
    [0], generators=("churn",), n_nodes=8, n_validators=6, height=10,
    max_virtual_s=180.0, max_wall_s=30.0, shrink=False,
)
first_churn = next(r for r in res.runs if r["generator"] == "churn" and r["seed"] == 0)
print(json.dumps({
    "ok": res.ok,
    "n_runs": len(res.runs),
    "all_ok": all(r["ok"] for r in res.runs),
    "replay_exact": (
        rerun.runs[0]["fingerprint"] == first_churn["fingerprint"]
        and rerun.runs[0]["faults"] == first_churn["faults"]
    ),
    "reasons": [r["reason"] for r in res.runs if not r["ok"]],
}))
"""
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        env=_purepy_env(),
        cwd=REPO,
        timeout=90,
    )
    out = (r.stdout or b"").decode(errors="replace")
    assert r.returncode == 0, (
        f"mini sweep crashed:\n{(r.stderr or b'').decode(errors='replace')[-3000:]}"
    )
    verdict = json.loads(out.strip().splitlines()[-1])
    assert verdict["ok"] and verdict["all_ok"], verdict
    assert verdict["n_runs"] == 10
    assert verdict["replay_exact"], "generator or cluster replay drifted"


def test_smoke_cli_partition_heal_crash_restart():
    """The acceptance gate: `simnet_run.py --smoke` — 4 nodes, partition
    + heal + crash/WAL-restart at a fixed seed, height >= 20, two runs
    with identical fingerprints — on CPU, without the OpenSSL wheel,
    in well under 60s."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "simnet_run.py"), "--smoke"],
        capture_output=True,
        env=_purepy_env(),
        cwd=REPO,
        timeout=60,
    )
    out = (r.stdout or b"").decode(errors="replace")
    assert r.returncode == 0, f"smoke run failed:\n{out[-3000:]}"
    verdict = json.loads(out)
    assert verdict["ok"] is True
    assert verdict["replay_exact"] is True
    assert verdict["height"] >= 20
    assert verdict["violations"] == []
    assert "partition" in verdict["faults"] and "crash" in verdict["faults"]


def test_devcheck_smoke_partition_heal_clean():
    """ISSUE 8 satellite: the 4-node partition+heal preset runs with the
    TM_TPU_DEVCHECK runtime checkers armed (device-thread assertions,
    lock-order cycle detection, write-after-resolve canary, instrumented
    from process start via --devcheck) and must come back devcheck-clean
    — zero violations, with the lock instrumentation demonstrably live."""
    r = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "simnet_run.py"),
            "--preset", "partition_heal", "--height", "10", "--devcheck",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=REPO,
        timeout=60,
    )
    out = (r.stdout or b"").decode(errors="replace")
    assert r.returncode == 0, f"devcheck smoke failed:\n{out[-3000:]}"
    verdict = json.loads(out)
    assert verdict["ok"] is True
    assert verdict["height"] >= 10
    dc = verdict["devcheck"]
    assert dc["enabled"] is True
    assert dc["violations"] == []
    # the checkers must have actually been exercised, not just enabled
    assert dc["counts"]["lock_acquires"] > 0


# keep the importable surface honest: these names must exist without any
# crypto wheel for the unit layer above to be tier-1-safe
assert importlib.util.find_spec("tendermint_tpu.simnet.clock") is not None
