"""simnet end-to-end tests: real consensus nodes, virtual network,
deterministic replay, fault injection, safety invariants.

Needs a working ed25519 signer. With the `cryptography` wheel the module
runs directly; without it, tests/test_simnet_isolated.py re-runs it in a
subprocess under TM_TPU_PUREPY_CRYPTO=1 (the env must NOT be set in the
main pytest process — see that module's docstring).
"""

import importlib.util
import os

import pytest

if importlib.util.find_spec("cryptography") is None and not os.environ.get(
    "TM_TPU_PUREPY_CRYPTO"
):
    pytest.skip(
        "needs an ed25519 signer (cryptography wheel or the isolated runner)",
        allow_module_level=True,
    )

from tendermint_tpu.simnet import (
    Cluster,
    Fault,
    LinkConfig,
    crash_restart_schedule,
    partition_heal_schedule,
    rotation_schedule,
    smoke_schedule,
)


def run(seed, faults=None, h=6, n=4, link=None, max_virtual_s=300.0, txs=0):
    c = Cluster(n_nodes=n, seed=seed, faults=faults, link=link, txs_per_node=txs)
    try:
        rep = c.run_to_height(h, max_virtual_s=max_virtual_s)
    finally:
        c.stop()
    return c, rep


class TestLiveness:
    def test_four_nodes_reach_height_invariants_green(self):
        c, rep = run(seed=1, h=6, txs=3)
        assert rep.ok, rep.reason
        assert rep.heights == [6, 6, 6, 6] or min(rep.heights) >= 6
        assert rep.violations == []
        # seeded txs actually landed in blocks
        all_txs = [
            tx
            for h in range(1, c.nodes[0].height() + 1)
            for tx in c.nodes[0].bstore.load_block(h).data.txs
        ]
        assert b"k0_0=v0" in all_txs and b"k3_2=v2" in all_txs

    def test_seven_nodes_with_minority_partition(self):
        """f=2 cluster: isolating 2 of 7 validators must not stop the
        majority (5/7 > 2/3)."""
        faults = [
            Fault(
                kind="partition",
                at_height=2,
                groups=[[0, 1, 2, 3, 4], [5, 6]],
                duration=3.0,
            )
        ]
        _, rep = run(seed=2, faults=faults, h=6, n=7)
        assert rep.ok, rep.reason

    def test_lossy_links_still_commit(self):
        link = LinkConfig(
            latency_s=0.01, jitter_s=0.02, drop=0.05, duplicate=0.05, reorder=0.1
        )
        _, rep = run(seed=3, link=link, h=6, max_virtual_s=600.0)
        assert rep.ok, rep.reason
        assert rep.net["dropped"] > 0  # the fault model actually engaged
        assert rep.net["duplicated"] > 0


class TestDeterminism:
    def test_same_seed_identical_fingerprint(self):
        _, r1 = run(seed=7)
        _, r2 = run(seed=7)
        assert r1.ok and r2.ok
        assert r1.fingerprint == r2.fingerprint
        assert r1.schedule_digest == r2.schedule_digest

    def test_same_seed_identical_with_crash_restart(self):
        """The acceptance bar: replay exactness must survive a crash +
        WAL-restart fault (the restart path replays the WAL tail)."""
        sched = crash_restart_schedule(node=2, at_height=3, restart_after=1.0)
        c1, r1 = run(seed=9, faults=sched, h=8)
        c2, r2 = run(seed=9, faults=sched, h=8)
        assert r1.ok, r1.reason
        assert c1.nodes[2].restarts == 1
        assert r1.fingerprint == r2.fingerprint
        assert r1.schedule_digest == r2.schedule_digest

    def test_different_seeds_different_schedules(self):
        """Different seeds must actually change the event order (jitter
        draws + gossip picks), not just relabel the same run."""
        link = LinkConfig(latency_s=0.005, jitter_s=0.01)
        _, r1 = run(seed=100, link=link)
        _, r2 = run(seed=101, link=link)
        assert r1.schedule_digest != r2.schedule_digest


class TestFaults:
    def test_even_partition_stalls_then_heals(self):
        """2/2 split: no side has +2/3, so commits must stop while the
        partition holds and resume after heal — BFT liveness needs a
        quorum-connected component."""
        c = Cluster(
            n_nodes=4,
            seed=4,
            faults=[
                Fault(kind="partition", at_time=0.1, groups=[[0, 1], [2, 3]])
            ],
        )
        c.start()
        t0 = c.clock.time()
        c.clock.run_until(deadline=t0 + 30.0)
        stalled_at = max(c.heights())
        # whatever committed before the split landed, nothing much after
        assert stalled_at <= 2, f"committed through a 2/2 partition: {c.heights()}"
        c._heal()
        done = c.clock.run_until(
            predicate=lambda: min(c.heights()) >= stalled_at + 3,
            deadline=c.clock.time() + 60.0,
        )
        assert done, f"no progress after heal: {c.heights()}"
        assert c.check_invariants() == []
        c.stop()

    def test_crash_restart_converges_via_wal(self):
        sched = crash_restart_schedule(node=1, at_height=3, restart_after=2.0)
        c, rep = run(seed=5, faults=sched, h=8)
        assert rep.ok, rep.reason
        assert c.nodes[1].restarts == 1
        # the restarted node's chain is byte-identical to the others
        for h in range(1, 9):
            assert (
                c.nodes[1].bstore.load_block(h).hash()
                == c.nodes[0].bstore.load_block(h).hash()
            )

    def test_crash_stop_without_restart_excluded_from_target(self):
        """A crash fault with no scheduled restart is crash-stop: the
        remaining 3/4 (quorum) must reach the target and the run must end
        at that point, not burn the virtual deadline waiting."""
        faults = [Fault(kind="crash", at_height=2, node=3)]
        c, rep = run(seed=13, faults=faults, h=5)
        assert rep.ok, rep.reason
        assert c.nodes[3].crashed and c.nodes[3].restarts == 0
        assert rep.virtual_s < 60.0  # ended on target, not on deadline
        live = [h for i, h in enumerate(rep.heights) if i != 3]
        assert min(live) >= 5

    def test_byzantine_double_sign_does_not_break_agreement(self):
        faults = [Fault(kind="double_sign", node=3)]
        c, rep = run(seed=6, faults=faults, h=6)
        assert rep.ok, rep.reason
        assert rep.violations == []
        assert c.nodes[3].byzantine
        assert any("double_sign node 3" in f for f in rep.faults_applied)

    def test_byzantine_double_sign_honors_height_trigger(self):
        """A double_sign with at_height must start equivocating at that
        height, not from genesis."""
        faults = [Fault(kind="double_sign", node=2, at_height=3)]
        c, rep = run(seed=6, faults=faults, h=6)
        assert rep.ok, rep.reason
        assert c.nodes[2].cs.do_prevote_override is not None
        applied = [f for f in rep.faults_applied if "double_sign" in f]
        assert applied and applied[0].startswith("t=")  # fired at a time

    def test_clock_skew_node_keeps_up(self):
        faults = [Fault(kind="clock_skew", at_time=0.2, node=2, skew=0.8)]
        _, rep = run(seed=8, faults=faults, h=6)
        assert rep.ok, rep.reason

    def test_smoke_schedule_end_to_end(self):
        """The CLI's --smoke scenario at module level: partition+heal then
        crash+WAL-restart, height >= 10, invariants green."""
        c, rep = run(seed=42, faults=smoke_schedule(4), h=10)
        assert rep.ok, rep.reason
        assert min(rep.heights) >= 10
        assert any("partition" in f for f in rep.faults_applied)
        assert any("restart" in f for f in rep.faults_applied)


class TestValsetRotation:
    """ISSUE 6 tentpole leg (a): val_join/val_leave/val_power faults route
    through the REAL EndBlock -> update_state -> _update_with_change_set
    path, structurally invalidating ValidatorSet.hash() every churn."""

    def test_join_leave_rotation_changes_valset_and_converges(self):
        faults = rotation_schedule(
            n_nodes=6, n_validators=4, every=4, start=3, until=12
        )
        assert [f.kind for f in faults] == [
            "val_join", "val_leave"] * 3
        c = Cluster(n_nodes=6, n_validators=4, seed=42, faults=faults)
        try:
            rep = c.run_to_height(16, max_virtual_s=300.0)
        finally:
            c.stop()
        assert rep.ok, rep.reason
        assert rep.n_validators == 4
        # every rotation surfaced as a validators_hash change on-chain
        assert len(rep.valset_changes) == 3, rep.valset_changes
        # the joined standby actually validates: the final commit carries
        # a signature from a node outside the genesis set
        seen = c.nodes[0].bstore.load_seen_commit()
        vals = c.nodes[0].sstore.load_validators(seen.height)
        genesis_pubs = {n.sk.pub_key().bytes() for n in c.nodes[:4]}
        assert any(
            v.pub_key.bytes() not in genesis_pubs for v in vals.validators
        )

    def test_rotation_replay_exact(self):
        def run():
            faults = rotation_schedule(
                n_nodes=6, n_validators=4, every=4, start=3, until=12
            )
            c = Cluster(n_nodes=6, n_validators=4, seed=7, faults=faults)
            try:
                return c.run_to_height(14, max_virtual_s=300.0)
            finally:
                c.stop()

        r1, r2 = run(), run()
        assert r1.ok and r2.ok, (r1.reason, r2.reason)
        assert r1.fingerprint == r2.fingerprint
        assert r1.schedule_digest == r2.schedule_digest

    def test_power_rotation_full_validator_cluster(self):
        """No standbys: rotations degrade to power changes — still a
        structural hash invalidation per churn."""
        faults = rotation_schedule(
            n_nodes=4, n_validators=4, every=4, start=3, until=8
        )
        assert all(f.kind == "val_power" for f in faults)
        c, rep = run(seed=3, faults=faults, h=12)
        assert rep.ok, rep.reason
        assert len(rep.valset_changes) == 2, rep.valset_changes

    def test_epoch_cache_cycles_cold_warm_evict_under_churn(self):
        """Rotation drives the device epoch cache through its whole
        lifecycle: every distinct valset is a miss once, warm
        re-verifies hit, and a rotated set maps onto the resident table
        instead of building (and evicting) one of its own — asserted
        live by the harness invariants."""
        from tendermint_tpu.ops import epoch_cache

        epoch_cache.reset(depth=2)
        try:
            faults = rotation_schedule(
                n_nodes=6, n_validators=4, every=4, start=3, until=12
            )
            c = Cluster(n_nodes=6, n_validators=4, seed=7, faults=faults)
            try:
                rep = c.run_to_height(16, max_virtual_s=300.0)
            finally:
                c.stop()
            assert rep.ok, rep.reason  # includes the epoch-cache invariants
            ec = rep.epoch_cache
            assert ec["enabled"] and ec["depth"] == 2
            # genesis + 3 rotations = 4 distinct validator sets, each a
            # miss once; a rotation swaps one key of four, so the sets map
            # onto the table the first one built and nothing is evicted
            assert ec["misses"] >= 4
            assert ec["hits"] > 0
            assert ec["tables_shared"] >= 3
            assert ec["evictions"] >= ec["tables_built"] - ec["depth"]
        finally:
            epoch_cache.reset()

    def test_standby_nodes_track_chain_without_voting(self):
        c = Cluster(n_nodes=5, n_validators=3, seed=5)
        try:
            rep = c.run_to_height(6, max_virtual_s=120.0)
            assert rep.ok, rep.reason
            # standbys committed the chain...
            assert min(rep.heights) >= 6
            # ...but commits carry only the 3 validators' signature slots
            seen = c.nodes[4].bstore.load_seen_commit()
            assert len(seen.signatures) == 3
        finally:
            c.stop()


class TestScheduleSearch:
    """ISSUE 6 tentpole leg (c): seeds x generators explored until an
    invariant breaks, failing schedules delta-debugged to minimal."""

    def test_search_green_on_fixed_build(self, tmp_path):
        from tendermint_tpu.simnet.search import search_schedules

        res = search_schedules(
            [3], generators=("mixed",), n_nodes=4, height=6,
            max_virtual_s=120.0, max_wall_s=30.0,
            scenario_dir=str(tmp_path),
        )
        assert res.ok, res.failure
        assert len(res.runs) == 1 and res.runs[0]["ok"]
        assert list(tmp_path.iterdir()) == []  # no failure, no scenario

    def test_committed_scenarios_replay_green(self):
        """Every shrunk bug the search has ever found must stay fixed:
        tests/scenarios/*.json replay clean on the current build."""
        import glob

        from tendermint_tpu.simnet.search import load_scenario, run_schedule

        here = os.path.dirname(os.path.abspath(__file__))
        paths = sorted(glob.glob(os.path.join(here, "scenarios", "*.json")))
        assert paths, "regression scenario directory is empty"
        for path in paths:
            kw = load_scenario(path)
            rep = run_schedule(
                kw["faults"], kw["seed"], kw["n_nodes"],
                kw["n_validators"], kw["link"], kw["height"],
                max_virtual_s=120.0, max_wall_s=60.0,
            )
            if not rep.ok and rep.wall_budget_hit:
                pytest.skip(
                    f"{os.path.basename(path)}: wall budget cut the "
                    "replay short (machine too slow) — inconclusive"
                )
            assert rep.ok, f"{os.path.basename(path)}: {rep.reason}"


class TestInvariantCheckers:
    def test_agreement_checker_detects_divergence(self):
        """The checker itself must fire: feed it a forged conflicting
        block hash and expect a violation record."""
        c, rep = run(seed=10, h=3)
        assert rep.ok
        # simulate a diverged commit observation
        c._canonical[2] = b"\x00" * 32
        violations = c.check_invariants()
        assert any("convergence" in v for v in violations)

    def test_quorum_checker_detects_thin_commit(self):
        c, rep = run(seed=11, h=3)
        assert rep.ok
        seen = c.nodes[0].bstore.load_seen_commit()
        # the real commit passes the real checker...
        assert c.commit_quorum_violation(seen, 0) is None
        # ...and a forged sub-quorum commit must trip it
        import dataclasses

        thin = dataclasses.replace(
            seen,
            signatures=[
                sig if i == 0 else dataclasses.replace(
                    sig, block_id_flag=1, signature=b"", validator_address=b"",
                )
                for i, sig in enumerate(seen.signatures)
            ],
        )
        violation = c.commit_quorum_violation(thin, 0)
        assert violation is not None and "quorum" in violation

    def test_fault_validation_rejects_bad_schedules(self):
        with pytest.raises(ValueError):
            Cluster(n_nodes=4, faults=[Fault(kind="warp", at_time=0.0)])
        with pytest.raises(ValueError):
            Cluster(n_nodes=4, faults=[Fault(kind="crash", at_height=2, node=9)])
        with pytest.raises(ValueError):
            Cluster(n_nodes=4, faults=[Fault(kind="partition", at_time=1.0)])


class TestSteppedModeParity:
    def test_wait_for_height_blocking_wait(self):
        """The condition-based wait_for_height (satellite: no sleep-poll)
        still works on a threaded node."""
        from tests.test_consensus import make_node
        from tendermint_tpu.crypto import ed25519

        sk = ed25519.gen_priv_key(bytes([9]) * 32)
        cs, bstore, _ = make_node([sk], 0)
        cs.start()
        try:
            cs.wait_for_height(2, timeout=60)
            assert bstore.height() >= 2
            with pytest.raises(TimeoutError):
                cs.wait_for_height(10_000, timeout=0.3)
        finally:
            cs.stop()

    def test_partition_heal_schedule_helper(self):
        sched = partition_heal_schedule(4, at_height=3, duration=1.0)
        assert sched[0].groups == [[0, 1], [2, 3]]
        _, rep = run(seed=12, faults=sched, h=6)
        assert rep.ok, rep.reason
