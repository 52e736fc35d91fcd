#!/usr/bin/env python3
"""simnet_run — drive a deterministic in-process consensus cluster.

Runs N real consensus nodes over the simnet virtual network with a fault
schedule, checks the Tendermint safety invariants live, and emits a JSON
verdict (and optionally a Chrome-trace span file from the observability
tracer). Same --seed ⇒ byte-identical run; a failing seed IS the repro.

Examples:
    # 4 nodes to height 20, defaults
    python tools/simnet_run.py --height 20

    # the tier-1 smoke: partition-and-heal + crash/WAL-restart, run twice,
    # assert replay-exact fingerprints
    python tools/simnet_run.py --smoke

    # 100-node cluster, 12 active validators, rotation every 5 heights,
    # two replay-exact runs
    python tools/simnet_run.py --nodes 100 --validators 12 \\
        --preset rotation --rotate-every 5 --height 20 --repeat 2

    # property-based schedule search: seeds x generators until an
    # invariant breaks, then shrink the failing schedule to a minimal
    # JSON regression scenario
    python tools/simnet_run.py --search --search-seeds 0:20 \\
        --nodes 8 --height 12 --scenario-dir tests/scenarios

    # replay a recorded regression scenario
    python tools/simnet_run.py --scenario tests/scenarios/foo.json

Fault schedule JSON: see tendermint_tpu/simnet/faults.py docstring.
Runs on CPU without the `cryptography` wheel (pure-Python ed25519
fallback), without TCP, and without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE_SEED = 42
SMOKE_HEIGHT = 20  # the acceptance bar: partition+heal+crash/restart to h>=20


def build_cluster(args, faults, link=None, tracing=None):
    from tendermint_tpu.simnet import Cluster, LinkConfig

    if link is None:
        link = LinkConfig(
            latency_s=args.latency_ms / 1000.0,
            jitter_s=args.jitter_ms / 1000.0,
            drop=args.drop,
            duplicate=args.duplicate,
            reorder=args.reorder,
            bandwidth_bps=args.bandwidth_bps or None,
        )
    return Cluster(
        n_nodes=args.nodes,
        seed=args.seed,
        link=link,
        faults=faults,
        txs_per_node=args.txs,
        n_validators=args.validators or None,
        tracing=tracing,
        vote_ingress=getattr(args, "vote_ingress", None) or None,
    )


def load_faults(args):
    from tendermint_tpu.simnet import (
        crash_restart_schedule,
        parse_faults,
        partition_heal_schedule,
        rotation_schedule,
        smoke_schedule,
    )

    if args.faults:
        with open(args.faults) as fh:
            return parse_faults(json.load(fh))
    preset = args.preset
    if preset == "partition_heal":
        return partition_heal_schedule(args.nodes)
    if preset == "crash_restart":
        return crash_restart_schedule(args.nodes - 1)
    if preset == "smoke":
        return smoke_schedule(args.nodes)
    if preset == "rotation":
        return rotation_schedule(
            args.nodes,
            args.validators or args.nodes,
            every=args.rotate_every,
            start=args.rotate_start,
            until=args.height,
        )
    return []


def run_once(args, faults, link=None, want_trace=False) -> tuple:
    """One cluster run; returns (verdict_dict, merged_trace_doc_or_None).
    The merged doc (ISSUE 10) is the CLUSTER export — per-node
    virtual-clock tracers + the driver's wall-clock spans, flow chains
    intact — not just the process-wide ring."""
    from tendermint_tpu.observability import trace as _trace

    # per-node tracing only where the doc is actually kept: with --trace
    # --repeat N, runs 1..N-1 force it OFF instead of paying full span
    # recording for buffers that are discarded (tracing never perturbs a
    # run, so replay-exactness across the repeats is unaffected)
    cluster = build_cluster(
        args, faults, link=link,
        tracing=want_trace if args.trace else None,
    )
    if getattr(args, "replay_node", -1) >= 0:
        from tendermint_tpu.simnet import CatchupDriver

        rdrop = getattr(args, "replay_drop", -1.0)
        CatchupDriver(
            cluster, args.replay_node,
            drop=rdrop if rdrop >= 0 else args.drop,
            start_after=5.0,
            start_at_height=getattr(args, "replay_at", 0) or None,
        )
    merged = None
    try:
        with _trace.span("simnet.run", seed=args.seed, nodes=args.nodes):
            rep = cluster.run_to_height(
                args.height,
                max_virtual_s=args.max_virtual_s,
                max_wall_s=_wall_budget(args, None),
            )
        if want_trace:
            merged = cluster.export_merged_trace()
    finally:
        cluster.stop()  # closes WALs and removes the temp dir even on error
    out = rep.to_dict()
    out["commits_per_s"] = (
        round(rep.height / rep.wall_s, 2) if rep.wall_s > 0 else None
    )
    return out, merged


def _wall_budget(args, mode_default):
    """-1 = mode default, 0 = explicitly unbounded, else the bound."""
    if args.max_wall_s < 0:
        return mode_default
    return args.max_wall_s or None


def _attach_devcheck(verdict: dict) -> None:
    """Embed the runtime-checker report; any violation fails the run."""
    from tendermint_tpu.libs import devcheck

    rep = devcheck.report()
    verdict["devcheck"] = rep
    if rep["violations"]:
        verdict["ok"] = False
        verdict["reason"] = (
            f"{len(rep['violations'])} devcheck violation(s): "
            + "; ".join(v["message"] for v in rep["violations"][:3])
        )


def run_soak(args) -> int:
    """--soak: one cluster, all four QoS workloads, time-series telemetry
    and a declarative SLO verdict (ISSUE 16). The verify engine runs with
    the device MOCKED by default (real packing/prep/transfer, all-accept
    verdict behind --soak-rtt-ms) so CI boxes measure the harness and the
    SLOs, not jax compile time; --soak-real runs live kernels. Exit 0 on
    a green verdict, 1 on any conclusive failure (SLO breach, invariant,
    devcheck), 3 when the wall budget cut the run short (inconclusive —
    the same classification --scenario applies)."""
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.simnet.soak import SoakConfig, SoakDriver

    real_prepare = _pl.AsyncBatchVerifier._prepare
    force_prev = os.environ.get("TM_TPU_FORCE_DEVICE")
    if not args.soak_real:
        from tendermint_tpu.ops._testing import mock_mempool_prepare

        _pl.AsyncBatchVerifier._prepare = staticmethod(
            mock_mempool_prepare(real_prepare, args.soak_rtt_ms / 1e3)
        )
        os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    t0 = time.monotonic()
    runs = []
    try:
        for _ in range(max(args.repeat, 1)):
            v = _pl.AsyncBatchVerifier(depth=2)
            try:
                cfg = SoakConfig.from_env(
                    duration_s=args.soak,
                    seed=args.seed,
                    n_nodes=args.nodes,
                    catchup_at_height=getattr(args, "replay_at", 0) or None,
                    max_wall_s=_wall_budget(args, 300.0),
                )
                runs.append(SoakDriver(v, cfg).run())
            finally:
                v.close()
    finally:
        _pl.AsyncBatchVerifier._prepare = real_prepare
        if not args.soak_real:
            if force_prev is None:
                os.environ.pop("TM_TPU_FORCE_DEVICE", None)
            else:
                os.environ["TM_TPU_FORCE_DEVICE"] = force_prev
    verdict = dict(runs[0])
    verdict["mode"] = "real" if args.soak_real else "mocked-device"
    verdict["device_rtt_ms"] = None if args.soak_real else args.soak_rtt_ms
    verdict["runs"] = len(runs)
    verdict["wall_total_s"] = round(time.monotonic() - t0, 3)
    verdict["replay_exact"] = all(
        r["fingerprint"] == runs[0]["fingerprint"]
        and r["schedule_digest"] == runs[0]["schedule_digest"]
        for r in runs
    )
    if len(runs) > 1 and not verdict["replay_exact"]:
        verdict["ok"] = False
        verdict["reason"] = (
            "same-seed soak runs diverged (replay exactness broken)"
        )
    if args.devcheck:
        _attach_devcheck(verdict)
    if args.soak_out:
        with open(args.soak_out, "w") as fh:
            json.dump(verdict, fh, indent=1, default=str)
            fh.write("\n")
    # stdout stays readable: the bulky rings live only in --soak-out
    slim = {
        k: v for k, v in verdict.items()
        if k not in ("gauges", "windows", "verify_engine", "flight_recorder")
    }
    print(json.dumps(slim, indent=2, default=str))
    if verdict["ok"]:
        return 0
    inconclusive = (
        verdict.get("wall_budget_hit")
        and verdict.get("reason") == "wall budget exhausted"
        and not (verdict.get("devcheck") or {}).get("violations")
    )
    return 3 if inconclusive else 1


def run_fleet(args) -> int:
    """--fleet: the shared-verification-fleet scenario (ISSUE 18). A
    100-node cluster submits EntryBlock verify requests at all three QoS
    tiers through the real wire codec (loopback transport) to ONE fleet
    host; --fleet-kill-at crashes it mid-run and every node degrades to
    local verification with zero stalled requests. --repeat N asserts
    replay-exact reports; the verdict also checks verdict parity against
    an all-local run of the same seed (degradation may move WHERE a
    verdict is computed, never what it is). Pure host-side — no jax, no
    crypto wheel."""
    from tendermint_tpu.simnet.fleet import run_fleet_scenario

    kw = dict(
        seed=args.seed,
        n_nodes=args.fleet_nodes,
        kill_at=args.fleet_kill_at if args.fleet_kill_at >= 0 else None,
        revive_at=args.fleet_revive_at if args.fleet_revive_at >= 0 else None,
    )
    t0 = time.monotonic()
    runs = [run_fleet_scenario(**kw) for _ in range(max(args.repeat, 1))]
    baseline = run_fleet_scenario(seed=args.seed, n_nodes=args.fleet_nodes,
                                  all_local=True)
    verdict = dict(runs[0])
    verdict["runs"] = len(runs)
    verdict["wall_total_s"] = round(time.monotonic() - t0, 3)
    verdict["replay_exact"] = all(r == runs[0] for r in runs)
    verdict["verdict_parity"] = (
        runs[0]["verdict_fingerprint"] == baseline["verdict_fingerprint"]
    )
    verdict["ok"] = bool(
        verdict["replay_exact"]
        and verdict["verdict_parity"]
        and verdict["stalled_requests"] == 0
    )
    if not verdict["ok"]:
        verdict["reason"] = (
            "same-seed fleet runs diverged" if not verdict["replay_exact"]
            else "fleet/local verdict streams differ"
            if not verdict["verdict_parity"]
            else "%d requests stalled" % verdict["stalled_requests"]
        )
    print(json.dumps(verdict, indent=2, default=str))
    return 0 if verdict["ok"] else 1


def parse_seed_range(spec: str):
    """"a:b" -> range(a, b); "3,7,9" -> [3, 7, 9]; "12" -> [12]."""
    if ":" in spec:
        a, b = spec.split(":", 1)
        return list(range(int(a), int(b)))
    return [int(s) for s in spec.split(",") if s.strip() != ""]


def run_search(args) -> int:
    from tendermint_tpu.simnet.search import GENERATORS, search_schedules

    seeds = parse_seed_range(args.search_seeds)
    generators = [g for g in args.generators.split(",") if g]
    # an empty grid or a typo'd generator must be a usage error, not a
    # vacuous green sweep / raw KeyError
    if not seeds:
        print(f"error: empty seed grid {args.search_seeds!r}", file=sys.stderr)
        return 2
    unknown = [g for g in generators if g not in GENERATORS]
    if not generators or unknown:
        print(
            f"error: unknown generators {unknown or args.generators!r}; "
            f"available: {sorted(GENERATORS)}",
            file=sys.stderr,
        )
        return 2
    t0 = time.monotonic()
    res = search_schedules(
        seeds,
        generators=generators,
        n_nodes=args.nodes,
        n_validators=args.validators or None,
        height=args.height,
        max_virtual_s=args.max_virtual_s,
        max_wall_s=_wall_budget(args, 120.0),
        shrink=not args.no_shrink,
        scenario_dir=args.scenario_dir or None,
        stop_on_failure=not args.keep_searching,
        progress=(lambda m: print(f"# {m}", file=sys.stderr))
        if args.verbose
        else None,
    )
    out = res.to_dict()
    out["wall_total_s"] = round(time.monotonic() - t0, 3)
    out["seeds"] = seeds
    out["generators"] = generators
    if args.devcheck:
        _attach_devcheck(out)
    print(json.dumps(out, indent=2, default=str))
    return 0 if out.get("ok", res.ok) else 1


def run_scenario(args) -> int:
    """Replay a recorded regression scenario. Exit 0 when it passes,
    1 on a real failure (the bug is back), 3 when the wall budget cut
    the run short — inconclusive, the same classification the search
    applies (machine speed must never read as a regression)."""
    from tendermint_tpu.simnet.search import load_scenario, run_schedule

    kw = load_scenario(args.scenario)
    t0 = time.monotonic()
    rep = run_schedule(
        kw["faults"],
        kw["seed"],
        kw["n_nodes"],
        kw["n_validators"],
        kw["link"],
        kw["height"],
        max_virtual_s=args.max_virtual_s,
        max_wall_s=_wall_budget(args, 120.0),
    )
    inconclusive = (not rep.ok) and rep.wall_budget_hit
    out = rep.to_dict()
    out["scenario"] = args.scenario
    out["inconclusive"] = inconclusive
    out["wall_total_s"] = round(time.monotonic() - t0, 3)
    if args.devcheck:
        _attach_devcheck(out)
    print(json.dumps(out, indent=2, default=str))
    if args.devcheck and out["devcheck"]["violations"]:
        # a recorded checker violation is conclusive evidence regardless
        # of whether the wall budget cut the run short — never exit 3
        return 1
    if not rep.ok and inconclusive:
        return 3
    return 0 if out["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument(
        "--validators",
        type=int,
        default=0,
        help="genesis validator count (0 = all nodes); the rest are "
        "standby full nodes that val_join faults can rotate in",
    )
    ap.add_argument("--height", type=int, default=20)
    ap.add_argument("--max-virtual-s", type=float, default=600.0)
    ap.add_argument(
        "--max-wall-s", type=float, default=-1.0,
        help="bound REAL elapsed time per run (0 = unbounded; default: "
        "unbounded for plain runs, 120s per run in --search/--scenario "
        "modes, where a budget-cut run counts as inconclusive, not a bug)",
    )
    ap.add_argument("--faults", default="", help="JSON fault schedule file")
    ap.add_argument(
        "--preset",
        choices=["none", "partition_heal", "crash_restart", "smoke", "rotation"],
        default="none",
    )
    ap.add_argument(
        "--rotate-every", type=int, default=5,
        help="rotation preset: churn the valset every N heights",
    )
    ap.add_argument(
        "--rotate-start", type=int, default=3,
        help="rotation preset: first churn height",
    )
    ap.add_argument("--txs", type=int, default=0, help="seed N txs per node")
    ap.add_argument("--latency-ms", type=float, default=5.0)
    ap.add_argument("--jitter-ms", type=float, default=0.0)
    ap.add_argument("--drop", type=float, default=0.0)
    ap.add_argument("--duplicate", type=float, default=0.0)
    ap.add_argument("--reorder", type=float, default=0.0)
    ap.add_argument("--bandwidth-bps", type=float, default=0.0)
    ap.add_argument("--trace", default="", help="write Chrome-trace spans here")
    ap.add_argument(
        "--repeat",
        type=int,
        default=1,
        help="run N times with the same seed and require identical fingerprints",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help=f"tier-1 smoke: 4 nodes, smoke schedule, seed {SMOKE_SEED}, "
        f"height {SMOKE_HEIGHT}, two replay-exact runs",
    )
    # -- property-based schedule search ----------------------------------
    ap.add_argument(
        "--search",
        action="store_true",
        help="explore --search-seeds x --generators until an invariant "
        "breaks, then shrink the failing schedule to a minimal repro",
    )
    ap.add_argument(
        "--search-seeds", default="0:10",
        help='seed grid: "a:b" range or comma list (default 0:10)',
    )
    ap.add_argument(
        "--generators", default="mixed,churn",
        help="comma list of schedule generators (mixed, churn)",
    )
    ap.add_argument(
        "--vote-ingress", action="store_true",
        help="attach the stepped live-vote ingress accumulator on every "
             "node (ISSUE 15) — flush points ride the pump, so runs stay "
             "replay-exact",
    )
    ap.add_argument("--no-shrink", action="store_true")
    ap.add_argument(
        "--keep-searching", action="store_true",
        help="do not stop at the first failure",
    )
    ap.add_argument(
        "--scenario-dir", default="",
        help="write the shrunk failing schedule here as a JSON scenario",
    )
    ap.add_argument(
        "--scenario", default="",
        help="replay a recorded regression scenario file and exit",
    )
    ap.add_argument(
        "--inject-bug",
        choices=["", "catchup", "starve"],
        default="",
        help="re-introduce a known-fixed gossip bug (TM_TPU_GOSSIP_BUG_* "
        "seam) so the search demonstrably rediscovers and shrinks it; "
        "'starve' arms the reserved-ingress-slot seam "
        "(TM_TPU_INJECT_LINTBUG, implies devcheck) so a --soak run "
        "demonstrably fails its ingress-admission SLO",
    )
    # -- soak harness (ISSUE 16) ------------------------------------------
    ap.add_argument(
        "--soak", type=float, default=0.0,
        help="run the soak harness for this many VIRTUAL seconds instead "
        "of --height: all four QoS workloads (consensus + light fleets + "
        "tx floods through partition/heal + crash-rejoin catch-up) on one "
        "shared verify engine, with time-series telemetry and per-lane "
        "SLO budgets; --repeat N asserts replay-exact fingerprints",
    )
    ap.add_argument(
        "--soak-rtt-ms", type=float, default=4.0,
        help="soak mocked-device round-trip per launch (default 4)",
    )
    ap.add_argument(
        "--soak-real", action="store_true",
        help="soak with live kernels instead of the mocked device",
    )
    ap.add_argument(
        "--soak-out", default="",
        help="write the full soak artifact JSON (gauge rings, windows, "
        "flight recorder on failure) here — tools/soak_report.py renders it",
    )
    # -- shared verification fleet (ISSUE 18) -----------------------------
    ap.add_argument(
        "--fleet", action="store_true",
        help="run the shared-verification-fleet scenario instead of "
        "--height: --fleet-nodes nodes submit EntryBlock verify requests "
        "at all three QoS tiers through the real fleet wire codec to one "
        "fleet host; the verdict asserts zero stalled requests, verdict "
        "parity vs an all-local run, and (--repeat N) replay exactness",
    )
    ap.add_argument(
        "--fleet-nodes", type=int, default=100,
        help="cluster size for --fleet (default 100)",
    )
    ap.add_argument(
        "--fleet-kill-at", type=float, default=4.0,
        help="kill the fleet host this many virtual seconds in "
        "(default 4.0; negative = never)",
    )
    ap.add_argument(
        "--fleet-revive-at", type=float, default=7.0,
        help="revive the fleet host at this virtual second "
        "(default 7.0; negative = never)",
    )
    # -- chain-replay catch-up (ISSUE 14) ---------------------------------
    ap.add_argument(
        "--replay-node", type=int, default=-1,
        help="attach a CatchupDriver to this node index: after it crashes "
        "(schedule a crash fault via --faults/--preset), replay the gap "
        "live through the blocksync ReplayEngine and rejoin at the tip; "
        "the verdict's `catchup` list carries the range hit-rate",
    )
    ap.add_argument(
        "--replay-at", type=int, default=0,
        help="hold the first replay fetch until the live tip reaches this "
        "height, so the rejoin happens N heights behind (0 = chase "
        "immediately)",
    )
    ap.add_argument(
        "--replay-drop", type=float, default=-1.0,
        help="P(range-fetch response lost) on the replay request path "
        "(default: --drop)",
    )
    ap.add_argument(
        "--devcheck",
        action="store_true",
        help="run with the TM_TPU_DEVCHECK runtime invariant checkers on "
        "(device-thread assertions, lock-order cycle detection, write-"
        "after-resolve canary); the verdict embeds the devcheck report "
        "and any violation fails the run",
    )
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    if args.devcheck:
        # before any tendermint_tpu import: import-time lock creation
        # (metrics registries, epoch cache) is then instrumented too
        os.environ["TM_TPU_DEVCHECK"] = "1"

    if args.inject_bug == "catchup":
        # must land before tendermint_tpu.consensus.peer_state is imported
        os.environ["TM_TPU_GOSSIP_BUG_CATCHUP"] = "1"
    if args.inject_bug == "starve":
        # the seam is devcheck-gated (a stale env export with the
        # checkers off must stay inert), so arming it arms devcheck too
        os.environ["TM_TPU_DEVCHECK"] = "1"
        os.environ["TM_TPU_INJECT_LINTBUG"] = "starve"

    if args.scenario:
        return run_scenario(args)
    if args.search:
        return run_search(args)
    if args.soak > 0:
        return run_soak(args)
    if args.fleet:
        return run_fleet(args)

    if args.smoke:
        args.nodes = 4
        args.validators = 0
        args.seed = SMOKE_SEED
        args.height = max(args.height if args.height != 20 else 0, SMOKE_HEIGHT)
        args.preset = "smoke"
        args.repeat = max(args.repeat, 2)

    from tendermint_tpu.observability import trace as _trace

    if args.trace:
        _trace.configure(enabled=True)

    t0 = time.monotonic()
    faults = load_faults(args)
    runs = []
    merged_doc = None
    for i in range(max(args.repeat, 1)):
        out, doc = run_once(
            args, load_faults(args),
            want_trace=bool(args.trace) and i == 0,
        )
        runs.append(out)
        if doc is not None:
            merged_doc = doc
    verdict = dict(runs[0])
    verdict["runs"] = len(runs)
    verdict["wall_total_s"] = round(time.monotonic() - t0, 3)
    verdict["replay_exact"] = all(
        r["fingerprint"] == runs[0]["fingerprint"]
        and r["schedule_digest"] == runs[0]["schedule_digest"]
        for r in runs
    )
    if len(runs) > 1 and not verdict["replay_exact"]:
        verdict["ok"] = False
        verdict["reason"] = "same-seed runs diverged (replay exactness broken)"
    verdict["faults"] = [f.kind for f in faults]
    if args.devcheck:
        _attach_devcheck(verdict)

    if args.trace and merged_doc is not None:
        verdict["trace_path"] = _trace.dump_doc(merged_doc, args.trace)

    print(json.dumps(verdict, indent=2, default=str))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
