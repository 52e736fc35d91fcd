"""Tier-1 soak-harness coverage (ISSUE 16) for containers without the
`cryptography` wheel.

Two subprocess runs of `tools/simnet_run.py --soak` under
TM_TPU_PUREPY_CRYPTO=1 (the env flag must NOT leak into the main pytest
interpreter — same pattern as tests/test_simnet_isolated.py):

  1. mini-soak smoke: all four workload lanes drive ONE shared verifier
     on a mocked device for a few virtual seconds, twice at the same
     seed — green verdict, replay-exact, every lane demonstrably active.
  2. starved run: TM_TPU_INJECT_LINTBUG=starve makes the pipeline worker
     withhold ingress-priority dispatch — the soak must FAIL with the
     breach localized to the ingress lane + a concrete time window, and
     the artifact must carry the flight-recorder tail.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _env(**extra):
    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


@pytest.mark.parametrize("seed,duration", [("7", "6"), ("8", "5")])
def test_mini_soak_smoke_green_and_replay_exact(tmp_path, seed, duration):
    """`simnet_run.py --soak` — 4 nodes, crash + catchup rejoin +
    partition/heal, commit echo + light fleet + tx floods through one
    shared AsyncBatchVerifier on a mocked device, twice per seed at TWO
    seeds: green verdict, identical fingerprint/schedule digest per
    seed, zero timeouts, devcheck-clean (no devcheck key when unarmed),
    all lanes active."""
    out = tmp_path / "soak.json"
    r = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "simnet_run.py"),
            "--soak", duration, "--repeat", "2", "--seed", seed,
            "--soak-out", str(out),
        ],
        capture_output=True,
        env=_env(),
        cwd=REPO,
        timeout=90,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"mini soak failed:\n{tail}"
    v = json.loads(out.read_text())
    assert v["ok"] is True, v["reason"]
    assert v["replay_exact"] is True and v["runs"] == 2
    assert v["mode"] == "mocked-device"
    assert v["slo"]["ok"] and v["slo"]["evaluated"] == 5
    assert v["violations"] == []
    # every workload lane demonstrably ran (a lane that silently no-ops
    # would still produce a "green" verdict — refuse that)
    c = v["counters"]
    assert c["echo_submitted"] > 0 and c["echo_errors"] == 0
    assert c["light_verdicts"] > 0 and c["light_timeouts"] == 0
    assert c["ingress_admitted"] > 0 and c["ingress_timeouts"] == 0
    # aggregated-commit echo probe (ISSUE 20): rode the shared verifier
    # through the fused BLS pairing seam, its SLO evaluated
    assert c["bls_echoes"] > 0 and c["bls_echo_errors"] == 0
    assert any(b["slo"] == "bls_agg_p99_ms" and b["ok"]
               for b in v["slo"]["results"])
    cu = v["catchup"][0]
    assert cu["rejoined"] and cu["heights_applied"] > 0
    # the shared verifier saw both consensus-priority and ingress traffic
    # (this short smoke's catchup gap sits under the device threshold, so
    # the replay lane goes through the sequential path — SOAK_r01's
    # 1000+-height gap covers the device replay lane)
    assert v["lane_counts"]["consensus"] > 0
    assert v["lane_counts"]["ingress"] > 0
    assert v["sampler_ticks"] >= int(duration) - 1  # 1 s cadence


def test_starved_soak_fails_localized_to_ingress(tmp_path):
    """ISSUE 16 satellite: with the deterministic starvation seam armed
    (TM_TPU_INJECT_LINTBUG=starve — the pipeline worker withholds
    ingress-priority dispatch), the soak must fail CONCLUSIVELY: exit 1,
    the abort reason naming ingress admission, the ingress SLO breach
    carrying an observed latency + a concrete breach window, and the
    flight-recorder tail attached to the artifact."""
    out = tmp_path / "soak_starved.json"
    r = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "simnet_run.py"),
            "--soak", "8", "--seed", "7", "--inject-bug", "starve",
            "--soak-out", str(out),
        ],
        capture_output=True,
        env=_env(
            # short admission deadline + tight budget so the starved
            # burst times out (and breaches) in seconds, not minutes
            TM_TPU_SOAK_INGRESS_TIMEOUT_S="2",
            TM_TPU_SOAK_INGRESS_P99_MS="1000",
        ),
        cwd=REPO,
        timeout=60,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 1, f"starved soak did not fail:\n{tail}"
    v = json.loads(out.read_text())
    assert v["ok"] is False
    assert "ingress admission timed out" in v["reason"]
    assert v["counters"]["ingress_timeouts"] > 0
    assert v["counters"]["ingress_admitted"] == 0

    breaches = {b["slo"]: b for b in v["slo"]["breaches"]}
    ing = breaches["ingress_admission_p99_ms"]
    assert ing["lane"] == "ingress"
    # localization: observed latency == the admission deadline, and a
    # concrete worst window to point an operator at
    assert ing["observed"] is not None and ing["observed"] >= 1000.0
    bw = ing["breach_window"]
    assert bw and bw["t1"] > bw["t0"] and bw["count"] > 0
    # the ingress breach is the ONLY one with a localized window — the
    # other lanes breach as starved/idle because fail-fast ends the run
    # before they accrue samples (downstream of the same root cause)
    for name, b in breaches.items():
        if name != "ingress_admission_p99_ms":
            assert not b.get("breach_window"), name

    # conclusive-failure artifact: flight-recorder tail rides along, and
    # the armed devcheck checkers saw no UNRELATED violation (the seam
    # starves scheduling; it must not corrupt state)
    assert v.get("flight_recorder")
    assert (v.get("devcheck") or {}).get("violations") == []
