"""Tier-1 perf ratchet (ISSUE 11 satellite, ROADMAP item 4): every
committed bench artifact kind is gated against a pinned last-good round
through `tools/bench_report.py --compare --gate-pct` — direction-aware,
so a future PR that commits a regressed artifact FAILS tier-1 instead of
silently drifting the record.

Pure stdlib + the in-repo bench_report module: runs in the main tier-1
process without jax, numpy or any crypto wheel.
"""

import json
import os
import re
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools import bench_report  # noqa: E402

PINS_PATH = os.path.join(REPO_ROOT, "tools", "bench_pins.json")


def _pins():
    with open(PINS_PATH) as fh:
        return json.load(fh)


def _latest_of_kind(kind: str):
    """Newest committed artifact of `kind` by round number."""
    rx = re.compile(rf"^{kind.upper()}_r(\d+)\.json$")
    best, best_n = None, -1
    for name in os.listdir(REPO_ROOT):
        m = rx.match(name)
        if m and int(m.group(1)) > best_n:
            best, best_n = name, int(m.group(1))
    return best


def test_pins_file_is_wellformed():
    pins = _pins()
    assert pins["gate_pct"] > 0
    for kind, name in pins["pins"].items():
        path = os.path.join(REPO_ROOT, name)
        assert os.path.exists(path), f"pinned {kind} artifact {name} missing"
        art = bench_report.load(path)
        assert not bench_report.validate(art), f"pinned {name} is invalid"
        assert art["kind"] == kind


@pytest.mark.parametrize(
    "kind",
    ["multichip", "light", "mempool", "blocksync", "votes", "soak",
     "fleet", "schemes", "agg"],
)
def test_ratchet_gate(kind, capsys):
    """--compare pinned-last-good → newest-committed must pass the gate.
    While the pin IS the newest round this is a self-compare (trivially
    green); the moment a newer round is committed, this test is the
    ratchet that refuses a >gate_pct regression on any tracked metric."""
    pins = _pins()
    pin = pins["pins"].get(kind)
    if pin is None:
        pytest.skip(f"no pin for kind {kind}")
    latest = _latest_of_kind(kind)
    assert latest is not None
    rc = bench_report.main([
        "--compare", os.path.join(REPO_ROOT, pin),
        os.path.join(REPO_ROOT, latest),
        "--gate-pct", str(pins["gate_pct"]),
    ])
    out = capsys.readouterr().out
    assert rc == 0, (
        f"{latest} regressed past {pins['gate_pct']}% vs pinned {pin}:\n{out}"
    )


def test_gate_actually_bites(tmp_path):
    """The wiring is only worth tier-1 space if a regression FAILS:
    synthesize a 30%-worse copy of the pinned light artifact and assert
    the same gate invocation exits 1."""
    pins = _pins()
    pin_path = os.path.join(REPO_ROOT, pins["pins"]["light"])
    with open(pin_path) as fh:
        art = json.load(fh)
    art["value"] = art["value"] * 0.7
    bad = tmp_path / "LIGHT_r99.json"
    bad.write_text(json.dumps(art))
    rc = bench_report.main([
        "--compare", pin_path, str(bad),
        "--gate-pct", str(pins["gate_pct"]),
    ])
    assert rc == 1


def test_soak_gate_is_direction_aware(tmp_path):
    """SOAK lane p99s regress on a RISE, replay_heights_per_s on a FALL
    (ISSUE 16): both synthetic regressions must trip the same gate."""
    pins = _pins()
    pin_path = os.path.join(REPO_ROOT, pins["pins"]["soak"])
    with open(pin_path) as fh:
        art = json.load(fh)

    worse_p99 = dict(art)
    worse_p99["ingress_admission_p99_ms"] = (
        (art.get("ingress_admission_p99_ms") or 1.0) * 1.5
    )
    bad = tmp_path / "SOAK_r98.json"
    bad.write_text(json.dumps(worse_p99))
    rc = bench_report.main([
        "--compare", pin_path, str(bad),
        "--gate-pct", str(pins["gate_pct"]),
    ])
    assert rc == 1, "a 50% ingress-admission p99 rise must fail the gate"

    slower_replay = dict(art)
    slower_replay["replay_heights_per_s"] = (
        (art.get("replay_heights_per_s") or 1.0) * 0.5
    )
    bad2 = tmp_path / "SOAK_r99.json"
    bad2.write_text(json.dumps(slower_replay))
    rc = bench_report.main([
        "--compare", pin_path, str(bad2),
        "--gate-pct", str(pins["gate_pct"]),
    ])
    assert rc == 1, "a 50% replay heights/s fall must fail the gate"


def test_schemes_artifact_meets_acceptance_floor():
    """ISSUE 19 acceptance pinned into tier-1: the committed scheme-lane
    artifact must show the 10k-validator secp commit clearing >= 10x the
    per-signature baseline in ONE device launch. bench.py schemes already
    exits nonzero below 10x; this keeps the COMMITTED record honest."""
    latest = _latest_of_kind("schemes")
    assert latest is not None, "no SCHEMES_r*.json committed"
    with open(os.path.join(REPO_ROOT, latest)) as fh:
        art = json.load(fh)
    assert art["vs_per_sig"] >= 10.0
    assert art["launches"] == 1
    assert art["vals"] >= 10_000


def test_agg_artifact_meets_acceptance_floor():
    """ISSUE 20 acceptance pinned into tier-1: the committed
    aggregation-lane artifact must show K commits fused into one
    multi-pairing launch (pairings amortized under 2 per commit) and the
    128-validator aggregated commit within 1/10 of the per-signature
    ed25519 commit on the wire. bench.py bls already exits nonzero past
    these floors; this keeps the COMMITTED record honest."""
    latest = _latest_of_kind("agg")
    assert latest is not None, "no AGG_r*.json committed"
    with open(os.path.join(REPO_ROOT, latest)) as fh:
        art = json.load(fh)
    assert art["pairings_per_commit"] < 2.0
    assert art["wire_ratio_vs_ed25519"] <= 0.10
    assert art["launches"] == 1
    assert art["vals"] >= 128


def test_light_artifact_in_trajectory(capsys):
    """LIGHT_r* renders through --trajectory like every other kind."""
    rc = bench_report.main(["--trajectory"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "light_r01" in out
