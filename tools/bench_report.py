#!/usr/bin/env python
"""bench_report — validate, tabulate and diff the BENCH/MULTICHIP artifacts.

The repo's perf record is the committed `BENCH_r*.json` / `MULTICHIP_r*.
json` files, but their schemas drifted across rounds (driver wrappers,
rc-only failures, a direct artifact in r06) until the cross-PR trajectory
was unextractable. This tool (ISSUE 10 tentpole piece 3) makes the record
mechanical again:

    # schema-check every committed artifact (tier-1 wires this)
    python tools/bench_report.py --validate

    # one row per round: the cross-PR perf trajectory
    python tools/bench_report.py --trajectory

    # diff two artifacts with a percentage regression gate
    python tools/bench_report.py --compare BENCH_r04.json BENCH_r05.json \\
        --gate-pct 10

Canonical schema (SCHEMA_VERSION 1) — what `normalize()` maps EVERY
historical shape onto (the committed artifacts are never rewritten):

    {"schema_version": 1, "kind": "bench"|"multichip", "round": N,
     "ok": bool, "metric": str|None, "value": float|None, "unit": str,
     "metrics": {canonical_key: number, ...}, "notes": [str, ...]}

Known historical shapes:
  * driver wrapper  {"n", "cmd", "rc", "tail", "parsed"}  (BENCH r01+;
    `parsed` is the bench JSON line, None when the round's bench crashed)
  * multichip wrapper  {"n_devices", "ok", "rc", "skipped", "tail"}
    (MULTICHIP r01-r05 — pass/fail smoke, no rates)
  * direct artifact  {"metric", "value", ...}  (MULTICHIP r06+, bench.py
    output lines, `bench.py multichip --out`)

Exit codes: 0 clean, 1 validation failure / regression past the gate,
2 usage error. Pure stdlib — runs without jax, numpy or any crypto wheel.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

SCHEMA_VERSION = 1

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Canonical numeric metric keys, plus the legacy aliases that map onto
# them (the satellite normalizer: old keys → canonical, artifacts stay
# untouched on disk). Higher-is-better unless listed in _LOWER_IS_BETTER.
KEY_ALIASES: Dict[str, str] = {
    # identity for every current bench.py key happens by default; aliases:
    "device_sigs_per_s": "value",
    "sigs_per_s": "value",
    "speedup": "vs_baseline",
}

# numeric keys carried into metrics{} when present (after aliasing)
METRIC_KEYS = (
    "value", "vs_baseline", "host_sigs_per_s", "host_multicore_sigs_per_s",
    "vs_host_multicore", "host_batch_sigs_per_s", "vs_host_batch",
    "kernel_vs_host_batch", "single_commit_sigs_per_s",
    "single_commit_vs_baseline", "device_rtt_ms", "kernel_stream_sigs_per_s",
    "sustained_sigs_per_s", "sustained_vs_baseline", "mixed_curve_sigs_per_s",
    "pipelined_headers_per_s", "simnet_commits_per_s",
    "simnet_churn_commits_per_s", "speedup_2v1", "n_devices",
    # light-service artifacts (LIGHT_r*, ISSUE 11)
    "light_unique_headers_per_s", "light_sequential_headers_per_s",
    "vs_sequential", "memo_hit_ratio",
    # mempool-ingress artifacts (MEMPOOL_r*, ISSUE 13)
    "mempool_seq_sigs_per_s", "commit_p99_unloaded_ms",
    "commit_p99_flood_ms", "flood_latency_ratio", "checktx_preemptions",
    "ingress_windows", "ingress_batch_wait_ms_avg",
    # chain-replay artifacts (BLOCKSYNC_r*, ISSUE 14)
    "replay_seq_heights_per_s", "kernel_serial_heights_per_s",
    "vs_kernel_serial", "range_hit_rate", "fallback_ranges",
    # live-vote-ingress artifacts (VOTES_r*, ISSUE 15)
    "votes_seq_votes_per_s", "window_dups", "memo_hits",
    # soak-harness artifacts (SOAK_r*, ISSUE 16)
    "consensus_commit_p99_ms", "light_verdict_p99_ms",
    "ingress_admission_p99_ms", "replay_heights_per_s",
    # ingress-fabric curve artifacts (LANES_r*, ISSUE 17); the headline
    # "value" is the adaptive policy's flood sigs/s
    "lanes_adaptive_idle_p99_ms", "lanes_adaptive_sigs_per_window",
    "lanes_shallow_flood_sigs_per_s", "lanes_shallow_idle_p99_ms",
    "lanes_shallow_sigs_per_window", "lanes_deep_flood_sigs_per_s",
    "lanes_deep_idle_p99_ms", "adaptive_window_grows",
    "adaptive_window_shrinks",
    # verification-fleet scale-out artifacts (FLEET_r*, ISSUE 18); the
    # headline "value" is the aggregate sigs/s at the largest host count
    "clients",
    # scheme-lane artifacts (SCHEMES_r*, ISSUE 19); the headline "value"
    # is counted secp256k1 commit sigs/s through ONE device launch
    "secp_seq_sigs_per_s", "vs_per_sig", "launches", "sigs_counted",
    # aggregation-lane artifacts (AGG_r*, ISSUE 20); the headline "value"
    # is aggregated BLS commits/s through the fused multi-pairing launch
    "pairings_per_commit", "sigs_replaced_per_pairing",
    "wire_ratio_vs_ed25519", "agg_wire_bytes", "ed25519_wire_bytes",
    "commits",
)

# gate semantics: for these, SMALLER is better (a rise is the regression)
_LOWER_IS_BETTER = {
    "device_rtt_ms", "commit_p99_unloaded_ms", "commit_p99_flood_ms",
    "flood_latency_ratio", "fallback_ranges",
    # soak lane p99s regress on a RISE; replay_heights_per_s (a rate)
    # stays in the default higher-is-better direction
    "consensus_commit_p99_ms", "light_verdict_p99_ms",
    "ingress_admission_p99_ms",
    # lanes-curve idle latencies regress on a RISE
    "lanes_adaptive_idle_p99_ms", "lanes_shallow_idle_p99_ms",
    "lanes_deep_idle_p99_ms",
    # aggregation-lane economics regress on a RISE: more pairings per
    # commit or more wire bytes than the pinned round
    "pairings_per_commit", "wire_ratio_vs_ed25519",
}

# keys a COMPARE tracks by default (rate-like, present across most rounds)
COMPARE_KEYS = (
    "value", "sustained_sigs_per_s", "kernel_stream_sigs_per_s",
    "pipelined_headers_per_s", "mixed_curve_sigs_per_s", "device_rtt_ms",
    "speedup_2v1", "light_unique_headers_per_s", "flood_latency_ratio",
    "vs_kernel_serial", "consensus_commit_p99_ms", "light_verdict_p99_ms",
    "ingress_admission_p99_ms", "replay_heights_per_s",
    "lanes_adaptive_idle_p99_ms", "lanes_adaptive_sigs_per_window",
    "vs_per_sig", "pairings_per_commit", "wire_ratio_vs_ed25519",
)

_NAME_RE = re.compile(
    r"(BENCH|MULTICHIP|LIGHT|MEMPOOL|BLOCKSYNC|VOTES|SOAK|LANES|FLEET"
    r"|SCHEMES|AGG)_r(\d+)",
    re.I)


def _round_kind_from_name(path: str):
    m = _NAME_RE.search(os.path.basename(path))
    if not m:
        return None, None
    return m.group(1).lower(), int(m.group(2))


def _num(v) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


def _collect_metrics(src: dict) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k, v in src.items():
        ck = KEY_ALIASES.get(k, k)
        if ck in METRIC_KEYS:
            n = _num(v)
            if n is not None:
                out[ck] = n
    return out


def normalize(raw: dict, path: str = "") -> dict:
    """Map any committed artifact shape onto the canonical schema."""
    kind, rnd = _round_kind_from_name(path)
    art = {
        "schema_version": SCHEMA_VERSION,
        "kind": kind or "bench",
        "round": rnd,
        "path": os.path.basename(path) if path else "",
        "ok": False,
        "metric": None,
        "value": None,
        "unit": "",
        "mode": "",
        "backend": "",
        "metrics": {},
        "notes": [],
    }
    if not isinstance(raw, dict):
        art["notes"].append("artifact is not a JSON object")
        return art

    if "parsed" in raw and "cmd" in raw:
        # driver wrapper around a bench.py JSON line
        parsed = raw.get("parsed")
        if rnd is None:
            art["round"] = raw.get("n")
        if not isinstance(parsed, dict):
            art["ok"] = False
            art["notes"].append(
                f"bench run produced no parsed JSON line (rc={raw.get('rc')})"
            )
            return art
        art["ok"] = raw.get("rc", 1) == 0
        src = parsed
    elif "n_devices" in raw and "metric" not in raw:
        # legacy multichip smoke wrapper: pass/fail only
        art["kind"] = kind or "multichip"
        art["ok"] = bool(raw.get("ok")) and not raw.get("skipped")
        art["metrics"] = _collect_metrics(raw)
        art["notes"].append("legacy multichip smoke (compile pass/fail, "
                            "no throughput figures)")
        if not art["ok"]:
            art["notes"].append(f"smoke failed (rc={raw.get('rc')})")
        return art
    elif "metric" in raw:
        # direct artifact (MULTICHIP r06+, bench.py line); soak records
        # carry their own SLO verdict in "ok" — honor it
        art["ok"] = bool(raw.get("ok", True))
        src = raw
    else:
        art["notes"].append("unrecognized artifact shape "
                            f"(keys: {sorted(raw)[:8]})")
        return art

    art["metric"] = src.get("metric")
    art["unit"] = src.get("unit", "")
    art["mode"] = src.get("mode", "")
    art["backend"] = src.get("backend", "")
    art["value"] = _num(src.get("value"))
    art["metrics"] = _collect_metrics(src)
    ss = src.get("span_summary")
    if isinstance(ss, dict):
        # tolerate both pre- and post-ISSUE-10 span summaries: absent
        # stats under {"tracing": false} are NOT an error (the satellite
        # contract — better no number than a misleading 0.0)
        art["span_tracing"] = bool(ss.get("tracing", True))
    if src.get("error"):
        art["ok"] = False
        art["notes"].append(str(src["error"]))
    return art


def load(path: str) -> dict:
    try:
        with open(path) as fh:
            raw = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        art = normalize({}, path)
        art["notes"] = [f"unreadable: {e}"]
        art["unreadable"] = True
        return art
    return normalize(raw, path)


def default_paths(root: str = REPO) -> List[str]:
    paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "LIGHT_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "MEMPOOL_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "BLOCKSYNC_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "VOTES_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "SOAK_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "LANES_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "FLEET_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "SCHEMES_r*.json")))
    paths += sorted(glob.glob(os.path.join(root, "AGG_r*.json")))
    return paths


# ---------------------------------------------------------------------------
# validate
# ---------------------------------------------------------------------------


def validate(art: dict) -> List[str]:
    """Schema problems for one normalized artifact. A FAILED round is a
    valid artifact (the record honestly says the round failed); an
    artifact the normalizer cannot even classify is not."""
    probs: List[str] = []
    if art.get("unreadable"):
        probs.append("; ".join(art["notes"]))
        return probs
    if art["kind"] not in ("bench", "multichip", "light", "mempool",
                           "blocksync", "votes", "soak", "lanes", "fleet",
                           "schemes", "agg"):
        probs.append(f"unknown kind {art['kind']!r}")
    if art["round"] is None:
        probs.append("cannot derive the round number (filename or 'n')")
    if any(n.startswith("unrecognized") for n in art["notes"]):
        probs.append("; ".join(art["notes"]))
    if art["ok"]:
        if art["kind"] == "bench" and _num(art["value"]) is None:
            probs.append("ok bench artifact without a numeric value")
        for k, v in art["metrics"].items():
            if _num(v) is None:
                probs.append(f"non-numeric metric {k}={v!r}")
    return probs


# ---------------------------------------------------------------------------
# trajectory
# ---------------------------------------------------------------------------


def _fmt(v, width=10) -> str:
    if v is None:
        return " " * (width - 1) + "-"
    if abs(v) >= 1000:
        return f"{v:>{width},.0f}"
    return f"{v:>{width}.2f}"


def trajectory_rows(arts: List[dict]) -> List[dict]:
    rows = []
    for art in sorted(arts, key=lambda a: (a["kind"], a["round"] or 0)):
        m = art["metrics"]
        rows.append({
            "kind": art["kind"],
            "round": art["round"],
            "ok": art["ok"],
            "value": art["value"] if art["kind"] == "bench"
            else m.get("value"),
            "sustained": m.get("sustained_sigs_per_s"),
            "kernel_stream": m.get("kernel_stream_sigs_per_s"),
            "headers_per_s": m.get("pipelined_headers_per_s"),
            "rtt_ms": m.get("device_rtt_ms"),
            "speedup_2v1": m.get("speedup_2v1"),
            "mode": art["mode"],
            "backend": art["backend"],
            "note": art["notes"][0] if art["notes"] else "",
        })
    return rows


def print_trajectory(rows: List[dict]) -> None:
    hdr = (f"{'artifact':<14} {'ok':<4} {'sigs/s':>10} {'sustained':>10} "
           f"{'kernel':>10} {'hdrs/s':>8} {'rtt ms':>7} {'2v1':>6}  "
           f"{'mode/backend':<24} note")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        name = f"{r['kind']}_r{r['round']:02d}" if r["round"] is not None \
            else r["kind"]
        mb = "/".join(x for x in (r["mode"], r["backend"]) if x)
        print(f"{name:<14} {'yes' if r['ok'] else 'NO':<4} "
              f"{_fmt(r['value'])} {_fmt(r['sustained'])} "
              f"{_fmt(r['kernel_stream'])} {_fmt(r['headers_per_s'], 8)} "
              f"{_fmt(r['rtt_ms'], 7)} {_fmt(r['speedup_2v1'], 6)}  "
              f"{mb:<24} {r['note'][:48]}")


# ---------------------------------------------------------------------------
# compare
# ---------------------------------------------------------------------------


def compare(a: dict, b: dict, gate_pct: float,
            keys=COMPARE_KEYS) -> dict:
    """Diff two normalized artifacts: per-metric delta %, and the list of
    metrics that regressed past `gate_pct` (direction-aware)."""
    rows = []
    regressions = []
    am = dict(a["metrics"])
    bm = dict(b["metrics"])
    if a["value"] is not None:
        am.setdefault("value", a["value"])
    if b["value"] is not None:
        bm.setdefault("value", b["value"])
    for k in keys:
        va, vb = am.get(k), bm.get(k)
        if va is None or vb is None:
            continue
        delta_pct = ((vb - va) / abs(va) * 100.0) if va else 0.0
        worse = -delta_pct if k not in _LOWER_IS_BETTER else delta_pct
        regressed = worse > gate_pct
        rows.append({
            "metric": k, "a": va, "b": vb,
            "delta_pct": round(delta_pct, 2), "regressed": regressed,
        })
        if regressed:
            regressions.append(k)
    return {
        "a": a.get("path") or f"{a['kind']}_r{a['round']}",
        "b": b.get("path") or f"{b['kind']}_r{b['round']}",
        "gate_pct": gate_pct,
        "rows": rows,
        "regressions": regressions,
        "ok": not regressions,
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench_report")
    ap.add_argument("paths", nargs="*",
                    help="artifact files (default: every committed "
                    "BENCH_r*/MULTICHIP_r* at the repo root)")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check the artifacts; exit 1 on problems")
    ap.add_argument("--trajectory", action="store_true",
                    help="print one row per round (the cross-PR record)")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"),
                    help="diff artifact A (baseline) against B")
    ap.add_argument("--gate-pct", type=float, default=10.0,
                    help="--compare: fail when a tracked metric regresses "
                    "by more than this percentage (default 10)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if args.compare:
        a, b = (load(p) for p in args.compare)
        for art, p in ((a, args.compare[0]), (b, args.compare[1])):
            if art.get("unreadable"):
                print(f"error: {p}: {art['notes'][0]}", file=sys.stderr)
                return 2
        res = compare(a, b, args.gate_pct)
        if args.as_json:
            print(json.dumps(res, indent=2))
        else:
            print(f"{res['a']}  →  {res['b']}   (gate {args.gate_pct}%)")
            for r in res["rows"]:
                flag = "  REGRESSED" if r["regressed"] else ""
                print(f"  {r['metric']:<28} {_fmt(r['a'])} → {_fmt(r['b'])} "
                      f"({r['delta_pct']:+.1f}%){flag}")
            if not res["rows"]:
                print("  (no comparable metrics)")
        return 0 if res["ok"] else 1

    paths = args.paths or default_paths()
    if not paths:
        print("error: no artifacts found", file=sys.stderr)
        return 2
    arts = [load(p) for p in paths]

    rc = 0
    if args.validate or not args.trajectory:
        problems = {a["path"] or p: validate(a)
                    for a, p in zip(arts, paths)}
        bad = {k: v for k, v in problems.items() if v}
        if args.as_json:
            print(json.dumps({
                "schema_version": SCHEMA_VERSION,
                "checked": len(arts),
                "ok": not bad,
                "problems": bad,
            }, indent=2))
        else:
            for a in arts:
                name = a["path"]
                ps = problems[name or ""] if name in problems else []
                status = "ok" if not ps else "INVALID: " + "; ".join(ps)
                print(f"{name:<22} {status}")
            print(f"{len(arts)} artifact(s), {len(bad)} invalid")
        if bad:
            rc = 1

    if args.trajectory:
        rows = trajectory_rows(arts)
        if args.as_json:
            print(json.dumps(rows, indent=2))
        else:
            print_trajectory(rows)

    return rc


if __name__ == "__main__":
    sys.exit(main())
