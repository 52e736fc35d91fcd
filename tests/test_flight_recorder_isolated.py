"""Tier-1 flight-recorder coverage for containers without the
`cryptography` wheel (ISSUE 10).

Three layers, same pattern as tests/test_simnet_isolated.py:
  1. Crypto-free unit tests IN PROCESS: trace flow events / per-node
     tracers / merging, the devcheck unbalanced-span canary (+ its
     TM_TPU_INJECT_LINTBUG=span seam), and tools/bench_report.py over
     both synthetic shapes and every committed BENCH/MULTICHIP artifact.
  2. Subprocess acceptance runs under TM_TPU_PUREPY_CRYPTO=1: the
     cluster/RPC suite (tests/test_flight_recorder.py), the
     `simnet_run.py --smoke --trace` merged-trace acceptance, and the
     tracing-disabled overhead guard extended to flow-carrying spans.
  3. The committed-artifact gate: `bench_report --validate` and
     `--trajectory` must exit 0 over everything committed at the root.
"""

import json
import os
import subprocess
import sys

import pytest

from tendermint_tpu.libs import devcheck
from tendermint_tpu.observability import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

sys.path.insert(0, os.path.join(REPO, "tools"))
try:
    import bench_report
finally:
    sys.path.pop(0)


@pytest.fixture(autouse=True)
def _reset_tracer():
    tr.configure(enabled=False)
    tr.TRACER.clear()
    yield
    tr.configure(enabled=False)
    tr.TRACER.clear()


# ---------------------------------------------------------------------------
# trace: flow events, per-node tracers, merging
# ---------------------------------------------------------------------------


class TestFlowEvents:
    def test_span_with_flow_exports_flow_event(self):
        t = tr.SpanTracer(node="n0", now=lambda: 5.0, epoch=0.0)
        t.configure(enabled=True)
        with t.span("a", flow=3, flow_phase="s", k=1):
            pass
        doc = t.export_chrome()
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        flows = [e for e in doc["traceEvents"] if e["ph"] in "stf"]
        assert len(xs) == 1 and len(flows) == 1
        assert xs[0]["args"]["flow"] == 3
        assert xs[0]["args"]["flow_phase"] == "s"
        assert flows[0] == {
            "name": "flow", "cat": "flow", "ph": "s", "id": 3,
            "pid": xs[0]["pid"], "tid": xs[0]["tid"], "ts": xs[0]["ts"],
        }

    def test_finish_phase_binds_enclosing(self):
        t = tr.SpanTracer(node="n0")
        t.configure(enabled=True)
        with t.span("end", flow=9, flow_phase="f"):
            pass
        fev = [e for e in t.export_chrome()["traceEvents"]
               if e["ph"] == "f"][0]
        assert fev["bp"] == "e"

    def test_flow_point_is_instant(self):
        clock = {"t": 1.0}
        t = tr.SpanTracer(node="n1", now=lambda: clock["t"], epoch=0.0)
        t.configure(enabled=True)
        t.flow_point("send", 7, "s", to="x")
        (name, s, e, _tid, args), = t.events()
        assert name == "send" and s == e == 1.0
        assert args["flow"] == 7 and args["to"] == "x"
        # disabled / flow-less points record nothing
        t.flow_point("send", None, "s")
        t.configure(enabled=False)
        t.flow_point("send", 8, "s")
        assert len(t.events()) == 1

    def test_spans_without_flow_unchanged(self):
        tr.configure(enabled=True)
        with tr.span("plain", n=4):
            pass
        doc = tr.TRACER.export_chrome()
        assert [e["ph"] for e in doc["traceEvents"]] == ["X"]
        assert doc["traceEvents"][0]["args"] == {"n": 4}

    def test_next_flow_unique_and_offset(self):
        a, b = tr.next_flow(), tr.next_flow()
        assert a != b and min(a, b) > (1 << 32)

    def test_node_tracer_metadata_and_injected_clock(self):
        clock = {"t": 10.0}
        t = tr.SpanTracer(node="sim7", now=lambda: clock["t"], epoch=10.0)
        t.configure(enabled=True)
        with t.span("work"):
            clock["t"] = 10.5
        doc = t.export_chrome()
        meta = doc["traceEvents"][0]
        assert meta["ph"] == "M" and meta["args"]["name"] == "sim7"
        ev = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
        assert ev["ts"] == 0.0
        assert ev["dur"] == pytest.approx(0.5e6)
        assert ev["pid"] != os.getpid()


class TestMergeTraces:
    def _doc(self, node, flow, phase, name="ev"):
        t = tr.SpanTracer(node=node, now=lambda: 1.0, epoch=0.0)
        t.configure(enabled=True)
        t.flow_point(name, flow, phase)
        return t.export_chrome()

    def test_pids_rekeyed_flow_ids_preserved(self):
        a = self._doc("alpha", 42, "s", "send")
        b = self._doc("beta", 42, "f", "recv")
        m = tr.merge_traces([a, b])
        xs = [e for e in m["traceEvents"] if e["ph"] == "X"]
        pids = {e["pid"] for e in xs}
        assert len(pids) == 2
        chains = tr.flow_chains(m)
        assert list(chains) == [42]
        assert [e["name"] for e in chains[42]] == ["send", "recv"]
        names = {e["args"]["name"] for e in m["traceEvents"]
                 if e["ph"] == "M"}
        assert names == {"alpha", "beta"}

    def test_labels_name_unnamed_docs(self):
        tr.configure(enabled=True)
        with tr.span("global"):
            pass
        g = tr.TRACER.export_chrome()  # no process_name of its own
        m = tr.merge_traces([g], labels=["driver"])
        meta = [e for e in m["traceEvents"] if e["ph"] == "M"]
        assert meta and meta[0]["args"]["name"] == "driver"

    def test_merge_then_summarize(self):
        a = self._doc("n0", 1, "s")
        b = self._doc("n1", 1, "f")
        s = tr.summarize_events(tr.merge_traces([a, b]))
        assert s["ev"]["count"] == 2  # flow/meta events not double-counted

    def test_flow_chains_orders_by_phase(self):
        doc = {"traceEvents": [
            {"ph": "X", "name": "c", "pid": 1, "ts": 5.0,
             "args": {"flow": 1, "flow_phase": "f"}},
            {"ph": "X", "name": "a", "pid": 2, "ts": 9.0,
             "args": {"flow": 1, "flow_phase": "s"}},
            {"ph": "X", "name": "b", "pid": 1, "ts": 7.0,
             "args": {"flow": 1, "flow_phase": "t"}},
        ]}
        chains = tr.flow_chains(doc)
        assert [e["name"] for e in chains[1]] == ["a", "b", "c"]


# ---------------------------------------------------------------------------
# devcheck: unbalanced-span canary + inject seam
# ---------------------------------------------------------------------------


class TestSpanCanary:
    @pytest.fixture(autouse=True)
    def _fresh_devcheck(self):
        was_on = devcheck.enabled()
        devcheck.enable(reset=True)
        yield
        devcheck.reset_state()
        if not was_on:
            devcheck.disable()

    def test_balanced_spans_are_clean(self):
        t = tr.SpanTracer(node="x")
        t.configure(enabled=True)
        with t.span("outer"):
            with t.span("inner"):
                pass
        t.close()  # must not raise
        assert not devcheck.violations()
        assert devcheck.report()["counts"]["span_opens"] == 2
        assert devcheck.report()["open_spans"] == 0

    def test_leaked_span_fires_at_close(self):
        t = tr.SpanTracer(node="x")
        t.configure(enabled=True)
        s = t.span("leaky")
        s.__enter__()  # never exited — the bug class
        with pytest.raises(devcheck.DevcheckViolation, match="leaky"):
            t.close()
        assert devcheck.violations()[0]["kind"] == "unbalanced-span"
        # state cleared: the same leak does not re-report forever
        devcheck._violations.clear()
        t.close()
        assert not devcheck.violations()

    def test_inject_seam_fires(self, monkeypatch):
        """TM_TPU_INJECT_LINTBUG=span: a well-formed `with` leaks its
        balance bookkeeping, and close() must catch it."""
        monkeypatch.setenv("TM_TPU_INJECT_LINTBUG", "span")
        t = tr.SpanTracer(node="x")
        t.configure(enabled=True)
        with t.span("seeded"):
            pass
        assert len(t.events()) == 1  # the span still records
        with pytest.raises(devcheck.DevcheckViolation,
                           match="unbalanced-span|seeded"):
            t.close()

    def test_inject_seam_inert_without_devcheck(self, monkeypatch):
        devcheck.disable()
        monkeypatch.setenv("TM_TPU_INJECT_LINTBUG", "span")
        t = tr.SpanTracer(node="x")
        t.configure(enabled=True)
        with t.span("quiet"):
            pass
        t.close()
        assert not devcheck.violations()

    def test_disable_mid_span_pops_like_devlock(self):
        t = tr.SpanTracer(node="x")
        t.configure(enabled=True)
        with t.span("outer"):
            devcheck.disable()
        devcheck.enable()
        t.close()  # the armed-time open was popped unconditionally
        assert not devcheck.violations()

    def test_zero_cost_when_devcheck_off(self):
        devcheck.disable()
        tr.configure(enabled=True)
        with tr.span("a"):
            pass
        assert devcheck.report()["counts"]["span_opens"] == 0


# ---------------------------------------------------------------------------
# bench_report: normalizer, validate, trajectory, compare gate
# ---------------------------------------------------------------------------


BENCH_WRAPPER = {
    "n": 4, "cmd": "python bench.py", "rc": 0, "tail": "...",
    "parsed": {
        "metric": "verify_commit_10000", "value": 264349.2,
        "unit": "sigs/s", "sustained_sigs_per_s": 264349.2,
        "device_rtt_ms": 64.3, "pipelined_headers_per_s": 1652.0,
        "mode": "stream8", "backend": "tpu",
    },
}


class TestNormalizer:
    def test_bench_wrapper(self):
        art = bench_report.normalize(BENCH_WRAPPER, "BENCH_r04.json")
        assert art["schema_version"] == bench_report.SCHEMA_VERSION
        assert art["kind"] == "bench" and art["round"] == 4
        assert art["ok"] and art["value"] == 264349.2
        assert art["metrics"]["sustained_sigs_per_s"] == 264349.2
        assert not bench_report.validate(art)

    def test_failed_round_is_valid_but_not_ok(self):
        art = bench_report.normalize(
            {"n": 1, "cmd": "x", "rc": 1, "tail": "boom", "parsed": None},
            "BENCH_r01.json",
        )
        assert not art["ok"] and art["value"] is None
        assert not bench_report.validate(art), "an honest failure is valid"

    def test_legacy_multichip_wrapper(self):
        art = bench_report.normalize(
            {"n_devices": 8, "ok": True, "rc": 0, "skipped": False,
             "tail": ""},
            "MULTICHIP_r02.json",
        )
        assert art["kind"] == "multichip" and art["ok"]
        assert art["metrics"]["n_devices"] == 8
        assert not bench_report.validate(art)

    def test_direct_artifact_and_key_alias(self):
        art = bench_report.normalize(
            {"metric": "m", "device_sigs_per_s": 99.0, "unit": "sigs/s"},
            "MULTICHIP_r06.json",
        )
        assert art["ok"]
        assert art["metrics"]["value"] == 99.0  # old key -> canonical

    def test_unrecognized_shape_fails_validation(self):
        art = bench_report.normalize({"bogus": 1}, "BENCH_r09.json")
        assert bench_report.validate(art)

    def test_tracing_false_span_summary_tolerated(self):
        raw = dict(BENCH_WRAPPER)
        raw["parsed"] = dict(raw["parsed"], span_summary={"tracing": False})
        art = bench_report.normalize(raw, "BENCH_r07.json")
        assert art["span_tracing"] is False
        assert not bench_report.validate(art)


class TestCompareGate:
    def test_regression_past_gate_fails(self):
        a = bench_report.normalize(BENCH_WRAPPER, "BENCH_r04.json")
        raw_b = dict(BENCH_WRAPPER)
        raw_b["parsed"] = dict(
            raw_b["parsed"], value=150000.0, sustained_sigs_per_s=150000.0
        )
        b = bench_report.normalize(raw_b, "BENCH_r05.json")
        res = bench_report.compare(a, b, gate_pct=10.0)
        assert not res["ok"]
        assert "value" in res["regressions"]
        assert "device_rtt_ms" not in res["regressions"]

    def test_within_gate_passes_and_rtt_is_lower_better(self):
        a = bench_report.normalize(BENCH_WRAPPER, "BENCH_r04.json")
        raw_b = dict(BENCH_WRAPPER)
        raw_b["parsed"] = dict(
            raw_b["parsed"], value=260000.0, sustained_sigs_per_s=260000.0,
            device_rtt_ms=80.0,
        )
        b = bench_report.normalize(raw_b, "BENCH_r05.json")
        res = bench_report.compare(a, b, gate_pct=10.0)
        assert res["regressions"] == ["device_rtt_ms"]  # a RISE regressed


class TestCommittedArtifacts:
    """The satellite/acceptance gate: every artifact committed at the repo
    root validates, and --trajectory renders one row per round, exit 0."""

    def test_defaults_find_all_committed_artifacts(self):
        paths = bench_report.default_paths()
        assert len(paths) >= 10, paths
        assert any("MULTICHIP_r06" in p for p in paths)

    def test_validate_exit_0(self, capsys):
        assert bench_report.main(["--validate"]) == 0
        out = capsys.readouterr().out
        assert "0 invalid" in out

    def test_trajectory_exit_0_one_row_per_artifact(self, capsys):
        assert bench_report.main(["--trajectory"]) == 0
        out = capsys.readouterr().out
        n = len(bench_report.default_paths())
        rows = [ln for ln in out.splitlines()
                if ln.startswith(("bench_r", "multichip_r", "light_r",
                                  "mempool_r", "blocksync_r", "votes_r",
                                  "soak_r", "lanes_r", "fleet_r",
                                  "schemes_r", "agg_r"))]
        assert len(rows) == n, out

    def test_trajectory_json_mode(self, capsys):
        assert bench_report.main(["--trajectory", "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert {r["kind"] for r in rows} == {"multichip", "light",
                                             "mempool", "blocksync", "votes",
                                             "soak", "lanes", "fleet",
                                             "schemes", "agg"}
        m6 = next(r for r in rows
                  if r["kind"] == "multichip" and r["round"] == 6)
        assert m6["speedup_2v1"] == pytest.approx(1.95, abs=0.01)

    def test_cli_compare_gate_exit_codes(self, tmp_path):
        a = tmp_path / "BENCH_r90.json"
        b = tmp_path / "BENCH_r91.json"
        raw_b = dict(BENCH_WRAPPER)
        raw_b["parsed"] = dict(raw_b["parsed"], value=100.0)
        a.write_text(json.dumps(BENCH_WRAPPER))
        b.write_text(json.dumps(raw_b))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py"),
             "--compare", str(a), str(b), "--gate-pct", "5"],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert r.returncode == 1, r.stdout
        assert "REGRESSED" in r.stdout
        r2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py"),
             "--compare", str(a), str(a)],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert r2.returncode == 0, r2.stdout

    def test_cli_usage_error(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py"),
             "/nonexistent/dir/*.json"],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert r.returncode == 1  # unreadable artifact is a finding


# ---------------------------------------------------------------------------
# subprocess acceptance (purepy; env must not leak into this interpreter)
# ---------------------------------------------------------------------------


def _purepy_env(**extra):
    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_flight_recorder_suite_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_flight_recorder runs directly")
    except ModuleNotFoundError:
        pass
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(HERE, "test_flight_recorder.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True, env=_purepy_env(), cwd=REPO, timeout=60,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated flight-recorder run failed:\n{tail}"


def test_smoke_exports_merged_trace_with_cross_node_chain(tmp_path):
    """THE acceptance criterion: `simnet_run.py --smoke --trace` exports
    one merged Chrome trace containing at least one cross-node flow chain
    (gossip send → deliver → verify dispatch) and its verdict carries a
    populated height_timelines ring — while staying replay-exact."""
    trace_path = str(tmp_path / "merged.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "simnet_run.py"),
         "--smoke", "--trace", trace_path],
        capture_output=True, env=_purepy_env(), cwd=REPO, timeout=60,
    )
    out = (r.stdout or b"").decode(errors="replace")
    assert r.returncode == 0, f"smoke failed:\n{out[-3000:]}"
    verdict = json.loads(out)
    assert verdict["ok"] and verdict["replay_exact"]
    # populated timeline ring in the report
    tls = verdict["height_timelines"]
    assert tls and tls[-1]["height"] >= 20
    assert any(t.get("phases") for t in tls)
    # ONE merged trace document, flow chain crossing node boundaries
    doc = json.load(open(trace_path))
    procs = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert {"sim0", "sim1", "sim2", "sim3"} <= procs
    chains = tr.flow_chains(doc)
    full = [
        evs for evs in chains.values()
        if [e["name"] for e in evs][0] == "gossip.send"
        and evs[-1]["name"] == "consensus.verify_dispatch"
        and len({e["pid"] for e in evs}) > 1
    ]
    assert full, "no cross-node gossip send -> deliver -> verify chain"


def test_disabled_overhead_guard_covers_flow_spans():
    """The tracing-disabled overhead guard, wired tier-1 without the
    OpenSSL wheel (a subprocess on the pure-Python signer): the best-of-k
    cost of a disabled site, flow-carrying spans and flow points included,
    times the sites one commit crosses on its way through the pipeline
    (counted with the tracer on), under 0.2 % of the 5.0 ms a hub150
    commit takes on the chip (ledger, PR 23). No crypto call's speed is
    the yardstick: that is what made this guard fail when verification
    went native."""
    code = r"""
import sys
sys.path.insert(0, %r)
import _launch_trace as lt

records, _names = lt.traced_commit()
flows = [r for r in records if r[4] and "flow" in r[4]]
assert len(flows) >= 3, flows
print("OK", *lt.assert_off_cost_within_budget(records))
""" % HERE
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, env=_purepy_env(), cwd=REPO, timeout=120,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0 and "OK" in out, f"{out}\n{err[-2000:]}"
