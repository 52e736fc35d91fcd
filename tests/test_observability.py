"""Observability: Prometheus exposition format, the scrape endpoint over
HTTP, span tracer nesting/ring-buffer/export, the trace_report tool, the
tracing-disabled overhead guard, and node-level integration (metrics
server + /dump_trace + /status verify-engine stats + OnStop trace flush).
"""

import json
import os
import re
import time
import urllib.request

import pytest

try:  # signature-backed paths need the OpenSSL wheel or the opt-in
    # pure-Python fallback (TM_TPU_PUREPY_CRYPTO=1, ~3ms/op — fine for
    # the handful of sigs the node tests sign); container images with
    # neither skip those classes and the rest of this suite must pass
    import cryptography  # noqa: F401

    HAVE_WHEEL = True
except ModuleNotFoundError:
    HAVE_WHEEL = False

HAVE_CRYPTO = HAVE_WHEEL or bool(os.environ.get("TM_TPU_PUREPY_CRYPTO"))

needs_crypto = pytest.mark.skipif(
    not HAVE_CRYPTO, reason="no ed25519 implementation available"
)
# the device-kernel tests cold-compile a large XLA program (~25s/shape on
# one CPU core); run them where the full image (OpenSSL wheel) is present
# or when explicitly requested alongside the pure-Python fallback
needs_wheel = pytest.mark.skipif(
    not (HAVE_WHEEL or os.environ.get("TM_TPU_RUN_KERNEL_TESTS")),
    reason="cryptography (OpenSSL wheel) not installed",
)

from tendermint_tpu.libs.metrics import (
    ConsensusMetrics,
    Counter,
    Gauge,
    Histogram,
    MempoolMetrics,
    MetricsServer,
    OpsMetrics,
    P2PMetrics,
    Registry,
    ops_stats,
)
from tendermint_tpu.observability import trace as tr


@pytest.fixture(autouse=True)
def _reset_tracer():
    """Each test starts with a clean, disabled tracer."""
    tr.configure(enabled=False)
    tr.TRACER.clear()
    yield
    tr.configure(enabled=False)
    tr.TRACER.clear()


# ---------------------------------------------------------------------------
# Exposition format
# ---------------------------------------------------------------------------


class TestExpositionFormat:
    def test_help_type_ordering(self):
        reg = Registry("tm")
        c = reg.counter("sub", "events_total", "Events.")
        c.inc(3)
        g = reg.gauge("sub", "depth", "Depth.")
        g.set(2)
        text = reg.expose()
        lines = text.strip().splitlines()
        # every family: HELP line, then TYPE line, then samples
        i = lines.index("# HELP tm_sub_events_total Events.")
        assert lines[i + 1] == "# TYPE tm_sub_events_total counter"
        assert lines[i + 2] == "tm_sub_events_total 3.0"
        j = lines.index("# HELP tm_sub_depth Depth.")
        assert lines[j + 1] == "# TYPE tm_sub_depth gauge"
        assert lines[j + 2] == "tm_sub_depth 2"
        assert text.endswith("\n")

    def test_label_escaping(self):
        c = Counter("c_total")
        c.inc(1, msg='say "hi"\nback\\slash')
        line = [ln for ln in c.expose() if not ln.startswith("#")][0]
        assert line == 'c_total{msg="say \\"hi\\"\\nback\\\\slash"} 1.0'

    def test_counter_labels_sorted_deterministic(self):
        c = Counter("x_total")
        c.inc(1, b="2", a="1")
        c.inc(1, a="1", b="2")
        lines = [ln for ln in c.expose() if not ln.startswith("#")]
        assert lines == ['x_total{a="1",b="2"} 2.0']

    def test_histogram_cumulative_invariant_unlabeled(self):
        h = Histogram("h", buckets=[0.1, 1, 10])
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        lines = h.expose()
        buckets = [ln for ln in lines if ln.startswith("h_bucket")]
        counts = [float(ln.rsplit(" ", 1)[1]) for ln in buckets]
        assert counts == sorted(counts), "buckets must be cumulative"
        assert buckets[-1] == 'h_bucket{le="+Inf"} 4'
        assert "h_sum 55.55" in lines
        assert "h_count 4" in lines

    def test_histogram_label_support(self):
        """The satellite fix: OpsMetrics-style bucket="10240" labels merge
        with the cumulative le label and keep one HELP/TYPE header."""
        h = Histogram("hp_seconds", "Prep.", buckets=[0.01, 0.1], labeled=True)
        h.observe(0.005, bucket="128")
        h.observe(0.05, bucket="128")
        h.observe(0.5, bucket="10240")
        lines = h.expose()
        assert lines.count("# HELP hp_seconds Prep.") == 1
        assert lines.count("# TYPE hp_seconds histogram") == 1
        assert 'hp_seconds_bucket{bucket="128",le="0.01"} 1' in lines
        assert 'hp_seconds_bucket{bucket="128",le="0.1"} 2' in lines
        assert 'hp_seconds_bucket{bucket="128",le="+Inf"} 2' in lines
        assert 'hp_seconds_bucket{bucket="10240",le="+Inf"} 1' in lines
        assert 'hp_seconds_sum{bucket="128"} 0.055' in lines
        assert 'hp_seconds_count{bucket="10240"} 1' in lines
        # per-labelset cumulative invariant
        for label in ("128", "10240"):
            seq = [
                float(ln.rsplit(" ", 1)[1])
                for ln in lines
                if ln.startswith(f'hp_seconds_bucket{{bucket="{label}"')
            ]
            assert seq == sorted(seq)

    def test_unobserved_unlabeled_histogram_exposes_zeroes(self):
        h = Histogram("empty_h", buckets=[1])
        lines = h.expose()
        assert 'empty_h_bucket{le="+Inf"} 0' in lines
        assert "empty_h_count 0" in lines

    def test_metric_set_constructors(self):
        reg = Registry("tendermint")
        ConsensusMetrics(reg)
        MempoolMetrics(reg)
        P2PMetrics(reg)
        OpsMetrics(reg)
        text = reg.expose()
        for fam in (
            "tendermint_consensus_height",
            "tendermint_consensus_block_interval_seconds",
            "tendermint_mempool_size",
            "tendermint_p2p_peers",
            "tendermint_ops_sigs_verified_total",
            "tendermint_ops_host_prep_seconds",
            "tendermint_ops_device_seconds",
            "tendermint_ops_pad_waste_ratio",
        ):
            assert f"# TYPE {fam}" in text, fam


class TestScrapeEndpoint:
    def test_http_scrape_end_to_end(self):
        reg = Registry("tm")
        c = reg.counter("rpc", "requests_total", "Requests.")
        c.inc(7, method="status")
        reg2 = Registry("tm2")
        reg2.gauge("x", "y", "Y.").set(1)
        srv = MetricsServer([reg, reg2], "tcp://127.0.0.1:0")
        srv.start()
        try:
            with urllib.request.urlopen(
                f"http://{srv.listen_addr}/metrics", timeout=5
            ) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
            assert 'tm_rpc_requests_total{method="status"} 7.0' in body
            assert "tm2_x_y 1" in body  # both registries served
        finally:
            srv.stop()

    def test_collect_hook_runs_at_scrape(self):
        reg = Registry("tm")
        g = reg.gauge("mempool", "size", "Size.")
        state = {"n": 0}
        reg.add_collect_hook(lambda: g.set(state["n"]))
        state["n"] = 42
        assert "tm_mempool_size 42" in reg.expose()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disabled_records_nothing(self):
        with tr.span("x", a=1):
            pass
        assert tr.TRACER.events() == []
        assert tr.TRACER.recorded_total == 0

    def test_nesting_containment(self):
        tr.configure(enabled=True)
        with tr.span("parent"):
            with tr.span("child"):
                time.sleep(0.002)
        evs = {name: (s, e) for name, s, e, _, _ in tr.TRACER.events()}
        ps, pe = evs["parent"]
        cs, ce = evs["child"]
        assert ps <= cs and ce <= pe, "child span must nest inside parent"

    def test_ring_buffer_wraparound(self):
        tr.TRACER.configure(capacity=16)
        try:
            tr.configure(enabled=True)
            for i in range(40):
                tr.TRACER.record(f"s{i}", 0.0, 1.0)
            evs = tr.TRACER.events()
            assert len(evs) == 16
            assert [e[0] for e in evs] == [f"s{i}" for i in range(24, 40)]
            assert tr.TRACER.recorded_total == 40
        finally:
            tr.TRACER.configure(capacity=tr.DEFAULT_CAPACITY)

    def test_chrome_export_valid_json(self, tmp_path):
        tr.configure(enabled=True)
        with tr.span("outer", bucket=128):
            with tr.span("inner"):
                pass
        doc = tr.TRACER.export_chrome()
        rt = json.loads(json.dumps(doc))  # JSON-serializable round trip
        assert rt["displayTimeUnit"] == "ms"
        evs = rt["traceEvents"]
        assert len(evs) == 2
        for ev in evs:
            assert ev["ph"] == "X"
            assert set(ev) >= {"name", "ts", "dur", "pid", "tid"}
            assert ev["dur"] >= 0
        assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
        assert {"outer", "inner"} == {e["name"] for e in evs}
        outer = next(e for e in evs if e["name"] == "outer")
        assert outer["args"] == {"bucket": 128}
        # dump() writes the same doc to disk
        path = tr.TRACER.dump(str(tmp_path / "trace.json"))
        assert json.load(open(path)) == doc

    def test_summary_percentiles_and_wall_extent(self):
        doc = {
            "traceEvents": [
                {"name": "host_prep", "ph": "X", "ts": 0.0, "dur": 100.0},
                {"name": "device_wait", "ph": "X", "ts": 100.0, "dur": 850.0},
                {"name": "device_wait", "ph": "X", "ts": 500.0, "dur": 450.0},
            ]
        }
        s = tr.summarize_events(doc)
        assert s["host_prep"]["count"] == 1
        assert s["device_wait"]["count"] == 2
        assert s["device_wait"]["p50_ms"] == pytest.approx(0.65)
        assert s["device_wait"]["total_ms"] == pytest.approx(1.3)
        # host spans say nothing about the device: the wall entry carries
        # the extent and the event count, no utilization guess
        assert s["_wall"] == {"wall_ms": pytest.approx(0.95), "events": 3}

    def test_trace_report_cli(self, tmp_path, capsys):
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        try:
            import trace_report
        finally:
            sys.path.pop(0)
        tr.configure(enabled=True)
        for _ in range(5):
            with tr.span("ops.host_prep"):
                pass
            with tr.span("ops.device_wait"):
                time.sleep(0.001)
        path = tr.TRACER.dump(str(tmp_path / "t.json"))
        assert trace_report.main([path]) == 0
        out = capsys.readouterr().out
        assert "ops.host_prep" in out and "ops.device_wait" in out
        assert "wall clock" in out and "flow chains" in out
        assert "device utilization" not in out
        assert trace_report.main([path, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["ops.device_wait"]["count"] == 5


# ---------------------------------------------------------------------------
# Hot-path coverage + overhead
# ---------------------------------------------------------------------------


def _entries(n, tamper=()):
    from tendermint_tpu.crypto import ed25519

    out = []
    for i in range(n):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        msg = b"obs-%d" % i
        sig = sk.sign(msg)
        if i in tamper:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        out.append((sk.pub_key().bytes(), msg, sig))
    return out


@needs_crypto
class TestHotPathInstrumentation:
    @needs_wheel
    def test_verify_batch_records_spans_and_metrics(self, monkeypatch):
        from tendermint_tpu.libs import metrics as m
        from tendermint_tpu.ops import backend

        monkeypatch.setenv("TM_TPU_PALLAS", "0")
        backend.engine.cache_clear()
        try:
            tr.configure(enabled=True)
            before = m.ops_metrics().sigs_verified.value(path="device")
            res = backend.verify_batch(_entries(8))
            assert res.all()
            assert (
                m.ops_metrics().sigs_verified.value(path="device") == before + 8
            )
            names = {e[0] for e in tr.TRACER.events()}
            assert "ops.host_prep" in names
            assert "ops.device_dispatch" in names
            assert "ops.device_wait" in names
            stats = ops_stats()
            assert stats["sigs_verified_device"] >= 8
            assert "128" in stats["batches_by_bucket"]
            assert 0.0 <= stats["pad_waste_ratio"] <= 1.0
        finally:
            backend.engine.cache_clear()

    @needs_wheel
    def test_span_coverage_of_verify_wall_clock(self, monkeypatch):
        """Acceptance shape: host prep + dispatch + device wait sub-spans
        account for >= 90% of the measured verify_batch wall clock."""
        from tendermint_tpu.ops import backend

        monkeypatch.setenv("TM_TPU_PALLAS", "0")
        backend.engine.cache_clear()
        try:
            entries = _entries(64)
            backend.verify_batch(entries)  # warm: compile outside the trace
            tr.TRACER.clear()
            tr.configure(enabled=True)
            t0 = time.perf_counter()
            with tr.span("wall"):
                backend.verify_batch(entries)
            wall = time.perf_counter() - t0
            parts = sum(
                e - s
                for name, s, e, _, _ in tr.TRACER.events()
                if name in ("ops.host_prep", "ops.device_dispatch",
                            "ops.device_wait")
            )
            assert parts >= 0.90 * wall, (parts, wall)
        finally:
            backend.engine.cache_clear()

    def test_host_fallback_counter(self):
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.libs import metrics as m
        from tendermint_tpu.ops.backend import Ed25519DeviceBatchVerifier

        before = m.ops_metrics().host_fallback.total()
        bv = Ed25519DeviceBatchVerifier()
        sk = ed25519.gen_priv_key(b"\x01" * 32)
        bv.add(sk.pub_key(), b"m", sk.sign(b"m"))
        ok, valid = bv.verify()  # 1 < DEVICE_THRESHOLD -> host path
        assert ok and valid == [True]
        assert m.ops_metrics().host_fallback.total() == before + 1

    @needs_wheel
    def test_tracing_disabled_overhead_guard(self):
        """Tracing off must cost ~nothing where the driver measures it:
        the best-of-k cost of a disabled site (a `with` span with kwargs
        and a flow id, a guarded flow point), times the sites one commit
        crosses (counted by verifying one commit through the pipeline
        with the tracer on, the launch path's hand-off, per-put, kernel/
        readback and resolve sites included), must stay under 0.2 % of
        the 5.0 ms a hub150 commit takes on the chip (ledger, PR 23)."""
        import _launch_trace as lt

        records, _names = lt.traced_commit()
        names = {r[0] for r in records}
        assert {"pipeline.queue_wait.intake", "pipeline.coalesce",
                "pipeline.transfer.put", "pipeline.device_wait.kernel",
                "pipeline.resolve", "ops.pipeline_wait.wake"} <= names
        lt.assert_off_cost_within_budget(records)

    @needs_wheel
    def test_pipeline_records_metrics(self):
        from tendermint_tpu.libs import metrics as m
        from tendermint_tpu.ops.pipeline import AsyncBatchVerifier

        v = AsyncBatchVerifier(depth=2)
        try:
            before = m.ops_metrics().pipeline_coalesced_jobs.total()
            res = v.submit(_entries(6)).result(timeout=120)
            assert res.all()
            assert m.ops_metrics().pipeline_coalesced_jobs.total() > before
        finally:
            v.close()


# ---------------------------------------------------------------------------
# Node integration
# ---------------------------------------------------------------------------


@needs_crypto
class TestNodeIntegration:
    def _single_node(self, tmp_path=None, **instr):
        from tendermint_tpu.abci import KVStoreApplication
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.node import make_node
        from tendermint_tpu.p2p import NodeKey
        from tendermint_tpu.privval import FilePV
        from tendermint_tpu.types import Timestamp
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
        from tests.test_consensus import FAST
        from tendermint_tpu.config import Config

        sk = ed25519.gen_priv_key(bytes([9]) * 32)
        doc = GenesisDoc(
            chain_id="obs-chain",
            genesis_time=Timestamp(seconds=1_700_000_000),
            validators=[
                GenesisValidator(address=b"", pub_key=sk.pub_key(), power=10)
            ],
        )
        cfg = Config()
        cfg.base.home = str(tmp_path) if tmp_path else ""
        cfg.base.db_backend = "memdb"
        cfg.consensus = FAST
        cfg.p2p.laddr = "none"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "tcp://127.0.0.1:0"
        for k, val in instr.items():
            setattr(cfg.instrumentation, k, val)
        if tmp_path:
            cfg.ensure_dirs()
        node = make_node(
            cfg,
            app=KVStoreApplication(),
            genesis=doc,
            priv_validator=FilePV(sk),
            node_key=NodeKey.generate(bytes([88]) * 32),
            with_rpc=True,
        )
        return node

    def test_metrics_server_and_rpc_introspection(self):
        node = self._single_node(tracing=True)
        node.start()
        try:
            node.wait_for_height(2, timeout=60)
            node.mempool.check_tx(b"obs=1")
            # -- /metrics scrape: consensus + ops + mempool series -------
            with urllib.request.urlopen(
                f"http://{node.metrics_server.listen_addr}/metrics", timeout=5
            ) as resp:
                body = resp.read().decode()
            m = re.search(r"^tendermint_consensus_height (\d+)", body, re.M)
            assert m and int(m.group(1)) >= 2
            assert "# TYPE tendermint_ops_sigs_verified_total counter" in body
            assert re.search(r"^tendermint_mempool_size \d", body, re.M)
            assert "tendermint_consensus_block_interval_seconds_bucket" in body
            assert re.search(r"^tendermint_consensus_validators 1", body, re.M)
            # -- RPC: /status verify_engine + /dump_trace ----------------
            from tendermint_tpu.rpc import HTTPClient

            rpc = HTTPClient(node.rpc_server.listen_addr)
            st = rpc.status()
            ve = st["verify_engine"]
            assert ve["tracing"] is True
            assert ve["sigs_verified_host"] + ve["sigs_verified_device"] > 0
            dt = rpc.call("dump_trace")
            assert dt["enabled"] is True
            assert dt["trace"]["traceEvents"], "commit verifies must trace"
            json.dumps(dt["trace"])  # valid JSON document
            names = {e["name"] for e in dt["trace"]["traceEvents"]}
            assert "verify_commit" in names
            summ = rpc.call("dump_trace", summary=True)
            assert "trace" not in summ and "verify_commit" in summ["summary"]
        finally:
            node.stop()
            tr.configure(enabled=False)

    def test_stop_flushes_complete_trace_file(self, tmp_path):
        node = self._single_node(
            tmp_path, tracing=True, trace_dump_path="data/trace.json"
        )
        node.start()
        try:
            node.wait_for_height(1, timeout=60)
        finally:
            node.stop()
            tr.configure(enabled=False)
        path = tmp_path / "data" / "trace.json"
        assert path.exists()
        doc = json.load(open(path))
        assert doc["traceEvents"], "flushed trace must carry the run's spans"
