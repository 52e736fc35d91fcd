"""Metrics — Prometheus-style counters/gauges/histograms.

Reference parity: the go-kit metric sets wired in node/setup.go
defaultMetricsProvider (internal/consensus/metrics.go:8+, p2p/mempool/
state/proxy metric sets) and the Prometheus scrape endpoint from the
instrumentation config. Text exposition format, stdlib HTTP server.

Beyond the reference: `OpsMetrics` — the device verification engine's
metric set (sigs verified, batches by bucket, pad waste, host-prep vs
device-seconds histograms) — lives on a process-wide registry
(`global_registry()`), because the device engine is shared by every node
in the process; a node's MetricsServer serves both its own registry and
the global one.
"""

from __future__ import annotations

import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple

from . import devcheck as _devcheck


def _escape_label_value(v: str) -> str:
    """Prometheus text-format label value escaping: backslash, quote, LF."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(pairs: Tuple[Tuple[str, str], ...]) -> str:
    """('a','1'),('b','x') -> 'a="1",b="x"' (values escaped)."""
    return ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in pairs)


class _Metric:
    def __init__(self, name: str, help_: str, typ: str):
        self.name = name
        self.help = help_
        self.type = typ
        self._values: Dict[Tuple, float] = {}
        # devcheck-instrumented under TM_TPU_DEVCHECK=1 (plain Lock off):
        # metric locks sit at the BOTTOM of the lock-order graph — any
        # acquisition of another lock while holding one is a cycle risk
        self._mtx = _devcheck.lock("metrics.metric")

    def _key(self, labels: Dict[str, str]) -> Tuple:
        return tuple(sorted((k, str(v)) for k, v in labels.items()))

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.type}"]
        with self._mtx:
            for key in sorted(self._values):
                val = self._values[key]
                if key:
                    out.append(f"{self.name}{{{_fmt_labels(key)}}} {val}")
                else:
                    out.append(f"{self.name} {val}")
        return out

    # -- introspection (for /status verify-engine stats & tests) --------

    def value(self, **labels) -> float:
        with self._mtx:
            return self._values.get(self._key(labels), 0.0)

    def total(self) -> float:
        """Sum over every labelset (e.g. counter total across labels)."""
        with self._mtx:
            return sum(self._values.values())

    def by_label(self) -> Dict[Tuple, float]:
        with self._mtx:
            return dict(self._values)

    def sample(self) -> dict:
        """Plain-dict point-in-time read for Registry.snapshot(): label
        strings (exposition-format, e.g. 'lane="ingress"'; '' for the
        unlabeled series) -> current value. Lock-safe, no text parsing."""
        with self._mtx:
            return {
                "type": self.type,
                "values": {_fmt_labels(k): v for k, v in self._values.items()},
            }


class Counter(_Metric):
    def __init__(self, name: str, help_: str = ""):
        super().__init__(name, help_, "counter")

    def inc(self, delta: float = 1.0, **labels) -> None:
        with self._mtx:
            k = self._key(labels)
            self._values[k] = self._values.get(k, 0.0) + delta


class Gauge(_Metric):
    def __init__(self, name: str, help_: str = ""):
        super().__init__(name, help_, "gauge")

    def set(self, value: float, **labels) -> None:
        with self._mtx:
            self._values[self._key(labels)] = value

    def add(self, delta: float, **labels) -> None:
        with self._mtx:
            k = self._key(labels)
            self._values[k] = self._values.get(k, 0.0) + delta


class Histogram(_Metric):
    """Prometheus histogram with fixed buckets and label support.

    Each labelset gets its own (counts, sum, total) series; exposition
    merges the series labels with the cumulative `le` label per bucket
    line and always ends with the `+Inf` bucket equal to `_count` — the
    cumulative-bucket invariant scrapers check. The unlabeled series is
    pre-created so an unobserved histogram still exposes zeroed lines
    (go-kit/prometheus client behavior).
    """

    def __init__(self, name: str, help_: str = "", buckets=None,
                 labeled: bool = False):
        super().__init__(name, help_, "histogram")
        self.buckets = list(buckets or [0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10])
        # labelset key -> [counts list (len(buckets)+1), sum, total]
        self._series: Dict[Tuple, list] = {}
        if not labeled:
            self._series[()] = [[0] * (len(self.buckets) + 1), 0.0, 0]

    def observe(self, value: float, **labels) -> None:
        key = self._key(labels)
        with self._mtx:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = [[0] * (len(self.buckets) + 1), 0.0, 0]
            s[1] += value
            s[2] += 1
            for i, b in enumerate(self.buckets):
                if value <= b:
                    s[0][i] += 1
                    return
            s[0][-1] += 1

    @staticmethod
    def _fmt_le(b) -> str:
        return str(b)

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._mtx:
            for key in sorted(self._series):
                counts, sum_, total = self._series[key]
                base = _fmt_labels(key)
                cumulative = 0
                for i, b in enumerate(self.buckets):
                    cumulative += counts[i]
                    lbl = (base + "," if base else "") + f'le="{self._fmt_le(b)}"'
                    out.append(f"{self.name}_bucket{{{lbl}}} {cumulative}")
                cumulative += counts[-1]
                lbl = (base + "," if base else "") + 'le="+Inf"'
                out.append(f"{self.name}_bucket{{{lbl}}} {cumulative}")
                suffix = f"{{{base}}}" if base else ""
                out.append(f"{self.name}_sum{suffix} {sum_}")
                out.append(f"{self.name}_count{suffix} {total}")
        return out

    # -- introspection --------------------------------------------------
    # _Metric.value()/by_label() read _values, which a histogram never
    # writes — override them onto _series so the Counter/Gauge-shaped API
    # returns observation counts instead of silent zeros.

    def value(self, **labels) -> float:
        """Observation count for the labelset (use snapshot() for sums)."""
        with self._mtx:
            s = self._series.get(self._key(labels))
            return float(s[2]) if s else 0.0

    def by_label(self) -> Dict[Tuple, float]:
        with self._mtx:
            return {k: float(s[2]) for k, s in self._series.items()}

    def snapshot(self) -> Dict[Tuple, Tuple[float, int]]:
        """labelset -> (sum, count)."""
        with self._mtx:
            return {k: (s[1], s[2]) for k, s in self._series.items()}

    def total(self) -> float:
        with self._mtx:
            return sum(s[2] for s in self._series.values())

    def sum_all(self) -> float:
        with self._mtx:
            return sum(s[1] for s in self._series.values())

    def sample(self) -> dict:
        """Histogram shape of _Metric.sample(): per-labelset sum/count
        plus raw (non-cumulative) bucket counts, keyed like sample()."""
        with self._mtx:
            return {
                "type": self.type,
                "buckets": list(self.buckets),
                "series": {
                    _fmt_labels(k): {
                        "sum": s[1], "count": s[2], "bucket_counts": list(s[0]),
                    }
                    for k, s in self._series.items()
                },
            }


class Registry:
    def __init__(self, namespace: str = "tendermint"):
        self.namespace = namespace
        self._metrics: List[_Metric] = []
        self._collect_hooks: List[Callable[[], None]] = []
        self._mtx = _devcheck.lock("metrics.registry")

    def counter(self, subsystem: str, name: str, help_: str = "") -> Counter:
        m = Counter(f"{self.namespace}_{subsystem}_{name}", help_)
        with self._mtx:
            self._metrics.append(m)
        return m

    def gauge(self, subsystem: str, name: str, help_: str = "") -> Gauge:
        m = Gauge(f"{self.namespace}_{subsystem}_{name}", help_)
        with self._mtx:
            self._metrics.append(m)
        return m

    def histogram(self, subsystem: str, name: str, help_: str = "",
                  buckets=None, labeled: bool = False) -> Histogram:
        m = Histogram(f"{self.namespace}_{subsystem}_{name}", help_, buckets,
                      labeled=labeled)
        with self._mtx:
            self._metrics.append(m)
        return m

    def add_collect_hook(self, fn: Callable[[], None]) -> None:
        """Run `fn` at the top of every expose() — for pull-style gauges
        (mempool size, connected peers, pipeline queue depth) that are
        cheaper to sample at scrape time than to push on every change."""
        with self._mtx:
            self._collect_hooks.append(fn)

    def expose(self) -> str:
        with self._mtx:
            hooks = list(self._collect_hooks)
            metrics = list(self._metrics)
        for fn in hooks:
            try:
                fn()
            except Exception:  # noqa: BLE001 — a scrape must never 500
                pass
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """Lock-safe structured read of every metric: name -> sample()
        dict. Runs the collect hooks first (same contract as expose(), so
        pull-style gauges are fresh), then reads each metric under its
        own lock. The soak sampler and /status handlers consume this
        instead of re-parsing exposition text; expose() stays the only
        text path and its bytes are untouched."""
        with self._mtx:
            hooks = list(self._collect_hooks)
            metrics = list(self._metrics)
        for fn in hooks:
            try:
                fn()
            except Exception:  # noqa: BLE001 — a snapshot must never throw
                pass
        return {m.name: m.sample() for m in metrics}


class ConsensusMetrics:
    """internal/consensus/metrics.go:19+ — the consensus metric set."""

    def __init__(self, registry: Registry):
        self.height = registry.gauge("consensus", "height", "Height of the chain.")
        self.rounds = registry.gauge("consensus", "rounds", "Round of the chain.")
        self.validators = registry.gauge("consensus", "validators", "Number of validators.")
        self.validators_power = registry.gauge(
            "consensus", "validators_power", "Total power of all validators."
        )
        self.missing_validators = registry.gauge(
            "consensus", "missing_validators", "Validators missing from the last commit."
        )
        self.missing_validators_power = registry.gauge(
            "consensus", "missing_validators_power",
            "Voting power of the missing validators.",
        )
        self.byzantine_validators = registry.gauge(
            "consensus", "byzantine_validators", "Validators that equivocated."
        )
        self.block_interval_seconds = registry.histogram(
            "consensus", "block_interval_seconds", "Time between this and the last block."
        )
        self.num_txs = registry.gauge("consensus", "num_txs", "Txs in the latest block.")
        self.total_txs = registry.counter("consensus", "total_txs", "Total txs committed.")
        self.block_size_bytes = registry.gauge(
            "consensus", "block_size_bytes", "Size of the latest block."
        )
        # per-height latency attribution (ISSUE 10): the HeightTimeline
        # phase durations (propose / prevote / precommit / commit / apply)
        # as one labeled histogram — the 2302.00418-style per-phase
        # breakdown, scrapeable instead of paper-only
        self.phase_seconds = registry.histogram(
            "consensus", "phase_seconds",
            "Consensus phase durations per committed height, by phase "
            "label (propose|prevote|precommit|commit|apply).",
            buckets=[0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0],
            labeled=True,
        )


class VoteIngressMetrics:
    """Live-vote ingress (ISSUE 15): the consensus/vote_ingress.py
    accumulator's device-batching counters. A separate set (not
    ConsensusMetrics) because the accumulator is shared machinery like
    the mempool ingress — benches and multi-node sims use the
    process-wide instance."""

    def __init__(self, registry: Registry):
        self.batches = registry.counter(
            "consensus", "vote_ingress_batches",
            "Vote windows flushed to the device pipeline.",
        )
        self.batch_sigs = registry.counter(
            "consensus", "vote_ingress_sigs",
            "Vote signatures verified through ingress windows.",
        )
        self.batch_wait_ms = registry.histogram(
            "consensus", "vote_ingress_batch_wait_ms",
            "Milliseconds the oldest vote of each window waited before "
            "its flush.",
            buckets=[0.5, 1, 2.5, 5, 10, 25, 50, 100, 250],
        )
        self.memo_hits = registry.counter(
            "consensus", "vote_ingress_memo_hits",
            "Votes answered from the signature memo without re-dispatch "
            "(re-gossiped duplicates).",
        )
        self.sync_fallbacks = registry.counter(
            "consensus", "vote_ingress_sync_fallbacks",
            "Vote windows verified on the host (below "
            "BATCH_VERIFY_THRESHOLD, engine absent, or stepped mode).",
        )
        self.dispatch_errors = registry.counter(
            "consensus", "vote_ingress_dispatch_errors",
            "Vote windows poisoned by a DispatchError and re-driven "
            "through the per-vote fallback.",
        )


class MempoolMetrics:
    """internal/mempool/metrics.go — the mempool metric set. size/
    size_bytes are sampled by a registry collect hook at scrape time; the
    rest are pushed from TxMempool when a metrics set is attached."""

    def __init__(self, registry: Registry):
        self.size = registry.gauge("mempool", "size", "Number of uncommitted txs.")
        self.size_bytes = registry.gauge(
            "mempool", "size_bytes", "Total byte size of uncommitted txs."
        )
        self.tx_size_bytes = registry.histogram(
            "mempool", "tx_size_bytes", "Tx sizes in bytes.",
            buckets=[32, 128, 512, 2048, 8192, 32768, 131072, 1048576],
        )
        self.failed_txs = registry.counter(
            "mempool", "failed_txs", "Txs that failed CheckTx."
        )
        self.evicted_txs = registry.counter(
            "mempool", "evicted_txs", "Txs evicted to make room for higher priority."
        )
        self.recheck_times = registry.counter(
            "mempool", "recheck_times", "Txs rechecked after a block commit."
        )
        # device-batched ingress back-pressure (ISSUE 13): pushed by
        # mempool/ingress.py IngressAccumulator
        self.ingress_queue_depth = registry.gauge(
            "mempool", "ingress_queue_depth",
            "Tx signatures waiting in the ingress accumulator window.",
        )
        self.ingress_batch_wait_ms = registry.histogram(
            "mempool", "ingress_batch_wait_ms",
            "Milliseconds the oldest tx of each ingress batch waited "
            "before its window flushed to the device.",
            buckets=[0.5, 1, 2.5, 5, 10, 25, 50, 100, 250],
        )
        self.checktx_preemptions = registry.counter(
            "mempool", "checktx_preemptions",
            "Queued ingress CheckTx batches bypassed by a higher-priority "
            "consensus batch in the QoS dispatch queue.",
        )


class BlockSyncMetrics:
    """Blocksync catch-up metric set (ISSUE 14): speculation-cache
    accounting for the depth-1 pipelined path plus range-replay counters
    for the ReplayEngine. Pushed from blocksync; surfaced in /status."""

    def __init__(self, registry: Registry):
        self.speculation_hits = registry.counter(
            "blocksync", "speculation_hits",
            "Pre-verified next-height speculations whose device verdict "
            "was usable (height/valset/block hashes all matched).",
        )
        self.speculation_misses = registry.counter(
            "blocksync", "speculation_misses",
            "Heights applied with no speculation available (cold start, "
            "fetch gap, or below the device threshold).",
        )
        self.speculation_discards = registry.counter(
            "blocksync", "speculation_discards",
            "Speculations invalidated before use: height/valset/hash "
            "mismatch, dispatch error, or device timeout.",
        )
        self.replay_ranges = registry.counter(
            "blocksync", "replay_ranges",
            "Epoch ranges verified through the range-batched replay engine.",
        )
        self.replay_heights = registry.counter(
            "blocksync", "replay_heights",
            "Heights whose commit was verified as part of a replay range.",
        )
        self.replay_fallback_heights = registry.counter(
            "blocksync", "replay_fallback_heights",
            "Heights verified per-height (sequential fallback or "
            "sub-threshold range) during replay catch-up.",
        )
        self.replay_fallback_ranges = registry.counter(
            "blocksync", "replay_fallback_ranges",
            "Replay ranges that fell back to sequential verification "
            "(bad commit, prepare failure, or dispatch trouble).",
        )


class IngressMetrics:
    """One ingress fabric (ISSUE 17): the unified per-lane metric set
    pushed by ops/ingress.py IngressEngine. Every series carries a
    `lane` label (mempool|votes|light|replay) — the canonical names for
    what used to be four parallel sets. The old per-workload names
    (mempool_ingress_*, vote_ingress_*) are still written by the lane
    wrappers as ALIASES so /status, soak SLO evaluation, and existing
    dashboards keep working unchanged."""

    def __init__(self, registry: Registry):
        self.queue_depth = registry.gauge(
            "ingress", "queue_depth",
            "Signatures waiting in a lane's open windows, by lane label.",
        )
        self.batch_wait_ms = registry.histogram(
            "ingress", "batch_wait_ms",
            "Milliseconds the oldest item of each window waited before "
            "its flush, by lane label.",
            buckets=[0.5, 1, 2.5, 5, 10, 25, 50, 100, 250],
            labeled=True,
        )
        self.batches = registry.counter(
            "ingress", "batches",
            "Windows flushed through the fabric, by lane label.",
        )
        self.sigs = registry.counter(
            "ingress", "sigs",
            "Signatures flushed through the fabric (windowed + "
            "whole-block), by lane label.",
        )
        self.host_lane_sigs = registry.counter(
            "ingress", "host_lane_sigs",
            "Signatures route_fn-directed to the host lane (schemes "
            "without a device kernel), by lane label.",
        )
        self.sync_fallbacks = registry.counter(
            "ingress", "sync_fallbacks",
            "Windows host-verified as a fallback (sub-threshold, "
            "stepped mode, or engine absent), by lane label.",
        )
        self.dispatch_errors = registry.counter(
            "ingress", "dispatch_errors",
            "Windows poisoned by a DispatchError and handed back for "
            "per-item retry, by lane label.",
        )
        self.remote_fallbacks = registry.counter(
            "ingress", "remote_fallbacks",
            "Windows host-verified because a remote (fleet) verifier "
            "became unavailable after submit, by lane label (ISSUE 18).",
        )
        self.preemptions = registry.counter(
            "ingress", "preemptions",
            "Queued lane batches bypassed by a higher-priority batch in "
            "the QoS dispatch queue, by lane label.",
        )
        self.blocks = registry.counter(
            "ingress", "blocks",
            "Whole-block passthrough submissions (light stages, mempool "
            "recheck, replay fused chunks), by lane label.",
        )
        self.window_ms = registry.gauge(
            "ingress", "window_ms",
            "Current adaptive window length per lane (the controller's "
            "base trigger, before the SLO deadline bound).",
        )
        self.batch_target = registry.gauge(
            "ingress", "batch_target",
            "Current adaptive batch-size trigger per lane.",
        )
        self.deadline_flushes = registry.counter(
            "ingress", "deadline_flushes",
            "Flushes fired early by the SLO deadline bound (budget minus "
            "service-time headroom), by lane label.",
        )


class FleetMetrics:
    """The verification fleet (ISSUE 18): client- and server-side series
    for the network-facing EntryBlock verify service. Client series are
    labeled by `target` (the fleet address as the client knows it);
    server series by `lane` (the client-declared lane name riding the
    wire) or `reason` (frame-reject class). One labeled set serves
    any number of FleetClients/FleetServers in the process — benches and
    simnet runs host both ends."""

    def __init__(self, registry: Registry):
        # -- client side ------------------------------------------------
        self.client_connected = registry.gauge(
            "fleet", "client_connected",
            "1 while the client holds a live fleet connection, 0 while "
            "degraded to local fallback, by target label.",
        )
        self.client_rtt_ewma_ms = registry.gauge(
            "fleet", "client_rtt_ewma_ms",
            "EWMA of submit→verdict round-trip milliseconds, by target.",
        )
        self.client_requests = registry.counter(
            "fleet", "client_requests",
            "SUBMIT frames sent to the fleet, by target label.",
        )
        self.client_timeouts = registry.counter(
            "fleet", "client_timeouts",
            "Requests that hit the fleet deadline and were failed over, "
            "by target label.",
        )
        self.client_fallbacks = registry.counter(
            "fleet", "client_fallbacks",
            "Requests failed with FleetUnavailable (timeout, socket "
            "error, or fleet marked down), by target label.",
        )
        self.client_rejoins = registry.counter(
            "fleet", "client_rejoins",
            "Successful reconnects after a degraded interval, by target.",
        )
        # -- server side ------------------------------------------------
        self.server_connections = registry.gauge(
            "fleet", "server_connections",
            "Client connections currently held by the fleet server.",
        )
        self.server_frames_accepted = registry.counter(
            "fleet", "server_frames_accepted",
            "Well-formed SUBMIT frames accepted, by lane label.",
        )
        self.server_frames_rejected = registry.counter(
            "fleet", "server_frames_rejected",
            "Frames rejected, by reason label "
            "(malformed|version|oversize|closed).",
        )
        self.server_sigs = registry.counter(
            "fleet", "server_sigs",
            "Signatures received for verification, by lane label.",
        )
        self.server_verdicts_streamed = registry.counter(
            "fleet", "server_verdicts_streamed",
            "Verdict frames streamed back in completion order.",
        )
        self.server_dispatch_errors = registry.counter(
            "fleet", "server_dispatch_errors",
            "Requests answered with an ERROR frame because the verifier "
            "raised (DispatchError or submit failure).",
        )


class P2PMetrics:
    """p2p/metrics.go — the router metric set. peers is sampled by a
    registry collect hook at scrape time."""

    def __init__(self, registry: Registry):
        self.peers = registry.gauge("p2p", "peers", "Connected peers.")
        self.peer_receive_bytes_total = registry.counter(
            "p2p", "peer_receive_bytes_total", "Bytes received from peers."
        )
        self.peer_send_bytes_total = registry.counter(
            "p2p", "peer_send_bytes_total", "Bytes sent to peers."
        )


class OpsMetrics:
    """The device verification engine's metric set (ops/backend.py +
    ops/pipeline.py). Batch-labeled series carry a `bucket` label — the
    padded device batch size the batch compiled/dispatched as."""

    # seconds-scale buckets: 1 ms to 2.5 s covers host prep and device
    # batches; the edges are not tuned to this machine
    _TIME_BUCKETS = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5]

    def __init__(self, registry: Registry):
        self.sigs_verified = registry.counter(
            "ops", "sigs_verified_total",
            "Signatures verified, by path label (device|host).",
        )
        self.batches = registry.counter(
            "ops", "batches_total", "Device batches dispatched, by bucket label."
        )
        # the RLC kernel's lane width follows the batch size
        # (ops/pallas_rlc.plan_bucket): which widths a deployment's own
        # traffic runs at, counted where the width is chosen
        self.rlc_launches = registry.counter(
            "ops", "rlc_launches_total",
            "RLC launches prepared, by lane width label m (signatures a lane).",
        )
        self.rlc_sigs = registry.counter(
            "ops", "rlc_sigs_total",
            "Live signatures of RLC launches, by lane width label m.",
        )
        self.rlc_rejected_lanes = registry.counter(
            "ops", "rlc_rejected_lanes_total",
            "RLC lanes the device rejected and the host re-verified "
            "signature by signature, by lane width label m.",
        )
        self.padded_lanes = registry.counter(
            "ops", "padded_lanes_total",
            "Padding lanes dispatched (bucket size minus live signatures).",
        )
        self.pad_waste_ratio = registry.gauge(
            "ops", "pad_waste_ratio", "Pad fraction of the last device batch."
        )
        self.host_prep_seconds = registry.histogram(
            "ops", "host_prep_seconds",
            "Host-side batch prep (pack/hash/limb) seconds, by bucket label.",
            buckets=self._TIME_BUCKETS, labeled=True,
        )
        self.device_seconds = registry.histogram(
            "ops", "device_seconds",
            "Dispatch-to-materialized device seconds, by bucket label.",
            buckets=self._TIME_BUCKETS, labeled=True,
        )
        self.host_fallback = registry.counter(
            "ops", "host_fallback_total",
            "Batches below DEVICE_THRESHOLD verified on the host path.",
        )
        self.dispatch_errors = registry.counter(
            "ops", "dispatch_errors_total",
            "Device batches the dispatcher failed (prep, transfer, table "
            "upload or launch) — each one failed its callers' futures "
            "with a DispatchError.",
        )
        self.pipeline_queue_depth = registry.gauge(
            "ops", "pipeline_queue_depth", "Jobs waiting in the async verifier queue."
        )
        self.pipeline_inflight = registry.gauge(
            "ops", "pipeline_inflight", "Device batches in flight (dispatched, not resolved)."
        )
        self.pipeline_coalesced_jobs = registry.histogram(
            "ops", "pipeline_coalesced_jobs",
            "Jobs fused into one device batch by the coalescing worker.",
            buckets=[1, 2, 4, 8, 16, 32, 64],
        )
        self.dispatch_queue_depth = registry.gauge(
            "ops", "dispatch_queue_depth",
            "Prepared batches waiting for the dispatch-owner thread.",
        )
        self.dispatch_busy_ratio = registry.gauge(
            "ops", "dispatch_busy_ratio",
            "Dispatch-owner thread occupancy (launch time / wall time).",
        )
        # valset epoch cache (ops/epoch_cache.py): hits = warm epochs
        # (committee already device-resident), misses = cold epochs
        # (table registered, first commit rides the uncached path),
        # evictions = LRU pops past TM_TPU_EPOCH_CACHE depth
        self.epoch_cache_hits = registry.counter(
            "ops", "epoch_cache_hits_total",
            "Commit preps that found their validator set device-resident.",
        )
        self.epoch_cache_misses = registry.counter(
            "ops", "epoch_cache_misses_total",
            "Commit preps that registered a new validator-set epoch.",
        )
        self.epoch_cache_evictions = registry.counter(
            "ops", "epoch_cache_evictions_total",
            "Validator-set epochs evicted from the device cache (LRU).",
        )
        # a set never seen may still gather from a resident table
        # (ops/epoch_cache.py map_rows): shared = such misses, patched =
        # the keys they appended to it, built = cold builds of a table
        self.epoch_tables_shared = registry.counter(
            "ops", "epoch_tables_shared_total",
            "Validator sets never seen that mapped onto a resident table.",
        )
        self.epoch_rows_patched = registry.counter(
            "ops", "epoch_rows_patched_total",
            "Public keys appended to resident tables by mapped sets.",
        )
        self.epoch_tables_built = registry.counter(
            "ops", "epoch_tables_built_total",
            "Device tables registered for a set no resident table could take.",
        )
        # wire decode (types/block.py Commit.decode): which path parsed
        # the commit — native = commit_decode_columns took the bytes,
        # python = it answered None (off the canonical shape) or the
        # module is absent; the hit share of a deployment's own traffic
        self.commit_decodes = registry.counter(
            "ops", "commit_decodes_total",
            "Commits decoded from wire bytes, by path label (native|python).",
        )
        # the same for a validator set (types/validator_set.py
        # ValidatorSet.decode; native = valset_decode_columns)
        self.valset_decodes = registry.counter(
            "ops", "valset_decodes_total",
            "Validator sets decoded from wire bytes, by path label "
            "(native|python).",
        )
        # the light client catching up by skipping (light/client.py): hops
        # = verifier.verify calls of the bisection, by outcome; fetched =
        # provider calls that answered; trusting sigs = where the
        # by-address check against the trusted set was verified
        # (types/validation.py verify_commit_light_trusting)
        self.light_hops = registry.counter(
            "ops", "light_hops_total",
            "Bisection attempts of the light client, by outcome label "
            "(verified|refused: not enough trusted power signed).",
        )
        self.light_hops_fused = registry.counter(
            "ops", "light_hops_fused_total",
            "Skipping hops whose trusting and +2/3 checks went to the "
            "device as one submission (light.verifier.verify_non_adjacent).",
        )
        self.light_blocks_fetched = registry.counter(
            "ops", "light_blocks_fetched_total",
            "Light blocks the light client was handed by its providers.",
        )
        self.light_trusting_sigs = registry.counter(
            "ops", "light_trusting_sigs_total",
            "Signatures verified by address against a trusted validator "
            "set, by path label (device|host).",
        )
        # the sr25519 lane (schnorrkel over ristretto255): signatures by
        # where they were verified, and the device launches that carried
        # them; sigs_verified and batches count them too
        self.sr25519_sigs = registry.counter(
            "ops", "sr25519_sigs_total",
            "sr25519 signatures verified, by path label (device|host).",
        )
        self.sr25519_launches = registry.counter(
            "ops", "sr25519_launches_total",
            "Device launches of the sr25519 ristretto kernel.",
        )
        self.h2d_bytes_per_commit = registry.gauge(
            "ops", "h2d_bytes_per_commit",
            "Host bytes shipped to the device by the last dispatched "
            "batch, averaged over its coalesced commits.",
        )
        # device_pool.transfer's device_put calls: h2d_ops / launches is 1
        # on the warm-epoch RLC path (one packed buffer), 4 on the uncached
        self.h2d_ops = registry.counter(
            "ops", "h2d_ops_total",
            "Host-to-device copy operations issued for launch arguments.",
        )
        # overlapped device (ops/pipeline.py dispatcher + ops/device_pool):
        # transfer_overlap_ratio = fraction of H2D transfer time issued
        # while a kernel was in flight (hidden behind compute); the pool
        # counters split slot acquires into recycled vs freshly minted —
        # steady state over one bucket shows misses == pool depth, then
        # hits only (allocations flat)
        self.transfer_overlap_ratio = registry.gauge(
            "ops", "transfer_overlap_ratio",
            "Fraction of recent H2D transfer time hidden behind device "
            "compute (windowed).",
        )
        self.buffer_pool_hits = registry.counter(
            "ops", "buffer_pool_hits_total",
            "Device input-buffer slot acquires served by a recycled slot.",
        )
        self.buffer_pool_misses = registry.counter(
            "ops", "buffer_pool_misses_total",
            "Device input-buffer slot acquires that minted a new slot.",
        )
        # mesh dispatcher (ops/mesh.py + ops/pipeline.py _worker_mesh):
        # lane packing efficiency of the last superbatch launch —
        # occupancy = live signatures / (lanes x lane_bucket), pad waste
        # = identity padding rows / total rows (occupancy + pad = 1; the
        # two gauges are published separately so dashboards can alert on
        # either without arithmetic)
        self.mesh_lane_occupancy = registry.gauge(
            "ops", "mesh_lane_occupancy",
            "Live-signature fraction of the last mesh superbatch's lanes.",
        )
        self.mesh_pad_waste_ratio = registry.gauge(
            "ops", "mesh_pad_waste_ratio",
            "Identity-padding fraction of the last mesh superbatch.",
        )
        # QoS lane queue wait (ISSUE 16): seconds a prepared batch sat in
        # the dispatch queue before winning its launch slot, by lane.
        # Before this, only the consensus lane's wait was observable (via
        # pipeline.queue_wait spans) — ingress starvation was invisible
        # to a scrape.
        self.queue_wait_seconds = registry.histogram(
            "ops", "queue_wait_seconds",
            "Dispatch-queue wait before launch, by QoS lane label "
            "(consensus|replay|ingress).",
            buckets=self._TIME_BUCKETS, labeled=True,
        )


# ---------------------------------------------------------------------------
# Process-wide registry: the device engine is shared by every node in the
# process, so its metrics live here; node MetricsServers serve this
# registry alongside their own.
# ---------------------------------------------------------------------------

# RLock: ops_metrics() calls global_registry() while holding it
_global_mtx = threading.RLock()
_global_registry: Optional[Registry] = None
_global_ops: Optional[OpsMetrics] = None


def global_registry() -> Registry:
    global _global_registry
    with _global_mtx:
        if _global_registry is None:
            _global_registry = Registry("tendermint")
        return _global_registry


def ops_metrics() -> OpsMetrics:
    global _global_ops
    with _global_mtx:
        if _global_ops is None:
            _global_ops = OpsMetrics(global_registry())
        return _global_ops


_global_mempool: Optional["MempoolMetrics"] = None


def mempool_metrics() -> "MempoolMetrics":
    """Process-wide MempoolMetrics for the ingress accumulator when no
    node-attached set exists (benches, tests, multi-node sims sharing one
    device engine). Nodes with instrumentation enabled still build their
    own per-node set; the accumulator uses whichever it was handed."""
    global _global_mempool
    with _global_mtx:
        if _global_mempool is None:
            _global_mempool = MempoolMetrics(global_registry())
        return _global_mempool


_global_vote_ingress: Optional["VoteIngressMetrics"] = None


def vote_ingress_metrics() -> "VoteIngressMetrics":
    """Process-wide VoteIngressMetrics — same sharing rationale as
    mempool_metrics(): many consensus states (simnet nodes, benches) can
    feed one shared device pipeline."""
    global _global_vote_ingress
    with _global_mtx:
        if _global_vote_ingress is None:
            _global_vote_ingress = VoteIngressMetrics(global_registry())
        return _global_vote_ingress


_global_ingress: Optional["IngressMetrics"] = None


def ingress_metrics() -> "IngressMetrics":
    """Process-wide IngressMetrics — the one labeled set behind every
    fabric lane (ops/ingress.py). Same sharing rationale as
    mempool_metrics(): the fabric's scheduler/completer are process
    infrastructure, so its counters live on the process registry."""
    global _global_ingress
    with _global_mtx:
        if _global_ingress is None:
            _global_ingress = IngressMetrics(global_registry())
        return _global_ingress


_global_fleet: Optional["FleetMetrics"] = None


def fleet_metrics() -> "FleetMetrics":
    """Process-wide FleetMetrics — same sharing rationale as
    ingress_metrics(): fleet clients hang off process-shared lanes and a
    fleet server fronts the process-shared verifier, so both ends push
    to the process registry."""
    global _global_fleet
    with _global_mtx:
        if _global_fleet is None:
            _global_fleet = FleetMetrics(global_registry())
        return _global_fleet


def fleet_stats() -> dict:
    """Fleet snapshot for /status — cheap counter reads, no fleet (or
    jax) import; safe to call whether or not a fleet exists (all-zero
    series then)."""
    m = fleet_metrics()

    def _by(metric, label):
        return {
            (dict(k).get(label, "") or "unlabeled"): int(v)
            for k, v in metric.by_label().items()
        }

    def _gauge_by(metric, label):
        return {
            (dict(k).get(label, "") or "unlabeled"): float(v)
            for k, v in metric.by_label().items()
        }

    return {
        "client": {
            "connected": _by(m.client_connected, "target"),
            "rtt_ewma_ms": _gauge_by(m.client_rtt_ewma_ms, "target"),
            "requests": _by(m.client_requests, "target"),
            "timeouts": _by(m.client_timeouts, "target"),
            "fallbacks": _by(m.client_fallbacks, "target"),
            "rejoins": _by(m.client_rejoins, "target"),
        },
        "server": {
            "connections": int(m.server_connections.value()),
            "frames_accepted": _by(m.server_frames_accepted, "lane"),
            "frames_rejected": _by(m.server_frames_rejected, "reason"),
            "sigs": _by(m.server_sigs, "lane"),
            "verdicts_streamed": int(m.server_verdicts_streamed.total()),
            "dispatch_errors": int(m.server_dispatch_errors.total()),
        },
    }


_global_blocksync: Optional["BlockSyncMetrics"] = None


def blocksync_metrics() -> "BlockSyncMetrics":
    """Process-wide BlockSyncMetrics — same sharing rationale as
    mempool_metrics(): the catch-up engine rides the shared device
    pipeline, so its counters live on the process registry."""
    global _global_blocksync
    with _global_mtx:
        if _global_blocksync is None:
            _global_blocksync = BlockSyncMetrics(global_registry())
        return _global_blocksync


def blocksync_stats() -> dict:
    """Blocksync catch-up snapshot for /status — cheap counter reads."""
    m = blocksync_metrics()
    hits = int(m.speculation_hits.total())
    misses = int(m.speculation_misses.total())
    discards = int(m.speculation_discards.total())
    rng = int(m.replay_heights.total())
    seq = int(m.replay_fallback_heights.total())
    return {
        "speculation_hits": hits,
        "speculation_misses": misses,
        "speculation_discards": discards,
        "replay_ranges": int(m.replay_ranges.total()),
        "replay_fallback_ranges": int(m.replay_fallback_ranges.total()),
        "replay_heights": rng,
        "replay_fallback_heights": seq,
        "replay_hit_rate": (rng / (rng + seq)) if (rng + seq) else 0.0,
    }


def _by_width(counter) -> dict:
    """An RLC counter's values keyed by its lane width label."""
    return {dict(k).get("m", ""): int(v)
            for k, v in counter.by_label().items()}


def ops_stats() -> dict:
    """Verify-engine snapshot for /status — no jax import, cheap reads."""
    from .. import native as _native

    m = ops_metrics()
    im = ingress_metrics()
    sigs_device = m.sigs_verified.value(path="device")
    sigs_host = m.sigs_verified.value(path="host")
    padded = m.padded_lanes.total()
    dispatched = sigs_device + padded
    prep_sum = m.host_prep_seconds.sum_all()
    prep_n = m.host_prep_seconds.total()
    return {
        "sigs_verified_device": int(sigs_device),
        "sigs_verified_host": int(sigs_host),
        "batches_by_bucket": {
            (dict(k).get("bucket", "") or "unbucketed"): int(v)
            for k, v in m.batches.by_label().items()
        },
        "rlc_launches_by_width": _by_width(m.rlc_launches),
        "rlc_sigs_by_width": _by_width(m.rlc_sigs),
        "rlc_rejected_lanes_by_width": _by_width(m.rlc_rejected_lanes),
        "pad_waste_ratio": (padded / dispatched) if dispatched else 0.0,
        "host_fallback_batches": int(m.host_fallback.total()),
        "dispatch_errors": int(m.dispatch_errors.total()),
        # every way an ingress-fabric window can end up somewhere other
        # than the device verdict it asked for, summed over lanes
        "ingress_fallbacks": {
            "sync": int(im.sync_fallbacks.total()),
            "remote": int(im.remote_fallbacks.total()),
            "dispatch_errors": int(im.dispatch_errors.total()),
        },
        "host_prep_seconds_avg": (prep_sum / prep_n) if prep_n else 0.0,
        "pipeline_queue_depth": int(m.pipeline_queue_depth.value()),
        "pipeline_inflight": int(m.pipeline_inflight.value()),
        "dispatch_queue_depth": int(m.dispatch_queue_depth.value()),
        "dispatch_busy_ratio": float(m.dispatch_busy_ratio.value()),
        "epoch_cache_hits": int(m.epoch_cache_hits.total()),
        "epoch_cache_misses": int(m.epoch_cache_misses.total()),
        "epoch_cache_evictions": int(m.epoch_cache_evictions.total()),
        "epoch_tables_shared": int(m.epoch_tables_shared.total()),
        "epoch_rows_patched": int(m.epoch_rows_patched.total()),
        "epoch_tables_built": int(m.epoch_tables_built.total()),
        "commit_decode_native": int(m.commit_decodes.value(path="native")),
        "commit_decode_python": int(m.commit_decodes.value(path="python")),
        "valset_decode_native": int(m.valset_decodes.value(path="native")),
        "valset_decode_python": int(m.valset_decodes.value(path="python")),
        "light_hops_verified": int(m.light_hops.value(outcome="verified")),
        "light_hops_refused": int(m.light_hops.value(outcome="refused")),
        "light_hops_fused": int(m.light_hops_fused.total()),
        "light_blocks_fetched": int(m.light_blocks_fetched.total()),
        "light_trusting_sigs_device": int(
            m.light_trusting_sigs.value(path="device")),
        "light_trusting_sigs_host": int(
            m.light_trusting_sigs.value(path="host")),
        "sr25519_sigs_device": int(m.sr25519_sigs.value(path="device")),
        "sr25519_sigs_host": int(m.sr25519_sigs.value(path="host")),
        "sr25519_launches": int(m.sr25519_launches.total()),
        "h2d_bytes_per_commit": float(m.h2d_bytes_per_commit.value()),
        "h2d_ops": int(m.h2d_ops.total()),
        "transfer_overlap_ratio": float(m.transfer_overlap_ratio.value()),
        "buffer_pool_hits": int(m.buffer_pool_hits.total()),
        "buffer_pool_misses": int(m.buffer_pool_misses.total()),
        "mesh_lane_occupancy": float(m.mesh_lane_occupancy.value()),
        "mesh_pad_waste_ratio": float(m.mesh_pad_waste_ratio.value()),
        # per-QoS-lane dispatch-queue wait (ISSUE 16) — sits next to the
        # lane_counts() intake split in /status verify_engine
        "queue_wait_by_lane": {
            (dict(k).get("lane", "") or "unlabeled"): {
                "count": int(c),
                "avg_ms": (s / c * 1000.0) if c else 0.0,
            }
            for k, (s, c) in m.queue_wait_seconds.snapshot().items()
        },
        "cpu_seconds_by_thread": cpu_seconds_by_thread(),
        # {entry: (sections, free_s, wait_s, held)}: the native module's
        # timed entries, sections that gave the GIL up, their seconds
        # without it, seconds waited to win it back, and sections that
        # kept it (native.gil_stats)
        "native_gil": _native.gil_stats(),
    }


# the dispatcher's threads as ops/pipeline.py names them: verify-coalesce,
# verify-dispatch, verify-resolve, verify-prep_<i>
_PIPELINE_THREADS = "verify-"


def cpu_seconds_by_thread() -> dict:
    """CPU seconds so far: "process" (every thread, time.process_time())
    and, by thread name, the live threads of the verify pipeline, each
    from its own CPU clock. Read when a snapshot asks; nothing on the hot
    path keeps it. Threads of one name (two verifiers) add up; a thread
    that has exited is no longer counted."""
    out = {"process": time.process_time()}
    for t in threading.enumerate():
        if not t.name.startswith(_PIPELINE_THREADS) or not t.is_alive():
            continue
        try:
            cpu = time.clock_gettime(time.pthread_getcpuclockid(t.ident))
        except OSError:  # the thread exited under us
            continue
        out[t.name] = out.get(t.name, 0.0) + cpu
    return out


class MetricsServer:
    """The instrumentation scrape endpoint (config.instrumentation).

    Accepts one registry or a list of registries (a node serves its own
    consensus/mempool/p2p registry plus the process-wide ops registry).
    """

    def __init__(self, registry, laddr: str):
        regs = list(registry) if isinstance(registry, (list, tuple)) else [registry]
        addr = laddr.replace("tcp://", "")
        host, _, port = addr.rpartition(":")

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: A003
                pass

            def do_GET(self):  # noqa: N802
                body = "".join(r.expose() for r in regs).encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer((host or "127.0.0.1", int(port)), Handler)

    @property
    def listen_addr(self) -> str:
        h, p = self._httpd.server_address[:2]
        return f"{h}:{p}"

    def start(self) -> None:
        threading.Thread(target=self._httpd.serve_forever, daemon=True).start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
