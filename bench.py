"""Benchmark: VerifyCommit hot path — 10k-validator ed25519 commit.

BASELINE.md north star: device batch verification vs the host per-signature
path (OpenSSL via `cryptography`, the fastest CPU verifier available here;
the reference's Go crypto/batch cannot run in this image — no Go toolchain).

Prints JSON lines of the form
  {"metric": "verify_commit_10k", "value": <device sigs/s>,
   "unit": "sigs/s", "vs_baseline": <device/host speedup>, "backend": ...}
— a "partial" one right after the primary measurement and the full one
last; the last line is the result.

One process per chip: the parent process never imports jax. It starts
exactly ONE worker subprocess (which owns the device for the whole run),
lets it write straight to this process's stdout/stderr, kills it at
TM_TPU_BENCH_WORKER_TIMEOUT and exits with its code. The worker FAILS
when JAX finds no accelerator unless CPU was asked for explicitly
(JAX_PLATFORMS=cpu); a phase that throws is reported, the run prints what
it has, and the exit code is non-zero. A missing chip or a failed phase
is never turned into exit 0.

Timing is end-to-end per batch (host prep: packing + transfer + the device
ladder) — what VerifyCommit actually pays per commit.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

WORKER_TIMEOUT = float(os.environ.get("TM_TPU_BENCH_WORKER_TIMEOUT", "900"))


def main() -> None:
    from tendermint_tpu.libs import jaxcache

    env = jaxcache.set_env(dict(os.environ))
    env["TM_TPU_BENCH_WORKER"] = "1"
    here = os.path.abspath(__file__)
    try:
        rc = subprocess.run(
            [sys.executable, here], env=env, cwd=os.path.dirname(here),
            timeout=WORKER_TIMEOUT,
        ).returncode
    except subprocess.TimeoutExpired:
        print(f"# bench worker killed after {WORKER_TIMEOUT:.0f}s",
              file=sys.stderr)
        rc = 124
    sys.exit(rc)


# ---------------------------------------------------------------------------
# Worker: the actual measurement (runs in a subprocess).
# ---------------------------------------------------------------------------


def _mp_verify_chunk(chunk) -> bool:
    from tendermint_tpu.crypto import ed25519 as _e

    return all(_e.verify_zip215_fast(p, m, s) for p, m, s in chunk)


def _host_multicore_rate(entries) -> float:
    """Strongest-CPU figure the 20x claim gets judged against: per-sig
    OpenSSL verify fanned over every core (the reference's Go batch
    verifier is single-threaded, but a fair host baseline isn't)."""
    import multiprocessing as mp

    nproc = min(mp.cpu_count(), 32)
    chunks = [entries[i::nproc] for i in range(nproc)]
    ctx = mp.get_context("spawn")  # no fork: jax/TPU client is live here
    with ctx.Pool(nproc) as pool:
        pool.map(_mp_verify_chunk, [c[:2] for c in chunks])  # warm imports
        t0 = time.perf_counter()
        oks = pool.map(_mp_verify_chunk, chunks)
    dt = time.perf_counter() - t0
    assert all(oks)
    return len(entries) / dt


def worker() -> None:
    import traceback

    import jax

    backend_kind = jax.default_backend()
    on_accel = backend_kind not in ("cpu",)
    if not on_accel and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(
            "bench.py: JAX found no accelerator (default backend "
            f"{backend_kind!r}). Refusing to record a CPU number under a "
            "device metric; set JAX_PLATFORMS=cpu to ask for the CPU run."
        )
    failures: list = []

    def phase(name: str, fn, default=0.0):
        """One secondary measurement. A throw is reported and recorded;
        the run carries on to print what it has and then exits non-zero."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — reported, then fatal at exit
            traceback.print_exc()
            print(f"# {name} failed: {e!r}", file=sys.stderr)
            failures.append(f"{name}: {e!r}")
            return default
    n_sigs = int(os.environ.get("TM_TPU_BENCH_SIGS", "10000" if on_accel else "512"))
    # the timed loop below feeds one bucket directly (no chunking)
    n_sigs = min(n_sigs, 10240)

    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import backend

    # Build a synthetic 10k-validator commit: unique keys, ~120B canonical
    # vote-sized messages (types/vote.go:93 sign bytes scale).
    entries = []
    msg_pad = b"\x08\x02\x10\x01" + b"p" * 100
    for i in range(n_sigs):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        msg = i.to_bytes(8, "big") + msg_pad
        entries.append((sk.pub_key().bytes(), msg, sk.sign(msg)))

    # Host baseline: per-signature OpenSSL verify (ZIP-215 fast path).
    n_base = min(n_sigs, 2000)
    t0 = time.perf_counter()
    ok = all(
        ed25519.verify_zip215_fast(p, m, s) for p, m, s in entries[:n_base]
    )
    host_s = (time.perf_counter() - t0) / n_base
    assert ok

    # Honest batch baseline: host random-linear-
    # combination batch verification — crypto/ed25519/ed25519.go:192-227
    # semantics — implemented natively (Pippenger MSM over 2n points,
    # native/tm_native.cpp ed25519_batch_verify).
    def _host_batch() -> float:
        from tendermint_tpu.native import load as _load_native

        _native = _load_native()
        if _native is None or not hasattr(_native, "ed25519_batch_verify"):
            raise RuntimeError("tm_native is not built")
        _pubs = b"".join(p for p, _, _ in entries)
        _sigs = b"".join(s for _, _, s in entries)
        _msgs = [m for _, m, _ in entries]
        _native.ed25519_batch_verify(
            _pubs[: 64 * 32], _sigs[: 64 * 64], _msgs[:64]
        )  # warm
        t0 = time.perf_counter()
        ok = _native.ed25519_batch_verify(_pubs, _sigs, _msgs)
        rate = n_sigs / (time.perf_counter() - t0)
        assert ok
        return rate

    host_batch_rate = phase("host RLC batch baseline", _host_batch)

    # Device path: warm up (compile), then steady-state.
    import numpy as _np

    use_pallas = backend.engine().pallas
    bucket = (
        backend._pallas_bucket(n_sigs) if use_pallas else backend._bucket_for(n_sigs)
    )
    t0 = time.perf_counter()
    res = backend.verify_batch(entries)
    warm = time.perf_counter() - t0
    assert bool(res.all()), "all benchmark signatures must verify"

    # Single cold commit: one synchronous end-to-end verify (prep +
    # transfer + kernel + result readback) through the production batch
    # path. This pays one full device round-trip
    # — the latency a lone VerifyCommit call experiences.
    # Span-traced reps: the tracer records host-prep vs device spans so
    # the JSON line carries a per-component breakdown (ISSUE 1 satellite —
    # BENCH_r*.json trajectories get a host/device split, not just a
    # single rate). Record overhead is ~µs on ~100ms ops.
    # TM_TPU_BENCH_TRACE=0 turns the per-rep tracing off; span_summary
    # then honestly reports {"tracing": false} with the stats OMITTED
    # (ISSUE 10 satellite — a 0.0 p50 that means "not measured" poisons
    # every downstream trajectory that averages it).
    from tendermint_tpu.observability import trace as _tr

    trace_on = os.environ.get("TM_TPU_BENCH_TRACE", "1") not in ("", "0")
    if trace_on:
        _tr.TRACER.clear()
        _tr.configure(enabled=True)
    reps = 5 if on_accel else 1
    rep_times = []
    rep_preps = []
    pad_bucket = bucket
    for _ in range(reps):
        prep_t = 0.0
        t0 = time.perf_counter()
        p0 = time.perf_counter()
        if use_pallas and backend.engine().rlc:
            from tendermint_tpu.ops import pallas_rlc

            _b, _g, _blk, _m = pallas_rlc.plan_bucket(n_sigs)
            pad_bucket = _b
            with _tr.span("bench.host_prep", n=n_sigs, bucket=_b):
                args = pallas_rlc.prepare_rlc(entries, _b, _m)
            prep_t += time.perf_counter() - p0
            with _tr.span("bench.device", bucket=_b):
                lanes = pallas_rlc.verify_rlc_compact(
                    *args, _m, block=_blk, interpret=not on_accel
                )
            assert bool(lanes.all())
        elif use_pallas:
            from tendermint_tpu.ops import pallas_verify

            with _tr.span("bench.host_prep", n=n_sigs, bucket=bucket):
                args = pallas_verify.prepare_compact(entries, bucket)
            prep_t += time.perf_counter() - p0
            with _tr.span("bench.device", bucket=bucket):
                pallas_verify.verify_compact(*args, interpret=not on_accel)
        else:
            with _tr.span("bench.host_prep", n=n_sigs, bucket=bucket):
                args = backend.prepare_batch(entries, bucket)
            prep_t += time.perf_counter() - p0
            kern = backend.ed25519_verify.jitted_verify()
            with _tr.span("bench.device", bucket=bucket):
                _np.asarray(kern(*args))
        rep_times.append(time.perf_counter() - t0)
        rep_preps.append(prep_t)
    # median rep: one device hiccup (tens of ms on a ~100ms op) must not
    # distort the recorded latency figure; prep reports the same median
    # statistic so the printed components stay consistent
    import statistics

    single_s = statistics.median(rep_times) / n_sigs
    prep_med = statistics.median(rep_preps)

    _span_stats = _tr.TRACER.summary() if trace_on else {}
    _tr.configure(enabled=False)
    # host_gil_ms_per_commit: estimated GIL-HELD host milliseconds per
    # n_sigs commit prep — the quantity that bounds concurrent
    # verify_commit throughput (the EntryBlock representation's target;
    # not measured on this machine). Estimate = host_prep p50 minus
    # the stages that run GIL-RELEASED in native code (challenges /
    # fused prep) when the native module is loaded; paths without inner
    # spans (prepare_rlc) degrade to the conservative full-prep figure.
    _prep_p50 = _span_stats.get("bench.host_prep", {}).get("p50_ms", 0.0)
    _released_ms = sum(
        _span_stats.get(s, {}).get("p50_ms", 0.0)
        for s in ("ops.challenges", "ops.prep_fused")
    )
    from tendermint_tpu.native import load as _load_native_for_gil

    _gil_ms = _prep_p50 - (
        _released_ms if _load_native_for_gil() is not None else 0.0
    )
    span_summary = {"tracing": False} if not trace_on else {
        "tracing": True,
        "host_prep_ms_p50": round(
            _span_stats.get("bench.host_prep", {}).get("p50_ms", 0.0), 3
        ),
        "host_gil_ms_per_commit": round(max(_gil_ms, 0.0), 3),
        "host_prep_ms_p95": round(
            _span_stats.get("bench.host_prep", {}).get("p95_ms", 0.0), 3
        ),
        "device_ms_p50": round(
            _span_stats.get("bench.device", {}).get("p50_ms", 0.0), 3
        ),
        "device_ms_p95": round(
            _span_stats.get("bench.device", {}).get("p95_ms", 0.0), 3
        ),
        "pad_waste_ratio": round(
            (pad_bucket - n_sigs) / pad_bucket if pad_bucket else 0.0, 4
        ),
        # dispatch-owner split (PR 4): prepared-to-launched wait vs the
        # actual device occupancy of the single dispatch thread — queue
        # growth shows up here, not as caller convoy on the device
        "queue_wait_ms_p50": round(
            _span_stats.get("pipeline.queue_wait", {}).get("p50_ms", 0.0), 3
        ),
        "dispatch_device_ms_p50": round(
            _span_stats.get("pipeline.dispatch", {}).get("p50_ms", 0.0), 3
        ),
    }

    def measure_rtt() -> float:
        """Device round-trip: a trivial device computation fetched
        synchronously — the irreducible latency floor every synchronous
        call pays, and the bench's device-health signal."""
        if not on_accel:
            return 0.0
        one = jax.jit(lambda x: x + 1)
        _np.asarray(one(_np.int32(0)))  # warm
        t0 = time.perf_counter()
        for _ in range(3):
            _np.asarray(one(_np.int32(0)))
        return (time.perf_counter() - t0) / 3 * 1e3

    rtt_ms = measure_rtt()

    # Secondary: kernel-only stream (the figure rounds 3-4 reported as the
    # headline) — prep in a helper thread, async dispatch, depth-3
    # in-flight. Kept as `kernel_stream_sigs_per_s`; the HEADLINE below
    # rides types.verify_commit end to end.
    kern_rate = 0.0
    if on_accel and use_pallas and backend.engine().rlc:
        # pre-compile every coalesced shape BEFORE any timed stream — a
        # fresh ~25s Mosaic compile inside a timed pass reads as a 20x
        # slowdown (burned round-5 measurement time; keep this first)
        from tendermint_tpu.ops import pallas_rlc as _prw

        for _b in _prw.RLC_BUCKETS:
            _wm = _prw.lane_width(_b)
            _prw.verify_rlc_compact(*_prw.prepare_rlc([], _b, _wm), _wm)
    if on_accel and use_pallas:
        from concurrent.futures import ThreadPoolExecutor

        if backend.engine().rlc:
            from tendermint_tpu.ops import pallas_rlc as _pk

            # the production pipeline coalesces concurrent commits to
            # MAX_SIGS per device batch;
            # measure the kernel at that same coalesced scale
            k_entries = (entries * ((_pk.MAX_SIGS + n_sigs - 1) // n_sigs))[
                : _pk.MAX_SIGS
            ]
            rlc_bucket, g, blk, rlc_m = _pk.plan_bucket(len(k_entries))
            f = _pk._jitted_rlc_verify(rlc_m, g, blk, False)
            # kernel_stream is the DEVICE capability figure (transfer +
            # execute steady state); host prep at this scale (~230 ms
            # GIL-mixed) is the headline's cost, not the kernel's — so
            # pre-build DISTINCT args per batch (distinct: jax caches
            # transfers per array object, and reused args would measure
            # execute-only) and keep prep out of the timed loop
            n_batches = 4
            pre = [
                _pk.prepare_rlc(k_entries, rlc_bucket, rlc_m)
                for _ in range(n_batches)
            ]
            prep_fn = None
            kern_sigs = len(k_entries)
        else:
            from tendermint_tpu.ops import pallas_verify as _pk

            f = _pk._jitted_pallas_verify(bucket, _pk.BLOCK, False)
            prep_fn = lambda: _pk.prepare_compact(entries, bucket)  # noqa: E731
            kern_sigs = n_sigs
            n_batches = 8
        with ThreadPoolExecutor(1) as ex:
            t0 = time.perf_counter()
            prep = ex.submit(prep_fn) if prep_fn else None
            inflight = []
            for i in range(n_batches):
                if prep is not None:
                    args = prep.result()
                    if i + 1 < n_batches:
                        prep = ex.submit(prep_fn)
                else:
                    args = pre[i]
                o = f(*args)
                o.copy_to_host_async()
                inflight.append(o)
                if len(inflight) > 3:
                    assert _np.asarray(inflight.pop(0)).all()
            for o in inflight:
                assert _np.asarray(o).all()
            kern_rate = n_batches * kern_sigs / (time.perf_counter() - t0)

    # HEADLINE: types.verify_commit end to end — real
    # Commit + ValidatorSet at n_sigs validators, 8 distinct commits
    # streamed through the DEFAULT verification path (sign-bytes
    # composition, seam dispatch, async pipeline, tally, blame), the way a
    # blocksync/consensus node pays it. Device-health-gated best-of
    #: re-measure when the device RTT is degraded or
    # attempts disagree, keep every attempt in the log.
    sus_rate = 0.0
    attempts: list = []
    if on_accel and use_pallas:
        sus_rate, attempts = phase(
            "verify_commit stream",
            lambda: _bench_verify_commit_stream(
                _build_commit_jobs(n_sigs, n_commits=8), n_sigs,
                measure_rtt, traced=trace_on,
            ),
            default=(0.0, []),
        )
    if attempts:
        # stream-variance accounting: the
        # min/mean/max spread of per-attempt queue-wait and device
        # occupancy across the stream attempts — a tight spread with
        # queue_wait >> dispatch confirms the single dispatch-owner is
        # pacing the device; a wide spread refutes it
        def _spread(key):
            vals = [a.get(key, 0.0) for a in attempts]
            return {
                "min": round(min(vals), 3),
                "mean": round(sum(vals) / len(vals), 3),
                "max": round(max(vals), 3),
            }

        span_summary["stream_rate_spread_sigs_per_s"] = _spread("rate")
        # span-derived spreads exist only when the per-attempt tracer ran
        # — with TM_TPU_BENCH_TRACE=0 the keys are OMITTED, not zeroed
        # (downstream consumers key on presence, bench_report tolerates
        # absence)
        if trace_on:
            span_summary["stream_queue_wait_ms_p50"] = _spread(
                "queue_wait_ms_p50"
            )
            span_summary["stream_dispatch_device_ms_p50"] = _spread(
                "dispatch_device_ms_p50"
            )
            # overlapped-device accounting (ISSUE 7): per-attempt H2D time
            # hidden behind device compute, and the overlap ratio spread —
            # the 0.8x-kernel / <=15%-spread acceptance is checkable from
            # this artifact alone
            span_summary["stream_transfer_hidden_ms"] = _spread(
                "transfer_hidden_ms"
            )
            span_summary["stream_overlap_ratio"] = _spread("overlap_ratio")
            # mesh dispatcher (ISSUE 9): per-attempt lane-packing
            # efficiency (all-zero when TM_TPU_MESH is off — the classic
            # dispatcher records no mesh_pack spans)
            span_summary["stream_mesh_lane_occupancy"] = _spread(
                "mesh_lane_occupancy"
            )
            span_summary["stream_mesh_pad_waste_ratio"] = _spread(
                "mesh_pad_waste_ratio"
            )
    dev_s = 1.0 / sus_rate if sus_rate else single_s

    host_mc = phase("multicore host baseline",
                    lambda: _host_multicore_rate(entries))

    # Print the core result NOW: the driver takes the LAST JSON line, so
    # if a later (secondary) benchmark stalls past the worker timeout the
    # headline number still stands.
    partial = {
        "schema_version": 1,
        "metric": f"verify_commit_{n_sigs}",
        "value": round(1.0 / dev_s, 1),
        "unit": "sigs/s",
        "vs_baseline": round(host_s / dev_s, 3),
        "mode": "verify_commit_stream8" if sus_rate else "single_sync",
        "backend": backend_kind,
        "kernel": ("pallas_rlc" if backend.engine().rlc else "pallas")
        if use_pallas else "xla",
        "host_sigs_per_s": round(1.0 / host_s, 1),
        "host_multicore_sigs_per_s": round(host_mc, 1),
        "host_batch_sigs_per_s": round(host_batch_rate, 1),
        "vs_host_batch": round(1.0 / dev_s / host_batch_rate, 3) if host_batch_rate else 0.0,
        "kernel_vs_host_batch": round(kern_rate / host_batch_rate, 3) if host_batch_rate else 0.0,
        "single_commit_sigs_per_s": round(1.0 / single_s, 1),
        "single_commit_vs_baseline": round(host_s / single_s, 3),
        "device_rtt_ms": round(rtt_ms, 1),
        "kernel_stream_sigs_per_s": round(kern_rate, 1),
        "stream_attempts": attempts,
        "sustained_sigs_per_s": round(sus_rate, 1),
        "sustained_vs_baseline": round(sus_rate * host_s, 3),
        "span_summary": span_summary,
        "partial": True,
    }
    print(json.dumps(partial), flush=True)

    # BASELINE config #5: pipelined adjacent-header verification
    # (light/verifier.go VerifyAdjacent over a fetched range, signature
    # batches double-buffered on the device via ops.pipeline). The
    # primary metric above is already printed if this phase fails.
    hdr_rate = phase("pipelined-header bench",
                     lambda: _bench_pipelined_headers(on_accel))

    # BASELINE config #4: mixed-curve batch (ed25519 device lane +
    # sr25519 device lane + secp256k1 lane). A kernel that fails to
    # compile raises out of ops.mixed and fails this phase.
    mixed_rate = phase("mixed-curve bench", _bench_mixed_curve) if on_accel else 0.0

    # Optional closed-loop consensus probe (TM_TPU_BENCH_SIMNET=1): a
    # 4-node simnet cluster — real state machine + reactor + WAL over the
    # virtual network — measured in committed heights per wall second.
    # This exercises the whole host consensus path (sign, gossip, verify,
    # commit), not just the kernel, so it moves when consensus-side work
    # regresses even if the device rate holds.
    simnet_rate = 0.0
    simnet_churn_rate = 0.0
    if os.environ.get("TM_TPU_BENCH_SIMNET"):
        simnet_rate = phase("simnet bench", _bench_simnet)
        simnet_churn_rate = phase("simnet churn bench", _bench_simnet_churn)

    out = {
        "schema_version": 1,
        "metric": f"verify_commit_{n_sigs}",
        "value": round(1.0 / dev_s, 1),
        "unit": "sigs/s",
        "vs_baseline": round(host_s / dev_s, 3),
        "mode": "verify_commit_stream8" if sus_rate else "single_sync",
        "backend": backend_kind,
        "kernel": ("pallas_rlc" if backend.engine().rlc else "pallas")
        if use_pallas else "xla",
        "host_sigs_per_s": round(1.0 / host_s, 1),
        "host_multicore_sigs_per_s": round(host_mc, 1),
        "vs_host_multicore": round(1.0 / dev_s / host_mc, 3) if host_mc else 0.0,
        "host_batch_sigs_per_s": round(host_batch_rate, 1),
        "vs_host_batch": round(1.0 / dev_s / host_batch_rate, 3) if host_batch_rate else 0.0,
        "kernel_vs_host_batch": round(kern_rate / host_batch_rate, 3) if host_batch_rate else 0.0,
        "single_commit_sigs_per_s": round(1.0 / single_s, 1),
        "single_commit_vs_baseline": round(host_s / single_s, 3),
        "device_rtt_ms": round(rtt_ms, 1),
        "kernel_stream_sigs_per_s": round(kern_rate, 1),
        "stream_attempts": attempts,
        "sustained_sigs_per_s": round(sus_rate, 1),
        "sustained_vs_baseline": round(sus_rate * host_s, 3),
        "mixed_curve_sigs_per_s": round(mixed_rate, 1),
        "pipelined_headers_per_s": round(hdr_rate, 1),
        "simnet_commits_per_s": round(simnet_rate, 2),
        "simnet_churn_commits_per_s": round(simnet_churn_rate, 2),
        "span_summary": span_summary,
        "failed_phases": failures,
    }
    print(json.dumps(out))
    print(
        f"# backend={backend_kind} bucket={bucket} warmup={warm:.1f}s "
        f"host={1.0/host_s:.0f} sigs/s host_mc={host_mc:.0f} sigs/s "
        f"verify_commit_stream={1.0/dev_s:.0f} sigs/s "
        f"kernel_stream={kern_rate:.0f} sigs/s "
        f"single={1.0/single_s:.0f} sigs/s "
        f"rtt={rtt_ms:.0f}ms host_prep={prep_med:.3f}s/batch "
        f"pipelined_headers={hdr_rate:.1f}/s",
        file=sys.stderr,
    )
    if failures:
        sys.exit("bench.py: failed phases: " + "; ".join(failures))


# ---------------------------------------------------------------------------
# `bench.py multichip` — aggregate sigs/s vs lane count (ISSUE 9 (d)).
# ---------------------------------------------------------------------------


def multichip_main(argv) -> None:
    """Drive CONCURRENT commit streams through the mesh dispatcher at
    increasing lane counts and report the aggregate-throughput linearity
    curve (sigs/s vs lanes), per-lane occupancy and pad waste.

    Default mode is the MOCKED mesh: the real
    lane packing, host prep, transfer and demux machinery runs, but the
    launch returns behind a fixed device RTT with per-lane compute
    modeled as parallel (an L-device mesh computes its lanes
    concurrently; this box has one device). The curve therefore isolates
    exactly what the mesh dispatcher contributes — signatures packed per
    device launch vs the dispatcher's own serial host costs. `--real`
    launches the actual kernels instead (the TPU-mesh measurement mode;
    on a single CPU device it measures simulated-lane packing against
    real serial compute and the curve flattens accordingly)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py multichip")
    ap.add_argument("--lanes", default="1,2,4",
                    help="comma-separated lane counts for the curve")
    ap.add_argument("--jobs", type=int, default=24,
                    help="concurrent commit-stream jobs per point")
    ap.add_argument("--job-sigs", type=int, default=1024,
                    help="signatures per job (= lane bucket)")
    ap.add_argument("--rtt-ms", type=float, default=60.0,
                    help="mocked device RTT per superbatch launch")
    ap.add_argument("--reps", type=int, default=2,
                    help="attempts per point (best-of)")
    ap.add_argument("--real", action="store_true",
                    help="launch the real kernels (TPU mesh mode) "
                    "instead of the mocked mesh device")
    ap.add_argument("--hosts", default="",
                    help="fleet scale-out mode (ISSUE 18): comma-separated "
                    "FLEET-HOST counts (e.g. 1,2,4) — one FleetServer + "
                    "verify pipeline per host over real loopback sockets, "
                    "mocked device, clients round-robined across hosts; "
                    "reports fleet_aggregate_sigs_per_s vs host count "
                    "instead of the mesh-lane curve")
    ap.add_argument("--out", default="",
                    help="also write the JSON artifact to this path")
    args = ap.parse_args(argv)

    if args.hosts:
        return _multichip_fleet(args)

    os.environ["TM_TPU_MESH_LANE_BUCKET"] = str(args.job_sigs)

    import numpy as np

    import jax

    from tendermint_tpu.libs.metrics import ops_stats
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, mock_mesh_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock

    rng = np.random.RandomState(3)
    blocks = []
    for t in range(args.jobs):
        n = args.job_sigs
        blocks.append(EntryBlock(
            rng.randint(0, 256, (n, 32), dtype=np.uint8),
            rng.randint(0, 256, (n, 64), dtype=np.uint8),
            bytes(rng.randint(0, 256, 40 * n, dtype=np.uint8)),
            np.arange(0, 40 * (n + 1), 40, dtype=np.int64),
        ))

    orig_prep = pl.AsyncBatchVerifier._prepare_mesh
    if not args.real:
        pl.AsyncBatchVerifier._prepare_mesh = staticmethod(
            mock_mesh_prepare(orig_prep, args.rtt_ms / 1e3)
        )

    def point(lanes: int) -> dict:
        best = None
        for _ in range(max(args.reps, 1)):
            v = pl.AsyncBatchVerifier(depth=3, mesh_lanes=lanes)
            try:
                v.submit(blocks[0][0 : min(64, args.job_sigs)]).result(
                    timeout=600
                )  # warm: compile/trace the shapes off the clock
                # tracing starts AFTER the warm launch so its mesh_pack
                # span does not pollute the timed pass's packing stats
                tr.TRACER.clear()
                tr.configure(enabled=True)
                t0 = time.perf_counter()
                futs = [v.submit(b) for b in blocks]
                for f in futs:
                    f.result(timeout=600)
                dt = time.perf_counter() - t0
                drain_pool(v._pool)
                pool = v._pool.stats()
            finally:
                tr.configure(enabled=False)
                v.close()
            # mesh_pack spans of the timed pass: packing efficiency
            launches = live = total = 0
            lane_buckets = set()
            for name, _s, _e, _tid, sargs in tr.TRACER.events():
                if name != "pipeline.mesh_pack" or not sargs:
                    continue
                launches += 1
                live += int(sargs.get("live", 0))
                total += int(sargs.get("lanes", 0)) * int(
                    sargs.get("lane_bucket", 0)
                )
                lane_buckets.add(int(sargs.get("lane_bucket", 0)))
            s = ops_stats()
            att = {
                "lanes": lanes,
                "sigs_per_s": round(args.jobs * args.job_sigs / dt, 1),
                "wall_s": round(dt, 4),
                "launches": launches,
                # the OBSERVED per-lane bucket(s) — the plan quantizes
                # the lane cap to the ladder, so this can exceed
                # --job-sigs (occupancy below is against this value)
                "lane_bucket": sorted(lane_buckets),
                "mean_occupancy": round(live / total, 4) if total else 0.0,
                "pad_waste_ratio": round(
                    (total - live) / total, 4
                ) if total else 0.0,
                "last_gauge_occupancy": round(
                    s["mesh_lane_occupancy"], 4
                ),
                "pool": pool,
            }
            print(f"# multichip lanes={lanes}: {att['sigs_per_s']:.0f} "
                  f"sigs/s over {launches} launches "
                  f"(occ {att['mean_occupancy']})", file=sys.stderr)
            if best is None or att["sigs_per_s"] > best["sigs_per_s"]:
                best = att
        return best

    try:
        curve = [point(L) for L in
                 sorted({int(x) for x in args.lanes.split(",") if x})]
    finally:
        pl.AsyncBatchVerifier._prepare_mesh = orig_prep

    by_lanes = {c["lanes"]: c["sigs_per_s"] for c in curve}
    base = by_lanes.get(1, curve[0]["sigs_per_s"] if curve else 0.0)
    out = {
        "schema_version": 1,
        "metric": "multichip_aggregate_sigs_per_s",
        "value": curve[-1]["sigs_per_s"] if curve else 0.0,
        "unit": "sigs/s",
        "mode": "real" if args.real else "mocked_mesh",
        "backend": jax.default_backend(),
        "jobs": args.jobs,
        "job_sigs": args.job_sigs,
        "lane_bucket": (curve[-1]["lane_bucket"] if curve else []),
        "mock_rtt_ms": None if args.real else args.rtt_ms,
        "curve": curve,
        "linearity_vs_1_lane": {
            str(k): round(v / base, 3) for k, v in sorted(by_lanes.items())
        } if base else {},
        "speedup_2v1": round(by_lanes.get(2, 0.0) / base, 3) if base else 0.0,
    }
    if not args.real and out["speedup_2v1"] and out["speedup_2v1"] < 1.6:
        print(f"# WARNING: 2-lane aggregate speedup {out['speedup_2v1']} "
              "< 1.6x acceptance bar", file=sys.stderr)
    line = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(out, indent=2) + "\n")
    print(line)


def _multichip_fleet(args) -> None:
    """`bench.py multichip --hosts N`: the verification-fleet scale-out
    curve (ISSUE 18). One FleetServer + its own verify pipeline per
    fleet host, all in this process; eight FleetClient nodes round-robin
    across the hosts over REAL loopback TCP (the full wire codec runs —
    encode, framing, parse, verdict demux). The device is MOCKED per the
    multichip methodology: real ingress, host prep and transfer, but
    each launch's verdict matures --rtt-ms after launch
    (DeadlineReadback), so the curve isolates what multi-host dispatch
    contributes — independent device pipelines draining one cluster's
    verify traffic in parallel. Blocks ride at PRIORITY_INGRESS (fleet
    traffic IS network ingress), whose fuse cap keeps launches
    per-block, so host count — not coalescing luck — moves the curve."""
    import numpy as np

    import jax

    from tendermint_tpu.fleet.client import FleetClient
    from tendermint_tpu.fleet.server import FleetServer
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, mock_vote_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock

    rng = np.random.RandomState(7)
    blocks = []
    for t in range(args.jobs):
        n = args.job_sigs
        blocks.append(EntryBlock(
            rng.randint(0, 256, (n, 32), dtype=np.uint8),
            rng.randint(0, 256, (n, 64), dtype=np.uint8),
            bytes(rng.randint(0, 256, 40 * n, dtype=np.uint8)),
            np.arange(0, 40 * (n + 1), 40, dtype=np.int64),
        ))
    n_clients = 8

    orig_prep = pl.AsyncBatchVerifier._prepare
    pl.AsyncBatchVerifier._prepare = staticmethod(
        mock_vote_prepare(orig_prep, args.rtt_ms / 1e3)
    )

    def point(hosts: int) -> dict:
        best = None
        for _ in range(max(args.reps, 1)):
            vs = [pl.AsyncBatchVerifier(depth=3) for _ in range(hosts)]
            srvs = [FleetServer(verifier=v).start() for v in vs]
            clients = [
                FleetClient(srvs[i % hosts].addr, name=f"bench-{i}",
                            lane="bench", timeout_ms=300_000)
                for i in range(n_clients)
            ]
            try:
                # warm every host pipeline and connection off the clock
                for c in clients:
                    c.submit(blocks[0][0:64], flow=1,
                             priority=pl.PRIORITY_INGRESS).result(timeout=600)
                tr.TRACER.clear()
                tr.configure(enabled=True)
                t0 = time.perf_counter()
                futs = [
                    clients[t % n_clients].submit(
                        b, flow=100 + t, priority=pl.PRIORITY_INGRESS)
                    for t, b in enumerate(blocks)
                ]
                for f in futs:
                    f.result(timeout=600)
                dt = time.perf_counter() - t0
                for v in vs:
                    drain_pool(v._pool)
                leaked = sum(v._pool.stats()["in_flight"] for v in vs)
            finally:
                tr.configure(enabled=False)
                for c in clients:
                    c.close()
                for s in srvs:
                    s.stop()
                for v in vs:
                    v.close()
            launches = sum(1 for name, *_ in tr.TRACER.events()
                           if name == "pipeline.dispatch")
            att = {
                "hosts": hosts,
                "clients": n_clients,
                "sigs_per_s": round(args.jobs * args.job_sigs / dt, 1),
                "wall_s": round(dt, 4),
                "launches": launches,
                "pool_leaked": leaked,
            }
            print(f"# multichip --hosts {hosts}: "
                  f"{att['sigs_per_s']:.0f} sigs/s over {launches} "
                  f"launches ({n_clients} clients)", file=sys.stderr)
            if best is None or att["sigs_per_s"] > best["sigs_per_s"]:
                best = att
        return best

    try:
        curve = [point(H) for H in
                 sorted({int(x) for x in args.hosts.split(",") if x})]
    finally:
        pl.AsyncBatchVerifier._prepare = orig_prep

    by_hosts = {c["hosts"]: c["sigs_per_s"] for c in curve}
    base = by_hosts.get(1, curve[0]["sigs_per_s"] if curve else 0.0)
    out = {
        "schema_version": 1,
        "metric": "fleet_aggregate_sigs_per_s",
        "value": curve[-1]["sigs_per_s"] if curve else 0.0,
        "unit": "sigs/s",
        "mode": "real" if args.real else "mocked_fleet_transport",
        "backend": jax.default_backend(),
        "jobs": args.jobs,
        "job_sigs": args.job_sigs,
        "clients": n_clients,
        "mock_rtt_ms": None if args.real else args.rtt_ms,
        "curve": curve,
        "linearity_vs_1_host": {
            str(k): round(v / base, 3) for k, v in sorted(by_hosts.items())
        } if base else {},
        "speedup_2v1": round(
            by_hosts.get(2, 0.0) / base, 3) if base else 0.0,
    }
    if not args.real and out["speedup_2v1"] and out["speedup_2v1"] < 1.6:
        print(f"# WARNING: 2-host aggregate speedup {out['speedup_2v1']} "
              "< 1.6x acceptance bar", file=sys.stderr)
    line = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(out, indent=2) + "\n")
    print(line)


def _build_commit_jobs(n_vals: int, n_commits: int):
    """Real ValidatorSet + n_commits distinct Commits at n_vals validators
    (unique keys, canonical precommit sign-bytes), for the end-to-end
    verify_commit headline. Commits are built directly from signed
    CommitSigs (VoteSet.add_vote would re-verify every vote during
    setup)."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types import Validator, ValidatorSet, Vote
    from tendermint_tpu.types.block import (
        BlockID, Commit, CommitSig, PartSetHeader, BLOCK_ID_FLAG_COMMIT,
    )
    from tendermint_tpu.types.vote import PRECOMMIT_TYPE
    from tendermint_tpu.wire.canonical import Timestamp

    chain_id = "bench-chain"
    sks, vals = [], []
    for i in range(n_vals):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        sks.append(sk)
        vals.append(Validator.new(sk.pub_key(), 100))
    vset = ValidatorSet.new(vals)
    by_addr = {v.address: sk for sk, v in zip(sks, vals)}
    ordered = [by_addr[v.address] for v in vset.validators]

    jobs = []
    for h in range(1, n_commits + 1):
        bid = BlockID(
            hash=bytes([h]) * 32,
            part_set_header=PartSetHeader(total=1, hash=bytes([h]) * 32),
        )
        ts = Timestamp(seconds=1_600_000_000 + h)
        sigs = []
        for idx, sk in enumerate(ordered):
            v = Vote(
                type=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                timestamp=ts,
                validator_address=vset.validators[idx].address,
                validator_index=idx,
            )
            sigs.append(
                CommitSig(
                    block_id_flag=BLOCK_ID_FLAG_COMMIT,
                    validator_address=vset.validators[idx].address,
                    timestamp=ts,
                    signature=sk.sign(v.sign_bytes(chain_id)),
                )
            )
        commit = Commit(height=h, round=0, block_id=bid, signatures=sigs)
        jobs.append((chain_id, vset, bid, h, commit))
    return jobs


def _bench_verify_commit_stream(jobs, n_sigs: int, measure_rtt,
                                traced: bool = True) -> tuple:
    """Stream the commits through types.verify_commit concurrently (their
    device batches pipeline through the shared AsyncBatchVerifier) and
    return (best_rate, attempts). Device-health gating: retry when the RTT
    exceeds RTT_HEALTHY_MS or the attempt disagrees with the best by >15%
    — one bad-luck device window must not record a 2x-low number.

    Each attempt runs span-traced (cleared per pass) and carries its OWN
    queue_wait_ms_p50 / dispatch_device_ms_p50, so the dispatch-owner
    design is confirmed (or refuted) by the attempt-to-attempt spread,
    not a single aggregate."""
    from concurrent.futures import ThreadPoolExecutor

    from tendermint_tpu.observability import trace as _tr
    from tendermint_tpu.types import validation as _val

    RTT_HEALTHY_MS = float(os.environ.get("TM_TPU_BENCH_RTT_HEALTHY_MS", "90"))
    MIN_ATTEMPTS = int(os.environ.get("TM_TPU_BENCH_STREAM_MIN_ATTEMPTS", "3"))
    MAX_ATTEMPTS = int(os.environ.get("TM_TPU_BENCH_STREAM_ATTEMPTS", "5"))

    def clear_caches() -> None:
        # per-commit sign-bytes template + hash caches: the timed pass
        # must pay the real host composition cost exactly once per commit
        for _, _, _, _, commit in jobs:
            commit._sb_tpl = None
            commit._hash = None

    def transfer_overlap(trace_doc: dict) -> tuple:
        """(hidden_ms, total_ms) over the pass's pipeline.transfer spans
        — hidden=1 marks copies issued while a kernel was in flight."""
        hidden = total = 0.0
        for ev in trace_doc.get("traceEvents", []):
            if ev.get("name") != "pipeline.transfer":
                continue
            dur = float(ev.get("dur", 0.0)) / 1e3
            total += dur
            if (ev.get("args") or {}).get("hidden"):
                hidden += dur
        return hidden, total

    def mesh_pack_stats(trace_doc: dict) -> tuple:
        """(occupancy, pad_waste) over the pass's pipeline.mesh_pack
        spans — (0, 0) when the mesh dispatcher is off (TM_TPU_MESH
        unset). ISSUE 9 satellite: per-attempt lane-packing efficiency
        rides the stream artifact next to the overlap ratios."""
        live = total = 0
        for ev in trace_doc.get("traceEvents", []):
            if ev.get("name") != "pipeline.mesh_pack":
                continue
            a = ev.get("args") or {}
            live += int(a.get("live", 0))
            total += int(a.get("lanes", 0)) * int(a.get("lane_bucket", 0))
        if not total:
            return 0.0, 0.0
        return live / total, (total - live) / total

    def one_pass(traced: bool = False) -> tuple:
        clear_caches()
        if traced:
            _tr.TRACER.clear()
            _tr.configure(enabled=True)
        try:
            with ThreadPoolExecutor(len(jobs)) as ex:
                t0 = time.perf_counter()
                futs = [
                    ex.submit(_val.verify_commit, cid, vs, bid, h, cm)
                    for cid, vs, bid, h, cm in jobs
                ]
                for f in futs:
                    f.result()  # raises on any verification failure
                rate = len(jobs) * n_sigs / (time.perf_counter() - t0)
        finally:
            if traced:
                doc = _tr.TRACER.export_chrome()
                spans = _tr.summarize_events(doc)
                spans["_transfer_overlap"] = transfer_overlap(doc)
                spans["_mesh_pack"] = mesh_pack_stats(doc)
                _tr.configure(enabled=False)
            else:
                spans = {}
        return rate, spans

    one_pass()  # warm: compiles shapes, fills ValidatorSet-level caches
    attempts = []
    for attempt in range(MAX_ATTEMPTS):
        import gc

        gc.collect()  # each pass churns ~100 MB of entry tuples/arrays;
        # collect OUTSIDE the timed window, not during it
        rtt = measure_rtt()
        rate, spans = one_pass(traced=traced)
        att = {"rate": round(rate, 1), "rtt_ms": round(rtt, 1)}
        if traced:
            hidden_ms, transfer_ms = spans.get(
                "_transfer_overlap", (0.0, 0.0)
            )
            occ, pad = spans.get("_mesh_pack", (0.0, 0.0))
            att.update({
                "mesh_lane_occupancy": round(occ, 4),
                "mesh_pad_waste_ratio": round(pad, 4),
                "queue_wait_ms_p50": round(
                    spans.get("pipeline.queue_wait", {}).get("p50_ms", 0.0),
                    3,
                ),
                "dispatch_device_ms_p50": round(
                    spans.get("pipeline.dispatch", {}).get("p50_ms", 0.0), 3
                ),
                # overlapped device (ISSUE 7): how much of this attempt's
                # H2D time rode behind device compute
                "transfer_ms": round(transfer_ms, 3),
                "transfer_hidden_ms": round(hidden_ms, 3),
                "overlap_ratio": round(
                    hidden_ms / transfer_ms if transfer_ms else 0.0, 4
                ),
            })
        attempts.append(att)
        print(f"# verify_commit stream attempt {attempt}: {rate:.0f} sigs/s "
              f"(rtt {rtt:.0f}ms)", file=sys.stderr)
        # best-of over >= MIN_ATTEMPTS passes: batch splits and GIL
        # scheduling are nondeterministic, so single passes scatter.
        # Extra passes (up to MAX) while the device looks unhealthy OR the
        # recent passes still disagree by >15%.
        if len(attempts) >= MIN_ATTEMPTS and rtt <= RTT_HEALTHY_MS:
            recent = [a["rate"] for a in attempts[-MIN_ATTEMPTS:]]
            if max(recent) - min(recent) <= 0.15 * max(recent):
                break
    return max(a["rate"] for a in attempts), attempts


def _bench_mixed_curve() -> float:
    """Mixed 4k set: 2048 ed25519 + 1792 sr25519 + 256 secp256k1 through
    ops.mixed.verify_mixed — the three lanes run concurrently (ed future
    on the shared pipeline + sr device thread + secp host loop), so the
    batch costs max(lanes), not sum. sr25519 signing is pure-Python
    ~10 ms/sig; the set is sized to keep generation inside the worker
    budget."""
    from tendermint_tpu.crypto import ed25519, secp256k1, sr25519
    from tendermint_tpu.ops.mixed import verify_mixed

    entries = []
    for i in range(2048):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        m = b"mx-ed-%d" % i
        entries.append((sk.pub_key(), m, sk.sign(m)))
    srk = sr25519.gen_priv_key(b"\x09" * 32)
    for i in range(1792):
        m = b"mx-sr-%d" % i
        entries.append((srk.pub_key(), m, srk.sign(m)))
    sck = secp256k1.gen_priv_key()
    for i in range(256):
        m = b"mx-secp-%d" % i
        entries.append((sck.pub_key(), m, sck.sign(m)))
    import random

    random.Random(5).shuffle(entries)
    res = verify_mixed(entries)  # warm (compiles both device lanes)
    assert all(res), "mixed batch must verify"
    t0 = time.perf_counter()
    res = verify_mixed(entries)
    dt = time.perf_counter() - t0
    return len(entries) / dt


def _bench_simnet(height: int = 15) -> float:
    """simnet throughput probe: 4 real consensus nodes, fixed seed,
    default links, run to `height`; committed heights per wall second."""
    from tendermint_tpu.simnet import Cluster

    cluster = Cluster(n_nodes=4, seed=1)
    try:
        rep = cluster.run_to_height(height, max_virtual_s=600.0)
    finally:
        cluster.stop()  # closes WALs and removes the temp dir even on error
    if not rep.ok or rep.wall_s <= 0:
        return 0.0
    return rep.height / rep.wall_s


def _bench_simnet_churn(height: int = 15) -> float:
    """Rotation variant of the simnet probe: 6 nodes / 4 active
    validators with a join+leave churn every 4 heights, so the measured
    path includes EndBlock validator updates, valset-hash invalidation
    and (when enabled) epoch-cache cold/warm cycling. Heights per wall
    second; 0.0 when the run goes red."""
    from tendermint_tpu.simnet import Cluster, rotation_schedule

    faults = rotation_schedule(6, 4, every=4, start=3, until=height - 4)
    cluster = Cluster(n_nodes=6, n_validators=4, seed=1, faults=faults)
    try:
        rep = cluster.run_to_height(height, max_virtual_s=600.0)
    finally:
        cluster.stop()
    if not rep.ok or rep.wall_s <= 0:
        return 0.0
    return rep.height / rep.wall_s


def _build_header_chain(chain_id: str, n_headers: int, n_vals: int):
    """Synthetic adjacent signed-header chain over one validator set —
    shared by the pipelined-header benchmark and `bench.py light`.
    Returns [(SignedHeader, ValidatorSet), ...] of length n_headers + 1
    (index 0 is the root of trust)."""
    from dataclasses import replace as _dc_replace

    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types import SignedHeader, Validator, ValidatorSet, Vote
    from tendermint_tpu.types.block import BlockID, Header, PartSetHeader, Version
    from tendermint_tpu.types.vote import PRECOMMIT_TYPE
    from tendermint_tpu.types.vote_set import VoteSet
    from tendermint_tpu.wire.canonical import Timestamp

    sks, vals = [], []
    for i in range(n_vals):
        sk = ed25519.gen_priv_key((i + 7).to_bytes(32, "little"))
        sks.append(sk)
        vals.append(Validator.new(sk.pub_key(), 100))
    vset = ValidatorSet.new(vals)
    by_addr = {v.address: sk for sk, v in zip(sks, vals)}
    ordered = [by_addr[v.address] for v in vset.validators]

    shs = []
    prev_hash = b"\x00" * 32
    for h in range(1, n_headers + 2):
        hdr = Header(
            version=Version(block=11, app=0), chain_id=chain_id, height=h,
            time=Timestamp(seconds=1_600_000_000 + h),
            last_block_id=BlockID(
                hash=prev_hash, part_set_header=PartSetHeader(total=1, hash=prev_hash)
            ) if h > 1 else BlockID(),
            validators_hash=vset.hash(), next_validators_hash=vset.hash(),
            consensus_hash=b"\x01" * 32, app_hash=b"",
            proposer_address=vset.validators[0].address,
        )
        bid = BlockID(hash=hdr.hash(), part_set_header=PartSetHeader(total=1, hash=hdr.hash()))
        vs = VoteSet(chain_id, h, 0, PRECOMMIT_TYPE, vset)
        for idx, sk in enumerate(ordered):
            v = Vote(
                type=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                timestamp=Timestamp(seconds=1_600_000_000 + h),
                validator_address=vset.validators[idx].address, validator_index=idx,
            )
            v = _dc_replace(v, signature=sk.sign(v.sign_bytes(chain_id)))
            vs.add_vote(v)
        shs.append((SignedHeader(header=hdr, commit=vs.make_commit()), vset))
        prev_hash = hdr.hash()
    return shs


def _bench_pipelined_headers(on_accel: bool) -> float:
    """Build a synthetic adjacent header chain and measure pipelined
    verification throughput (headers/s, steady-state after warmup)."""
    from tendermint_tpu.ops import pipeline as _pl

    n_headers = int(os.environ.get("TM_TPU_BENCH_HEADERS", "1000" if on_accel else "32"))
    n_vals = int(os.environ.get("TM_TPU_BENCH_HEADER_VALS", "128" if on_accel else "8"))
    chain_id = "bench-chain"
    shs = _build_header_chain(chain_id, n_headers, n_vals)

    trusted = shs[0][0]
    # warm pass compiles the full-bucket kernel shape (the 10240-lane
    # compile is ~11s/process even with the persistent cache); the timed
    # pass is steady state with all per-commit caches cleared so every
    # header pays its real sign-bytes/hashing cost exactly once
    _pl.verify_headers_pipelined(chain_id, trusted, shs[1:])
    for sh, _ in shs:
        sh.commit._sb_tpl = None
        sh.commit._hash = None
    t0 = time.perf_counter()
    _pl.verify_headers_pipelined(chain_id, trusted, shs[1:])
    dt = time.perf_counter() - t0
    return (len(shs) - 1) / dt


def light_main(argv) -> None:
    """`bench.py light` — the light-service serving benchmark (ISSUE 11):
    C simulated clients each requesting skipping verification of H
    headers (one warm epoch — the trust-period shape both light-client
    papers observe), driven through LightVerifyService over the real
    pipeline with the device mocked behind a fixed device RTT (the
    --overlap/multichip mock philosophy: real host prep, epoch grouping,
    coalescing and transfer; the launch returns an all-accept verdict
    row behind rtt_ms). Headline: delivered header verdicts/s across the
    client fleet. Honest secondary figures: the UNIQUE-verification rate
    (client 1's cold pass — no request-level dedup), the sequential
    per-request baseline on the same mocked engine, and the memo hit
    ratio. `--real` runs live kernels instead of the mock (TPU runs).

    Prints ONE JSON line; --out also writes it as an artifact file
    (LIGHT_r*.json, schema_version 1, rendered by tools/bench_report.py
    --trajectory and gated by --compare)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py light")
    ap.add_argument("--clients", type=int, default=256,
                    help="simulated light clients (default 256)")
    ap.add_argument("--headers", type=int, default=48,
                    help="target headers per client (default 48)")
    ap.add_argument("--vals", type=int, default=32,
                    help="validators per set (default 32)")
    ap.add_argument("--rtt-ms", type=float, default=60.0,
                    help="mocked device round-trip per launch (default 60)")
    ap.add_argument("--real", action="store_true",
                    help="run live kernels instead of the mocked device")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.light import verifier as _lv
    from tendermint_tpu.light.batch import HeaderRequest
    from tendermint_tpu.light.service import LightVerifyService
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import mock_light_prepare
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.wire.canonical import Timestamp

    chain_id = "light-bench"
    print(f"# building {args.headers + 1}-header chain, "
          f"{args.vals} validators", file=sys.stderr)
    shs = _build_header_chain(chain_id, args.headers, args.vals)
    trusted, vset = shs[0]
    now = Timestamp(seconds=1_600_000_000 + len(shs) + 60)
    period = 1e9

    def requests_for_client(_c: int):
        # every client skip-verifies the same published chain from the
        # same root of trust — the serving shape the papers motivate
        return [
            HeaderRequest(
                trusted_header=trusted, trusted_vals=vset,
                untrusted_header=shs[k][0], untrusted_vals=shs[k][1],
                trusting_period=period,
            )
            for k in range(1, args.headers + 1)
        ]

    _epoch.reset(8)  # warm-epoch methodology: device tables amortize
    real_prepare = _pl.AsyncBatchVerifier._prepare
    if not args.real:
        _pl.AsyncBatchVerifier._prepare = staticmethod(
            mock_light_prepare(real_prepare, args.rtt_ms / 1e3)
        )
    v = _pl.AsyncBatchVerifier(depth=3)
    svc = LightVerifyService(verifier=v, memo_size=4 * args.headers)
    try:
        # cold pass (client 1): every request is a unique verification —
        # host prep + epoch grouping + coalescing, no request-level dedup
        t0 = time.perf_counter()
        svc.submit_many(requests_for_client(0), now=now).results(timeout=900)
        unique_rate = args.headers / (time.perf_counter() - t0)
        # warm fleet: C clients re-request the same trust window
        t0 = time.perf_counter()
        batches = [
            svc.submit_many(requests_for_client(c), now=now)
            for c in range(1, args.clients)
        ]
        n_done = sum(len(b.results(timeout=900)) for b in batches)
        dt = time.perf_counter() - t0
        rate = n_done / dt
        stats = svc.stats()

        # sequential per-request baseline on the SAME engine: one
        # verifier.verify call per header, no cross-request anything.
        # TM_TPU_FORCE_DEVICE routes the sub-threshold commit sizes
        # through the (mocked) device engine too, so both columns pay
        # the same device cost model — per-request dispatch pays the RTT
        # per stage, which is exactly the ~1.2k headers/s ceiling the
        # service removes (without it the baseline silently measures
        # host-crypto speed instead).
        seq_n = min(args.headers, 16)
        os.environ["TM_TPU_FORCE_DEVICE"] = "1"
        try:
            t0 = time.perf_counter()
            for k in range(1, seq_n + 1):
                _lv.verify(trusted, vset, shs[k][0], shs[k][1], period, now,
                           10.0, _lv.DEFAULT_TRUST_LEVEL)
            seq_rate = seq_n / (time.perf_counter() - t0)
        finally:
            os.environ.pop("TM_TPU_FORCE_DEVICE", None)
    finally:
        svc.close()
        v.close()
        _pl.AsyncBatchVerifier._prepare = real_prepare

    out = {
        "schema_version": 1,
        "metric": "light_service_headers_per_s",
        "value": round(rate, 1),
        "unit": "headers/s",
        "mode": "real" if args.real else "mocked-device",
        "backend": os.environ.get("JAX_PLATFORMS", "") or "cpu",
        "light_clients": args.clients,
        "headers_per_client": args.headers,
        "vals_per_set": args.vals,
        "device_rtt_ms": args.rtt_ms if not args.real else None,
        "light_unique_headers_per_s": round(unique_rate, 1),
        "light_sequential_headers_per_s": round(seq_rate, 1),
        "vs_sequential": round(rate / seq_rate, 2) if seq_rate else None,
        "memo_hit_ratio": round(
            stats["memo_hits"] / max(stats["requests"], 1), 4
        ),
        "unique_verifications": stats["unique"],
        "requests": stats["requests"],
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
            fh.write("\n")


def _p99_ms(samples_s) -> float:
    xs = sorted(samples_s)
    if not xs:
        return 0.0
    return xs[min(int(round(0.99 * (len(xs) - 1))), len(xs) - 1)] * 1e3


def mempool_main(argv) -> None:
    """`bench.py mempool` — device-batched transaction ingress (ISSUE 13).

    Floods signed txs through the FULL CheckTx path (envelope parse,
    seen-cache, batched device signature verdict, nonce, app CheckTx)
    with the device mocked behind a fixed per-launch device RTT
    (mock_mempool_prepare — real accumulation, EntryBlock packing, host
    prep and transfer; the launch's verdict matures rtt_ms after launch).
    Headline: CheckTx signature verdicts/s through the windowed
    accumulator. The honest baseline is the SAME mocked engine driven
    per-tx (window=0, batch=1 — one device launch per tx, the shape
    CheckTx had before the accumulator), under the TM_TPU_FORCE_DEVICE
    discipline so neither column quietly routes to host crypto.

    QoS figure: consensus-priority commit batches run back-to-back
    unloaded, then again under a sustained ingress flood — the artifact
    records both p99s and their ratio (the ISSUE 13 bound: within 2x),
    plus the preemption count the priority queue logged while consensus
    overtook queued tx superbatches.

    Prints ONE JSON line; --out also writes it as an artifact file
    (MEMPOOL_r*.json, schema_version 1, rendered by tools/bench_report.py
    --trajectory and gated by --compare)."""
    import argparse
    import threading

    ap = argparse.ArgumentParser(prog="bench.py mempool")
    ap.add_argument("--txs", type=int, default=4096,
                    help="signed txs in the flood (default 4096)")
    ap.add_argument("--senders", type=int, default=64,
                    help="distinct sender keys (default 64)")
    ap.add_argument("--batch", type=int, default=512,
                    help="accumulator max batch (default 512)")
    ap.add_argument("--window-ms", type=float, default=4.0,
                    help="accumulator window (default 4)")
    ap.add_argument("--rtt-ms", type=float, default=40.0,
                    help="mocked device round-trip per launch (default 40)")
    ap.add_argument("--commits", type=int, default=100,
                    help="consensus commit batches per column (default 100)")
    ap.add_argument("--commit-sigs", type=int, default=128,
                    help="signatures per commit batch (default 128)")
    ap.add_argument("--seq-txs", type=int, default=48,
                    help="txs for the per-tx baseline (default 48)")
    ap.add_argument("--real", action="store_true",
                    help="run live kernels instead of the mocked device")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.mempool import TxMempool
    from tendermint_tpu.mempool import ingress as _ing
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import mock_mempool_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock

    print(f"# signing {args.txs} txs from {args.senders} senders",
          file=sys.stderr)
    import hashlib as _hashlib

    privs = [
        _ed.gen_priv_key(
            seed=_hashlib.sha256(b"mempool-bench-%d" % s).digest()
        )
        for s in range(args.senders)
    ]
    txs = [
        _ing.make_signed_tx(
            privs[i % args.senders],
            b"bench_k%d=v%d" % (i, i),
            nonce=i // args.senders + 1,
        )
        for i in range(args.txs)
    ]
    stxs = [_ing.parse_signed_tx(tx) for tx in txs]
    # the consensus lane's payload: one commit-shaped ed25519 batch,
    # resubmitted per "height" at PRIORITY_CONSENSUS
    commit_block = EntryBlock.from_entries([
        (s.pub, s.signed_bytes(), s.sig)
        for s in stxs[: args.commit_sigs]
    ])

    _epoch.reset(8)
    real_prepare = _pl.AsyncBatchVerifier._prepare
    if not args.real:
        _pl.AsyncBatchVerifier._prepare = staticmethod(
            mock_mempool_prepare(real_prepare, args.rtt_ms / 1e3)
        )
    # both columns under the force-device discipline: nothing below may
    # quietly route a small batch to host crypto and skip the device cost
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    # purepy host crypto makes every pipeline stage a CPU-bound Python
    # thread; the default 5 ms GIL switch interval lets those threads
    # convoy for 100+ ms, which lands on the QoS latency tail as pure
    # interpreter-scheduler noise. Pin 1 ms for the run (restored in
    # the finally) so the columns measure the pipeline, not the GIL.
    _swi = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    v = _pl.AsyncBatchVerifier(depth=3)
    acc = _ing.IngressAccumulator(
        verifier=v, max_batch=args.batch, window_ms=args.window_ms
    )

    def fresh_mempool(ingress):
        from tendermint_tpu.config import MempoolConfig

        cfg = MempoolConfig()
        cfg.size = max(cfg.size, args.txs * 2)
        cfg.max_txs_bytes = max(cfg.max_txs_bytes, args.txs * 4096)
        return TxMempool(
            LocalClient(KVStoreApplication()), config=cfg, ingress=ingress
        )

    def commit_column(n):
        lats = []
        for _ in range(n):
            t0 = time.perf_counter()
            v.submit(
                commit_block, priority=_pl.PRIORITY_CONSENSUS
            ).result(timeout=300)
            lats.append(time.perf_counter() - t0)
        return lats

    try:
        # -- column A: the headline — flood through full CheckTx ---------
        mp = fresh_mempool(acc)
        t0 = time.perf_counter()
        futs = [mp.check_tx_async(tx) for tx in txs]
        n_ok = sum(1 for f in futs if f.result(timeout=300).is_ok())
        dt = time.perf_counter() - t0
        rate = len(futs) / dt
        if n_ok != len(futs):
            print(f"# WARNING: {len(futs) - n_ok} floods rejected",
                  file=sys.stderr)
        windows_a = acc.batches

        # column A leaves ~`txs` response futures and a fully-loaded
        # mempool behind; a gen-2 GC pass over that heap mid-commit is a
        # 50+ ms pause attributed to the wrong column. Drop both, collect
        # once, and freeze the survivors out of the collector before the
        # latency columns (unfrozen in the finally).
        import gc

        del futs, mp
        gc.collect()
        gc.freeze()

        # -- column B: consensus commits, unloaded -----------------------
        p99_unloaded = _p99_ms(commit_column(args.commits))

        # -- column C: the same commit cadence under sustained flood -----
        # the flood driver resubmits the pre-signed pool straight into
        # the accumulator (device pressure is the contended resource;
        # the mempool's dedup cache would starve a tx-level loop)
        stop = threading.Event()
        flood_sigs = [0]

        def flood():
            # one pool pass outstanding at a time: ~txs/batch windows
            # queued (well past the pipeline depth — real contention)
            # without letting the backlog grow unboundedly
            while not stop.is_set():
                last = None
                for s in stxs:
                    if stop.is_set():
                        break
                    last = acc.submit(s)
                    flood_sigs[0] += 1
                acc.flush_now()
                if last is not None:
                    try:
                        last.result(timeout=300)
                    except Exception:  # noqa: BLE001 — pressure, not verdicts
                        pass

        ft = threading.Thread(target=flood, daemon=True)
        ft.start()
        time.sleep(args.window_ms / 1e3 * 4)  # let the queue build
        flood_lats = commit_column(args.commits)
        stop.set()
        ft.join(timeout=30)
        acc.flush_now()
        p99_flood = _p99_ms(flood_lats)

        # -- baseline: per-tx dispatch on the SAME mocked engine ---------
        seq_acc = _ing.IngressAccumulator(
            verifier=v, max_batch=1, window_ms=0.0
        )
        try:
            mp_seq = fresh_mempool(seq_acc)
            seq_n = min(args.seq_txs, len(txs))
            t0 = time.perf_counter()
            for tx in txs[:seq_n]:
                mp_seq.check_tx(tx)
            seq_rate = seq_n / (time.perf_counter() - t0)
        finally:
            seq_acc.close()
        stats = acc.stats()
    finally:
        acc.close()
        v.close()
        sys.setswitchinterval(_swi)
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        _pl.AsyncBatchVerifier._prepare = real_prepare
        import gc

        gc.unfreeze()

    out = {
        "schema_version": 1,
        "metric": "mempool_checktx_sigs_per_s",
        "value": round(rate, 1),
        "unit": "sigs/s",
        "mode": "real" if args.real else "mocked-device",
        "backend": os.environ.get("JAX_PLATFORMS", "") or "cpu",
        "txs": args.txs,
        "senders": args.senders,
        "ingress_batch": args.batch,
        "ingress_window_ms": args.window_ms,
        "device_rtt_ms": args.rtt_ms if not args.real else None,
        "mempool_seq_sigs_per_s": round(seq_rate, 1),
        "vs_sequential": round(rate / seq_rate, 2) if seq_rate else None,
        "commit_p99_unloaded_ms": round(p99_unloaded, 2),
        "commit_p99_flood_ms": round(p99_flood, 2),
        "flood_latency_ratio": (
            round(p99_flood / p99_unloaded, 2) if p99_unloaded else None
        ),
        "checktx_preemptions": stats["preemptions"],
        "ingress_windows": windows_a,
        "ingress_batch_wait_ms_avg": round(stats["batch_wait_ms_avg"], 2),
        "flood_sigs_submitted": flood_sigs[0],
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
            fh.write("\n")


def _replay_bench_valsets(n_vals: int, n_sets: int):
    """Cycle of distinct validator sets for the replay bench chain —
    real keys (host prep hashes the real pubkeys), one set per rotation
    epoch class. Returns [(ValidatorSet, vals_hash, proposer_addr)]."""
    import hashlib as _hashlib

    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    sets = []
    for s in range(n_sets):
        vals = [
            Validator.new(
                _ed.gen_priv_key(
                    seed=_hashlib.sha256(
                        b"replay-bench-%d-%d" % (s, i)
                    ).digest()
                ).pub_key(),
                100,
            )
            for i in range(n_vals)
        ]
        vset = ValidatorSet.new(vals)
        sets.append((vset, vset.hash(), vset.validators[0].address))
    return sets


def _replay_bench_chain(chain_id: str, vsets, rotate: int, rng):
    """Infinite generator of consecutive fully-linked blocks with FAKE
    commit signatures (the simnet rotation-schedule shape: validator
    set cycles every `rotate` heights). The mocked device returns
    all-accept verdicts, so the signature bytes are never checked —
    everything the replay engine actually pays for is real: block
    encode, part sets, block-id binding, per-signature sign-bytes prep,
    epoch cuts and range packing."""
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        Block,
        BlockID,
        Commit,
        CommitSig,
        Data,
        Header,
        Version,
    )
    from tendermint_tpu.types.part_set import BLOCK_PART_SIZE_BYTES, PartSet
    from tendermint_tpu.wire.canonical import Timestamp

    def at(h):
        return vsets[((h - 1) // rotate) % len(vsets)]

    ts0 = Timestamp(seconds=1_600_000_000, nanos=0)
    last_commit, prev_bid = None, BlockID()
    h = 1
    while True:
        vset, vhash, proposer = at(h)
        hdr = Header(
            version=Version(block=11, app=0), chain_id=chain_id, height=h,
            time=Timestamp(seconds=1_600_000_000 + h),
            last_block_id=prev_bid,
            validators_hash=vhash, next_validators_hash=at(h + 1)[1],
            consensus_hash=b"\x01" * 32, app_hash=b"",
            proposer_address=proposer,
        )
        block = Block(header=hdr, data=Data(), last_commit=last_commit)
        block.fill_header()
        parts = PartSet.from_data(block.encode(), BLOCK_PART_SIZE_BYTES)
        bid = BlockID(hash=block.hash(), part_set_header=parts.header())
        last_commit = Commit(
            height=h, round=0, block_id=bid,
            signatures=[
                CommitSig(
                    block_id_flag=BLOCK_ID_FLAG_COMMIT,
                    validator_address=val.address,
                    timestamp=ts0, signature=rng.randbytes(64),
                )
                for val in vset.validators
            ],
        )
        prev_bid = bid
        yield block
        h += 1


def blocksync_main(argv) -> None:
    """`bench.py blocksync` — chain-replay catch-up (ISSUE 14).

    Replays a ≥100k-height synthetic chain with the simnet rotation
    shape (validator set rotates every ~50 heights) through the
    ReplayEngine with the device mocked behind a fixed per-launch device
    RTT (mock_mempool_prepare — real epoch cuts, range packing, host
    sign-bytes prep, EntryBlock coalescing and transfer; the launch's
    all-accept verdict matures rtt_ms after launch). Chain synthesis is
    the fetch stand-in and runs OFF the clock; the headline times only
    what the engine does with a full block window in hand.

    Headline: replayed heights/s. Honest columns: the per-height
    baseline on the SAME mocked engine (one launch per height — the
    verify-one-ahead shape replay replaces), and the kernel-serial rate
    (heights / (launches x RTT): what the device alone would cost if the
    host pipelined perfectly — the ISSUE 14 bound is >= 0.5x of it).

    QoS figure: consensus-priority commit batches unloaded vs under a
    sustained replay-priority flood (the rejoining-node scenario: a
    peer catching up must not move live consensus p99 — PR 12's ratio
    methodology at the new PRIORITY_REPLAY tier).

    Prints ONE JSON line; --out also writes it as an artifact file
    (BLOCKSYNC_r*.json, schema_version 1, rendered by
    tools/bench_report.py --trajectory and gated by --compare)."""
    import argparse
    import random
    import threading

    ap = argparse.ArgumentParser(prog="bench.py blocksync")
    ap.add_argument("--heights", type=int, default=100_000,
                    help="heights to replay (default 100000)")
    ap.add_argument("--vals", type=int, default=32,
                    help="validators per set (default 32)")
    ap.add_argument("--val-sets", type=int, default=4,
                    help="distinct validator sets cycled (default 4)")
    ap.add_argument("--rotate", type=int, default=50,
                    help="heights per valset epoch (default 50)")
    ap.add_argument("--window", type=int, default=256,
                    help="replay window in heights (default 256)")
    ap.add_argument("--rtt-ms", type=float, default=40.0,
                    help="mocked device round-trip per launch (default 40)")
    ap.add_argument("--seq-heights", type=int, default=48,
                    help="heights for the per-height baseline (default 48)")
    ap.add_argument("--commits", type=int, default=100,
                    help="consensus commit batches per column (default 100)")
    ap.add_argument("--commit-sigs", type=int, default=128,
                    help="signatures per commit batch (default 128)")
    ap.add_argument("--flood-heights", type=int, default=20_000,
                    help="chain prebuilt for the flood column (default 20000)")
    ap.add_argument("--real", action="store_true",
                    help="run live kernels instead of the mocked device")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.blocksync.replay import ReplayEngine
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import mock_mempool_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock
    from tendermint_tpu.types import validation as _val
    from tendermint_tpu.types.block import BlockID
    from tendermint_tpu.types.part_set import BLOCK_PART_SIZE_BYTES, PartSet

    chain_id = "blocksync-bench"
    print(f"# {args.val_sets} validator sets x {args.vals} vals, "
          f"rotation every {args.rotate} heights", file=sys.stderr)
    vsets = _replay_bench_valsets(args.vals, args.val_sets)

    def vals_at(h):
        return vsets[((h - 1) // args.rotate) % len(vsets)][0]

    class _St:
        def __init__(self, cid):
            self.chain_id = cid
            self.validators = vals_at(1)
            self.last_block_height = 0

    def _noop_save(block, parts, seen_commit):
        pass

    def _mk_apply(st):
        def apply(bid, block):
            st.last_block_height = block.header.height
            st.validators = vals_at(block.header.height + 1)
            return st

        return apply

    # the consensus lane's payload: one commit-shaped batch resubmitted
    # per "height" at PRIORITY_CONSENSUS (fake keys — mocked device)
    crng = random.Random(0xC0117)
    commit_block = EntryBlock.from_entries([
        (crng.randbytes(32), b"bench-commit-%d" % i, crng.randbytes(64))
        for i in range(args.commit_sigs)
    ])

    _epoch.reset(8)
    launches = [0]
    real_prepare = _pl.AsyncBatchVerifier._prepare
    if not args.real:
        _mock = mock_mempool_prepare(real_prepare, args.rtt_ms / 1e3)

        def _counting_prepare(entries):
            f, pargs, rlc, bucket = _mock(entries)

            def launch(*xs):
                launches[0] += 1
                return f(*xs)

            return launch, pargs, rlc, bucket

        _pl.AsyncBatchVerifier._prepare = staticmethod(_counting_prepare)
    # force-device discipline: the per-height baseline (22-sig batches)
    # and the commit column must pay the device cost model, not quietly
    # route to host crypto (where the fake signatures would also fail)
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    _swi = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    v = _pl.AsyncBatchVerifier(depth=3)
    eng = ReplayEngine(window=args.window, synchronous=True, verifier=v)

    def commit_column(n):
        lats = []
        for _ in range(n):
            t0 = time.perf_counter()
            v.submit(
                commit_block, priority=_pl.PRIORITY_CONSENSUS
            ).result(timeout=300)
            lats.append(time.perf_counter() - t0)
        return lats

    try:
        # -- column A: the headline — windowed chain replay --------------
        print(f"# replaying {args.heights} heights "
              f"(window {args.window})", file=sys.stderr)
        gen = _replay_bench_chain(
            chain_id, vsets, args.rotate, random.Random(0xB10C)
        )
        st = _St(chain_id)
        apply = _mk_apply(st)
        buf = []
        t_replay = t_build = 0.0
        applied = 0
        launches[0] = 0
        while applied < args.heights:
            t0 = time.perf_counter()
            while len(buf) < args.window + 1:
                buf.append(next(gen))
            t_build += time.perf_counter() - t0
            t0 = time.perf_counter()
            st, out_r = eng.replay_blocks(st, buf, _noop_save, apply)
            t_replay += time.perf_counter() - t0
            if out_r.applied <= 0:
                raise RuntimeError(
                    f"replay stalled at height {st.last_block_height}: "
                    f"{out_r.error!r}"
                )
            applied += out_r.applied
            del buf[: out_r.applied]
        rate = applied / t_replay
        n_launches = launches[0]
        stats = eng.stats()
        kernel_rate = (
            applied / (n_launches * (args.rtt_ms / 1e3))
            if (n_launches and not args.real) else None
        )
        print(f"# {applied} heights in {t_replay:.1f}s replay "
              f"(+{t_build:.1f}s synthesis, off the clock), "
              f"{n_launches} launches", file=sys.stderr)

        import gc

        gc.collect()
        gc.freeze()

        # -- column B: consensus commits, unloaded -----------------------
        p99_unloaded = _p99_ms(commit_column(args.commits))

        # -- column C: the same commit cadence while a node catches up ---
        # the flood chain is prebuilt so the driver thread's only work
        # is feeding the engine (synthesis must not throttle the flood)
        fgen = _replay_bench_chain(
            chain_id, vsets, args.rotate, random.Random(0xF100D)
        )
        fchain = [next(fgen) for _ in range(args.flood_heights + 1)]
        stop = threading.Event()
        flood_applied = [0]

        def flood():
            feng = ReplayEngine(
                window=args.window, synchronous=True, verifier=v
            )
            fst = _St(chain_id)
            fapply = _mk_apply(fst)
            pos = 0
            while not stop.is_set():
                if pos + 1 >= len(fchain):
                    pos = 0
                    fst = _St(chain_id)
                run = fchain[pos : pos + args.window + 1]
                fst, fo = feng.replay_blocks(
                    fst, run, _noop_save, fapply,
                    should_stop=stop.is_set,
                )
                if fo.applied <= 0:
                    break
                pos += fo.applied
                flood_applied[0] += fo.applied

        ft = threading.Thread(target=flood, daemon=True)
        ft.start()
        time.sleep(args.rtt_ms / 1e3 * 4)  # let replay chunks queue
        p99_flood = _p99_ms(commit_column(args.commits))
        stop.set()
        ft.join(timeout=60)

        # -- baseline: one launch per height on the SAME mocked engine ---
        seq_n = min(args.seq_heights, args.rotate - 1)
        sgen = _replay_bench_chain(
            chain_id, vsets, args.rotate, random.Random(0x5E0)
        )
        schain = [next(sgen) for _ in range(seq_n + 1)]
        t0 = time.perf_counter()
        for i in range(seq_n):
            b = schain[i]
            h = b.header.height
            parts = PartSet.from_data(b.encode(), BLOCK_PART_SIZE_BYTES)
            bid = BlockID(hash=b.hash(), part_set_header=parts.header())
            prepared, _synced = _val.prepare_commit_range(
                chain_id, vals_at(h),
                [(h, bid, schain[i + 1].last_commit)],
            )
            _h, eb, conclude = prepared[0]
            valid = v.submit(
                eb, priority=_pl.PRIORITY_REPLAY
            ).result(timeout=300)
            conclude(valid[: len(eb)])
        seq_rate = seq_n / (time.perf_counter() - t0)
    finally:
        eng.close()
        v.close()
        sys.setswitchinterval(_swi)
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        _pl.AsyncBatchVerifier._prepare = real_prepare
        import gc

        gc.unfreeze()

    out = {
        "schema_version": 1,
        "metric": "blocksync_replay_heights_per_s",
        "value": round(rate, 1),
        "unit": "heights/s",
        "mode": "real" if args.real else "mocked-device",
        "backend": os.environ.get("JAX_PLATFORMS", "") or "cpu",
        "heights": applied,
        "vals": args.vals,
        "val_sets": args.val_sets,
        "rotate": args.rotate,
        "window": args.window,
        "device_rtt_ms": args.rtt_ms if not args.real else None,
        "launches": n_launches,
        "sigs_submitted": stats["sigs_submitted"],
        "range_hit_rate": round(stats["hit_rate"], 4),
        "fallback_ranges": stats["fallback_ranges"],
        "kernel_serial_heights_per_s": (
            round(kernel_rate, 1) if kernel_rate else None
        ),
        "vs_kernel_serial": (
            round(rate / kernel_rate, 2) if kernel_rate else None
        ),
        "replay_seq_heights_per_s": round(seq_rate, 1),
        "vs_sequential": round(rate / seq_rate, 2) if seq_rate else None,
        "chain_synth_heights_per_s": (
            round(applied / t_build, 1) if t_build else None
        ),
        "commit_p99_unloaded_ms": round(p99_unloaded, 2),
        "commit_p99_flood_ms": round(p99_flood, 2),
        "flood_latency_ratio": (
            round(p99_flood / p99_unloaded, 2) if p99_unloaded else None
        ),
        "flood_heights_applied": flood_applied[0],
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
            fh.write("\n")


def votes_main(argv) -> None:
    """`bench.py votes` — device-batched live-vote ingress (ISSUE 15).

    Floods gossiped prevotes through the FULL AddVote split path (host
    check_vote, vote-ingress windowing, EntryBlock packing, verdict
    application into real VoteSets) with the device mocked behind a
    fixed per-launch device RTT (mock_vote_prepare — real windowing,
    packing, host prep and transfer; the launch's verdict matures
    rtt_ms after launch). Headline: vote signature verdicts/s through
    the windowed accumulator, measured to the LAST verdict applied.
    The honest baseline is the SAME mocked engine driven per-vote
    (window=0, batch=1 — one device launch per vote, the shape AddVote
    had before the accumulator), under the TM_TPU_FORCE_DEVICE
    discipline so neither column quietly routes to host crypto.

    Prints ONE JSON line; --out also writes it as an artifact file
    (VOTES_r*.json, schema_version 1, rendered by tools/bench_report.py
    --trajectory and gated by --compare)."""
    import argparse
    import threading

    ap = argparse.ArgumentParser(prog="bench.py votes")
    ap.add_argument("--votes", type=int, default=4096,
                    help="signed votes in the flood (default 4096)")
    ap.add_argument("--vals", type=int, default=64,
                    help="validators in the set (default 64)")
    ap.add_argument("--batch", type=int, default=256,
                    help="accumulator max batch (default 256)")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="accumulator window (default 2)")
    ap.add_argument("--rtt-ms", type=float, default=40.0,
                    help="mocked device round-trip per launch (default 40)")
    ap.add_argument("--seq-votes", type=int, default=48,
                    help="votes for the per-vote baseline (default 48)")
    ap.add_argument("--real", action="store_true",
                    help="run live kernels instead of the mocked device")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.consensus import vote_ingress as _vi
    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import mock_vote_prepare
    from tendermint_tpu.types import (
        BlockID,
        PartSetHeader,
        Timestamp,
        Validator,
        ValidatorSet,
        Vote,
        VoteSet,
    )
    from tendermint_tpu.types.vote import PREVOTE_TYPE

    chain_id = "votes-bench"
    height = 10
    n_rounds = -(-args.votes // args.vals)
    n_votes = n_rounds * args.vals
    print(f"# signing {n_votes} votes ({args.vals} vals x {n_rounds} "
          "rounds)", file=sys.stderr)
    pairs = []
    for i in range(args.vals):
        sk = _ed.gen_priv_key(bytes([(i % 255) + 1]) * 31 +
                              bytes([i // 255 + 1]))
        pairs.append((sk, Validator.new(sk.pub_key(), 100)))
    vset = ValidatorSet.new([v for _, v in pairs])
    by_addr = {v.address: sk for sk, v in pairs}
    sks = [by_addr[v.address] for v in vset.validators]
    bid = BlockID(hash=b"\x07" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x07" * 32))
    votes = []
    for r in range(n_rounds):
        for i, sk in enumerate(sks):
            vote = Vote(
                type=PREVOTE_TYPE, height=height, round=r, block_id=bid,
                timestamp=Timestamp(seconds=1_600_000_000, nanos=0),
                validator_address=vset.validators[i].address,
                validator_index=i,
            )
            msg = vote.sign_bytes(chain_id)
            votes.append((
                Vote(**{**vote.__dict__, "signature": sk.sign(msg)}), msg,
            ))

    def fresh_sets():
        return {r: VoteSet(chain_id, height, r, PREVOTE_TYPE, vset)
                for r in range(n_rounds)}

    _epoch.reset(8)
    _epoch.note_valset(vset)  # register
    _epoch.note_valset(vset)  # warm: windows attach val_idx + epoch_key
    real_prepare = _pl.AsyncBatchVerifier._prepare
    if not args.real:
        _pl.AsyncBatchVerifier._prepare = staticmethod(
            mock_vote_prepare(real_prepare, args.rtt_ms / 1e3)
        )
    # both columns under the force-device discipline (see mempool_main)
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    _swi = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    v = _pl.AsyncBatchVerifier(depth=3)

    def make_apply(sets, counter, done):
        def apply(batch, verdicts, error):
            for i, p in enumerate(batch):
                if error is None and verdicts[i]:
                    try:
                        sets[p.vote.round].apply_vote_verdict(p.vote, True)
                    except Exception:  # noqa: BLE001 — tally only
                        pass
                counter[0] += 1
            if counter[0] >= counter[1]:
                done.set()
        return apply

    try:
        # -- column A: the headline — windowed flood ---------------------
        sets = fresh_sets()
        done = threading.Event()
        counter = [0, n_votes]
        acc = _vi.VoteIngress(make_apply(sets, counter, done), verifier=v,
                              max_batch=args.batch,
                              window_ms=args.window_ms)
        try:
            t0 = time.perf_counter()
            for vote, msg in votes:
                chk = sets[vote.round].check_vote(vote)  # host stage
                assert chk is not None
                acc.submit(_vi.PendingVote(
                    vote, "bench-peer", chk.pub_key.bytes(), msg,
                    t_enq=time.perf_counter(),
                ), vset)
            acc.flush_now()
            if not done.wait(timeout=600):
                raise RuntimeError(
                    f"only {counter[0]}/{n_votes} verdicts arrived"
                )
            dt = time.perf_counter() - t0
            rate = n_votes / dt
            stats = acc.stats()
            n_applied = sum(
                1 for r in range(n_rounds) for i in range(args.vals)
                if sets[r].bit_array().get_index(i)
            )
            if n_applied != n_votes:
                print(f"# WARNING: {n_votes - n_applied} votes not "
                      "applied", file=sys.stderr)
        finally:
            acc.close()

        # -- baseline: per-vote dispatch on the SAME mocked engine -------
        seq_sets = fresh_sets()
        seq_n = min(args.seq_votes, n_votes)
        seq_done = threading.Event()
        seq_counter = [0, seq_n]
        seq_acc = _vi.VoteIngress(
            make_apply(seq_sets, seq_counter, seq_done), verifier=v,
            max_batch=1, window_ms=0.0,
        )
        try:
            t0 = time.perf_counter()
            for vote, msg in votes[:seq_n]:
                chk = seq_sets[vote.round].check_vote(vote)
                want = seq_counter[0] + 1
                seq_acc.submit(_vi.PendingVote(
                    vote, "bench-peer", chk.pub_key.bytes(), msg,
                    t_enq=time.perf_counter(),
                ), vset)
                seq_acc.flush_now()
                # sequential shape: wait for THIS vote's verdict before
                # the next — one device launch per vote
                deadline = time.perf_counter() + 300
                while (seq_counter[0] < want
                       and time.perf_counter() < deadline):
                    time.sleep(0.0005)
            seq_rate = seq_n / (time.perf_counter() - t0)
        finally:
            seq_acc.close()
    finally:
        v.close()
        sys.setswitchinterval(_swi)
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        _pl.AsyncBatchVerifier._prepare = real_prepare

    out = {
        "schema_version": 1,
        "metric": "vote_ingress_votes_per_s",
        "value": round(rate, 1),
        "unit": "votes/s",
        "mode": "real" if args.real else "mocked-device",
        "backend": os.environ.get("JAX_PLATFORMS", "") or "cpu",
        "votes": n_votes,
        "vals": args.vals,
        "rounds": n_rounds,
        "ingress_batch": args.batch,
        "ingress_window_ms": args.window_ms,
        "device_rtt_ms": args.rtt_ms if not args.real else None,
        "votes_seq_votes_per_s": round(seq_rate, 1),
        "vs_sequential": round(rate / seq_rate, 2) if seq_rate else None,
        "ingress_windows": stats["batches"],
        "ingress_batch_wait_ms_avg": round(stats["batch_wait_ms_avg"], 2),
        "window_dups": stats["window_dups"],
        "memo_hits": stats["memo_hits"],
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
            fh.write("\n")


def schemes_main(argv) -> None:
    """`bench.py schemes` — the secp256k1 scheme lane at committee scale
    (ISSUE 19).

    Verifies a 10k-validator all-secp256k1 commit through the FULL
    production seam (prepare_commit_light -> scheme-routed pipeline
    prep -> launch -> conclude) with the device mocked behind a fixed
    per-launch device RTT (mock_vote_prepare: the real host prep — epoch
    table gather, GLV decomposition, scalar packing — and the H2D
    transfer run unchanged; the launch's verdict matures rtt_ms after
    launch). Headline: counted commit signatures/s to conclude().

    The honest baseline is the SAME mocked engine driven per-signature
    (one device launch per signature — the shape the reference's
    "secp256k1 never batches" verdict forces, crypto/batch/batch.go:
    26-33), so the ratio measures exactly what the scheme lane adds:
    signatures fused per device launch. Gated at >= 10x (the ISSUE 19
    acceptance); kernel-verdict correctness is pinned separately by
    tests/test_secp_lane.py and `tools/prep_bench.py --schemes`, which
    run the kernel for real.

    Prints ONE JSON line; --out also writes it as an artifact file
    (SCHEMES_r*.json, schema_version 1, rendered by tools/bench_report.py
    --trajectory and gated by --compare)."""
    import argparse

    import numpy as np

    ap = argparse.ArgumentParser(prog="bench.py schemes")
    ap.add_argument("--vals", type=int, default=10240,
                    help="secp256k1 validators in the set (default 10240)")
    ap.add_argument("--rtt-ms", type=float, default=40.0,
                    help="mocked device round-trip per launch (default 40)")
    ap.add_argument("--seq-sigs", type=int, default=48,
                    help="signatures for the per-sig baseline (default 48)")
    ap.add_argument("--real", action="store_true",
                    help="run live kernels instead of the mocked device")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.crypto import secp256k1 as _secp
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import mock_vote_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock
    from tendermint_tpu.types import validation as V
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.wire.canonical import Timestamp

    chain_id = "schemes-bench"
    n_ord = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
    rng = np.random.RandomState(191)
    print(f"# deriving {args.vals} secp256k1 validators", file=sys.stderr)
    vals, sigs = [], []
    for i in range(args.vals):
        pk = _secp.PrivKey((i + 1).to_bytes(32, "big")).pub_key()
        vals.append(Validator.new(pk, 100))
        # full-range lower-S (r, s): signing 10k purepy ECDSA sigs costs
        # ~11 ms each and the mocked device never checks validity, but
        # the rows must still pay the FULL host prep (range checks pass,
        # GLV decomposition runs) — same rationale as
        # build_synthetic_commit's random ed25519 signatures
        r = int.from_bytes(rng.bytes(32), "big") % (n_ord - 1) + 1
        s = int.from_bytes(rng.bytes(32), "big") % (n_ord // 2) + 1
        sigs.append(CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=pk.address(),
            timestamp=Timestamp(seconds=1_700_000_000, nanos=int(i) + 1),
            signature=r.to_bytes(32, "big") + s.to_bytes(32, "big"),
        ))
    # keep commit.signatures index-aligned with the validator list
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    bid = BlockID(hash=b"\x13" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x13" * 32))
    commit = Commit(height=19, round=0, block_id=bid, signatures=sigs)

    _epoch.reset(8)
    _epoch.note_valset(vset)  # register
    _epoch.note_valset(vset)  # warm: blocks attach val_idx + epoch_key
    real_prepare = _pl.AsyncBatchVerifier._prepare
    launches = [0]
    if not args.real:
        mocked = mock_vote_prepare(real_prepare, args.rtt_ms / 1e3)

        def counting(entries):
            launches[0] += 1
            return mocked(entries)

        _pl.AsyncBatchVerifier._prepare = staticmethod(counting)
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    v = _pl.AsyncBatchVerifier(depth=3)
    try:
        def run_once():
            entries, conclude = V.prepare_commit_light(
                chain_id, vset, bid, commit.height, commit
            )
            verdicts = np.asarray(v.submit(entries).result(timeout=600))
            conclude(verdicts)
            return len(entries)

        # warm rep: epoch Q-table decompression + shape warmup happen
        # once per process, outside the timed window
        run_once()
        launches[0] = 0
        t0 = time.perf_counter()
        n_counted = run_once()
        dt = time.perf_counter() - t0
        rate = n_counted / dt
        headline_launches = launches[0]

        # -- baseline: per-signature dispatch on the SAME mocked engine -
        seq_n = min(args.seq_sigs, args.vals)
        rows = [
            (vset.validators[i].pub_key.bytes(), b"seq-%d" % i,
             sigs[i].signature)
            for i in range(seq_n)
        ]
        t0 = time.perf_counter()
        for row in rows:
            blk = EntryBlock.from_entries([row], scheme="secp256k1")
            # sequential shape: wait for THIS signature's verdict before
            # the next — one device launch per signature
            np.asarray(v.submit(blk).result(timeout=300))
        seq_rate = seq_n / (time.perf_counter() - t0)
    finally:
        v.close()
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        _pl.AsyncBatchVerifier._prepare = real_prepare

    speedup = rate / seq_rate if seq_rate else None
    out = {
        "schema_version": 1,
        "metric": "secp_commit_sigs_per_s",
        "value": round(rate, 1),
        "unit": "sigs/s",
        "mode": "real" if args.real else "mocked-device",
        "backend": os.environ.get("JAX_PLATFORMS", "") or "cpu",
        "scheme": "secp256k1",
        "vals": args.vals,
        "sigs_counted": n_counted,
        "device_rtt_ms": args.rtt_ms if not args.real else None,
        "launches": headline_launches,
        "epoch": "warm",
        "secp_seq_sigs_per_s": round(seq_rate, 1),
        "vs_per_sig": round(speedup, 2) if speedup else None,
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
            fh.write("\n")
    if speedup is None or speedup < 10.0:
        print(f"# FAIL: scheme-lane speedup {speedup} < 10x the per-sig "
              "baseline (ISSUE 19 acceptance)", file=sys.stderr)
        sys.exit(1)


def bls_main(argv) -> None:
    """`bench.py bls` — the BLS12-381 aggregation lane at committee
    scale (ISSUE 20).

    Drives K aggregated commits (ONE 96-byte signature + a signer
    bitmap each, 2302.00418's BLS shape) through the FULL production
    seam (prepare_aggregated_commit -> AggBlock -> pipeline coalescer
    -> fused multi-pairing launch -> conclude) with the device mocked
    behind a fixed per-launch device RTT (mock_vote_prepare: the real
    host prep — signature/pubkey status walk, epoch G1-table columns,
    mask/RLC-coefficient packing — and the H2D transfer run unchanged;
    the launch's verdict matures rtt_ms after launch). Headline:
    aggregated commits/s to conclude().

    Two economics columns ride along, both ANALYTIC from the launch
    ledger (widths the coalescer actually dispatched), not timed:

      pairings_per_commit   a sequential BLS verify pays 2 pairings
                            (2 Miller loops + 2 final exponentiations)
                            per commit; the fused lane pays 2W Miller
                            loops + ONE shared final exp per W-wide
                            launch — counting a pairing as one Miller +
                            one final exp, that amortizes to
                            1 + 1/(2W) < 2. This RLC fusion is the
                            structural contrast with the ECDSA lane,
                            where no such cross-signature fusion exists.
      wire_ratio_vs_ed25519 bytes of the aggregated commit vs the SAME
                            committee's per-signature ed25519 commit
                            (96B sig + V/8 bitmap vs V 64-byte rows +
                            addresses + timestamps) — gated at <= 0.10
                            for the 128-validator acceptance committee.

    Exits nonzero when a gate fails. Prints ONE JSON line; --out also
    writes it as an artifact file (AGG_r*.json, schema_version 1,
    rendered by tools/bench_report.py --trajectory and gated by
    --compare)."""
    import argparse

    import numpy as np

    ap = argparse.ArgumentParser(prog="bench.py bls")
    ap.add_argument("--vals", type=int, default=128,
                    help="BLS validators in the committee (default 128)")
    ap.add_argument("--commits", type=int, default=16,
                    help="aggregated commits in the window (default 16)")
    ap.add_argument("--rtt-ms", type=float, default=40.0,
                    help="mocked device round-trip per launch (default 40)")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.crypto import bls12381 as _bls
    from tendermint_tpu.libs.bits import BitArray
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import mock_vote_prepare
    from tendermint_tpu.types import validation as V
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        AggregatedCommit,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.wire.canonical import Timestamp

    chain_id = "bls-bench"
    print(f"# deriving {args.vals} bls12381 validators (pure-python G1 "
          "scalar muls)", file=sys.stderr)
    vals = []
    for i in range(args.vals):
        pk = _bls.PrivKey((i + 1).to_bytes(32, "big")).pub_key()
        vals.append(Validator.new(pk, 100))
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    bid = BlockID(hash=b"\x20" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x20" * 32))

    # ONE real signature shared across the window: the mocked device
    # never runs the pairing, but the host prep's signature_status
    # (decompress + G2 subgroup check) must see a live aggregate — and
    # memoizes per sig bytes exactly like production's repeated gossip
    print("# signing one aggregate (hash-to-G2 + cofactor clearing)",
          file=sys.stderr)
    full = BitArray(args.vals)
    for i in range(args.vals):
        full.set_index(i, True)
    probe = AggregatedCommit(height=1, round=0, block_id=bid, signers=full)
    sig = _bls.PrivKey(b"\x2a" * 32).sign(probe.sign_bytes(chain_id))

    def agg_at(h):
        ba = BitArray(args.vals)
        for i in range(args.vals):
            ba.set_index(i, True)
        return AggregatedCommit(height=h, round=0, block_id=bid,
                                signature=sig, signers=ba)

    # -- wire economics (real encodings, independent of the device) ------
    agg_bytes = len(agg_at(1).encode())
    ed_sigs = [CommitSig(
        block_id_flag=BLOCK_ID_FLAG_COMMIT,
        validator_address=v.address,
        timestamp=Timestamp(seconds=1_700_000_000, nanos=i + 1),
        signature=bytes(64),
    ) for i, v in enumerate(vals)]
    ed_bytes = len(Commit(height=1, round=0, block_id=bid,
                          signatures=ed_sigs).encode())
    wire_ratio = agg_bytes / ed_bytes

    _epoch.reset(8)
    _epoch.note_valset(vset)  # register
    _epoch.note_valset(vset)  # warm: pub48 columns + device G1 tables
    real_prepare = _pl.AsyncBatchVerifier._prepare
    widths = []
    mocked = mock_vote_prepare(real_prepare, args.rtt_ms / 1e3)

    def counting(entries):
        widths.append(len(entries))
        return mocked(entries)

    _pl.AsyncBatchVerifier._prepare = staticmethod(counting)
    v = _pl.AsyncBatchVerifier(depth=3)
    try:
        def run_once():
            pairs = [V.prepare_aggregated_commit(
                chain_id, vset, bid, h, agg_at(h), k_hint=args.commits)
                for h in range(1, args.commits + 1)]
            futs = [(v.submit(blk), conc) for blk, conc in pairs]
            for fut, conc in futs:
                conc(np.asarray(fut.result(timeout=600)))
            return len(pairs)

        # warm rep: pubkey_status memoization + epoch table upload +
        # shape warmup happen once per process, outside the timed window
        run_once()
        widths.clear()
        t0 = time.perf_counter()
        k = run_once()
        dt = time.perf_counter() - t0
    finally:
        v.close()
        _pl.AsyncBatchVerifier._prepare = real_prepare

    launches = len(widths)
    # a pairing = one Miller loop + one final exponentiation; a W-wide
    # fused launch runs 2W Millers (pads included — they burn device
    # lanes like any fixed-shape batch) and ONE shared final exp
    millers = sum(2 * w for w in widths)
    final_exps = launches
    pairings = millers / 2 + final_exps / 2
    pairings_per_commit = pairings / k
    sigs_replaced_per_pairing = (args.vals * k) / pairings
    rate = k / dt

    out = {
        "schema_version": 1,
        "metric": "bls_agg_commits_per_s",
        "value": round(rate, 1),
        "unit": "commits/s",
        "mode": "mocked-device",
        "backend": os.environ.get("JAX_PLATFORMS", "") or "cpu",
        "scheme": "bls12381",
        "vals": args.vals,
        "commits": k,
        "device_rtt_ms": args.rtt_ms,
        "launches": launches,
        "launch_widths": widths,
        "epoch": "warm",
        "pairings_per_commit": round(pairings_per_commit, 4),
        "sigs_replaced_per_pairing": round(sigs_replaced_per_pairing, 1),
        "agg_wire_bytes": agg_bytes,
        "ed25519_wire_bytes": ed_bytes,
        "wire_ratio_vs_ed25519": round(wire_ratio, 4),
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
            fh.write("\n")
    fails = []
    if pairings_per_commit >= 2.0:
        fails.append(f"pairings_per_commit {pairings_per_commit:.3f} >= 2 "
                     "(fusion must amortize the final exponentiation)")
    if args.vals >= 128 and wire_ratio > 0.10:
        fails.append(f"wire ratio {wire_ratio:.4f} > 0.10 vs the "
                     "per-signature ed25519 commit")
    for f in fails:
        print(f"# FAIL: {f} (ISSUE 20 acceptance)", file=sys.stderr)
    if fails:
        sys.exit(1)


def lanes_main(argv) -> None:
    """`bench.py lanes` — the ingress-fabric latency-vs-load curve
    (ISSUE 17).

    Drives one fabric lane per WINDOW POLICY through the mocked device
    (mock_mempool_prepare: real windowing, EntryBlock packing, host prep
    and transfer; each launch's verdict matures rtt_ms after launch) at
    both ends of the load curve:

      idle   lone signatures at a fixed inter-arrival — the latency a
             single request pays when nothing else is queued (p99 ms)
      flood  a paced signature flood — sustained sigs/s measured to the
             LAST verdict delivered

    Three policies: fixed-shallow (the latency end point: small window,
    small batch), fixed-deep (the throughput end point: big window, big
    batch), and adaptive (base == shallow, growth cap beyond deep).
    The gate is that adaptive holds BOTH ends of the curve:

      * at idle it strictly beats deep on p99 latency and stays within
        tolerance of shallow;
      * at flood it strictly beats shallow on DEVICE-LAUNCH ECONOMICS —
        sigs per launch window, the quantity the window policy actually
        controls (the device is one serial command channel, so fewer,
        fuller launches is the 2302.00418 batch-economics win) — while
        holding wall-clock throughput within tolerance of BOTH fixed
        policies. (Raw sigs/s alone cannot separate shallow from deep
        under backlog: flushes take the whole queue, so a backlogged
        shallow lane self-heals into big launches. The launch count is
        the honest fingerprint of the policy.)

    Exits nonzero when adaptive loses the curve.

    Prints ONE JSON line; --out also writes it as an artifact file
    (LANES_r*.json, schema_version 1, rendered by tools/bench_report.py
    --trajectory and gated by --compare)."""
    import argparse
    import threading

    ap = argparse.ArgumentParser(prog="bench.py lanes")
    ap.add_argument("--flood-sigs", type=int, default=8192,
                    help="signatures in the flood (default 8192)")
    ap.add_argument("--idle-sigs", type=int, default=25,
                    help="lone signatures at the idle end (default 25)")
    ap.add_argument("--idle-gap-ms", type=float, default=40.0,
                    help="idle inter-arrival (default 40)")
    ap.add_argument("--burst", type=int, default=96,
                    help="flood pacing: sigs per 1 ms burst (default 96)")
    ap.add_argument("--rtt-ms", type=float, default=20.0,
                    help="mocked device round-trip per launch (default 20)")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.ops import ingress as _fabric
    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import drain_pool, mock_mempool_prepare

    # 8 real signed triples, repeated to fill the streams: the device is
    # mocked (all-accept), so prep cost per entry — what the policies
    # differ on — is what matters, not verdict content
    triples = []
    for i in range(8):
        sk = _ed.gen_priv_key(bytes([i + 1]) * 32)
        msg = b"lanes-bench-%d" % i
        triples.append((sk.pub_key().bytes(), msg, sk.sign(msg)))

    # the three window policies: shallow/deep are the fixed end points,
    # adaptive spans past both (batch cap 8x base, window x8 / /4)
    policies = {
        "shallow": dict(batch=32, window_ms=4.0, adaptive=False),
        "deep": dict(batch=256, window_ms=32.0, adaptive=False),
        "adaptive": dict(batch=64, window_ms=4.0, adaptive=True),
    }

    real_prepare = _pl.AsyncBatchVerifier._prepare
    _pl.AsyncBatchVerifier._prepare = staticmethod(
        mock_mempool_prepare(real_prepare, args.rtt_ms / 1e3)
    )
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    eng = _fabric.IngressEngine()
    results = {}
    leaked = 0
    try:
        for name, pol in policies.items():
            # a FRESH verifier per policy — a shared one lets the
            # previous policy's flood tail queue under the next one's
            # idle measurement. depth=1: the device is ONE serial command
            # channel, so sigs per device launch — what
            # the window policy controls — bounds flood throughput
            # exactly the way the 2302.00418 batch economics say
            v = _pl.AsyncBatchVerifier(depth=1)
            mtx = threading.Lock()
            lat: list = []
            count = [0]
            target = [0]
            done = threading.Event()

            def deliver(items, verdicts, err, lat=lat, count=count,
                        target=target, done=done, mtx=mtx):
                now = time.perf_counter()
                with mtx:
                    for it in items:
                        lat.append((now - it.t_enq) * 1e3)
                    count[0] += len(items)
                    if count[0] >= target[0]:
                        done.set()

            lane = eng.register(_fabric.LaneSpec(
                name=f"bench-{name}", priority=_fabric.PRIORITY_INGRESS,
                verifier=v, entries_fn=lambda i: triples[i % 8],
                deliver=deliver, **pol))
            try:
                # -- idle end: lone signatures, per-item latency ---------
                with mtx:
                    lat.clear()
                    count[0] = 0
                    target[0] = args.idle_sigs
                    done.clear()
                for i in range(args.idle_sigs):
                    lane.submit(i)
                    time.sleep(args.idle_gap_ms / 1e3)
                if not done.wait(timeout=120):
                    raise RuntimeError(f"{name}: idle verdicts missing")
                with mtx:
                    idle_lat = sorted(lat)
                idle_p99 = idle_lat[int(0.99 * (len(idle_lat) - 1))]

                # -- flood end: paced bursts, time to last verdict -------
                with mtx:
                    lat.clear()
                    count[0] = 0
                    target[0] = args.flood_sigs
                    done.clear()
                t0 = time.perf_counter()
                for base in range(0, args.flood_sigs, args.burst):
                    for i in range(base,
                                   min(base + args.burst, args.flood_sigs)):
                        lane.submit(i)
                    time.sleep(0.001)
                if not done.wait(timeout=300):
                    raise RuntimeError(f"{name}: flood verdicts missing")
                flood_dt = time.perf_counter() - t0
                st = lane.stats()
            finally:
                lane.close(timeout=30)
                drain_pool(v._pool)
                leaked += v._pool.stats()["in_flight"]
                v.close()
            results[name] = {
                "idle_p99_ms": round(idle_p99, 2),
                "flood_sigs_per_s": round(args.flood_sigs / flood_dt, 1),
                "flood_launch_windows": st["batches"],
                "flood_sigs_per_window": round(
                    args.flood_sigs / max(st["batches"], 1), 1),
                "window_grows": st["window_grows"],
                "window_shrinks": st["window_shrinks"],
                "batch_final": st["max_batch"],
            }
            print(f"# {name}: idle_p99={results[name]['idle_p99_ms']}ms "
                  f"flood={results[name]['flood_sigs_per_s']} sigs/s "
                  f"windows={st['batches']} grows={st['window_grows']} "
                  f"shrinks={st['window_shrinks']}", file=sys.stderr)
    finally:
        eng.close(timeout=5)
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        _pl.AsyncBatchVerifier._prepare = real_prepare

    ad, sh, dp = (results[k] for k in ("adaptive", "shallow", "deep"))
    # the curve gate: adaptive strictly beats each fixed policy at the
    # end that policy is weak on — deep on idle p99, shallow on device-
    # command economics (sigs per launch window; raw sigs/s cannot
    # separate the policies under backlog because take-all flushes
    # self-heal a backlogged shallow lane into big launches) — and
    # holds wall-clock throughput/latency tolerance everywhere else
    checks = {
        "beats_deep_at_idle": ad["idle_p99_ms"] < 0.8 * dp["idle_p99_ms"],
        "beats_shallow_at_flood": (
            ad["flood_sigs_per_window"] > 1.3 * sh["flood_sigs_per_window"]),
        "holds_idle_vs_shallow": (
            ad["idle_p99_ms"] <= 1.15 * sh["idle_p99_ms"]),
        "holds_flood_vs_shallow": (
            ad["flood_sigs_per_s"] >= 0.9 * sh["flood_sigs_per_s"]),
        "holds_flood_vs_deep": (
            ad["flood_sigs_per_s"] >= 0.85 * dp["flood_sigs_per_s"]),
        "moved_both_directions": (
            ad["window_grows"] >= 1 and ad["window_shrinks"] >= 1),
        "no_pool_leak": leaked == 0,
    }
    ok = all(checks.values())
    out = {
        "schema_version": 1,
        "metric": "lanes_adaptive_flood_sigs_per_s",
        "value": ad["flood_sigs_per_s"],
        "unit": "sigs/s",
        "mode": "mocked-device",
        "backend": os.environ.get("JAX_PLATFORMS", "") or "cpu",
        "device_rtt_ms": args.rtt_ms,
        "flood_sigs": args.flood_sigs,
        "idle_sigs": args.idle_sigs,
        "idle_gap_ms": args.idle_gap_ms,
        "lanes_adaptive_idle_p99_ms": ad["idle_p99_ms"],
        "lanes_adaptive_sigs_per_window": ad["flood_sigs_per_window"],
        "lanes_shallow_flood_sigs_per_s": sh["flood_sigs_per_s"],
        "lanes_shallow_idle_p99_ms": sh["idle_p99_ms"],
        "lanes_shallow_sigs_per_window": sh["flood_sigs_per_window"],
        "lanes_deep_flood_sigs_per_s": dp["flood_sigs_per_s"],
        "lanes_deep_idle_p99_ms": dp["idle_p99_ms"],
        "adaptive_window_grows": ad["window_grows"],
        "adaptive_window_shrinks": ad["window_shrinks"],
        "adaptive_batch_final": ad["batch_final"],
        "policies": results,
        "checks": checks,
        "ok": ok,
        "pool_slots_leaked": leaked,
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
            fh.write("\n")
    if not ok:
        sys.exit(1)


def soak_main(argv) -> None:
    """`bench.py soak` — one cluster, all four workloads, SLO verdict
    (ISSUE 16).

    Runs the simnet soak harness (tendermint_tpu/simnet/soak.py): a live
    consensus cluster drives commit-echo verification, light-client
    request fleets, signed-tx floods through a partition/heal fault, and
    a crash-rejoin catch-up — all through ONE shared AsyncBatchVerifier
    — for a configurable virtual duration, with time-series telemetry
    sampled on the virtual clock and declarative per-lane SLO budgets
    evaluated at the end. The device is MOCKED by default
    (mock_mempool_prepare: real packing, host prep and transfer; the
    launch's all-accept verdict matures rtt_ms after launch), so the
    bench measures the harness and the QoS queue, not kernel time;
    --real runs live kernels.

    Prints ONE JSON summary line; --out writes the FULL artifact
    (SOAK_r*.json, schema_version 1: per-lane latency percentiles over
    time windows, gauge time series, final SLO verdict — rendered by
    tools/soak_report.py, gated by tools/bench_report.py --compare).
    Exits nonzero when the verdict is not green."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py soak")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="virtual seconds of combined load (default 30)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--catchup-at", type=int, default=0,
                    help="hold the catch-up replay until the live tip "
                    "reaches this height, so the node rejoins N heights "
                    "behind (0 = chase immediately)")
    ap.add_argument("--sample-s", type=float, default=1.0,
                    help="telemetry sampler cadence, virtual s (default 1)")
    ap.add_argument("--rtt-ms", type=float, default=4.0,
                    help="mocked device round-trip per launch (default 4)")
    ap.add_argument("--real", action="store_true",
                    help="run live kernels instead of the mocked device")
    ap.add_argument("--max-wall-s", type=float, default=1800.0)
    ap.add_argument("--out", default="",
                    help="also write the full artifact JSON to this path")
    args = ap.parse_args(argv)

    from tendermint_tpu.ops import pipeline as _pl
    from tendermint_tpu.ops._testing import drain_pool, mock_mempool_prepare
    from tendermint_tpu.simnet.soak import SoakConfig, SoakDriver

    real_prepare = _pl.AsyncBatchVerifier._prepare
    if not args.real:
        _pl.AsyncBatchVerifier._prepare = staticmethod(
            mock_mempool_prepare(real_prepare, args.rtt_ms / 1e3)
        )
        os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    v = _pl.AsyncBatchVerifier(depth=2)
    try:
        cfg = SoakConfig.from_env(
            duration_s=args.duration, seed=args.seed, n_nodes=args.nodes,
            sample_every_s=args.sample_s, max_wall_s=args.max_wall_s,
            catchup_at_height=args.catchup_at or None,
        )
        rec = SoakDriver(v, cfg).run()
        leaked = None
        if not args.real:
            drain_pool(v._pool)
            leaked = v._pool.stats()["in_flight"]
    finally:
        v.close()
        if not args.real:
            os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        _pl.AsyncBatchVerifier._prepare = real_prepare

    rec["mode"] = "real" if args.real else "mocked-device"
    rec["device_rtt_ms"] = args.rtt_ms if not args.real else None
    rec["backend"] = os.environ.get("JAX_PLATFORMS", "") or "cpu"
    rec["pool_slots_leaked"] = leaked
    # the ratchet block (tools/bench_report.py SOAK kind): direction-
    # aware compare keys — the p99s regress on RISE, heights/s on FALL
    lp = rec.get("lane_percentiles", {})
    rec["metric"] = "soak_slo_ok"
    rec["value"] = 1 if rec["ok"] else 0
    rec["unit"] = "verdict"
    rec["consensus_commit_p99_ms"] = lp.get("consensus", {}).get("p99_ms")
    rec["light_verdict_p99_ms"] = lp.get("light", {}).get("p99_ms")
    rec["ingress_admission_p99_ms"] = lp.get("ingress", {}).get("p99_ms")
    summary = {
        k: rec.get(k)
        for k in (
            "schema_version", "metric", "value", "unit", "ok", "reason",
            "mode", "device_rtt_ms", "backend", "seed", "duration_s",
            "virtual_s", "wall_s", "heights", "sampler_ticks",
            "consensus_commit_p99_ms", "light_verdict_p99_ms",
            "ingress_admission_p99_ms", "replay_heights_per_s",
            "pool_slots_leaked",
        )
    }
    print(json.dumps(summary, default=str))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rec, fh, indent=1, default=str)
            fh.write("\n")
    if not rec["ok"] or leaked:
        sys.exit(1)


if __name__ == "__main__":
    if sys.argv[1:2] == ["multichip"]:
        multichip_main(sys.argv[2:])
    elif sys.argv[1:2] == ["light"]:
        light_main(sys.argv[2:])
    elif sys.argv[1:2] == ["mempool"]:
        mempool_main(sys.argv[2:])
    elif sys.argv[1:2] == ["blocksync"]:
        blocksync_main(sys.argv[2:])
    elif sys.argv[1:2] == ["votes"]:
        votes_main(sys.argv[2:])
    elif sys.argv[1:2] == ["schemes"]:
        schemes_main(sys.argv[2:])
    elif sys.argv[1:2] == ["bls"]:
        bls_main(sys.argv[2:])
    elif sys.argv[1:2] == ["lanes"]:
        lanes_main(sys.argv[2:])
    elif sys.argv[1:2] == ["soak"]:
        soak_main(sys.argv[2:])
    elif os.environ.get("TM_TPU_BENCH_WORKER") == "1":
        worker()
    else:
        main()
