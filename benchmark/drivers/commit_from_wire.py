"""commit_from_wire — one request is the wire bytes of a commit in, the
verdict out: Commit.decode(bytes), then types.validation.verify_commit
(chain_id, vals, block_id, height, commit), timed as one interval on the
caller's thread. Bytes are the one form of a commit no PR can reshape
(decode already builds the columns verify reads, so work can move across
that line at will), they are what a validator, a syncing node and a light
client are handed, and a fresh decode has empty per-commit caches.

What the harness asks of a driver module is `open(...)` returning a
session with: n_pool, setup (dict), request(i) -> signatures verified,
record_span(name, start, end), warm(traffic, say), compiles(),
counters(), check(), device(), trace_start(dir), trace_mark_end(),
trace_stop(), close().
"""

from __future__ import annotations

import os
import shutil
import threading
import time

PLATFORM = "tpu"           # no chip, no number
WARM_PASSES = 2            # bursts per caller count when warming the ladder
N_SYNC_MARKS = 5

_now = time.perf_counter


def open(config: dict, seed: int, root: str, chips: int, say):  # noqa: A001
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"commit_from_wire: JAX found no backend: {e}")
    if devices[0].platform != PLATFORM or len(devices) < chips:
        raise SystemExit(
            f"commit_from_wire: needs {chips} {PLATFORM} chip(s); JAX reports "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})")
    return Session(config, seed, root, devices, say)


class Session:
    def __init__(self, config, seed, root, devices, say):
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.libs import jaxcache, metrics
        from tendermint_tpu.observability import trace
        from tendermint_tpu.types import Validator, ValidatorSet, validation
        from tendermint_tpu.types.block import BlockID, Commit, PartSetHeader

        from benchmark import data

        self._devices = devices
        self._jaxcache, self._ops_stats = jaxcache, metrics.ops_stats
        self._tracer = trace.TRACER
        self._decode, self._verify = Commit.decode, validation.verify_commit

        t = _now()
        pool = data.pool(root, config, seed)
        self.setup = {"data_build_s": _now() - t}
        say(f"data: {len(pool.commits)} commits x {pool.n_validators} "
            f"signatures, {len(pool.commits[0])} bytes each, "
            f"{'built' if pool.built else 'loaded from the pool cache'} in "
            f"{self.setup['data_build_s']:.2f}s")

        def bid(d):
            return BlockID(hash=d, part_set_header=PartSetHeader(total=1, hash=d))

        self.vals = ValidatorSet.new([
            Validator.new(ed25519.PubKey(bytes(p)), pool.power)
            for p in pool.pubkeys])
        if [v.address for v in self.vals.validators] != [
                data.address(bytes(p)) for p in pool.pubkeys]:
            raise RuntimeError("the program orders the validator set "
                               "otherwise than the data builder signed it")
        self.chain_id = pool.chain_id
        self.n_sigs = pool.n_validators
        self.n_pool = len(pool.commits)
        self._jobs = [(w, bid(d), h) for w, d, h in
                      zip(pool.commits, pool.digests, pool.heights)]
        self._blame = [(c, bid(c.digest)) for c in pool.blame]
        self._base = self.counters()

    # -- the request -----------------------------------------------------------

    def request(self, i: int) -> int:
        wire, block_id, height = self._jobs[i]
        t0 = _now()
        commit = self._decode(wire)
        t1 = _now()
        self._verify(self.chain_id, self.vals, block_id, height, commit)
        if self._tracer.enabled:
            self._tracer.record("bench.decode", t0, t1)
        return self.n_sigs

    def record_span(self, name, start, end) -> None:
        if self._tracer.enabled:
            self._tracer.record(name, start, end)

    # -- set-up ----------------------------------------------------------------

    def _burst(self, k: int) -> None:
        """k decoded commits enter verify_commit together: the arrival
        pattern under which the dispatcher fuses launches."""
        gate = threading.Barrier(k)
        errors = []

        def one(i):
            wire, block_id, height = self._jobs[i % self.n_pool]
            commit = self._decode(wire)
            gate.wait()
            try:
                self._verify(self.chain_id, self.vals, block_id, height, commit)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(k)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def warm(self, traffic: dict, say) -> None:
        """Every shape this cell can meet and no other cell's: the cold
        first sight of the validator set (uncached kernel), the table
        upload and the cached kernel, then — for concurrent callers — the
        coalesced ladder, met by bursts of 2..callers commits."""
        t = _now()
        for step in ("cold (first sight of the set)", "warm (table upload)",
                     "repeat"):
            t1 = _now()
            self.request(0)
            say(f"warm-up: verify_commit {step}: {_now() - t1:.3f}s")
        callers = traffic["generator"].get(
            "callers", traffic["generator"].get("workers", 1))
        for k in range(2, callers + 1):
            t1, n0 = _now(), self.compiles()
            for _ in range(WARM_PASSES):
                self._burst(k)
            if self.compiles() > n0:
                say(f"warm-up: bursts of {k}: {_now() - t1:.3f}s, "
                    f"{self.compiles() - n0} new program(s)")
        wall = _now() - t
        c = self._jaxcache.counters()
        compile_s = sum(s for _n, s in c["compiles"])
        self.setup.update(compile_s=compile_s,
                          trace_lower_s=max(wall - compile_s, 0.0))
        say(f"warm-up: {wall:.2f}s, of which backend compile or cache load "
            f"{compile_s:.2f}s ({c['requests']} requests, {c['hits']} hits, "
            f"{c['writes']} written); launches by bucket "
            f"{self._ops_stats()['batches_by_bucket']}")

    def compiles(self) -> int:
        return len(self._jaxcache.counters()["compiles"])

    # -- counters and checks ---------------------------------------------------

    def counters(self) -> dict:
        s = self._ops_stats()
        buckets = {int(b): n for b, n in s["batches_by_bucket"].items()
                   if str(b).isdigit()}
        return {
            "sigs_verified_device": s["sigs_verified_device"],
            "sigs_verified_host": s["sigs_verified_host"],
            "launches": sum(s["batches_by_bucket"].values()),
            "launch_capacity": sum(b * n for b, n in buckets.items()),
            "epoch_cache_hits": s["epoch_cache_hits"],
            "epoch_cache_misses": s["epoch_cache_misses"],
            "host_fallback_batches": s["host_fallback_batches"],
            "dispatch_errors": s["dispatch_errors"],
            "h2d_bytes_per_commit": s["h2d_bytes_per_commit"],
            "sigs_per_request": self.n_sigs,
        }

    def check(self) -> list:
        """After the window, outside the clock: the commits built to fail
        must raise what the plain reference says, through the same path;
        no dispatch error and no host fallback all run."""
        bad = []
        for case, block_id in self._blame:
            try:
                self._verify(self.chain_id, self.vals, block_id, case.height,
                             self._decode(case.wire))
                got = None
            except Exception as e:  # noqa: BLE001 — the verdict IS the error
                got = (type(e).__name__, str(e))
            if got != case.expect:
                bad.append(f"{case.what}: raised {got!r}, the reference "
                           f"{case.expect!r}")
        now = self.counters()
        for k in ("dispatch_errors", "host_fallback_batches"):
            if now[k] != self._base[k]:
                bad.append(f"{k} moved from {self._base[k]} to {now[k]}")
        return bad

    def device(self) -> dict:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self._devices]
        return {"platform": self._devices[0].platform,
                "kind": self._devices[0].device_kind,
                "count": len(self._devices), "memory_peak_bytes": max(peaks)}

    # -- the traced stretch ----------------------------------------------------

    def trace_start(self, out_dir: str) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self._trace_dir = out_dir
        self._marks = []
        self._tracer.clear()
        self._tracer.configure(enabled=True)
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        self._mark()
        self._c0, self._cpu0 = self.counters(), time.process_time()
        self._t_a = _now()

    def _mark(self) -> None:
        import jax

        for _ in range(N_SYNC_MARKS):
            self._marks.append(_now())
            with jax.profiler.TraceAnnotation("bench.sync"):
                pass
            time.sleep(0.0002)

    def trace_mark_end(self) -> None:
        self._t_b = _now()
        self._c1, self._cpu1 = self.counters(), time.process_time()
        self._tracer.configure(enabled=False)
        self._spans = [[n, s, e, tid] for n, s, e, tid, _a
                       in self._tracer.events()]
        self._recorded = self._tracer.recorded_total

    def trace_stop(self) -> dict:
        import jax

        from benchmark import xplane

        self._mark()
        jax.profiler.stop_trace()
        path = xplane.newest(self._trace_dir)
        events, notes = (xplane.read(path, self._marks) if path
                         else ([], {"error": "the profiler wrote no trace"}))
        shutil.rmtree(os.path.join(self._trace_dir, "plugins"),
                      ignore_errors=True)   # tens of MB a traced run
        return {
            "trace": {"t_a": self._t_a, "t_b": self._t_b,
                      "device_events": events, "spans": self._spans,
                      "spans_recorded": self._recorded,
                      "ring_capacity": self._tracer.capacity},
            "counters": {"before": self._c0, "after": self._c1},
            "cpu_s": self._cpu1 - self._cpu0, "notes": notes,
        }

    def close(self) -> None:
        pass
