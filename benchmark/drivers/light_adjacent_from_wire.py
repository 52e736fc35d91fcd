"""light_adjacent_from_wire — one request is the wire bytes of a light
block in, the verdict out: LightBlock.decode(bytes), then
light.verifier.verify_adjacent(trusted, new.signed_header, new.validators,
trusting_period, now, max_clock_drift) against the header one height
below, which the client holds decoded in its store. Timed as one interval
on the caller's thread. request(i) verifies the block at height i + 2
against the one at height i + 1, so a lone caller walks the chain in
height order and wraps; every step carries a validator set the program
has not seen since the walk last came by.

The session interface is the one commit_from_wire.py documents; the
tracing, device and compile-count parts are that driver's own code.
"""

from __future__ import annotations

import os
import time

_now = time.perf_counter
WARM_STEPS = 32            # a 128-row table of 100 keys is full after 27


def _base():
    from benchmark import spec

    return spec.load_driver(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "commit_from_wire")


def open(config: dict, seed: int, root: str, chips: int, say):  # noqa: A001
    from tendermint_tpu.light.provider import LightBlock

    if not hasattr(LightBlock, "decode"):
        raise SystemExit("light_adjacent_from_wire: this program has no "
                         "LightBlock.decode: it cannot take a light block "
                         "from wire bytes")
    base = _base()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"light_adjacent_from_wire: JAX found no backend: {e}")
    if devices[0].platform != base.PLATFORM or len(devices) < chips:
        raise SystemExit(
            f"light_adjacent_from_wire: needs {chips} {base.PLATFORM} chip(s); "
            f"JAX reports {len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind})")
    return session_class(base)(config, seed, root, devices, say)


def session_class(base=None):
    """The session, as a subclass of commit_from_wire's (loaded by file
    name, as the harness loads it)."""
    base = base or _base()

    class Session(base.Session):
        def __init__(self, config, seed, root, devices, say):
            from tendermint_tpu.libs import jaxcache, metrics
            from tendermint_tpu.light import verifier
            from tendermint_tpu.light.provider import LightBlock
            from tendermint_tpu.observability import trace
            from tendermint_tpu.wire.canonical import Timestamp

            from benchmark import lightchain

            self._devices = devices
            self._jaxcache, self._ops_stats = jaxcache, metrics.ops_stats
            self._tracer = trace.TRACER
            self._decode = LightBlock.decode
            self._verify = verifier.verify_adjacent

            t = _now()
            pool = lightchain.pool(root, config, seed)
            self.setup = {"data_build_s": _now() - t}
            say(f"data: {len(pool.blocks)} light blocks x {pool.n_validators} "
                f"validators, {len(pool.blocks[1])} bytes each, "
                f"{'built' if pool.built else 'loaded from the pool cache'} "
                f"in {self.setup['data_build_s']:.2f}s")
            self._wire = pool.blocks
            # the light client's store: every header of the chain, decoded
            t = _now()
            self._trusted = [self._decode(w).signed_header for w in pool.blocks]
            say(f"store: {len(self._trusted)} trusted headers decoded in "
                f"{_now() - t:.2f}s")
            self._args = (float(pool.trusting_period_s), Timestamp(*pool.now),
                          float(pool.max_clock_drift_s))
            needed = pool.n_validators * pool.power * 2 // 3
            self.n_sigs = needed // pool.power + 1     # where the tally stops
            self.n_pool = len(pool.blocks) - 1
            self._blame = pool.blame
            self._base = self.counters()

        # -- the request -------------------------------------------------------

        def request(self, i: int) -> int:
            wire, trusted = self._wire[i + 1], self._trusted[i]
            t0 = _now()
            lb = self._decode(wire)
            t1 = _now()
            self._verify(trusted, lb.signed_header, lb.validators, *self._args)
            if self._tracer.enabled:
                self._tracer.record("bench.decode", t0, t1)
            return self.n_sigs

        # -- set-up ------------------------------------------------------------

        def warm(self, traffic: dict, say) -> None:
            """The chain's first steps, in order: first sight of a table
            (uncached kernel), the table's build and the cached kernel, a
            patched table, and a table rebuilt when the first is full."""
            t = _now()
            c0 = self.counters()
            for i in range(min(WARM_STEPS, self.n_pool)):
                t1, n0 = _now(), self.compiles()
                self.request(i)
                if self.compiles() > n0 or i < 3:
                    say(f"warm-up: step {i} (height {i + 2}): "
                        f"{_now() - t1:.3f}s, {self.compiles() - n0} new "
                        f"program(s)")
            c1 = self.counters()
            wall = _now() - t
            c = self._jaxcache.counters()
            compile_s = sum(s for _n, s in c["compiles"])
            self.setup.update(compile_s=compile_s,
                              trace_lower_s=max(wall - compile_s, 0.0))
            say(f"warm-up: {wall:.2f}s, of which backend compile or cache "
                f"load {compile_s:.2f}s ({c['requests']} requests, "
                f"{c['hits']} hits, {c['writes']} written); tables built "
                f"{c1['epoch_tables_built'] - c0['epoch_tables_built']}, sets "
                f"mapped {c1['epoch_tables_shared'] - c0['epoch_tables_shared']}"
                f", rows patched "
                f"{c1['epoch_rows_patched'] - c0['epoch_rows_patched']}; "
                f"launches by bucket {self._ops_stats()['batches_by_bucket']}")

        # -- counters and checks -----------------------------------------------

        def counters(self) -> dict:
            s = self._ops_stats()
            return dict(super().counters(), **{
                k: s.get(k, 0) for k in ("epoch_tables_shared",
                                         "epoch_rows_patched",
                                         "epoch_tables_built")})

        def _verdict(self, case):
            try:
                lb = self._decode(case.wire)
                self._verify(self._trusted[case.height - 2], lb.signed_header,
                             lb.validators, *self._args)
            except Exception as e:  # noqa: BLE001 — the verdict IS the error
                return (type(e).__name__, str(e))
            return None

        def check(self) -> list:
            """After the window, outside the clock: the blocks built to
            fail (and the one built to pass) must give what the plain
            reference says, through the same path, at a height served
            from a PATCHED table: the cases exist at two adjacent heights,
            and the second is run when a full table made a cold build of
            the first. No dispatch error, no host fallback all run."""
            bad = []
            h = self._blame[0][0].height
            for i in (h - 4, h - 3):        # the two steps below, honest
                self.request(i)
            for group in self._blame:
                built = self.counters()["epoch_tables_built"]
                for case in group:
                    got = self._verdict(case)
                    if got != case.expect:
                        bad.append(f"{case.what}: raised {got!r}, the "
                                   f"reference {case.expect!r}")
                if self.counters()["epoch_tables_built"] == built:
                    break
            else:
                bad.append("neither height's blame cases ran on a patched "
                           "table")
            now = self.counters()
            for k in ("dispatch_errors", "host_fallback_batches"):
                if now[k] != self._base[k]:
                    bad.append(f"{k} moved from {self._base[k]} to {now[k]}")
            return bad

    return Session
