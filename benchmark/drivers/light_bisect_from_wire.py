"""light_bisect_from_wire — one request is one catch-up of a light client
that was offline: a fresh light.Client on a fresh store, root of trust at
the configuration's trusted height, asked for its target height, which it
reaches by skipping (light/client.go verifySkipping: the far header
against the trusted validator set at the trust level, then against its
own, bisecting at 9/16 when too little trusted power signed). Primary and
witness are one provider that serves tendermint.types.LightBlock wire
bytes from memory, decoded at every call; the whole catch-up is timed as
one interval on the caller's thread and counts the signatures the plain
reference (benchmark/reference_bisect.py) says it looks at.

The session interface is the one commit_from_wire.py documents; the
tracing, device and compile-count parts are that driver's own code.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import random
import time

_now = time.perf_counter
WARM_REQUESTS = 2
HOP_COUNTERS = ("light_hops_verified", "light_hops_refused",
                "light_blocks_fetched", "light_trusting_sigs_host",
                "light_trusting_sigs_device")
# read as the last whole request left them: the hop counters, and what a
# ratio sets beside them
WHOLE_REQUESTS = HOP_COUNTERS + ("epoch_tables_built",)


def _base():
    from benchmark import spec

    return spec.load_driver(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "commit_from_wire")


def open(config: dict, seed: int, root: str, chips: int, say):  # noqa: A001
    base = _base()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"light_bisect_from_wire: JAX found no backend: {e}")
    if devices[0].platform != base.PLATFORM or len(devices) < chips:
        raise SystemExit(
            f"light_bisect_from_wire: needs {chips} {base.PLATFORM} chip(s); "
            f"JAX reports {len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind})")
    return session_class(base)(config, seed, root, devices, say)


def _fraction(text: str):
    num, den = text.split("/")
    return int(num), int(den)


def stops(cfg: dict) -> tuple:
    """Signatures of equal power at which the two checks of a hop stop:
    above the trust level of the trusted set, above two thirds of the new."""
    n, power = cfg["validators"], cfg["voting_power"]
    num, den = _fraction(cfg["trust_level"])
    return ((n * power * num // den) // power + 1,
            (n * power * 2 // 3) // power + 1)


# A catch-up built to fail (or to pass where a check must not look): the
# target height, what the client is told it is, what the provider serves
# instead of the honest block ({height: wire bytes}), and what the plain
# reference does with it (a reference_bisect.CatchUp).
Case = collections.namedtuple("Case", "what target now served expect")


def _flip(blk, idx: int, rng):
    sigs = list(blk.sigs)
    sig = bytearray(sigs[idx][2])
    sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
    sigs[idx] = (sigs[idx][0], sigs[idx][1], bytes(sig))
    return dataclasses.replace(blk, sigs=tuple(sigs))


def build_cases(cfg: dict, keys, blocks, honest, seed: int) -> list:
    """The fault cases of the configuration's `guarantees`, each with the
    reference's verdict, built on the first hop the honest catch-up
    verifies (trusted height -> `hop`)."""
    from benchmark import lightchain, reference_bisect

    rng = random.Random(f"{seed}/{cfg['name']}/bisect-cases")
    level = _fraction(cfg["trust_level"])
    first, now = cfg["trusted_height"], _clock(cfg)
    root, hop = blocks[first - 1], honest.trace[1]
    blk = blocks[hop - 1]
    trusted = {v.address for v in root.vals}
    stop_trusting, stop_light = stops(cfg)
    by_address = [i for i, v in enumerate(blk.vals) if v.address in trusted]
    third = by_address[:stop_trusting]
    inside_new = [i for i in range(stop_light) if i not in third]
    past_both = [i for i in range(stop_light, len(blk.vals))
                 if i not in third]
    if len(third) < stop_trusting or not inside_new or not past_both:
        raise RuntimeError(f"hop {first}->{hop} cannot carry the fault cases")
    swapped = list(blk.vals)
    _sk, pub, addr = keys[10 ** 6 + hop]                # a key of no set
    j = rng.randrange(len(swapped))
    swapped[j] = dataclasses.replace(swapped[j], pub=pub, address=addr)
    # the widest gap that verifies in one hop: a third of the trusted power
    # is still in the set (one key leaves a height)
    widest = first + (len(root.vals) - stop_trusting
                      ) // cfg["keys_replaced_per_height"]
    expired = (root.header.seconds + cfg["trusting_period_s"],
               root.header.nanos)
    plans = [
        ("forged_in_trusted_third", hop, now,
         {hop: (_flip(blk, rng.choice(third), rng), blk.vals)}),
        ("forged_in_new_two_thirds", hop, now,
         {hop: (_flip(blk, rng.choice(inside_new), rng), blk.vals)}),
        ("forged_past_both_stops", hop, now,
         {hop: (_flip(blk, rng.choice(past_both), rng), blk.vals)}),
        ("swapped_valset", hop, now, {hop: (blk, tuple(swapped))}),
        ("expired_root", hop, expired, {}),
        ("widest_gap", widest, now, {}),
        ("widest_gap_plus_one", widest + 1, now, {}),
    ]
    cases = []
    for what, target, at, served in plans:
        def fetch(h, served=served):
            return served.get(h) or (blocks[h - 1], blocks[h - 1].vals)
        expect = reference_bisect.catch_up(
            fetch, cfg["chain_id"], first, root.block_hash, target,
            cfg["trusting_period_s"], at, cfg["max_clock_drift_s"], level)
        cases.append(Case(
            f"{what}@{target}", target, at,
            {h: lightchain.light_block_wire(b, vals=v)
             for h, (b, v) in served.items()}, expect))
    said = {c.what.split("@")[0]: c.expect for c in cases}
    ok = (said["forged_in_trusted_third"].error
          and said["forged_in_new_two_thirds"].error
          and said["forged_past_both_stops"].error is None
          # refused before any signature of the hop: the root's check alone
          and said["swapped_valset"].error
          and said["swapped_valset"].sigs == stop_light
          and said["expired_root"].error[0] == "ErrOldHeaderExpired"
          and said["widest_gap"].trace == [first, widest]
          and not said["widest_gap"].refused
          and said["widest_gap_plus_one"].refused == [(first, widest + 1)])
    if not ok:
        raise RuntimeError(f"the reference says {said!r}")
    return cases


def _clock(cfg: dict) -> tuple:
    from benchmark import lightchain

    return (lightchain.T0 + cfg["now_s_after_btime"], 0)


def session_class(base=None):
    """The session, as a subclass of commit_from_wire's (loaded by file
    name, as the harness loads it)."""
    base = base or _base()

    class Session(base.Session):
        def __init__(self, config, seed, root, devices, say):
            from tendermint_tpu.db import MemDB
            from tendermint_tpu.libs import jaxcache, metrics
            from tendermint_tpu.light import client, provider, store
            from tendermint_tpu.observability import trace
            from tendermint_tpu.types import Fraction
            from tendermint_tpu.wire.canonical import Timestamp

            from benchmark import lightchain, reference_bisect

            self._devices = devices
            self._jaxcache, self._ops_stats = jaxcache, metrics.ops_stats
            self._tracer = tracer = trace.TRACER
            decode = provider.LightBlock.decode

            class Node(provider.Provider):
                """The full node: light blocks as wire bytes in memory,
                decoded anew at every call (the harness's bench.decode)."""

                def __init__(self, wire, other=None):
                    self.wire, self.other = wire, other or {}
                    self.asked = []

                def light_block(self, height: int):
                    height = height or len(self.wire)
                    self.asked.append(height)
                    raw = self.other.get(height)
                    if raw is None:
                        if not 1 <= height <= len(self.wire):
                            raise provider.ErrLightBlockNotFound(height)
                        raw = self.wire[height - 1]
                    t0 = _now()
                    lb = decode(raw)
                    if tracer.enabled:
                        tracer.record("bench.decode", t0, _now())
                    return lb

            t = _now()
            keys, blocks = lightchain.chain(config, seed)
            self._wire = [lightchain.light_block_wire(b) for b in blocks]
            first, target = config["trusted_height"], config["target_height"]
            level = _fraction(config["trust_level"])
            self._honest = reference_bisect.catch_up(
                lambda h: (blocks[h - 1], blocks[h - 1].vals),
                config["chain_id"], first, blocks[first - 1].block_hash,
                target, config["trusting_period_s"], _clock(config),
                config["max_clock_drift_s"], level)
            if self._honest.error is not None:
                raise RuntimeError("the reference refuses the honest chain: "
                                   f"{self._honest.error!r}")
            self._cases = build_cases(config, keys, blocks, self._honest, seed)
            self.setup = {"data_build_s": _now() - t}
            h = self._honest
            say(f"data: {len(blocks)} light blocks x {config['validators']} "
                f"validators, {len(self._wire[0])} bytes each, built in "
                f"{self.setup['data_build_s']:.2f}s; one catch-up "
                f"{first} -> {target}: {len(h.trace) - 1} hops, "
                f"{len(h.refused)} refused, {len(h.fetched)} fetched, "
                f"{h.sigs} signatures (reference)")

            def catch_up(target, now, other=None):
                """The request: a fresh client on a fresh store."""
                node = Node(self._wire, other)
                at = Timestamp(*now)
                c = client.Client(
                    config["chain_id"],
                    client.TrustOptions(float(config["trusting_period_s"]),
                                        first, blocks[first - 1].block_hash),
                    node, [node] * config["witnesses"],
                    store.LightStore(MemDB()),
                    trust_level=Fraction(*level),
                    max_clock_drift=float(config["max_clock_drift_s"]),
                    now_fn=lambda: at)
                return c.verify_light_block_at_height(target, at), node.asked

            self._catch_up = catch_up
            self._target = (target, _clock(config), blocks[target - 1].block_hash)
            self._hashes = {b.height: b.block_hash for b in blocks}
            self.n_sigs = h.sigs
            # what one launch carries: the gauge h2d_bytes_per_commit is a
            # launch's bytes, and a launch here is one +2/3 check
            self._third, self._launch_sigs = stops(config)
            self.n_pool = 1
            self._counted = HOP_COUNTERS[0] in self._ops_stats()
            # the program's hop counters as the last whole request left
            # them (40 us of a request), so that a snapshot taken while a
            # request is under way counts whole requests and their hops
            self._rebase()

        def _rebase(self) -> None:
            """What check() accounts for starts here: nothing but honest
            requests may touch the program's counters until it runs."""
            self._done = 0             # honest catch-ups completed
            self._strayed = []         # requests that fetched other heights
            self._hops = self._hop_counters()
            self._base = self.counters()

        # -- the request -------------------------------------------------------

        def request(self, i: int) -> int:
            target, now, want = self._target
            lb, asked = self._catch_up(target, now)
            if lb.height != target or lb.hash() != want:
                raise RuntimeError(f"verified {lb.height} {lb.hash().hex()}, "
                                   f"the chain has {want.hex()} at {target}")
            if asked != self._honest.fetched and len(self._strayed) < 5:
                self._strayed.append(asked)
            self._done += 1
            self._hops = self._hop_counters()
            return self.n_sigs

        def _hop_counters(self) -> dict:
            s = self._ops_stats()
            return {k: s.get(k, 0) for k in WHOLE_REQUESTS}

        # -- set-up ------------------------------------------------------------

        def warm(self, traffic: dict, say) -> None:
            """Whole requests: the root's and every hop's +2/3 check meet
            the uncached kernel at the one shape they have; nothing else
            of the request reaches the device."""
            t = _now()
            for k in range(WARM_REQUESTS):
                t1, n0 = _now(), self.compiles()
                try:
                    self.request(0)
                except Exception as e:  # noqa: BLE001 — no client, no cell
                    raise SystemExit(
                        "light_bisect_from_wire: this program's light client "
                        f"cannot catch up on an honest chain: {e!r}")
                say(f"warm-up: catch-up {k}: {_now() - t1:.3f}s, "
                    f"{self.compiles() - n0} new program(s)")
            wall = _now() - t
            c = self._jaxcache.counters()
            compile_s = sum(s for _n, s in c["compiles"])
            self.setup.update(compile_s=compile_s,
                              trace_lower_s=max(wall - compile_s, 0.0))
            now = self.counters()
            say(f"warm-up: {wall:.2f}s, of which backend compile or cache "
                f"load {compile_s:.2f}s ({c['requests']} requests, "
                f"{c['hits']} hits, {c['writes']} written); tables built "
                f"{now['epoch_tables_built'] - self._base['epoch_tables_built']}"
                f", sets mapped "
                f"{now['epoch_tables_shared'] - self._base['epoch_tables_shared']}"
                f"; launches by bucket {self._ops_stats()['batches_by_bucket']}")

        # -- counters and checks -----------------------------------------------

        def counters(self) -> dict:
            s = self._ops_stats()
            out = dict(super().counters(), sigs_per_request=self._launch_sigs,
                       **{k: s.get(k, 0) for k in (
                           "epoch_tables_shared", "epoch_rows_patched",
                           "h2d_ops")}, **self._hops)
            # requests counted only where the program counts hops, so that
            # a ratio over them reads nothing on a program that does not
            out["catchups"] = self._done if self._counted else 0
            return out

        def _said(self, case: Case):
            """What the program did with a case, in the reference's form:
            (error, heights asked of the provider)."""
            try:
                lb, asked = self._catch_up(case.target, case.now, case.served)
            except Exception as e:  # noqa: BLE001 — the verdict IS the error
                return (type(e).__name__, str(e)), None
            if lb.hash() != self._hashes[case.target]:
                return ("accepted another block", lb.hash().hex()), asked
            return None, asked

        def _attempts(self):
            """One honest catch-up under the tracer: its attempts as the
            program's spans tell them, or None where it has none."""
            tr = self._tracer
            tr.clear()
            tr.configure(enabled=True)
            try:
                self.request(0)
            finally:
                tr.configure(enabled=False)
            spans = [a for n, _s, _e, _tid, a in tr.events()
                     if n == "light.bisect.attempt"]
            tr.clear()
            if not spans:
                return None
            hops = [(a["from"], a["to"]) for a in spans
                    if a["outcome"] == "verified"]
            return ([hops[0][0]] + [t for _f, t in hops],
                    [(a["from"], a["to"]) for a in spans
                     if a["outcome"] == "not_enough_trust"])

        def check(self) -> list:
            """After the window, outside the clock. Every request returned
            the chain's block (or failed); here: each fetched the heights
            the reference fetches, the program's counters moved by what
            the reference counts a request, a traced request made the
            reference's hops and refusals, and each case built to fail (or
            to pass) gives the reference's verdict through the same path."""
            bad = []
            h = self._honest
            for asked in self._strayed:
                bad.append(f"a request fetched {asked}, the reference "
                           f"{h.fetched}")
            got = self._attempts()
            if got is not None and got != (h.trace, h.refused):
                bad.append(f"hops and refusals {got!r}, the reference "
                           f"{(h.trace, h.refused)!r}")
            now, n = self.counters(), self._done

            def rise(*keys):
                return sum(now[k] - self._base[k] for k in keys)

            sigs = rise("sigs_verified_device", "sigs_verified_host")
            if sigs != n * h.sigs:
                bad.append(f"{sigs} signatures verified in {n} catch-ups, "
                           f"the reference counts {h.sigs} each")
            # a signature verified on the host belongs to a whole check that
            # ran under the device threshold (the trusting third, at this
            # size): host signatures = a thirds + b two-thirds checks, in
            # as many batches as the program says ran under it
            third, light, hops = self._third, self._launch_sigs, len(h.trace) - 1
            on_host, under = rise("sigs_verified_host"), rise(
                "host_fallback_batches")
            b, rest = divmod(on_host - third * under, light - third)
            if rest or not (0 <= b <= n * (hops + 1)
                            and 0 <= under - b <= n * hops):
                bad.append(f"{on_host} signatures on the host in {under} "
                           f"batches under the device threshold: not whole "
                           f"checks of {third} or {light}")
            if rise("dispatch_errors"):
                bad.append(f"dispatch_errors moved by {rise('dispatch_errors')}")
            if self._counted:
                want = {"light_hops_verified": len(h.trace) - 1,
                        "light_hops_refused": len(h.refused),
                        "light_blocks_fetched": len(h.fetched)}
                for k, per in want.items():
                    if rise(k) != n * per:
                        bad.append(f"{k} rose {rise(k)} in {n} catch-ups, "
                                   f"the reference counts {per} each")
                if rise("light_trusting_sigs_host",
                        "light_trusting_sigs_device") != n * third * (
                            len(h.trace) - 1):
                    bad.append("light_trusting_sigs_* rose "
                               f"{rise('light_trusting_sigs_host', 'light_trusting_sigs_device')}"
                               f" in {n} catch-ups of {len(h.trace) - 1} hops "
                               f"x {third}")
            for case in self._cases:
                error, asked = self._said(case)
                want = case.expect
                if error != want.error or (asked is not None
                                           and asked != want.fetched):
                    bad.append(f"{case.what}: {error!r} after fetching "
                               f"{asked}, the reference {want.error!r} after "
                               f"{want.fetched}")
            if self.counters()["dispatch_errors"] != self._base["dispatch_errors"]:
                bad.append("dispatch_errors moved during the fault cases")
            self._rebase()
            return bad

    return Session
