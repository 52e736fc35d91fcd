"""sr_commit_from_wire — commit_from_wire's request over a validator set
whose keys are sr25519: the wire bytes of a commit in, the verdict out,
Commit.decode(bytes) then types.validation.verify_commit(chain_id, vals,
block_id, height, commit), timed as one interval on the caller's thread.
The pool is benchmark/data_sr25519.py's, signed and judged by the plain
schnorrkel reference (benchmark/reference_sr25519.py).

The session is commit_from_wire's (loaded by file name, as the harness
loads it), with its own set-up and two more checks after the window: the
program's sr25519 counters, where it keeps them, say that every signature
of every request went to the device and none to the host.
"""

from __future__ import annotations

import os
import time

_now = time.perf_counter
SR_COUNTERS = ("sr25519_sigs_device", "sr25519_sigs_host")


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
        raise SystemExit(f"sr_commit_from_wire: JAX found no backend: {e}")
    if devices[0].platform != base.PLATFORM or len(devices) < chips:
        raise SystemExit(
            f"sr_commit_from_wire: needs {chips} {base.PLATFORM} chip(s); "
            f"JAX reports {len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind})")
    return session_class(base)(config, seed, root, devices, say)


def session_class(base=None):
    """The session, as a subclass of commit_from_wire's."""
    base = base or _base()

    class Session(base.Session):
        def __init__(self, config, seed, root, devices, say):
            from tendermint_tpu.crypto import sr25519
            from tendermint_tpu.libs import jaxcache, metrics
            from tendermint_tpu.observability import trace
            from tendermint_tpu.types import Validator, ValidatorSet, validation
            from tendermint_tpu.types.block import (
                BlockID, Commit, PartSetHeader,
            )

            from benchmark import data, data_sr25519

            self._devices = devices
            self._jaxcache, self._ops_stats = jaxcache, metrics.ops_stats
            self._tracer = trace.TRACER
            self._decode, self._verify = Commit.decode, validation.verify_commit

            t = _now()
            pool = data_sr25519.pool(root, config, seed)
            self.setup = {"data_build_s": _now() - t}
            say(f"data: {len(pool.commits)} commits x {pool.n_validators} "
                f"sr25519 signatures, {len(pool.commits[0])} bytes each, "
                f"{'built' if pool.built else 'loaded from the pool cache'} "
                f"in {self.setup['data_build_s']:.2f}s")

            def bid(d):
                return BlockID(hash=d,
                               part_set_header=PartSetHeader(total=1, hash=d))

            self.vals = ValidatorSet.new([
                Validator.new(sr25519.PubKey(bytes(p)), pool.power)
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
            self._requests = 0
            self._base = self.counters()
            self._sr_base = self._sr_counters()

        def request(self, i: int) -> int:
            n = super().request(i)
            self._requests += 1
            return n

        def _sr_counters(self):
            """The program's sr25519 counters, or None where it keeps
            none."""
            s = self._ops_stats()
            if not all(k in s for k in SR_COUNTERS):
                return None
            return {k: s[k] for k in SR_COUNTERS}

        def check(self) -> list:
            """commit_from_wire's checks, after these two: every signature
            of the requests made went to the device, none to the host."""
            bad = []
            now = self._sr_counters()
            if now is not None and self._sr_base is not None:
                dev = now["sr25519_sigs_device"] - self._sr_base[
                    "sr25519_sigs_device"]
                host = now["sr25519_sigs_host"] - self._sr_base[
                    "sr25519_sigs_host"]
                if dev != self._requests * self.n_sigs:
                    bad.append(f"{dev} sr25519 signatures on the device for "
                               f"{self._requests} requests of {self.n_sigs}")
                if host:
                    bad.append(f"{host} sr25519 signatures on the host")
            bad += super().check()
            # the cases built to fail counted too: a later check counts
            # from here
            self._sr_base, self._requests = self._sr_counters(), 0
            return bad

    return Session
