#!/usr/bin/env python3
"""chip_smoke.py — does the commit-verify path run on the chip, today?

The quickest proof that the system still starts on one TPU chip with the
installed JAX. One process, the default device, every byte of data made
from --seed. It drives the main path once, through the entry points a
user calls, at the reference's own sizes (MaxVotesCount = 10 000
validators, types/vote_set.go:18; the Cosmos-Hub-sized 150-validator
commit; a 128-validator header chain):

  1. refuse     exit non-zero, before building anything, unless
                jax.default_backend() is "tpu"
  2. library    types.validation.verify_commit / verify_commit_light on
                150- and 10 000-validator commits: cold (first sight of
                the validator set), warm (epoch table upload) and repeat
  3. blame      forged signatures and sub-2/3 commits must raise the
                byte-identical error of the sequential reference
                (_verify_commit_single)
  4. stream     8 distinct 10 000-validator commits from 8 threads
                through the shared dispatcher, until the largest
                coalesced bucket has launched
  5. server     an in-process node: GET /status, then a few dozen
                /light_verify requests over HTTP (one forged)
  6. churn      30 heights of a 100-validator chain that replaces one
                key a height, each light block from its wire bytes
                through light.verifier.verify_adjacent: tables built,
                sets mapped onto a resident table, rows patched and the
                kernels launched are printed and held to what one key a
                height into a 128-row table implies; every set must have
                been decoded by the native pass
  7. skipping   one catch-up of a fresh light.Client over 150 heights of
                such a chain (benchmark/lightchain.py builds it), by
                skipping at trust level 1/3: hops, refusals, fetches and
                signatures are held to the plain reference
                (benchmark/reference_bisect.py); the by-address third of
                every hop rides the launch of its +2/3 check (34 + 67
                signatures, one submission): nothing on the host
  8. accounts   sigs_verified{device} rose by exactly what stages 2-7
                submitted; host / fallback / dispatch-error counters
                moved only by what the smoke states
  9. report     per-step wall time (first-use set-up apart from
                repeats), every compile, one JSON line last

Any stage failing fails the run: nothing is caught and skipped, and no
path below turns a missing chip into exit 0. It measures nothing a
benchmark would report — times printed here are for finding what is slow
to start, not for claims.

    python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import random
import sys
import tempfile
import time
import urllib.request

HUB_VALS = 150        # BASELINE config #2 (Cosmos-Hub-sized commit)
BIG_VALS = 10_000     # BASELINE config #3 (MaxVotesCount)
LIGHT_VALS = 128      # BASELINE config #5's header-chain shape
STREAM = 8            # concurrent big commits (verify_commit_stream8)
N_ADJACENT = 24       # /light_verify requests h -> h+1
N_SKIPPING = 11       # /light_verify requests 0 -> k (two stages each)
CHURN_VALS = 100      # the light-client sequence benchmark's set
N_CHURN = 30          # adjacent steps, one key replaced at each
N_SKIP = 150          # heights the skipping client catches up across
POWER = 100           # every validator's voting power
CHAIN_ID = "chip-smoke"
T0 = 1_600_000_000

_START = time.perf_counter()


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _START:7.1f}s] {msg}", flush=True)


class Failed(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


# -- stage 1 -----------------------------------------------------------------


def refuse_without_chip() -> dict:
    """Everything else builds on this: no TPU, no run."""
    import jax

    try:
        platform = jax.default_backend()
        devices = jax.devices()
    except RuntimeError as e:  # a TPU build that cannot reach its chip
        sys.exit(f"chip_smoke: JAX could not initialise a backend: {e}")
    if platform != "tpu" or not devices or devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found — jax.default_backend() is "
            f"{platform!r}, devices {devices}. This smoke only passes on "
            f"the chip (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})."
        )
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def describe_install(device: dict) -> dict:
    import jax
    import jaxlib

    from tendermint_tpu import native
    from tendermint_tpu.libs import jaxcache

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    t = time.perf_counter()
    built_here = not os.path.exists(native._so_path())
    native_ok = native.load() is not None
    info = {
        "device": device,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "python": sys.version.split()[0],
        "compile_cache_dir": jaxcache.cache_dir(),
        "compile_cache_from_env": bool(os.environ.get(jaxcache.ENV_DIR)),
        "native_loaded": native_ok,
        "native_built_this_run": built_here and native_ok,
        "native_load_s": round(time.perf_counter() - t, 2),
    }
    for k, v in info.items():
        say(f"  {k}: {v}")
    check(native_ok, "native module did not build/load (see the log above "
                     "for the compiler's output)")
    return info


# -- seeded data -------------------------------------------------------------


def _keys(seed: int, tag: str, n: int):
    from tendermint_tpu.crypto import ed25519

    return [
        ed25519.gen_priv_key(
            hashlib.sha256(f"{seed}/{tag}/{i}".encode()).digest()
        )
        for i in range(n)
    ]


def _valset(sks):
    from tendermint_tpu.types import Validator, ValidatorSet

    vals = [Validator.new(sk.pub_key(), POWER) for sk in sks]
    vset = ValidatorSet.new(vals)
    by_addr = {v.address: sk for sk, v in zip(sks, vals)}
    return vset, [by_addr[v.address] for v in vset.validators]


def _signed_commit(vset, ordered, height: int, bid):
    """A Commit built directly from signed CommitSigs over the canonical
    precommit sign-bytes (OpenSSL signatures) — the construction
    bench._build_commit_jobs uses; VoteSet.add_vote would verify every
    vote during set-up and spoil the accounting."""
    from tendermint_tpu.types import Vote
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, Commit, CommitSig,
    )
    from tendermint_tpu.types.vote import PRECOMMIT_TYPE
    from tendermint_tpu.wire.canonical import Timestamp

    ts = Timestamp(seconds=T0 + height)
    sigs = []
    for idx, sk in enumerate(ordered):
        addr = vset.validators[idx].address
        v = Vote(type=PRECOMMIT_TYPE, height=height, round=0, block_id=bid,
                 timestamp=ts, validator_address=addr, validator_index=idx)
        sigs.append(CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT, validator_address=addr,
            timestamp=ts, signature=sk.sign(v.sign_bytes(CHAIN_ID)),
        ))
    return Commit(height=height, round=0, block_id=bid, signatures=sigs)


def build_commit_jobs(seed: int, tag: str, n_vals: int, n_commits: int):
    """[(chain_id, vset, block_id, height, commit)] over ONE validator set."""
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    vset, ordered = _valset(_keys(seed, tag, n_vals))
    jobs = []
    for h in range(1, n_commits + 1):
        digest = hashlib.sha256(f"{seed}/{tag}/block/{h}".encode()).digest()
        bid = BlockID(hash=digest,
                      part_set_header=PartSetHeader(total=1, hash=digest))
        jobs.append((CHAIN_ID, vset, bid, h,
                     _signed_commit(vset, ordered, h, bid)))
    return jobs


def build_header_chain(seed: int, n_headers: int, n_vals: int):
    """[(SignedHeader, ValidatorSet)] — an adjacent chain over one set."""
    from tendermint_tpu.types import SignedHeader
    from tendermint_tpu.types.block import (
        BlockID, Header, PartSetHeader, Version,
    )
    from tendermint_tpu.wire.canonical import Timestamp

    vset, ordered = _valset(_keys(seed, "light", n_vals))
    chain = []
    prev = b"\x00" * 32
    for h in range(1, n_headers + 1):
        hdr = Header(
            version=Version(block=11, app=0), chain_id=CHAIN_ID, height=h,
            time=Timestamp(seconds=T0 + h),
            last_block_id=BlockID(
                hash=prev, part_set_header=PartSetHeader(total=1, hash=prev)
            ) if h > 1 else BlockID(),
            validators_hash=vset.hash(), next_validators_hash=vset.hash(),
            consensus_hash=b"\x01" * 32, app_hash=b"",
            proposer_address=vset.validators[0].address,
        )
        bid = BlockID(hash=hdr.hash(),
                      part_set_header=PartSetHeader(total=1, hash=hdr.hash()))
        commit = _signed_commit(vset, ordered, h, bid)
        chain.append((SignedHeader(header=hdr, commit=commit), vset))
        prev = hdr.hash()
    return chain


def build_churn_chain(seed: int, n_headers: int, n_vals: int):
    """[wire bytes of the LightBlock at height h]: at every height the
    oldest key leaves the set and a new one joins (light/helpers_test.go
    genLightBlocksWithKeys with valVariation 1)."""
    from tendermint_tpu.light.provider import LightBlock
    from tendermint_tpu.types import SignedHeader
    from tendermint_tpu.types.block import (
        BlockID, Header, PartSetHeader, Version,
    )
    from tendermint_tpu.wire.canonical import Timestamp

    sks = _keys(seed, "churn", n_vals + n_headers)
    sets = [_valset(sks[h:h + n_vals]) for h in range(n_headers + 1)]
    out, prev = [], b""
    for h in range(1, n_headers + 1):
        vset, ordered = sets[h - 1]
        hdr = Header(
            version=Version(block=11, app=0), chain_id=CHAIN_ID, height=h,
            time=Timestamp(seconds=T0 + h),
            last_block_id=BlockID(
                hash=prev, part_set_header=PartSetHeader(total=1, hash=prev)
            ) if prev else BlockID(),
            validators_hash=vset.hash(),
            next_validators_hash=sets[h][0].hash(),
            consensus_hash=b"\x01" * 32,
            proposer_address=vset.validators[0].address,
        )
        prev = hdr.hash()
        bid = BlockID(hash=prev,
                      part_set_header=PartSetHeader(total=1, hash=prev))
        out.append(LightBlock(
            signed_header=SignedHeader(
                header=hdr, commit=_signed_commit(vset, ordered, h, bid)),
            validators=vset).encode())
    return out


def forge(commit, idx: int):
    """The commit with one bit of signature #idx flipped."""
    sigs = list(commit.signatures)
    bad = bytearray(sigs[idx].signature)
    bad[7] ^= 0x10
    sigs[idx] = dataclasses.replace(sigs[idx], signature=bytes(bad))
    return dataclasses.replace(commit, signatures=sigs)


def starve(commit, keep: int):
    """The commit with every signature past the first `keep` absent."""
    from tendermint_tpu.types.block import CommitSig

    sigs = list(commit.signatures)
    sigs[keep:] = [CommitSig.absent() for _ in sigs[keep:]]
    return dataclasses.replace(commit, signatures=sigs)


def early_stop_count(n_vals: int, num: int = 2, den: int = 3) -> int:
    """Signatures a `light` verification of an all-signed, equal-power
    commit selects: in order, until the tally first exceeds num/den of
    the total (validation.go:152, countAllSignatures=false)."""
    needed = n_vals * POWER * num // den
    return min(needed // POWER + 1, n_vals)


def forged_position(rng: random.Random, n: int) -> int:
    """Where to forge one of a batch's n signatures: anywhere but the
    last few. An RLC lane holds m consecutive signatures of the LAUNCHED
    batch, m being the width that launch's size gave it (ops/pallas_rlc.py
    plan_bucket: 2, 4 or 8), and on a reject the host re-verifies the
    lane's live ones; several jobs may share a launch at any offset, so
    only a position at least the widest lane short of the job's end is
    certain to sit in a full lane — and cost exactly m host re-verifies."""
    from tendermint_tpu.ops import pallas_rlc

    return rng.randrange(n - pallas_rlc.WIDTHS[-1])


def reverified(before: dict, after: dict) -> int:
    """Signatures the host re-verified for the RLC lanes the device
    rejected between two counters(): each lane its launch's width."""
    was = before["rlc_rejected_lanes_by_width"]
    return sum(int(m) * (k - was.get(m, 0))
               for m, k in after["rlc_rejected_lanes_by_width"].items())


# -- the sequential reference ------------------------------------------------


def reference_error(job, light: bool):
    """(type name, message) the sequential per-signature path raises on
    this input — types/validation._verify_commit_single, no batching, no
    device."""
    from tendermint_tpu.types import validation as V

    chain_id, vset, _bid, _h, commit = job
    needed = vset.total_voting_power() * 2 // 3
    try:
        if light:
            V._verify_commit_single(chain_id, vset, commit, needed,
                                    V._ignore_not_for_block, V._count_all,
                                    False, True)
        else:
            V._verify_commit_single(chain_id, vset, commit, needed,
                                    V._ignore_absent, V._count_for_block,
                                    True, True)
    except ValueError as e:
        return type(e).__name__, str(e)
    raise Failed("the sequential reference accepted a commit built to fail")


def light_reference(req, now):
    """(type name, message) of light/verifier.py's sequential verify on
    the HOST batch verifier, or None when it accepts."""
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.light import verifier as lv

    device_factory = cbatch.use_device_engine(cbatch.Ed25519HostBatchVerifier)
    try:
        lv.verify(req.trusted_header, req.trusted_vals, req.untrusted_header,
                  req.untrusted_vals, req.trusting_period, now,
                  req.max_clock_drift, req.trust_level)
    except Exception as e:  # noqa: BLE001 — the verdict IS the error
        return type(e).__name__, str(e)
    finally:
        cbatch.use_device_engine(device_factory)
    return None


# -- bookkeeping -------------------------------------------------------------


class Ledger:
    """What the smoke submitted, to be held against what the engine says
    it verified."""

    def __init__(self):
        self.device = 0
        self.host = 0
        self.forged = 0  # jobs with one forged signature: one lane each
        self.notes = []
        self.steps = []  # (stage, step, seconds, first_use)

    def submitted(self, n: int, forged: bool = False) -> None:
        self.device += n
        self.forged += forged

    def run(self, stage: str, step: str, fn, sigs: int = 0,
            first_use: bool = False, forged: bool = False):
        """Time one step and book the signatures it submits."""
        t = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t
        self.steps.append((stage, step, dt, first_use))
        say(f"  {stage}/{step}: {dt:.3f}s" + ("  (first use)" if first_use else ""))
        if sigs:
            self.submitted(sigs, forged)
        return out


LIGHT_COUNTERS = ("light_hops_verified", "light_hops_refused",
                  "light_hops_fused", "light_blocks_fetched",
                  "light_trusting_sigs_host", "light_trusting_sigs_device")


def counters() -> dict:
    from tendermint_tpu.libs.metrics import ops_stats

    s = ops_stats()
    return {
        "device": s["sigs_verified_device"],
        "host": s["sigs_verified_host"],
        "host_fallback_batches": s["host_fallback_batches"],
        "dispatch_errors": s["dispatch_errors"],
        "ingress_fallbacks": dict(s["ingress_fallbacks"]),
        "batches_by_bucket": dict(s["batches_by_bucket"]),
        "rlc_launches_by_width": dict(s["rlc_launches_by_width"]),
        "rlc_rejected_lanes_by_width": dict(s["rlc_rejected_lanes_by_width"]),
        "epoch_cache_hits": s["epoch_cache_hits"],
        "epoch_cache_misses": s["epoch_cache_misses"],
        "epoch_tables_built": s["epoch_tables_built"],
        "epoch_tables_shared": s["epoch_tables_shared"],
        "epoch_rows_patched": s["epoch_rows_patched"],
        "valset_decode_native": s["valset_decode_native"],
        "valset_decode_python": s["valset_decode_python"],
        **{k: s[k] for k in LIGHT_COUNTERS},
    }


def check_engine(desc, where: str) -> None:
    """The engine the process runs must be the compiled TPU RLC pipeline
    (ops/engine.py) — never the interpreter, never the op-graph kernels."""
    check(bool(desc) and desc.get("platform") == "tpu"
          and desc.get("kernel") == "pallas_rlc"
          and desc.get("interpret") is False,
          f"{where} does not report the compiled TPU RLC engine: {desc}")


def expect_error(fn, want, what: str) -> None:
    try:
        fn()
    except ValueError as e:
        got = (type(e).__name__, str(e))
        check(got == want,
              f"{what}: raised {got!r}, the sequential reference {want!r}")
        say(f"  {what}: {got[1][:72]}  == reference")
        return
    raise Failed(f"{what}: accepted a commit the reference rejects")


# -- stages 2-4: the library entry -------------------------------------------


def stage_library(led: Ledger, hub, big, base: dict) -> None:
    from tendermint_tpu.types import validation as V

    check("tendermint_tpu.ops.pipeline" not in sys.modules,
          "the dispatcher was loaded before the first verify_commit")
    for name, jobs, n_vals in (("hub", hub, HUB_VALS), ("10k", big, BIG_VALS)):
        led.run("library", f"{name} verify_commit cold",
                lambda: V.verify_commit(*jobs[0]), n_vals, first_use=True)
        if jobs is hub:
            now = counters()
            check(now["device"] - base["device"] == HUB_VALS
                  and now["host"] == base["host"],
                  f"the FIRST verify_commit of the process did not reach "
                  f"the device: {now}")
        led.run("library", f"{name} verify_commit warm (table upload)",
                lambda: V.verify_commit(*jobs[1]), n_vals, first_use=True)
        led.run("library", f"{name} verify_commit repeat",
                lambda: V.verify_commit(*jobs[2]), n_vals)
        # the hub's light selection is a new (smaller) kernel shape; the
        # 10k one lands in the bucket its full commit already compiled
        led.run("library", f"{name} verify_commit_light",
                lambda: V.verify_commit_light(*jobs[0]),
                early_stop_count(n_vals), first_use=jobs is hub)
        led.run("library", f"{name} verify_commit_light repeat",
                lambda: V.verify_commit_light(*jobs[1]),
                early_stop_count(n_vals))
    c = counters()
    check(c["epoch_cache_misses"] - base["epoch_cache_misses"] >= 2
          and c["epoch_cache_hits"] > base["epoch_cache_hits"],
          f"the epoch cache saw no cold->warm transition: {c}")


def stage_blame(led: Ledger, cases) -> None:
    from tendermint_tpu.types import validation as V

    for what, job, light, want, n_submitted in cases:
        fn = V.verify_commit_light if light else V.verify_commit
        led.run("blame", what,
                lambda: expect_error(lambda: fn(*job), want, what),
                n_submitted, forged=True)


def stage_stream(led: Ledger, big) -> None:
    from concurrent.futures import ThreadPoolExecutor

    from tendermint_tpu.ops import pallas_rlc
    from tendermint_tpu.types import validation as V

    largest = str(pallas_rlc.MAX_SIGS)

    def one_pass() -> None:
        with ThreadPoolExecutor(len(big)) as ex:
            futs = [ex.submit(V.verify_commit, *job) for job in big]
            for f in futs:
                f.result()  # raises on any verification failure

    # coalescing is opportunistic (what is queued when the dispatcher
    # looks); a pass or two is enough for >4 commits to meet in one
    # launch, which is what compiles and runs the largest bucket
    for attempt in range(1, 5):
        before = counters()["batches_by_bucket"].get(largest, 0)
        led.run("stream", f"8 x 10k verify_commit, pass {attempt}",
                one_pass, len(big) * BIG_VALS, first_use=attempt == 1)
        if counters()["batches_by_bucket"].get(largest, 0) > before:
            led.run("stream", "8 x 10k verify_commit, repeat", one_pass,
                    len(big) * BIG_VALS)
            return
    raise Failed(f"no {largest}-signature coalesced launch in 4 passes: "
                 f"{counters()['batches_by_bucket']}")


# -- stage 5: the server entry ------------------------------------------------


def _rpc(addr: str, method: str, params: dict, timeout: float = 900.0) -> dict:
    req = urllib.request.Request(
        f"http://{addr}/",
        data=json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                         "params": params}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        out = json.loads(r.read())
    check("result" in out, f"/{method} answered an error: {out.get('error')}")
    return out["result"]


def light_requests(chain, rng: random.Random):
    """[(HeaderRequest, device sigs, forged?)]"""
    from tendermint_tpu.light.batch import HeaderRequest
    from tendermint_tpu.wire.canonical import Timestamp

    now = Timestamp(seconds=T0 + len(chain) + 60)
    n_full = early_stop_count(LIGHT_VALS)
    n_trust = early_stop_count(LIGHT_VALS, 1, 3)

    def req(t, u, untrusted=None):
        return HeaderRequest(
            trusted_header=chain[t][0], trusted_vals=chain[t][1],
            untrusted_header=untrusted or chain[u][0],
            untrusted_vals=chain[u][1], trusting_period=1e9, now=now,
        )

    out = [(req(k, k + 1), n_full, False) for k in range(N_ADJACENT)]
    out += [(req(0, k), n_trust + n_full, False)
            for k in range(2, 2 + N_SKIPPING)]
    # one forged commit: adjacent hop onto the last header, a signature
    # among those the early-stopping selection actually checks
    t = len(chain) - 2
    sh = chain[t + 1][0]
    forged = dataclasses.replace(
        sh, commit=forge(sh.commit, forged_position(rng, n_full))
    )
    out.append((req(t, t + 1, untrusted=forged), n_full, True))
    return out, now


def stage_server(led: Ledger, reqs, now, want_forged) -> dict:
    from tendermint_tpu import cli
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.config import Config
    from tendermint_tpu.light.service import request_to_json
    from tendermint_tpu.node import make_node

    home = tempfile.mkdtemp(prefix="chip-smoke-node-")
    cli.main(["--home", home, "init", "validator"])
    cfg = Config.load(os.path.join(home, "config", "config.toml"))
    cfg.base.home = home
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    before = counters()
    node = make_node(cfg, app=KVStoreApplication(), with_rpc=True)
    node.start()
    try:
        addr = node.rpc_server.listen_addr
        with urllib.request.urlopen(f"http://{addr}/status", timeout=60) as r:
            status = json.loads(r.read())
        ve = status.get("result", status)["verify_engine"]
        say(f"  GET /status verify_engine.engine: {ve.get('engine')}")
        check_engine(ve.get("engine"), "GET /status verify_engine")

        wire = [request_to_json(r) for r, _, _ in reqs]
        verdicts = {}
        third = (len(wire) + 2) // 3
        for part, lo in enumerate(range(0, len(wire), third), 1):
            res = led.run(
                "server", f"POST /light_verify {len(wire[lo:lo + third])} requests",
                lambda: _rpc(addr, "light_verify",
                             {"requests": wire[lo:lo + third],
                              "timeout": 900}),
                first_use=part == 1,
            )
            for v in res["verdicts"]:
                verdicts[lo + v["index"]] = v
        check(len(verdicts) == len(reqs), "missing /light_verify verdicts")
        for i, (_r, n, forged) in enumerate(reqs):
            v = verdicts[i]
            if not forged:
                check(v["ok"], f"/light_verify request {i} rejected: {v}")
            else:
                got = (v["error_type"], v["error"])
                check(not v["ok"] and got == want_forged,
                      f"/light_verify forged request: {got!r}, sequential "
                      f"light/verifier.py {want_forged!r}")
                say(f"  forged request: {got[1][:72]}  == reference")
            led.submitted(n, forged)
    finally:
        node.stop()
    # the one-validator chain verifies its own one-signature LastCommits
    # on the host — the reference's rule (batching starts at 2
    # signatures, validation.go:12). Block h+1 may have been validated
    # but not yet stored when the node stopped.
    height = node.block_store.height()
    after = counters()
    consensus_host = (after["host"] - before["host"]
                      - reverified(before, after))
    check(max(height - 1, 0) <= consensus_host <= height,
          f"the node's consensus host-verified {consensus_host} signatures "
          f"at chain height {height}")
    led.host += consensus_host
    led.notes.append(
        f"{consensus_host} one-signature LastCommits single-verified on "
        f"the host by the node's own consensus (chain height {height})"
    )
    return {"node_height": height, "light_requests": len(reqs)}


# -- stage 6/7 -----------------------------------------------------------------


def stage_churn(led: Ledger, wires) -> dict:
    """A validator set never seen at every step: the light client's own
    sequential entry, from wire bytes."""
    from tendermint_tpu.light import verifier
    from tendermint_tpu.light.provider import LightBlock
    from tendermint_tpu.wire.canonical import Timestamp

    now = Timestamp(seconds=T0 + len(wires) + 1)
    n_sigs = early_stop_count(CHURN_VALS)
    c0 = counters()
    trusted = LightBlock.decode(wires[0]).signed_header

    def step(k):
        nonlocal trusted
        lb = LightBlock.decode(wires[k])
        verifier.verify_adjacent(trusted, lb.signed_header, lb.validators,
                                 3600.0, now, 10.0)
        trusted = lb.signed_header

    for k in range(1, len(wires)):
        led.run("churn", f"verify_adjacent height {k + 1}",
                lambda k=k: step(k), sigs=n_sigs, first_use=k <= 3)
    c1 = counters()
    rise = {k: c1[k] - c0[k] for k in (
        "epoch_cache_hits", "epoch_cache_misses", "epoch_tables_built",
        "epoch_tables_shared", "epoch_rows_patched",
        "valset_decode_native", "valset_decode_python")}
    launched = {m: n - c0["rlc_launches_by_width"].get(m, 0)
                for m, n in c1["rlc_launches_by_width"].items()
                if n != c0["rlc_launches_by_width"].get(m, 0)}
    say(f"  {len(wires) - 1} steps x {n_sigs} signatures: {rise}; RLC "
        f"launches by lane width {launched}")
    # one key a height into a 128-row table of 100: a set maps until the
    # table's 27 free rows are used, then one cold build, and so on
    steps = len(wires) - 1
    built = -(-steps // (128 - CHURN_VALS))
    check(rise["epoch_cache_misses"] == steps and rise["epoch_cache_hits"] == 0,
          f"every step carries a set never seen: {rise}")
    check(rise["epoch_tables_built"] == built
          and rise["epoch_tables_shared"] == steps - built
          and rise["epoch_rows_patched"] == steps - built,
          f"{steps} steps of one-key churn imply {built} tables built and "
          f"{steps - built} sets mapped, one row each: {rise}")
    # a build that lost valset_decode_columns must fail here, not read as
    # "no gain" in the benchmark
    check(rise["valset_decode_native"] == len(wires)
          and rise["valset_decode_python"] == 0,
          f"{len(wires)} all-ed25519 sets decoded from wire bytes must all "
          f"take the native pass: {rise}")
    return dict(rise, steps=steps, rlc_launches_by_width=launched)


def build_skip_chain(seed: int):
    """(wire bytes of the light block at height h, the chain's records,
    now, what the plain reference does on a catch-up 1 -> N_SKIP)."""
    from benchmark import lightchain, reference_bisect

    cfg = {"name": "smoke-skip", "validators": CHURN_VALS,
           "voting_power": POWER, "chain_id": CHAIN_ID + "-skip",
           "headers": N_SKIP, "keys_replaced_per_height": 1,
           "block_interval_s": 60}
    _keys_of, blocks = lightchain.chain(cfg, seed)
    now = (lightchain.T0 + 60 * (N_SKIP + 1), 0)
    want = reference_bisect.catch_up(
        lambda h: (blocks[h - 1], blocks[h - 1].vals), cfg["chain_id"], 1,
        blocks[0].block_hash, N_SKIP, 86400, now, 10)
    return [lightchain.light_block_wire(b) for b in blocks], blocks, now, want


def stage_skipping(led: Ledger, wires, blocks, now, want) -> dict:
    """The light client's default mode, through light.Client: the far
    header against the trusted set by address (a third) and against its
    own (+2/3), both checks of a hop in ONE launch, bisecting."""
    from tendermint_tpu.db import MemDB
    from tendermint_tpu.light.client import Client, TrustOptions
    from tendermint_tpu.light.provider import LightBlock, Provider
    from tendermint_tpu.light.store import LightStore
    from tendermint_tpu.types import Fraction
    from tendermint_tpu.wire.canonical import Timestamp

    class Node(Provider):
        def light_block(self, height: int):
            return LightBlock.decode(wires[(height or len(wires)) - 1])

    check(want.error is None and len(want.refused) >= 2,
          f"the reference on the honest chain: {want}")
    hops = len(want.trace) - 1
    third, two_thirds = (early_stop_count(CHURN_VALS, 1, 3),
                         early_stop_count(CHURN_VALS))
    check(want.sigs == two_thirds + hops * (third + two_thirds),
          f"the reference counts {want.sigs} signatures in {hops} hops")
    at = Timestamp(*now)
    c0 = counters()

    def catch_up():
        node = Node()
        client = Client(
            blocks[0].header.chain_id,
            TrustOptions(86400.0, 1, blocks[0].block_hash), node, [node],
            LightStore(MemDB()), trust_level=Fraction(1, 3),
            max_clock_drift=10.0, now_fn=lambda: at)
        return client.verify_light_block_at_height(N_SKIP, at)

    lb = led.run("skipping", f"catch-up 1 -> {N_SKIP}: {want.trace}",
                 catch_up, sigs=want.sigs, first_use=True)
    check(lb.height == N_SKIP and lb.hash() == blocks[-1].block_hash,
          f"the client verified {lb.height} {lb.hash().hex()}")
    c1 = counters()
    rise = {k: c1[k] - c0[k] for k in LIGHT_COUNTERS + (
        "device", "host", "host_fallback_batches", "epoch_tables_built")}
    say(f"  {hops} hops, {len(want.refused)} refused: {rise}")
    check((rise["light_hops_verified"], rise["light_hops_refused"],
           rise["light_blocks_fetched"]) == (hops, len(want.refused),
                                             len(want.fetched)),
          f"the reference makes {hops} hops, {len(want.refused)} refusals "
          f"and {len(want.fetched)} fetches: {rise}")
    check((rise["device"], rise["host"], rise["host_fallback_batches"])
          == (want.sigs, 0, 0),
          f"the reference looks at {want.sigs} signatures, all on the "
          f"device and none in a batch under its threshold: {rise}")
    check((rise["light_trusting_sigs_device"], rise["light_trusting_sigs_host"],
           rise["light_hops_fused"]) == (hops * third, 0, hops),
          f"{hops} trusting checks of {third}, each in the launch of its "
          f"hop's +2/3 check: {rise}")
    return dict(rise, trace=want.trace, refused=len(want.refused))


def stage_accounts(led: Ledger, base: dict) -> dict:
    from tendermint_tpu.ops.engine import engine

    eng = engine().describe()
    c = counters()
    say(f"  engine: {eng}")
    say(f"  counters: {c}")
    check_engine(eng, "ops.engine")
    dev = c["device"] - base["device"]
    host = c["host"] - base["host"]
    lanes = {m: k - base["rlc_rejected_lanes_by_width"].get(m, 0)
             for m, k in c["rlc_rejected_lanes_by_width"].items()}
    check(sum(lanes.values()) == led.forged,
          f"{led.forged} forged jobs, each one signature in a full lane, "
          f"but the device rejected {lanes} lanes (by width)")
    blamed = reverified(base, c)
    led.host += blamed
    led.notes.append(
        f"{blamed} host re-verifies for the rejected RLC lanes of "
        f"{led.forged} forged jobs (lanes by width: {lanes})"
    )
    say(f"  RLC launches by lane width: {c['rlc_launches_by_width']}")
    check(dev == led.device,
          f"sigs_verified device rose by {dev}, submitted {led.device}")
    check(host == led.host,
          f"sigs_verified host rose by {host}, expected {led.host} "
          f"({led.notes})")
    check(c["host_fallback_batches"] == base["host_fallback_batches"],
          f"batches under the device threshold: host_fallback_batches moved "
          f"{base['host_fallback_batches']} -> {c['host_fallback_batches']}")
    check(c["dispatch_errors"] == base["dispatch_errors"],
          f"dispatch_errors moved: {base['dispatch_errors']} -> "
          f"{c['dispatch_errors']}")
    check(c["ingress_fallbacks"] == base["ingress_fallbacks"],
          f"ingress fallbacks moved: {c['ingress_fallbacks']}")
    for note in led.notes:
        say(f"  host path, stated: {note}")
    return {"engine": eng, "sigs_verified_device": dev,
            "sigs_verified_host": host, "host_notes": led.notes,
            "batches_by_bucket": c["batches_by_bucket"],
            "rlc_launches_by_width": c["rlc_launches_by_width"]}


def report(led: Ledger) -> dict:
    from tendermint_tpu.libs import jaxcache

    cc = jaxcache.counters()
    say("steps (first use = trace + compile-or-load + run):")
    for stage, step, dt, first in led.steps:
        say(f"  {stage:8s} {step:48s} {dt:9.3f}s{'  first use' if first else ''}")
    say(f"compiles: {cc['requests']} requests, {cc['hits']} persistent-cache "
        f"hits, {cc['writes']} entries written "
        f"({cc['requests'] - cc['hits']} built here)")
    for name, secs in cc["compiles"]:
        if secs >= 0.5:
            say(f"  {name:44s} {secs:7.2f}s")
    return {
        "steps": [
            {"stage": s, "step": n, "s": round(dt, 4), "first_use": f}
            for s, n, dt, f in led.steps
        ],
        "setup_s": round(sum(dt for *_x, dt, f in led.steps if f), 2),
        "repeat_s": round(sum(dt for *_x, dt, f in led.steps if not f), 2),
        "compile": {
            "requests": cc["requests"], "cache_hits": cc["hits"],
            "cache_writes": cc["writes"],
            "seconds": [[n, round(s, 2)] for n, s in cc["compiles"] if s >= 0.5],
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    say("stage 1: the chip")
    device = refuse_without_chip()
    info = describe_install(device)

    say(f"building data from seed {args.seed}")
    rng = random.Random(args.seed)
    t = time.perf_counter()
    hub = build_commit_jobs(args.seed, "hub", HUB_VALS, 3)
    big = build_commit_jobs(args.seed, "big", BIG_VALS, STREAM)
    chain = build_header_chain(args.seed, N_ADJACENT + 2, LIGHT_VALS)
    reqs, now = light_requests(chain, rng)
    churn = build_churn_chain(args.seed, N_CHURN + 1, CHURN_VALS)
    skip = build_skip_chain(args.seed)

    def variant(job, commit):
        return job[:4] + (commit,)

    big_light_n = early_stop_count(BIG_VALS)
    cases = [  # (what, job, light?, device signatures)
        ("hub forged",
         variant(hub[0], forge(hub[0][4], forged_position(rng, HUB_VALS))),
         False, HUB_VALS),
        ("10k forged",
         variant(big[0], forge(big[0][4], forged_position(rng, BIG_VALS))),
         False, BIG_VALS),
        ("10k forged, light",
         variant(big[1], forge(big[1][4], forged_position(rng, big_light_n))),
         True, big_light_n),
        ("hub under 2/3", variant(hub[1], starve(hub[1][4], HUB_VALS * 2 // 3)),
         False, 0),
        ("10k under 2/3", variant(big[2], starve(big[2][4], BIG_VALS * 2 // 3)),
         False, 0),
    ]
    say(f"  built in {time.perf_counter() - t:.1f}s; sequential references")
    t = time.perf_counter()
    cases = [(w, j, li, reference_error(j, li), n) for w, j, li, n in cases]
    check("ErrNotEnoughVotingPowerSigned" == cases[-1][3][0],
          f"the under-2/3 reference is {cases[-1][3]}")
    want_forged = light_reference(reqs[-1][0], now)
    check(want_forged is not None and "wrong signature" in want_forged[1],
          f"light/verifier.py's reference on the forged request: {want_forged}")
    say(f"  references in {time.perf_counter() - t:.1f}s")

    # everything above verified on the host, on purpose; what the engine
    # does is counted from here
    base = counters()
    led = Ledger()
    say("stage 2: library entry")
    stage_library(led, hub, big, base)
    say("stage 3: blame and shortfall")
    stage_blame(led, cases)
    say("stage 4: dispatcher under concurrency")
    stage_stream(led, big)
    say("stage 5: server entry")
    server = stage_server(led, reqs, now, want_forged)
    say("stage 6: a validator set that changes every height")
    churned = stage_churn(led, churn)
    say("stage 7: a light client that catches up by skipping")
    skipped = stage_skipping(led, *skip)
    say("stage 8: accounts")
    accounts = stage_accounts(led, base)
    say("stage 9: report")
    summary = dict(install=info, server=server, churn=churned,
                   skipping=skipped, accounts=accounts,
                   **report(led), seed=args.seed,
                   wall_s=round(time.perf_counter() - _START, 1), claim=None)

    from tendermint_tpu.ops import pipeline

    if pipeline._shared is not None:
        pipeline._shared.close()
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except Failed as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
