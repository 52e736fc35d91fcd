"""An sr25519 validator set's commit on the normal path, on the CPU: Commit
wire bytes -> verify_commit / verify_commit_light -> the fused prep ->
the shared dispatcher -> select_kernel's sr25519 arm -> the ristretto
kernel in interpret mode at its smallest bucket (128 lanes, one trace for
the whole file). Every verdict, exception type and message is held to
the plain reference (benchmark/reference_sr25519.py) and to the
sequential walk (types/validation._verify_commit_single); the counters
say where each signature went, and the launches' thread says who
launched."""

import os
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import data, data_sr25519, reference_sr25519 as ref, wire  # noqa: E402

pytest.importorskip("jax")

POWER = 100
CHAIN = "sr-commit-test"


@pytest.fixture(scope="module")
def lane():
    """The Pallas engine (interpret mode here) for the file, and a
    device threshold under the 4-validator commit."""
    from tendermint_tpu.ops import backend, mixed

    mp = pytest.MonkeyPatch()
    mp.setenv("TM_TPU_PALLAS", "1")
    mp.setattr(mixed, "SR_DEVICE_THRESHOLD", 2)
    backend.engine.cache_clear()
    yield
    mp.undo()
    backend.engine.cache_clear()


class Chain:
    """One sr25519 validator set (data_sr25519's keys, power 100 each)
    and commits over it as wire bytes, with the records the reference
    reads."""

    def __init__(self, n: int, seed: int):
        from tendermint_tpu.crypto import sr25519
        from tendermint_tpu.types import Validator, ValidatorSet

        self.name, self.seed = f"t{n}", seed
        self.keys = data_sr25519._validators(self.name, seed, n)
        self.vals = ValidatorSet.new([
            Validator.new(sr25519.PubKey(pub), POWER)
            for _a, pub, _x in self.keys])
        assert [v.address for v in self.vals.validators] == [
            a for a, _p, _x in self.keys]
        self.n = n

    def records(self, height: int, signers=None, seed: int = 0):
        import random

        rng = random.Random(f"{self.seed}/{height}/{seed}")
        digest = data._digest(self.seed, self.name, "block", height)
        return digest, data_sr25519._sign_commit(
            CHAIN, self.keys, height, digest, rng, signers)

    def case(self, height: int, recs, digest):
        from tendermint_tpu.types.block import BlockID, Commit, PartSetHeader

        commit = Commit.decode(data._encode(self.keys, height, digest, recs))
        bid = BlockID(hash=digest,
                      part_set_header=PartSetHeader(total=1, hash=digest))
        return bid, commit

    def reference(self, height, digest, recs):
        return ref.verify_commit(CHAIN, [p for _a, p, _x in self.keys],
                                 [POWER] * self.n, height, digest, recs)


def _forge(recs, idx):
    sig = bytearray(recs[idx][2])
    sig[5] ^= 0x10
    recs[idx] = (recs[idx][0], recs[idx][1], bytes(sig))


def _outcome(fn, *args):
    try:
        fn(*args)
        return None
    except Exception as e:  # noqa: BLE001 — the verdict IS the error
        return (type(e).__name__, str(e))


def _single(chain, bid, height, commit, light=False):
    """_verify_commit_single behind verify_commit's own basic checks."""
    from tendermint_tpu.types import validation as v

    def run():
        v._verify_basic_vals_and_commit(chain.vals, commit, height, bid)
        needed = chain.vals.total_voting_power() * 2 // 3
        if light:
            v._verify_commit_single(CHAIN, chain.vals, commit, needed,
                                    v._ignore_not_for_block, v._count_all,
                                    False, True)
        else:
            v._verify_commit_single(CHAIN, chain.vals, commit, needed,
                                    v._ignore_absent, v._count_for_block,
                                    True, True)

    return _outcome(run)


def _stats():
    from tendermint_tpu.libs.metrics import ops_stats

    return ops_stats()


def _rise(before, after, *keys):
    return tuple(after[k] - before[k] for k in keys)


@pytest.fixture(scope="module", params=[4, 70], ids=["v4", "v70"])
def chain(request, lane):
    return Chain(request.param, seed=2 ** 31 + request.param)


CASES = {
    "all_valid": dict(),
    "forged_first": dict(forge=("first",)),
    "forged_middle": dict(forge=("middle",)),
    "forged_last": dict(forge=("last",)),
    "forged_three": dict(forge=("first", "middle", "last")),
    "too_little_power": dict(starve=True),
    "absent_signer": dict(absent=True),
}


@pytest.mark.time_limit(400)
@pytest.mark.parametrize("case", list(CASES))
def test_verify_commit_equals_the_reference_and_the_sequential_walk(
        chain, case):
    from tendermint_tpu.types import validation

    spec = CASES[case]
    height = 10 + list(CASES).index(case)
    signers = None
    if spec.get("starve"):
        signers = (chain.n * POWER * 2 // 3) // POWER   # one short of +2/3
    digest, recs = chain.records(height, signers)
    if spec.get("absent"):
        recs[chain.n // 2] = None
    where = {"first": 0, "middle": chain.n // 2, "last": chain.n - 1}
    for w in spec.get("forge", ()):
        _forge(recs, where[w])
    bid, commit = chain.case(height, recs, digest)

    before = _stats()
    got = _outcome(validation.verify_commit, CHAIN, chain.vals, bid, height,
                   commit)
    after = _stats()
    want = chain.reference(height, digest, recs)
    assert got == want
    assert _single(chain, bid, height, commit) == want
    if spec.get("forge"):
        assert got[1].startswith(f"wrong signature (#{where[spec['forge'][0]]}):")
    signed = sum(r is not None for r in recs)
    dev, host, launches, errs = _rise(
        before, after, "sr25519_sigs_device", "sr25519_sigs_host",
        "sr25519_launches", "dispatch_errors")
    if spec.get("starve"):
        # the tally refuses before any signature is looked at
        assert (dev, host, launches) == (0, 0, 0)
    else:
        assert (dev, host, launches, errs) == (signed, 0, 1, 0)
        assert after["batches_by_bucket"].get("128", 0) == \
            before["batches_by_bucket"].get("128", 0) + 1


@pytest.mark.time_limit(400)
@pytest.mark.parametrize("at", ["before_the_stop", "after_the_stop"])
def test_light_early_stop_is_exact(chain, at):
    """verify_commit_light checks signatures until the tally passes 2/3
    and no further: a forgery past that point is not seen, by the device
    path as by the sequential walk, and the device gets exactly the
    selected signatures."""
    from tendermint_tpu.types import validation

    needed = chain.n * POWER * 2 // 3
    stop = needed // POWER + 1           # signatures that pass the tally
    height = 40 + (at == "after_the_stop")
    digest, recs = chain.records(height)
    forged = stop - 1 if at == "before_the_stop" else min(stop, chain.n - 1)
    _forge(recs, forged)
    bid, commit = chain.case(height, recs, digest)

    before = _stats()
    got = _outcome(validation.verify_commit_light, CHAIN, chain.vals, bid,
                   height, commit)
    after = _stats()
    assert got == _single(chain, bid, height, commit, light=True)
    if at == "before_the_stop":
        assert got == chain.reference(height, digest, recs)
    elif forged >= stop:
        assert got is None
        assert _outcome(validation.verify_commit, CHAIN, chain.vals, bid,
                        height, commit) == chain.reference(height, digest, recs)
    assert _rise(before, after, "sr25519_sigs_device")[0] == stop


@pytest.mark.time_limit(400)
def test_an_ed25519_key_in_the_set_raises_what_it_raised(lane):
    """A set whose proposer is sr25519 and one of whose keys is ed25519:
    the batch verifier is sr25519's, and the other key fails its add as
    upstream's Add fails (crypto/sr25519 batch.go: "pubkey is not
    sr25519") — no launch, no host verification."""
    from tendermint_tpu.crypto import ed25519, sr25519
    from tendermint_tpu.types import Validator, ValidatorSet, validation
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader,
    )
    from tendermint_tpu.types.vote import PRECOMMIT_TYPE, Vote
    from tendermint_tpu.wire.canonical import Timestamp

    for salt in range(8):
        sks = [sr25519.gen_priv_key(bytes([salt, i]) * 16) for i in range(5)]
        sks.append(ed25519.gen_priv_key(bytes([salt, 99]) * 16))
        vals = ValidatorSet.new([Validator.new(k.pub_key(), POWER)
                                 for k in sks])
        if vals.get_proposer().pub_key.type() == "sr25519":
            break
    by_addr = {k.pub_key().address(): k for k in sks}
    d = bytes(range(32))
    bid = BlockID(hash=d, part_set_header=PartSetHeader(total=1, hash=d))
    ts = Timestamp(seconds=1_700_000_000)
    sigs = []
    for idx, v in enumerate(vals.validators):
        vote = Vote(type=PRECOMMIT_TYPE, height=3, round=0, block_id=bid,
                    timestamp=ts, validator_address=v.address,
                    validator_index=idx)
        sigs.append(CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT, validator_address=v.address,
            timestamp=ts,
            signature=by_addr[v.address].sign(vote.sign_bytes(CHAIN))))
    commit = Commit.decode(
        Commit(height=3, round=0, block_id=bid, signatures=sigs).encode())
    before = _stats()
    got = _outcome(validation.verify_commit, CHAIN, vals, bid, 3, commit)
    after = _stats()
    assert got == ("TypeError", "pubkey is not sr25519")
    assert _rise(before, after, "sr25519_sigs_device", "sr25519_sigs_host",
                 "sigs_verified_host") == (0, 0, 0)


@pytest.mark.time_limit(400)
def test_nothing_launches_on_the_callers_thread(lane, monkeypatch):
    """The ristretto kernel is called by the dispatch-owner thread alone,
    through select_kernel's arm; the caller waits on a future."""
    from tendermint_tpu.ops import pallas_sr25519 as ps
    from tendermint_tpu.ops import pipeline
    from tendermint_tpu.types import validation

    chain = Chain(4, seed=7)
    launched = []
    real = ps.verify_sr25519_compact

    def spy(*args, **kw):
        launched.append(threading.current_thread().name)
        return real(*args, **kw)

    monkeypatch.setattr(ps, "verify_sr25519_compact", spy)
    digest, recs = chain.records(5)
    bid, commit = chain.case(5, recs, digest)
    before = _stats()
    validation.verify_commit(CHAIN, chain.vals, bid, 5, commit)
    after = _stats()
    assert launched == ["verify-dispatch"]
    assert threading.get_ident() not in \
        pipeline.shared_verifier().dispatch_thread_idents
    assert _rise(before, after, "sr25519_launches", "sr25519_sigs_device",
                 "sigs_verified_device") == (1, 4, 4)


def test_a_burst_of_two_schemes_never_shares_a_launch(monkeypatch):
    """ed25519 and sr25519 jobs queued together coalesce by scheme: each
    launch holds one scheme (EntryBlock.concat would refuse two), and the
    jobs of a scheme that arrive together still fuse. Stand-in kernels:
    the coalescer's choice is what is under test."""
    import time

    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool
    from tendermint_tpu.ops.entry_block import EntryBlock

    launches = []
    gate = threading.Event()

    def prepare(entries):
        if not launches:
            gate.wait(5)        # the first batch holds the coalescer
        launches.append((entries.scheme, len(entries)))
        n = len(entries)
        return (lambda *_a: np.ones((n,), dtype=bool)), (), None, n

    monkeypatch.setattr(pl.AsyncBatchVerifier, "_prepare",
                        staticmethod(prepare))

    def block(scheme, n, tag):
        rows = [(bytes([tag, i]) * 16, b"m%d" % i, bytes([tag]) * 64)
                for i in range(n)]
        return EntryBlock.from_entries(rows, scheme=scheme)

    v = pl.AsyncBatchVerifier(depth=2)
    try:
        futs = [v.submit(block("ed25519", 3, 0))]
        time.sleep(0.1)
        order = ["ed25519", "ed25519", "sr25519", "sr25519", "sr25519",
                 "ed25519", "sr25519", "ed25519"]
        futs += [v.submit(block(s, 5, i + 1)) for i, s in enumerate(order)]
        gate.set()
        assert [len(f.result(timeout=10)) for f in futs] == [3] + [5] * 8
        assert launches[0] == ("ed25519", 3)
        assert [s for s, _n in launches[1:]] == [
            "ed25519", "sr25519", "ed25519", "sr25519", "ed25519"]
        assert [n for _s, n in launches[1:]] == [10, 15, 5, 5, 5]
        drain_pool(v._pool)
    finally:
        v.close()
