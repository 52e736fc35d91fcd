"""The yardstick's own data against the program on the CPU, at a size the
sequential path takes (no kernel compiles): the benchmark's wire bytes and
sign-bytes are the program's byte for byte, its plain reference says what
_verify_commit_single says, and the post-window check fails `correct` on
each kind of fault."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import data, spec, wire  # noqa: E402

CFG = {"name": "tiny", "validators": 12, "voting_power": 100,
       "chain_id": "bench-tiny", "pool_commits": 3}
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import jax

    driver = spec.load_driver(os.path.join(ROOT, "benchmark"), "commit_from_wire")
    root = str(tmp_path_factory.mktemp("checkout"))
    return driver.Session(CFG, SEED, root, jax.devices(), lambda m: None), root


def test_same_seed_same_bytes_and_the_pool_cache_round_trips(session):
    _s, root = session
    again = data.build(CFG, SEED)
    cached = data.pool(root, CFG, SEED)
    assert not cached.built, "the session built it; the second caller loads it"
    assert again.commits == cached.commits and again.digests == cached.digests
    assert [(c.what, c.wire, c.expect) for c in again.blame] == \
           [(c.what, c.wire, c.expect) for c in cached.blame]
    assert data.build(CFG, SEED + 1).commits[0] != again.commits[0]
    assert len(set(again.commits)) == CFG["pool_commits"]


def test_wire_bytes_and_sign_bytes_are_the_programs(session):
    from tendermint_tpu.types.block import Commit

    s, _root = session
    for w, block_id, height in s._jobs:
        c = Commit.decode(w)
        assert c.encode() == w and c.height == height and c.block_id == block_id
        tpl = wire.sign_bytes_template(CFG["chain_id"], height, block_id.hash)
        for idx in (0, 5, 11):
            ts = c.signatures[idx].timestamp
            assert c.vote_sign_bytes(CFG["chain_id"], idx) == \
                wire.sign_bytes(tpl, ts.seconds, ts.nanos)
    starved = Commit.decode(s._blame[-1][0].wire)
    assert starved.encode() == s._blame[-1][0].wire
    assert sum(cs.is_absent() for cs in starved.signatures) == 4


def test_the_reference_says_what_verify_commit_single_says(session):
    from tendermint_tpu.types import validation as V
    from tendermint_tpu.types.block import Commit

    s, _root = session
    needed = s.vals.total_voting_power() * 2 // 3
    kinds = set()
    for case, _bid in s._blame:
        with pytest.raises(ValueError) as e:
            V._verify_commit_single(
                CFG["chain_id"], s.vals, Commit.decode(case.wire), needed,
                V._ignore_absent, V._count_for_block, True, True)
        assert (type(e.value).__name__, str(e.value)) == case.expect
        kinds.add(case.expect[0])
    assert kinds == {"ValueError", "ErrNotEnoughVotingPowerSigned"}
    assert s.request(0) == 12, "an honest commit verifies through the driver"


@pytest.fixture
def quiet_counters(session, monkeypatch):
    """At 12 validators the CPU's sequential path counts every verify as a
    host fallback; the chip's cells never take it. Hold the counters still
    so that the check's verdicts are what is tested."""
    s, _root = session
    frozen = s._ops_stats()
    monkeypatch.setattr(s, "_ops_stats", lambda: frozen)
    monkeypatch.setattr(s, "_base", s.counters())
    return s


def test_the_check_passes_on_the_program_as_it_is(quiet_counters):
    assert quiet_counters.check() == []


@pytest.mark.parametrize("fault", ["wrong_error_string", "wrong_verdict",
                                   "dispatch_errors_moved",
                                   "host_fallback_moved"])
def test_the_check_fails_on(quiet_counters, fault, monkeypatch):
    s = quiet_counters
    real = s._verify

    def wrong_string(*a):
        try:
            real(*a)
        except ValueError as e:
            raise ValueError(str(e).replace("#", "# ")) from None

    if fault == "wrong_error_string":
        monkeypatch.setattr(s, "_verify", wrong_string)
    elif fault == "wrong_verdict":
        monkeypatch.setattr(s, "_verify", lambda *a: None)
    else:
        key = {"dispatch_errors_moved": "dispatch_errors",
               "host_fallback_moved": "host_fallback_batches"}[fault]
        stats = s._ops_stats
        monkeypatch.setattr(s, "_ops_stats",
                            lambda: dict(stats(), **{key: stats()[key] + 1}))
    bad = s.check()
    assert bad, fault
    if fault == "wrong_verdict":
        assert len(bad) == 3 and "raised None" in bad[0]
