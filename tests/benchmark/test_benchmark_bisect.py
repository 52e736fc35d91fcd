"""The skipping light client's yardstick against the program on the CPU:
on a 12-validator, 60-header chain that replaces a key a height the
program's catch-up (light.Client.verify_light_block_at_height at trust
level 1/3) makes the hops, the refusals, the fetches and the signature
count of the plain reference (benchmark/reference_bisect.py), gives its
type and message on every case built to fail, and writes the spans and
counters the cell's per-layer metrics read; the driver passes its own
check, fails it on a wrong verdict, runs through benchmark/run.py's cell
loop, and — on a 100-validator chain with the epoch cache on — sends the
two thirds through the device path and the trusting third to the host."""

import ast
import dataclasses
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import lightchain, reference_bisect, run, spec  # noqa: E402

TINY = {"name": "tiny", "validators": 12, "voting_power": 100,
        "chain_id": "bench-tiny", "headers": 60, "keys_replaced_per_height": 1,
        "block_interval_s": 60, "trusting_period_s": 86400,
        "max_clock_drift_s": 10, "trusted_height": 1, "target_height": 60,
        "trust_level": "1/3", "witnesses": 1, "now_s_after_btime": 3600}
FX100 = dict(TINY, name="fx100", validators=100, chain_id="bench-fx100",
             headers=80, target_height=80, now_s_after_btime=4800)
SEED = 2 ** 31 + 7
CASES = ["forged_in_trusted_third@6", "forged_in_new_two_thirds@6",
         "forged_past_both_stops@6", "swapped_valset@6", "expired_root@6",
         "widest_gap@8", "widest_gap_plus_one@9"]
QUIET = {"generator": {"kind": "closed_loop", "callers": 1}}


def _driver():
    return spec.load_driver(os.path.join(ROOT, "benchmark"),
                            "light_bisect_from_wire")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    import jax

    return _driver().session_class()(
        TINY, SEED, str(tmp_path_factory.mktemp("checkout")), jax.devices(),
        lambda m: None)


def _rise(s, fn):
    c0 = s.counters()
    out = fn()
    c1 = s.counters()
    return out, {k: c1[k] - c0[k] for k in c1
                 if isinstance(c1[k], int) and c1[k] != c0[k]}


# -- the program against the reference ----------------------------------------------


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference_bisect.py")) as f:
        tree = ast.parse(f.read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert names and not [n for n in names if "tendermint" in n]
    assert all(n.level == 1 for n in ast.walk(tree)
               if isinstance(n, ast.ImportFrom) and not n.module)


def test_the_catch_up_is_the_references(tiny):
    h = tiny._honest
    assert h.error is None
    assert h.trace == [1, 6, 11, 15, 19, 23, 27, 34, 41, 48, 54, 60]
    assert h.refused == [(1, 60), (1, 34), (1, 19), (1, 11), (11, 19),
                         (19, 34), (19, 27), (34, 60), (34, 48), (48, 60)]
    assert h.fetched == [1, 1, 60, 34, 19, 11, 6, 15, 27, 23, 48, 41, 54, 60]
    # the root's 9, then a hop's 5 by address + 9 by index
    assert h.sigs == 9 + 11 * (5 + 9) == tiny.n_sigs
    got, rose = _rise(tiny, lambda: tiny.request(0))
    assert got == h.sigs and not tiny._strayed
    del rose["catchups"]
    assert rose == {"sigs_verified_host": h.sigs, "host_fallback_batches": 23,
                    "light_hops_verified": 11, "light_hops_refused": 10,
                    "light_blocks_fetched": 14,
                    "light_trusting_sigs_host": 11 * 5}
    assert tiny._attempts() == (h.trace, h.refused)


@pytest.mark.parametrize("case", range(len(CASES)), ids=CASES)
def test_a_case_built_to_fail_gives_the_references_verdict(tiny, case):
    c = tiny._cases[case]
    assert c.what == CASES[case]
    error, asked = tiny._said(c)
    assert error == c.expect.error
    kind = c.what.split("@")[0]
    if error is None:
        assert asked == c.expect.fetched
        assert kind in ("forged_past_both_stops", "widest_gap",
                        "widest_gap_plus_one")
    else:
        assert error[0] == {"expired_root": "ErrOldHeaderExpired"}.get(
            kind, "ErrInvalidHeader")
        assert {"forged_in_trusted_third": "wrong signature (#",
                "forged_in_new_two_thirds": "wrong signature (#",
                "swapped_valset": "expected new header validators (",
                "expired_root": "old header has expired at Timestamp(seconds="
                }[kind] in error[1]
    if kind == "widest_gap":
        assert (c.expect.trace, c.expect.refused) == ([1, 8], [])
    if kind == "widest_gap_plus_one":
        # 4 of the 12 trusted keys are left at height 9: not above a third
        assert (c.expect.trace, c.expect.refused) == ([1, 5, 9], [(1, 9)])


def test_the_two_forged_cases_are_blamed_on_different_checks(tiny):
    """The trusting check looks at the first 5 trusted signers in the
    commit's order, the +2/3 check at the first 9 rows: a signature
    forged in the first is the trusting check's to refuse even where the
    second would look at it too."""
    keys, blocks = lightchain.chain(TINY, SEED)
    trusted = {v.address for v in blocks[0].vals}
    third = [i for i, v in enumerate(blocks[5].vals)
             if v.address in trusted][:5]
    idx = {c.what: int(c.expect.error[1].split("#")[1].split(")")[0])
           for c in tiny._cases[:2]}
    assert idx["forged_in_trusted_third@6"] in third
    assert idx["forged_in_new_two_thirds@6"] not in third
    assert idx["forged_in_new_two_thirds@6"] < 9


@pytest.mark.parametrize("what,change,kind,says", [
    ("adjacent", dict(target=2), "ValueError",
     "headers must be non adjacent in height"),
    ("trust_level", dict(level=(1, 4)), "ValueError",
     "trustLevel must be within [1/3, 1], given Fraction(numerator=1, "
     "denominator=4)"),
    ("below_trusted", dict(trusted=7, target=5), "ErrInvalidHeader",
     "expected new header height 5 to be greater than one of old header 7"),
    ("from_the_future", dict(drift=-10 ** 6), "ErrInvalidHeader",
     "new header has a time from the future (max clock drift exceeded)"),
    ("starved", dict(target=30), "ErrNotEnoughTrust",
     "invalid commit -- insufficient voting power: got 0, needed more than "
     "400"),
])
def test_the_reference_orders_the_non_adjacent_checks_as_the_program(
        what, change, kind, says):
    from tendermint_tpu.light import verifier
    from tendermint_tpu.light.provider import LightBlock
    from tendermint_tpu.types import Fraction
    from tendermint_tpu.wire.canonical import Timestamp

    _keys, blocks = lightchain.chain(TINY, SEED)
    t, u = blocks[change.get("trusted", 1) - 1], blocks[change.get("target", 6) - 1]
    level, drift = change.get("level", (1, 3)), change.get("drift", 10)
    now = (lightchain.T0 + 3600, 0)
    want, looked = reference_bisect.verify_non_adjacent(
        (t, t.vals), u, u.vals, 86400, now, drift, level)
    assert want == (kind, says) and looked == 0
    tl, ul = (LightBlock.decode(lightchain.light_block_wire(b)) for b in (t, u))
    with pytest.raises(Exception) as e:
        verifier.verify_non_adjacent(
            tl.signed_header, tl.validators, ul.signed_header, ul.validators,
            86400.0, Timestamp(*now), float(drift), Fraction(*level))
    assert (type(e.value).__name__, str(e.value)) == want


def test_a_second_vote_of_one_trusted_validator_is_refused():
    """The commit names one trusted validator in two rows (the set the
    light block supplies stays the honest one): by address the second is a
    double vote, for the reference and for the program."""
    from tendermint_tpu.light.provider import LightBlock
    from tendermint_tpu.types import Fraction
    from tendermint_tpu.types.validation import verify_commit_light_trusting

    _keys, blocks = lightchain.chain(TINY, SEED)
    root, blk = blocks[0], blocks[5]
    trusted = {v.address for v in root.vals}
    a, b = [i for i, v in enumerate(blk.vals) if v.address in trusted][:2]
    vals = list(blk.vals)
    vals[b] = vals[a]
    twice = dataclasses.replace(blk, vals=tuple(vals))
    want, looked = reference_bisect.verify_commit_light_trusting(
        TINY["chain_id"], root.vals, twice, (1, 3))
    assert want[0] == "ValueError" and looked == 0
    assert want[1].startswith("double vote from Validator(address=b") and \
        want[1].endswith(f"({a} and {b})")
    lb = LightBlock.decode(lightchain.light_block_wire(twice, vals=blk.vals))
    trusted_vals = LightBlock.decode(
        lightchain.light_block_wire(root)).validators
    with pytest.raises(ValueError) as e:
        verify_commit_light_trusting(TINY["chain_id"], trusted_vals,
                                     lb.signed_header.commit, Fraction(1, 3))
    assert (type(e.value).__name__, str(e.value)) == want


# -- spans and counters -------------------------------------------------------------


def test_the_spans_and_their_arguments(tiny):
    tr = tiny._tracer
    tr.clear()
    tr.configure(enabled=True)
    try:
        tiny.request(0)
    finally:
        tr.configure(enabled=False)
    events = tr.events()
    tr.clear()
    by = {}
    for name, start, end, _tid, args in events:
        assert end >= start
        by.setdefault(name, []).append(args or {})
    assert by["light.client.verify_at_height"] == [{"from": 1, "to": 60}]
    attempts = by["light.bisect.attempt"]
    assert [a["outcome"] for a in attempts].count("verified") == 11
    assert [(a["from"], a["to"]) for a in attempts
            if a["outcome"] == "not_enough_trust"] == tiny._honest.refused
    # a refused attempt is also a span of its own name, same interval
    assert [(a["from"], a["to"]) for a in by["light.bisect.refused"]] == \
        tiny._honest.refused
    both = {(n, s, e) for n, s, e, _t, _a in events if n.startswith("light.bisect.")}
    assert all(("light.bisect.attempt", s, e) in both
               for n, s, e in both if n == "light.bisect.refused")
    assert [(a["height"], a["source"]) for a in by["light.fetch"]] == [
        (h, "witness" if k in (1, 13) else "primary")
        for k, h in enumerate(tiny._honest.fetched)]
    assert len(by["bench.decode"]) == len(by["light.fetch"]) == 14
    # one header check an attempt; the trusting check runs in each, the
    # +2/3 check only where the first passed
    assert len(by["light.header_checks"]) == 21
    assert len(by["light.trusting_check"]) == 21
    assert len(by["light.light_check"]) == 11
    assert by["light.detect_divergence"] == [{"hops": 11}]
    assert [a["height"] for a in by["light.store.save"]] == [1, 60]


def test_the_adjacent_step_writes_the_same_check_spans():
    """light.header_checks and light.light_check cover verify_adjacent's
    work as they cover verify_non_adjacent's: one metric, both light cells."""
    from tendermint_tpu.light import verifier
    from tendermint_tpu.light.provider import LightBlock
    from tendermint_tpu.observability import trace
    from tendermint_tpu.wire.canonical import Timestamp

    _keys, blocks = lightchain.chain(dict(TINY, headers=3), SEED)
    a, b = (LightBlock.decode(lightchain.light_block_wire(x)) for x in blocks[:2])
    tr = trace.TRACER
    tr.clear()
    tr.configure(enabled=True)
    try:
        verifier.verify_adjacent(a.signed_header, b.signed_header, b.validators,
                                 86400.0, Timestamp(lightchain.T0 + 600, 0), 10.0)
    finally:
        tr.configure(enabled=False)
    names = [e[0] for e in tr.events()]
    tr.clear()
    assert names.count("light.header_checks") == 1
    assert names.count("light.light_check") == 1
    assert "light.trusting_check" not in names


def test_a_span_records_under_a_second_name_only_when_told():
    from tendermint_tpu.observability import trace

    tr = trace.SpanTracer(capacity=16)
    tr.enabled = True
    with tr.span("a", k=1) as sp:
        sp.also("a.kind")
    with tr.span("b"):
        pass
    assert [(n, args) for n, _s, _e, _t, args in tr.events()] == [
        ("a", {"k": 1}), ("a.kind", {"k": 1}), ("b", None)]
    (_, s0, e0, *_), (_, s1, e1, *_) = tr.events()[:2]
    assert (s0, e0) == (s1, e1)
    tr.enabled = False
    with tr.span("c") as sp:
        sp.also("never")
    assert len(tr.events()) == 3


# -- the driver -------------------------------------------------------------------


def test_the_drivers_check_passes_and_fails_on_a_wrong_verdict(tiny, monkeypatch):
    s = tiny
    s._rebase()             # the tests above ran cases of their own
    s.request(0)
    assert s.check() == []
    real = s._catch_up

    def accept_all(target, now, other=None):
        try:
            return real(target, now, other)
        except ValueError:
            return real(target, (lightchain.T0 + 3600, 0))

    monkeypatch.setattr(s, "_catch_up", accept_all)
    bad = s.check()
    assert len(bad) == 4 and all("the reference ('Err" in b for b in bad), bad
    monkeypatch.setattr(s, "_catch_up", real)
    stats = s._ops_stats
    monkeypatch.setattr(
        s, "_ops_stats",
        lambda: dict(stats(), dispatch_errors=stats()["dispatch_errors"] + 1))
    assert any("dispatch_errors moved" in b for b in s.check())
    monkeypatch.setattr(s, "_ops_stats", stats)
    # a request that fetched other heights than the reference is reported
    monkeypatch.setattr(s, "_strayed", [[1, 1, 60]])
    assert any("a request fetched [1, 1, 60]" in b for b in s.check())


def test_a_counter_the_program_lacks_reads_as_nothing(tiny, monkeypatch):
    """The parent of the PR that added the hop counters has none: the
    ratios over them must read nothing there, not zero and not an error."""
    from benchmark import readers

    monkeypatch.setattr(tiny, "_counted", False)
    before = tiny.counters()
    tiny.request(0)
    obs = {"counters": {"before": before, "after": tiny.counters()}}
    assert readers.counter_delta_ratio(
        obs, ["light_hops_verified"], ["catchups"]) is None
    monkeypatch.setattr(tiny, "_counted", True)
    before = tiny.counters()
    tiny.request(0)
    obs = {"counters": {"before": before, "after": tiny.counters()}}
    assert readers.counter_delta_ratio(
        obs, ["light_hops_verified"], ["catchups"]) == 11.0
    assert readers.counter_delta_ratio(
        obs, ["sigs_verified_host"],
        ["sigs_verified_host", "sigs_verified_device"], 100.0) == 100.0


def test_the_cell_loop_runs_the_driver_end_to_end(tiny, monkeypatch):
    """benchmark/run.py's own loop over the cell's files (warm-up, settle,
    window, check, result), with the small chain in place of the
    configuration and the session opened without the chip."""
    cell = spec.load_cell(ROOT, "bisect100-catchup1")
    assert cell.traffic["driver"] == "light_bisect_from_wire"
    assert cell.config["target_height"] == 1000 and cell.config["reduced"] == []
    cell.config = TINY
    cell.traffic = dict(cell.traffic, settle_s=0.2)
    monkeypatch.setattr(cell.driver, "open", lambda *a: tiny)
    monkeypatch.setattr(spec, "load_cell", lambda root, name: cell)
    lines = []
    res = run.run_cell(ROOT, "bisect100-catchup1", SEED, 1.0, False,
                       started=time.time(), say=lines.append)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 3
    assert set(res["metrics"]) == {"commit_p50_ms", "setup_s"}
    assert 1.0 < res["metrics"]["commit_p50_ms"]["value"] < 1000
    assert any("warm-up: catch-up 1" in ln for ln in lines)
    assert not any("CHECK FAILED" in ln for ln in lines)


def test_the_driver_says_so_when_the_client_cannot_catch_up(tiny, monkeypatch):
    def refuses(i):
        raise ValueError("expected new header height 11 to be greater")

    monkeypatch.setattr(tiny, "request", refuses)
    with pytest.raises(SystemExit, match="cannot catch up on an honest chain"):
        tiny.warm(QUIET, lambda m: None)


# -- the driver, through the device path ---------------------------------------------


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import jax

    from tendermint_tpu.ops import epoch_cache

    # the cell walks 22 sets over 8 tables, again and again: here 3 over 2,
    # so that the LRU has dropped a set's table before its next request
    epoch_cache.reset(depth=2)
    try:
        yield _driver().session_class()(
            FX100, SEED, str(tmp_path_factory.mktemp("checkout")),
            jax.devices(), lambda m: None)
    finally:
        epoch_cache.reset()


@pytest.mark.time_limit(600)
def test_two_thirds_on_the_device_and_the_third_on_the_host(session):
    s = session
    h = s._honest
    assert (h.trace, h.refused, h.sigs) == ([1, 45, 80], [(1, 80)],
                                            67 + 2 * (34 + 67))
    assert (s._third, s._launch_sigs, s.n_pool) == (34, 67, 1)
    lines = []
    _none, warm = _rise(s, lambda: s.warm(QUIET, lines.append))
    assert "trace_lower_s" in s.setup and s.setup["compile_s"] >= 0
    got, rose = _rise(s, lambda: s.request(0))
    assert got == h.sigs
    for r, n in ((warm, 2), (rose, 1)):
        assert r["sigs_verified_device"] == n * 3 * 67
        assert r["sigs_verified_host"] == n * 2 * 34 == r["light_trusting_sigs_host"]
        assert r["launches"] == n * 3 and r["host_fallback_batches"] == n * 2
        assert "light_trusting_sigs_device" not in r
        # no hop's set maps onto a resident table (35 or more new keys
        # where a 128-row table of 100 has 27 free rows) and none is found
        # again: every +2/3 check is a cold epoch
        assert r["epoch_tables_built"] == n * 3 == r["epoch_cache_misses"]
        assert "epoch_tables_shared" not in r and "epoch_cache_hits" not in r
    assert s.counters()["sigs_per_request"] == 67
    assert s.check() == []
