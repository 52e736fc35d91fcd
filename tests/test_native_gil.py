"""The native module times its own sections (ISSUE 37):
last_sections() is the calling thread's last call, on perf_counter's
clock; gil_stats() only rises, by what the sections sum to; and a wait
to win the GIL back shows as a span of its own where a second thread
holds it. A section too short to be worth a hand-over keeps the GIL
(ISSUE 38): commit_prep_fused's two scans always, its third under 1 024
selected rows; it is timed and counted all the same, and waits for
nothing."""

import contextlib
import sys
import threading
import time

import numpy as np
import pytest

pytest.importorskip("cryptography", reason="signs a real commit")

import _launch_trace as lt  # noqa: E402

from tendermint_tpu import native  # noqa: E402
from tendermint_tpu.observability import trace as tr  # noqa: E402
from tendermint_tpu.ops import commit_prep as cp  # noqa: E402

pytestmark = pytest.mark.native_required

ENTRIES = {"commit_decode_columns", "valset_decode_columns",
           "commit_prep_fused", "ed25519_rlc_prep", "sr25519_challenges_buf"}


@pytest.fixture(scope="module")
def mod():
    return native.load()


@pytest.fixture(scope="module")
def commit():
    """(validator set, decoded commit, its wire bytes), 40 validators."""
    from tendermint_tpu.types.block import Commit

    vset, _bid, c = lt.signed_commit(40, first=4000)
    wire = c.encode()
    return vset, Commit.decode(wire), wire


@pytest.fixture(autouse=True)
def _tracer_off():
    tr.configure(enabled=False)
    tr.TRACER.clear()
    yield
    tr.configure(enabled=False)
    tr.TRACER.clear()


def _fused_prep(mod, vset, decoded, threshold):
    """One commit_prep_fused call as ops/commit_prep makes it."""
    cols = vset.ed25519_columns()
    tpl_c = decoded.sign_bytes_template(lt.CHAIN_ID, cp.FLAG_COMMIT)
    tpl_n = decoded.sign_bytes_template(lt.CHAIN_ID, cp.FLAG_NIL)
    args = cp._contiguous(decoded.commit_block(), cols[0], cols[1])[1:]
    return mod.commit_prep_fused(*args, tpl_c[0], tpl_n[0], tpl_c[1],
                                 threshold, cp.MODE_SELECT_COMMIT_ONLY)


def test_the_tracers_clock_is_the_modules():
    assert native._ONE_CLOCK, time.get_clock_info("perf_counter")


def test_sections_are_ordered_and_lie_inside_the_call_on_perf_counters_clock(
        mod, commit):
    _vset, _decoded, wire = commit
    t0 = time.perf_counter()
    assert mod.commit_decode_columns(wire) is not None
    t1 = time.perf_counter()
    (released, wanted, got, held), = mod.last_sections()
    assert t0 <= released <= wanted <= got <= t1 and held is False
    # read again, the same call's: nothing was consumed
    assert mod.last_sections() == [(released, wanted, got, False)]


def test_the_fused_prep_has_three_sections_and_one_where_it_returns_early(
        mod, commit):
    vset, decoded, _wire = commit
    power = vset.total_voting_power()
    t0 = time.perf_counter()
    res = _fused_prep(mod, vset, decoded, power * 2 // 3)
    t1 = time.perf_counter()
    sections = mod.last_sections()
    assert len(res) == 6 and len(sections) == 3
    flat = [t for s in sections for t in s[:3]]
    assert flat == sorted(flat) and t0 <= flat[0] and flat[-1] <= t1
    # 40 rows: every section kept the GIL, so none waited for it
    assert all(held and got == wanted for _r, wanted, got, held in sections)
    # not enough power: the tally fails after the first section
    assert len(_fused_prep(mod, vset, decoded, power)) == 2
    assert len(mod.last_sections()) == 1
    # an entry that never gives the GIL up (a str is no bytes) has none
    assert mod.commit_decode_columns("no bytes") is None
    assert mod.last_sections() == []


def test_sections_are_the_calling_threads_own(mod, commit):
    vset, decoded, wire = commit
    seen = {}

    def other():
        _fused_prep(mod, vset, decoded, vset.total_voting_power() * 2 // 3)
        ready.set()
        go.wait(timeout=30)
        seen["other"] = mod.last_sections()

    ready, go = threading.Event(), threading.Event()
    t = threading.Thread(target=other)
    t.start()
    assert ready.wait(timeout=30)
    mod.commit_decode_columns(wire)     # after the other thread's call
    mine = mod.last_sections()
    go.set()
    t.join(timeout=30)
    assert not t.is_alive()
    assert len(mine) == 1 and len(seen["other"]) == 3
    assert seen["other"][-1][2] <= mine[0][0]
    assert mod.last_sections() == mine


def test_gil_stats_only_rise_by_what_the_sections_sum_to(mod, commit):
    vset, decoded, wire = commit
    first = mod.gil_stats()
    assert set(first) == ENTRIES
    calls = [("commit_decode_columns", lambda: mod.commit_decode_columns(wire)),
             ("commit_prep_fused", lambda: _fused_prep(
                 mod, vset, decoded, vset.total_voting_power() * 2 // 3)),
             ("valset_decode_columns",
              lambda: mod.valset_decode_columns(vset.encode()))]
    for entry, call in calls:
        before = mod.gil_stats()
        assert call() is not None
        sections = mod.last_sections()
        after = mod.gil_stats()
        let_go = [s for s in sections if not s[3]]
        assert len(sections) >= 1
        assert after[entry][0] - before[entry][0] == len(let_go)
        assert after[entry][3] - before[entry][3] == len(sections) - len(let_go)
        assert after[entry][1] - before[entry][1] == pytest.approx(
            sum(w - r for r, w, _g, _h in let_go), abs=1e-8)
        assert after[entry][2] - before[entry][2] == pytest.approx(
            sum(g - w for _r, w, g, _h in let_go), abs=1e-8)
        assert all(a >= b for e in ENTRIES for a, b in zip(after[e], before[e]))
        assert all(after[e] == before[e] for e in ENTRIES - {entry})
    # the program's snapshot carries the same counter
    from tendermint_tpu.libs.metrics import ops_stats

    assert ops_stats()["native_gil"] == mod.gil_stats() == native.gil_stats()


def test_traced_call_records_a_pair_a_section_and_nothing_when_off(mod, commit):
    _vset, _decoded, wire = commit
    assert native.columns("commit_decode_columns", wire) is not None
    assert tr.TRACER.events() == []
    tr.configure(enabled=True)
    t0 = time.perf_counter()
    native.columns("commit_decode_columns", wire)
    t1 = time.perf_counter()
    tr.configure(enabled=False)
    work, wait = tr.TRACER.events()
    at = {"entry": "commit_decode_columns", "section": 0}
    assert (work[0], wait[0]) == ("wire.columns.native", "wire.columns.gil")
    assert work[4] == wait[4] == at
    assert work[3] == wait[3] == threading.get_ident()
    assert t0 <= work[1] <= work[2] == wait[1] <= wait[2] <= t1
    assert [(work[1], work[2], wait[2], False)] == mod.last_sections()


def test_on_another_clock_no_native_span_is_recorded(monkeypatch, commit):
    _vset, _decoded, wire = commit
    monkeypatch.setattr(native, "_ONE_CLOCK", False)
    tr.configure(enabled=True)
    assert native.columns("commit_decode_columns", wire) is not None
    tr.configure(enabled=False)
    assert tr.TRACER.events() == []


def test_record_all_is_record_for_each_under_one_lock():
    t = tr.SpanTracer(capacity=16)
    t.set_thread_args(launch=3)
    t.record_all([("a", 1.0, 2.0, {"entry": "e"}), ("b", 2.0, 3.0, None)])
    t.set_thread_args()
    t.record_all([("c", 3.0, 4.0, None)])
    tid = threading.get_ident()
    assert t.events() == [("a", 1.0, 2.0, tid, {"launch": 3, "entry": "e"}),
                          ("b", 2.0, 3.0, tid, {"launch": 3}),
                          ("c", 3.0, 4.0, tid, None)]
    assert t.recorded_total == 3


CALLS = 40
LANES = 50_000
SWITCH_S = 0.005


@contextlib.contextmanager
def _spinner():
    """A second thread that spins in Python and asks for the GIL once a
    switch interval (5 ms), for the block's length."""
    stop, spinning = threading.Event(), threading.Event()

    def spin():
        n = 0
        while not stop.is_set():
            n += 1
            if n == 1000:
                spinning.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(SWITCH_S)
    t = threading.Thread(target=spin)
    t.start()
    try:
        assert spinning.wait(timeout=30)
        yield
    finally:
        stop.set()
        t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not t.is_alive()


@pytest.fixture(scope="module")
def long_wire():
    """Wire bytes whose native decode runs for about a millisecond with
    the GIL given up: 50 000 lanes (the decode checks no signature)."""
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader)
    from tendermint_tpu.wire.canonical import Timestamp

    bid = BlockID(hash=b"\x11" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
    sigs = [CommitSig(block_id_flag=BLOCK_ID_FLAG_COMMIT,
                      validator_address=i.to_bytes(20, "big"),
                      timestamp=Timestamp(seconds=1_700_000_000, nanos=i + 1),
                      signature=i.to_bytes(64, "big"))
            for i in range(LANES)]
    return Commit(height=9, round=0, block_id=bid, signatures=sigs).encode()


def _gil_wait(wire) -> float:
    """Seconds of `wire.columns.gil` over CALLS traced native decodes."""
    tr.TRACER.clear()
    tr.configure(enabled=True)
    try:
        for _ in range(CALLS):
            assert native.columns("commit_decode_columns", wire) is not None
    finally:
        tr.configure(enabled=False)
    waits = [e - s for n, s, e, _t, _a in tr.TRACER.events()
             if n == "wire.columns.gil"]
    assert len(waits) == CALLS
    return sum(waits)


@pytest.mark.time_limit(120)
def test_a_thread_that_holds_the_gil_shows_as_gil_wait(long_wire):
    """A second thread spinning in Python asks for the GIL once a switch
    interval (5 ms); the decode's next release hands it over, and the
    decode that wants it back waits out the spinner's interval: about one
    call in four of the 40 waits 5 ms or more, some 50 ms in all, where a
    lone caller waits about a microsecond a call. The floor lies 5 times
    and more from both. (A release alone rarely loses the GIL: a section
    is over before the waiting thread is awake; it changes hands where a
    drop request is pending when it is released.)"""
    floor = SWITCH_S                       # 5 ms
    alone = _gil_wait(long_wire)
    assert alone < floor, f"a lone caller waited {alone * 1e3:.3f} ms for the GIL"

    free_before = native.gil_stats()["commit_decode_columns"][1]
    with _spinner():
        contended = _gil_wait(long_wire)
    assert contended > 5 * floor and alone < floor / 5, (contended, alone)
    # the always-on counter saw the same wait, and the wait is no part of
    # the work: `free_s` rose by the decodes alone
    _n, free_s, wait_s, _held = native.gil_stats()["commit_decode_columns"]
    assert wait_s >= contended
    assert free_s - free_before < CALLS * 0.02


HUB_ROWS = 150               # a hub150 commit: every section holds
BIG_ROWS = 4096              # past the floor: the third section lets go


def _rows(n):
    """commit_prep_fused's eleven arguments for n COMMIT lanes (the prep
    checks no signature: random rows do)."""
    rng = np.random.RandomState(n)
    return (np.full(n, cp.FLAG_COMMIT, np.uint8),
            rng.randint(0, 256, (n, 64), dtype=np.uint8),
            np.full(n, 1_700_000_000, np.int64),
            np.arange(1, n + 1, dtype=np.int32),
            rng.randint(0, 256, (n, 32), dtype=np.uint8),
            np.full(n, 100, np.int64),
            b"\x08\x02\x11\x07prefix", b"\x08\x02\x11\x07nil", b"\x32\x05chain",
            100 * n * 2 // 3, cp.MODE_SELECT_COMMIT_ONLY)


@pytest.mark.time_limit(120)
def test_a_short_prep_keeps_the_gil_and_a_long_one_gives_it_up_once(mod):
    hub, big = _rows(HUB_ROWS), _rows(BIG_ROWS)
    with _spinner():
        before = mod.gil_stats()["commit_prep_fused"]
        for _ in range(CALLS):
            assert len(mod.commit_prep_fused(*hub)) == 6
        sections = mod.last_sections()
        after = mod.gil_stats()["commit_prep_fused"]
        # beside a thread that wants the GIL: nothing let go, no wait
        assert len(sections) == 3
        assert all(held and got == wanted
                   for _r, wanted, got, held in sections)
        assert (after[0], after[1], after[2]) == before[:3]
        assert after[3] - before[3] == 3 * CALLS
        assert len(mod.commit_prep_fused(*big)) == 6
        sections = mod.last_sections()
        last = mod.gil_stats()["commit_prep_fused"]
    assert [held for *_t, held in sections] == [True, True, False]
    assert last[0] - after[0] == 1 and last[3] - after[3] == 2
    released, wanted, got, _held = sections[2]
    assert last[1] - after[1] == pytest.approx(wanted - released, abs=1e-8)
    assert last[2] - after[2] == pytest.approx(got - wanted, abs=1e-8)


def test_sixteen_threads_in_the_held_prep_give_what_one_gives(mod):
    """A held section takes no lock a released one did not take: many
    callers at once (the interpreter switches them between calls, never
    inside one) compute what one computes."""
    hub = _rows(HUB_ROWS)
    alone = mod.commit_prep_fused(*hub)
    before = mod.gil_stats()["commit_prep_fused"]
    results, go = {}, threading.Barrier(16)

    def call(k):
        go.wait(timeout=30)
        results[k] = [mod.commit_prep_fused(*hub) for _ in range(50)]

    threads = [threading.Thread(target=call, args=(k,)) for k in range(16)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)      # switch the callers as often as can be
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(results) == 16
    assert all(r == alone for rs in results.values() for r in rs)
    after = mod.gil_stats()["commit_prep_fused"]
    assert after[3] - before[3] == 16 * 50 * 3 and after[:3] == before[:3]


@pytest.mark.parametrize("rows,held", [(HUB_ROWS, [True, True, True]),
                                       (BIG_ROWS, [True, True, False])])
def test_traced_call_records_no_gil_span_for_a_section_that_held(
        mod, rows, held):
    args = _rows(rows)
    tr.configure(enabled=True)
    res = native.traced_call(mod, "commit_prep_fused", "ops.commit_prep",
                             *args)
    tr.configure(enabled=False)
    assert len(res) == 6
    sections = mod.last_sections()
    assert [h for *_t, h in sections] == held
    want = []
    for i, (released, wanted, got, h) in enumerate(sections):
        at = {"entry": "commit_prep_fused", "section": i}
        if h:
            want.append(("ops.commit_prep.native", released, wanted,
                         {**at, "held": True}))
        else:
            want.append(("ops.commit_prep.native", released, wanted, at))
            want.append(("ops.commit_prep.gil", wanted, got, at))
    tid = threading.get_ident()
    assert tr.TRACER.events() == [(n, a, b, tid, at) for n, a, b, at in want]


@pytest.mark.time_limit(120)
def test_the_gil_probe_runs_without_a_device(tmp_path, capsys):
    """tools/gil_probe.py, two callers for a second on hub150's pool: the
    commits it counts are the fused preps it made (three held sections
    each at 150 rows, none that let go), one decode a commit."""
    import json

    from tools import gil_probe

    assert gil_probe.main(["--callers", "2", "--sleep-ms", "16",
                           "--seconds", "1", "--root", str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["callers"] == 2 and out["sigs_per_commit"] == HUB_ROWS
    assert 2 <= out["commits"] and 0 < out["commits_per_s"]
    assert out["gil"]["commit_prep_fused"] == {
        "released": 0, "held": 3 * out["commits"], "free_s": 0.0,
        "wait_s": 0.0}
    assert out["gil"]["commit_decode_columns"]["released"] == out["commits"]


def test_sr25519_challenges_keep_the_gil_under_the_floor_and_match(mod):
    """sr25519_challenges_buf: one timed section, held below 1 024 rows
    (a 150-signature commit) and given up from there, and the scalars
    of the pure-Python merlin transcript."""
    import numpy as np

    from tendermint_tpu.crypto import sr25519
    from tendermint_tpu.crypto._edwards import L

    for n, held in ((150, True), (1024, False)):
        rng = np.random.default_rng(n)
        pubs = rng.integers(0, 256, 32 * n, dtype=np.uint8).tobytes()
        rs = rng.integers(0, 256, 32 * n, dtype=np.uint8).tobytes()
        msgs = [bytes([i % 251]) * (100 + i % 7) for i in range(n)]
        offs = np.cumsum([0] + [len(m) for m in msgs]).astype(np.int64)
        before = mod.gil_stats()["sr25519_challenges_buf"]
        k = mod.sr25519_challenges_buf(sr25519.SIGNING_CTX, pubs, rs,
                                       b"".join(msgs), offs.tobytes())
        (section,) = mod.last_sections()
        after = mod.gil_stats()["sr25519_challenges_buf"]
        assert section[3] is held
        assert (after[0] - before[0], after[3] - before[3]) == (
            (0, 1) if held else (1, 0))
        for i in range(0, n, 37):
            t = sr25519._signing_transcript(msgs[i])
            t.append_message(b"proto-name", b"Schnorr-sig")
            t.append_message(b"sign:pk", pubs[32 * i:32 * i + 32])
            t.append_message(b"sign:R", rs[32 * i:32 * i + 32])
            want = int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little")
            assert int.from_bytes(k[32 * i:32 * i + 32], "little") == want % L
