"""The launch path accounted for from inside the dispatcher (ISSUE 26):
the records of one launch join on one `launch` id across the four
threads and nest as designed, a jax.profiler capture holds the program's
spans, CPU seconds are read per pipeline thread, and the tracer module
still imports without jax. Host prep says what it waits for (ISSUE 37):
on the chip's host path every stage of both preps is a span inside its
parent, the native entries' GIL-free sections among them.
"""

import glob
import os
import subprocess
import sys
import time

import pytest

pytest.importorskip("cryptography", reason="signs a real commit")

import _launch_trace as lt  # noqa: E402

from tendermint_tpu.libs.metrics import cpu_seconds_by_thread, ops_stats  # noqa: E402
from tendermint_tpu.observability import trace as tr  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# span -> (thread, the span it lies inside, or None)
DESIGN = {
    # the caller's wait, filed under the caller though the coalescer records it
    "pipeline.queue_wait.intake": ("MainThread", "ops.pipeline_wait"),
    "pipeline.coalesce": ("verify-coalesce", None),
    "pipeline.prep": ("verify-coalesce", "pipeline.coalesce"),
    "pipeline.queue_wait.dispatch": ("verify-dispatch", None),
    "pipeline.transfer": ("verify-dispatch", None),
    "pipeline.transfer.put": ("verify-dispatch", "pipeline.transfer"),
    "pipeline.queue_wait": ("verify-dispatch", None),
    "pipeline.dispatch": ("verify-dispatch", None),
    "pipeline.queue_wait.resolve": ("verify-resolve", None),
    "pipeline.device_wait": ("verify-resolve", None),
    "pipeline.device_wait.kernel": ("verify-resolve", "pipeline.device_wait"),
    "pipeline.device_wait.readback": ("verify-resolve", "pipeline.device_wait"),
    "pipeline.resolve": ("verify-resolve", None),
    "ops.pipeline_wait.wake": ("MainThread", "ops.pipeline_wait"),
}


@pytest.fixture(autouse=True)
def _reset_tracer():
    tr.configure(enabled=False)
    tr.TRACER.clear()
    yield
    tr.configure(enabled=False)
    tr.TRACER.clear()


@pytest.fixture(scope="module")
def one_commit():
    return lt.traced_commit()


def test_one_launch_joins_on_its_id_across_the_four_threads(one_commit):
    records, names = one_commit
    by_name = {}
    for r in records:
        by_name.setdefault(r[0], []).append(r)
    assert set(DESIGN) <= set(by_name), set(DESIGN) - set(by_name)
    launches = {r[4]["launch"] for n in DESIGN for r in by_name[n]}
    assert len(launches) == 1 and launches.pop() >= 1
    threads = {names[r[3]] for n in DESIGN for r in by_name[n]}
    assert threads == {"MainThread", "verify-coalesce", "verify-dispatch",
                       "verify-resolve"}
    for name, (thread, parent) in DESIGN.items():
        for _n, start, end, tid, _a in by_name[name]:
            assert names[tid] == thread, name
            assert end >= start, name
            if parent is not None:
                (_p, p0, p1, ptid, _pa), = by_name[parent]
                assert ptid == tid and p0 <= start and end <= p1, (name, parent)
    puts = by_name["pipeline.transfer.put"]
    assert len(puts) >= 2 and all(r[4]["bytes"] > 0 for r in puts)
    assert by_name["pipeline.device_wait.readback"][0][4]["bytes"] > 0
    coalesce = by_name["pipeline.coalesce"][0][4]
    assert (coalesce["jobs"], coalesce["sigs"]) == (1, lt.N_VALIDATORS)
    assert coalesce["bucket"] >= lt.N_VALIDATORS
    assert by_name["pipeline.resolve"][0][4]["jobs"] == 1
    assert "pipeline.queue_wait.linger" not in by_name, "a lone caller never lingers"


def test_the_launchs_stages_follow_one_another(one_commit):
    """Hand-off spans start where the previous stage stopped: what they
    cover is waiting, and the stages between them do not overlap."""
    records, _names = one_commit
    at = {r[0]: r for r in records if r[0] in DESIGN and r[0] != "pipeline.transfer.put"}
    order = ["pipeline.queue_wait.intake", "pipeline.coalesce",
             "pipeline.queue_wait.dispatch", "pipeline.transfer",
             "pipeline.queue_wait", "pipeline.dispatch",
             "pipeline.queue_wait.resolve", "pipeline.device_wait",
             "pipeline.resolve"]
    for a, b in zip(order, order[1:]):
        assert at[a][1] <= at[b][1], (a, b)
        assert at[a][2] <= at[b][2] + 1e-4, (a, b)
    # the caller's wake starts inside the resolver's fan-out
    wake, res = at["ops.pipeline_wait.wake"], at["pipeline.resolve"]
    assert res[1] <= wake[1] <= res[2] and wake[2] >= wake[1]


def test_launch_ids_rise_by_one_per_batch():
    from tendermint_tpu.ops.pipeline import AsyncBatchVerifier, resolved_at

    vset, _bid, _commit = lt.signed_commit(8)
    entries = [(v.pub_key.bytes(), b"m%d" % i, b"\x00" * 64)
               for i, v in enumerate(vset.validators)]
    v = AsyncBatchVerifier(depth=2)
    try:
        off = v.submit(entries)
        off.result(timeout=300)
        assert resolved_at(off) == (0.0, 0), "nothing is stamped with the tracer off"
        tr.configure(enabled=True)
        ids = []
        for _ in range(3):
            fut = v.submit(entries)
            fut.result(timeout=300)
            t, launch = resolved_at(fut)
            assert 0 < t <= time.perf_counter()
            ids.append(launch)
        assert ids == [ids[0], ids[0] + 1, ids[0] + 2] and ids[0] == 2
    finally:
        tr.configure(enabled=False)
        v.close()


def test_a_profiler_capture_holds_the_programs_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from tendermint_tpu.types import validation

    vset, bid, commit = lt.signed_commit()
    validation.verify_commit(lt.CHAIN_ID, vset, bid, commit.height, commit)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tr.configure(enabled=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        validation.verify_commit(lt.CHAIN_ID, vset, bid, commit.height, commit)
    finally:
        jax.profiler.stop_trace()
        tr.configure(enabled=False)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host.setdefault(ev.name, []).append(ev.duration_ns)
    for name in ("pipeline.dispatch", "pipeline.transfer", "pipeline.device_wait",
                 "pipeline.device_wait.kernel", "verify_commit", "ops.pipeline_wait"):
        assert name in host, (name, sorted(n for n in host if "." in n))
    # the same interval on both clocks: the profiler's span is the ring's
    ring = {r[0]: r[2] - r[1] for r in tr.TRACER.events()}
    assert host["pipeline.device_wait"][0] * 1e-9 == pytest.approx(
        ring["pipeline.device_wait"], rel=0.2, abs=2e-4)


def test_cpu_seconds_by_thread_names_the_pipelines_threads_and_only_rises(one_commit):
    from tendermint_tpu.types import validation

    first = cpu_seconds_by_thread()
    assert {"process", "verify-coalesce", "verify-dispatch",
            "verify-resolve"} <= set(first)
    assert all(n == "process" or n.startswith("verify-") for n in first)
    vset, bid, commit = lt.signed_commit()
    validation.verify_commit(lt.CHAIN_ID, vset, bid, commit.height, commit)
    second = ops_stats()["cpu_seconds_by_thread"]
    assert set(first) <= set(second)
    assert all(second[n] >= first[n] for n in first)
    assert second["verify-coalesce"] > first["verify-coalesce"], "it prepared a commit"
    threads = sum(s for n, s in second.items() if n != "process")
    assert 0 < threads <= second["process"]


def test_thread_args_ride_on_every_record_of_their_thread_only():
    import threading

    t = tr.SpanTracer(capacity=32)
    t.configure(enabled=True)
    t.set_thread_args(launch=7)
    with t.span("a", bucket=128):
        pass
    t.record("b", 0.0, 1.0, {"launch": 9})          # a span's own args win
    t.flow_point("c", 5, "s")
    other = threading.Thread(target=lambda: t.record("d", 0.0, 1.0))
    other.start()
    other.join(timeout=10)
    t.set_thread_args()
    t.record("e", 0.0, 1.0)
    args = {r[0]: r[4] for r in t.events()}
    assert args == {"a": {"launch": 7, "bucket": 128}, "b": {"launch": 9},
                    "c": {"launch": 7, "flow": 5, "flow_phase": "s"},
                    "d": None, "e": None}


def test_the_tracer_imports_and_records_without_jax():
    code = (
        "import sys\n"
        "from tendermint_tpu.observability import trace as tr\n"
        "tr.configure(enabled=True)\n"
        "with tr.span('x', n=1):\n"
        "    pass\n"
        "assert [r[0] for r in tr.TRACER.events()] == ['x']\n"
        "assert tr.TRACER.capacity == tr.DEFAULT_CAPACITY == 262144\n"
        "assert 'jax' not in sys.modules, 'the tracer pulled jax in'\n"
        "print('OK')\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       cwd=REPO, timeout=120, text=True)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


def test_a_virtual_clock_tracer_opens_no_profiler_annotation(monkeypatch):
    import jax  # noqa: F401 — the annotation is looked up only once jax is loaded

    opened = []

    class Spy:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tr, "_annotation_cls", Spy)
    sim = tr.SpanTracer(capacity=16, node="n0", now=lambda: 1.0)
    sim.configure(enabled=True)
    with sim.span("consensus.step"):
        pass
    assert opened == []
    tr.configure(enabled=True)
    with tr.span("pipeline.dispatch"):
        pass
    assert opened == ["pipeline.dispatch"]


# -- host prep's stages (ISSUE 37) ----------------------------------------------

# span -> (thread, the span it lies inside)
PREP_DESIGN = {
    "wire.columns.native": ("MainThread", "bench.decode"),
    "wire.columns.gil": ("MainThread", "bench.decode"),
    "ops.commit_prep.columns": ("MainThread", "verify_commit.prep_fused"),
    # no ops.commit_prep.gil: under 1 024 rows the fused prep's sections
    # all keep the GIL (ISSUE 38; the pair of one that lets go is
    # tests/test_native_gil.py's)
    "ops.commit_prep.native": ("MainThread", "verify_commit.prep_fused"),
    "ops.commit_prep.block": ("MainThread", "verify_commit.prep_fused"),
    "ops.rlc_prep.pack": ("verify-coalesce", "pipeline.prep"),
    "ops.rlc_prep.z": ("verify-coalesce", "pipeline.prep"),
    "ops.rlc_prep.native": ("verify-coalesce", "pipeline.prep"),
    "ops.rlc_prep.gil": ("verify-coalesce", "pipeline.prep"),
    "ops.rlc_prep.fill": ("verify-coalesce", "pipeline.prep"),
}
FROM_THE_NATIVE_CLOCK = [n for n in PREP_DESIGN if n.endswith((".native", ".gil"))]


def _by_name(records):
    by = {}
    for r in records:
        by.setdefault(r[0], []).append(r)
    return by


def _held_to_the_design(records, names, design):
    by = _by_name(records)
    assert set(design) <= set(by), set(design) - set(by)
    for name, (thread, parent) in design.items():
        (_p, p0, p1, ptid, _pa), = by[parent]
        for _n, start, end, tid, _a in by[name]:
            assert names[tid] == thread, name
            assert ptid == tid and p0 <= start <= end <= p1, (name, parent)
    return by


@pytest.fixture(scope="module")
def prep_requests():
    with lt.chip_host_path():
        yield lt.traced_requests()


@pytest.mark.parametrize("sight", ["cold", "warm"])
def test_every_stage_of_both_preps_lies_inside_its_parent(prep_requests, sight):
    (cold, warm), names = prep_requests
    by = _held_to_the_design(cold if sight == "cold" else warm, names,
                             PREP_DESIGN)
    assert by["pipeline.prep"][0][4]["cached"] == int(sight == "warm")
    # either sight ships its launch as one packed buffer: one put
    assert len(by["pipeline.transfer.put"]) == 1
    # one pair a section that gave the GIL up: one of the decode, one of
    # the RLC prep; a pair shares its boundary
    for prefix, entry in [("wire.columns", "commit_decode_columns"),
                          ("ops.rlc_prep", "ed25519_rlc_prep")]:
        (work,), (wait,) = by[prefix + ".native"], by[prefix + ".gil"]
        assert work[4]["section"] == wait[4]["section"] == 0
        assert work[4]["entry"] == wait[4]["entry"] == entry
        assert work[2] == wait[1] and "held" not in work[4]
    # the fused commit prep's three sections kept it: the work alone, in
    # order, marked, and no wait
    held = by["ops.commit_prep.native"]
    assert [r[4]["section"] for r in held] == [0, 1, 2]
    assert all(r[4]["entry"] == "commit_prep_fused" and r[4]["held"] is True
               for r in held)
    assert all(a[2] <= b[1] for a, b in zip(held, held[1:]))
    assert "ops.commit_prep.gil" not in by
    # the stages follow one another and the dispatcher's launch id rides
    # on the coalescer's records, the native ones too
    at = {n: by[n][0] for n in ("ops.commit_prep.columns",
                                "ops.commit_prep.native", "ops.rlc_prep.pack",
                                "ops.rlc_prep.z", "ops.rlc_prep.native")}
    assert at["ops.commit_prep.columns"][2] <= at["ops.commit_prep.native"][1]
    assert held[-1][2] <= by["ops.commit_prep.block"][0][1]
    assert (at["ops.rlc_prep.pack"][2] <= at["ops.rlc_prep.z"][1]
            <= at["ops.rlc_prep.z"][2] <= at["ops.rlc_prep.native"][1])
    assert by["ops.rlc_prep.gil"][0][2] <= by["ops.rlc_prep.fill"][0][1]
    launch = by["pipeline.prep"][0][4]["launch"]
    assert all(r[4]["launch"] == launch for n in PREP_DESIGN
               if n.startswith("ops.rlc_prep") for r in by[n])


@pytest.mark.parametrize("parent", ["verify_commit.prep_fused", "pipeline.prep"])
def test_the_stages_cover_their_parent_but_for_a_remainder(prep_requests, parent):
    """What is left of a parent outside its stages is its self time
    (select_kernel, plan_bucket, the span's own note, frames, the tracer's
    own cost): under half of it on any host, a tenth on a quiet one."""
    (_cold, warm), _names = prep_requests
    by = _by_name(warm)
    (_n, p0, p1, _tid, _a), = by[parent]
    kids = sum(r[2] - r[1] for n, (_t, p) in PREP_DESIGN.items() if p == parent
               for r in by[n])
    assert 0.5 * (p1 - p0) < kids <= p1 - p0, (kids, p1 - p0)


def test_with_the_tracer_off_no_section_is_read_and_nothing_is_recorded(monkeypatch):
    from tendermint_tpu import native
    from tendermint_tpu.types import validation
    from tendermint_tpu.types.block import Commit

    mod = native.load()
    if mod is None:
        pytest.skip("tm_native did not build")
    reads = []
    real = mod.last_sections
    monkeypatch.setattr(mod, "last_sections",
                        lambda: reads.append(1) or real())
    with lt.chip_host_path():
        vset, bid, commit = lt.signed_commit(66, height=66, first=3000)
        wire = commit.encode()
        for _ in range(2):
            validation.verify_commit(lt.CHAIN_ID, vset, bid, commit.height,
                                     Commit.decode(wire))
        assert reads == [] and tr.TRACER.events() == []
        before = ops_stats()["native_gil"]
        tr.configure(enabled=True)
        validation.verify_commit(lt.CHAIN_ID, vset, bid, commit.height,
                                 Commit.decode(wire))
        tr.configure(enabled=False)
        after = ops_stats()["native_gil"]
    # one read a call of a timed entry: decode, fused prep, RLC prep
    assert len(reads) == 3
    # the counter needs no tracer: it moved by the traced call's sections
    # (those that gave the GIL up, those that held it)
    assert {e: (after[e][0] - before[e][0], after[e][3] - before[e][3])
            for e in after} == {
        "commit_decode_columns": (1, 0), "valset_decode_columns": (0, 0),
        "commit_prep_fused": (0, 3), "ed25519_rlc_prep": (1, 0),
        "sr25519_challenges_buf": (0, 0)}


def test_the_pure_python_paths_record_the_stages_and_no_native_section():
    """TM_TPU_NO_NATIVE=1: the decode is the Python walk, the commit prep
    the numpy fallback, the RLC scalars the split path, whose span stands
    where the fused call's `.native` does; nothing waits to win a GIL it
    never gave up."""
    with lt.chip_host_path(native=False):
        (cold, warm), names = lt.traced_requests(68)
    stages = {n: v for n, v in PREP_DESIGN.items()
              if n not in FROM_THE_NATIVE_CLOCK or n == "ops.rlc_prep.native"}
    for records in (cold, warm):
        by = _held_to_the_design(records, names, stages)
        assert not [n for n in by if n.endswith(".gil")]
        assert "wire.columns.native" not in by
        assert "ops.commit_prep.native" not in by
        # a `with` span around the fallback: no section of a native entry
        (fallback,) = by["ops.rlc_prep.native"]
        assert "entry" not in fallback[4]
