"""The launch path accounted for from inside the dispatcher (ISSUE 26):
the records of one launch join on one `launch` id across the four
threads and nest as designed, a jax.profiler capture holds the program's
spans, CPU seconds are read per pipeline thread, and the tracer module
still imports without jax."""

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
