"""The per-layer metrics that read the dispatcher's launch-path spans
(PR 26): the gap attribution files every new span name in the group its
prefix was chosen for, and every new metric file loads and reads a small
hand-made stretch whose answers can be worked out on paper."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import readers, spec, trace_reduce as tr  # noqa: E402

MAN = spec.manifest(ROOT)
OPS = "XLA Ops"

WAITING = ["pipeline.queue_wait.intake", "pipeline.queue_wait.linger",
           "pipeline.queue_wait.dispatch", "pipeline.queue_wait.resolve",
           "pipeline.device_wait.kernel", "pipeline.device_wait.readback",
           "ops.pipeline_wait.wake"]
ACTIVE = ["pipeline.transfer.put", "pipeline.coalesce", "pipeline.resolve"]

# one second, two launches of 100 signatures. Threads: callers 1 and 5,
# coalescer 2, dispatcher 3, resolver 4. Launch A is caller 1's alone; in
# launch B caller 1's job lingers 40 ms and is fused with caller 5's.
STRETCH = {
    "t_a": 0.0, "t_b": 1.0, "sigs": 200, "spans_recorded": 40,
    "ring_capacity": 262144,
    "device_events": [["/device:TPU:0", OPS, "%rlc_verify_cached.5", 0.130, 0.050],
                      ["/device:TPU:0", OPS, "%rlc_verify_cached.5", 0.640, 0.050]],
    "spans": [
        # launch A
        ["ops.pipeline_wait", 0.000, 0.300, 1],
        ["pipeline.queue_wait.intake", 0.000, 0.010, 1],
        ["pipeline.coalesce", 0.010, 0.060, 2],
        ["pipeline.prep", 0.020, 0.050, 2],
        ["pipeline.queue_wait.dispatch", 0.060, 0.070, 3],
        ["pipeline.transfer", 0.070, 0.110, 3],
        ["pipeline.transfer.put", 0.070, 0.080, 3],
        ["pipeline.transfer.put", 0.080, 0.110, 3],
        ["pipeline.queue_wait", 0.110, 0.112, 3],
        ["pipeline.dispatch", 0.112, 0.130, 3],
        ["pipeline.queue_wait.resolve", 0.130, 0.140, 4],
        ["pipeline.device_wait", 0.140, 0.240, 4],
        ["pipeline.device_wait.kernel", 0.140, 0.190, 4],
        ["pipeline.device_wait.readback", 0.190, 0.240, 4],
        ["pipeline.resolve", 0.240, 0.280, 4],
        ["ops.pipeline_wait.wake", 0.270, 0.300, 1],
        # launch B
        ["ops.pipeline_wait", 0.500, 0.760, 1],
        ["ops.pipeline_wait", 0.530, 0.760, 5],
        ["pipeline.queue_wait.intake", 0.500, 0.510, 1],
        ["pipeline.queue_wait.intake", 0.530, 0.550, 5],
        ["pipeline.coalesce", 0.510, 0.600, 2],
        ["pipeline.queue_wait.linger", 0.510, 0.550, 2],
        ["pipeline.prep", 0.560, 0.590, 2],
        ["pipeline.queue_wait.dispatch", 0.600, 0.605, 3],
        ["pipeline.transfer", 0.605, 0.625, 3],
        ["pipeline.transfer.put", 0.606, 0.624, 3],
        ["pipeline.dispatch", 0.625, 0.640, 3],
        ["pipeline.queue_wait.resolve", 0.640, 0.645, 4],
        ["pipeline.device_wait", 0.645, 0.700, 4],
        ["pipeline.device_wait.kernel", 0.645, 0.690, 4],
        ["pipeline.device_wait.readback", 0.690, 0.700, 4],
        ["pipeline.resolve", 0.700, 0.720, 4],
        ["ops.pipeline_wait.wake", 0.710, 0.760, 1],
        ["ops.pipeline_wait.wake", 0.715, 0.760, 5],
    ],
}

# metric base name -> what the stretch above reads, by hand
EXPECT = {
    "transfer_us_per_sig": (0.040 + 0.020) * 1e6 / 200,
    "h2d_put_p50_ms": 18.0,                       # puts of 10, 30, 18 ms
    "kernel_wait_p50_ms": (50.0 + 45.0) / 2,
    "readback_p50_ms": (50.0 + 10.0) / 2,
    # A: 50 less prep 30; B: 90 less linger 40 and prep 30
    "coalesce_self_us_per_sig": (0.020 + 0.020) * 1e6 / 200,
    "linger_p50_ms": 40.0,
    # caller 1: 10 + 30 + 10 + 50, caller 5: 20 + 45, dispatcher 10 + 5,
    # resolver 10 + 5
    "handoff_us_per_sig": 0.195 * 1e6 / 200,
    "resolve_us_per_sig": (0.040 + 0.020) * 1e6 / 200,
}

# the fifteen launch-path metrics, by name: a later PR may declare
# another form of one of these quantities for a cell of its own
FIFTEEN = [f"{base}.{form}" for base in EXPECT for form in ("lat", "thr")
           if f"{base}.{form}" != "linger_p50_ms.lat"]
NEW = [m for name in FIFTEEN for m in MAN["per_layer"] if m["name"] == name]


@pytest.mark.parametrize("name", WAITING)
def test_waiting_children_keep_their_parents_rank(name):
    parent = name.rsplit(".", 1)[0]
    assert parent in tr._WAITING
    assert tr._rank(name) == tr._rank(parent) > len(tr._ACTIVE)


@pytest.mark.parametrize("name", ACTIVE)
def test_working_spans_outrank_every_wait(name):
    assert tr._rank(name) <= len(tr._ACTIVE) < min(tr._rank(w) for w in WAITING)


def test_put_is_filed_with_the_transfer_it_is_part_of():
    assert tr._rank("pipeline.transfer.put") == tr._rank("pipeline.transfer")


def test_idle_gaps_name_the_hand_offs_instead_of_the_callers_wait():
    gaps = dict(tr.idle_gaps(STRETCH, n=50))
    assert sum(gaps.values()) == pytest.approx(0.9)
    # every piece of a launch has a better owner than the caller's
    # blocking wait, down to the queue hand-offs
    assert "ops.pipeline_wait" not in gaps
    assert gaps["pipeline.queue_wait.intake"] == pytest.approx(0.010 + 0.010 + 0.020)
    assert gaps["pipeline.queue_wait.linger"] == pytest.approx(0.020)   # 0.51-0.53
    assert gaps["pipeline.coalesce"] == pytest.approx(4 * 0.010)
    assert gaps["pipeline.prep"] == pytest.approx(0.030 + 0.030)
    assert gaps["pipeline.queue_wait.dispatch"] == pytest.approx(0.010 + 0.005)
    assert gaps["pipeline.transfer.put"] == pytest.approx(0.040 + 0.018)
    assert gaps["pipeline.transfer"] == pytest.approx(0.002), "what the puts leave"
    assert gaps["pipeline.dispatch"] == pytest.approx(0.018 + 0.015)
    # the device is busy 0.13-0.18 and 0.64-0.69: most of the kernel wait
    assert gaps["pipeline.device_wait.kernel"] == pytest.approx(0.010)
    assert gaps["pipeline.device_wait.readback"] == pytest.approx(0.050 + 0.010)
    # the resolver's fan-out is work: it beats the woken callers' wake
    assert gaps["pipeline.resolve"] == pytest.approx(0.040 + 0.020)
    assert gaps["ops.pipeline_wait.wake"] == pytest.approx(0.020 + 0.040)
    # between the launches nothing of the program is open
    assert gaps["no_span"] == pytest.approx(0.200 + 0.240)


def test_sixteen_less_one_new_metrics_are_declared():
    assert len(NEW) == 15, [m["name"] for m in NEW]
    assert "linger_p50_ms.lat" not in {m["name"] for m in MAN["per_layer"]}, \
        "a lone caller never lingers: nothing to read in a serial cell"


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_metric_file_reads_the_stretch(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric["name"] + ".json")) as f:
        d = json.load(f)
    base, form = metric["name"].rsplit(".", 1)
    assert metric["source"] == "program_span"
    assert metric["moves"] == {"lat": "commit_p50_ms", "thr": "sigs_per_s"}[form]
    assert metric["layer"] in ("device link", "dispatcher")
    assert readers.read(d, {"trace": STRETCH}) == pytest.approx(EXPECT[base])
    # the parent commit's program has none of the new spans: nothing is
    # reported there, but for the transfer span it already had
    parent = dict(STRETCH, spans=[s for s in STRETCH["spans"] if s[0] in (
        "ops.pipeline_wait", "pipeline.prep", "pipeline.transfer",
        "pipeline.queue_wait", "pipeline.dispatch", "pipeline.device_wait")])
    assert readers.read(d, {"trace": parent}) == (
        pytest.approx(EXPECT[base]) if base == "transfer_us_per_sig" else None)
