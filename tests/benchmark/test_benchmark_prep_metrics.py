"""The per-layer metrics that read host prep's stages and the waits to win
the GIL back (PR 37): every new metric file loads and reads a small
hand-made stretch whose answers can be worked out on paper, reports
nothing from a program that lacks the spans, and the gap attribution
charges an idle device to the stage that was open."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import readers, spec, trace_reduce as tr  # noqa: E402

MAN = spec.manifest(ROOT)
OPS = "XLA Ops"

# one second, two commits of 100 signatures, one launch each. Threads:
# callers 1 and 5, coalescer 2. Caller 5 loses the GIL after its decode
# and after the third section of its fused prep; the coalescer after the
# RLC prep of launch B.
STRETCH = {
    "t_a": 0.0, "t_b": 1.0, "sigs": 200, "spans_recorded": 45,
    "ring_capacity": 262144,
    "device_events": [["/device:TPU:0", OPS, "%rlc_verify_cached.5", 0.200, 0.050],
                      ["/device:TPU:0", OPS, "%rlc_verify_cached.5", 0.800, 0.050]],
    "spans": [
        # commit A, caller 1
        ["bench.call", 0.000, 0.300, 1],
        ["bench.decode", 0.000, 0.010, 1],
        ["wire.columns.native", 0.001, 0.004, 1],
        ["wire.columns.gil", 0.004, 0.005, 1],
        ["verify_commit", 0.010, 0.300, 1],
        ["verify_commit.prep_fused", 0.010, 0.060, 1],
        ["ops.commit_prep.columns", 0.011, 0.020, 1],
        ["ops.commit_prep.native", 0.021, 0.022, 1],
        ["ops.commit_prep.gil", 0.022, 0.023, 1],
        ["ops.commit_prep.native", 0.024, 0.025, 1],
        ["ops.commit_prep.gil", 0.025, 0.025, 1],
        ["ops.commit_prep.native", 0.026, 0.036, 1],
        ["ops.commit_prep.gil", 0.036, 0.038, 1],
        ["ops.commit_prep.block", 0.040, 0.059, 1],
        ["epoch.map_set", 0.045, 0.050, 1],
        ["ops.pipeline_wait", 0.060, 0.300, 1],
        # launch A, coalescer
        ["pipeline.coalesce", 0.065, 0.180, 2],
        ["pipeline.prep", 0.070, 0.170, 2],
        ["ops.rlc_prep.pack", 0.072, 0.082, 2],
        ["ops.rlc_prep.z", 0.083, 0.088, 2],
        ["ops.rlc_prep.native", 0.090, 0.140, 2],
        ["ops.rlc_prep.gil", 0.140, 0.142, 2],
        ["ops.rlc_prep.fill", 0.144, 0.168, 2],
        # commit B, caller 5
        ["bench.call", 0.500, 0.900, 5],
        ["bench.decode", 0.500, 0.530, 5],
        ["wire.columns.native", 0.501, 0.504, 5],
        ["wire.columns.gil", 0.504, 0.524, 5],
        ["verify_commit", 0.530, 0.900, 5],
        ["verify_commit.prep_fused", 0.530, 0.640, 5],
        ["ops.commit_prep.columns", 0.531, 0.541, 5],
        ["ops.commit_prep.native", 0.542, 0.543, 5],
        ["ops.commit_prep.gil", 0.543, 0.543, 5],
        ["ops.commit_prep.native", 0.544, 0.545, 5],
        ["ops.commit_prep.gil", 0.545, 0.545, 5],
        ["ops.commit_prep.native", 0.546, 0.556, 5],
        ["ops.commit_prep.gil", 0.556, 0.616, 5],
        ["ops.commit_prep.block", 0.618, 0.638, 5],
        ["ops.pipeline_wait", 0.640, 0.900, 5],
        # launch B, coalescer
        ["pipeline.coalesce", 0.645, 0.790, 2],
        ["pipeline.prep", 0.650, 0.780, 2],
        ["ops.rlc_prep.pack", 0.652, 0.662, 2],
        ["ops.rlc_prep.z", 0.663, 0.670, 2],
        ["ops.rlc_prep.native", 0.672, 0.722, 2],
        ["ops.rlc_prep.gil", 0.722, 0.752, 2],
        ["ops.rlc_prep.fill", 0.754, 0.778, 2],
    ],
}

# the ten waits for the GIL, ms, in rising order
GIL_MS = [0.0, 0.0, 0.0, 1.0, 1.0, 2.0, 2.0, 20.0, 30.0, 60.0]

# metric base name -> (layer, what the stretch above reads, by hand)
EXPECT = {
    "gil_wait_us_per_sig": ("host all threads", sum(GIL_MS) * 1e3 / 200),
    # rank 0.95 x 9 = 8.55: 0.55 of the way from 30 ms to 60 ms
    "gil_wait_p95_ms": ("host all threads", 46.5),
    # the fused prep's sections 1 + 1 + 10 a commit, the RLC prep 50 a launch
    "prep_native_us_per_sig": ("host prep", (0.012 + 0.012 + 0.050 + 0.050) * 1e6 / 200),
    # A: columns 9 + block 19 less the 5 of epoch.map_set; B: 10 + 20
    "prep_caller_python_us_per_sig": ("host prep", (0.023 + 0.030) * 1e6 / 200),
    "prep_pack_us_per_sig": ("host prep", (0.010 + 0.010) * 1e6 / 200),
    "prep_z_us_per_sig": ("host prep", (0.005 + 0.007) * 1e6 / 200),
    "prep_fill_us_per_sig": ("host prep", (0.024 + 0.024) * 1e6 / 200),
}

# the thirteen stage and GIL-wait metrics, by name and in their order, and the
# index of the first in `per_layer`: later entries come after the last
THIRTEEN = ["gil_wait_us_per_sig.lat", "gil_wait_us_per_sig.thr",
            "gil_wait_p95_ms.thr",
            "prep_native_us_per_sig.lat", "prep_native_us_per_sig.thr",
            "prep_caller_python_us_per_sig.lat",
            "prep_caller_python_us_per_sig.thr",
            "prep_pack_us_per_sig.lat", "prep_pack_us_per_sig.thr",
            "prep_z_us_per_sig.lat", "prep_z_us_per_sig.thr",
            "prep_fill_us_per_sig.lat", "prep_fill_us_per_sig.thr"]
FIRST = 54
NEW = [m for name in THIRTEEN for m in MAN["per_layer"] if m["name"] == name]
LAT = ["hub150-serial1", "max10k-serial1", "churn100-seq1", "bisect100-catchup1"]
THR = ["max10k-stream8", "hub150-sync32"]


def pin_thirteen(man):
    """What the benchmark had stands where it stood: the thirteen as one
    run at their index, each on at least the cells it was declared for
    (a later PR appends cells to a list and entries after the last)."""
    at = man["per_layer"][FIRST:FIRST + len(THIRTEEN)]
    assert [m["name"] for m in at] == THIRTEEN
    for m in at:
        want = {"lat": LAT, "thr": THR}[m["name"].rsplit(".", 1)[1]]
        assert m["workloads"][:len(want)] == want, m["name"]


def test_the_stretch_holds_what_its_comments_say():
    waits = sorted(round((s[2] - s[1]) * 1e3, 6) for s in STRETCH["spans"]
                   if s[0].endswith(".gil"))
    assert waits == GIL_MS
    assert len(STRETCH["spans"]) == STRETCH["spans_recorded"]


def test_thirteen_new_metrics_are_declared_each_where_it_has_something_to_read():
    assert len(NEW) == 13, [m["name"] for m in NEW]
    assert "gil_wait_p95_ms.lat" not in {m["name"] for m in MAN["per_layer"]}, \
        "a lone caller has no one to lose the GIL to"
    pin_thirteen(MAN)


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_metric_file_reads_the_stretch(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric["name"] + ".json")) as f:
        d = json.load(f)
    base, form = metric["name"].rsplit(".", 1)
    layer, value = EXPECT[base]
    assert metric["source"] == "program_span" and metric["better"] == "lower"
    assert metric["moves"] == {"lat": "commit_p50_ms", "thr": "sigs_per_s"}[form]
    assert metric["layer"] == layer
    assert readers.read(d, {"trace": STRETCH}) == pytest.approx(value)
    # the parent commit's program has none of the new spans: the reader
    # finds nothing, raises nothing, and the line leaves the metric out
    old = ("bench.", "verify_commit", "pipeline.", "ops.pipeline_wait", "epoch.")
    parent = dict(STRETCH, spans=[s for s in STRETCH["spans"]
                                  if s[0].startswith(old)])
    assert len(parent["spans"]) == 15
    assert readers.read(d, {"trace": parent}) is None
    # and a ring that wrapped reports no span metric
    assert readers.read(d, {"trace": dict(STRETCH, spans_recorded=262145)}) is None


def test_the_stages_leave_their_parents_little_self_time():
    """What the coverage criterion of PR 37 computes over a chip run's
    stretch: a parent's self time is the span less the spans nested in it."""
    for parent, self_s in [("verify_commit.prep_fused", 0.007 + 0.008),
                           ("pipeline.prep", 0.009 + 0.009),
                           ("bench.decode", 0.006 + 0.007)]:
        assert tr.span_seconds(STRETCH, [parent], True) == pytest.approx(self_s)


def test_idle_gaps_name_the_stage_that_was_open():
    gaps = dict(tr.idle_gaps(STRETCH, n=50))
    assert sum(gaps.values()) == pytest.approx(0.9)
    # the stages are `ops.` names: active work, ahead of their parents
    assert tr._rank("ops.rlc_prep.gil") == tr._rank("ops.commit_prep.columns") \
        < tr._rank("verify_commit.prep_fused")
    assert gaps["ops.rlc_prep.native"] == pytest.approx(0.050 + 0.050)
    assert gaps["ops.rlc_prep.gil"] == pytest.approx(0.002 + 0.030)
    assert gaps["ops.rlc_prep.fill"] == pytest.approx(0.024 + 0.024)
    assert gaps["ops.commit_prep.gil"] == pytest.approx(0.001 + 0.002 + 0.060)
    assert gaps["ops.commit_prep.block"] == pytest.approx(0.014 + 0.020)
    assert gaps["epoch.map_set"] == pytest.approx(0.005)
    assert gaps["wire.columns.gil"] == pytest.approx(0.001 + 0.020)
    # what the stages leave of their parents
    assert gaps["pipeline.prep"] == pytest.approx(0.009 + 0.009)
    assert gaps["verify_commit.prep_fused"] == pytest.approx(0.007 + 0.008)
    assert gaps["bench.decode"] == pytest.approx(0.006 + 0.007)
