"""The reduction from a traced stretch to numbers: on a hand-made stretch
whose answers can be worked out on paper, and on a small stretch recorded
on the chip (benchmark/fixtures/trace_small.json)."""

import copy
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import readers, trace_reduce as tr  # noqa: E402

OPS, MODS = "XLA Ops", "XLA Modules"

# one second, one device, two requests of 100 signatures; caller thread 1,
# dispatcher thread 2
HAND = {
    "t_a": 0.0, "t_b": 1.0, "sigs": 200, "spans_recorded": 9,
    "ring_capacity": 16,
    "device_events": [
        ["/device:TPU:0", OPS, "rlc_verify_cached_g64_b64_vp256.3", 0.10, 0.10],
        ["/device:TPU:0", OPS, "rlc_verify_cached_g64_b64_vp256.4", 0.15, 0.10],
        ["/device:TPU:0", MODS, "jit_rlc_verify_cached_g64_b64_vp256(1)", 0.10, 0.15],
        ["/device:TPU:0", OPS, "fusion.1", 0.90, 0.20],   # runs past the end
        ["/device:TPU:0", MODS, "jit_other(2)", 0.90, 0.20],
    ],
    "spans": [
        ["bench.call", 0.00, 0.50, 1],
        ["bench.decode", 0.00, 0.05, 1],
        ["verify_commit", 0.05, 0.50, 1],
        ["verify_commit.prep_fused", 0.05, 0.07, 1],
        ["ops.pipeline_wait", 0.08, 0.50, 1],
        ["pipeline.dispatch", 0.09, 0.10, 2],
        ["pipeline.device_wait", 0.10, 0.30, 3],
        ["bench.wait", 0.50, 0.60, 1],
        ["bench.call", 0.60, 1.20, 1],                    # ends after t_b
    ],
}


def test_busy_and_idle_come_from_the_device_events_alone():
    assert tr.busy_seconds(HAND) == pytest.approx(0.15 + 0.10)
    assert tr.idle_share(HAND) == pytest.approx(75.0)
    no_spans = dict(HAND, spans=[])
    assert tr.idle_share(no_spans) == pytest.approx(75.0)
    assert tr.busy_seconds(dict(HAND, device_events=[])) is None


def test_kernel_time_by_jit_name():
    assert tr.device_op_seconds(HAND, MODS, "rlc_verify|epoch_coords_table") \
        == pytest.approx(0.15)
    assert tr.device_op_seconds(HAND, OPS, r"^rlc_verify") == pytest.approx(0.20)
    top = tr.top_device_ops(HAND)
    assert [n for n, _s in top] == ["rlc_verify_cached_g64_b64_vp256.3",
                                    "rlc_verify_cached_g64_b64_vp256.4",
                                    "fusion.1"]
    assert top[2][1] == pytest.approx(0.10), "clipped to the stretch"
    d = {"reader": "device_ops_per_sig",
         "params": {"line": MODS, "pattern": "rlc_verify"}}
    assert readers.read(d, {"trace": HAND}) == pytest.approx(0.15e6 / 200)


# the verify kernels' ops as the profiler names them (`%`), as the ledger's
# breakdown prints them (`_`) and bare; the ops of a launch's own reshapes
# and fusions, and a jitted step's module name, are not kernels
KERNEL_OPS = ["%rlc_verify_cached_g128_m2_b128_vp256.5",
              "_rlc_verify_cached_g5120_m8_b128_vp16384.3____s32_2048_5120__1_0",
              "_rlc_verify_g64_m2_b64.5___s32_1_64__1_0:T_1_128___custom-call_s",
              "%rlc_verify_g64_m2_b64.4",
              "%sr25519_verify_n256_b256.5",
              "_sr25519_verify_n256_b256.3____s32_256_256__1_0:T_8_128_S_1____s",
              "epoch_coords_table.2", "rlc_verify_cached_g64_b64_vp256.3"]
OTHER_OPS = ["_fusion.1___s32_256__0:T_256_S_1___fusion_s32_256__0:T_256_S_1__",
             "_reshape.16___s32_3328_2__1_0:T_8_128_S_1___reshape_s32_6656__0:",
             "%fusion.1", "%copy.8", "_while.5____s32___:T_128____s32_20_128__",
             "jit_rlc_verify_cached_g64_b64_vp256(1)", "%sr25519_prep.2",
             "_epoch_coords_patch.1"]
KERNEL_FILES = ["kernel_us_per_sig.lat", "kernel_us_per_sig.thr"]


def _kernel_metric(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", KERNEL_FILES)
@pytest.mark.parametrize("op", KERNEL_OPS + OTHER_OPS)
def test_the_kernel_pattern_takes_the_verify_kernels_alone(name, op):
    d = _kernel_metric(name)
    assert d["reader"] == "device_ops_per_sig" and d["params"]["line"] == OPS
    assert bool(re.search(d["params"]["pattern"], op)) == (op in KERNEL_OPS)


@pytest.mark.parametrize("name", KERNEL_FILES)
def test_kernel_time_a_signature_on_a_stretch_of_both_schemes(name):
    """One second, 300 signatures: every kernel op of the list once, each
    1 ms, the last running 0.5 ms past the stretch's end, and every other
    op 2 ms: the kernels' device time over the signatures."""
    n = len(KERNEL_OPS)
    events = [["/device:TPU:0", OPS, op, 0.1 * i, 0.001]
              for i, op in enumerate(KERNEL_OPS[:-1])]
    events += [["/device:TPU:0", OPS, KERNEL_OPS[-1], 0.9995, 0.001]]
    events += [["/device:TPU:0", OPS, op, 0.05 + 0.1 * i, 0.002]
               for i, op in enumerate(OTHER_OPS)]
    stretch = {"t_a": 0.0, "t_b": 1.0, "sigs": 300, "spans_recorded": 0,
               "ring_capacity": 16, "device_events": events, "spans": []}
    d = _kernel_metric(name)
    assert readers.read(d, {"trace": stretch}) == \
        pytest.approx(((n - 1) * 1.0 + 0.5) * 1e3 / 300)
    # a program whose kernels the pattern does not know reads nothing,
    # not 0.0; so does a stretch in which no kernel ran
    renamed = dict(stretch, device_events=[
        ev[:2] + ["%verify_k" + ev[2][1:]] + ev[3:] for ev in events[:n]])
    assert readers.read(d, {"trace": renamed}) is None
    late = dict(stretch, device_events=[
        ev[:3] + [ev[3] + 2.0] + ev[4:] for ev in events])
    assert readers.read(d, {"trace": late}) is None


def test_idle_gaps_are_charged_to_what_the_host_was_doing():
    gaps = dict(tr.idle_gaps(HAND))
    assert sum(gaps.values()) == pytest.approx(0.75)
    assert gaps["bench.decode"] == pytest.approx(0.05)
    assert gaps["verify_commit.prep_fused"] == pytest.approx(0.02)
    assert gaps["verify_commit"] == pytest.approx(0.01)      # 0.07-0.08
    assert gaps["pipeline.dispatch"] == pytest.approx(0.01)  # beats the waits
    # 0.25-0.30 the resolver waits on the device; 0.30-0.50 only the caller's
    # blocking wait is open; 0.08-0.09 likewise
    assert gaps["pipeline.device_wait"] == pytest.approx(0.05)
    assert gaps["ops.pipeline_wait"] == pytest.approx(0.21)
    assert gaps["bench.wait"] == pytest.approx(0.10)
    assert gaps["bench.call"] == pytest.approx(0.30)         # 0.60-0.90
    assert "no_span" not in gaps
    without_harness = dict(HAND, spans=[s for s in HAND["spans"]
                                        if not s[0].startswith("bench.")])
    assert dict(tr.idle_gaps(without_harness))["no_span"] == pytest.approx(0.45)


def test_span_readers():
    obs = {"trace": HAND}
    r = lambda reader, **p: readers.read({"reader": reader, "params": p}, obs)  # noqa: E731
    assert r("span_time_per_sig", spans=["bench.decode"]) == pytest.approx(0.05e6 / 200)
    # entry self time: 0.45 less the two spans nested in it (0.02 + 0.42)
    assert r("span_time_per_sig", spans=["verify_commit"], self_time=True) \
        == pytest.approx(0.01e6 / 200)
    # only the bench.call that ended inside the stretch counts
    assert r("span_time_per_sig", spans=["bench.call"]) == pytest.approx(0.5e6 / 200)
    assert r("span_percentile", spans=["pipeline.device_wait"], q=50) \
        == pytest.approx(200.0)
    assert r("span_percentile", spans=["no.such.span"], q=50) is None
    assert r("span_time_per_sig", spans=["no.such.span"]) is None
    assert readers.read({"reader": "device_idle_share"}, obs) == pytest.approx(75.0)
    assert readers.read({"reader": "process_cpu_per_sig"},
                        {"trace": HAND, "cpu_s": 0.4}) == pytest.approx(2000.0)


def test_counter_and_setup_readers():
    obs = {"counters": {"before": {"sigs": 100, "launches": 2, "hits": 5, "miss": 1},
                        "after": {"sigs": 1100, "launches": 6, "hits": 15, "miss": 1,
                                  "h2d": 30000.0, "per": 150}},
           "setup": {"compile_s": 4.5, "compiles_in_window": 0}}
    r = lambda reader, **p: readers.read({"reader": reader, "params": p}, obs)  # noqa: E731
    assert r("counter_delta_ratio", num=["sigs"], den=["launches"]) == 250.0
    assert r("counter_delta_ratio", num=["hits"], den=["hits", "miss"],
             scale=100.0) == 100.0
    assert r("counter_delta_ratio", num=["sigs"], den=["miss"]) is None
    assert r("gauge_per_sig", gauge="h2d", per="per") == 200.0
    assert r("setup_field", field="compile_s") == 4.5
    assert r("setup_field", field="compiles_in_window") == 0
    assert r("setup_field", field="absent") is None


def test_a_wrapped_ring_is_reported_not_reduced():
    wrapped = dict(copy.deepcopy(HAND), spans_recorded=17)
    assert tr.ring_wrapped(wrapped) and not tr.ring_wrapped(HAND)
    obs = {"trace": wrapped}
    for d in ({"reader": "span_time_per_sig", "params": {"spans": ["bench.decode"]}},
              {"reader": "span_percentile",
               "params": {"spans": ["pipeline.device_wait"], "q": 50}}):
        assert readers.read(d, obs) is None
    # what does not read the ring is still reported
    assert readers.read({"reader": "device_idle_share"}, obs) == pytest.approx(75.0)


def test_the_tracers_ring_reports_how_much_it_dropped():
    """The count the harness compares with the ring's capacity is the
    program's own: recorded_total keeps rising when the ring wraps."""
    from tendermint_tpu.observability.trace import SpanTracer

    t = SpanTracer(capacity=16)
    t.configure(enabled=True)
    for i in range(40):
        t.record("x", float(i), i + 0.5)
    assert t.recorded_total == 40 and len(t.events()) == 16 == t.capacity
    assert tr.ring_wrapped({"spans_recorded": t.recorded_total,
                            "ring_capacity": t.capacity})
    t.clear()
    assert t.recorded_total == 0
