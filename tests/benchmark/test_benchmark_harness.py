"""The benchmark's harness on the CPU: every file BENCHMARK.json names
loads by name, a cell a later PR would add as new files loads and runs
over a driver that needs no JAX, the end-to-end arithmetic counts what it
says, and the command refuses to run without the chip."""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import generators, readers, run, spec, stats  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MAN = spec.manifest(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


# -- every named file loads ----------------------------------------------------


@pytest.mark.parametrize("workload", [w["name"] for w in MAN["workloads"]])
def test_workload_loads_by_name(workload):
    cell = spec.load_cell(ROOT, workload)
    assert cell.chips == 1
    assert cell.config["name"] in workload and cell.traffic["name"] in workload
    assert cell.traffic["generator"]["kind"] in ("closed_loop", "open_loop")
    assert hasattr(cell.driver, "open")
    assert cell.traffic["settle_s"] > 0
    names = {m["name"] for m, _d in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_deployment(cfg):
    with open(os.path.join(ROOT, cfg["file"])) as f:
        doc = json.load(f)
    assert doc["name"] == cfg["name"] and doc["source"] == cfg["source"]
    assert doc["reduced"] == cfg["reduced"] == []
    for key in ("validators", "voting_power", "pool_commits", "guarantees",
                "layout", "size", "assumed", "chain_id"):
        assert key in doc, key
    callers = max(
        spec.load_cell(ROOT, w["name"]).traffic["generator"]["callers"]
        for w in MAN["workloads"] if w["config"] == cfg["name"])
    assert doc["pool_commits"] >= 2 * callers, "each caller walks distinct commits"


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_file_names_a_reader(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric["name"] + ".json")) as f:
        d = json.load(f)
    assert d["reader"] in readers.READERS
    # with nothing observed a reader returns nothing — and takes its params
    assert readers.read(d, {}) is None
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    moved = e2e[metric["moves"]]
    cells = metric.get("workloads") or [w["name"] for w in MAN["workloads"]]
    assert all(spec.applies(moved, c) for c in cells), \
        "a per-layer metric is reported only where the metric it moves is"
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    if metric["layer"] in ("kernels", "device"):
        assert metric["source"] == "device_trace"


def test_manifest_meets_the_contract():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    every = MAN["configs"] + MAN["workloads"] + MAN["end_to_end"] + MAN["per_layer"]
    assert all(NAME.match(e["name"]) for e in every)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MAN[group]]
        assert len(names) == len(set(names)), group
    metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert all(re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
               for m in MAN["end_to_end"] + MAN["per_layer"])
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in MAN["configs"]} == {w["config"] for w in MAN["workloads"]}
    assert all(len(w["why"]) <= 200 for w in MAN["workloads"] + MAN["configs"])
    assert all(len(c["source"]) <= 200 for c in MAN["configs"])
    for w in MAN["workloads"]:
        reported = [m["name"] for m in MAN["end_to_end"] if spec.applies(m, w["name"])]
        assert "setup_s" in reported and len(reported) >= 2, w["name"]
    # nothing of a cell, a config or a metric is named in the harness's code
    code = "".join(open(os.path.join(ROOT, "benchmark", f)).read()
                   for f in os.listdir(os.path.join(ROOT, "benchmark"))
                   if f.endswith(".py"))
    for e in MAN["workloads"] + MAN["end_to_end"][:-1]:
        assert e["name"] not in code, e["name"]


# -- a cell added as new files plus entries, and rehearsed ----------------------


@pytest.fixture(scope="module")
def grown_root(tmp_path_factory):
    """A checkout to which a later PR added a configuration, two traffic
    mixes, a driver and a per-layer metric: new files and new entries,
    no edit of a file that was there."""
    root = str(tmp_path_factory.mktemp("grown"))
    bdir = os.path.join(root, "benchmark")
    for folder in ("configs", "traffic", "drivers", "layer_metrics", "end_to_end"):
        shutil.copytree(os.path.join(ROOT, "benchmark", folder),
                        os.path.join(bdir, folder))
        extra = os.path.join(HERE, "fixture_cell", folder)
        for f in os.listdir(extra) if os.path.isdir(extra) else ():
            assert not os.path.exists(os.path.join(bdir, folder, f))
            shutil.copy(os.path.join(extra, f), os.path.join(bdir, folder, f))
    man = json.loads(json.dumps(MAN))
    man["configs"].append({"name": "fx", "source": "tests", "reduced": [],
                           "file": "benchmark/configs/fx.json", "why": "fixture"})
    for traffic in ("fx_closed", "fx_open"):
        man["workloads"].append({"name": f"fx-{traffic}", "config": "fx",
                                 "traffic": traffic, "chips": 1, "why": "fixture"})
    cells = ["fx-fx_closed", "fx-fx_open"]
    for m in man["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + cells
    man["per_layer"].append({
        "name": "fx_calls_per_launch", "unit": "sigs/launch", "better": "higher",
        "source": "program_counter", "layer": "dispatcher",
        "moves": "sigs_per_s", "workloads": cells})
    for m in man["per_layer"]:
        if m["name"] in ("device_idle_share.thr", "decode_us_per_sig.thr",
                         "tail_p95_ms.lat"):
            m["workloads"] = m["workloads"] + cells
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_a_later_pr_adds_a_cell_as_files_and_entries(grown_root):
    cell = spec.load_cell(grown_root, "fx-fx_open")
    assert cell.config["service_ms"] == 4
    assert cell.driver.__name__.endswith("synthetic")
    assert "fx_calls_per_launch" in [m["name"] for m, _d in cell.per_layer]
    # and the cells that were there still load from the grown checkout
    assert spec.load_cell(grown_root, MAN["workloads"][0]["name"]).per_layer
    with pytest.raises(spec.SpecError):
        spec.load_cell(grown_root, "no-such-cell")


def _sibling(name):
    """A test module of this directory, loaded on its own: its pins."""
    s = importlib.util.spec_from_file_location(
        f"_pins_{name}", os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_the_pins_of_what_stands_take_a_later_prs_additions(grown_root, tmp_path):
    """What the next PR that brings a metric or a cell does to the
    manifest: a per-layer metric after the last entry (grown_root's), and
    a cell appended to the `workloads` of the prep stage metrics and of
    kernel_us_per_sig.lat. The tests that hold what the benchmark had pass
    on that copy, and still fail where an entry is put before one of
    theirs or a cell is taken from a list."""
    prep = _sibling("test_benchmark_prep_metrics")
    sr = _sibling("test_benchmark_sr25519")
    root = str(tmp_path / "next")
    shutil.copytree(grown_root, root)
    man = spec.manifest(root)
    assert man["per_layer"][-1]["name"] == "fx_calls_per_launch"
    added = {"prep_native_us_per_sig.lat": "fx-fx_closed",
             "kernel_us_per_sig.lat": "fx-fx_closed",
             "gil_wait_us_per_sig.lat": "sr150-lastcommit1"}
    for m in man["per_layer"]:
        if m["name"] in added:
            m["workloads"] = m["workloads"] + [added[m["name"]]]

    def write(manifest):
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(manifest, f)

    write(man)
    prep.pin_thirteen(spec.manifest(root))
    sr.pin_the_cell(root)
    names = {m["name"] for m, _d in spec.load_cell(root, "fx-fx_closed").per_layer}
    assert {"prep_native_us_per_sig.lat", "kernel_us_per_sig.lat",
            "fx_calls_per_launch"} <= names
    assert "gil_wait_us_per_sig.lat" in {
        m["name"] for m, _d in spec.load_cell(root, sr.CELL).per_layer}

    before = json.loads(json.dumps(man))
    before["per_layer"].insert(prep.FIRST, before["per_layer"].pop())
    with pytest.raises(AssertionError):
        prep.pin_thirteen(before)
    fewer = json.loads(json.dumps(man))
    fewer["per_layer"][prep.FIRST + 1]["workloads"].pop(0)
    with pytest.raises(AssertionError):
        prep.pin_thirteen(fewer)
    for m in man["per_layer"]:
        if m["name"] == "kernel_us_per_sig.lat":
            m["workloads"].remove(sr.CELL)
    write(man)
    with pytest.raises(AssertionError):
        sr.pin_the_cell(root)


@pytest.mark.parametrize("traffic", ["fx_closed", "fx_open"])
@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_rehearsal_prints_the_contracts_keys(grown_root, traffic, trace, capsys):
    lines = []
    res = run.run_cell(grown_root, f"fx-{traffic}", 2 ** 31 + 11, 0.6, trace,
                       started=time.time(), say=lines.append)
    assert set(res) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 20
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    json.dumps(res)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], (int, float))
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
        assert res["metrics"]["fx_calls_per_launch"]["value"] == 10.0
        assert 40 < res["metrics"]["device_idle_share.thr"]["value"] < 99
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert res["breakdown"]["device_ops"][0][0] == "syn_kernel"
        assert "commit_p50_ms" not in res["metrics"]
        assert res["metrics"]["tail_p95_ms.lat"]["value"] >= 4.0
    else:
        assert set(res["metrics"]) == {"commit_p50_ms", "sigs_per_s", "setup_s"}
        assert 4.0 <= res["metrics"]["commit_p50_ms"]["value"] < 50
        assert res["metrics"]["setup_s"]["value"] > 0
    if traffic == "fx_open":
        assert any("lateness" in ln for ln in lines)


def test_a_failed_check_fails_correct(grown_root):
    with open(os.path.join(grown_root, "benchmark", "configs", "fx_bad.json"), "w") as f:
        json.dump({"name": "fx_bad", "validators": 10, "pool_commits": 8,
                   "service_ms": 2, "check_says": ["forged#3: accepted"]}, f)
    man = spec.manifest(grown_root)
    man["configs"].append({"name": "fx_bad", "file": "benchmark/configs/fx_bad.json"})
    man["workloads"].append({"name": "fx_bad-closed", "config": "fx_bad",
                             "traffic": "fx_closed", "chips": 1})
    for m in man["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("fx_bad-closed")
    with open(os.path.join(grown_root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    res = run.run_cell(grown_root, "fx_bad-closed", 1, 0.2, False,
                       started=0.0, say=lambda m: None)
    assert res["correct"] is False and res["failed"] == 0


# -- the end-to-end arithmetic ---------------------------------------------------


def test_rate_counts_requests_completed_inside_the_window():
    # three callers of unequal speed, 100 signatures a request, window
    # [10, 20]: no rounds, no barrier; the request that ends at 20.4 and
    # the one that ended at 9.9 are outside, the failed one counts nothing
    recs = [(s, s, e, ok, 100 if ok else 0) for s, e, ok in [
        (9.5, 9.9, True), (9.8, 10.2, True), (10.2, 13.0, True),
        (13.0, 19.99, True), (10.0, 15.0, True), (15.0, 20.0, True),
        (19.0, 20.4, True), (11.0, 12.0, False)]]
    win = stats.in_window(recs, 10.0, 20.0)
    assert len(win) == 6
    assert stats.completed_per_s(win, 10.0) == 5 * 100 / 10.0
    # one more commit completed moves the rate by one commit, not a round
    more = win + [(19.0, 19.0, 19.5, True, 100)]
    assert stats.completed_per_s(more, 10.0) - stats.completed_per_s(win, 10.0) == 10.0


def test_percentiles_are_over_all_samples():
    lat = [(0.0, 0.0, ms / 1e3, True, 1) for ms in range(1, 101)]
    assert stats.latency_percentile(lat, 50) == pytest.approx(50.5)
    assert stats.latency_percentile(lat, 95) == pytest.approx(95.05)


def test_closed_loop_callers_run_independently():
    def request(i):     # each caller's speed follows its slice of the pool
        time.sleep(0.002 if i % 2 == 0 else 0.02)
        return 7 if i % 2 == 0 else 9

    traffic = {"generator": {"kind": "closed_loop", "callers": 2}}
    t0, recs, errs = generators.run(traffic, request, 8, 0.3)
    assert not errs
    fast = [r for r in recs if r[4] == 7]
    slow = [r for r in recs if r[4] == 9]
    assert len(fast) > 2 * len(slow) > 0, "no barrier holds the fast caller back"
    assert all(r[0] == r[1] for r in recs), "closed loop: due == start"
    assert all(r[1] >= t0 for r in recs)


def test_open_loop_times_from_the_due_instant_and_reports_lateness():
    spans = []

    def request(i):
        time.sleep(0.03 if i == 0 else 0.001)   # the first request stalls
        return 1

    traffic = {"generator": {"kind": "open_loop", "rate": 100, "burst": 1,
                             "workers": 1}}
    t0, recs, _ = generators.run(traffic, request, 4, 0.2,
                                 lambda n, s, e: spans.append(n))
    recs.sort(key=lambda r: r[0])
    assert [round((r[0] - t0) * 100) for r in recs[:4]] == [0, 1, 2, 3]
    # the second request was due at 10 ms but could start only after the
    # stall: its latency counts the wait, its lateness reports it
    assert recs[1][1] - recs[1][0] > 0.015
    assert recs[1][2] - recs[1][0] > 0.02
    assert stats.lateness_ms(recs)["max"] > 15
    assert "bench.wait" in spans and "bench.call" in spans
    assert 15 <= len(recs) <= 20


def test_open_loop_bursts_share_a_due_instant():
    traffic = {"generator": {"kind": "open_loop", "rate": 100, "burst": 5,
                             "workers": 5}}
    t0, recs, _ = generators.run(traffic, lambda i: 1, 4, 0.2)
    dues = sorted({round((r[0] - t0) * 1e3) for r in recs})
    assert dues == [0, 50, 100, 150] and len(recs) == 20


# -- no chip, no number ----------------------------------------------------------


def test_the_command_refuses_to_run_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         MAN["workloads"][0]["name"], "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "chip" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    assert '"metrics"' not in p.stdout
