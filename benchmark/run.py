#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: set-up (data from the seed, every shape
the cell can meet, a settle on the cell's own traffic), then the measured
window, then the correctness check outside the clock. The last line of
standard output is the result as one JSON object; --trace 0 reports the
cell's end-to-end metrics, --trace 1 its per-layer metrics from a traced
stretch at the end of the window. Without the chips the cell asks for it
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_T_ENV = "BENCH_PROCESS_START"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_STRETCH_S = 5.0      # the tracer's ring holds 16 384 spans
MAX_SETTLES = 3


def _fix_interpreter() -> float:
    """One interpreter state for every run: re-execute once, before
    anything is imported, with a fixed hash seed. Returns the wall-clock
    time the first process started."""
    if os.environ.get("PYTHONHASHSEED") != "0":
        env = dict(os.environ, PYTHONHASHSEED="0")
        env.setdefault(_T_ENV, repr(time.time()))
        os.execve(sys.executable, [sys.executable] + sys.argv, env)
    return float(os.environ.get(_T_ENV) or time.time())


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             started: float, say=None) -> dict:
    """Runs one cell and returns the result object."""
    from benchmark import generators, spec, stats

    t_first = time.perf_counter()

    def _say(msg: str) -> None:
        print(f"[{time.perf_counter() - t_first:7.2f}s] {msg}", flush=True)

    say = say or _say
    cell = spec.load_cell(root, workload)
    traffic = cell.traffic
    say(f"cell {cell.name}: config {cell.config['name']}, traffic "
        f"{traffic['name']} ({json.dumps(traffic['generator'])}), seed {seed}, "
        f"{seconds}s, trace {int(trace)}")
    session = cell.driver.open(cell.config, seed, root, cell.chips, say)
    try:
        session.warm(traffic, say)
        for attempt in range(1, MAX_SETTLES + 1):
            n0 = session.compiles()
            _t0, recs, errs = generators.run(
                traffic, session.request, session.n_pool, traffic["settle_s"])
            new = session.compiles() - n0
            say(f"settle {attempt}: {len(recs)} requests in "
                f"{traffic['settle_s']}s, {len(errs)} failed, {new} program(s) "
                f"compiled")
            if not new:
                break
        gc.collect()
        gc.freeze()

        timers, out_dir = [], os.path.join(root, ".bench_cache", "trace")
        if trace:
            stretch = min(TRACE_STRETCH_S, seconds / 2)
            timers = [(seconds - stretch, lambda: session.trace_start(out_dir)),
                      (seconds, session.trace_mark_end)]
        n0 = session.compiles()
        t0, records, errors = generators.run(
            traffic, session.request, session.n_pool, seconds,
            session.record_span, timers)
        setup_s = time.time() - (time.perf_counter() - t0) - started
        obs = session.trace_stop() if trace else {}
        obs["setup"] = dict(session.setup,
                            compiles_in_window=session.compiles() - n0)

        window = stats.in_window(records, t0, t0 + seconds)
        if not window:
            raise SystemExit(f"no request completed inside the {seconds}s "
                             "window: no result")
        failed = sum(1 for r in window if not r[3])
        for e in errors[:5]:
            say(f"a request failed: {e}")
        say(f"window: {len(window)} requests completed inside {seconds}s "
            f"({len(records) - len(window)} ended after it), {failed} failed; "
            f"compiles in the window: {obs['setup']['compiles_in_window']}")
        if traffic["generator"]["kind"] == "open_loop":
            late = stats.lateness_ms(window)
            say(f"generator lateness ms: p50 {late['p50']:.3f} "
                f"p95 {late['p95']:.3f} max {late['max']:.3f}")
        c = session.counters()
        if c.get("launch_capacity"):
            dev = c["sigs_verified_device"]
            say(f"since start: {dev} signatures on the device, "
                f"{c['sigs_verified_host']} on the host, {c['launches']} "
                f"launches, pad waste "
                f"{100 * (1 - dev / c['launch_capacity']):.1f}% of launched "
                f"capacity")
        bad = session.check()
        for b in bad:
            say(f"CHECK FAILED: {b}")
        device = session.device()
    finally:
        session.close()

    result = {"correct": not failed and not bad, "attempted": len(window),
              "failed": failed, "metrics": {}, "device": device}
    if trace:
        obs["trace"]["sigs"] = sum(
            r[4] for r in stats.in_window(records, obs["trace"]["t_a"],
                                          obs["trace"]["t_b"]) if r[3])
        _per_layer(cell, obs, result, out_dir, say)
    else:
        for entry, definition in cell.end_to_end:
            result["metrics"][entry["name"]] = {
                "value": stats.end_to_end(definition, window, seconds, setup_s),
                "unit": entry["unit"]}
    return result


def _per_layer(cell, obs: dict, result: dict, out_dir: str, say) -> None:
    """Fills a traced run's result: the cell's per-layer metrics, the
    device's busy time, the breakdown."""
    from benchmark import readers, trace_reduce

    tr = obs["trace"]
    # the stretch as reduced, for whoever reads this checkout next (the
    # form fixtures/trace_small.json was cut from)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell.name}.stretch.json"), "w") as f:
        json.dump(tr, f)
    say(f"traced stretch: {tr['t_b'] - tr['t_a']:.3f}s, {tr['sigs']} "
        f"signatures, {tr['spans_recorded']} spans "
        f"(ring {tr['ring_capacity']}), {len(tr['device_events'])} device "
        f"events; {json.dumps(obs['notes'])}")
    if trace_reduce.ring_wrapped(tr):
        say("the tracer's ring wrapped inside the stretch: the metrics read "
            "from spans are left out")
    busy = trace_reduce.busy_seconds(tr)
    if not busy:
        raise SystemExit("the traced stretch holds no device operation: "
                         "no per-layer result")
    for entry, definition in cell.per_layer:
        value = readers.read(definition, obs)
        if value is not None:
            result["metrics"][entry["name"]] = {"value": value,
                                                "unit": entry["unit"]}
    result["device"].update(busy_s=busy, window_s=tr["t_b"] - tr["t_a"])
    result["breakdown"] = {
        "device_ops": [list(x) for x in trace_reduce.top_device_ops(tr)],
        "idle_gaps": [list(x) for x in trace_reduce.idle_gaps(tr)]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    started = _fix_interpreter()
    sys.path.insert(0, ROOT)
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), started)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
