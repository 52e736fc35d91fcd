#!/usr/bin/env python3
"""What many callers cost one another on the host side of verify_commit,
measured without a device.

`--callers` threads each run a closed loop over `hub150`'s pool of signed
commits (benchmark/data.py, the benchmark's own wire bytes):

    Commit.decode(wire)            native decode, gives the GIL up once
    commit.validate_basic()        Python
    commit_prep.prep_commit_from   the fused native prep + the EntryBlock
    time.sleep(--sleep-ms)         the pipeline the caller would wait on

and the probe prints the commits a second they complete together and what
`tm_native.gil_stats()` rose by: per timed entry the sections that gave
the GIL up, the seconds they ran without it, the seconds their threads
then waited to win it back, and the sections that kept it. A lone thread
at `--sleep-ms 0` gives the interpreter's ceiling; 32 callers at 16 ms are
`hub150-sync32`'s callers (PERF.md §6, PR 38). A change to the callers'
path can be sized here before it asks for chip time; a rate, an idle share
or a latency of the system itself comes only from the chip.

Usage:
    python tools/gil_probe.py [--callers 32] [--sleep-ms 16] [--seconds 5]
                              [--seed 1] [--root <checkout>]
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")    # the probe never takes a chip

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def probe(callers: int, sleep_s: float, seconds: float, seed: int,
          root: str) -> dict:
    from benchmark import data
    from tendermint_tpu import native
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import commit_prep
    from tendermint_tpu.types import Validator, ValidatorSet
    from tendermint_tpu.types.block import Commit

    if native.load() is None:
        raise SystemExit("gil_probe: tm_native did not build; there is no "
                         "native section to probe")
    with open(os.path.join(ROOT, "benchmark", "configs", "hub150.json")) as f:
        pool = data.pool(root, json.load(f), seed)
    vals = ValidatorSet.new([
        Validator.new(ed25519.PubKey(bytes(p)), pool.power)
        for p in pool.pubkeys])
    needed = vals.total_voting_power() * 2 // 3
    mode = commit_prep.MODE_COUNT_FOR_BLOCK       # verify_commit's
    done = [0] * callers
    go, stop = threading.Barrier(callers + 1), threading.Event()

    def one(wire):
        commit = Commit.decode(wire)
        commit.validate_basic()
        _sel, _tallied, block = commit_prep.prep_commit_from(
            commit, vals, pool.chain_id, needed, mode)
        if block is None or len(block) != pool.n_validators:
            raise RuntimeError("the fused prep refused an honest commit")

    one(pool.commits[0])      # the lazy imports and the set's columns

    def caller(k):
        wires, n, i = pool.commits, 0, k
        go.wait()
        while not stop.is_set():
            one(wires[i % len(wires)])
            n += 1
            i += callers
            if sleep_s:
                time.sleep(sleep_s)
        done[k] = n

    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(callers)]
    for t in threads:
        t.start()
    before = native.gil_stats()
    go.wait()
    t0 = time.perf_counter()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    after = native.gil_stats()
    commits = sum(done)
    gil = {}
    for entry, (sections, free_s, wait_s, held) in after.items():
        b = before[entry]
        if sections - b[0] or held - b[3]:
            gil[entry] = {"released": sections - b[0], "held": held - b[3],
                          "free_s": free_s - b[1], "wait_s": wait_s - b[2]}
    return {"callers": callers, "sleep_ms": sleep_s * 1e3, "seconds": wall,
            "commits": commits, "commits_per_s": commits / wall,
            "sigs_per_commit": pool.n_validators, "gil": gil}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--callers", type=int, default=32)
    ap.add_argument("--sleep-ms", type=float, default=16.0)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--root", default=ROOT,
                    help="where the pool is kept (<root>/.bench_cache/pool)")
    args = ap.parse_args(argv)
    if args.callers < 1 or args.seconds <= 0 or args.sleep_ms < 0:
        ap.error("callers >= 1, seconds > 0, sleep-ms >= 0")
    out = probe(args.callers, args.sleep_ms / 1e3, args.seconds, args.seed,
                args.root)
    print(f"{out['callers']} callers, {out['sleep_ms']:g} ms of sleep a "
          f"commit, {out['seconds']:.2f} s: {out['commits']} commits, "
          f"{out['commits_per_s']:.1f} commits/s")
    for entry, g in out["gil"].items():
        per = g["wait_s"] / max(out["commits"], 1) * 1e3
        print(f"  {entry}: {g['released']} sections gave the GIL up "
              f"({g['free_s']:.3f} s without it, {g['wait_s']:.3f} s waiting "
              f"to win it back: {per:.3f} ms a commit), {g['held']} held it")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
