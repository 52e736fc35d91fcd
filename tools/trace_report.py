#!/usr/bin/env python
"""Summarize (and merge) Chrome-trace dumps from the span tracer.

Usage:
    python tools/trace_report.py <trace.json> [--json] [--top N]
    python tools/trace_report.py --merge a.json b.json ... \\
        [--out merged.json] [--json] [--top N]

Inputs are Trace Event Format files — what `/dump_trace` returns under
"trace", what the node's OnStop flush writes to
instrumentation.trace_dump_path, what `tools/simnet_run.py --trace`
exports (already merged per cluster), or any hand-rolled
observability.trace.TRACER.dump() output. Prints a per-span table
(count, total, p50/p95/p99 ms, sorted by total ms — `--top N` keeps the
N heaviest rows) plus the wall-clock extent and the flow-chain count; --json emits the same summary as one JSON object.

`--merge` (ISSUE 10) re-keys pids and concatenates several documents
into one (written to `--out` when given) before summarizing — the
offline path to a single cluster-wide Perfetto view when per-node traces
were dumped separately; flow ids are preserved so cross-file causal
chains stay linked.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.observability.trace import (  # noqa: E402
    dump_doc,
    flow_chains,
    merge_traces,
    summarize_events,
)


def _load_doc(path: str):
    with open(path) as fh:
        doc = json.load(fh)
    if "traceEvents" not in doc:
        # tolerate a /dump_trace response body saved verbatim
        doc = doc.get("trace", doc.get("result", {}).get("trace", {}))
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return None
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trace_report")
    ap.add_argument("trace_files", nargs="+",
                    help="Chrome-trace JSON file(s); several with --merge")
    ap.add_argument("--merge", action="store_true",
                    help="merge the inputs into one document (pids "
                    "re-keyed, flow ids preserved) before summarizing")
    ap.add_argument("--out", default="",
                    help="with --merge: also write the merged document here")
    ap.add_argument("--top", type=int, default=0,
                    help="only print the N spans heaviest by total ms")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the summary as JSON")
    args = ap.parse_args(argv)

    if len(args.trace_files) > 1 and not args.merge:
        print("error: multiple inputs require --merge", file=sys.stderr)
        return 2

    docs = []
    for path in args.trace_files:
        doc = _load_doc(path)
        if doc is None:
            print(f"error: no traceEvents found in {path}", file=sys.stderr)
            return 1
        docs.append(doc)
    doc = (
        merge_traces(docs, labels=[os.path.basename(p)
                                   for p in args.trace_files])
        if args.merge else docs[0]
    )
    if args.merge and args.out:
        dump_doc(doc, args.out)

    summary = summarize_events(doc)
    chains = flow_chains(doc)
    cross = sum(
        1 for evs in chains.values()
        if len({e.get("pid") for e in evs}) > 1
    )
    if args.as_json:
        summary["_flows"] = {"chains": len(chains), "cross_process": cross}
        print(json.dumps(summary))
        return 0

    wall = summary.pop("_wall")
    rows = sorted(summary.items(), key=lambda kv: -kv[1]["total_ms"])
    dropped = 0
    if args.top and args.top > 0 and len(rows) > args.top:
        dropped = len(rows) - args.top
        rows = rows[: args.top]
    name_w = max([len(n) for n, _ in rows] + [len("span")])
    hdr = (f"{'span':<{name_w}}  {'count':>7}  {'total ms':>10}  "
           f"{'p50 ms':>9}  {'p95 ms':>9}  {'p99 ms':>9}")
    print(hdr)
    print("-" * len(hdr))
    for name, s in rows:
        print(f"{name:<{name_w}}  {s['count']:>7}  {s['total_ms']:>10.3f}  "
              f"{s['p50_ms']:>9.3f}  {s['p95_ms']:>9.3f}  {s['p99_ms']:>9.3f}")
    print("-" * len(hdr))
    if dropped:
        print(f"(… {dropped} lighter span name(s) below --top {args.top})")
    print(f"wall clock: {wall['wall_ms']:.3f} ms over {wall['events']} events; "
          f"flow chains: {len(chains)} ({cross} cross-process)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
