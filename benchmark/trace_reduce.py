"""From a traced stretch to numbers. The input is one plain dict (the form
fixtures/trace_small.json records), all times in seconds on one clock:

  t_a, t_b         the stretch
  sigs             signatures of requests completed inside it
  device_events    [[plane, line, name, start, duration], ...] — the
                   profiler's device planes, shifted onto the host clock
  spans            [[name, start, end, tid], ...] — the program's tracer
                   ring plus the harness's own bench.* spans
  spans_recorded, ring_capacity
                   spans written during the stretch / what the ring holds

Device busy time, idle share and kernel time come from device_events
only; host spans only explain the gaps."""

from __future__ import annotations

import bisect
import re

OPS_LINE = "XLA Ops"
SHORT_GAP_S = 20e-6
GRID_S = 1e-3

# Who is charged with an idle gap when several spans are open: the most
# specific activity first, a caller's blocking wait last. Names not
# listed rank between the two groups.
_ACTIVE = ("pipeline.dispatch", "pipeline.transfer", "pipeline.table_upload",
           "pipeline.prep", "ops.", "pipeline.commit_prep_fused",
           "verify_commit.prep_fused", "verify_commit.sign_bytes",
           "bench.decode", "verify_commit.verify", "verify_commit")
_WAITING = ("pipeline.device_wait", "pipeline.queue_wait",
            "ops.pipeline_wait", "bench.call", "bench.wait")


def _rank(name: str) -> int:
    for i, p in enumerate(_WAITING):
        if name.startswith(p):
            return len(_ACTIVE) + 1 + i
    for i, p in enumerate(_ACTIVE):
        if name.startswith(p):
            return i
    return len(_ACTIVE)


def ring_wrapped(trace: dict) -> bool:
    return trace["spans_recorded"] > trace["ring_capacity"]


def _clip(start, end, a, b):
    return max(start, a), min(end, b)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_planes(trace: dict):
    return sorted({ev[0] for ev in trace["device_events"]})


def busy_intervals(trace: dict, plane: str):
    a, b = trace["t_a"], trace["t_b"]
    return _union(_clip(ev[3], ev[3] + ev[4], a, b)
                  for ev in trace["device_events"]
                  if ev[0] == plane and ev[1] == OPS_LINE)


def busy_seconds(trace: dict):
    """Seconds in which an operation ran on the device, averaged over the
    device planes; None when the trace holds no device plane."""
    planes = device_planes(trace)
    if not planes:
        return None
    return sum(sum(e - s for s, e in busy_intervals(trace, p))
               for p in planes) / len(planes)


def idle_share(trace: dict):
    busy = busy_seconds(trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / (trace["t_b"] - trace["t_a"]))


def device_op_seconds(trace: dict, line: str, pattern: str):
    """Device seconds of the events on `line` whose name matches, clipped
    to the stretch and averaged over the device planes; None where no
    event inside the stretch matches (a renamed kernel reads nothing, not
    0.0)."""
    planes = device_planes(trace)
    if not planes:
        return None
    rx = re.compile(pattern)
    a, b = trace["t_a"], trace["t_b"]
    total, found = 0.0, False
    for ev in trace["device_events"]:
        if ev[1] == line and rx.search(ev[2]):
            s, e = _clip(ev[3], ev[3] + ev[4], a, b)
            if e > s:
                total += e - s
                found = True
    return total / len(planes) if found else None


def top_device_ops(trace: dict, n: int = 10):
    a, b = trace["t_a"], trace["t_b"]
    by = {}
    for ev in trace["device_events"]:
        if ev[1] == OPS_LINE:
            s, e = _clip(ev[3], ev[3] + ev[4], a, b)
            if e > s:
                by[ev[2]] = by.get(ev[2], 0.0) + (e - s)
    return sorted(by.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: dict, n: int = 10):
    """The device's idle time inside the stretch, charged to what the host
    was doing: each gap is cut at span boundaries and every piece goes to
    the best-ranked of the threads' innermost open spans ('no_span' if
    none). Gaps under SHORT_GAP_S are summed as 'short_gaps'. First device
    plane only."""
    planes = device_planes(trace)
    if not planes:
        return []
    a, b = trace["t_a"], trace["t_b"]
    gaps, t = [], a
    for s, e in busy_intervals(trace, planes[0]):
        if s > t:
            gaps.append((t, s))
        t = e
    if b > t:
        gaps.append((t, b))
    # spans by the GRID_S cells they touch, so a gap looks at its own
    # neighbourhood and not at every span of the stretch
    grid = {}
    for s in trace["spans"]:
        if s[2] <= a or s[1] >= b:
            continue
        sp = (s[1], s[2], _rank(s[0]), s[0], s[3])
        for cell in range(int((max(s[1], a) - a) / GRID_S),
                          int((min(s[2], b) - a) / GRID_S) + 1):
            grid.setdefault(cell, []).append(sp)
    charged = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_S:
            charged["short_gaps"] = charged.get("short_gaps", 0.0) + g1 - g0
            continue
        over = {sp for cell in range(int((g0 - a) / GRID_S),
                                     int((g1 - a) / GRID_S) + 1)
                for sp in grid.get(cell, ()) if sp[0] < g1 and sp[1] > g0}
        cuts = sorted({g0, g1, *(x for sp in over for x in sp[:2]
                                 if g0 < x < g1)})
        for c0, c1 in zip(cuts, cuts[1:]):
            mid = (c0 + c1) / 2
            inner = {}      # thread -> its innermost span open over the piece
            for sp in over:
                if sp[0] <= mid < sp[1] and (
                        sp[4] not in inner
                        or (sp[0], -sp[1]) > (inner[sp[4]][0], -inner[sp[4]][1])):
                    inner[sp[4]] = sp
            name = (min(inner.values(), key=lambda sp: (sp[2], -sp[0]))[3]
                    if inner else "no_span")
            charged[name] = charged.get(name, 0.0) + c1 - c0
    return sorted(charged.items(), key=lambda kv: -kv[1])[:n]


def span_durations(trace: dict, names):
    a, b = trace["t_a"], trace["t_b"]
    return [s[2] - s[1] for s in trace["spans"]
            if s[0] in names and a <= s[2] <= b]


def span_seconds(trace: dict, names, self_time: bool):
    """Seconds the named spans that ended inside the stretch cover, thread
    by thread and without counting an instant twice. With self_time, less
    what other spans nested in them on their own thread cover."""
    a, b = trace["t_a"], trace["t_b"]
    by_tid = {}
    for s in trace["spans"]:
        by_tid.setdefault(s[3], []).append(s)
    total = 0.0
    for spans in by_tid.values():
        named = [p for p in spans if p[0] in names and a <= p[2] <= b]
        total += sum(e - s for s, e in _union((p[1], p[2]) for p in named))
        if not self_time:
            continue
        others = sorted((c[1], c[2]) for c in spans if c[0] not in names)
        starts = [c[0] for c in others]
        for p in named:
            lo = bisect.bisect_left(starts, p[1])
            hi = bisect.bisect_right(starts, p[2])
            kids = _union(c for c in others[lo:hi] if c[1] <= p[2])
            total -= sum(e - s for s, e in kids)
    return total
