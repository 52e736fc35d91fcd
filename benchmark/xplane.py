"""Reads a JAX profiler trace (.xplane.pb) into the plain device-event
list trace_reduce works on, shifted onto the host's perf_counter clock.

The shift comes from marks the harness sets itself: each mark is a
jax.profiler.TraceAnnotation named SYNC whose perf_counter reading was
taken as it opened; the profiler records the same marks on its own
clock, and the median difference is the offset."""

from __future__ import annotations

import glob
import os
import statistics

SYNC = "bench.sync"
DEVICE_PREFIX = "/device:"
LINES = ("XLA Ops", "XLA Modules")


def newest(trace_dir: str):
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def read(path: str, marks):
    """(device_events, notes): events [[plane, line, name, start, dur]] in
    host-clock seconds for every line of LINES on every device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    seen, raw = [], []
    planes = {}
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        planes[plane.name] = [ln.name for ln in plane.lines] if is_dev else []
        for line in plane.lines:
            if is_dev and line.name in LINES:
                raw += [(plane.name, line.name, ev.name, ev.start_ns,
                         ev.duration_ns) for ev in line.events]
            elif not is_dev:
                seen += [ev.start_ns for ev in line.events if ev.name == SYNC]
    seen.sort()
    notes = {"planes": planes, "sync_marks_found": len(seen)}
    if len(seen) != len(marks) or not marks:
        notes["error"] = (f"{len(seen)} of {len(marks)} sync marks found: "
                          "device events cannot be placed on the host clock")
        return [], notes
    diffs = [m - ns * 1e-9 for m, ns in zip(sorted(marks), seen)]
    offset = statistics.median(diffs)
    notes["offset_spread_us"] = (max(diffs) - min(diffs)) * 1e6
    if raw:     # where the device's events lie against the first mark
        starts = [ns * 1e-9 + offset - min(marks) for _p, _l, _n, ns, _d in raw]
        notes["device_events_from_first_mark_s"] = [min(starts), max(starts)]
    return ([[p, ln, name, ns * 1e-9 + offset, dur * 1e-9]
             for p, ln, name, ns, dur in raw], notes)
