"""The arithmetic of the end-to-end metrics, over a generator's records
(due, start, end, ok, sigs). A request belongs to the window when its
completion falls inside it; a rate is all such work over the whole
window; a latency runs from the due instant (== start in a closed loop)
to completion, over all such requests."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def in_window(records, t0: float, t1: float):
    return [r for r in records if t0 <= r[2] <= t1]


def latency_percentile(records, q: float) -> float:
    """ms, due -> completion."""
    return percentile([(r[2] - r[0]) * 1e3 for r in records], q)


def completed_per_s(records, seconds: float) -> float:
    """Signatures of accepted requests completed in the window, per
    second of window: granularity one request."""
    return sum(r[4] for r in records if r[3]) / seconds


def lateness_ms(records):
    """How late the generator ran (start - due): p50, p95, max in ms."""
    late = [(r[1] - r[0]) * 1e3 for r in records]
    return {"p50": percentile(late, 50), "p95": percentile(late, 95),
            "max": max(late)}


def end_to_end(definition: dict, records, seconds: float, setup_s: float):
    stat = definition["stat"]
    if stat == "setup_s":
        return setup_s
    if stat == "latency_percentile":
        return latency_percentile(records, definition["q"])
    if stat == "completed_per_s":
        return completed_per_s(records, seconds)
    raise ValueError(f"unknown end-to-end stat {stat!r}")
