"""The fixed set of per-layer metric readers. A file in layer_metrics/
names one of them and its parameters; a reader that finds nothing to read
returns None and the metric is left out of the line.

`obs` is what a traced run observed:
  trace      the traced stretch (see trace_reduce), or None
  counters   {"before": {...}, "after": {...}} flat counter snapshots
             around the stretch
  cpu_s      process CPU seconds spent in the stretch
  setup      {"trace_lower_s", "compile_s", "data_build_s",
              "compiles_in_window", ...}
"""

from __future__ import annotations

from . import stats, trace_reduce as tr


def _spans_ok(obs) -> bool:
    t = obs.get("trace")
    return bool(t) and not tr.ring_wrapped(t) and t["sigs"] > 0


def span_time_per_sig(obs, spans, self_time=False):
    """us the spans cover per signature completed in the stretch; with
    self_time, less what spans nested inside them cover."""
    if not _spans_ok(obs):
        return None
    t = obs["trace"]
    if not tr.span_durations(t, spans):
        return None
    return tr.span_seconds(t, spans, self_time) * 1e6 / t["sigs"]


def span_percentile(obs, spans, q):
    """ms, the q-th percentile of the spans' durations."""
    if not _spans_ok(obs):
        return None
    d = tr.span_durations(obs["trace"], spans)
    return stats.percentile(d, q) * 1e3 if d else None


def counter_delta_ratio(obs, num, den, scale=1.0):
    """scale * (rise of the `num` counters) / (rise of the `den` counters)."""
    c = obs.get("counters")
    if not c:
        return None
    def rise(keys):
        return sum(c["after"][k] - c["before"][k] for k in keys)

    d = rise(den)
    return scale * rise(num) / d if d else None


def gauge_per_sig(obs, gauge, per):
    """A gauge's reading after the stretch over a counter-snapshot constant."""
    c = obs.get("counters")
    if not c or not c["after"].get(per):
        return None
    return c["after"][gauge] / c["after"][per]


def device_ops_per_sig(obs, line, pattern):
    """us of device time of the matching profiler events per signature."""
    t = obs.get("trace")
    if not t or not t["sigs"]:
        return None
    s = tr.device_op_seconds(t, line, pattern)
    return None if s is None else s * 1e6 / t["sigs"]


def device_idle_share(obs):
    t = obs.get("trace")
    return tr.idle_share(t) if t else None


def process_cpu_per_sig(obs):
    t = obs.get("trace")
    if not t or not t["sigs"] or obs.get("cpu_s") is None:
        return None
    return obs["cpu_s"] * 1e6 / t["sigs"]


def setup_field(obs, field):
    return obs.get("setup", {}).get(field)


READERS = {f.__name__: f for f in (
    span_time_per_sig, span_percentile, counter_delta_ratio, gauge_per_sig,
    device_ops_per_sig, device_idle_share, process_cpu_per_sig, setup_field)}


def read(definition: dict, obs: dict):
    return READERS[definition["reader"]](obs, **definition.get("params", {}))
