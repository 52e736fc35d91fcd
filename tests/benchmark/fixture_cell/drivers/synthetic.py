"""A driver that runs no JAX: each request sleeps the config's service
time. It lets the harness be rehearsed end to end on the CPU, and shows
that a later PR can bring a driver as a new file."""

import threading
import time

_now = time.perf_counter


def open(config, seed, root, chips, say):  # noqa: A001
    return Session(config, seed)


class Session:
    def __init__(self, config, seed):
        self.cfg = config
        self.n_pool = config["pool_commits"]
        self.n_sigs = config["validators"]
        self.setup = {"data_build_s": 0.0}
        self.spans = []
        self.tracing = False
        self.done = 0
        self._mtx = threading.Lock()

    def request(self, i):
        t0 = _now()
        time.sleep(self.cfg["service_ms"] / 1e3)
        if self.cfg.get("fail_every") and i % self.cfg["fail_every"] == 0:
            raise ValueError("synthetic failure")
        with self._mtx:
            self.done += 1
        self.record_span("device.op", t0, t0 + self.cfg["service_ms"] / 4e3)
        return self.n_sigs

    def record_span(self, name, start, end):
        if self.tracing:
            self.spans.append([name, start, end, threading.get_ident()])

    def warm(self, traffic, say):
        self.setup.update(compile_s=0.0, trace_lower_s=0.0)

    def compiles(self):
        return 0

    def counters(self):
        return {"sigs_verified_device": self.done * self.n_sigs,
                "launches": self.done, "sigs_per_request": self.n_sigs}

    def check(self):
        return list(self.cfg.get("check_says", []))

    def device(self):
        return {"platform": "synthetic", "kind": "none", "count": 1,
                "memory_peak_bytes": 0}

    def trace_start(self, out_dir):
        self.tracing, self._c0, self._t_a = True, self.counters(), _now()

    def trace_mark_end(self):
        self._t_b, self._c1, self.tracing = _now(), self.counters(), False

    def trace_stop(self):
        dev = [["/device:SYN:0", "XLA Ops", "syn_kernel", s, e - s]
               for n, s, e, _t in self.spans if n == "device.op"]
        return {"trace": {"t_a": self._t_a, "t_b": self._t_b,
                          "device_events": dev,
                          "spans": [s for s in self.spans
                                    if s[0] != "device.op"],
                          "spans_recorded": len(self.spans),
                          "ring_capacity": 16384},
                "counters": {"before": self._c0, "after": self._c1},
                "cpu_s": 0.001, "notes": {}}

    def close(self):
        pass
