"""The load generators: one general closed loop and one general open loop,
parameterised by a traffic file. No JAX here: a generator only calls
`request(i)` — the driver's entry point for pool item i, which returns the
number of signatures it verified or raises — and keeps the clock.

A record is (due, start, end, ok, sigs), perf_counter seconds. In a closed
loop due == start: a caller's next request leaves when its last returned.
In an open loop requests are due on a fixed schedule whatever the system
does, latency counts from the due instant, and start - due is how late
the generator ran."""

from __future__ import annotations

import threading
import time

_now = time.perf_counter


class _Log(list):
    """One worker's records, and the errors its requests raised."""

    def __init__(self):
        super().__init__()
        self.errors = []


def _issue(request, i, due, start, log, record_span):
    try:
        sigs, ok = request(i), True
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        sigs, ok = 0, False
        log.errors.append(repr(e))
    end = _now()
    log.append((due, start, end, ok, sigs))
    if record_span is not None:
        record_span("bench.call", start, end)


def _worker_closed(request, idxs, t0, t1, log, record_span):
    while (rem := t0 - _now()) > 0:
        time.sleep(min(rem, 0.001))
    k = 0
    while (start := _now()) < t1:
        _issue(request, idxs[k % len(idxs)], start, start, log, record_span)
        k += 1


class _Schedule:
    """Open loop: request k is due at t0 + (k // burst) * burst / rate."""

    def __init__(self, t0, t1, rate, burst):
        self.t0, self.t1, self.step, self.burst = t0, t1, burst / rate, burst
        self._k = 0
        self._mtx = threading.Lock()

    def next(self):
        with self._mtx:
            k = self._k
            due = self.t0 + (k // self.burst) * self.step
            if due >= self.t1:
                return None
            self._k += 1
            return k, due


def _worker_open(request, pool_size, sched, log, record_span):
    while True:
        nxt = sched.next()
        if nxt is None:
            return
        k, due = nxt
        t = _now()
        if t < due:
            while (rem := due - _now()) > 0:
                time.sleep(rem if rem > 0.002 else 0)
            if record_span is not None:
                record_span("bench.wait", t, _now())
        _issue(request, k % pool_size, due, _now(), log, record_span)


def run(traffic: dict, request, pool_size: int, seconds: float,
        record_span=None, timers=()):
    """Drive `request` for `seconds` as the traffic file says. `timers` is
    [(seconds after the start, fn)], run on this thread while the callers
    work. Returns (t0, records sorted by end, errors)."""
    gen = traffic["generator"]
    t0 = _now() + 0.05       # every caller is parked before the start
    t1 = t0 + seconds
    if gen["kind"] == "closed_loop":
        n = gen["callers"]
        if pool_size < n:
            raise ValueError(f"{n} callers need a pool of at least {n}")
        logs = [_Log() for _ in range(n)]
        threads = [
            threading.Thread(
                target=_worker_closed, name=f"bench-caller-{c}", daemon=True,
                args=(request, list(range(c, pool_size, n)), t0, t1, logs[c],
                      record_span))
            for c in range(n)
        ]
    elif gen["kind"] == "open_loop":
        sched = _Schedule(t0, t1, gen["rate"], gen.get("burst", 1))
        logs = [_Log() for _ in range(gen["workers"])]
        threads = [
            threading.Thread(
                target=_worker_open, name=f"bench-worker-{c}", daemon=True,
                args=(request, pool_size, sched, logs[c], record_span))
            for c in range(gen["workers"])
        ]
    else:
        raise ValueError(f"unknown generator kind {gen['kind']!r}")
    for t in threads:
        t.start()
    for at, fn in sorted(timers, key=lambda x: x[0]):
        while (rem := t0 + at - _now()) > 0:
            time.sleep(min(rem, 0.05))
        fn()
    for t in threads:
        t.join()
    records = sorted((r for lg in logs for r in lg), key=lambda r: r[2])
    return t0, records, [e for lg in logs for e in lg.errors]
