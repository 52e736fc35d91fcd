"""Shared test scaffolding for the overlapped dispatcher (ISSUE 7).

Used by BOTH tests/test_overlap.py and the `tools/prep_bench.py
--overlap` tier-1 gate: they pin the same dispatcher loop structure
(transfer k+1 issued before batch k resolves) against the same mock, so
the mock lives in one place instead of drifting as two copies.

Not imported by any production path.
"""

from __future__ import annotations

import time


class SlowReadback:
    """Proxy device result whose materialization costs `delay` seconds —
    the resolver blocks on __array__ exactly like a TPU's
    D2H wait; async-copy capability passes through to the real result."""

    def __init__(self, dev, delay: float):
        self._dev = dev
        self._delay = delay

    def copy_to_host_async(self):
        fn = getattr(self._dev, "copy_to_host_async", None)
        if fn is not None:
            fn()

    def __array__(self, dtype=None):
        import numpy as np

        time.sleep(self._delay)
        a = np.asarray(self._dev)
        # a device-array stand-in must return the raw (possibly
        # non-owning) materialization — the resolver's owndata guard is
        # exactly what the overlap tests exercise
        return a.astype(dtype) if dtype is not None else a  # tmlint: disable=donation-aliasing — mock mimics device semantics


def slow_prepare(real_prepare, delay: float):
    """Wrap AsyncBatchVerifier._prepare so every kernel result rides a
    SlowReadback — the kernel itself (and its donation/transfer path)
    runs unchanged; only the readback is slowed."""

    def prep(entries):
        f, args, rlc, bucket = real_prepare(entries)
        return (lambda *xs: SlowReadback(f(*xs), delay)), args, rlc, bucket

    return prep


def slow_mesh_prepare(real_prepare, delay: float):
    """Mesh-mode twin of slow_prepare: wrap AsyncBatchVerifier's
    `_prepare_mesh` so every superbatch's kernel result rides a
    SlowReadback — the REAL packing, prep, transfer shardings and kernel
    run unchanged; only the readback is slowed (the `tools/prep_bench.py
    --mesh` gate's device-RTT proxy)."""

    def prep(block, plan):
        res = real_prepare(block, plan)
        f, args, rlc, bucket = res[:4]
        return (
            (lambda *xs: SlowReadback(f(*xs), delay)), args, rlc, bucket,
        ) + tuple(res[4:])

    return prep


def mock_mesh_prepare(real_prepare, rtt_s: float):
    """Fully-mocked mesh DEVICE for `bench.py multichip`'s simulated-lane
    curve: the real lane packing, host prep and H2D transfer run
    unchanged, but the launch returns an all-accept verdict row behind a
    fixed device RTT instead of running the kernel — modeling an L-device
    mesh (per-lane compute parallel across devices, one device launch
    per superbatch) on a box with one physical device. The curve then
    measures exactly what the mesh dispatcher adds: signatures packed
    per device launch vs the dispatcher's own serial host costs."""
    import numpy as np

    def prep(block, plan):
        res = real_prepare(block, plan)
        _f, args, rlc, bucket = res[:4]

        def launch(*_xs):
            return SlowReadback(np.ones((bucket,), dtype=bool), rtt_s)

        return (launch, args, rlc, bucket) + tuple(res[4:])

    return prep


def mock_light_prepare(real_prepare, rtt_s: float):
    """Mocked-device DEVICE for `bench.py light` and the
    `tools/prep_bench.py --light` throughput figure: the real host prep
    (sign-bytes, epoch grouping, coalescing, packing) and the H2D
    transfer run unchanged, but the launch returns an all-accept verdict
    row behind a fixed device RTT instead of running the kernel — the
    mock_mesh_prepare philosophy applied to the classic single-lane
    `_prepare`. What the light-service curve then measures is exactly
    what the service adds over per-request dispatch: cross-request
    epoch-grouped coalescing (headers per device launch) and
    request-level dedup, not kernel speed."""
    import numpy as np

    def prep(entries):
        _f, args, rlc, bucket = real_prepare(entries)

        def launch(*_xs):
            return SlowReadback(np.ones((bucket,), dtype=bool), rtt_s)

        return launch, args, rlc, bucket

    return prep


class DeadlineReadback:
    """Proxy device result that materializes at an absolute deadline —
    `rtt_s` after LAUNCH, not after the resolver gets around to it. The
    SlowReadback mock charges its delay inside __array__, which
    serializes the resolver at one RTT per batch; a real device's compute
    proceeds while the host pipelines, so concurrent launches' readbacks
    mature in parallel. bench.py mempool uses this so the mocked device
    models per-launch LATENCY (each batch's verdict is unavailable for a
    full RTT) without inventing a serial resolver bottleneck no real
    backend has."""

    def __init__(self, verdict, deadline: float):
        self._verdict = verdict
        self._deadline = deadline

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None):
        import numpy as np

        now = time.perf_counter()
        if now < self._deadline:
            time.sleep(self._deadline - now)
        a = np.asarray(self._verdict)
        return a.astype(dtype) if dtype is not None else a  # tmlint: disable=donation-aliasing — mock mimics device semantics


def mock_mempool_prepare(real_prepare, rtt_s: float):
    """Mocked-device DEVICE for `bench.py mempool` (ISSUE 13): the real
    ingress accumulation, EntryBlock packing, host prep and H2D transfer
    run unchanged, but the launch returns an all-accept verdict row that
    matures `rtt_s` after launch (DeadlineReadback) instead of running
    the kernel. Both bench columns — the windowed accumulator and the
    per-tx baseline — pay this same device latency per LAUNCH, so the
    ratio measures exactly what device-batched CheckTx adds: signatures
    fused per device launch."""
    import numpy as np

    def prep(entries):
        _f, args, rlc, bucket = real_prepare(entries)

        def launch(*_xs):
            return DeadlineReadback(
                np.ones((bucket,), dtype=bool),
                time.perf_counter() + rtt_s,
            )

        return launch, args, rlc, bucket

    return prep


def mock_vote_prepare(real_prepare, rtt_s: float):
    """Mocked-device DEVICE for `bench.py votes` and the
    `tools/prep_bench.py --votes` gate (ISSUE 15): the real vote-ingress
    windowing, EntryBlock packing, host prep and H2D transfer run
    unchanged, but the launch returns an all-accept verdict row that
    matures `rtt_s` after launch (DeadlineReadback) instead of running
    the kernel. Both bench columns — the windowed accumulator and the
    per-vote baseline — pay this same device latency per LAUNCH, so the
    ratio measures exactly what device-batched AddVote adds: live-vote
    signatures fused per device launch."""
    import numpy as np

    def prep(entries):
        _f, args, rlc, bucket = real_prepare(entries)

        def launch(*_xs):
            return DeadlineReadback(
                np.ones((bucket,), dtype=bool),
                time.perf_counter() + rtt_s,
            )

        return launch, args, rlc, bucket

    return prep


def drain_pool(pool, timeout: float = 5.0) -> None:
    """Wait for every in-flight slot to return. The resolver completes a
    batch's futures BEFORE releasing its pool slot, so a caller waking
    from future.result() can observe in_flight briefly nonzero — tests
    and the --overlap gate drain here before asserting leak-freedom."""
    deadline = time.time() + timeout
    while pool.in_flight() and time.time() < deadline:
        time.sleep(0.01)
