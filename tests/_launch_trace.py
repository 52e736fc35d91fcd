"""Shared by the launch-path tracing tests and the two disabled-overhead
guards: one real commit through verify_commit with the tracer on, and the
yardstick for what tracing costs when it is off.

The guards hold the cost to what the benchmark's driver would see, not to
some crypto call's speed: the best-of-k cost of a disabled site, times
the sites one commit crosses (counted from the records of a traced commit:
`with` spans at the cost of a `with` span with kwargs, guarded record()
sites at the cost of the one check they are; the per-put spans of one
transfer sit behind one check), against the measured commit of the
smallest cell."""

import contextlib
import threading
import time

import pytest

from tendermint_tpu.observability import trace as tr

CHAIN_ID = "launch-trace-test"
N_VALIDATORS = 80            # >= DEVICE_THRESHOLD: the commit rides the pipeline
COMMIT_S = 5.0e-3            # hub150-serial1 commit_p50_ms 4.9996 (ledger, PR 23)
OFF_BUDGET = 0.002           # of one commit


def signed_commit(n=N_VALIDATORS, height=7, first=1):
    """(validator set, block id, commit): n validators, all sign. Their
    keys are numbers first..first+n-1: sets from one `first` share keys."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader)
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.wire.canonical import Timestamp, compose_vote_sign_bytes

    sks = [ed25519.gen_priv_key(i.to_bytes(2, "big") * 16) for i in range(first, first + n)]
    vals = [Validator.new(sk.pub_key(), 100) for sk in sks]
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    bid = BlockID(hash=b"\x11" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
    tpl = Commit(height=height, round=0, block_id=bid).sign_bytes_template(
        CHAIN_ID, BLOCK_ID_FLAG_COMMIT)
    sigs = []
    for i, sk in enumerate(sks):
        ts = Timestamp(seconds=1_700_000_000, nanos=i + 1)
        sigs.append(CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=sk.pub_key().address(), timestamp=ts,
            signature=sk.sign(compose_vote_sign_bytes(tpl, ts))))
    return vset, bid, Commit(height=height, round=0, block_id=bid,
                             signatures=sigs)


def _traced(request):
    """The ring's records of one `request()` made with the tracer on."""
    tr.TRACER.clear()
    tr.configure(enabled=True)
    try:
        request()
        # the resolver writes its last records after it wakes the caller
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not any(
                r[0] == "pipeline.verdict" for r in tr.TRACER.events()):
            time.sleep(0.005)
    finally:
        tr.configure(enabled=False)
    return tr.TRACER.events()


def traced_commit():
    """Verifies one commit untraced (compiles, spins the pipeline up),
    then one with the tracer on. Returns (records, {thread ident: name}):
    the ring's records of the traced commit alone."""
    from tendermint_tpu.types import validation

    vset, bid, commit = signed_commit()

    def request():
        validation.verify_commit(CHAIN_ID, vset, bid, commit.height, commit)

    request()
    return _traced(request), {t.ident: t.name for t in threading.enumerate()}


@contextlib.contextmanager
def chip_host_path(native: bool = True):
    """The host path a commit takes on the chip, on the CPU: the Pallas RLC
    family chosen, the epoch cache on, the host prep real (commit_prep,
    pallas_rlc.prepare_rlc[_cached]) and the kernels stood in for by a
    launch that accepts every lane (the kernels: test_pallas_rlc*.py).
    With native=False the module reads as absent (TM_TPU_NO_NATIVE=1)."""
    import jax.numpy as jnp

    from tendermint_tpu import native as native_mod
    from tendermint_tpu.ops import backend, epoch_cache, pallas_rlc as pr

    def accepts(_m, g, *_a, **_k):
        return lambda *_args: jnp.ones((1, g), jnp.int32)

    mp = pytest.MonkeyPatch()
    mp.setenv("TM_TPU_PALLAS", "1")
    mp.setenv("TM_TPU_RLC", "1")
    mp.setattr(pr, "BLOCK_LANES", 4)
    mp.setattr(pr, "_jitted_rlc_verify", accepts)
    mp.setattr(pr, "rlc_cached_fn", lambda _ep, m, g, *a, **k: accepts(m, g))
    if not native:
        mp.setattr(native_mod, "_module", None)
        mp.setattr(native_mod, "_tried", True)
    backend.engine.cache_clear()
    epoch_cache.reset(depth=8)
    try:
        yield
    finally:
        mp.undo()
        backend.engine.cache_clear()
        epoch_cache.reset()


def traced_requests(n=72):
    """Inside chip_host_path(): a validator set of its own, then one
    request — Commit.decode of the wire bytes inside a `bench.decode`
    record, as the benchmark's driver writes it, then verify_commit —
    traced at the set's first sight (the uncached prep) and once its
    table is resident (the cached one). Returns ((cold records, warm
    records), {thread ident: name})."""
    from tendermint_tpu.types import validation
    from tendermint_tpu.types.block import Commit

    vset, bid, commit = signed_commit(n, height=n, first=2000)
    wire = commit.encode()

    def request():
        t0 = time.perf_counter()
        decoded = Commit.decode(wire)
        t1 = time.perf_counter()
        validation.verify_commit(CHAIN_ID, vset, bid, commit.height, decoded)
        if tr.TRACER.enabled:
            tr.TRACER.record("bench.decode", t0, t1)

    # a first commit of the process spins the pipeline up on another set
    other = signed_commit(N_VALIDATORS, first=1000)
    validation.verify_commit(CHAIN_ID, other[0], other[1], other[2].height,
                             other[2])
    cold = _traced(request)
    request()  # the table upload
    warm = _traced(request)
    return (cold, warm), {t.ident: t.name for t in threading.enumerate()}


# Sites that are a bare `if TRACER.enabled:` around record() / flow_point():
# one attribute check when the tracer is off. Every other record of a traced
# commit comes from a `with` span, the costliest kind of site (kwargs built,
# the shared null context entered and left).
CHECK_ONLY = frozenset({
    "pipeline.submit", "pipeline.dispatch.flow", "pipeline.verdict",
    "pipeline.queue_wait.intake", "pipeline.coalesce",
    "pipeline.queue_wait.dispatch", "pipeline.queue_wait",
    "pipeline.queue_wait.resolve", "pipeline.resolve",
    "ops.pipeline_wait.wake"})
# ... and the checks that write no record of their own: submit()'s stamp,
# the coalescer's and resolver's per-batch flags, the dispatcher's and the
# prep's launch naming, device_pool.transfer's, _resolve's, backend's wake
UNRECORDED_CHECKS = 8


def _best_of(body, k=15, n=10000):
    # this thread's CPU time, not wall time: the tier-1 run's five sibling
    # workers can preempt a 1 ms repeat, they cannot add to its CPU time
    best = float("inf")
    for _ in range(k):
        t0 = time.thread_time()
        body(n)
        best = min(best, (time.thread_time() - t0) / n)
    return best


def _spans(n):
    # the launch path's spans carry 0-4 kwargs; a flow-carrying one too
    for _ in range(n // 2):
        with tr.span("x", n=64, bucket=128):
            pass
        with tr.span("y", bucket=128, flow=123, flow_phase="t"):
            pass


def _checks(n):
    for _ in range(n):
        if tr.TRACER.enabled:
            tr.TRACER.flow_point("pipeline.submit", 123, "s", n=64)


def disabled_site_costs():
    """(seconds per disabled `with` span site, per bare check), best of k."""
    assert not tr.TRACER.enabled
    return _best_of(_spans), _best_of(_checks)


def assert_off_cost_within_budget(records):
    """The guard itself. Returns (span sites, check sites, seconds a commit)."""
    names = [r[0] for r in records]
    # a native entry's sections are recorded behind ONE disabled check
    # (native.traced_call): count the call, not its records
    native_calls = sum(r[0].endswith(".native") and (r[4] or {}).get("section") == 0
                       for r in records)
    names = [n for n in names if not n.endswith((".native", ".gil"))]
    # a transfer's puts share ONE disabled check (device_pool.transfer)
    checks = (sum(n in CHECK_ONLY for n in names) + UNRECORDED_CHECKS
              + native_calls)
    spans = sum(n not in CHECK_ONLY and n != "pipeline.transfer.put"
                for n in names)
    assert spans >= 10 and checks >= 15, (spans, checks, sorted(names))
    c_span, c_check = disabled_site_costs()
    cost = spans * c_span + checks * c_check
    assert cost < OFF_BUDGET * COMMIT_S, (
        f"{spans} span sites x {c_span * 1e9:.0f} ns + {checks} checks x "
        f"{c_check * 1e9:.0f} ns = {cost * 1e6:.2f} us a commit with the "
        f"tracer off, over {OFF_BUDGET:.1%} of a {COMMIT_S * 1e3:.1f} ms commit")
    return spans, checks, cost
