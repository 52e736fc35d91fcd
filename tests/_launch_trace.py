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

import threading
import time

from tendermint_tpu.observability import trace as tr

CHAIN_ID = "launch-trace-test"
N_VALIDATORS = 80            # >= DEVICE_THRESHOLD: the commit rides the pipeline
COMMIT_S = 5.0e-3            # hub150-serial1 commit_p50_ms 4.9996 (ledger, PR 23)
OFF_BUDGET = 0.002           # of one commit


def signed_commit(n=N_VALIDATORS, height=7):
    """(validator set, block id, commit): n validators, all sign."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader)
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.wire.canonical import Timestamp, compose_vote_sign_bytes

    sks = [ed25519.gen_priv_key(i.to_bytes(2, "big") * 16) for i in range(1, n + 1)]
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


def traced_commit():
    """Verifies one commit untraced (compiles, spins the pipeline up),
    then one with the tracer on. Returns (records, {thread ident: name}):
    the ring's records of the traced commit alone."""
    from tendermint_tpu.types import validation

    vset, bid, commit = signed_commit()
    validation.verify_commit(CHAIN_ID, vset, bid, commit.height, commit)
    tr.TRACER.clear()
    tr.configure(enabled=True)
    try:
        validation.verify_commit(CHAIN_ID, vset, bid, commit.height, commit)
        # the resolver writes its last records after it wakes the caller
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not any(
                r[0] == "pipeline.verdict" for r in tr.TRACER.events()):
            time.sleep(0.005)
    finally:
        tr.configure(enabled=False)
    return tr.TRACER.events(), {t.ident: t.name for t in threading.enumerate()}


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
    # a transfer's puts share ONE disabled check (device_pool.transfer)
    checks = sum(n in CHECK_ONLY for n in names) + UNRECORDED_CHECKS
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
