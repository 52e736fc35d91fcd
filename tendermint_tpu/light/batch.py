"""Batched light-client verification plans (ISSUE 11).

One client request is a (trusted, untrusted) header pair plus trust
parameters. `prepare_request` runs every host-side check (trust level,
expiry, hash chaining, clock drift — through the light/verifier.py
prepare seam, so the checks are the SAME code the sequential path runs)
and captures the request's sig work as EntryBlocks with epoch metadata
attached. The service ships those blocks through the shared
AsyncBatchVerifier, where same-epoch work from MANY requests coalesces
into one device batch (mesh lanes when enabled); `conclude_request`
applies the device verdict rows back in sequential stage order so error
precedence — and every error string — matches light/verifier.py exactly.

The error-precedence contract that makes that so, and its one
implementation, are verifier.prepare_stages / conclude_stages: the
synchronous verify_non_adjacent runs a hop's two checks through them too.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..types import Fraction
from ..wire.canonical import Timestamp
from . import verifier

# light/client.go:56 (mirrors client.DEFAULT_MAX_CLOCK_DRIFT without
# pulling the provider/store stack into this module's import graph)
DEFAULT_MAX_CLOCK_DRIFT = 10.0


@dataclass
class HeaderRequest:
    """One light-client verification request: skip-verify
    `untrusted_header` from `trusted_header` (light/verifier.go Verify).
    `now` is optional — the service resolves one clock reading per RPC
    batch when omitted, which is also what lets identical requests from
    different clients share a verification."""

    trusted_header: object  # SignedHeader
    trusted_vals: object  # ValidatorSet
    untrusted_header: object  # SignedHeader
    untrusted_vals: object  # ValidatorSet
    trusting_period: float
    max_clock_drift: float = DEFAULT_MAX_CLOCK_DRIFT
    trust_level: Fraction = verifier.DEFAULT_TRUST_LEVEL
    now: Optional[Timestamp] = None


def fingerprint(req: HeaderRequest, now: Timestamp) -> Optional[tuple]:
    """Memo / single-flight key: fully identifies the verification's
    inputs. Header hashes pin every header field, the untrusted COMMIT
    hash pins the signatures (a forged commit under a genuine header
    must never alias a clean request), valset hashes pin keys+powers,
    and every trust parameter — including the resolved `now`, because
    expiry and clock-drift verdicts depend on it — rides along.

    Returns None when the request is NOT fingerprintable: an incomplete
    header hashes to b"" (Header.hash's nil convention), which would
    alias every such request onto one memo slot — those verify uniquely
    instead of risking a wrong cached verdict."""
    th = req.trusted_header.header.hash()
    uh = req.untrusted_header.header.hash()
    if not th or not uh:
        return None
    return (
        th,
        uh,
        req.untrusted_header.commit.hash(),
        req.trusted_vals.hash(),
        req.untrusted_vals.hash(),
        float(req.trusting_period),
        float(req.max_clock_drift),
        req.trust_level.numerator,
        req.trust_level.denominator,
        now.seconds,
        now.nanos,
    )


@dataclass
class RequestPlan:
    stages: List[verifier.StagePlan] = field(default_factory=list)
    error: Optional[BaseException] = None  # host-check failure (pre-sig)

    def entry_stages(self) -> List[verifier.StagePlan]:
        return [s for s in self.stages if s.entries is not None]


def prepare_request(req: HeaderRequest, now: Timestamp) -> RequestPlan:
    """Host half of one request: non-sig checks + sig-work extraction.
    Never raises — failures land in the plan so the service turns them
    into streamed verdicts."""
    try:
        checks = verifier.prepare_verify(
            req.trusted_header, req.trusted_vals,
            req.untrusted_header, req.untrusted_vals,
            req.trusting_period, now, req.max_clock_drift, req.trust_level,
        )
    except Exception as e:  # noqa: BLE001 — any host-check error is the verdict
        return RequestPlan(error=e)
    return RequestPlan(stages=verifier.prepare_stages(checks))


def conclude_request(plan: RequestPlan, verdicts) -> Optional[BaseException]:
    """Apply device verdicts in SEQUENTIAL stage order. `verdicts` has
    one item per entry_stages() entry, in that order — each a bool
    validity row or the exception its pipeline future resolved with.
    Returns the request's error (byte-identical to the sequential
    path's) or None on acceptance."""
    if plan.error is not None:
        return plan.error
    return verifier.conclude_stages(plan.stages, verdicts)


def group_stats(plans) -> Dict[Optional[bytes], int]:
    """Per-epoch stage-block counts across a batch of plans — the
    epoch-grouping shape the service reports (the actual coalescing is
    the shared pipeline's; this is its observable input)."""
    groups: Dict[Optional[bytes], int] = {}
    for p in plans:
        for st in p.entry_stages():
            k = getattr(st.entries, "epoch_key", None)
            groups[k] = groups.get(k, 0) + 1
    return groups
