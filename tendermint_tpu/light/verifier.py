"""Light-client header verification — hot path #2.

Reference parity: light/verifier.go — VerifyAdjacent (:103),
VerifyNonAdjacent (:33), Verify (:152), VerifyBackwards (:201). The
commit checks route through types.validation (VerifyCommitLight /
VerifyCommitLightTrusting), i.e. through the device batch engine — the
pipelined 1k-header sync workload of BASELINE config #5.
"""

from __future__ import annotations

from typing import Callable, List

from ..types import ErrNotEnoughVotingPowerSigned, Fraction, SignedHeader, ValidatorSet
from ..types import validation as _validation
from ..types.validation import (
    verify_commit_light,
    verify_commit_light_trusting,
)
from ..observability import trace as _trace
from ..wire.canonical import Timestamp

_span = _trace.span

# light.DefaultTrustLevel (light/verifier.go:20)
DEFAULT_TRUST_LEVEL = Fraction(1, 3)


class ErrNotEnoughTrust(ValueError):
    """verifier.go ErrNewValSetCantBeTrusted."""


class ErrInvalidHeader(ValueError):
    pass


class ErrOldHeaderExpired(ValueError):
    pass


def _ts_add(ts: Timestamp, seconds: float) -> Timestamp:
    total_ns = ts.seconds * 10**9 + ts.nanos + int(seconds * 1e9)
    return Timestamp(seconds=total_ns // 10**9, nanos=total_ns % 10**9)


def _ts_before(a: Timestamp, b: Timestamp) -> bool:
    return (a.seconds, a.nanos) < (b.seconds, b.nanos)


def header_expired(h: SignedHeader, trusting_period: float, now: Timestamp) -> bool:
    """verifier.go HeaderExpired: expiration = header.Time + trustingPeriod."""
    expiration = _ts_add(h.header.time, trusting_period)
    return not _ts_before(now, expiration)


def validate_trust_level(lvl: Fraction) -> None:
    """verifier.go ValidateTrustLevel: must be in [1/3, 1]."""
    if (
        lvl.numerator * 3 < lvl.denominator
        or lvl.numerator > lvl.denominator
        or lvl.denominator == 0
    ):
        raise ValueError(f"trustLevel must be within [1/3, 1], given {lvl}")


def verify_new_header_and_vals(
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted_header: SignedHeader,
    now: Timestamp,
    max_clock_drift: float,
) -> None:
    """verifier.go:236-283 verifyNewHeaderAndVals."""
    chain_id = trusted_header.header.chain_id
    try:
        untrusted_header.validate_basic(chain_id)
    except ValueError as e:
        raise ErrInvalidHeader(f"untrustedHeader.ValidateBasic failed: {e}") from e
    if untrusted_header.header.height <= trusted_header.header.height:
        raise ErrInvalidHeader(
            f"expected new header height {untrusted_header.header.height} to be greater "
            f"than one of old header {trusted_header.header.height}"
        )
    if not _ts_before(trusted_header.header.time, untrusted_header.header.time):
        raise ErrInvalidHeader("expected new header time to be after old header time")
    if not _ts_before(untrusted_header.header.time, _ts_add(now, max_clock_drift)):
        raise ErrInvalidHeader(
            "new header has a time from the future (max clock drift exceeded)"
        )
    if untrusted_header.header.validators_hash != untrusted_vals.hash():
        raise ErrInvalidHeader(
            f"expected new header validators ({untrusted_header.header.validators_hash.hex()}) "
            f"to match those supplied ({untrusted_vals.hash().hex()})"
        )


class SigCheck:
    """One commit-signature check of a header verification (ISSUE 11).

    The prepare_* functions below run every NON-sig check host-side
    (heights, trust level, expiry, hash chaining, clock drift — exactly
    the lines the old verify_* bodies ran) and return the sig work as
    SigCheck objects instead of verifying in place. Two consumers:

      run_sync()  the sequential path — calls the SAME types.validation
                  entry point the old code called, with the identical
                  error wrapping, so verify_adjacent/verify_non_adjacent
                  keep their byte-for-byte behavior;
      prepare()   the batched light service — returns (entries, conclude)
                  where `entries` is the check's EntryBlock (epoch
                  metadata attached) to ship through the shared device
                  pipeline and `conclude(valid)` raises the identical
                  (wrapped) error over the device verdict row. A check
                  the async seam cannot represent falls back to
                  run_sync() inside prepare() and returns (None, None),
                  as does the sub-threshold single-signature path.
    """

    __slots__ = ("kind", "_span_name", "_run", "_prep", "_wrap")

    def __init__(self, kind: str, run: Callable[[], None],
                 prep: Callable[[], tuple],
                 wrap: Callable[[BaseException], BaseException]):
        self.kind = kind
        # light.trusting_check / light.light_check
        self._span_name = f"light.{kind}_check"
        self._run = run
        self._prep = prep
        self._wrap = wrap

    def _raise(self, e: BaseException):
        w = self._wrap(e)
        if w is e:
            raise
        raise w from e

    def run_sync(self) -> None:
        with _span(self._span_name):
            try:
                self._run()
            except Exception as e:  # noqa: BLE001 — wrap decides
                self._raise(e)

    def prepare(self):
        try:
            entries, conclude = self._prep()
        except _validation.PrepareUnsupported:
            self.run_sync()
            return None, None
        except Exception as e:  # noqa: BLE001 — wrap decides
            self._raise(e)
        if conclude is None:
            return None, None

        def _conclude(valid) -> None:
            try:
                conclude(valid)
            except Exception as e:  # noqa: BLE001 — wrap decides
                self._raise(e)

        return entries, _conclude


def _wrap_trusting(e: BaseException) -> BaseException:
    """verify_non_adjacent's trusting-stage wrapping (verifier.go:67-80):
    only insufficient tallied power is a (retryable) trust failure — any
    other commit defect is an invalid header."""
    if isinstance(e, ErrNotEnoughVotingPowerSigned):
        return ErrNotEnoughTrust(str(e))
    if isinstance(e, ValueError):
        return ErrInvalidHeader(str(e))
    return e


def _wrap_light(e: BaseException) -> BaseException:
    """The +2/3 commit check's wrapping (verifier.go:143-148): any commit
    defect surfaces as ErrInvalidHeader."""
    if isinstance(e, ErrInvalidHeader):
        return e
    if isinstance(e, ValueError):
        return ErrInvalidHeader(str(e))
    return e


def _light_check(chain_id: str, vals: ValidatorSet, block_id, height: int,
                 commit) -> SigCheck:
    return SigCheck(
        "light",
        run=lambda: verify_commit_light(chain_id, vals, block_id, height, commit),
        prep=lambda: _validation.prepare_commit_light(
            chain_id, vals, block_id, height, commit
        ),
        wrap=_wrap_light,
    )


def _trusting_check(chain_id: str, vals: ValidatorSet, commit,
                    trust_level: Fraction) -> SigCheck:
    return SigCheck(
        "trusting",
        run=lambda: verify_commit_light_trusting(chain_id, vals, commit, trust_level),
        prep=lambda: _validation.prepare_commit_light_trusting(
            chain_id, vals, commit, trust_level
        ),
        wrap=_wrap_trusting,
    )


def prepare_adjacent(
    trusted_header: SignedHeader,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
) -> List[SigCheck]:
    """verifier.go:103-150 host checks; returns the sig work (one +2/3
    commit check) instead of running it."""
    with _span("light.header_checks"):
        if untrusted_header.header.height != trusted_header.header.height + 1:
            raise ValueError("headers must be adjacent in height")
        if header_expired(trusted_header, trusting_period, now):
            raise ErrOldHeaderExpired(f"old header has expired at {now}")
        verify_new_header_and_vals(
            untrusted_header, untrusted_vals, trusted_header, now, max_clock_drift
        )
        # valhash continuity (verifier.go:134-142)
        if untrusted_header.header.validators_hash != trusted_header.header.next_validators_hash:
            raise ErrInvalidHeader(
                f"expected old header next validators ({trusted_header.header.next_validators_hash.hex()}) "
                f"to match those from new header ({untrusted_header.header.validators_hash.hex()})"
            )
    return [
        _light_check(
            trusted_header.header.chain_id,
            untrusted_vals,
            untrusted_header.commit.block_id,
            untrusted_header.header.height,
            untrusted_header.commit,
        )
    ]


def prepare_non_adjacent(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
    trust_level: Fraction,
) -> List[SigCheck]:
    """verifier.go:33-101 host checks; returns the sig work — the
    trust-level check against the OLD set, then the full +2/3 of the NEW
    set, IN ORDER (the service applies verdicts in stage order so error
    precedence matches the sequential path)."""
    with _span("light.header_checks"):
        if untrusted_header.header.height == trusted_header.header.height + 1:
            raise ValueError("headers must be non adjacent in height")
        validate_trust_level(trust_level)
        if header_expired(trusted_header, trusting_period, now):
            raise ErrOldHeaderExpired(f"old header has expired at {now}")
        verify_new_header_and_vals(
            untrusted_header, untrusted_vals, trusted_header, now,
            max_clock_drift
        )
    chain_id = trusted_header.header.chain_id
    return [
        _trusting_check(
            chain_id, trusted_vals, untrusted_header.commit, trust_level
        ),
        _light_check(
            chain_id,
            untrusted_vals,
            untrusted_header.commit.block_id,
            untrusted_header.header.height,
            untrusted_header.commit,
        ),
    ]


def prepare_verify(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
    trust_level: Fraction,
) -> List[SigCheck]:
    """verifier.go:152-176 Verify dispatch, over the prepare seam."""
    if untrusted_header.header.height != trusted_header.header.height + 1:
        return prepare_non_adjacent(
            trusted_header, trusted_vals, untrusted_header, untrusted_vals,
            trusting_period, now, max_clock_drift, trust_level,
        )
    return prepare_adjacent(
        trusted_header, untrusted_header, untrusted_vals,
        trusting_period, now, max_clock_drift,
    )


def verify_adjacent(
    trusted_header: SignedHeader,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
) -> None:
    """verifier.go:103-150: the prepare seam driven synchronously —
    full commit verification on the device engine (verifier.go:143-148);
    any commit defect surfaces as ErrInvalidHeader."""
    with _span("light.verify_adjacent"):
        for chk in prepare_adjacent(
            trusted_header, untrusted_header, untrusted_vals,
            trusting_period, now, max_clock_drift,
        ):
            chk.run_sync()


def verify_non_adjacent(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
    trust_level: Fraction,
) -> None:
    """verifier.go:33-101: the prepare seam driven synchronously."""
    for chk in prepare_non_adjacent(
        trusted_header, trusted_vals, untrusted_header, untrusted_vals,
        trusting_period, now, max_clock_drift, trust_level,
    ):
        chk.run_sync()


def verify(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period: float,
    now: Timestamp,
    max_clock_drift: float,
    trust_level: Fraction,
) -> None:
    """verifier.go:152-176 Verify: dispatch adjacent/non-adjacent."""
    if untrusted_header.header.height != trusted_header.header.height + 1:
        verify_non_adjacent(
            trusted_header, trusted_vals, untrusted_header, untrusted_vals,
            trusting_period, now, max_clock_drift, trust_level,
        )
    else:
        verify_adjacent(
            trusted_header, untrusted_header, untrusted_vals,
            trusting_period, now, max_clock_drift,
        )


def verify_backwards(untrusted_header, trusted_header) -> None:
    """verifier.go:201-234: walk back by hash linkage."""
    if header_expired(trusted_header, 0, trusted_header.header.time):
        pass  # expiry handled by caller in backwards mode
    if untrusted_header.header.chain_id != trusted_header.header.chain_id:
        raise ErrInvalidHeader("header belongs to another chain")
    if not _ts_before(untrusted_header.header.time, trusted_header.header.time):
        raise ErrInvalidHeader(
            "expected older header time to be before newer header time"
        )
    if trusted_header.header.last_block_id.hash != untrusted_header.header.hash():
        raise ErrInvalidHeader(
            f"older header hash {untrusted_header.header.hash().hex()} does not match "
            f"trusted header's last block {trusted_header.header.last_block_id.hash.hex()}"
        )
