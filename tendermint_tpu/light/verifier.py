"""Light-client header verification — hot path #2.

Reference parity: light/verifier.go — VerifyAdjacent (:103),
VerifyNonAdjacent (:33), Verify (:152), VerifyBackwards (:201). The
commit checks route through types.validation (VerifyCommitLight /
VerifyCommitLightTrusting), i.e. through the device batch engine — the
pipelined 1k-header sync workload of BASELINE config #5.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Callable, Iterable, List, Optional

from ..crypto import batch as _batch
from ..libs import metrics as _metrics
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
    SigCheck objects instead of verifying in place. Two ways to run one:

      run_sync()  calls the types.validation entry point the sequential
                  code always called, with the identical error wrapping
                  (verify_adjacent's one check; a hop whose sets are not
                  all-ed25519);
      prepare()   returns (entries, conclude) where `entries` is the
                  check's EntryBlock (epoch metadata attached) and
                  `conclude(valid, on_device=True)` raises the identical
                  (wrapped) error over the verdict row: the batched light
                  service ships the block through the shared device
                  pipeline, verify_non_adjacent hands a hop's two blocks
                  to one batch verifier (ISSUE 36). A check the seam
                  cannot represent falls back to run_sync() inside
                  prepare() and returns (None, None), as does the
                  sub-threshold single-signature path.

    Either way a check is ONE span of its name (light.trusting_check /
    light.light_check): around run_sync(), or around prepare()'s host
    work — selection, tally, sign bytes — wherever the signatures are
    then verified (conclude, an argmin over the verdict row, runs inside
    the verification's span).
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

    def _wrapped(self, fn, *args):
        """fn(*args), its errors through the wrap (which leaves a
        PrepareUnsupported, like any non-ValueError, as it is)."""
        try:
            return fn(*args)
        except Exception as e:  # noqa: BLE001 — wrap decides
            w = self._wrap(e)
            if w is e:
                raise
            raise w from e

    def run_sync(self) -> None:
        with _span(self._span_name):
            self._wrapped(self._run)

    def prepare(self):
        try:
            with _span(self._span_name):
                entries, conclude = self._wrapped(self._prep)
        except _validation.PrepareUnsupported:
            self.run_sync()
            return None, None
        if conclude is None:
            return None, None
        return entries, lambda valid, on_device=True: self._wrapped(
            conclude, valid, on_device)


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
                    trust_level: Fraction, epoch_lookup: bool) -> SigCheck:
    return SigCheck(
        "trusting",
        run=lambda: verify_commit_light_trusting(chain_id, vals, commit, trust_level),
        prep=lambda: _validation.prepare_commit_light_trusting(
            chain_id, vals, commit, trust_level, epoch_lookup
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
    epoch_lookup: bool = True,
) -> List[SigCheck]:
    """verifier.go:33-101 host checks; returns the sig work — the
    trust-level check against the OLD set, then the full +2/3 of the NEW
    set, IN ORDER (the service applies verdicts in stage order so error
    precedence matches the sequential path). `epoch_lookup` False leaves
    the old set's device table to the caller (verify_non_adjacent, which
    knows only after the +2/3 check's prepare whether one serves both)."""
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
            chain_id, trusted_vals, untrusted_header.commit, trust_level,
            epoch_lookup,
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


@dataclass
class StagePlan:
    """One prepared sig-check stage: exactly one of {entries+conclude,
    error, neither} — `neither` means the stage completed synchronously
    at prepare time (sub-threshold commit) and passed."""

    kind: str
    entries: object = None
    conclude: Optional[Callable] = None
    error: Optional[BaseException] = None


def prepare_stages(checks: Iterable[SigCheck]) -> List[StagePlan]:
    """The host half of a verification's sig checks, in order. Never
    raises — with conclude_stages, the ONE implementation of the
    error-precedence contract that makes a verification whose checks are
    verified together (the light service: many requests' blocks in one
    device batch; verify_non_adjacent: a hop's two blocks in one
    submission) byte-identical to checks run one after the other, where
    the trusting stage raises before the +2/3 stage runs at all:

      * a host-side failure while preparing stage k is recorded ON stage
        k and later stages are not prepared (sequential never reached
        them);
      * verdicts are applied in stage order — stage k's sig failure masks
        anything recorded for stage k+1."""
    stages: List[StagePlan] = []
    for chk in checks:
        try:
            entries, conclude = chk.prepare()
        except Exception as e:  # noqa: BLE001 — the stage's verdict
            stages.append(StagePlan(chk.kind, error=e))
            break
        stages.append(StagePlan(chk.kind, entries=entries, conclude=conclude))
    return stages


def conclude_stages(stages: List[StagePlan], verdicts,
                    on_device: bool = True) -> Optional[BaseException]:
    """Apply verdicts in SEQUENTIAL stage order. `verdicts` has one item
    per stage that has entries, in that order — each a bool validity row
    or the exception its pipeline future resolved with; `on_device` says
    where the rows were computed. Returns the error the sequential path
    raises (byte-identical) or None on acceptance."""
    vi = 0
    for st in stages:
        if st.error is not None:
            return st.error
        if st.entries is None:
            continue  # verified synchronously at prepare time
        v = verdicts[vi]
        vi += 1
        if isinstance(v, BaseException):
            return v  # pipeline-level failure (DispatchError): not parity
        try:
            st.conclude(v, on_device)
        except Exception as e:  # noqa: BLE001 — the wrapped stage error
            return e
    return None


def _verify_together(checks: List[SigCheck], sets) -> None:
    """checks[i], against sets[i], in order — their signatures as ONE
    submission to crypto.batch's ed25519 verifier where their total
    reaches the device (ops.backend DEVICE_THRESHOLD). Under it each
    check stays a host batch of its own, as where checks run one after
    the other: the host verifies one signature at a time either way, and
    whoever counts host batches keeps reading whole checks. That verifier
    takes the leading checks whose sets are all-ed25519 (asked of a set
    only once the checks before it have prepared: a refused attempt
    touches nothing of the new set); the rest run_sync() after them."""
    def ed25519_first():
        for chk, vals in zip(checks, sets):
            if vals.ed25519_columns() is None:
                return
            yield chk

    stages = prepare_stages(ed25519_first())
    live = [st for st in stages if st.entries is not None]
    verdicts, on_device = [], False
    if live:
        from ..ops import backend as _backend

        n = sum(len(st.entries) for st in live)
        together = n >= _backend.DEVICE_THRESHOLD
        if together and len(live) == 2 and live[1].entries.epoch_key is not None:
            # the new set is warm: the old one rides its table if it maps
            # onto it (a set that moves slowly), else ships its keys
            live[0].entries = _validation.on_table_of(
                live[0].entries, sets[0], live[1].entries)
        with _span("light.hop_verify", n=n, stages=len(live)) as sp:
            for group in [live] if together else [[st] for st in live]:
                bv = _batch.create_batch_verifier(sets[0].validators[0].pub_key)
                for st in group:
                    bv.add_block(st.entries)
                rows = iter(bv.verify()[1])
                verdicts += [list(itertools.islice(rows, len(st.entries)))
                             for st in group]
                on_device = bv.on_device
                if on_device and len(group) > 1:
                    _metrics.ops_metrics().light_hops_fused.inc()
            sp.note(on_device=on_device)
            err = conclude_stages(stages, verdicts, on_device)
    else:
        err = conclude_stages(stages, verdicts)
    if err is not None:
        raise err
    for chk in checks[len(stages):]:
        chk.run_sync()


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
    """verifier.go:33-101: the prepare seam driven synchronously. Both
    checks are over the SAME commit, so their signatures go as one
    submission (at 100 validators 34 + 67: one launch, where 34 alone
    are under the device threshold)."""
    _verify_together(
        prepare_non_adjacent(
            trusted_header, trusted_vals, untrusted_header, untrusted_vals,
            trusting_period, now, max_clock_drift, trust_level,
            epoch_lookup=False,
        ),
        (trusted_vals, untrusted_vals),
    )


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
