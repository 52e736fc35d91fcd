"""Light client — stateful header verification with bisection.

Reference parity: light/client.go — trust options bootstrap (:370),
VerifyLightBlockAtHeight (:406), sequential verification (:546), skipping
verification with the 9/16 bisection pivot (:639, :44-45), backwards
verification (:878), primary/witness management (:935-1035), and the
divergence detector (detector.go) comparing the primary's headers against
witnesses.
"""

from __future__ import annotations

import http.client as _http
from dataclasses import dataclass
from typing import Callable, List, Optional

from ..libs import metrics as _metrics
from ..libs.timeutil import now_ts as _now_ts
from ..observability import trace as _trace
from ..types import Fraction
from ..wire.canonical import Timestamp
from . import verifier
from .provider import ErrLightBlockNotFound, LightBlock, Provider
from .store import LightStore

DEFAULT_PRUNING_SIZE = 1000
DEFAULT_MAX_CLOCK_DRIFT = 10.0  # seconds (light/client.go:56)

# bisection pivot: 9/16 (light/client.go:44-45)
_BISECT_NUM = 9
_BISECT_DEN = 16

_span = _trace.span
_ops = _metrics.ops_metrics      # the process-wide set, cached there


@dataclass
class TrustOptions:
    """light/client.go TrustOptions: period + (height, hash) root of trust."""

    period: float  # seconds
    height: int
    hash: bytes

    def validate(self) -> None:
        if self.period <= 0:
            raise ValueError("trusting period must be greater than zero")
        if self.height <= 0:
            raise ValueError("trust option height must be greater than zero")
        if len(self.hash) != 32:
            raise ValueError(f"expected hash size to be 32 bytes, got {len(self.hash)}")


class ErrLightClientAttack(RuntimeError):
    """detector.go: divergence between primary and witness."""


class ErrNoWitnesses(RuntimeError):
    """light/errors.go ErrNoWitnesses."""


class ErrFailedHeaderCrossReferencing(RuntimeError):
    """light/errors.go: no witness could confirm the primary's header."""


def make_attack_evidence(conflicted: LightBlock, trusted: LightBlock, common: LightBlock):
    """detector.go:406-423 newLightClientAttackEvidence. The common height
    encodes the attack form: lunatic (forged state hashes) points at the
    last common header; equivocation/amnesia at the conflicting height."""
    from ..types.evidence import LightBlockData, LightClientAttackEvidence

    ev = LightClientAttackEvidence(
        conflicting_block=LightBlockData.from_parts(
            conflicted.signed_header, conflicted.validators
        ),
        common_height=0,
    )
    if ev.conflicting_header_is_invalid(trusted.signed_header.header):
        ev.common_height = common.height
        ev.timestamp = common.signed_header.header.time
        ev.total_voting_power = common.validators.total_voting_power()
    else:
        ev.common_height = trusted.height
        ev.timestamp = trusted.signed_header.header.time
        ev.total_voting_power = trusted.validators.total_voting_power()
    ev.byzantine_validators = ev.get_byzantine_validators(
        common.validators, trusted.signed_header
    )
    return ev


class Client:
    """light/client.go:130-1100."""

    def __init__(
        self,
        chain_id: str,
        trust_options: TrustOptions,
        primary: Provider,
        witnesses: List[Provider],
        store: LightStore,
        trust_level: Fraction = verifier.DEFAULT_TRUST_LEVEL,
        max_clock_drift: float = DEFAULT_MAX_CLOCK_DRIFT,
        sequential: bool = False,
        pruning_size: int = DEFAULT_PRUNING_SIZE,
        now_fn: Optional[Callable[[], Timestamp]] = None,
    ):
        trust_options.validate()
        verifier.validate_trust_level(trust_level)
        # injected clock (ISSUE 11 satellite): simnet-driven light
        # clients read virtual time through here; the wall-clock default
        # lives in libs/timeutil, outside tmlint's deterministic scope
        self._now_ts = now_fn or _now_ts
        self._chain_id = chain_id
        self._trusting_period = trust_options.period
        self._trust_level = trust_level
        self._max_clock_drift = max_clock_drift
        self._primary = primary
        self._witnesses = list(witnesses)
        self._store = store
        self._sequential = sequential
        self._pruning_size = pruning_size
        self._initialize(trust_options)

    # -- bootstrap (client.go:370-404) -----------------------------------

    def _initialize(self, opts: TrustOptions) -> None:
        existing = self._store.latest_light_block()
        if existing is not None:
            return  # already bootstrapped (checkTrustedHeaderUsingOptions simplified)
        lb = self._fetch(self._primary, opts.height, "primary")
        if lb.hash() != opts.hash:
            raise ValueError(
                f"expected header's hash {opts.hash.hex()}, but got {lb.hash().hex()}"
            )
        lb.signed_header.validate_basic(self._chain_id)
        if lb.signed_header.header.validators_hash != lb.validators.hash():
            raise ValueError("expected header's validators to match those supplied")
        # verify the commit against its own validator set (1/1 trust at root)
        from ..types.validation import verify_commit_light

        verify_commit_light(
            self._chain_id,
            lb.validators,
            lb.signed_header.commit.block_id,
            lb.height,
            lb.signed_header.commit,
        )
        # cross-check BEFORE persisting: a failed construction must not
        # leave the store bootstrapped (a retry would skip this check)
        self._compare_first_header_with_witnesses(lb)
        self._store.save_light_block(lb)

    def _compare_first_header_with_witnesses(self, root: LightBlock) -> None:
        """client.go:1086 compareFirstHeaderWithWitnesses: every reachable
        witness must agree with the primary's root header. A witness that
        cannot serve the height (unreachable / missing block) is ignored —
        the reference keeps such witnesses too; one that serves a
        DIFFERENT header is a conflict the operator must resolve (raise).
        No witnesses at all is ErrNoWitnesses (light/errors.go): a client
        with nothing to cross-check against must not bootstrap silently."""
        if not self._witnesses:
            raise ErrNoWitnesses(
                "no witnesses configured; cannot cross-check the root header"
            )
        compared = 0
        for i, w in enumerate(self._witnesses):
            try:
                wlb = self._fetch(w, root.height, "witness")
            except (OSError, ValueError, KeyError, TimeoutError,
                    ConnectionError, RuntimeError, _http.HTTPException):
                continue  # unreachable / missing block: ignore this witness
            compared += 1
            if wlb.hash() != root.hash():
                # compareNewHeaderWithWitness: hash mismatch at the root is
                # errConflictingHeaders — the operator must pick a side
                raise ErrLightClientAttack(
                    f"witness {i} has a different header at the root height "
                    f"{root.height}: {wlb.hash().hex()} vs {root.hash().hex()}"
                )
        if compared == 0:
            raise ErrFailedHeaderCrossReferencing(
                f"none of the {len(self._witnesses)} configured witnesses "
                f"could serve the root header at height {root.height}"
            )

    # -- public API -------------------------------------------------------

    def verify_header(self, new_header, now: Optional[Timestamp] = None) -> None:
        """client.go:456 VerifyHeader: verify an externally obtained
        header — already-trusted headers must match byte-for-byte; fresh
        ones are fetched from the primary (with vals) and must hash-match
        before the normal verification path runs."""
        if new_header is None:
            raise ValueError("nil header")
        if new_header.height <= 0:
            raise ValueError("negative or zero height")
        existing = self._store.light_block(new_header.height)
        if existing is not None:
            if existing.hash() != new_header.hash():
                raise ValueError(
                    f"existing trusted header {existing.hash().hex()} does not "
                    f"match newHeader {new_header.hash().hex()}"
                )
            return
        # compare the primary's header BEFORE any verification/storage
        # (client.go:482): a mismatch must not pin the primary's fork into
        # the trusted store
        probe = self._light_block_from_primary(new_header.height)
        if probe.hash() != new_header.hash():
            raise ValueError(
                f"header from primary {probe.hash().hex()} does not match "
                f"newHeader {new_header.hash().hex()}"
            )
        # then verify through the normal dispatch (forward bisection or
        # the backwards hash-link walk for heights below trust) — a height
        # below the pruning window must never be stored unverified
        lb = self.verify_light_block_at_height(new_header.height, now)
        if lb.hash() != new_header.hash():
            raise ValueError(
                f"verified header {lb.hash().hex()} does not match "
                f"newHeader {new_header.hash().hex()}"
            )

    def last_trusted_height(self) -> int:
        """client.go:801 (-1 when empty)."""
        lb = self._store.latest_light_block()
        return lb.height if lb is not None else -1

    def first_trusted_height(self) -> int:
        """client.go:809 (-1 when empty)."""
        return self._store.first_light_block_height()

    def chain_id(self) -> str:
        return self._chain_id

    def primary(self) -> Provider:
        return self._primary

    def witnesses(self) -> List[Provider]:
        return list(self._witnesses)

    def add_provider(self, p: Provider) -> None:
        """client.go:841."""
        self._witnesses.append(p)

    def remove_witnesses(self, indexes: List[int]) -> None:
        """client.go:975: drop misbehaving witnesses (descending order so
        earlier removals do not shift later indexes)."""
        uniq = sorted(set(indexes), reverse=True)
        if any(i < 0 or i >= len(self._witnesses) for i in uniq):
            raise IndexError(f"witness index out of range: {indexes}")
        if len(self._witnesses) <= len(uniq):
            raise RuntimeError("cannot remove all witnesses")
        for i in uniq:
            self._witnesses.pop(i)

    def cleanup(self) -> None:
        """client.go:849: remove all stored light blocks."""
        self._store.prune(0)

    def trusted_light_block(self, height: int) -> Optional[LightBlock]:
        if height == 0:
            return self._store.latest_light_block()
        return self._store.light_block(height)

    def update(self, now: Optional[Timestamp] = None) -> Optional[LightBlock]:
        """client.go Update: verify the primary's latest header."""
        latest = self._fetch(self._primary, 0, "primary")
        trusted = self._store.latest_light_block()
        if trusted is not None and latest.height <= trusted.height:
            return None
        return self.verify_light_block_at_height(latest.height, now)

    def verify_light_block_at_height(
        self, height: int, now: Optional[Timestamp] = None
    ) -> LightBlock:
        """client.go:406-487."""
        if height <= 0:
            raise ValueError("height must be positive")
        now = now or self._now_ts()
        with _span("light.client.verify_at_height", to=height) as sp:
            existing = self._store.light_block(height)
            if existing is not None:
                return existing
            latest_trusted = self._store.latest_light_block()
            if latest_trusted is None:
                raise RuntimeError("no trusted state — client not initialized")
            sp.note(**{"from": latest_trusted.height})
            if height < latest_trusted.height:
                return self._backwards(latest_trusted, height, now)
            new_block = self._light_block_from_primary(height)
            self._verify_light_block(new_block, now)
            return new_block

    # -- verification strategies -----------------------------------------

    def _verify_light_block(self, new_block: LightBlock, now: Timestamp) -> None:
        closest = self._store.light_block_before(new_block.height) or \
            self._store.latest_light_block()
        if self._sequential:
            self._verify_sequential(closest, new_block, now)
        else:
            self._verify_skipping_against_witnesses(closest, new_block, now)
        self._store.save_light_block(new_block)
        self._store.prune(self._pruning_size)

    def _verify_sequential(
        self, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> None:
        """client.go:546-637: fetch and verify every intermediate header."""
        current = trusted
        for h in range(trusted.height + 1, new_block.height + 1):
            if h == new_block.height:
                interim = new_block
            else:
                interim = self._light_block_from_primary(h)
            verifier.verify_adjacent(
                current.signed_header,
                interim.signed_header,
                interim.validators,
                self._trusting_period,
                now,
                self._max_clock_drift,
            )
            self._store.save_light_block(interim)
            current = interim

    def _verify_skipping(
        self, source: Provider, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> List[LightBlock]:
        """client.go:639-720 verifySkipping: bisection with 9/16 pivot. The
        pivots are kept as a stack: after a pivot verifies, the pivot fetched
        before it is tried next (upstream starts again from the far target
        and walks its cache down to the same header: the same hops, more
        refused attempts)."""
        blocks_to_verify = [new_block]  # the far target, then each pivot
        verified = [trusted]
        current = trusted
        while True:
            target = blocks_to_verify[-1]
            try:
                self._attempt(current, target, now)
                verified.append(target)
                # a verified pivot leaves the stack and is the new lower
                # bound; the pivot fetched before it is tried next
                blocks_to_verify.pop()
                if not blocks_to_verify:
                    return verified
                current = target
            except verifier.ErrNotEnoughTrust:
                # bisect: pivot at 9/16 between current and target
                pivot = (
                    current.height
                    + (target.height - current.height) * _BISECT_NUM // _BISECT_DEN
                )
                if pivot <= current.height:
                    pivot = current.height + 1
                if pivot >= target.height:
                    raise
                blocks_to_verify.append(self._light_block_from(source, pivot))

    def _attempt(self, current: LightBlock, target: LightBlock,
                 now: Timestamp) -> None:
        """One verifier.verify of the bisection, as span
        light.bisect.attempt (args from, to, outcome) and in the
        light_hops counters; an attempt refused for lack of trusted power
        is also a span of its own name, light.bisect.refused, because a
        reader of span names cannot tell the outcomes apart."""
        with _span("light.bisect.attempt", **{"from": current.height,
                                              "to": target.height}) as sp:
            try:
                verifier.verify(
                    current.signed_header,
                    current.validators,
                    target.signed_header,
                    target.validators,
                    self._trusting_period,
                    now,
                    self._max_clock_drift,
                    self._trust_level,
                )
            except verifier.ErrNotEnoughTrust:
                sp.note(outcome="not_enough_trust")
                sp.also("light.bisect.refused")
                _ops().light_hops.inc(outcome="refused")
                raise
            sp.note(outcome="verified")
        _ops().light_hops.inc(outcome="verified")

    def _verify_skipping_against_witnesses(
        self, trusted: LightBlock, new_block: LightBlock, now: Timestamp
    ) -> None:
        """client.go:722-780 + detector.go: verify against the primary,
        then cross-check the verified trace with every witness."""
        trace = self._verify_skipping(self._primary, trusted, new_block, now)
        with _span("light.detect_divergence", hops=len(trace) - 1):
            self._detect_divergence(trace, now)

    # -- divergence detector (detector.go) --------------------------------

    def _detect_divergence(self, primary_trace: List[LightBlock], now: Timestamp) -> None:
        """detector.go:28-118 detectDivergence: compare the end of the
        verified trace with each witness; on a conflicting header, examine
        it against the trace, build LightClientAttackEvidence for both
        sides, submit, and halt. Witnesses that conflict but cannot sustain
        their own header are removed; if no witness matches, verification
        fails with ErrFailedHeaderCrossReferencing."""
        if not primary_trace or len(primary_trace) < 2:
            return  # nothing beyond the root of trust to cross-examine
        if not self._witnesses:
            raise ErrNoWitnesses("no witnesses connected. falling back to primary")
        last = primary_trace[-1]
        header_matched = False
        to_remove: List[int] = []
        for i, witness in enumerate(self._witnesses):
            try:
                w_block = self._fetch(witness, last.height, "witness")
            except (ErrLightBlockNotFound, ConnectionError):
                continue  # witness doesn't have it (yet) — tolerated
            if w_block.hash() != last.hash():
                # raises ErrLightClientAttack when the conflict is real;
                # returns normally when the witness can't sustain it
                self._handle_conflicting_headers(primary_trace, w_block, i, now)
                to_remove.append(i)
            else:
                header_matched = True
        for i in reversed(to_remove):
            del self._witnesses[i]
        if not header_matched:
            raise ErrFailedHeaderCrossReferencing(
                "all witnesses have either not responded, don't have the "
                "block or sent invalid blocks"
            )

    def _handle_conflicting_headers(
        self,
        primary_trace: List[LightBlock],
        challenging_block: LightBlock,
        witness_index: int,
        now: Timestamp,
    ) -> None:
        """detector.go:228-290 handleConflictingHeaders: hold the witness
        as source of truth -> evidence against the primary; then reverse
        roles -> evidence against the witness; always halt with
        ErrLightClientAttack."""
        witness = self._witnesses[witness_index]
        try:
            witness_trace, primary_block = self._examine_conflicting_header_against_trace(
                primary_trace, challenging_block, witness, now
            )
        except (ValueError, RuntimeError, ErrLightBlockNotFound, ConnectionError):
            # witness couldn't sustain its own header — not an attack proof
            return
        common, trusted_block = witness_trace[0], witness_trace[-1]
        ev_against_primary = make_attack_evidence(primary_block, trusted_block, common)
        self._send_evidence(ev_against_primary, witness)

        # Reverse: examine the witness's trace holding the primary as the
        # source of truth (best effort — we halt either way).
        try:
            primary_trace2, witness_block = self._examine_conflicting_header_against_trace(
                witness_trace, primary_block, self._primary, now
            )
            common2, trusted2 = primary_trace2[0], primary_trace2[-1]
            ev_against_witness = make_attack_evidence(witness_block, trusted2, common2)
            self._send_evidence(ev_against_witness, self._primary)
        except (ValueError, RuntimeError, ErrLightBlockNotFound, ConnectionError):
            pass
        raise ErrLightClientAttack(
            f"conflicting header at height {challenging_block.height}: "
            f"witness #{witness_index} {challenging_block.hash().hex()} vs "
            f"primary {primary_trace[-1].hash().hex()}"
        )

    def _examine_conflicting_header_against_trace(
        self,
        trace: List[LightBlock],
        target_block: LightBlock,
        source: Provider,
        now: Timestamp,
    ) -> tuple:
        """detector.go:289-374 examineConflictingHeaderAgainstTrace: walk
        the trace verifying the source's chain at each intermediate height
        until the bifurcation point. Returns (source_trace,
        divergent_trace_block)."""
        if target_block.height < trace[0].height:
            raise ValueError(
                f"target block height {target_block.height} below trusted "
                f"height {trace[0].height}"
            )
        previously_verified: Optional[LightBlock] = None
        source_trace: List[LightBlock] = []
        for idx, trace_block in enumerate(trace):
            # forward lunatic: the trace extends beyond the target
            if trace_block.height > target_block.height:
                tb_t = trace_block.signed_header.header.time
                tg_t = target_block.signed_header.header.time
                if (tb_t.seconds, tb_t.nanos) > (tg_t.seconds, tg_t.nanos):
                    raise RuntimeError(
                        "sanity: trace block after target must not be newer"
                    )
                if previously_verified.height != target_block.height:
                    source_trace = self._verify_skipping(
                        source, previously_verified, target_block, now
                    )
                return source_trace, trace_block
            if trace_block.height == target_block.height:
                source_block = target_block
            else:
                source_block = self._fetch(
                    source, trace_block.height,
                    "primary" if source is self._primary else "witness")
            if idx == 0:
                if source_block.hash() != trace_block.hash():
                    raise ValueError(
                        "trusted block differs from the source's first block"
                    )
                previously_verified = source_block
                continue
            source_trace = self._verify_skipping(
                source, previously_verified, source_block, now
            )
            if source_block.hash() != trace_block.hash():
                return source_trace, trace_block  # bifurcation point
            previously_verified = source_block
        raise RuntimeError("no divergence found along the trace")

    def _send_evidence(self, ev, receiver: Provider) -> None:
        """detector.go:220-226 sendEvidence (best effort)."""
        try:
            receiver.report_evidence(ev)
        except Exception:  # noqa: BLE001 — provider failure must not mask the halt
            pass

    def _backwards(
        self, trusted: LightBlock, height: int, now: Timestamp
    ) -> LightBlock:
        """client.go:878-933: hash-linked walk to an older header."""
        current = trusted
        for h in range(trusted.height - 1, height - 1, -1):
            interim = self._light_block_from_primary(h)
            verifier.verify_backwards(interim.signed_header, current.signed_header)
            self._store.save_light_block(interim)
            current = interim
        return current

    # -- provider plumbing (client.go:935-1035) ---------------------------

    def _fetch(self, provider: Provider, height: int, source: str) -> LightBlock:
        """Every provider call of the client: span light.fetch, and the
        light_blocks_fetched counter for the calls that answered."""
        with _span("light.fetch", height=height, source=source):
            lb = provider.light_block(height)
        _ops().light_blocks_fetched.inc()
        return lb

    def _light_block_from_primary(self, height: int) -> LightBlock:
        try:
            lb = self._fetch(self._primary, height, "primary")
        except (ErrLightBlockNotFound, ConnectionError):
            # primary failed: promote a witness (client.go findNewPrimary)
            for i, w in enumerate(self._witnesses):
                try:
                    lb = self._fetch(w, height, "witness")
                except (ErrLightBlockNotFound, ConnectionError):
                    continue
                self._witnesses.pop(i)
                self._witnesses.append(self._primary)
                self._primary = w
                return lb
            raise
        return lb

    def _light_block_from(self, source: Provider, height: int) -> LightBlock:
        if source is self._primary:
            return self._light_block_from_primary(height)
        return self._fetch(source, height, "witness")
