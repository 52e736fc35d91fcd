"""The plain reference of a light client that catches up by skipping:
Tendermint v0.35 light/client.go verifySkipping (:639-720, the 9/16
pivot of :44-45) over light/verifier.go Verify / VerifyNonAdjacent
(:33-101: the header checks, then VerifyCommitLightTrusting against the
set the client trusts, then VerifyCommitLight against the header's own)
and types/validation.go VerifyCommitLightTrusting (verifyCommitBatch,
countAllSignatures=false, lookUpByIndex=false: validators found by
address, a second vote of one validator refused, the tally stopped above
total * numerator / denominator, and only then the signatures), with the
root of trust checked as light.NewClient checks it. Sequential, one
signature after another with OpenSSL, on the chain builder's own records.
No batching, no device, none of the program's code; the +2/3 check and
SignedHeader.ValidateBasic are reference_light's.

Errors are (exception type name, message), upstream's as the program
spells them (tests/benchmark holds them against light.Client).
"""

from __future__ import annotations

import dataclasses

from cryptography.exceptions import InvalidSignature

from . import lightchain, reference_light, wire

INVALID = reference_light.INVALID
NO_TRUST = "ErrNotEnoughTrust"
PIVOT = (9, 16)


@dataclasses.dataclass
class CatchUp:
    """What one catch-up did: the heights verified in order (the root
    first), the attempts refused for lack of trusted power as (from, to),
    the heights asked of the provider in order, the signatures the checks
    that passed looked at, and None or the error that ended it."""
    trace: list
    refused: list
    fetched: list
    sigs: int
    error: object = None


def _validator(v) -> str:
    """The program's Validator as its double-vote message prints it."""
    return (f"Validator(address={v.address!r}, pub_key=PubKeyEd25519{{"
            f"{v.pub.hex().upper()}}}, voting_power={v.power}, "
            f"proposer_priority={v.priority})")


def verify_commit_light_trusting(chain_id: str, trusted_vals, blk, level):
    """(error or None, signatures looked at) of VerifyCommitLightTrusting:
    `blk`'s commit against `trusted_vals`, a set that need not be the one
    that signed. Selection and tally come first, as verifyCommitBatch has
    them: a starved tally looks at no signature. A check that fails
    counts none."""
    num, den = level
    needed = sum(v.power for v in trusted_vals) * num // den
    by_address = {v.address: (row, v) for row, v in enumerate(trusted_vals)}
    seen, selected, tallied = {}, [], 0
    for idx, rec in enumerate(blk.sigs):
        if rec is None:
            continue
        found = by_address.get(blk.vals[idx].address)
        if found is None:
            continue
        row, val = found
        if row in seen:
            return ("ValueError", f"double vote from {_validator(val)} "
                                  f"({seen[row]} and {idx})"), 0
        seen[row] = idx
        selected.append((idx, val))
        tallied += val.power
        if tallied > needed:
            break
    if tallied <= needed:
        return ("ErrNotEnoughVotingPowerSigned",
                "invalid commit -- insufficient voting power: "
                f"got {tallied}, needed more than {needed}"), 0
    tpl = wire.sign_bytes_template(chain_id, blk.height, blk.block_hash)
    for idx, val in selected:
        seconds, nanos, sig = blk.sigs[idx]
        try:
            reference_light._key(val.pub).verify(
                sig, wire.sign_bytes(tpl, seconds, nanos))
        except InvalidSignature:
            return ("ValueError",
                    f"wrong signature (#{idx}): {sig.hex().upper()}"), 0
    return None, len(selected)


def _commit_light(chain_id: str, vals, blk):
    """(error or None, signatures looked at) of reference_light's +2/3
    check; a check that fails counts none."""
    said = reference_light.verify_commit_light(
        chain_id, vals, blk.height, blk.block_hash, blk.sigs)
    if said is not None:
        return said, 0
    needed = sum(v.power for v in vals) * 2 // 3
    tallied = looked = 0
    for idx, rec in enumerate(blk.sigs):
        if rec is not None and tallied <= needed:
            tallied += vals[idx].power
            looked += 1
    return None, looked


def _new_header_and_vals(trusted, untrusted, vals, now, drift_s: int):
    """verifyNewHeaderAndVals (verifier.go:236-283)."""
    th, uh = trusted.header, untrusted.header
    bad = reference_light._validate_basic(th.chain_id, untrusted)
    if bad is not None:
        return (INVALID, f"untrustedHeader.ValidateBasic failed: {bad}")
    if uh.height <= th.height:
        return (INVALID, f"expected new header height {uh.height} to be "
                         f"greater than one of old header {th.height}")
    if (uh.seconds, uh.nanos) <= (th.seconds, th.nanos):
        return (INVALID, "expected new header time to be after old header time")
    if (uh.seconds, uh.nanos) >= (now[0] + drift_s, now[1]):
        return (INVALID, "new header has a time from the future "
                         "(max clock drift exceeded)")
    supplied = lightchain.valset_hash(vals)
    if uh.validators_hash != supplied:
        return (INVALID, f"expected new header validators "
                         f"({uh.validators_hash.hex()}) to match those "
                         f"supplied ({supplied.hex()})")
    return None


def verify_non_adjacent(trusted_pair, untrusted, vals, period_s: int, now,
                        drift_s: int, level):
    """(error or None, signatures looked at) of VerifyNonAdjacent:
    `trusted_pair` is (lightchain.Block, the set the client holds for it),
    `untrusted` a Block and `vals` the set its light block supplies."""
    trusted, trusted_vals = trusted_pair
    th, uh = trusted.header, untrusted.header
    if uh.height == th.height + 1:
        return ("ValueError", "headers must be non adjacent in height"), 0
    num, den = level
    if num * 3 < den or num > den or den == 0:
        return ("ValueError", "trustLevel must be within [1/3, 1], given "
                              f"Fraction(numerator={num}, denominator={den})"), 0
    if now >= (th.seconds + period_s, th.nanos):
        return ("ErrOldHeaderExpired",
                f"old header has expired at {reference_light._ts(*now)}"), 0
    bad = _new_header_and_vals(trusted, untrusted, vals, now, drift_s)
    if bad is not None:
        return bad, 0
    said, looked = verify_commit_light_trusting(
        th.chain_id, trusted_vals, untrusted, level)
    if said is not None:
        # verifier.go:67-80: only a starved tally may be retried closer
        kind = NO_TRUST if said[0] == "ErrNotEnoughVotingPowerSigned" else INVALID
        return (kind, said[1]), looked
    said, more = _commit_light(th.chain_id, vals, untrusted)
    return (None if said is None else (INVALID, said[1])), looked + more


def verify(trusted_pair, untrusted, vals, period_s, now, drift_s, level):
    """verifier.go:152-176 Verify: adjacent or not."""
    trusted = trusted_pair[0]
    if untrusted.header.height != trusted.header.height + 1:
        return verify_non_adjacent(trusted_pair, untrusted, vals, period_s,
                                   now, drift_s, level)
    said = reference_light.verify_adjacent(trusted, untrusted, vals, period_s,
                                           now, drift_s)
    return said, (0 if said is not None else
                  _commit_light(trusted.header.chain_id, vals, untrusted)[1])


def verify_skipping(fetch, trusted, target, period_s, now, drift_s, level,
                    out: CatchUp) -> None:
    """verifySkipping: try the far header against the trusted one; when too
    little trusted power signed it, fetch the header 9/16 of the way and
    try that first; a pivot that verifies becomes the trusted one and the
    header fetched before it is tried next. `fetch(height)` gives (Block,
    the set supplied); `trusted` and `target` are such pairs."""
    stack, current = [target], trusted
    out.trace.append(trusted[0].height)
    while stack:
        blk, vals = stack[-1]
        said, looked = verify(current, blk, vals, period_s, now, drift_s, level)
        out.sigs += looked
        if said is None:
            out.trace.append(blk.height)
            current = stack.pop()
            continue
        if said[0] != NO_TRUST:
            out.error = said
            return
        out.refused.append((current[0].height, blk.height))
        gap = blk.height - current[0].height
        pivot = max(current[0].height + gap * PIVOT[0] // PIVOT[1],
                    current[0].height + 1)
        if pivot >= blk.height:
            out.error = said
            return
        out.fetched.append(pivot)
        stack.append(fetch(pivot))


def catch_up(fetch, chain_id: str, trusted_height: int, trusted_hash: bytes,
             target_height: int, period_s: int, now, drift_s: int,
             level=(1, 3)) -> CatchUp:
    """A fresh client on an empty store, light.NewClient then
    VerifyLightBlockAtHeight: the root fetched and held to the hash it was
    given, to its own set and to +2/3 of it, asked of the witness too;
    then the target fetched, verified by skipping, and asked of the
    witness. Primary and witness are the one `fetch`."""
    out = CatchUp([], [], [trusted_height], 0)
    root, root_vals = fetch(trusted_height)
    if root.block_hash != trusted_hash:
        out.error = ("ValueError", f"expected header's hash {trusted_hash.hex()}"
                                   f", but got {root.block_hash.hex()}")
        return out
    bad = reference_light._validate_basic(chain_id, root)
    if bad is not None:
        out.error = ("ValueError", bad)
        return out
    if root.header.validators_hash != lightchain.valset_hash(root_vals):
        out.error = ("ValueError",
                     "expected header's validators to match those supplied")
        return out
    said, out.sigs = _commit_light(chain_id, root_vals, root)
    if said is not None:
        out.error = said
        return out
    out.fetched.append(trusted_height)          # the witness's root
    out.fetched.append(target_height)
    verify_skipping(fetch, (root, root_vals), fetch(target_height), period_s,
                    now, drift_s, level, out)
    if out.error is None:
        out.fetched.append(target_height)       # the witness's last header
    return out
