"""The plain reference of a light-client step: Tendermint v0.35
light/verifier.go VerifyAdjacent (:103-150) with verifyNewHeaderAndVals
(:236-283), SignedHeader.ValidateBasic (types/block.go) and
types/validation.go VerifyCommitLight (verifyCommitSingle,
countAllSignatures=false, lookUpByIndex=true), in upstream's order, one
signature after another with OpenSSL until more than two thirds of the
power has signed, on the chain builder's own records. No batching, no
device, none of the program's code.

It returns what the program must raise: None for a header that verifies,
else (exception type name, message) — upstream's errors as the program
spells them (tests/benchmark holds them against
light.verifier.verify_adjacent driven synchronously)."""

from __future__ import annotations

import functools

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from . import lightchain, wire

INVALID = "ErrInvalidHeader"


@functools.lru_cache(maxsize=4096)
def _key(pub: bytes) -> Ed25519PublicKey:
    return Ed25519PublicKey.from_public_bytes(pub)


def _ts(seconds: int, nanos: int) -> str:
    """wire.canonical.Timestamp's repr, which the expiry message carries."""
    return f"Timestamp(seconds={seconds}, nanos={nanos})"


def _validate_basic(chain_id: str, blk):
    """SignedHeader.ValidateBasic, as far as a built block can fail it."""
    h = blk.header
    if len(h.chain_id) > 50:
        return "chain_id is too long"
    if h.height <= 0:
        return "negative Height" if h.height < 0 else "zero Height"
    if not blk.sigs:
        return "no signatures in commit"
    if h.chain_id != chain_id:
        return (f"header belongs to another chain {h.chain_id!r}, "
                f"not {chain_id!r}")
    if lightchain.header_hash(h) != blk.block_hash:
        return "commit signs a header other than this one"
    return None


def verify_commit_light(chain_id: str, vals, height: int, block_hash: bytes,
                        sigs):
    """None, or the error of VerifyCommitLight: signatures in commit
    order, absent ones skipped, stopping as soon as the tally passes two
    thirds of the set's power."""
    if len(vals) != len(sigs):
        return ("ValueError", "invalid commit -- wrong set size: "
                f"{len(vals)} vs {len(sigs)}")
    needed = sum(v.power for v in vals) * 2 // 3
    tpl = wire.sign_bytes_template(chain_id, height, block_hash)
    tallied = 0
    for idx, rec in enumerate(sigs):
        if rec is None:
            continue
        seconds, nanos, sig = rec
        try:
            _key(vals[idx].pub).verify(
                sig, wire.sign_bytes(tpl, seconds, nanos))
        except InvalidSignature:
            return ("ValueError",
                    f"wrong signature (#{idx}): {sig.hex().upper()}")
        tallied += vals[idx].power
        if tallied > needed:
            return None
    return ("ErrNotEnoughVotingPowerSigned",
            "invalid commit -- insufficient voting power: "
            f"got {tallied}, needed more than {needed}")


def verify_adjacent(trusted, untrusted, untrusted_vals, trusting_period_s: int,
                    now, max_clock_drift_s: int):
    """trusted, untrusted: lightchain.Block; untrusted_vals: the set the
    light block supplies; now: (seconds, nanos)."""
    th, uh = trusted.header, untrusted.header
    if uh.height != th.height + 1:
        return ("ValueError", "headers must be adjacent in height")
    if now >= (th.seconds + trusting_period_s, th.nanos):
        return ("ErrOldHeaderExpired", f"old header has expired at {_ts(*now)}")
    bad = _validate_basic(th.chain_id, untrusted)
    if bad is not None:
        return (INVALID, f"untrustedHeader.ValidateBasic failed: {bad}")
    if (uh.seconds, uh.nanos) <= (th.seconds, th.nanos):
        return (INVALID, "expected new header time to be after old header time")
    if (uh.seconds, uh.nanos) >= (now[0] + max_clock_drift_s, now[1]):
        return (INVALID, "new header has a time from the future "
                         "(max clock drift exceeded)")
    supplied = lightchain.valset_hash(untrusted_vals)
    if uh.validators_hash != supplied:
        return (INVALID, f"expected new header validators "
                         f"({uh.validators_hash.hex()}) to match those "
                         f"supplied ({supplied.hex()})")
    if uh.validators_hash != th.next_validators_hash:
        return (INVALID, f"expected old header next validators "
                         f"({th.next_validators_hash.hex()}) to match those "
                         f"from new header ({uh.validators_hash.hex()})")
    said = verify_commit_light(th.chain_id, untrusted_vals, uh.height,
                               untrusted.block_hash, untrusted.sigs)
    # verifier.go:143-148: any commit defect is an invalid header
    return None if said is None else (INVALID, said[1])
