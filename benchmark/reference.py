"""The plain reference: Tendermint v0.35 types/validation.go VerifyCommit
(verifyCommitSingle, :265-334, countAllSignatures=true, lookUpByIndex=
true), one signature after another with OpenSSL, on the data builder's
own records. No batching, no device, none of the program's code.

It returns what the program must raise: None for a commit that verifies,
else (exception type name, message) — the strings of validation.go's
errors as the program spells them (tests/benchmark holds them against
types/validation._verify_commit_single)."""

from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from . import wire


def verify_commit(chain_id: str, pubkeys, powers, height: int, digest: bytes,
                  sigs):
    """sigs: per validator, None (absent) or (timestamp seconds, nanos,
    64-byte signature), in validator-set order."""
    needed = sum(powers) * 2 // 3
    tpl = wire.sign_bytes_template(chain_id, height, digest)
    tallied = 0
    for idx, rec in enumerate(sigs):
        if rec is None:
            continue
        seconds, nanos, sig = rec
        try:
            Ed25519PublicKey.from_public_bytes(bytes(pubkeys[idx])).verify(
                sig, wire.sign_bytes(tpl, seconds, nanos))
        except InvalidSignature:
            return ("ValueError",
                    f"wrong signature (#{idx}): {sig.hex().upper()}")
        tallied += powers[idx]
    if tallied <= needed:
        return ("ErrNotEnoughVotingPowerSigned",
                "invalid commit -- insufficient voting power: "
                f"got {tallied}, needed more than {needed}")
    return None
