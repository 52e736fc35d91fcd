"""Model-based-test conformance: replay the TLA+-derived light-client
traces against the verifier, on both the host oracle and the device batch
path.

Reference parity: light/mbt/driver_test.go — the JSON vectors
(light/mbt/json/MC4_4_faulty_*.json, copied verbatim into
tests/vectors/mbt/) are the bit-exactness oracle for the verifier
(SURVEY.md §4): header hashing, validator-set hashing, canonical vote
sign-bytes, ZIP-215 signature acceptance, trust-level arithmetic, and the
SUCCESS / NOT_ENOUGH_TRUST / INVALID error classes all have to line up
for every step of every trace.
"""

import glob
import json
import os

import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.light import verifier
from tendermint_tpu.wire.json_types import (
    parse_signed_header,
    parse_time,
    parse_validator_set as parse_valset,
)

VECTOR_DIR = os.path.join(os.path.dirname(__file__), "vectors", "mbt")


def trace_files():
    files = sorted(glob.glob(os.path.join(VECTOR_DIR, "*.json")))
    assert len(files) == 9, "expected the 9 MC4_4_faulty vectors"
    return files


@pytest.fixture(params=["host", "device"])
def batch_backend(request, monkeypatch):
    """Run every trace on both sides of the dispatch seam: the host
    per-signature oracle and the device batch engine (forced below its
    size threshold so the 4-signature commits still take the device
    path)."""
    if request.param == "host":
        monkeypatch.setattr(
            cbatch, "_device_verifier_factory", cbatch.Ed25519HostBatchVerifier
        )
    else:
        from tendermint_tpu.ops.backend import Ed25519DeviceBatchVerifier

        monkeypatch.setattr(
            cbatch,
            "_device_verifier_factory",
            lambda: Ed25519DeviceBatchVerifier(force_device=True),
        )
    return request.param


@pytest.mark.parametrize("path", trace_files(), ids=os.path.basename)
def test_mbt_trace(path, batch_backend):
    with open(path) as f:
        tc = json.load(f)

    trusted_sh = parse_signed_header(tc["initial"]["signed_header"])
    trusted_next_vals = parse_valset(tc["initial"]["next_validator_set"])
    trusting_period = int(tc["initial"]["trusting_period"]) / 1e9  # ns -> s

    for step, inp in enumerate(tc["input"]):
        blk = inp["block"]
        new_sh = parse_signed_header(blk["signed_header"])
        new_vals = parse_valset(blk["validator_set"])
        now = parse_time(inp["now"])

        err = None
        try:
            verifier.verify(
                trusted_sh,
                trusted_next_vals,
                new_sh,
                new_vals,
                trusting_period,
                now,
                1.0,  # maxClockDrift = 1s, as in driver_test.go:57
                verifier.DEFAULT_TRUST_LEVEL,
            )
        except ValueError as e:
            err = e

        verdict = inp["verdict"]
        ctx = f"{os.path.basename(path)} step {step} ({batch_backend})"
        if verdict == "SUCCESS":
            assert err is None, f"{ctx}: expected SUCCESS, got {err!r}"
        elif verdict == "NOT_ENOUGH_TRUST":
            assert isinstance(err, verifier.ErrNotEnoughTrust), (
                f"{ctx}: expected NOT_ENOUGH_TRUST, got {err!r}"
            )
        elif verdict == "INVALID":
            assert isinstance(
                err, (verifier.ErrInvalidHeader, verifier.ErrOldHeaderExpired)
            ), f"{ctx}: expected INVALID, got {err!r}"
        else:
            pytest.fail(f"{ctx}: unexpected verdict {verdict!r}")

        if err is None:  # advance trust, as the driver does
            trusted_sh = new_sh
            trusted_next_vals = parse_valset(blk["next_validator_set"])
