"""Batch-verifier dispatch — the seam where the TPU engine plugs in.

Reference parity: crypto/batch/batch.go:11-33 — CreateBatchVerifier /
SupportsBatchVerifier keyed on pubkey type; ed25519 and sr25519 batch,
secp256k1 does not.

The default ed25519 batch verifier is the device-backed one from
tendermint_tpu.ops.backend, resolved HERE, at the seam, on the first
call — not as a side effect of some other module importing the ops
package — so a library caller's first commit takes the same path as its
second. Which platform and kernel that is comes from ops/engine.py. Its
semantics are *per-signature* cofactored ZIP-215 verification —
deterministic, and exactly equal to the reference's single-verify
semantics (the reference's random-linear-combination batch accepts the
same set except with negligible probability; on failure it too falls
back to per-signature checks, ed25519.go:225-227).

Whatever path a batch takes is counted in the ops `sigs_verified` series
(path="device" / path="host"): a commit that was host-verified shows up
as host-verified.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from . import BatchVerifier, PubKey
from . import ed25519 as _ed25519
from . import _edwards


class Ed25519HostBatchVerifier(BatchVerifier):
    """Host-only verifier (the device verifier's oracle in tests and the
    explicit no-device choice): native RLC batch, else per-signature
    ZIP-215 via the OpenSSL fast path."""

    on_device = False      # the device verifier's attribute, for its readers

    def __init__(self):
        self._entries: List[Tuple[bytes, bytes, bytes]] = []

    def add(self, key: PubKey, msg: bytes, sig: bytes) -> None:
        if not isinstance(key, _ed25519.PubKey):
            raise TypeError("pubkey is not ed25519")
        if len(sig) != _ed25519.SIGNATURE_SIZE:
            raise ValueError("invalid signature length")
        self._entries.append((key.bytes(), msg, sig))

    def add_entries(self, entries, lengths_checked: bool = False) -> None:
        """Bulk add() — one pass. The key-type check always runs (a mixed
        validator set must fail like per-entry add); lengths_checked=True
        skips only the length scan for callers that already did it."""
        if any(not isinstance(k, _ed25519.PubKey) for k, _, _ in entries):
            raise TypeError("pubkey is not ed25519")
        if not lengths_checked and any(
            len(s) != _ed25519.SIGNATURE_SIZE for _, _, s in entries
        ):
            raise ValueError("invalid signature length")
        self._entries.extend((k.bytes(), m, s) for k, m, s in entries)

    def add_block(self, block, keys=None) -> None:
        """Columnar bulk add (ops.entry_block.EntryBlock). The host
        verifier is the no-device fallback, so the block is expanded to
        tuples here; the device verifier keeps it by reference. `keys`
        runs the same per-key TYPE check as add()/add_entries; lengths
        are structural in the block's (n, 32)/(n, 64) shape."""
        if keys is not None and any(
            not isinstance(k, _ed25519.PubKey) for k in keys
        ):
            raise TypeError("pubkey is not ed25519")
        self._entries.extend(block.iter_entries())

    def verify(self) -> Tuple[bool, List[bool]]:
        # Random-linear-combination batch first when the native module is
        # built (one Pippenger MSM — crypto/ed25519/ed25519.go:219-227
        # semantics), falling back to per-signature checks for blame
        # assignment exactly like the reference (:225-227).
        n = len(self._entries)
        from ..libs import metrics as _metrics

        _metrics.ops_metrics().sigs_verified.inc(n, path="host")
        if n >= 16:
            from ..native import load as _load_native

            native = _load_native()
            if native is not None and hasattr(native, "ed25519_batch_verify"):
                ok = native.ed25519_batch_verify(
                    b"".join(p for p, _, _ in self._entries),
                    b"".join(s for _, _, s in self._entries),
                    [m for _, m, _ in self._entries],
                )
                if ok:
                    return True, [True] * n
        valid = [
            _ed25519.verify_zip215_fast(pub, msg, sig) for pub, msg, sig in self._entries
        ]
        return all(valid) and len(valid) > 0, valid


def _device_verifier() -> BatchVerifier:
    """The default ed25519 engine. The import is deferred to the first
    verifier so decoding/types code never loads jax by importing this
    module."""
    from ..ops.backend import Ed25519DeviceBatchVerifier

    return Ed25519DeviceBatchVerifier()


_device_verifier_factory = _device_verifier


def use_device_engine(factory):
    """Replace the ed25519 verifier factory (the seam differentials use:
    e.g. Ed25519HostBatchVerifier pins the host side). Returns the
    factory it replaced, for the caller to restore."""
    global _device_verifier_factory
    previous, _device_verifier_factory = _device_verifier_factory, factory
    return previous


def create_batch_verifier(pk: PubKey) -> Optional[BatchVerifier]:
    """crypto/batch/batch.go:11-24. Returns None if unsupported."""
    if pk.type() == _ed25519.KEY_TYPE:
        return _device_verifier_factory()
    from . import sr25519 as _sr25519

    if pk.type() == _sr25519.KEY_TYPE:
        # the commit path hands it the fused prep's block, tagged
        # "sr25519", and it submits to the shared dispatcher, where
        # ops/backend.select_kernel runs the ristretto kernel; small
        # batches and engines without Pallas stay on the host
        # (ops/mixed.py). A key of another type in the set fails its
        # add_block, as upstream's Add does.
        from ..ops.mixed import Sr25519DeviceBatchVerifier

        return Sr25519DeviceBatchVerifier()
    # secp256k1 has no batch VERIFIER (batch.go:26-33) and must stay
    # None here: _verify_commit_batch's add_block path takes 32-byte keys
    # and would choke on 33-byte ones. Batched secp verification exists
    # anyway (ISSUE 19) — it routes through the scheme lanes instead:
    # types/validation.prepare_commit_batch (all-secp committees),
    # prepare_commit_scheme_split + the mesh packer (mixed committees),
    # and ops.mixed.Secp256k1DeviceBatchVerifier for explicit opt-in.
    return None


def supports_batch_verifier(pk: Optional[PubKey]) -> bool:
    """crypto/batch/batch.go:26-33."""
    if pk is None:
        return False
    from . import sr25519 as _sr25519

    return pk.type() in (_ed25519.KEY_TYPE, _sr25519.KEY_TYPE)
