"""tendermint_tpu.ops — the device (TPU) compute engine.

JAX/XLA kernels replacing the reference's native-performance seams
(SURVEY.md §2: the batch signature-verification engine,
crypto/ed25519/ed25519.go:192-227) with TPU-first designs:

- engine:         the process's platform / kernel / interpret decision
- fe:             GF(2^255-19) limb arithmetic (int32, 13-bit limbs)
- ed25519_verify: batched branchless ZIP-215 verification kernel
- pallas_rlc:     the TPU production kernel (RLC fast-accept pipeline)
- backend:        bucketing host driver + BatchVerifier implementation
- pipeline:       the coalescing single-owner dispatcher
- sharded:        multi-chip sharding of verification over a jax Mesh

Importing this package loads nothing heavy and installs nothing: the
crypto.batch seam resolves the device verifier itself
(crypto/batch.create_batch_verifier), so a caller that imported only
`tendermint_tpu.types` gets the device path on its FIRST commit. The
numpy-only columnar modules (entry_block, commit_prep) stay importable
from the wire/types layer — commits decode straight into CommitBlock
columns — without dragging jax into every decode; `backend` (and with it
jax) loads on the first create_batch_verifier call.
"""

from __future__ import annotations

_LAZY = ("Ed25519DeviceBatchVerifier", "verify_batch", "warmup")


def __getattr__(name: str):
    if name in _LAZY:
        from . import backend

        return getattr(backend, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
