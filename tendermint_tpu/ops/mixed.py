"""Mixed-curve batch verification (BASELINE config #4) and the sr25519
batch verifier.

Reference parity: crypto/batch/batch.go:11-33 — batch verifiers exist for
ed25519 and sr25519; secp256k1 never batches (batch.go:26-33). Here every
curve gets a DEVICE lane. ed25519 and sr25519 blocks are submitted to the
shared dispatcher (ops/pipeline.py), which launches each scheme's kernel
as backend.select_kernel picks it (the RLC kernel; the ristretto kernel
of ops/pallas_sr25519.py) and never fuses two schemes into one launch.
Since ISSUE 19 secp256k1 batches through the Strauss+GLV ECDSA kernel
(ops.secp_verify) — the reference's "no secp batching" is a
verifier-interface fact, not a verdict change, so the device lane stays
bit-identical to per-signature verification. The per-signature host
loops survive as the small-batch fallbacks: secp256k1's under
SECP_DEVICE_THRESHOLD or TM_TPU_SECP_DEVICE=0, thread-pooled because
each OpenSSL ECDSA_verify releases the GIL; sr25519's (the native
schnorrkel batch) under SR_DEVICE_THRESHOLD, with TM_TPU_SR_DEVICE=0 or
on an engine without Pallas.

verify_mixed() partitions one heterogeneous batch by key type, dispatches
all lanes, and reassembles per-signature verdicts in input order.
"""

from __future__ import annotations

import os
from typing import List, Sequence, Tuple

import numpy as np

from ..crypto import PubKey
from ..crypto import secp256k1 as _secp
from ..crypto import sr25519 as _sr
from . import backend as _backend

# Below this many sr25519 signatures the device round-trip loses to the
# host path only for very small counts; the device wins early because
# host schnorr math is so slow.
SR_DEVICE_THRESHOLD = int(os.environ.get("TM_TPU_SR_DEVICE_THRESHOLD", "8"))

# secp256k1 scheme lane (ISSUE 19): below this many signatures the
# device round-trip loses to the host's native ECDSA_verify loop
SECP_DEVICE_THRESHOLD = int(
    os.environ.get("TM_TPU_SECP_DEVICE_THRESHOLD", "8")
)
# host-fallback pool: ECDSA_verify releases the GIL, so the per-sig loop
# threads near-linearly; small batches stay single-threaded (pool spawn
# costs more than it saves)
SECP_HOST_POOL_MIN = int(os.environ.get("TM_TPU_SECP_HOST_POOL_MIN", "32"))


def _secp_device_enabled() -> bool:
    return os.environ.get("TM_TPU_SECP_DEVICE", "1") == "1"


def _secp_host_workers() -> int:
    w = os.environ.get("TM_TPU_SECP_HOST_WORKERS")
    if w is not None:
        return max(1, int(w))
    return max(1, min(8, (os.cpu_count() or 1)))


def _host_secp_batch(lane: Sequence[Tuple[PubKey, bytes, bytes]]) -> np.ndarray:
    """Per-signature host verification, thread-pooled (satellite of
    ISSUE 19): each native ECDSA_verify drops the GIL so N workers give
    ~N×; under TM_TPU_PUREPY_CRYPTO the math is pure Python and the pool
    is skipped (threads would just interleave GIL-held bignum ops)."""
    n = len(lane)
    workers = _secp_host_workers()
    if n < SECP_HOST_POOL_MIN or workers < 2 or _secp.is_pure_python():
        return np.array(
            [pk.verify_signature(m, s) for pk, m, s in lane], dtype=bool
        )
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers) as pool:
        return np.fromiter(
            pool.map(
                lambda e: e[0].verify_signature(e[1], e[2]),
                lane,
                chunksize=max(1, n // (workers * 4)),
            ),
            dtype=bool,
            count=n,
        )


def _verify_secp_batch(lane: Sequence[Tuple[PubKey, bytes, bytes]]) -> np.ndarray:
    """The secp lane: batched device kernel when enabled and worth the
    round-trip, the (pooled) host loop otherwise. Device and host agree
    bit-for-bit on verdicts (tests/test_secp_lane.py pins this)."""
    if len(lane) >= SECP_DEVICE_THRESHOLD and _secp_device_enabled():
        entries_b = [(pk.bytes(), m, s) for pk, m, s in lane]
        return np.array(_backend.verify_batch_secp(entries_b), dtype=bool)
    return _host_secp_batch(lane)


# host-fallback pool for sr25519 (satellite of ISSUE 20, mirroring the
# secp pool): the native schnorrkel batch call computes outside the GIL,
# so splitting a big batch across workers scales ~linearly; the
# pure-Python fallback is GIL-held bignum math and stays single-threaded
SR_HOST_POOL_MIN = int(os.environ.get("TM_TPU_SR_HOST_POOL_MIN", "32"))


def _sr_host_workers() -> int:
    w = os.environ.get("TM_TPU_SR_HOST_WORKERS")
    if w is not None:
        return max(1, int(w))
    return max(1, min(8, (os.cpu_count() or 1)))


def _sr_native_batch_available() -> bool:
    from ..native import load as _load_native

    native = _load_native()
    return native is not None and hasattr(native, "sr25519_verify_batch")


def _host_sr_batch(entries) -> np.ndarray:
    """Host sr25519 verdicts, thread-pooled over native batch chunks.
    Small batches (or the pure-Python fallback, where threads would only
    interleave GIL-held math) run the single verify_batch call. Counted
    in sigs_verified and sr25519_sigs under path="host"."""
    entries = list(entries)
    n = len(entries)
    m = _backend._ops_m()
    m.sigs_verified.inc(n, path="host")
    m.sr25519_sigs.inc(n, path="host")
    workers = _sr_host_workers()
    if (
        n < SR_HOST_POOL_MIN
        or workers < 2
        or not _sr_native_batch_available()
    ):
        return np.array(_sr.verify_batch(entries), dtype=bool)
    from concurrent.futures import ThreadPoolExecutor

    step = -(-n // workers)
    chunks = [entries[i:i + step] for i in range(0, n, step)]
    with ThreadPoolExecutor(max_workers=len(chunks)) as pool:
        parts = list(pool.map(_sr.verify_batch, chunks))
    return np.concatenate([np.asarray(p, dtype=bool) for p in parts])


def _sr_device_enabled() -> bool:
    """sr25519 device lane: on by default; TM_TPU_SR_DEVICE=0 is the
    explicit way to use the native host lane instead. There is no
    automatic fallback: a kernel that fails to compile or launch fails
    the caller's future."""
    return os.environ.get("TM_TPU_SR_DEVICE", "1") == "1"


def verify_mixed(
    entries: Sequence[Tuple[PubKey, bytes, bytes]],
) -> List[bool]:
    """entries: (PubKey, msg, sig) with heterogeneous key types. Returns
    per-entry validity in input order; ed25519 and sr25519 ride their
    device lanes, secp256k1 verifies per-signature on the host."""
    lanes = {"ed25519": [], "sr25519": [], "secp256k1": [], "other": []}
    order = []
    for i, (pk, msg, sig) in enumerate(entries):
        kind = pk.type() if pk.type() in lanes else "other"
        order.append((kind, len(lanes[kind])))
        lanes[kind].append((pk, msg, sig))

    # Lanes run CONCURRENTLY: the ed25519 batch rides the shared async
    # pipeline (a future), the secp256k1 device batch dispatches on a
    # helper thread, and the host loops and then the sr25519 batch (the
    # same dispatcher, never fused with ed25519's launch) fill the main
    # thread while the device works — the mixed batch costs max(lanes),
    # not sum(lanes).
    results = {}
    ed_future = None
    secp_thread = None
    secp_holder: dict = {}
    if lanes["ed25519"]:
        ed_entries = [(pk.bytes(), m, s) for pk, m, s in lanes["ed25519"]]
        if len(ed_entries) <= _backend.BUCKETS[-1]:
            from .pipeline import shared_verifier

            ed_future = shared_verifier().submit(ed_entries)
        else:
            results["ed25519"] = _backend.verify_batch(ed_entries)
    if lanes["secp256k1"]:
        import threading

        secp_lane = lanes["secp256k1"]

        def _secp_run():
            try:
                secp_holder["res"] = _verify_secp_batch(secp_lane)
            except Exception as e:  # noqa: BLE001
                secp_holder["err"] = e

        secp_thread = threading.Thread(target=_secp_run, daemon=True)
        secp_thread.start()
    if lanes["other"]:
        results["other"] = np.asarray(
            [pk.verify_signature(m, s) for pk, m, s in lanes["other"]],
            dtype=bool,
        )
    if lanes["sr25519"]:
        # submitted and waited for here, while the ed25519 launch and the
        # secp256k1 thread are under way
        sr_bv = Sr25519DeviceBatchVerifier()
        sr_bv.add_entries(lanes["sr25519"], lengths_checked=True)
        results["sr25519"] = sr_bv.verify()[1]
    if ed_future is not None:
        results["ed25519"] = np.asarray(ed_future.result(timeout=600))
    if secp_thread is not None:
        secp_thread.join(timeout=600)
        if secp_thread.is_alive():
            raise TimeoutError("secp256k1 lane did not finish in 600s")
        if "err" in secp_holder:
            raise secp_holder["err"]
        results["secp256k1"] = secp_holder["res"]
    return [bool(results[kind][j]) for kind, j in order]


class Sr25519DeviceBatchVerifier(_backend.DeviceBatchVerifier):
    """crypto.BatchVerifier for sr25519 (crypto/sr25519/batch.go parity):
    the device verifier's accumulate, submit and wait, with sr25519's keys
    and host lane. `add_block` takes the columnar block the fused commit
    prep builds (types/validation.py)."""

    scheme = _sr.KEY_TYPE
    key_class = _sr.PubKey

    def _host_lane(self, n: int) -> bool:
        return (
            n < SR_DEVICE_THRESHOLD
            or not _sr_device_enabled()
            or not _backend.engine().pallas
        )

    def _verify_host(self, block):
        return _host_sr_batch(block.iter_entries())


class Secp256k1DeviceBatchVerifier:
    """crypto.BatchVerifier shape over the secp256k1 scheme lane.

    NOT returned by crypto/batch.create_batch_verifier — that stays None
    for reference parity (batch.go:26-33), and _verify_commit_batch's
    ed25519-shaped add_block path must never see 33-byte keys. Callers
    that want batched secp opt in explicitly (ops.mixed, bench, tests);
    commits route through prepare_commit_batch / the mesh instead."""

    def __init__(self):
        self._entries: List[Tuple[PubKey, bytes, bytes]] = []

    def add(self, key, msg: bytes, sig: bytes) -> None:
        if key.type() != _secp.KEY_TYPE:
            raise TypeError("pubkey is not secp256k1")
        if len(sig) != _secp.SIGNATURE_LENGTH:
            raise ValueError("invalid signature length")
        self._entries.append((key, msg, sig))

    def verify(self) -> Tuple[bool, List[bool]]:
        if not self._entries:
            return False, []
        res = _verify_secp_batch(self._entries)
        valid = [bool(v) for v in res]
        return all(valid), valid
