"""Host-side driver for the device verification engine.

Feeds fixed-shape, bucketed batches to the jitted ZIP-215 kernel and
implements the `crypto.BatchVerifier` interface so the engine plugs into
the dispatch seam (crypto/batch/batch.go:11-33 parity; see
tendermint_tpu.crypto.batch.create_batch_verifier, which resolves it).

Bucketing: XLA compiles one executable per shape, so batches are padded to
the next bucket size {128, 1024, 10240} (10240 covers the reference's
MaxVotesCount=10000, types/vote_set.go:18); larger inputs are chunked.
Padding lanes carry a throwaway-but-valid layout and are masked out.

The challenge scalar k = SHA512(R||A||M) mod L is computed host-side via
_challenges — the native batch helper (tm_native.ed25519_challenges,
OpenSSL SHA-512 + fold-based mod L in one C call per batch) when built,
else a hashlib loop. The per-sig Python loop it replaced measured ~50% of
end-to-end batch time on a loaded host.

`select_kernel` is the one place that picks an engine for a batch: every
path that launches (the dispatcher's `_prepare`, the direct chunk loop
below, the mesh's segments) asks it.
"""

from __future__ import annotations

import functools
import hashlib
import os
import time
from typing import List, Tuple

import numpy as np

from ..crypto import BatchVerifier, PubKey
from ..crypto import ed25519 as _ed25519
from ..crypto._edwards import L
from ..libs import devcheck as _devcheck
from ..libs import metrics as _metrics
from ..observability import trace as _trace
from . import ed25519_verify
from .engine import engine
from .entry_block import EntryBlock, as_block

_span = _trace.span

_OPS = None


def _ops_m() -> "_metrics.OpsMetrics":
    """Process-wide ops metric set, cached to skip the registry lock on
    the per-batch hot path."""
    global _OPS
    if _OPS is None:
        _OPS = _metrics.ops_metrics()
    return _OPS


def _note_device_batch(n: int, bucket: int, prep_s: float = -1.0,
                       device_s: float = -1.0, scheme: str = "") -> None:
    """One dispatched device batch: counters + pad accounting (+ optional
    prep/device timing histograms when the caller measured them). An
    sr25519 batch also counts in the scheme's own series."""
    m = _ops_m()
    b = str(bucket)
    m.batches.inc(bucket=b)
    m.sigs_verified.inc(n, path="device")
    if scheme == "sr25519":
        m.sr25519_sigs.inc(n, path="device")
        m.sr25519_launches.inc()
    if bucket > n:
        m.padded_lanes.inc(bucket - n)
    m.pad_waste_ratio.set(max(bucket - n, 0) / bucket if bucket else 0.0)
    if prep_s >= 0.0:
        m.host_prep_seconds.observe(prep_s, bucket=b)
    if device_s >= 0.0:
        m.device_seconds.observe(device_s, bucket=b)

BUCKETS = (128, 1024, 10240)

# Below this many signatures the per-call dispatch overhead beats the
# device win; use the host (OpenSSL) path. Mirrors the spirit of the
# reference's batchVerifyThreshold (types/validation.go:12) at device scale.
DEVICE_THRESHOLD = int(os.environ.get("TM_TPU_DEVICE_THRESHOLD", "64"))

_L_BYTES = L.to_bytes(32, "little")


def _bucket_for(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


# The secp256k1 lane gets a finer bucket floor: with no RLC fusion the
# Strauss+GLV ladder's kernel time is ~linear per ROW (padding included),
# so a 10-signature commit on the 128 floor pays 12× its useful work —
# material on CPU backends where the ladder runs ~40 ms/row. One extra
# small shape in the compile cache buys it back.
SECP_BUCKETS = (16,) + BUCKETS


def _secp_bucket_for(n: int) -> int:
    for b in SECP_BUCKETS:
        if n <= b:
            return b
    return SECP_BUCKETS[-1]


# The sr25519 lane's ladder: the ristretto kernel is per-signature (no RLC
# lanes), so its time follows the padded width; powers of two from one
# 128-lane kernel block keep a 150-signature commit in 256 lanes.
SR_BUCKETS = (128, 256, 512, 1024, 2048, 4096, 8192, 10240)


def _sr_bucket_for(n: int) -> int:
    for b in SR_BUCKETS:
        if n <= b:
            return b
    return SR_BUCKETS[-1]


def _pack_le_limbs(enc: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 little-endian encodings -> (B, 20) int32 limbs of the
    low 255 bits (bit 255 — the sign bit — is excluded). Routes through the
    native packer (native/tm_native.cpp) when built."""
    from ..native import load as _load_native

    native = _load_native()
    n = enc.shape[0]
    if native is not None:
        raw = native.pack_le_limbs(np.ascontiguousarray(enc).tobytes(), n)
        return np.frombuffer(raw, dtype=np.int32).reshape(n, 20).copy()
    # vectorized word-shift extraction (mirrors the C packer): 4 uint64
    # words per row, 20 shifted 13-bit windows — ~6x the old
    # unpackbits-weights path, which built a (B, 20, 13) int32 transient
    w = np.ascontiguousarray(enc).view("<u8")  # (n, 4) LE words
    cols = [w[:, 0], w[:, 1], w[:, 2],
            w[:, 3] & np.uint64(0x7FFFFFFFFFFFFFFF)]  # bit 255 excluded
    out = np.empty((n, 20), dtype=np.int32)
    mask = np.uint64(0x1FFF)
    for limb in range(20):
        bit = limb * 13
        word, off = bit >> 6, bit & 63
        v = cols[word] >> np.uint64(off)
        if off > 64 - 13 and word < 3:
            v = v | (cols[word + 1] << np.uint64(64 - off))
        out[:, limb] = (v & mask).astype(np.int32)
    return out


def _bits_253(le32: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 little-endian scalars (< 2^253) -> (253, B) int32 bits,
    transposed for the ladder's row indexing.

    Always the vectorized numpy path: the native pack_bits_le writes the
    transposed output column-wise (one lane's 253 bits stride the whole
    row axis) and measures 20 ms vs 1.8 ms here at a 10240 bucket — the
    rare case where C loses to numpy on access pattern alone."""
    n = le32.shape[0]
    # extract bits along the TRANSPOSED byte axis so the result lands
    # directly in ladder row order — no (B, 253) -> (253, B) strided
    # transpose copy (which dominated the old fallback at 10k lanes)
    tt = np.ascontiguousarray(le32.T)  # (32, B)
    bits = (tt[:, None, :] >> np.arange(8, dtype=np.uint8)[None, :, None]) & 1
    return bits.reshape(256, n)[:253].astype(np.int32)


_L_BE = np.frombuffer(L.to_bytes(32, "big"), dtype=np.uint8)


def _pack_rows(entries, bucket: int):
    """Bulk-pack a batch into padded (bucket, 32) pub/R/s arrays. For an
    EntryBlock the columns already exist — three slice-assigns, no joins
    or per-signature Python objects; tuple lists keep the two-join path
    (SURVEY.md §7 hard-part 3: host prep must not dominate the batch).

    Padding lanes: A = R = identity encoding (y=1), s = 0 — these verify
    trivially and keep the ladder numerically meaningful."""
    n = len(entries)
    pub = np.zeros((bucket, 32), dtype=np.uint8)
    r_enc = np.zeros((bucket, 32), dtype=np.uint8)
    s_enc = np.zeros((bucket, 32), dtype=np.uint8)
    if n:
        if isinstance(entries, EntryBlock):
            pub[:n] = entries.pub
            r_enc[:n] = entries.sig[:, :32]
            s_enc[:n] = entries.sig[:, 32:]
        else:
            # length check before the joins: a single wrong-length key
            # would otherwise silently shift every later lane after the
            # reshape
            if any(len(pk) != 32 or len(s) != 64 for pk, _, s in entries):
                raise ValueError("entries must be (pub32, msg, sig64) triples")
            pub[:n] = np.frombuffer(
                b"".join(pk for pk, _, _ in entries), dtype=np.uint8
            ).reshape(n, 32)
            sig = np.frombuffer(
                b"".join(s for _, _, s in entries), dtype=np.uint8
            ).reshape(n, 64)
            r_enc[:n] = sig[:, :32]
            s_enc[:n] = sig[:, 32:]
    pub[n:, 0] = 1
    r_enc[n:, 0] = 1
    return pub, r_enc, s_enc


def _challenges(r_enc: np.ndarray, pub: np.ndarray, msgs) -> bytes:
    """Batch challenge scalars k_i = SHA512(R_i||A_i||M_i) mod L, 32B LE
    each. Native C helper when built (one call for the whole batch — the
    per-sig Python loop measured ~50% of end-to-end batch time on a loaded
    host); hashlib fallback otherwise."""
    from ..native import load as _load_native

    native = _load_native()
    if native is not None and hasattr(native, "ed25519_challenges"):
        return native.ed25519_challenges(
            np.ascontiguousarray(r_enc).tobytes(),
            np.ascontiguousarray(pub).tobytes(),
            msgs,
        )
    # pure-Python fallback: one R||A prefix pre-join, then the hashlib +
    # bigint-mod floor per signature (CPython's 512-by-253-bit % beats a
    # vectorized numpy limb reduction here — measured 6.5 vs 19 ms/10k)
    n = len(msgs)
    ra = np.empty((n, 64), dtype=np.uint8)
    ra[:, :32] = r_enc[:n]
    ra[:, 32:] = pub[:n]
    ra_b = ra.tobytes()
    sha = hashlib.sha512
    return b"".join(
        (
            int.from_bytes(
                sha(ra_b[64 * i : 64 * i + 64] + m).digest(), "little"
            )
            % L
        ).to_bytes(32, "little")
        for i, m in enumerate(msgs)
    )


def _challenges_block(r_enc: np.ndarray, pub: np.ndarray,
                      block: EntryBlock) -> bytes:
    """Columnar _challenges: the whole batch's sign-bytes live in ONE
    buffer + offset table, so the native path is a single GIL-released
    call with no per-message Python objects; the hashlib fallback hashes
    zero-copy memoryview slices."""
    from ..native import load as _load_native

    native = _load_native()
    if native is not None and hasattr(native, "ed25519_challenges_buf"):
        buf, offs = block.msgs_contiguous()
        return native.ed25519_challenges_buf(
            np.ascontiguousarray(r_enc).tobytes(),
            np.ascontiguousarray(pub).tobytes(),
            buf,
            np.ascontiguousarray(offs).tobytes(),
        )
    # bytes slices (not memoryviews): hashlib's C fast path and the older
    # native sequence API both run measurably faster on real bytes
    buf, offs = block.msgs_contiguous()
    b = buf if isinstance(buf, bytes) else bytes(buf)
    o = offs.tolist()
    msgs = [b[o[i] : o[i + 1]] for i in range(len(block))]
    return _challenges(r_enc, pub, msgs)


def _challenges_any(r_enc: np.ndarray, pub: np.ndarray, entries) -> bytes:
    """Dispatch on the batch representation (EntryBlock vs tuple list)."""
    if isinstance(entries, EntryBlock):
        return _challenges_block(r_enc, pub, entries)
    return _challenges(r_enc, pub, [m for _, m, _ in entries])


def _s_below_l(s_enc: np.ndarray, n: int, bucket: int) -> np.ndarray:
    """Vectorized s < L check (RFC 8032 scalar range): big-endian
    lexicographic compare against L. Padding lanes pass (s = 0)."""
    s_ok = np.zeros((bucket,), dtype=bool)
    s_ok[n:] = True
    if n:
        s_be = s_enc[:n, ::-1]
        diff = s_be != _L_BE
        has_diff = diff.any(axis=1)
        first = diff.argmax(axis=1)
        rng = np.arange(n)
        s_ok[:n] = has_diff & (s_be[rng, first] < _L_BE[first])
    return s_ok


def prepare_batch(entries, bucket: int) -> tuple:
    """entries: EntryBlock or (pub32, msg, sig64) triples, len <= bucket.
    Returns the kernel argument tuple, padded to `bucket` lanes.

    EntryBlock + native module: the ENTIRE prep (row pack + SHA-512
    challenges + limb/bit pack + s<L) is ONE GIL-released C call
    (tm_native.ed25519_prep_fused) over the block's contiguous buffers —
    the per-commit GIL share this stage used to hold is what capped
    concurrent verify_commit throughput. Columnar numpy and
    tuple-list fallbacks keep parity."""
    n = len(entries)
    t0 = time.perf_counter()
    with _span("ops.host_prep", n=n, bucket=bucket):
        if isinstance(entries, EntryBlock) and n:
            from ..native import load as _load_native

            native = _load_native()
            if native is not None and hasattr(native, "ed25519_prep_fused"):
                buf, offs = entries.msgs_contiguous()
                with _span("ops.prep_fused"):
                    pl, a_sign, rl, r_sign, sb, kb, sok = (
                        native.ed25519_prep_fused(
                            entries.pub.tobytes(),
                            entries.sig.tobytes(),
                            buf,
                            np.ascontiguousarray(offs).tobytes(),
                            bucket,
                        )
                    )
                args = (
                    np.frombuffer(pl, dtype=np.int32).reshape(bucket, 20),
                    np.frombuffer(a_sign, dtype=np.int32),
                    np.frombuffer(rl, dtype=np.int32).reshape(bucket, 20),
                    np.frombuffer(r_sign, dtype=np.int32),
                    np.frombuffer(sb, dtype=np.int32).reshape(253, bucket),
                    np.frombuffer(kb, dtype=np.int32).reshape(253, bucket),
                    np.frombuffer(sok, dtype=np.uint8).astype(bool),
                )
                _ops_m().host_prep_seconds.observe(
                    time.perf_counter() - t0, bucket=str(bucket)
                )
                return args
        with _span("ops.pack_rows"):
            pub, r_enc, s_enc = _pack_rows(entries, bucket)
        k_enc = np.zeros((bucket, 32), dtype=np.uint8)
        s_ok = _s_below_l(s_enc, n, bucket)
        if n:
            with _span("ops.challenges"):
                ks = _challenges_any(r_enc[:n], pub[:n], entries)
            k_enc[:n] = np.frombuffer(ks, dtype=np.uint8).reshape(n, 32)

        a_sign = (pub[:, 31] >> 7).astype(np.int32)
        r_sign = (r_enc[:, 31] >> 7).astype(np.int32)
        with _span("ops.limb_pack"):
            args = (
                _pack_le_limbs(pub),
                a_sign,
                _pack_le_limbs(r_enc),
                r_sign,
                _bits_253(s_enc),
                _bits_253(k_enc),
                s_ok,
            )
    _ops_m().host_prep_seconds.observe(
        time.perf_counter() - t0, bucket=str(bucket)
    )
    return args


def h2d_arg_bytes(args) -> int:
    """Host bytes a kernel-argument tuple ships to the device: numpy
    arrays transfer per call; jax Arrays (the epoch tables) are already
    device-resident and cost nothing per batch."""
    return sum(
        a.nbytes for a in args if isinstance(a, np.ndarray)
    )


def _pack_sig_rows(entries, bucket: int, ep):
    """Shared per-signature row prep for the epoch-cached paths: raw
    r/s rows (padding lanes identity/zero — the exact pattern _pack_rows
    gives the uncached kernels), host s<L flags, and the gather indices
    (padding lanes -> the table's identity row ep.vp - 1)."""
    n = len(entries)
    r_rows = np.zeros((bucket, 32), dtype=np.uint8)
    s_rows = np.zeros((bucket, 32), dtype=np.uint8)
    idx = np.full((bucket,), ep.vp - 1, dtype=np.int32)
    if n:
        r_rows[:n] = entries.sig[:, :32]
        s_rows[:n] = entries.sig[:, 32:]
        idx[:n] = entries.val_idx
    r_rows[n:, 0] = 1
    s_ok = _s_below_l(s_rows, n, bucket)
    return idx, r_rows, s_rows, s_ok


def cached_sig_args(entries: EntryBlock, bucket: int, ep) -> tuple:
    """The shared warm-epoch per-signature argument set: (idx, r_rows,
    s_rows, k_rows, s_ok (bucket,) bool) — gather indices, raw rows, and
    host SHA-512 challenges. Consumed by prepare_batch_cached (XLA) and
    pallas_verify.prepare_compact_cached; any padding or challenge-prep
    change lands in ONE place."""
    n = len(entries)
    idx, r_rows, s_rows, s_ok = _pack_sig_rows(entries, bucket, ep)
    k_rows = np.zeros((bucket, 32), dtype=np.uint8)
    if n:
        with _span("ops.challenges"):
            ks = _challenges_block(r_rows[:n], entries.pub, entries)
        k_rows[:n] = np.frombuffer(ks, dtype=np.uint8).reshape(n, 32)
    return idx, r_rows, s_rows, k_rows, s_ok


def prepare_batch_cached(entries: EntryBlock, bucket: int, ep) -> tuple:
    """Warm-epoch prep for jitted_verify_cached: NO pubkey-derived arrays
    and NO host limb/bit packing — the batch ships raw 32-byte rows
    (r/s/k) plus val_idx gather indices, and the device prologue unpacks
    (ed25519_verify.unpack_limbs_rows / bits253_rows). ~101 B/sig vs
    ~2.2 kB/sig for prepare_batch's unpacked arrays."""
    t0 = time.perf_counter()
    with _span("ops.host_prep", n=len(entries), bucket=bucket, cached=1):
        args = cached_sig_args(entries, bucket, ep)
    _ops_m().host_prep_seconds.observe(
        time.perf_counter() - t0, bucket=str(bucket)
    )
    return args


def cached_kernel(ep, donate: bool = False):
    """Kernel closure for a warm epoch: resolves the entry's device
    tables at CALL time — the caller is the pipeline's single
    dispatch-owner thread, so the one-time table upload happens on the
    only thread allowed to touch the device. The tables ride as the two
    leading (never-donated) arguments; `donate` applies only to the
    per-batch args."""
    base = ed25519_verify.jitted_verify_cached(donate)

    def call(*args):
        tbl_limbs, tbl_sign = ep.xla_tables()
        return base(tbl_limbs, tbl_sign, *args)

    return call


# -- secp256k1 scheme lane (ISSUE 19) ---------------------------------------
#
# ECDSA has no RLC fusion and no pallas variant (follow-up work): the
# scheme rides the XLA per-signature kernel family only, with the same
# bucket ladder, donation contract, and warm-epoch gather split as
# ed25519. Host prep is python-int math (s^-1 mod n + GLV), so it runs on
# the prep pool like every other prep.


def _secp_items(entries) -> list:
    """EntryBlock (scheme secp256k1) or (pub33, msg, sig64) tuple list ->
    the item tuples ops/secp_verify.prepare_rows* consume."""
    if isinstance(entries, EntryBlock):
        mvs = entries.msg_views()
        return [
            (entries.pub_bytes(i), mvs[i], entries.sig[i].tobytes())
            for i in range(len(entries))
        ]
    return list(entries)


def prepare_batch_secp(entries, bucket: int) -> tuple:
    """Direct (uncached) secp256k1 prep: host decompression + GLV split
    -> the jitted_secp_verify arg arrays, padded to `bucket` with
    trivial-accept rows."""
    from . import secp_verify as _sv

    t0 = time.perf_counter()
    with _span("ops.host_prep", n=len(entries), bucket=bucket,
               scheme="secp256k1"):
        args = _sv.prepare_rows(_secp_items(entries), bucket)
    _ops_m().host_prep_seconds.observe(
        time.perf_counter() - t0, bucket=str(bucket)
    )
    return args


def prepare_batch_secp_cached(entries: EntryBlock, bucket: int, ep) -> tuple:
    """Warm-epoch secp prep: the committee's decompressed affine Q
    columns are device-resident (ep.secp_tables) — the batch ships gather
    indices + scalar data only."""
    from . import secp_verify as _sv

    t0 = time.perf_counter()
    with _span("ops.host_prep", n=len(entries), bucket=bucket,
               scheme="secp256k1", cached=1):
        args = _sv.prepare_rows_cached(
            _secp_items(entries), entries.val_idx, bucket, ep.vp - 1
        )
    _ops_m().host_prep_seconds.observe(
        time.perf_counter() - t0, bucket=str(bucket)
    )
    return args


def secp_kernel(donate: bool = False):
    from . import secp_verify as _sv

    return _sv.jitted_secp_verify(donate)


def secp_cached_kernel(ep, donate: bool = False):
    """Warm-epoch secp kernel closure: resolves the entry's device Q
    tables at CALL time on the dispatch-owner thread (the cached_kernel
    contract — tables are the leading never-donated arguments)."""
    from . import secp_verify as _sv

    base = _sv.jitted_secp_verify_cached(donate)

    def call(*args):
        qx, qy, q_ok = ep.secp_tables()
        return base(qx, qy, q_ok, *args)

    return call


def verify_batch_secp(entries) -> np.ndarray:
    """Run the secp256k1 device kernel over arbitrary batch size
    (EntryBlock with scheme secp256k1, or (pub33, msg, sig64) tuples);
    returns (n,) bool. Direct device path — devcheck-exempt like
    verify_batch."""
    with _devcheck.exempt():
        return _verify_batch_direct(entries, BUCKETS[-1], "secp256k1")


# -- bls12381 aggregation lane (ISSUE 20) ------------------------------------
#
# One row is one aggregated COMMIT (a committee's worth of signatures
# collapsed into a single pairing check), so the bucket ladder is tiny:
# kernel time is ~linear in rows (2 Miller loops each) plus ONE fused
# final exponentiation amortized across the batch.

BLS_BUCKETS = (4, 16)

# Below this many concurrent aggregated commits the fused launch cannot
# amortize its final exponentiation; the pure-python oracle wins on
# latency and single commits verify synchronously
# (types/validation.py prepare_aggregated_commit).
BLS_DEVICE_THRESHOLD = int(os.environ.get("TM_TPU_BLS_DEVICE_THRESHOLD", "2"))


def _bls_bucket_for(n: int) -> int:
    for b in BLS_BUCKETS:
        if n <= b:
            return b
    return BLS_BUCKETS[-1]


def _bls_epoch(block):
    """AggBlock -> its EpochEntry or None. AggBlocks carry no val_idx
    (the signer bitmap IS the committee reference), so this bypasses
    epoch_cache.lookup()'s gather-index requirement and only guards the
    scheme."""
    from . import epoch_cache as _epoch

    key = getattr(block, "epoch_key", None)
    c = _epoch.cache()
    if key is None or c is None:
        return None
    ep = c.get(key)
    if ep is not None and ep.scheme != "bls12381":
        return None
    return ep


def _bls_bad_rows(pub48: np.ndarray) -> list:
    """Committee rows whose pubkey is unusable (malformed/identity/non-
    subgroup) — pubkey_status is memoized per key bytes, so this is a
    dict walk per batch after the first sight of an epoch."""
    from ..crypto import bls12381 as _bls

    return [
        i for i in range(pub48.shape[0])
        if _bls.pubkey_status(pub48[i].tobytes())[1] is not None
    ]


def prepare_batch_bls(block, bucket: int, vp: int, bad_rows=()) -> tuple:
    """Host prep for an AggBlock: Fiat-Shamir weights, G2 scalar muls and
    line-coefficient rows (ops/bls_verify.prepare_commits). Returns
    (masks, coeffs, ok, reasons); masks/coeffs are the device args, ok/
    reasons stay host-side for the verdict-code fold. Mesh pad rows
    (is_pad) are trailing by construction and prep as pad commits."""
    from . import bls_verify as _bv

    live = int(np.count_nonzero(~block.is_pad))
    if block.is_pad[:live].any():
        raise ValueError("AggBlock pad rows must be trailing")
    t0 = time.perf_counter()
    with _span("ops.host_prep", n=live, bucket=bucket, scheme="bls12381"):
        items = [
            (block.bits[i], block.msg(i), block.sig[i].tobytes())
            for i in range(live)
        ]
        masks, coeffs, ok, reasons = _bv.prepare_commits(
            items, bucket, vp, bad_rows=bad_rows
        )
    _ops_m().host_prep_seconds.observe(
        time.perf_counter() - t0, bucket=str(bucket)
    )
    return masks, coeffs, ok, reasons


def bls_kernel(block, ok, reasons, ep=None, donate: bool = False):
    """Launch closure for the aggregation lane: resolves the committee
    tables at CALL time (cached path: device residents owned by the
    epoch LRU; cold path: a host build from the block's pub48 snapshot),
    runs the two-launch verdict protocol (ops/bls_verify.run_verify) and
    returns the int32 verdict-code row as a HOST array — the protocol's
    branch point is a host reduce, so there is no device result left to
    read back."""
    from . import bls_verify as _bv

    def call(masks, coeffs):
        if ep is not None:
            tables = ep.bls_tables()
        else:
            tables = _bv.table_columns_g1(
                [r.tobytes() for r in block.pub48]
            )
        verdicts, cfail, apk_nz = _bv.run_verify(
            tables, masks, coeffs, ok, donate=donate
        )
        return _bv.verdict_codes(verdicts, cfail, apk_nz, reasons)

    return call


def verify_batch_bls_codes(block) -> np.ndarray:
    """Run the aggregation lane over an AggBlock; returns the (k,) int32
    verdict-code row (ops/bls_verify code constants). Direct device path —
    devcheck-exempt like verify_batch."""
    if len(block) == 0:
        return np.zeros((0,), dtype=np.int32)
    with _devcheck.exempt():
        return _verify_batch_direct(block, BLS_BUCKETS[-1])


def verify_batch_bls(block) -> np.ndarray:
    """Boolean face of the aggregation lane (one bool per COMMIT row)."""
    from . import bls_verify as _bv

    return verify_batch_bls_codes(block) == _bv.CODE_VALID


def _pallas_bucket(n: int) -> int:
    from . import pallas_verify

    b = pallas_verify.BLOCK
    return max(b, min(((n + b - 1) // b) * b, BUCKETS[-1]))


def quantized_bucket(n: int, scheme: str = "") -> int:
    """Device bucket (in signatures) a batch of n will be padded to."""
    if scheme == "sr25519":
        return _sr_bucket_for(n)
    if engine().rlc:
        from . import pallas_rlc

        return pallas_rlc.plan_bucket(n)[0]
    return _bucket_for(n)


def max_coalesce() -> int:
    """Largest device batch the async pipeline may fuse concurrent jobs
    into. The RLC path raises it well past MaxVotesCount (see
    pallas_rlc.MAX_SIGS)."""
    if engine().rlc:
        from . import pallas_rlc

        return pallas_rlc.MAX_SIGS
    return BUCKETS[-1]


def scheme_cap(scheme: str, cap: int) -> int:
    """`cap` rows, or fewer for a scheme whose bucket ladder stops short
    of it (sr25519's at 10 240)."""
    return min(cap, SR_BUCKETS[-1]) if scheme == "sr25519" else cap


def warm_epoch(entries, scheme: str = ""):
    """The batch's device-resident committee (its ops/epoch_cache.py
    entry, keyed by ValidatorSet.hash()), or None: no key, evicted, cache
    off, or another scheme's. A warm batch ships per-signature data only
    and the kernel gathers the committee's columns on the device."""
    from . import epoch_cache as _epoch

    if (scheme or getattr(entries, "scheme", "ed25519")) == "bls12381":
        # AggBlocks carry no gather indices: the lane keys its epoch on
        # the bitmap's committee
        return _bls_epoch(entries)
    return _epoch.lookup(entries)


def select_kernel(entries, bucket: int = 0, lanes: int = 0, mesh=None,
                  scheme: str = ""):
    """The engine for ONE device batch, chosen here and nowhere else:
    scheme, then the platform's ed25519 family (ops/engine.py: Pallas RLC
    on a TPU, the XLA op graph elsewhere, which is also the tests'
    reference), then warm epoch or not. Host prep runs here too.

    Returns the dispatcher's `_prepare` contract, (launch_fn, args,
    rlc_entries, bucket): `launch_fn(*args)` on the device-owner thread
    gives the verdict row; `rlc_entries` is the batch itself when that
    row holds one verdict per RLC lane of bucket // len(row) signatures,
    the width that launch ran at (the caller expands by it and
    re-verifies rejected lanes on the host, pallas_rlc.expand_lanes) and
    None when it holds one per row; `bucket` is the padded width.

    bucket  0 quantizes on the family's own ladder; the mesh forces its
            superbatch's width, warmup() the shape to compile
    lanes   > 0: a pack of that many lanes whose verdicts the caller
            demuxes by row, so the per-signature kernel of the family
            runs where the RLC one would (debt C1a)
    mesh    a jax Mesh to shard the batch axis over: the launch is then
            the family's shard_map twin (ops/sharded.py) and carries its
            per-argument transfer placements as `launch_fn.shardings`.
            secp256k1, sr25519 and bls12381 have no twin and launch on
            one device
    scheme  for tuple lists, which carry none (blocks carry their own)
    """
    n = len(entries)
    scheme = scheme or getattr(entries, "scheme", "ed25519")
    eng = engine()
    # donation: launches consume their per-batch inputs so XLA recycles
    # the pages; epoch tables stay exempt in every kernel's donate_argnums
    donate = eng.donate
    ep = warm_epoch(entries, scheme)
    if scheme == "bls12381":
        # one row = one aggregated commit
        bucket = bucket or _bls_bucket_for(n)
        vp = ep.vp if ep is not None else entries.pub48.shape[0] + 1
        masks, coeffs, ok, reasons = prepare_batch_bls(
            entries, bucket, vp, bad_rows=_bls_bad_rows(entries.pub48)
        )
        fn = bls_kernel(entries, ok, reasons, ep=ep, donate=donate)
        return fn, (masks, coeffs), None, bucket
    if scheme == "secp256k1":
        # Strauss+GLV ECDSA: plain XLA jit on every platform
        bucket = bucket or _secp_bucket_for(n)
        if ep is not None:
            return (secp_cached_kernel(ep, donate),
                    prepare_batch_secp_cached(entries, bucket, ep),
                    None, bucket)
        return (secp_kernel(donate), prepare_batch_secp(entries, bucket),
                None, bucket)
    if scheme == "sr25519":
        # schnorrkel over ristretto255: the Pallas ristretto kernel, the
        # lane's one engine (interpret mode off the TPU); per signature,
        # no epoch table, no donation
        from . import pallas_sr25519 as _ps
        from .pallas_verify import pick_block

        bucket = bucket or _sr_bucket_for(n)
        args = _ps.prepare_sr25519(entries, bucket)
        fn = functools.partial(_ps.verify_sr25519_compact,
                               block=pick_block(bucket),
                               interpret=eng.interpret)
        return fn, args, None, bucket
    if mesh is not None:
        from . import sharded as _sharded
    if eng.pallas:
        # the Pallas preps record no time of their own
        t0 = time.perf_counter()
        if eng.rlc and not lanes:
            from . import pallas_rlc

            fn, args, bucket, _m = pallas_rlc.rlc_launch(
                entries, ep, bucket=bucket, interpret=eng.interpret,
                donate=donate,
            )
            rlc_entries = entries
        else:
            from . import pallas_verify as _pv

            bucket = bucket or _pallas_bucket(n)
            block = _pv.pick_block(bucket // max(lanes, 1))
            rlc_entries = None
            if ep is not None and not lanes:
                args = _pv.prepare_compact_cached(entries, bucket, ep)
                fn = _pv.cached_compact_fn(
                    ep, bucket, block, eng.interpret, donate
                )
            else:
                # a lane pack ships its pubs: no coords table per shard
                args = _pv.prepare_compact(entries, bucket)
                if mesh is not None:
                    fn = _sharded.mesh_pallas_valid_fn(
                        mesh, bucket // lanes, block, eng.interpret
                    )
                else:
                    fn = _pv._jitted_pallas_verify(
                        bucket, block, eng.interpret, donate=donate
                    )
        _ops_m().host_prep_seconds.observe(
            time.perf_counter() - t0, bucket=str(bucket)
        )
        return fn, args, rlc_entries, bucket
    bucket = bucket or _bucket_for(n)
    if ep is not None:
        args = prepare_batch_cached(entries, bucket, ep)
        if mesh is not None:
            fn = _sharded.mesh_valid_fn_cached(mesh, ep, donate)
        else:
            fn = cached_kernel(ep, donate)
    else:
        args = prepare_batch(entries, bucket)
        if mesh is not None:
            fn = _sharded.mesh_valid_fn(mesh, donate)
        else:
            fn = ed25519_verify.jitted_verify(donate)
    return fn, args, None, bucket


def verify_batch(entries) -> np.ndarray:
    """Run the device kernel over arbitrary batch size (EntryBlock or
    tuple list); returns (n,) bool.

    This is the SANCTIONED direct device path (oversized batches past the
    pipeline's max bucket, standalone use, warmup) — under
    TM_TPU_DEVCHECK it runs in a devcheck.exempt() scope so the lazy
    epoch-table uploads it may trigger on the caller thread do not trip
    the device-ownership assertion while a dispatcher owns the device."""
    scheme = getattr(entries, "scheme", "ed25519")
    if scheme == "secp256k1":
        return verify_batch_secp(entries)
    if scheme == "bls12381":
        return verify_batch_bls(entries)
    with _devcheck.exempt():
        return _verify_batch_direct(entries,
                                    scheme_cap(scheme, max_coalesce()))


def _verify_batch_direct(entries, step: int, scheme: str = "") -> np.ndarray:
    """The chunk loop around select_kernel: `step` rows at a time, each
    chunk prepared, launched and waited for on the caller's thread."""
    out: List[np.ndarray] = []
    for i in range(0, len(entries), step):
        chunk = entries[i : i + step]
        fn, args, rlc_entries, bucket = select_kernel(chunk, scheme=scheme)
        # dispatch vs wait split: jax dispatch returns before the device
        # finishes; materializing the result blocks until it has
        t0 = time.perf_counter()
        with _span("ops.device_dispatch", bucket=bucket):
            dev = fn(*args)
        with _span("ops.device_wait", bucket=bucket):
            # owned copy, not a view: under donation a later chunk's
            # launch recycles the output page and would mutate earlier
            # chunks' verdicts still sitting in `out` (the PR-7 bug
            # class, here across the chunks of ONE oversized batch)
            res = np.array(dev)
        if res.ndim == 2:  # pallas rows are (1, N) int32
            res = res[0].astype(bool)
        if rlc_entries is not None:
            from . import pallas_rlc

            res = pallas_rlc.expand_lanes(
                res, rlc_entries, bucket // len(res))
        _note_device_batch(
            len(chunk), bucket, device_s=time.perf_counter() - t0,
            scheme=scheme or getattr(chunk, "scheme", ""),
        )
        out.append(res[: len(chunk)])
    return np.concatenate(out) if out else np.zeros((0,), dtype=bool)


class DeviceBatchVerifier(BatchVerifier):
    """Accumulate-then-verify for one scheme's keys: add() and add_block()
    gather the batch, verify() runs it on the scheme's host lane or
    submits it to the shared dispatcher (ops/pipeline.py), whose
    select_kernel picks the kernel by the block's scheme.

    Length/type validation on add() mirrors curve25519-voi's BatchVerifier
    Add (crypto/ed25519/ed25519.go:203-217); verify() returns
    (all_valid, per_sig_valid) like BatchVerifier.Verify (:219-227). A
    scheme's subclass names `scheme`, `key_class` and its host lane
    (_host_lane, _verify_host).
    """

    scheme = ""
    key_class = PubKey
    sig_size = 64      # ed25519's and sr25519's: a block's (n, 64) column

    def __init__(self):
        self._entries: List[Tuple[bytes, bytes, bytes]] = []
        self._blocks: List[EntryBlock] = []
        self.on_device = False     # where the last verify() ran

    def add(self, key: PubKey, msg: bytes, sig: bytes) -> None:
        if not isinstance(key, self.key_class):
            raise TypeError(f"pubkey is not {self.scheme}")
        if len(sig) != self.sig_size:
            raise ValueError("invalid signature length")
        self._entries.append((key.bytes(), msg, sig))

    def add_entries(self, entries, lengths_checked: bool = False) -> None:
        """Bulk add(): one validation pass + one extend instead of a call
        frame per signature (the per-commit GIL time this saves directly
        raises concurrent verify_commit throughput). The per-key TYPE
        check always runs — only the proposer's key type is validated at
        verifier creation, and a mixed-key validator set must fail here
        exactly as per-entry add() does. lengths_checked=True skips only
        the signature-length scan for callers that already enforced it
        (validation.py checks lengths during selection)."""
        if any(not isinstance(k, self.key_class) for k, _, _ in entries):
            raise TypeError(f"pubkey is not {self.scheme}")
        if not lengths_checked and any(
            len(s) != self.sig_size for _, _, s in entries
        ):
            raise ValueError("invalid signature length")
        self._entries.extend((k.bytes(), m, s) for k, m, s in entries)

    def add_block(self, block: EntryBlock, keys=None) -> None:
        """Columnar bulk add: the block rides BY REFERENCE to the device
        prep — no per-signature tuples at any point. `keys` (optional
        iterable of the lanes' PubKey objects) runs the same per-key TYPE
        check as add()/add_entries; lengths are structural in the block's
        (n, 32)/(n, 64) shape."""
        if keys is not None and any(
            not isinstance(k, self.key_class) for k in keys
        ):
            raise TypeError(f"pubkey is not {self.scheme}")
        if len(block):
            # flush interleaved add() entries first so verify order (and
            # blame indices) match submission order
            if self._entries:
                self._blocks.append(self._entry_block())
                self._entries = []
            self._blocks.append(block)

    def _entry_block(self) -> EntryBlock:
        return EntryBlock.from_entries(self._entries, scheme=self.scheme)

    def _host_lane(self, n: int) -> bool:
        """Whether a batch of n is verified on the host."""
        raise NotImplementedError

    def _verify_host(self, block: EntryBlock):
        """The host lane's (n,) verdicts."""
        raise NotImplementedError

    def _direct(self, n: int) -> bool:
        """Whether a device batch of n launches on the caller's thread
        (verify_batch) instead of through the dispatcher."""
        return False

    def verify(self) -> Tuple[bool, List[bool]]:
        n = len(self._entries) + sum(len(b) for b in self._blocks)
        if n == 0:
            return False, []
        blocks = list(self._blocks)
        if self._entries:
            blocks.append(self._entry_block())
        block = EntryBlock.concat(blocks)
        self.on_device = not self._host_lane(n)
        if not self.on_device:
            res = self._verify_host(block)
        elif self._direct(n):
            res = verify_batch(block)
        else:
            # the shared async pipeline: one worker thread owns every
            # device dispatch, so concurrent commit verifies coalesce into
            # full buckets and overlap host prep + D2H with device compute
            # instead of serializing RTTs
            from .pipeline import resolved_at, shared_verifier

            with _span("ops.pipeline_wait", n=n):
                fut = shared_verifier().submit(block)
                res = fut.result(timeout=600)
                if _trace.TRACER.enabled:
                    # the resolver's set_result -> this thread running again
                    t_res, launch = resolved_at(fut)
                    if t_res:
                        _trace.TRACER.record(
                            "ops.pipeline_wait.wake", t_res,
                            time.perf_counter(), {"launch": launch},
                        )
        res = np.asarray(res).astype(bool)
        # .all() and .tolist() both run in C — keeps the documented
        # (bool, List[bool]) interface without a 10k-iteration Python loop
        return bool(res.all()), res.tolist()


class Ed25519DeviceBatchVerifier(DeviceBatchVerifier):
    """ed25519: ZIP-215 on the host under DEVICE_THRESHOLD signatures
    (unless forced to the device), a batch past the largest bucket on the
    caller's thread (verify_batch), the dispatcher between."""

    scheme = _ed25519.KEY_TYPE
    key_class = _ed25519.PubKey

    def __init__(self, force_device: bool = False):
        super().__init__()
        self._force = force_device or bool(
            int(os.environ.get("TM_TPU_FORCE_DEVICE", "0"))
        )

    def _host_lane(self, n: int) -> bool:
        return n < DEVICE_THRESHOLD and not self._force

    def _verify_host(self, block: EntryBlock):
        m = _ops_m()
        m.host_fallback.inc()
        m.sigs_verified.inc(len(block), path="host")
        with _span("ops.verify_host", n=len(block)):
            return [
                _ed25519.verify_zip215_fast(pk, mg, s)
                for pk, mg, s in block.iter_entries()
            ]

    def _direct(self, n: int) -> bool:
        return n > BUCKETS[-1]


def warmup(bucket: int = BUCKETS[0]) -> None:
    """Pre-compile the platform's kernel for a bucket (the first compile
    of a shape is slow)."""
    fn, args, _rlc, _bucket = select_kernel(EntryBlock.empty(), bucket=bucket)
    np.asarray(fn(*args))
