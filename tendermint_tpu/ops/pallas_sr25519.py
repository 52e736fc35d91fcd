"""Device sr25519 (schnorrkel) verification — the ristretto lane.

Reference parity: crypto/sr25519/batch.go:13-19 (curve25519-voi's
schnorrkel batch verifier). Schnorr verification
    R == [s]B - [k]A,  k = merlin signing-transcript challenge
shares the joint double-scalar ladder with the ed25519 kernel
(ops.pallas_verify K2/K3 shapes); what differs is point DECODING
(ristretto255 DECODE instead of ZIP-215 edwards decompression) and the
final test (exact ristretto equality against R instead of cofactored
identity). The merlin challenges are host-side, reduced mod L in the
native C++ transcript (native/tm_native.cpp sr25519_challenges_buf;
pure-Python fallback); s/k scalars feed the same shift-grouped digit
layout.

The lane's batches come through the shared dispatcher like every other
scheme's: ops/backend.py select_kernel picks prepare_sr25519 and
verify_sr25519_compact for an EntryBlock tagged "sr25519", on the prep
thread and the dispatch-owner thread; nothing here launches on a
caller's thread. TM_TPU_SR_DEVICE=0, a batch under ops.mixed's
SR_DEVICE_THRESHOLD or an engine without Pallas verify on the host
instead (ops/mixed.py). A kernel that fails to compile or launch fails
its callers' futures with the dispatcher's DispatchError — there is no
watchdog and no silent host fallback.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fe_t, pallas_verify as pv
from ..crypto import _edwards
from ..observability.trace import span as _span
from .entry_block import EntryBlock

NL = fe_t.NLIMBS
P = _edwards.P
D = _edwards.D


def _ristretto_decode(s_limbs, ok_host):
    """ristretto255 DECODE on (20, B) limbs of s (host pre-checked:
    canonical s < p and even). Returns (ok (1,B), point)."""
    one = fe_t.limbs_from_int_t(1)
    d_col = fe_t.limbs_from_int_t(D)
    s = fe_t.carry(s_limbs)
    ss = fe_t.sq(s)
    u1 = fe_t.sub(one + jnp.zeros_like(s), ss)  # 1 - s^2
    u2 = fe_t.add(one + jnp.zeros_like(s), ss)  # 1 + s^2
    u2_sqr = fe_t.sq(u2)
    # v = -(D * u1^2) - u2^2
    v = fe_t.sub(fe_t.neg(fe_t.mul(d_col, fe_t.sq(u1))), u2_sqr)
    # invsqrt(v * u2^2): sqrt_ratio(1, x) gives r with x*r^2 == 1 when ok
    was_square, invsq = pv.sqrt_ratio(one + jnp.zeros_like(s), fe_t.mul(v, u2_sqr))
    den_x = fe_t.mul(invsq, u2)
    den_y = fe_t.mul(fe_t.mul(invsq, den_x), v)
    x = fe_t.mul(fe_t.add(s, s), den_x)
    # |x|: negate when odd
    x = fe_t.canon(x)
    x = jnp.where((x[0:1] & 1) != 0, fe_t.neg(x), x)
    y = fe_t.mul(u1, den_y)
    t = fe_t.mul(x, y)
    t_odd = (fe_t.canon(t)[0:1] & 1) != 0
    y_zero = fe_t.is_zero(y)
    ok = was_square & ~t_odd & ~y_zero & (ok_host != 0)
    z = jnp.broadcast_to(one, y.shape)
    return ok, (x, y, z, t)


def _k1r_decode_kernel(a_ref, r_ref, s_ref, k_ref, aok_ref, rok_ref,
                       coords_ref, ok_ref, sdig_ref, kdig_ref):
    """Ristretto decode of A and R (lane-folded) + scalar digit unpack.
    Output layout matches pallas_verify's K1 (32-row coordinate slots)."""
    a_enc = a_ref[:].astype(jnp.int32)
    r_enc = r_ref[:].astype(jnp.int32)
    sdig_ref[:] = pv._unpack_digits2_grouped(s_ref[:].astype(jnp.int32))
    kdig_ref[:] = pv._unpack_digits2_grouped(k_ref[:].astype(jnp.int32))

    a_y, _ = pv._unpack_limbs(a_enc)  # sign bit is rejected host-side
    r_y, _ = pv._unpack_limbs(r_enc)
    B = a_y.shape[-1]
    ok_ar, AR = _ristretto_decode(
        pv._cat([a_y, r_y]),
        pv._cat([aok_ref[0:1], rok_ref[0:1]]),
    )
    ok_ref[0:1] = ok_ar[:, :B].astype(jnp.int32)
    ok_ref[1:2] = ok_ar[:, B:].astype(jnp.int32)
    for c in range(4):
        coords_ref[c * 32 : c * 32 + NL] = AR[c][:, :B]
        coords_ref[(4 + c) * 32 : (4 + c) * 32 + NL] = AR[c][:, B:]


def _k3r_ladder_kernel(tbl_ref, sdig_ref, kdig_ref, coords_ref, ok_ref,
                       sok_ref, out_ref):
    """Joint ladder acc = [s]B + [k](-A), then EXACT ristretto equality
    against R: x1*y2 == y1*x2 or y1*y2 == x1*x2 (z cancels on both sides
    since R decodes with z=1 and both tests are cross-multiplied)."""
    B = sok_ref.shape[-1]
    zero = jnp.zeros((NL, B), dtype=jnp.int32)
    one = fe_t.limbs_from_int_t(1)
    ident = (zero, one + zero, one + zero, zero)

    def select(idx):
        out = [tbl_ref[c * 32 : c * 32 + NL] for c in range(4)]
        for e in range(1, 16):
            m = (idx == e)[None, :]
            for c in range(4):
                out[c] = jnp.where(
                    m, tbl_ref[(e * 4 + c) * 32 : (e * 4 + c) * 32 + NL], out[c]
                )
        return tuple(out)

    def body(i, acc):
        j = pv._digit_row(126 - i)
        # table entries are Niels-form since the shared K2 stores them
        # that way (pallas_verify._k2_table_kernel to_niels)
        acc = pv.point_double(pv.point_double(acc, need_t=False))
        return pv.point_add_niels(
            acc, select(sdig_ref[j] + 4 * kdig_ref[j]), need_t=False
        )

    acc = lax.fori_loop(0, 127, body, ident)
    rx = coords_ref[4 * 32 : 4 * 32 + NL]
    ry = coords_ref[5 * 32 : 5 * 32 + NL]
    rz = coords_ref[6 * 32 : 6 * 32 + NL]
    # acc == R (projective, ristretto equivalence class)
    eq1 = fe_t.is_zero(
        fe_t.sub(fe_t.mul(acc[0], ry), fe_t.mul(acc[1], rx))
    )
    eq2 = fe_t.is_zero(
        fe_t.sub(fe_t.mul(acc[1], ry), fe_t.mul(acc[0], rx))
    )
    del rz
    valid = (
        (ok_ref[0:1] != 0) & (ok_ref[1:2] != 0) & (sok_ref[0:1] != 0)
        & (eq1 | eq2)
    )
    out_ref[:] = valid.astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _jitted_sr25519_verify(n: int, block: int, interpret: bool):
    k2_block = min(block, 256)

    def mkspec(b):
        def spec(rows):
            return pl.BlockSpec((rows, b), lambda i: (0, i), memory_space=pltpu.VMEM)

        return spec

    spec = mkspec(block)
    spec2 = mkspec(k2_block)

    k1 = pl.pallas_call(
        _k1r_decode_kernel,
        grid=(n // block,),
        in_specs=[spec(32)] * 4 + [spec(1), spec(1)],
        out_specs=[spec(8 * 32), spec(2), spec(128), spec(128)],
        out_shape=[
            jax.ShapeDtypeStruct((8 * 32, n), jnp.int32),
            jax.ShapeDtypeStruct((2, n), jnp.int32),
            jax.ShapeDtypeStruct((128, n), jnp.int32),
            jax.ShapeDtypeStruct((128, n), jnp.int32),
        ],
        interpret=interpret,
    )
    k2 = pl.pallas_call(
        pv._k2_table_kernel,
        grid=(n // k2_block,),
        in_specs=[spec2(8 * 32)],
        out_specs=spec2(16 * 4 * 32),
        out_shape=jax.ShapeDtypeStruct((16 * 4 * 32, n), jnp.int32),
        interpret=interpret,
    )
    k3 = pl.pallas_call(
        _k3r_ladder_kernel,
        grid=(n // block,),
        in_specs=[spec(16 * 4 * 32), spec(128), spec(128), spec(8 * 32), spec(2), spec(1)],
        out_specs=spec(1),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )

    def pipeline(packed):
        a_t, r_t, s_t, k_t, aok_t, rok_t, sok_t = packed_views(packed)
        coords, ok, sdig, kdig = k1(a_t, r_t, s_t, k_t, aok_t, rok_t)
        tbl = k2(coords)
        return k3(tbl, sdig, kdig, coords, ok, sok_t)

    # the name the device trace files the launch's operations under, so
    # that a reader of kernel time can find them (PERF.md §7)
    pipeline.__name__ = f"sr25519_verify_n{n}_b{block}"
    return jax.jit(pipeline)


# One launch's arguments are ONE int32 buffer (a put costs the same
# whatever it carries, PERF.md §6 PR 29): rows of the A, R, s and k
# encodings, 32 bytes a lane each, then the a_ok, r_ok, s_ok flags.
PACKED_ROWS = 4 * 32 + 3


def packed_views(packed):
    """(a_t, r_t, s_t, k_t, aok_t, rok_t, sok_t): the kernel's arguments
    as row slices of the packed buffer (numpy on the host, jax inside
    the jitted launch)."""
    return (packed[0:32], packed[32:64], packed[64:96], packed[96:128],
            packed[128:129], packed[129:130], packed[130:131])


_P_BE = np.frombuffer(P.to_bytes(32, "big"), dtype=np.uint8)


def _canonical_even(enc: np.ndarray, n: int, bucket: int) -> np.ndarray:
    """(bucket, 32) LE field encodings -> host-side ristretto encoding
    admission: value < p AND even (ristretto rejects negative s)."""
    ok = np.zeros((bucket,), dtype=bool)
    ok[n:] = True  # padding (all-zero = identity encoding)
    if n:
        be = enc[:n, ::-1]
        diff = be != _P_BE
        has_diff = diff.any(axis=1)
        first = diff.argmax(axis=1)
        rng = np.arange(n)
        below_p = has_diff & (be[rng, first] < _P_BE[first])
        ok[:n] = below_p & ((enc[:n, 0] & 1) == 0)
    return ok


def _challenges(block: EntryBlock) -> bytes:
    """k_i = merlin "sign:c" challenge mod L of each row, 32 bytes LE a
    row: tm_native.sr25519_challenges_buf over the block's contiguous
    sign bytes (spans ops.sr_prep.challenges.native / .gil from its own
    clock reads, native.traced_call), else the pure-Python transcript."""
    from .. import native as _native
    from ..crypto._edwards import L
    from ..crypto.sr25519 import SIGNING_CTX, _signing_transcript

    pubs = block.pub.tobytes()
    rs = np.ascontiguousarray(block.sig[:, :32]).tobytes()
    mod = _native.load()
    if mod is not None and hasattr(mod, "sr25519_challenges_buf"):
        buf, offs = block.msgs_contiguous()
        return _native.traced_call(
            mod, "sr25519_challenges_buf", "ops.sr_prep.challenges",
            SIGNING_CTX, pubs, rs, buf, np.ascontiguousarray(offs).tobytes())
    out = []
    for i, msg in enumerate(block.msg_views()):
        t = _signing_transcript(bytes(msg))
        t.append_message(b"proto-name", b"Schnorr-sig")
        t.append_message(b"sign:pk", pubs[32 * i : 32 * i + 32])
        t.append_message(b"sign:R", rs[32 * i : 32 * i + 32])
        k = int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little") % L
        out.append(k.to_bytes(32, "little"))
    return b"".join(out)


def prepare_sr25519(entries, bucket: int):
    """An sr25519 EntryBlock (or (pub32, msg, sig64) schnorrkel triples)
    -> the kernel's one argument, `(packed,)`: PACKED_ROWS x `bucket`
    int32 (packed_views). Spans, inside pipeline.prep:
    ops.sr_prep.challenges (the merlin challenges, see _challenges), then
    ops.sr_prep.fill (v1-marker and s < L checks, canonical-encoding
    flags, the rows written in place). Padding lanes are the ristretto
    identity (all-zero encodings, s = k = 0) and verify."""
    from .backend import _s_below_l

    block = (entries if isinstance(entries, EntryBlock)
             else EntryBlock.from_entries(list(entries), scheme="sr25519"))
    n = len(block)
    with _span("ops.sr_prep.challenges", n=n):
        ks = _challenges(block) if n else b""
    with _span("ops.sr_prep.fill", n=n, bucket=bucket):
        packed = np.zeros((PACKED_ROWS, bucket), dtype=np.int32)
        a_t, r_t, s_t, k_t, aok, rok, sok = packed_views(packed)
        aok[:] = rok[:] = sok[:] = 1
        if n:
            r_enc = block.sig[:, :32]
            s_enc = block.sig[:, 32:].copy()
            # schnorrkel v1 marks s's top bit; the scalar is s without it
            marked = (s_enc[:, 31] & 0x80) != 0
            s_enc[:, 31] &= 0x7F
            a_t[:, :n] = block.pub.T
            r_t[:, :n] = r_enc.T
            s_t[:, :n] = s_enc.T
            k_t[:, :n] = np.frombuffer(ks, dtype=np.uint8).reshape(n, 32).T
            aok[0] = _canonical_even(block.pub, n, bucket)
            rok[0] = _canonical_even(r_enc, n, bucket)
            sok[0, :n] = _s_below_l(s_enc, n, n) & marked
        return (packed,)


def verify_sr25519_compact(packed, block: int = 0, interpret: bool = False):
    """Launches the ristretto kernel over prepare_sr25519's buffer and
    returns its (1, n) int32 verdict row as the device gives it: the
    dispatcher launches, the resolver waits (ops/backend.py select_kernel
    is the one caller in the program)."""
    block = block or pv.BLOCK
    n = packed.shape[-1]
    if n % block:
        raise ValueError(f"batch {n} not a multiple of block {block}")
    return _jitted_sr25519_verify(n, block, interpret)(packed)
