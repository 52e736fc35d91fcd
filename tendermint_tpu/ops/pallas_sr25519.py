"""Device sr25519 (schnorrkel) verification — the ristretto lane.

Reference parity: crypto/sr25519/batch.go:13-19 (curve25519-voi's
schnorrkel batch verifier). Schnorr verification
    R == [s]B - [k]A,  k = merlin signing-transcript challenge
shares the joint double-scalar ladder with the ed25519 kernel
(ops.pallas_verify K2/K3 shapes); what differs is point DECODING
(ristretto255 DECODE instead of ZIP-215 edwards decompression) and the
final test (exact ristretto equality against R instead of cofactored
identity). The merlin challenges are host-side via the native C++
transcript (native/tm_native.cpp sr25519_challenges; pure-Python
fallback), s/k scalars feed the same shift-grouped digit layout.

Round-3 measured context: pure-Python sr25519 verify is ~10 ms/sig — the
mixed-curve BASELINE config #4 was host-bound; this lane moves the EC
math (2 scalar mults/sig) onto the device and the transcripts into C.

The lane is ON by default (TM_TPU_SR_DEVICE=0 selects the native host
lane). A kernel that fails to compile or launch raises to the caller of
ops.mixed — there is no watchdog and no silent host fallback. Whether it
compiles under the installed libtpu is recorded in the README's scheme
matrix ("runs on v5e").
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

    def pipeline(a_t, r_t, s_t, k_t, aok_t, rok_t, sok_t):
        coords, ok, sdig, kdig = k1(a_t, r_t, s_t, k_t, aok_t, rok_t)
        tbl = k2(coords)
        return k3(tbl, sdig, kdig, coords, ok, sok_t)

    return jax.jit(pipeline)


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


def prepare_sr25519(entries, bucket: int):
    """(pub32, msg, sig64) schnorrkel triples -> kernel args. Host work:
    v1-marker/s<L checks, canonical-encoding flags, merlin challenges
    (native C++, pure-Python fallback) reduced mod L."""
    from ..crypto._edwards import L
    from ..crypto.sr25519 import SIGNING_CTX, _signing_transcript
    from ..native import load as _load_native
    from .backend import _pack_rows, _s_below_l

    n = len(entries)
    marker_ok = np.zeros((bucket,), dtype=bool)
    marker_ok[n:] = True
    cleaned = []
    for i, (pk, msg, sig) in enumerate(entries):
        if len(sig) != 64 or len(pk) != 32:
            marker_ok[i] = False
            cleaned.append((bytes(32), msg, bytes(64)))
            continue
        sig = bytearray(sig)
        marker_ok[i] = bool(sig[63] & 0x80)
        sig[63] &= 0x7F
        cleaned.append((pk, msg, bytes(sig)))
    pub, r_enc, s_enc = _pack_rows(cleaned, bucket)
    # padding: _pack_rows pads with the EDWARDS identity encoding (0x01),
    # which is an odd (invalid) ristretto encoding — the ristretto
    # identity is the all-zero string
    pub[n:] = 0
    r_enc[n:] = 0
    s_ok = _s_below_l(s_enc, n, bucket) & marker_ok
    a_ok = _canonical_even(pub, n, bucket)
    r_ok = _canonical_even(r_enc, n, bucket)

    k_enc = np.zeros((bucket, 32), dtype=np.uint8)
    if n:
        native = _load_native()
        pubs = b"".join(pk for pk, _, _ in cleaned)
        rss = bytes(r_enc[:n].tobytes())
        msgs = [m for _, m, _ in cleaned]
        if native is not None:
            raw = native.sr25519_challenges(SIGNING_CTX, pubs, rss, msgs)
            digests = [raw[64 * i : 64 * (i + 1)] for i in range(n)]
        else:
            digests = []
            for (pk, msg, _), i in zip(cleaned, range(n)):
                t = _signing_transcript(msg)
                t.append_message(b"proto-name", b"Schnorr-sig")
                t.append_message(b"sign:pk", pk)
                t.append_message(b"sign:R", rss[32 * i : 32 * (i + 1)])
                digests.append(t.challenge_bytes(b"sign:c", 64))
        ks = b"".join(
            (int.from_bytes(d, "little") % L).to_bytes(32, "little") for d in digests
        )
        k_enc[:n] = np.frombuffer(ks, dtype=np.uint8).reshape(n, 32)

    return (
        np.ascontiguousarray(pub.T),
        np.ascontiguousarray(r_enc.T),
        np.ascontiguousarray(s_enc.T),
        np.ascontiguousarray(k_enc.T),
        np.ascontiguousarray(a_ok.astype(np.int32)[None, :]),
        np.ascontiguousarray(r_ok.astype(np.int32)[None, :]),
        np.ascontiguousarray(s_ok.astype(np.int32)[None, :]),
    )


def verify_sr25519_compact(*args, block: int = 0, interpret: bool = False):
    block = block or pv.BLOCK
    n = args[0].shape[-1]
    if n % block:
        raise ValueError(f"batch {n} not a multiple of block {block}")
    out = _jitted_sr25519_verify(n, block, interpret)(*args)
    return np.asarray(out)[0].astype(bool)
