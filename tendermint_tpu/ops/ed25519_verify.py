"""Batched ZIP-215 ed25519 verification kernel as a plain XLA op graph.

The engine of every platform that is not a TPU (ops/engine.py: Pallas
could only interpret there) and the tests' reference for the Pallas
kernels the chip runs (ops/pallas_rlc.py). Semantics are *per-signature*
cofactored verification — exactly the oracle in
tendermint_tpu.crypto._edwards.verify_zip215:

    accept iff  A, R decompress (non-canonical y allowed),
                0 <= s < L (checked host-side), and
                [8]([s]B - R - [k]A) == O,  k = SHA512(R||A||M) mod L.

The challenge k is hashed on the host (backend._challenges: one native
SHA-512 pass per batch) and ships as a scalar; the kernel is the ladder.
Per-signature evaluation yields the valid[] vector that
types/validation.go:242-248 needs for blame assignment directly, with no
host-side randomness.

Control flow is branchless (complete twisted-Edwards formulas, masked
selects), shapes are static per bucket: everything jits to one XLA
computation with a 253-iteration fori_loop over the joint (Straus)
double-scalar ladder.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import fe
from ..crypto import _edwards

# Curve constants in limb form (host-computed Python ints -> 20-limb arrays).
# Kept as NUMPY arrays, not jnp: a module-level jnp constant created while
# another function is being traced becomes a tracer and leaks (the r2 bench
# crash); numpy constants are trace-immune and jit folds them identically.
D_L = np.asarray(fe.limbs_from_int(_edwards.D))
D2_L = np.asarray(fe.limbs_from_int(_edwards.D2))
SQRT_M1_L = np.asarray(fe.limbs_from_int(_edwards.SQRT_M1))
BX_L = np.asarray(fe.limbs_from_int(_edwards.BASE[0]))
BY_L = np.asarray(fe.limbs_from_int(_edwards.BASE[1]))
BT_L = np.asarray(fe.limbs_from_int(_edwards.BASE[3]))

SCALAR_BITS = 253  # s, k < L < 2^253


def point_add(p, q):
    """Unified add-2008-hwcd-3 (a=-1): complete for all inputs including
    the identity — mirrors crypto/_edwards.point_add."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = fe.mul(fe.sub(y1, x1), fe.sub(y2, x2))
    b = fe.mul(fe.add(y1, x1), fe.add(y2, x2))
    c = fe.mul(fe.mul(t1, D2_L), t2)
    zz = fe.mul(z1, z2)
    d = fe.add(zz, zz)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_double(p):
    """Dedicated dbl-2008-hwcd (a=-1) — mirrors crypto/_edwards.point_double."""
    x1, y1, z1, _ = p
    a = fe.sq(x1)
    b = fe.sq(y1)
    zz = fe.sq(z1)
    c = fe.add(zz, zz)
    e = fe.sub(fe.sub(fe.sq(fe.add(x1, y1)), a), b)
    g = fe.sub(b, a)  # (-a) + b
    f = fe.sub(g, c)
    h = fe.neg(fe.add(a, b))  # (-a) - b
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_neg(p):
    x, y, z, t = p
    return (fe.neg(x), y, z, fe.neg(t))


def sqrt_ratio(u, v):
    """(ok, r) with v*r^2 == u when ok; p ≡ 5 (mod 8) exponentiation trick
    (RFC 8032 §5.1.3 step 3; crypto/_edwards._sqrt_ratio)."""
    v3 = fe.mul(fe.sq(v), v)
    v7 = fe.mul(fe.sq(v3), v)
    r = fe.mul(fe.mul(u, v3), fe.pow22523(fe.mul(u, v7)))
    check = fe.mul(v, fe.sq(r))
    ok_pos = fe.eq(check, u)
    ok_neg = fe.is_zero(fe.add(check, u))
    r = jnp.where(ok_pos[..., None], r, fe.mul(r, SQRT_M1_L))
    return ok_pos | ok_neg, r


def decompress(y_limbs, sign):
    """ZIP-215 decompression: y already reduced mod-range (low 255 bits of
    the encoding; values >= p are implicitly reduced by the field arithmetic
    — the non-canonical acceptance of crypto/_edwards.decompress)."""
    y = fe.carry(y_limbs)
    yy = fe.sq(y)
    u = fe.sub(yy, fe.ONE)
    v = fe.add(fe.mul(D_L, yy), fe.ONE)
    ok, x = sqrt_ratio(u, v)
    # Conditional negate to match the sign bit; "negative zero" decodes to
    # x = 0 (no step-4 rejection — ZIP-215 / curve25519-dalek behavior).
    x = fe.canon(x)
    flip = (x[..., 0] & 1) != sign
    x = jnp.where(flip[..., None], fe.neg(x), x)
    t = fe.mul(x, y)
    z = jnp.broadcast_to(fe.ONE, y.shape)
    return ok, (x, y, z, t)


def _broadcast_point(coords, shape):
    return tuple(jnp.broadcast_to(c, shape) for c in coords)


def _stack_points(points, axis=0):
    """[(x,y,z,t), ...] -> one point whose coords carry a new stacked axis."""
    return tuple(
        jnp.stack([pt[c] for pt in points], axis=axis) for c in range(4)
    )


def _unstack_point(point, i):
    return tuple(c[i] for c in point)


def _select_point(table, idx):
    """table: point with (..., 16, 20) coords; idx: (...,) in [0,16)."""
    out = []
    for c in table:
        picked = jnp.take_along_axis(c, idx[..., None, None], axis=-2)
        out.append(picked[..., 0, :])
    return tuple(out)


def _bits_to_digits2(bits_t):
    """(253, B) LSB-first bits -> (127, B) base-4 digits (bit 253 = 0)."""
    pad = jnp.zeros((1,) + bits_t.shape[1:], dtype=bits_t.dtype)
    padded = jnp.concatenate([bits_t, pad], axis=0)  # (254, B)
    pairs = padded.reshape(127, 2, *padded.shape[1:])
    return pairs[:, 0] + 2 * pairs[:, 1]


def verify_kernel(a_y, a_sign, r_y, r_sign, s_bits_t, k_bits_t, s_ok):
    """Batched cofactored verification.

    Joint 2-bit-window Straus ladder: 127 iterations of (2 doublings +
    one add from a 16-entry per-element table of s2*B + k2*(-A)) — ~20%
    fewer field multiplies than the 1-bit ladder at the cost of 11 table
    adds per batch element.

    Args (B = batch):
      a_y, r_y:       (B, 20) int32 — low-255-bit limbs of A / R encodings
      a_sign, r_sign: (B,)    int32 — encoding bit 255
      s_bits_t:       (253, B) int32 — bits of s, LSB-first (transposed so
                      the ladder indexes rows dynamically)
      k_bits_t:       (253, B) int32 — bits of k = SHA512(R||A||M) mod L
      s_ok:           (B,)    bool  — host-checked s < L
    Returns: (B,) bool.
    """
    # Decompress A and R in ONE batched call: the dominant subgraph
    # (sqrt_ratio -> pow22523, ~254 squarings) traces/compiles once and the
    # two decompressions run data-parallel on a stacked leading axis.
    ok_ar, AR = decompress(
        jnp.stack([a_y, r_y], axis=0), jnp.stack([a_sign, r_sign], axis=0)
    )
    ok_a, ok_r = ok_ar[0], ok_ar[1]
    A = _unstack_point(AR, 0)
    R = _unstack_point(AR, 1)
    negA = point_neg(A)
    negR = point_neg(R)

    # Derive broadcast constants from the inputs (x + 0*input) so they carry
    # the same varying-manual-axes as the batch under shard_map — a plain
    # jnp.broadcast_to constant would be "replicated" and reject as a
    # fori_loop carry there.
    zero_b = a_y - a_y
    base = (BX_L + zero_b, BY_L + zero_b, fe.ONE + zero_b, BT_L + zero_b)
    ident = (zero_b, fe.ONE + zero_b, fe.ONE + zero_b, zero_b)

    # 16-entry table: idx = s2 + 4*k2 -> [s2]B + [k2](-A). Built with three
    # batched point ops (vs 13 separate traces): one double for {2B, 2(-A)},
    # one add for {3B, 3(-A)}, one 9-lane add for the cross terms.
    pair = _stack_points([base, negA])
    dbl = point_double(pair)
    tri = point_add(dbl, pair)
    b_row = [ident, base, _unstack_point(dbl, 0), _unstack_point(tri, 0)]
    a_col = [ident, negA, _unstack_point(dbl, 1), _unstack_point(tri, 1)]
    cross = point_add(
        _stack_points([b_row[s2] for _ in range(1, 4) for s2 in range(1, 4)]),
        _stack_points([a_col[k2] for k2 in range(1, 4) for _ in range(1, 4)]),
    )
    entries = []
    for k2 in range(4):
        for s2 in range(4):
            if k2 == 0:
                entries.append(b_row[s2])
            elif s2 == 0:
                entries.append(a_col[k2])
            else:
                entries.append(_unstack_point(cross, (k2 - 1) * 3 + (s2 - 1)))
    table = _stack_points(entries, axis=-2)  # coords (..., 16, 20)

    s_digits = _bits_to_digits2(s_bits_t)  # (127, B)
    k_digits = _bits_to_digits2(k_bits_t)

    def body(i, acc):
        j = 126 - i
        s2 = lax.dynamic_index_in_dim(s_digits, j, 0, keepdims=False)
        k2 = lax.dynamic_index_in_dim(k_digits, j, 0, keepdims=False)
        acc = point_double(point_double(acc))
        addend = _select_point(table, s2 + 4 * k2)
        return point_add(acc, addend)

    acc = lax.fori_loop(0, 127, body, ident)
    acc = point_add(acc, negR)
    # Multiply by the cofactor 8 and test against the identity.
    acc = lax.fori_loop(0, 3, lambda _, p: point_double(p), acc)
    is_ident = fe.is_zero(acc[0]) & fe.is_zero(fe.sub(acc[1], acc[2]))
    return ok_a & ok_r & s_ok & is_ident


# -- on-device unpack + epoch-cached variants --------------------------------
#
# The cached kernels take the COMMITTEE as a persistent device table
# (uploaded once per epoch by ops/epoch_cache.py) plus per-signature gather
# indices, and the per-signature scalars/encodings as RAW 32-byte rows —
# limb and bit unpacking are trivial device work, while on the host they
# were the bulk of prepare_batch's wall time. Steady-state
# batches therefore ship ~101 B/sig instead of ~2.2 kB/sig on this path.


def unpack_limbs_rows(enc):
    """(B, 32) int32 LE bytes -> ((B, 20) int32 low-255-bit limbs, (B,)
    int32 sign). The device twin of backend._pack_le_limbs — same 13-bit
    windows, row-major; static per-limb byte arithmetic, no gathers."""
    sign = enc[:, 31] >> 7
    b31 = enc[:, 31] & 0x7F

    def byte(i):
        return b31 if i == 31 else enc[:, i]

    rows = []
    for i in range(fe.NLIMBS):
        lo_bit = fe.RADIX * i
        byte0 = lo_bit >> 3
        shift = lo_bit & 7
        v = byte(byte0)
        if byte0 + 1 < 32:
            v = v + (byte(byte0 + 1) << 8)
        if byte0 + 2 < 32 and shift + fe.RADIX > 16:
            v = v + (byte(byte0 + 2) << 16)
        rows.append((v >> shift) & fe.MASK)
    return jnp.stack(rows, axis=-1), sign


def bits253_rows(enc):
    """(B, 32) int32 LE scalar bytes (< 2^253) -> (253, B) int32 bits,
    LSB-first, transposed for the ladder — the device twin of
    backend._bits_253."""
    bits = (enc[:, :, None] >> jnp.arange(8, dtype=enc.dtype)) & 1
    return bits.reshape(enc.shape[0], 256).T[:253]


def verify_kernel_cached(
    a_tbl_limbs, a_tbl_sign, val_idx, r_enc, s_enc, k_enc, s_ok
):
    """verify_kernel with the committee gathered from a device-resident
    epoch table and per-sig limb/bit unpack on device.

    a_tbl_limbs (V, 20) int32 / a_tbl_sign (V,) int32: the epoch's pubkey
    rows (row V-1 = identity, the padding lane). val_idx (B,) int32 gather
    indices; r_enc/s_enc/k_enc (B, 32) uint8 raw rows."""
    a_y = a_tbl_limbs[val_idx]
    a_sign = a_tbl_sign[val_idx]
    r_y, r_sign = unpack_limbs_rows(r_enc.astype(jnp.int32))
    s_bits_t = bits253_rows(s_enc.astype(jnp.int32))
    k_bits_t = bits253_rows(k_enc.astype(jnp.int32))
    return verify_kernel(a_y, a_sign, r_y, r_sign, s_bits_t, k_bits_t, s_ok)


# Donation (ISSUE 7): with donate=True the jitted wrapper donates every
# PER-BATCH input buffer to XLA, so a launch consumes its inputs and their
# pages return to the allocator for the next batch's device_put — the
# "recycled device allocation" steady state the dispatcher's buffer pool
# (ops/device_pool.py) bounds. The epoch-table arguments of the cached
# kernels (argnums 0-1) are persistent device residents shared across
# batches and are NEVER donated — donating them would invalidate the
# cache entry after one launch.


@functools.lru_cache(maxsize=None)
def jitted_verify(donate: bool = False):
    if donate:
        return jax.jit(verify_kernel, donate_argnums=tuple(range(7)))
    return jax.jit(verify_kernel)


@functools.lru_cache(maxsize=None)
def jitted_verify_cached(donate: bool = False):
    if donate:
        return jax.jit(verify_kernel_cached,
                       donate_argnums=tuple(range(2, 7)))
    return jax.jit(verify_kernel_cached)
