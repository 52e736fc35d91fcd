"""Single-kernel batched ZIP-215 verification (Pallas / Mosaic, TPU).

The whole verification — point decompression, 16-entry Straus table, the
127-iteration joint double-scalar ladder, cofactor-8 clearing and the
identity test — runs as ONE Pallas kernel per batch block, entirely in
VMEM. Rationale (measured on the target device, round 3): the XLA op-graph
kernel pays an HBM round-trip (and device dispatch overhead) per fused op,
capping it near ~15k sigs/s; fusing the ladder into one kernel removes
every intermediate HBM touch.

Inputs are the COMPACT wire encodings (batch-minor uint8: 32 B/sig for
each of A, R, S, k ≈ 129 B/sig total vs ~1.6 kB/sig for the unpacked
int32 arrays) — limb and base-4-digit unpacking happens in-kernel, which
matters because host→device transfer on the TPU is part of
every commit's critical path.

Semantics are identical to ops.ed25519_verify / crypto._edwards
(per-signature cofactored ZIP-215, crypto/ed25519/ed25519.go:26-31 parity):
  accept iff A, R decompress (non-canonical y allowed), s < L (host-checked
  flag), and [8]([s]B - [k]A) == [8]R — evaluated as a doubles-only
  projective cross-multiplication (complete for small-order inputs) —
  with k = SHA512(R||A||M) mod L computed host-side: the native batch
  helper is ~17 ms/batch, fully hidden behind the 33 ms device pass by
  the async pipeline, and shipping k costs 32 B/sig vs ~256 B/sig for
  on-device hashing.

Table entries are stored in Niels form (Y+X, Y-X, Z, T*2d) and the
ladder carries no T (doubles never read it; see point_double/
point_add_niels need_t).
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fe_t
from ..crypto import _edwards

# Curve constants are materialized per-trace from Python ints via
# fe_t.limbs_from_int_t (Pallas kernels cannot capture array constants);
# XLA/Mosaic CSEs the repeated scalar stacks.
def D_T():
    return fe_t.limbs_from_int_t(_edwards.D)


def D2_T():
    return fe_t.limbs_from_int_t(_edwards.D2)


def SQRT_M1_T():
    return fe_t.limbs_from_int_t(_edwards.SQRT_M1)


NL = fe_t.NLIMBS

# Default lanes per kernel block: table (4 coords x 16 x 20 x B x 4B) plus
# digit scratch must fit VMEM (~16 MB) with headroom. Env-tunable for
# block-size sweeps on real hardware; must divide every bucket size or
# grid=(n // block,) would silently leave the tail lanes unverified.
BLOCK = int(os.environ.get("TM_TPU_PALLAS_BLOCK", "512"))
if BLOCK <= 0 or 10240 % BLOCK:
    raise ValueError(
        f"TM_TPU_PALLAS_BLOCK={BLOCK} must be a positive divisor of 10240"
    )


def pick_block(n: int) -> int:
    """Largest kernel block size dividing an n-lane (per-shard) batch —
    the one candidate ladder shared by every sharded/mesh call site, so
    the grid shape for a given per-shard size can never drift between
    paths."""
    for cand in (BLOCK, 256, 128, 64, 32, 16, 8):
        if n % cand == 0:
            return cand
    return n


# -- point ops (limb-major; mirrors ops.ed25519_verify) ---------------------


def point_add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = fe_t.mul(fe_t.sub(y1, x1), fe_t.sub(y2, x2))
    b = fe_t.mul(fe_t.add(y1, x1), fe_t.add(y2, x2))
    c = fe_t.mul(fe_t.mul(t1, D2_T()), t2)
    zz = fe_t.mul(z1, z2)
    d = fe_t.add(zz, zz)
    e = fe_t.sub(b, a)
    f = fe_t.sub(d, c)
    g = fe_t.add(d, c)
    h = fe_t.add(b, a)
    return (fe_t.mul(e, f), fe_t.mul(g, h), fe_t.mul(f, g), fe_t.mul(e, h))


def point_double(p, need_t: bool = True):
    """Doubling never READS t; with need_t=False it also skips producing
    it (the e*h mul) — valid whenever the consumer is another double or a
    select, which covers the first double of every ladder iteration."""
    x1, y1, z1 = p[0], p[1], p[2]
    a = fe_t.sq(x1)
    b = fe_t.sq(y1)
    zz = fe_t.sq(z1)
    c = fe_t.add(zz, zz)
    e = fe_t.sub(fe_t.sub(fe_t.sq(fe_t.add(x1, y1)), a), b)
    g = fe_t.sub(b, a)
    f = fe_t.sub(g, c)
    h = fe_t.neg(fe_t.add(a, b))
    t = fe_t.mul(e, h) if need_t else jnp.zeros_like(x1)
    return (fe_t.mul(e, f), fe_t.mul(g, h), fe_t.mul(f, g), t)


def point_neg(p):
    x, y, z, t = p
    return (fe_t.neg(x), y, z, fe_t.neg(t))


def to_niels(p):
    """Projective (X, Y, Z, T) -> cached/Niels form (Y+X, Y-X, Z, T*2d).
    Table entries are stored this way so the ladder's add costs 8 field
    muls instead of 9 and skips two per-iteration carry passes."""
    x, y, z, t = p
    return (fe_t.add(y, x), fe_t.sub(y, x), z, fe_t.mul(t, D2_T()))


def point_add_niels(p, q, need_t: bool = True):
    """acc (extended projective) + table entry (Niels form). With
    need_t=False the e*h mul is skipped — sound when the consumer chain
    never reads T (doubles and the cross-multiplied equality test)."""
    x1, y1, z1, t1 = p
    yplusx2, yminusx2, z2, t2d2 = q
    a = fe_t.mul(fe_t.sub(y1, x1), yminusx2)
    b = fe_t.mul(fe_t.add(y1, x1), yplusx2)
    c = fe_t.mul(t1, t2d2)
    zz = fe_t.mul(z1, z2)
    d = fe_t.add(zz, zz)
    e = fe_t.sub(b, a)
    f = fe_t.sub(d, c)
    g = fe_t.add(d, c)
    h = fe_t.add(b, a)
    t = fe_t.mul(e, h) if need_t else jnp.zeros_like(x1)
    return (fe_t.mul(e, f), fe_t.mul(g, h), fe_t.mul(f, g), t)


def sqrt_ratio(u, v):
    v3 = fe_t.mul(fe_t.sq(v), v)
    v7 = fe_t.mul(fe_t.sq(v3), v)
    r = fe_t.mul(fe_t.mul(u, v3), fe_t.pow22523(fe_t.mul(u, v7)))
    check = fe_t.mul(v, fe_t.sq(r))
    ok_pos = fe_t.eq(check, u)
    ok_neg = fe_t.is_zero(fe_t.add(check, u))
    r = jnp.where(ok_pos, r, fe_t.mul(r, SQRT_M1_T()))
    return ok_pos | ok_neg, r


def decompress(y_limbs, sign):
    """ZIP-215 decompression; y_limbs (20, B), sign (1, B). All flag
    vectors stay 2D (1, B) — see fe_t.is_zero."""
    one = fe_t.limbs_from_int_t(1)
    y = fe_t.carry(y_limbs)
    yy = fe_t.sq(y)
    u = fe_t.sub(yy, one)
    v = fe_t.add(fe_t.mul(D_T(), yy), one)
    ok, x = sqrt_ratio(u, v)
    x = fe_t.canon(x)
    flip = (x[0:1] & 1) != sign
    x = jnp.where(flip, fe_t.neg(x), x)
    t = fe_t.mul(x, y)
    z = jnp.broadcast_to(one, y.shape)
    return ok, (x, y, z, t)


# -- in-kernel unpacking ----------------------------------------------------


def _unpack_limbs(enc32):
    """(32, B) int32 bytes (LE encoding) -> ((20, B) low-255-bit limbs,
    (B,) sign). Static per-limb byte-window arithmetic — no gathers."""
    b = enc32
    sign = b[31:32] >> 7  # (1, B)
    b31 = b[31] & 0x7F
    rows = []
    for i in range(NL):
        lo_bit = fe_t.RADIX * i
        byte0 = lo_bit >> 3
        shift = lo_bit & 7
        v = b[byte0] if byte0 != 31 else b31
        if byte0 + 1 < 32:
            nxt = b[byte0 + 1] if byte0 + 1 != 31 else b31
            v = v + (nxt << 8)
        if byte0 + 2 < 32 and shift + fe_t.RADIX > 16:
            nxt2 = b[byte0 + 2] if byte0 + 2 != 31 else b31
            v = v + (nxt2 << 16)
        rows.append((v >> shift) & fe_t.MASK)
    return jnp.stack(rows, axis=0), sign


def _unpack_digits2_grouped(enc32):
    """(32, B) int32 scalar bytes (LE, < 2^253) -> (128, B) base-4 digits
    in SHIFT-GROUPED layout: digit t (= bits [2t, 2t+2), both always in
    byte t>>2) is stored at row (t & 3) * 32 + (t >> 2). Grouping by
    in-byte shift keeps the unpack to four (32, B) block writes — the
    interleaved (32, 4, B) -> (128, B) reshape lowers to a 3D gather,
    which Mosaic rejects."""
    b = enc32  # (32, B)
    return jnp.concatenate([(b >> s) & 3 for s in (0, 2, 4, 6)], axis=0)


def _digit_row(t):
    """Row of digit t in the shift-grouped layout (works on traced t)."""
    return (t & 3) * 32 + (t >> 2)


# -- the kernel -------------------------------------------------------------


def _cat(parts):
    return jnp.concatenate(parts, axis=-1)


def _catp(points):
    """Concatenate points along the lane axis."""
    return tuple(_cat([p[c] for p in points]) for c in range(4))


def _slicep(point, i, b):
    return tuple(c[..., i * b : (i + 1) * b] for c in point)


def _k1_decompress_kernel(a_ref, r_ref, s_ref, k_ref, coords_ref, ok_ref, sdig_ref, kdig_ref):
    """K1: byte unpack + joint (lane-folded) decompression of A and R.

    Outputs: coords (160, B) = [Ax Ay Az At Rx Ry Rz Rt] x 20 limb rows,
    ok (2, B), and the base-4 scalar digits for s and k (128, B) each."""
    a_enc = a_ref[:].astype(jnp.int32)
    r_enc = r_ref[:].astype(jnp.int32)
    sdig_ref[:] = _unpack_digits2_grouped(s_ref[:].astype(jnp.int32))
    kdig_ref[:] = _unpack_digits2_grouped(k_ref[:].astype(jnp.int32))

    a_y, a_sign = _unpack_limbs(a_enc)
    r_y, r_sign = _unpack_limbs(r_enc)
    B = a_y.shape[-1]
    ok_ar, AR = decompress(_cat([a_y, r_y]), _cat([a_sign, r_sign]))
    ok_ref[0:1] = ok_ar[:, :B].astype(jnp.int32)
    ok_ref[1:2] = ok_ar[:, B:].astype(jnp.int32)
    # 32-row-aligned coordinate slots: Mosaic aborts on refs sliced at
    # offsets that are not multiples of the 8-row sublane tile, and 20-row
    # slots put 3 of every 4 coords off-tile (measured round 3)
    for c in range(4):
        coords_ref[c * 32 : c * 32 + NL] = AR[c][:, :B]
        coords_ref[(4 + c) * 32 : (4 + c) * 32 + NL] = AR[c][:, B:]


def _k1_decompress_kernel_cached(
    ac_ref, aok_ref, r_ref, s_ref, k_ref, coords_ref, ok_ref, sdig_ref,
    kdig_ref
):
    """K1 for a WARM epoch: the committee's decompressed coordinates
    arrive as an input (gathered on device from the epoch cache's
    persistent table — ops/epoch_cache.py coords_tables), so this variant
    decompresses HALF the points of _k1_decompress_kernel: R only.

    ac: (4*32, B) int32 A coords in the 32-row slot layout; aok (1, B)."""
    r_enc = r_ref[:].astype(jnp.int32)
    sdig_ref[:] = _unpack_digits2_grouped(s_ref[:].astype(jnp.int32))
    kdig_ref[:] = _unpack_digits2_grouped(k_ref[:].astype(jnp.int32))

    r_y, r_sign = _unpack_limbs(r_enc)
    ok_r, R = decompress(r_y, r_sign)
    ok_ref[0:1] = aok_ref[0:1]
    ok_ref[1:2] = ok_r.astype(jnp.int32)
    for c in range(4):
        coords_ref[c * 32 : c * 32 + NL] = ac_ref[c * 32 : c * 32 + NL]
        coords_ref[(4 + c) * 32 : (4 + c) * 32 + NL] = R[c]


def _k2_table_kernel(coords_ref, tbl_ref):
    """K2: 16-entry Straus table [s2]B + [k2](-A) built with three
    lane-folded point ops; entry e coord c lands at rows
    [(e*4 + c)*20, (e*4 + c + 1)*20)."""
    A = tuple(coords_ref[c * 32 : c * 32 + NL] for c in range(4))
    negA = point_neg(A)
    B = A[0].shape[-1]
    zero = jnp.zeros((NL, B), dtype=jnp.int32)
    one = fe_t.limbs_from_int_t(1)
    bx = fe_t.limbs_from_int_t(_edwards.BASE[0])
    by = fe_t.limbs_from_int_t(_edwards.BASE[1])
    bt = fe_t.limbs_from_int_t(_edwards.BASE[3])
    base = (bx + zero, by + zero, one + zero, bt + zero)
    ident = (zero, one + zero, one + zero, zero)
    pair = _catp([base, negA])
    dbl = point_double(pair)
    tri = point_add(dbl, pair)
    b_row = [ident, base, _slicep(dbl, 0, B), _slicep(tri, 0, B)]
    a_col = [ident, negA, _slicep(dbl, 1, B), _slicep(tri, 1, B)]
    cross = point_add(
        _catp([b_row[s2] for k2 in range(1, 4) for s2 in range(1, 4)]),
        _catp([a_col[k2] for k2 in range(1, 4) for s2 in range(1, 4)]),
    )
    entries = []
    for k2 in range(4):
        for s2 in range(4):
            if k2 == 0:
                entries.append(b_row[s2])
            elif s2 == 0:
                entries.append(a_col[k2])
            else:
                entries.append(_slicep(cross, (k2 - 1) * 3 + (s2 - 1), B))
    # store in Niels form (Y+X, Y-X, Z, T*2d): one 8-lane-folded to_niels
    # per half keeps the (20, 20, lanes) mul transient within VMEM
    for half in range(2):
        niels = to_niels(_catp(entries[half * 8 : half * 8 + 8]))
        for j in range(8):
            e = half * 8 + j
            ent = _slicep(niels, j, B)
            for c in range(4):
                tbl_ref[(e * 4 + c) * 32 : (e * 4 + c) * 32 + NL] = ent[c]


def _k3_ladder_kernel(tbl_ref, sdig_ref, kdig_ref, coords_ref, ok_ref, sok_ref, out_ref):
    """K3: the 127-iteration joint ladder. The table is an input ref —
    Mosaic aborts when point-op RESULTS cross into a fori_loop as live
    values (measured round 3), but ref reads inside the body are fine, so
    the 16-way select re-reads table rows each iteration (VMEM-resident)."""
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
        j = _digit_row(126 - i)
        # inner double & the add skip their T output (never read); only
        # the outer double's T feeds the Niels add's t1*T2d term
        acc = point_double(point_double(acc, need_t=False))
        return point_add_niels(
            acc, select(sdig_ref[j] + 4 * kdig_ref[j]), need_t=False
        )

    acc = lax.fori_loop(0, 127, body, ident)
    # [8]([s]B - [k]A - R) == O  <=>  [8]acc == [8]R, checked by projective
    # cross-multiplication — doubles-only (complete for all inputs, incl.
    # the small-order/mixed ZIP-215 edge points) and T-free end to end.
    R = tuple(coords_ref[(4 + c) * 32 : (4 + c) * 32 + NL] for c in range(4))
    acc8 = acc
    r8 = R
    for _ in range(3):
        acc8 = point_double(acc8, need_t=False)
        r8 = point_double(r8, need_t=False)
    eq_x = fe_t.is_zero(
        fe_t.sub(fe_t.mul(acc8[0], r8[2]), fe_t.mul(r8[0], acc8[2]))
    )
    eq_y = fe_t.is_zero(
        fe_t.sub(fe_t.mul(acc8[1], r8[2]), fe_t.mul(r8[1], acc8[2]))
    )
    valid = (
        (ok_ref[0:1] != 0)
        & (ok_ref[1:2] != 0)
        & (sok_ref[0:1] != 0)
        & eq_x
        & eq_y
    )
    out_ref[:] = valid.astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _jitted_pallas_verify(n: int, block: int, interpret: bool,
                          vma: frozenset | None = None,
                          donate: bool = False):
    """Three chained pallas_calls (single-kernel fusion SIGABRTs Mosaic;
    see the kernel docstrings). Intermediates live in HBM between kernels
    — ~3 MB/block, negligible next to the in-kernel work. K2's block is
    capped at 256 lanes: its double-buffered (2048, B) table output plus
    the 9B-lane cross-add working set exceeds VMEM at 512.

    vma: varying-mesh-axes annotation for the kernel outputs — required
    when the pipeline runs inside a checked shard_map (ops.sharded), where
    every output must declare which mesh axes it varies over.

    donate: donate the per-batch input buffers to XLA so launches recycle
    their pages (ISSUE 7; see ed25519_verify's donation note)."""
    k2_block = min(block, 256)

    def mkspec(b):
        def spec(rows):
            return pl.BlockSpec((rows, b), lambda i: (0, i), memory_space=pltpu.VMEM)

        return spec

    def out(rows):
        return jax.ShapeDtypeStruct((rows, n), jnp.int32, vma=vma)

    spec = mkspec(block)
    spec2 = mkspec(k2_block)

    k1 = pl.pallas_call(
        _k1_decompress_kernel,
        grid=(n // block,),
        in_specs=[spec(32)] * 4,
        out_specs=[spec(8 * 32), spec(2), spec(128), spec(128)],
        out_shape=[out(8 * 32), out(2), out(128), out(128)],
        interpret=interpret,
    )
    k2 = pl.pallas_call(
        _k2_table_kernel,
        grid=(n // k2_block,),
        in_specs=[spec2(8 * 32)],
        out_specs=spec2(16 * 4 * 32),
        out_shape=out(16 * 4 * 32),
        interpret=interpret,
    )
    k3 = pl.pallas_call(
        _k3_ladder_kernel,
        grid=(n // block,),
        in_specs=[spec(16 * 4 * 32), spec(128), spec(128), spec(8 * 32), spec(2), spec(1)],
        out_specs=spec(1),
        out_shape=out(1),
        interpret=interpret,
    )

    def pipeline(a_t, r_t, s_t, k_t, sok_t):
        coords, ok, sdig, kdig = k1(a_t, r_t, s_t, k_t)
        tbl = k2(coords)
        return k3(tbl, sdig, kdig, coords, ok, sok_t)

    if donate:
        return jax.jit(pipeline, donate_argnums=(0, 1, 2, 3, 4))
    return jax.jit(pipeline)


@functools.lru_cache(maxsize=None)
def _jitted_pallas_verify_cached(n: int, block: int, vp: int,
                                 interpret: bool,
                                 vma: frozenset | None = None,
                                 donate: bool = False):
    """The epoch-cached 3-kernel pipeline: the jitted program GATHERS the
    committee's decompressed coordinates from the persistent device table
    ((4*32, vp) int32 + (1, vp) ok) and transposes the raw per-sig rows
    on device — host prep ships row-major bytes only. K2/K3 are shared
    with the uncached pipeline; only K1 changes (R-only decompression)."""
    k2_block = min(block, 256)

    def mkspec(b):
        def spec(rows):
            return pl.BlockSpec((rows, b), lambda i: (0, i), memory_space=pltpu.VMEM)

        return spec

    def out(rows):
        return jax.ShapeDtypeStruct((rows, n), jnp.int32, vma=vma)

    spec = mkspec(block)
    spec2 = mkspec(k2_block)

    k1 = pl.pallas_call(
        _k1_decompress_kernel_cached,
        grid=(n // block,),
        in_specs=[spec(4 * 32), spec(1), spec(32), spec(32), spec(32)],
        out_specs=[spec(8 * 32), spec(2), spec(128), spec(128)],
        out_shape=[out(8 * 32), out(2), out(128), out(128)],
        interpret=interpret,
    )
    k2 = pl.pallas_call(
        _k2_table_kernel,
        grid=(n // k2_block,),
        in_specs=[spec2(8 * 32)],
        out_specs=spec2(16 * 4 * 32),
        out_shape=out(16 * 4 * 32),
        interpret=interpret,
    )
    k3 = pl.pallas_call(
        _k3_ladder_kernel,
        grid=(n // block,),
        in_specs=[spec(16 * 4 * 32), spec(128), spec(128), spec(8 * 32), spec(2), spec(1)],
        out_specs=spec(1),
        out_shape=out(1),
        interpret=interpret,
    )

    def pipeline(coords_tbl, ok_tbl, idx, r_rows, s_rows, k_rows, sok_t):
        ac = coords_tbl[:, idx]          # (4*32, n) device gather
        aok = ok_tbl[:, idx]             # (1, n)
        r_t = r_rows.T                   # device-side transposes: trivial
        s_t = s_rows.T                   # on-chip, ~31 ms on host at 10k
        k_t = k_rows.T
        coords, ok, sdig, kdig = k1(ac, aok, r_t, s_t, k_t)
        tbl = k2(coords)
        return k3(tbl, sdig, kdig, coords, ok, sok_t)

    if donate:
        # the persistent coords/ok epoch tables (argnums 0-1) are shared
        # across batches — never donated
        return jax.jit(pipeline, donate_argnums=(2, 3, 4, 5, 6))
    return jax.jit(pipeline)


def prepare_compact_cached(entries, bucket: int, ep):
    """Warm-epoch compact prep: ships val_idx + raw row-major r/s/k (the
    jitted pipeline transposes on device) — no pubkey bytes, no host
    transposes. entries must be an EntryBlock with val_idx set. Same
    argument build as the XLA path (backend.cached_sig_args); only the
    s_ok shaping differs (the kernel wants a (1, N) int32 row)."""
    from .backend import cached_sig_args

    idx, r_rows, s_rows, k_rows, s_ok = cached_sig_args(entries, bucket, ep)
    return (
        idx,
        r_rows,
        s_rows,
        k_rows,
        np.ascontiguousarray(s_ok.astype(np.int32)[None, :]),
    )


def cached_compact_fn(ep, n: int, block: int, interpret: bool,
                      donate: bool = False):
    """Kernel closure for the warm-epoch compact pipeline; the epoch's
    coords tables resolve at CALL time (dispatch-owner thread — the only
    thread allowed to issue the one-time upload)."""
    f = _jitted_pallas_verify_cached(n, block, ep.vp, interpret,
                                     donate=donate)

    def call(*args):
        coords_tbl, ok_tbl = ep.coords_tables()
        return f(coords_tbl, ok_tbl, *args)

    return call


def verify_compact_cached(args, ep, block: int = 0,
                          interpret: bool = False):
    """Run the cached kernel over prepare_compact_cached args; returns
    (N,) bool."""
    block = block or BLOCK
    n = args[1].shape[0]
    if n % block:
        raise ValueError(f"batch {n} not a multiple of block {block}")
    out = cached_compact_fn(ep, n, block, interpret)(*args)
    return np.asarray(out)[0].astype(bool)


def verify_compact(a_t, r_t, s_t, k_t, s_ok_t, block: int = 0, interpret: bool = False):
    """Run the kernel. Args are batch-minor:
    a_t/r_t/s_t/k_t (32, N) uint8, s_ok_t (1, N) int32; N % block == 0.
    block=0 means the module default (BLOCK, read at call time so tests
    can shrink it). Returns (N,) bool.
    """
    block = block or BLOCK
    n = a_t.shape[-1]
    if n % block:
        raise ValueError(f"batch {n} not a multiple of block {block}")
    out = _jitted_pallas_verify(n, block, interpret)(a_t, r_t, s_t, k_t, s_ok_t)
    return np.asarray(out)[0].astype(bool)


def prepare_compact(entries, bucket: int):
    """EntryBlock or (pub32, msg, sig64) triples -> compact batch-minor
    kernel args. Host work: one SHA-512 per sig for k (native batch helper
    when built — a single GIL-released call over the block's contiguous
    msgs buffer — else hashlib), s<L check, two transposes. Padding lanes
    verify trivially (A=R=identity, s=k=0)."""
    from .backend import _challenges_any, _pack_rows, _s_below_l

    n = len(entries)
    pub, r_enc, s_enc = _pack_rows(entries, bucket)  # (bucket, 32) uint8 each
    s_ok = _s_below_l(s_enc, n, bucket)
    k_enc = np.zeros((bucket, 32), dtype=np.uint8)
    if n:
        ks = _challenges_any(r_enc[:n], pub[:n], entries)
        k_enc[:n] = np.frombuffer(ks, dtype=np.uint8).reshape(n, 32)
    return (
        np.ascontiguousarray(pub.T),
        np.ascontiguousarray(r_enc.T),
        np.ascontiguousarray(s_enc.T),
        np.ascontiguousarray(k_enc.T),
        np.ascontiguousarray(s_ok.astype(np.int32)[None, :]),
    )
