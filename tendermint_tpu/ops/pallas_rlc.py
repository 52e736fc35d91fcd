"""Per-lane RLC fast-accept verification — M signatures per kernel lane.

The per-signature kernel (ops.pallas_verify) spends ~70% of its ladder on
point doubles: every lane doubles its own accumulator 254 times to verify
ONE signature. This module amortizes those doubles over M signatures by
verifying a random-linear-combination equation per lane (the same
construction Go's crypto/ed25519 batch path uses across a whole batch —
crypto/ed25519/ed25519.go:192-227 — applied at lane granularity):

    lane g covers sigs j = 0..M-1 with coefficients c_0 = 1,
    c_j = z_j (random 128-bit, host CSPRNG, fresh per batch):

    acc = [S]B - sum_j [u_j]A_j - sum_{j>=1} [z_j]R_j
    accept iff [8]acc == [8]R_0          (cofactored, ZIP-215-compatible)

    S = (s_0 + sum z_j s_j) mod L,  u_0 = k_0,  u_j = (z_j k_j) mod L

Soundness: [8] of each per-sig residual e_j = [s_j]B - [k_j]A_j - R_j
lies in the prime-order subgroup, so if any [8]e_j != O the combination
[8]acc = sum c_j [8]e_j vanishes with probability <= 2^-125 over the
z_j. Valid batches ALWAYS accept ([8]e_j = O for all j implies
[8]acc = O identically — torsion components cancel under the cofactor
exactly as in per-sig ZIP-215). On lane reject the caller re-verifies
that lane's M signatures individually for blame (the reference's own
accept/reject asymmetry, types/validation.go:242-248); per-sig
accept/reject semantics are therefore preserved exactly, up to the
negligible false-accept probability every RLC batch verifier carries.

The ladder processes 2M scalars (1 + M full 253-bit, M-1 half 128-bit)
through M joint 16-entry Straus tables — 2 doubles + ~(M/2+1..M) adds
per iteration for M signatures, vs 2 doubles + 1 add per signature in
the per-sig kernel. At M=4 that is ~1.9x fewer field muls per signature
with the SAME per-block VMEM footprint (per-lane table bytes x4, lanes
/4). Layouts, point ops, and Mosaic constraints are shared with
ops.pallas_verify.
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
from . import pallas_verify as pv
from ..crypto import _edwards

NL = fe_t.NLIMBS

# Signatures per lane. 2 scalars pair per joint table, so M tables serve
# 2M scalars; M=4 is the measured sweet spot (M=8 halves the remaining
# doubles but the z-lane adds start to dominate).
M = int(os.environ.get("TM_TPU_RLC_M", "4"))
if M not in (2, 4, 8):
    raise ValueError(f"TM_TPU_RLC_M={M} must be 2, 4 or 8")

# Lanes per kernel block (block covers BLOCK_LANES * M signatures). The
# per-block table is M x 16 entries x 4 coords — the same VMEM bytes as
# the per-sig kernel's 16-entry table at M x the lane count.
BLOCK_LANES = int(os.environ.get("TM_TPU_RLC_BLOCK", "128"))

# Max signatures per device batch: the async pipeline coalesces
# concurrent commits up to this cap (8 x MaxVotesCount's 10240 bucket).
# HBM at 81920 is ~900 MB of intermediates on a 16 GB part. Value not
# measured on this machine.
#
# Validated at import: every bucket plan_bucket can select —
# the cap included — must divide into whole kernel blocks (M * BLOCK_LANES
# signatures each) or the truncated pallas grid would leave trailing
# lanes' verdicts uninitialized, and a cap below the smallest quantized
# bucket would make plan_bucket select ABOVE it.
MAX_SIGS = int(os.environ.get("TM_TPU_RLC_MAX_SIGS", "81920"))
if MAX_SIGS < 512 or MAX_SIGS % (M * BLOCK_LANES):
    raise ValueError(
        f"TM_TPU_RLC_MAX_SIGS={MAX_SIGS} must be >= 512 and a multiple of "
        f"M*BLOCK_LANES={M * BLOCK_LANES}"
    )

# Scalar q: 0 -> S, 1..M -> u_{q-1}, M+1..2M-1 -> z_{q-M}.
N_SCAL = 2 * M
# Table t pairs scalar lo=2t (low 2 bits of the entry index) with
# hi=2t+1. Tables whose BOTH scalars are z's (lo index > M) carry zero
# digits above bit 128 and are skipped in the top half of the ladder.
N_FULL_TABLES = M // 2 + 1


def _point_rows(p: int, c: int) -> slice:
    """Rows of coord c of point p in the coords ref (32-row slots)."""
    base = (p * 4 + c) * 32
    return slice(base, base + NL)


def _tbl_rows(t: int, e: int, c: int) -> slice:
    base = ((t * 16 + e) * 4 + c) * 32
    return slice(base, base + NL)


# -- K1: byte unpack + decompression of 2M points ---------------------------


def _k1_rlc_kernel(a_ref, r_ref, scal_ref, coords_ref, ok_ref, dig_ref):
    """Unpack 2M scalars' base-4 digits and jointly decompress the 2M
    points (A_0..A_{M-1}, R_0..R_{M-1}) of each lane's M signatures.

    coords: ((2M*4)*32, G) 32-row coordinate slots, A's then R's.
    ok:     (2M, G) decompression flags.
    dig:    (2M*128, G) shift-grouped digits, scalar-major."""
    for q in range(N_SCAL):
        enc = scal_ref[q * 32 : (q + 1) * 32].astype(jnp.int32)
        dig_ref[q * 128 : (q + 1) * 128] = pv._unpack_digits2_grouped(enc)

    ys = []
    signs = []
    for j in range(M):
        y, s = pv._unpack_limbs(a_ref[j * 32 : (j + 1) * 32].astype(jnp.int32))
        ys.append(y)
        signs.append(s)
    for j in range(M):
        y, s = pv._unpack_limbs(r_ref[j * 32 : (j + 1) * 32].astype(jnp.int32))
        ys.append(y)
        signs.append(s)
    G = ys[0].shape[-1]
    ok_all, pts = pv.decompress(pv._cat(ys), pv._cat(signs))
    for p in range(2 * M):
        ok_ref[p : p + 1] = ok_all[:, p * G : (p + 1) * G].astype(jnp.int32)
        for c in range(4):
            coords_ref[_point_rows(p, c)] = pts[c][:, p * G : (p + 1) * G]


def _k1_rlc_kernel_cached(ac_ref, aok_ref, r_ref, scal_ref, coords_ref,
                          ok_ref, dig_ref):
    """_k1_rlc_kernel for a WARM epoch: the M committee points per lane
    arrive pre-decompressed (gathered on device from the epoch cache's
    persistent coords table), so this variant decompresses M points (the
    R's) instead of 2M — K1 was ~half committee work by construction.

    ac: (M*4*32, B) int32 — slot-major A coords, point p coord c at rows
    (p*4 + c)*32; aok: (M, B) int32 per-slot decompression flags."""
    for q in range(N_SCAL):
        enc = scal_ref[q * 32 : (q + 1) * 32].astype(jnp.int32)
        dig_ref[q * 128 : (q + 1) * 128] = pv._unpack_digits2_grouped(enc)

    for p in range(M):
        ok_ref[p : p + 1] = aok_ref[p : p + 1]
        for c in range(4):
            coords_ref[_point_rows(p, c)] = ac_ref[
                (p * 4 + c) * 32 : (p * 4 + c) * 32 + NL
            ]

    ys = []
    signs = []
    for j in range(M):
        y, s = pv._unpack_limbs(r_ref[j * 32 : (j + 1) * 32].astype(jnp.int32))
        ys.append(y)
        signs.append(s)
    G = ys[0].shape[-1]
    ok_all, pts = pv.decompress(pv._cat(ys), pv._cat(signs))
    for j in range(M):
        p = M + j
        ok_ref[p : p + 1] = ok_all[:, j * G : (j + 1) * G].astype(jnp.int32)
        for c in range(4):
            coords_ref[_point_rows(p, c)] = pts[c][:, j * G : (j + 1) * G]


# -- K2: M joint Straus tables ----------------------------------------------


def _k2_rlc_kernel(coords_ref, tbl_ref):
    """Build the M 16-entry joint tables. Table t holds
    [lo]P_t + [hi]Q_t for digits lo, hi in 0..3 at entry lo + 4*hi, where
    (P_t, Q_t) are the points of scalars (2t, 2t+1): B for S, -A_j for
    u_j, -R_j for z_j. Same lane-folded dbl/tri/cross construction as
    pallas_verify._k2_table_kernel, folded across all M tables."""
    pts = []
    for p in range(2 * M):
        pt = tuple(coords_ref[_point_rows(p, c)] for c in range(4))
        pts.append(pv.point_neg(pt))
    G = pts[0][0].shape[-1]
    zero = jnp.zeros((NL, G), dtype=jnp.int32)
    one = fe_t.limbs_from_int_t(1)
    bx = fe_t.limbs_from_int_t(_edwards.BASE[0])
    by = fe_t.limbs_from_int_t(_edwards.BASE[1])
    bt = fe_t.limbs_from_int_t(_edwards.BASE[3])
    base = (bx + zero, by + zero, one + zero, bt + zero)
    ident = (zero, one + zero, one + zero, zero)

    def point_of(q):
        if q == 0:
            return base
        if q <= M:
            return pts[q - 1]  # -A_{q-1}
        return pts[M + (q - M)]  # -R_{q-M}

    P = [point_of(2 * t) for t in range(M)]
    Q = [point_of(2 * t + 1) for t in range(M)]
    # one fold for all 2M doubles, one for all 2M triples
    pair = pv._catp(P + Q)
    dbl = pv.point_double(pair)
    tri = pv.point_add(dbl, pair)
    rows = []  # rows[t] = [O, P, 2P, 3P]; cols[t] = [O, Q, 2Q, 3Q]
    cols = []
    for t in range(M):
        rows.append([ident, P[t], pv._slicep(dbl, t, G), pv._slicep(tri, t, G)])
        cols.append(
            [ident, Q[t], pv._slicep(dbl, M + t, G), pv._slicep(tri, M + t, G)]
        )
    # 9 cross entries per table, folded PER TABLE (a single M*9-wide fold
    # overruns scoped VMEM at 128 lanes: the (20, 20, 9*M*G) mul transient
    # alone is ~7 MB)
    crosses = [
        pv.point_add(
            pv._catp([rows[t][lo] for hi in (1, 2, 3) for lo in (1, 2, 3)]),
            pv._catp([cols[t][hi] for hi in (1, 2, 3) for lo in (1, 2, 3)]),
        )
        for t in range(M)
    ]
    entries = []  # (t, e, point)
    for t in range(M):
        for hi in range(4):
            for lo in range(4):
                if hi == 0:
                    pt = rows[t][lo]
                elif lo == 0:
                    pt = cols[t][hi]
                else:
                    pt = pv._slicep(crosses[t], (hi - 1) * 3 + (lo - 1), G)
                entries.append((t, lo + 4 * hi, pt))
    # Niels-form store, folded 8 entries at a time (keeps the (20,20,B)
    # mul transient within VMEM; see pallas_verify._k2_table_kernel)
    for half in range(len(entries) // 8):
        chunk = entries[half * 8 : half * 8 + 8]
        niels = pv.to_niels(pv._catp([pt for _, _, pt in chunk]))
        for j, (t, e, _) in enumerate(chunk):
            ent = pv._slicep(niels, j, G)
            for c in range(4):
                tbl_ref[_tbl_rows(t, e, c)] = ent[c]


# -- K3: the shared-doubles ladder ------------------------------------------


def _k3_rlc_kernel(tbl_ref, dig_ref, coords_ref, ok_ref, sok_ref, out_ref):
    """127-iteration ladder with 2 doubles + n_tables adds per iteration
    (vs 2 doubles + 1 add PER SIGNATURE in the per-sig kernel). The top
    63 iterations skip the all-z tables (digits structurally zero: z_j <
    2^128). Final test: [8]acc == [8]R_0 by doubles-only projective
    cross-multiplication, identical to pallas_verify._k3_ladder_kernel."""
    G = sok_ref.shape[-1]
    zero = jnp.zeros((NL, G), dtype=jnp.int32)
    one = fe_t.limbs_from_int_t(1)
    ident = (zero, one + zero, one + zero, zero)

    def select(t, idx):
        out = [tbl_ref[_tbl_rows(t, 0, c)] for c in range(4)]
        for e in range(1, 16):
            m = (idx == e)[None, :]
            for c in range(4):
                out[c] = jnp.where(m, tbl_ref[_tbl_rows(t, e, c)], out[c])
        return tuple(out)

    def make_body(n_tables):
        def body(i, acc):
            j = pv._digit_row(126 - i)
            acc = pv.point_double(pv.point_double(acc, need_t=False))
            for t in range(n_tables):
                idx = dig_ref[2 * t * 128 + j] + 4 * dig_ref[(2 * t + 1) * 128 + j]
                # intermediate adds feed the next add's t1*T2d term; only
                # the last add before the wrap-around doubles skips T
                acc = pv.point_add_niels(acc, select(t, idx), need_t=t + 1 < n_tables)
            return acc

        return body

    # positions 126..64: z digits are all zero — all-z tables skipped
    acc = lax.fori_loop(0, 63, make_body(N_FULL_TABLES), ident)
    acc = lax.fori_loop(63, 127, make_body(M), acc)

    # [8]acc == [8]R_0, doubles-only (complete for small-order inputs)
    R0 = tuple(coords_ref[_point_rows(M, c)] for c in range(4))
    acc8, r8 = acc, R0
    for _ in range(3):
        acc8 = pv.point_double(acc8, need_t=False)
        r8 = pv.point_double(r8, need_t=False)
    eq_x = fe_t.is_zero(
        fe_t.sub(fe_t.mul(acc8[0], r8[2]), fe_t.mul(r8[0], acc8[2]))
    )
    eq_y = fe_t.is_zero(
        fe_t.sub(fe_t.mul(acc8[1], r8[2]), fe_t.mul(r8[1], acc8[2]))
    )
    valid = eq_x & eq_y
    for p in range(2 * M):
        valid = valid & (ok_ref[p : p + 1] != 0)
    for j in range(M):
        valid = valid & (sok_ref[j : j + 1] != 0)
    out_ref[:] = valid.astype(jnp.int32)


# -- pipeline ----------------------------------------------------------------


# Quantized bucket ladder (in signatures): XLA compiles one executable
# per shape, and the coalescing pipeline would otherwise produce a fresh
# shape (and a fresh trace + Mosaic compile) for every distinct batch total.
# Built as a sorted tuple filtered to <= MAX_SIGS (and to whole kernel
# blocks) so plan_bucket can never select above the cap or hand the
# jitted kernel a lane count that truncates its grid.
RLC_BUCKETS = tuple(
    sorted(
        b
        for b in {512, 2048, 10240, 20480, 40960, 81920, MAX_SIGS}
        if b <= MAX_SIGS and b % (M * BLOCK_LANES) == 0
    )
)
assert RLC_BUCKETS and RLC_BUCKETS[-1] == MAX_SIGS


def plan_bucket(n: int, block: int = 0) -> tuple:
    """(bucket_sigs, g_lanes, block) covering n signatures such that the
    lane count divides evenly into kernel blocks. EVERY caller that feeds
    _jitted_rlc_verify must size via this: a g not divisible by block
    would truncate the pallas grid and leave trailing lanes' verdicts
    uninitialized — read back as garbage 'valid' bits.

    Buckets quantize to RLC_BUCKETS (pow2 single-block below 512 sigs) so
    the compiled-shape set stays small under arbitrary coalesced sizes."""
    block = block or BLOCK_LANES
    lanes = max((n + M - 1) // M, 1)
    if block < BLOCK_LANES or lanes <= block:
        # explicit small blocks (tests) or tiny batches: pow2 single/multi
        # block, lane count padded to a multiple of the block
        block = min(block, 1 << (lanes - 1).bit_length())
        g = ((lanes + block - 1) // block) * block
        return g * M, g, block
    for b in RLC_BUCKETS:
        if n <= b:
            return b, b // M, block
    return RLC_BUCKETS[-1], RLC_BUCKETS[-1] // M, block


@functools.lru_cache(maxsize=None)
def _jitted_rlc_verify(g: int, block: int, interpret: bool,
                       vma: frozenset | None = None,
                       donate: bool = False):
    """g lanes (g*M signatures), block lanes per kernel invocation.
    donate=True donates the per-batch inputs (ISSUE 7; see
    ed25519_verify's donation note)."""
    if g % block:
        raise ValueError(
            f"lane count {g} not a multiple of block {block} (size buckets "
            "via plan_bucket — a truncated grid silently skips lanes)"
        )
    # Mosaic requires the minor block dim divisible by 128 (or the full
    # array dim); K2's working set at 128 lanes fits because its folds
    # are chunked (see _k2_rlc_kernel)
    k2_block = min(block, 128)

    def mkspec(b):
        def spec(rows):
            return pl.BlockSpec((rows, b), lambda i: (0, i), memory_space=pltpu.VMEM)

        return spec

    def out(rows):
        return jax.ShapeDtypeStruct((rows, g), jnp.int32, vma=vma)

    spec = mkspec(block)
    spec2 = mkspec(k2_block)
    coords_rows = 2 * M * 4 * 32
    tbl_rows = M * 16 * 4 * 32
    dig_rows = N_SCAL * 128

    k1 = pl.pallas_call(
        _k1_rlc_kernel,
        grid=(g // block,),
        in_specs=[spec(M * 32), spec(M * 32), spec(N_SCAL * 32)],
        out_specs=[spec(coords_rows), spec(2 * M), spec(dig_rows)],
        out_shape=[out(coords_rows), out(2 * M), out(dig_rows)],
        interpret=interpret,
    )
    k2 = pl.pallas_call(
        _k2_rlc_kernel,
        grid=(g // k2_block,),
        in_specs=[spec2(coords_rows)],
        out_specs=spec2(tbl_rows),
        out_shape=out(tbl_rows),
        interpret=interpret,
    )
    k3 = pl.pallas_call(
        _k3_rlc_kernel,
        grid=(g // block,),
        in_specs=[spec(tbl_rows), spec(dig_rows), spec(coords_rows),
                  spec(2 * M), spec(M)],
        out_specs=spec(1),
        out_shape=out(1),
        interpret=interpret,
    )

    def pipeline(a_t, r_t, scal_t, sok_t):
        coords, ok, dig = k1(a_t, r_t, scal_t)
        tbl = k2(coords)
        return k3(tbl, dig, coords, ok, sok_t)

    # a stable, shape-bearing name: it is what compile logs, the
    # persistent-cache counters and profiler traces show for this launch
    pipeline.__name__ = f"rlc_verify_g{g}_b{block}"
    if donate:
        return jax.jit(pipeline, donate_argnums=(0, 1, 2, 3))
    return jax.jit(pipeline)


# -- the warm-epoch launch's one argument buffer ------------------------------
#
# A host-to-device copy is priced per operation on the v5e (~0.25 ms
# whether it carries 600 B or 650 kB), so the warm-epoch launch ships its
# four per-signature arrays as ONE buffer of 32-bit words:
#
#     idx (bucket,) int32 | r_rows (bucket, 32) uint8
#         | scal_rows (g, N_SCAL, 32) uint8 | sok_rows (g, M) int32
#
# Every offset is a static function of bucket, M and N_SCAL, and every
# section is a whole number of words, so no section needs padding. int32
# is the element type because that is what the v5e splits cheapest: idx
# and sok_rows are plain slices, and the byte rows come back through one
# bitcast each (a uint8 buffer with idx/sok bitcast up to int32 cost the
# 10k launch 0.3 ms more on the device: PERF.md §6, PR 29).


def packed_layout(bucket: int) -> tuple:
    """Word offsets (r_rows, scal_rows, sok_rows, end) of the packed
    buffer's sections; idx starts at 0."""
    g = bucket // M
    o_r = bucket
    o_scal = o_r + 8 * bucket
    o_sok = o_scal + 8 * N_SCAL * g
    return o_r, o_scal, o_sok, o_sok + M * g


def packed_views(packed: np.ndarray, bucket: int) -> tuple:
    """The four host arrays of a packed buffer, as writable VIEWS of it:
    (idx (bucket,) int32, r_rows (bucket, 32) uint8,
    scal_rows (g, N_SCAL, 32) uint8, sok_rows (g, M) int32). The byte
    rows lie in the words little-endian, as the host has them."""
    g = bucket // M
    o_r, o_scal, o_sok, end = packed_layout(bucket)
    return (
        packed[:o_r],
        packed[o_r:o_scal].view(np.uint8).reshape(bucket, 32),
        packed[o_scal:o_sok].view(np.uint8).reshape(g, N_SCAL, 32),
        packed[o_sok:end].reshape(g, M),
    )


def split_packed(packed, bucket: int) -> tuple:
    """packed_views on device, inside the jitted pipeline: static slices,
    and each word of the byte rows bitcast back to its four bytes."""
    g = bucket // M
    o_r, o_scal, o_sok, end = packed_layout(bucket)
    return (
        packed[:o_r],
        lax.bitcast_convert_type(packed[o_r:o_scal], jnp.uint8).reshape(
            bucket, 32
        ),
        lax.bitcast_convert_type(packed[o_scal:o_sok], jnp.uint8).reshape(
            g, N_SCAL, 32
        ),
        packed[o_sok:end].reshape(g, M),
    )


@functools.lru_cache(maxsize=None)
def _jitted_rlc_verify_cached(g: int, block: int, vp: int, interpret: bool,
                              vma: frozenset | None = None,
                              donate: bool = False):
    """The epoch-cached RLC pipeline: splits the launch's ONE packed
    argument buffer back into its four arrays (split_packed), gathers
    the committee's decompressed coords from the persistent (4*32, vp)
    device table, rearranges them (and the raw row-major per-sig inputs)
    into the slot-major kernel layout ON DEVICE, and runs
    K1-cached/K2/K3. The host ships only val_idx + raw rows —
    prepare_rlc's slot-major transposes (the bulk of its 31 ms at 10k
    sigs) become device work."""
    if g % block:
        raise ValueError(
            f"lane count {g} not a multiple of block {block} (size buckets "
            "via plan_bucket — a truncated grid silently skips lanes)"
        )
    k2_block = min(block, 128)

    def mkspec(b):
        def spec(rows):
            return pl.BlockSpec((rows, b), lambda i: (0, i), memory_space=pltpu.VMEM)

        return spec

    def out(rows):
        return jax.ShapeDtypeStruct((rows, g), jnp.int32, vma=vma)

    spec = mkspec(block)
    spec2 = mkspec(k2_block)
    coords_rows = 2 * M * 4 * 32
    acoords_rows = M * 4 * 32
    tbl_rows = M * 16 * 4 * 32
    dig_rows = N_SCAL * 128

    k1 = pl.pallas_call(
        _k1_rlc_kernel_cached,
        grid=(g // block,),
        in_specs=[spec(acoords_rows), spec(M), spec(M * 32),
                  spec(N_SCAL * 32)],
        out_specs=[spec(coords_rows), spec(2 * M), spec(dig_rows)],
        out_shape=[out(coords_rows), out(2 * M), out(dig_rows)],
        interpret=interpret,
    )
    k2 = pl.pallas_call(
        _k2_rlc_kernel,
        grid=(g // k2_block,),
        in_specs=[spec2(coords_rows)],
        out_specs=spec2(tbl_rows),
        out_shape=out(tbl_rows),
        interpret=interpret,
    )
    k3 = pl.pallas_call(
        _k3_rlc_kernel,
        grid=(g // block,),
        in_specs=[spec(tbl_rows), spec(dig_rows), spec(coords_rows),
                  spec(2 * M), spec(M)],
        out_specs=spec(1),
        out_shape=out(1),
        interpret=interpret,
    )

    def pipeline(coords_tbl, ok_tbl, packed):
        idx, r_rows, scal_rows, sok_rows = split_packed(packed, g * M)
        # idx is signature-major (i = lane*M + slot); the reshapes below
        # land every array in the kernels' slot-major layout
        ac = (
            coords_tbl[:, idx]
            .reshape(4 * 32, g, M)
            .transpose(2, 0, 1)
            .reshape(acoords_rows, g)
        )
        aok = ok_tbl[:, idx].reshape(g, M).T
        r_t = r_rows.reshape(g, M, 32).transpose(1, 2, 0).reshape(M * 32, g)
        scal_t = scal_rows.transpose(1, 2, 0).reshape(N_SCAL * 32, g)
        sok_t = sok_rows.T
        coords, ok, dig = k1(ac, aok, r_t, scal_t)
        tbl = k2(coords)
        return k3(tbl, dig, coords, ok, sok_t)

    pipeline.__name__ = f"rlc_verify_cached_g{g}_b{block}_vp{vp}"
    if donate:
        # persistent epoch tables (argnums 0-1) are never donated
        return jax.jit(pipeline, donate_argnums=(2,))
    return jax.jit(pipeline)


def rlc_cached_fn(ep, g: int, block: int, interpret: bool,
                  donate: bool = False):
    """Kernel closure for the warm-epoch RLC pipeline; coords tables
    resolve at CALL time on the dispatch-owner thread."""
    f = _jitted_rlc_verify_cached(g, block, ep.vp, interpret, donate=donate)

    def call(*args):
        coords_tbl, ok_tbl = ep.coords_tables()
        return f(coords_tbl, ok_tbl, *args)

    return call


# -- host prep ---------------------------------------------------------------


def _rlc_scalars_py(s_enc: bytes, k_enc: bytes, z_enc: bytes, m: int) -> bytes:
    """Pure-Python fallback for tm_native.ed25519_rlc_scalars."""
    L = _edwards.L
    n = len(s_enc) // 32
    g = n // m
    S = bytearray()
    U = bytearray()
    for lane in range(g):
        b = lane * m
        s0 = int.from_bytes(s_enc[32 * b : 32 * b + 32], "little") % L
        U += k_enc[32 * b : 32 * b + 32]
        for j in range(1, m):
            i = b + j
            z = int.from_bytes(z_enc[32 * i : 32 * i + 32], "little")
            s = int.from_bytes(s_enc[32 * i : 32 * i + 32], "little")
            k = int.from_bytes(k_enc[32 * i : 32 * i + 32], "little")
            s0 = (s0 + z * s) % L
            U += ((z * k) % L).to_bytes(32, "little")
        S += s0.to_bytes(32, "little")
    return bytes(S) + bytes(U)


def _seed_allowed() -> bool:
    """Security gate for TM_TPU_RLC_SEED: deterministic RLC
    coefficients turn the 2^-125 soundness bound into 'attacker picks the
    coefficients', so the seed is honored only where no production verify
    can run — a non-TPU (interpret) backend — or under the explicit
    TM_TPU_RLC_SEED_UNSAFE=1 test override. On a TPU backend without the
    override it is refused: warn once + ignore."""
    if os.environ.get("TM_TPU_RLC_SEED_UNSAFE") == "1":
        return True
    from .engine import engine

    return not engine().on_tpu


_seed_refused = False


def _gen_z(bucket: int) -> np.ndarray:
    """(bucket, 32) uint8 random 128-bit coefficients (top 16 bytes 0).
    Slot-0 entries are ignored by the scalar prep (coefficient 1).
    TM_TPU_RLC_SEED makes them deterministic for tests — subject to
    _seed_allowed; a production TPU backend always gets CSPRNG draws."""
    z = np.zeros((bucket, 32), dtype=np.uint8)
    seed = os.environ.get("TM_TPU_RLC_SEED")
    if seed is not None and not _seed_allowed():
        global _seed_refused
        if not _seed_refused:
            _seed_refused = True
            import warnings

            warnings.warn(
                "TM_TPU_RLC_SEED ignored on the TPU backend: predictable "
                "RLC coefficients would break batch soundness (set "
                "TM_TPU_RLC_SEED_UNSAFE=1 only in tests)",
                RuntimeWarning,
                stacklevel=2,
            )
        seed = None
    if seed is not None:
        z[:, :16] = np.random.RandomState(int(seed)).randint(
            0, 256, size=(bucket, 16), dtype=np.uint8
        )
    else:
        z[:, :16] = np.frombuffer(os.urandom(16 * bucket), dtype=np.uint8).reshape(
            bucket, 16
        )
    return z


def _rlc_host_scalars(entries, live: int, g_live: int):
    """Shared host scalar stage for both RLC preps: packs the live rows,
    draws the z coefficients, and computes the lane scalars. For an
    EntryBlock with the native module built, challenges + scalar mul-adds
    + s<L run as ONE GIL-released call over the block's contiguous
    buffers (tm_native.ed25519_rlc_prep); tuple lists and native-absent
    builds keep the split numpy/Python path with identical outputs.

    Returns (pub (live, 32), r_enc (live, 32), scal (g_live, N_SCAL, 32),
    s_ok (live,) bool)."""
    from .backend import _challenges_any, _pack_rows, _s_below_l
    from .entry_block import EntryBlock
    from ..native import load as _load_native

    n = len(entries)
    pub, r_enc, s_enc = _pack_rows(entries, live)
    z = _gen_z(live)

    native = _load_native()
    if (
        n
        and isinstance(entries, EntryBlock)
        and native is not None
        and hasattr(native, "ed25519_rlc_prep")
    ):
        buf, offs = entries.msgs_contiguous()
        k_raw, raw, sok_raw = native.ed25519_rlc_prep(
            entries.pub.tobytes(),
            entries.sig.tobytes(),
            buf,
            np.ascontiguousarray(offs).tobytes(),
            z.tobytes(),
            M,
            live,
        )
        s_ok = np.frombuffer(sok_raw, dtype=np.uint8).astype(bool)
    else:
        s_ok = _s_below_l(s_enc, n, live)
        k_enc = np.zeros((live, 32), dtype=np.uint8)
        if n:
            ks = _challenges_any(r_enc[:n], pub[:n], entries)
            k_enc[:n] = np.frombuffer(ks, dtype=np.uint8).reshape(n, 32)
        s_b, k_b, z_b = s_enc.tobytes(), k_enc.tobytes(), z.tobytes()
        if native is not None and hasattr(native, "ed25519_rlc_scalars"):
            raw = native.ed25519_rlc_scalars(s_b, k_b, z_b, M)
        else:
            raw = _rlc_scalars_py(s_b, k_b, z_b, M)
    S = np.frombuffer(raw[: 32 * g_live], dtype=np.uint8).reshape(g_live, 32)
    U = np.frombuffer(raw[32 * g_live :], dtype=np.uint8).reshape(g_live, M, 32)

    scal = np.zeros((g_live, N_SCAL, 32), dtype=np.uint8)
    scal[:, 0] = S
    scal[:, 1 : M + 1] = U
    scal[:, M + 1 :] = z.reshape(g_live, M, 32)[:, 1:]
    return pub, r_enc, scal, s_ok


def prepare_rlc(entries, bucket: int):
    """EntryBlock or (pub32, msg, sig64) triples -> RLC kernel args,
    padded to `bucket` signatures (bucket % M == 0, bucket // M lanes).
    Host work on top of the per-sig prep (pack + SHA-512 challenges +
    s<L): one 128x256-bit mod-L mul-add per signature (see
    _rlc_host_scalars), then the slot-major transposes the kernel layout
    needs — warm epochs skip those via prepare_rlc_cached."""
    n = len(entries)
    if bucket % M:
        raise ValueError(f"bucket {bucket} not a multiple of M={M}")
    g = bucket // M
    # All host work runs over the LIVE lanes only; padding lanes get
    # their constant pattern (identity-point A/R encodings, zero scalars,
    # s_ok true) via broadcast assigns. A coalesced total just past a
    # quantized bucket would otherwise pay the full bucket's packing and
    # transposes on the host.
    g_live = min((n + M - 1) // M, g)
    live = g_live * M
    pub, r_enc, scal, s_ok = _rlc_host_scalars(entries, live, g_live)

    def slotmajor(arr):  # (live, 32) -> (M*32, g_live)
        return np.ascontiguousarray(
            arr.reshape(g_live, M, 32).transpose(1, 2, 0).reshape(M * 32, g_live)
        )

    a_t = np.zeros((M * 32, g), dtype=np.uint8)
    r_t = np.zeros((M * 32, g), dtype=np.uint8)
    scal_t = np.zeros((N_SCAL * 32, g), dtype=np.uint8)
    sok_t = np.ones((M, g), dtype=np.int32)
    # padding lanes: identity encoding = byte 0 of each slot set to 1
    a_t[np.arange(M) * 32, g_live:] = 1
    r_t[np.arange(M) * 32, g_live:] = 1
    if g_live:
        a_t[:, :g_live] = slotmajor(pub)
        r_t[:, :g_live] = slotmajor(r_enc)
        scal_t[:, :g_live] = np.ascontiguousarray(
            scal.transpose(1, 2, 0).reshape(N_SCAL * 32, g_live)
        )
        sok_t[:, :g_live] = s_ok.reshape(g_live, M).T.astype(np.int32)
    return a_t, r_t, scal_t, sok_t


def prepare_rlc_cached(entries, bucket: int, ep):
    """Warm-epoch RLC prep: same host scalar stage as prepare_rlc, but
    the committee ships as val_idx gather indices (the kernel gathers the
    cached decompressed A coords on device) and every per-sig array ships
    ROW-major — the slot-major transposes happen on device in the jitted
    cached pipeline. entries must be an EntryBlock with val_idx set.

    Returns the 1-tuple (packed,): ONE int32 buffer per launch, filled
    through its four views (packed_views: idx, r_rows, scal_rows,
    sok_rows) — one host-to-device operation instead of four."""
    n = len(entries)
    if bucket % M:
        raise ValueError(f"bucket {bucket} not a multiple of M={M}")
    g = bucket // M
    g_live = min((n + M - 1) // M, g)
    live = g_live * M
    _pub, r_enc, scal, s_ok = _rlc_host_scalars(entries, live, g_live)

    packed = np.zeros((packed_layout(bucket)[-1],), dtype=np.int32)
    idx, r_rows, scal_rows, sok_rows = packed_views(packed, bucket)
    idx[:n] = entries.val_idx
    idx[n:] = ep.vp - 1  # padding: the table's identity row
    r_rows[:live] = r_enc
    r_rows[live:, 0] = 1  # padding lanes: identity encoding
    scal_rows[:g_live] = scal
    sok_rows[:g_live] = s_ok.reshape(g_live, M)
    sok_rows[g_live:] = 1
    return (packed,)


def verify_rlc_compact(a_t, r_t, scal_t, sok_t, block: int = 0,
                       interpret: bool = False) -> np.ndarray:
    """Run the RLC kernel; returns (g,) bool LANE validity (a lane is
    valid iff the RLC equation holds and every slot's flags pass)."""
    block = block or BLOCK_LANES
    g = a_t.shape[-1]
    if g % block:
        raise ValueError(f"lane count {g} not a multiple of block {block}")
    out = _jitted_rlc_verify(g, block, interpret)(a_t, r_t, scal_t, sok_t)
    return np.asarray(out)[0].astype(bool)


def expand_lanes(lane_valid: np.ndarray, entries) -> np.ndarray:
    """Lane verdicts -> per-signature verdicts (entries: EntryBlock or
    tuple list). Valid lanes accept all M slots; rejected lanes re-verify
    their live signatures individually on the host for blame
    (types/validation.go:242-248 asymmetry — rejects are the rare path,
    and M host verifies cost ~0.5 ms). The blame path is the ONLY place a
    per-signature tuple is materialized from an EntryBlock — M lanes at a
    time, never the whole batch. Every re-verified signature is counted
    in sigs_verified{path="host"}: it was checked on the host, on top of
    the device lane that rejected it."""
    from ..crypto import ed25519 as _ed25519
    from .entry_block import EntryBlock

    n = len(entries)
    per_sig = np.repeat(lane_valid, M)[:n].copy()
    if not lane_valid.all():
        is_block = isinstance(entries, EntryBlock)
        reverified = 0
        for lane in np.nonzero(~lane_valid)[0]:
            for i in range(lane * M, min((lane + 1) * M, n)):
                pk, msg, sig = entries.entry(i) if is_block else entries[i]
                per_sig[i] = _ed25519.verify_zip215_fast(pk, msg, sig)
                reverified += 1
        from ..libs import metrics as _metrics

        _metrics.ops_metrics().sigs_verified.inc(reverified, path="host")
    return per_sig


def rlc_launch(entries, ep=None, bucket: int = 0, block: int = 0,
               interpret: bool = False, donate: bool = False) -> tuple:
    """One RLC launch for at most MAX_SIGS signatures: (launch_fn, args,
    bucket), sized for max(len(entries), bucket) signatures. With a warm
    epoch entry the committee gathers from the device-resident table
    (prepare_rlc_cached + rlc_cached_fn); without one the batch ships its
    pubs. backend.select_kernel's RLC arm and verify_batch_rlc are both
    this."""
    bucket, g, blk = plan_bucket(max(len(entries), bucket), block)
    if ep is not None:
        return (rlc_cached_fn(ep, g, blk, interpret, donate),
                prepare_rlc_cached(entries, bucket, ep), bucket)
    return (_jitted_rlc_verify(g, blk, interpret, donate=donate),
            prepare_rlc(entries, bucket), bucket)


def verify_batch_rlc(entries, block: int = 0, interpret: bool = False) -> np.ndarray:
    """Arbitrary-size batch through the RLC fast-accept path; returns
    per-signature (n,) bool with exact per-sig ZIP-215 blame. Warm-epoch
    EntryBlocks route through the cached kernel (committee gathered from
    the device-resident table)."""
    from . import epoch_cache as _epoch

    ep = _epoch.lookup(entries)
    out = []
    for i in range(0, len(entries), MAX_SIGS):
        chunk = entries[i : i + MAX_SIGS]
        fn, args, _bucket = rlc_launch(chunk, ep, block=block,
                                       interpret=interpret)
        lane_valid = np.asarray(fn(*args))[0].astype(bool)
        out.append(expand_lanes(lane_valid, chunk))
    return (
        np.concatenate(out) if out else np.zeros((0,), dtype=bool)
    )
