"""Per-lane RLC fast-accept verification — m signatures per kernel lane.

The per-signature kernel (ops.pallas_verify) spends ~70% of its ladder on
point doubles: every lane doubles its own accumulator 254 times to verify
ONE signature. This module amortizes those doubles over m signatures by
verifying a random-linear-combination equation per lane (the same
construction Go's crypto/ed25519 batch path uses across a whole batch —
crypto/ed25519/ed25519.go:192-227 — applied at lane granularity):

    lane g covers sigs j = 0..m-1 with coefficients c_0 = 1,
    c_j = z_j (random 128-bit, host CSPRNG, fresh per batch):

    acc = [S]B - sum_j [u_j]A_j - sum_{j>=1} [z_j]R_j
    accept iff [8]acc == [8]R_0          (cofactored, ZIP-215-compatible)

    S = (s_0 + sum z_j s_j) mod L,  u_0 = k_0,  u_j = (z_j k_j) mod L

Soundness: [8] of each per-sig residual e_j = [s_j]B - [k_j]A_j - R_j
lies in the prime-order subgroup, so if any [8]e_j != O the combination
[8]acc = sum c_j [8]e_j vanishes with probability <= 2^-125 over the
z_j, whatever m is. Valid batches ALWAYS accept ([8]e_j = O for all j
implies [8]acc = O identically — torsion components cancel under the
cofactor exactly as in per-sig ZIP-215). On lane reject the caller
re-verifies that lane's m signatures individually for blame (the
reference's own accept/reject asymmetry, types/validation.go:242-248);
per-sig accept/reject semantics are therefore preserved exactly, up to
the negligible false-accept probability every RLC batch verifier carries.

The ladder processes 2m scalars (1 + m full 253-bit, m-1 half 128-bit)
through m joint 16-entry Straus tables: 2 doubles + (m/2+1 .. m) adds per
iteration for m signatures, vs 2 doubles + 1 add per signature in the
per-sig kernel. Field multiplications and squarings of the ladder, per
lane and per signature:

    m   per lane   per signature
    2     3 810        1 905
    4     5 338        1 334
    8     8 394        1 049

A wider lane does less arithmetic per signature and more per lane, so the
width follows the batch (plan_bucket): where one kernel block holds the
whole batch the time is the per-lane chain and the narrowest width that
fits wins; where blocks run one after another the time is per signature
and the widest does. Layouts, point ops, and Mosaic constraints are
shared with ops.pallas_verify.
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
from .. import native as _native
from ..crypto import _edwards
from ..observability.trace import span as _span

NL = fe_t.NLIMBS

# Signatures per lane a launch can run at. Two scalars pair per joint
# table, so a lane of m signatures has m tables for its 2m scalars:
#   scalar q: 0 -> S, 1..m -> u_{q-1}, m+1..2m-1 -> z_{q-m}
#   table t pairs scalar lo=2t (low 2 bits of the entry index) with
#   hi=2t+1. Tables whose BOTH scalars are z's (lo index > m) carry zero
#   digits above bit 128 and are skipped in the top half of the ladder,
#   which leaves m//2 + 1 tables there.
WIDTHS = (2, 4, 8)

# Lanes per kernel block (a block covers BLOCK_LANES * m signatures). The
# per-block table is m x 16 entries x 4 coords of 32-row slots: 1 MiB per
# unit of m at 128 lanes.
BLOCK_LANES = int(os.environ.get("TM_TPU_RLC_BLOCK", "128"))

# Max signatures per device batch: the async pipeline coalesces
# concurrent commits up to this cap (8 x MaxVotesCount's 10240 bucket).
# HBM at 81920 is ~900 MB of intermediates on a 16 GB part. Value not
# measured on this machine.
#
# Validated at import: every multi-block bucket plan_bucket can select —
# the cap included — runs at the widest lane and must divide into whole
# kernel blocks (WIDTHS[-1] * BLOCK_LANES signatures each) or the
# truncated pallas grid would leave trailing lanes' verdicts
# uninitialized, and a cap below the smallest quantized bucket would make
# plan_bucket select ABOVE it.
MAX_SIGS = int(os.environ.get("TM_TPU_RLC_MAX_SIGS", "81920"))
if MAX_SIGS <= 0 or MAX_SIGS % (WIDTHS[-1] * BLOCK_LANES):
    raise ValueError(
        f"TM_TPU_RLC_MAX_SIGS={MAX_SIGS} must be a positive multiple of "
        f"{WIDTHS[-1]}*BLOCK_LANES={WIDTHS[-1] * BLOCK_LANES}"
    )

# Rows of one point (4 coords) and of one 16-entry table in the 32-row
# slot layout both kernels' refs use
_POINT_ROWS = 4 * 32
_TABLE_ROWS = 16 * _POINT_ROWS


def _coord_rows(c: int) -> slice:
    """Rows of coord c inside one point's 32-row slots."""
    return slice(c * 32, c * 32 + NL)


def _point_rows(p: int, c: int) -> slice:
    """Rows of coord c of point p in the coords ref (32-row slots)."""
    base = p * _POINT_ROWS + c * 32
    return slice(base, base + NL)


# -- K1: byte unpack + decompression of 2m points ---------------------------


def _k1_rlc_kernel(m: int, cached: bool):
    """Unpack 2m scalars' base-4 digits and jointly decompress the points
    of each lane's m signatures.

    coords: (2m * 128, G) 32-row coordinate slots, A's then R's.
    ok:     (2m, G) decompression flags.
    dig:    (2m * 128, G) shift-grouped digits, scalar-major.

    cached=False: refs (a, r, scal | coords, ok, dig); all 2m points
    (A_0..A_{m-1}, R_0..R_{m-1}) decompress here.
    cached=True, a WARM epoch: refs (ac, aok, r, scal | coords, ok, dig);
    the m committee points arrive pre-decompressed (gathered on device
    from the epoch cache's persistent coords table: ac (m * 128, G) int32
    slot-major A coords, aok (m, G) flags), so only the m R's decompress
    — K1 was ~half committee work by construction."""

    def kernel(*refs):
        *a_refs, r_ref, scal_ref, coords_ref, ok_ref, dig_ref = refs
        for q in range(2 * m):
            enc = scal_ref[q * 32 : (q + 1) * 32].astype(jnp.int32)
            dig_ref[q * 128 : (q + 1) * 128] = pv._unpack_digits2_grouped(enc)

        encs = [r_ref[j * 32 : (j + 1) * 32] for j in range(m)]
        if cached:
            ac_ref, aok_ref = a_refs
            ok_ref[0:m] = aok_ref[...]
            for p in range(m):
                for c in range(4):
                    coords_ref[_point_rows(p, c)] = ac_ref[_point_rows(p, c)]
        else:
            (a_ref,) = a_refs
            encs = [a_ref[j * 32 : (j + 1) * 32] for j in range(m)] + encs
        first = 2 * m - len(encs)  # coords slot of the first point unpacked
        ys, signs = zip(*(pv._unpack_limbs(e.astype(jnp.int32)) for e in encs))
        G = ys[0].shape[-1]
        ok_all, pts = pv.decompress(pv._cat(ys), pv._cat(signs))
        for j in range(len(encs)):
            p = first + j
            ok_ref[p : p + 1] = ok_all[:, j * G : (j + 1) * G].astype(jnp.int32)
            for c in range(4):
                coords_ref[_point_rows(p, c)] = pts[c][:, j * G : (j + 1) * G]

    return kernel


# -- K2: m joint Straus tables ----------------------------------------------


def _table_points(m: int, t):
    """Coords slots (P, Q) of table t's two points, t static or traced:
    scalars (2t, 2t+1), where scalar q <= m is u_{q-1} on A_{q-1} (slot
    q-1) and q > m is z_{q-m} on R_{q-m} (slot q). Table 0's P is the
    base point, which has no slot: the kernel substitutes it and slot 0
    stands in."""
    p = jnp.where(2 * t <= m, jnp.maximum(2 * t - 1, 0), 2 * t)
    q = jnp.where(2 * t + 1 <= m, 2 * t, 2 * t + 1)
    return p, q


def _k2_rlc_kernel(p_ref, q_ref, tbl_ref):
    """Build ONE 16-entry joint table per grid step (lane block i, table
    t): [lo]P + [hi]Q for digits lo, hi in 0..3 at entry lo + 4*hi, where
    (P, Q) are the points of scalars (2t, 2t+1): B for S, -A_j for u_j,
    -R_j for z_j. The block specs hand the step its two points
    (_table_points), so the body is traced once whatever the lane width.
    Same lane-folded dbl/tri/cross construction as
    pallas_verify._k2_table_kernel."""
    P = pv.point_neg(tuple(p_ref[_coord_rows(c)] for c in range(4)))
    Q = pv.point_neg(tuple(q_ref[_coord_rows(c)] for c in range(4)))
    G = P[0].shape[-1]
    zero = jnp.zeros((NL, G), dtype=jnp.int32)
    one = fe_t.limbs_from_int_t(1)
    ident = (zero, one + zero, one + zero, zero)
    first = pl.program_id(1) == 0
    base = (_edwards.BASE[0], _edwards.BASE[1], 1, _edwards.BASE[3])
    P = tuple(
        jnp.where(first, fe_t.limbs_from_int_t(b) + zero, p)
        for b, p in zip(base, P)
    )
    pair = pv._catp([P, Q])
    dbl = pv.point_double(pair)
    tri = pv.point_add(dbl, pair)
    row = [ident, P, pv._slicep(dbl, 0, G), pv._slicep(tri, 0, G)]
    col = [ident, Q, pv._slicep(dbl, 1, G), pv._slicep(tri, 1, G)]
    # the 9 cross entries in one fold
    cross = pv.point_add(
        pv._catp([row[lo] for hi in (1, 2, 3) for lo in (1, 2, 3)]),
        pv._catp([col[hi] for hi in (1, 2, 3) for lo in (1, 2, 3)]),
    )
    entries = []  # entry e = lo + 4*hi, in order
    for hi in range(4):
        for lo in range(4):
            if hi == 0:
                entries.append(row[lo])
            elif lo == 0:
                entries.append(col[hi])
            else:
                entries.append(pv._slicep(cross, (hi - 1) * 3 + (lo - 1), G))
    # Niels-form store, folded 8 entries at a time (keeps the (20,20,B)
    # mul transient within VMEM; see pallas_verify._k2_table_kernel)
    for half in range(2):
        niels = pv.to_niels(pv._catp(entries[half * 8 : half * 8 + 8]))
        for j in range(8):
            ent = pv._slicep(niels, j, G)
            for c in range(4):
                tbl_ref[_point_rows(half * 8 + j, c)] = ent[c]


# -- K3: the shared-doubles ladder ------------------------------------------


def _k3_rlc_kernel(m: int):
    """127-iteration ladder with 2 doubles + n_tables adds per iteration
    (vs 2 doubles + 1 add PER SIGNATURE in the per-sig kernel). The top
    63 iterations skip the all-z tables (digits structurally zero: z_j <
    2^128). The adds of an iteration are an unrolled chain: on the v5e a
    loop over the tables cost the 8-wide 10 240-signature launch 3.9 %
    (12.35 against 11.88 ms: PERF.md §5, PR 31), and what it saves the
    host in tracing K2 already saved. Final test: [8]acc == [8]R_0 by
    doubles-only projective cross-multiplication, identical to
    pallas_verify._k3_ladder_kernel."""

    def kernel(tbl_ref, dig_ref, r0_ref, ok_ref, sok_ref, out_ref):
        G = sok_ref.shape[-1]
        zero = jnp.zeros((NL, G), dtype=jnp.int32)
        one = fe_t.limbs_from_int_t(1)
        ident = (zero, one + zero, one + zero, zero)

        def select(t, idx):
            out = [tbl_ref[_point_rows(t * 16, c)] for c in range(4)]
            for e in range(1, 16):
                hit = (idx == e)[None, :]
                for c in range(4):
                    out[c] = jnp.where(
                        hit, tbl_ref[_point_rows(t * 16 + e, c)], out[c])
            return tuple(out)

        def make_body(n_tables):
            def body(i, acc):
                j = pv._digit_row(126 - i)
                acc = pv.point_double(pv.point_double(acc, need_t=False))
                for t in range(n_tables):
                    idx = (dig_ref[2 * t * 128 + j]
                           + 4 * dig_ref[(2 * t + 1) * 128 + j])
                    # intermediate adds feed the next add's t1*T2d term;
                    # only the last add before the wrap-around doubles
                    # skips T
                    acc = pv.point_add_niels(
                        acc, select(t, idx), need_t=t + 1 < n_tables)
                return acc

            return body

        # positions 126..64: z digits are all zero — all-z tables skipped
        acc = lax.fori_loop(0, 63, make_body(m // 2 + 1), ident)
        acc = lax.fori_loop(63, 127, make_body(m), acc)

        # [8]acc == [8]R_0, doubles-only (complete for small-order inputs)
        acc8 = acc
        r8 = tuple(r0_ref[_coord_rows(c)] for c in range(4))
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
        for p in range(2 * m):
            valid = valid & (ok_ref[p : p + 1] != 0)
        for j in range(m):
            valid = valid & (sok_ref[j : j + 1] != 0)
        out_ref[:] = valid.astype(jnp.int32)

    return kernel


# -- pipeline ----------------------------------------------------------------


# Quantized ladder of the multi-block buckets (in signatures): XLA
# compiles one executable per shape, and the coalescing pipeline would
# otherwise produce a fresh shape (and a fresh trace + Mosaic compile) for
# every distinct batch total. Every bucket here is more than one block of
# any width, so all of them run at the widest lane. A sorted tuple
# filtered to <= MAX_SIGS (and to whole kernel blocks) so plan_bucket can
# never select above the cap or hand the jitted kernel a lane count that
# truncates its grid.
RLC_BUCKETS = tuple(
    sorted(
        b
        for b in {2048, 10240, 20480, 40960, 81920, MAX_SIGS}
        if b <= MAX_SIGS and b % (WIDTHS[-1] * BLOCK_LANES) == 0
    )
)
assert RLC_BUCKETS and RLC_BUCKETS[-1] == MAX_SIGS


def lane_width(n: int, block: int = 0) -> int:
    """Signatures a lane for a launch of n signatures: the narrowest
    width whose single block holds the batch (at 128-lane blocks: n <=
    256 -> 2, <= 512 -> 4) and the widest for everything larger. One
    block's time is its per-lane chain, the same for 1 lane or 128 (a
    (20, 64) and a (20, 128) limb array are the same vector registers),
    so a batch that fits one block runs the shortest chain that still
    fits; past that, blocks run one after another, time is per signature,
    and the widest lane does the least arithmetic per signature (module
    docstring). The rule reads nothing but n, and a bucket has the width
    of every n it pads: lane_width(plan_bucket(n)[0]) == lane_width(n)."""
    block = block or BLOCK_LANES
    return next((w for w in WIDTHS if n <= w * block), WIDTHS[-1])


def plan_bucket(n: int, block: int = 0) -> tuple:
    """(bucket_sigs, g_lanes, block, m) covering n signatures: the lane
    width m (lane_width), and a lane count that divides evenly into
    kernel blocks. EVERY caller that feeds _jitted_rlc_verify must size
    via this: a g not divisible by block would truncate the pallas grid
    and leave trailing lanes' verdicts uninitialized — read back as
    garbage 'valid' bits.

    Buckets quantize to RLC_BUCKETS (pow2 single-block below one block's
    worth) so the compiled-shape set stays small under arbitrary
    coalesced sizes."""
    block = block or BLOCK_LANES
    m = lane_width(n, block)
    lanes = max((n + m - 1) // m, 1)
    if block < BLOCK_LANES or lanes <= block:
        # explicit small blocks (tests) or single-block batches: pow2
        # block, lane count padded to a multiple of the block
        block = min(block, 1 << (lanes - 1).bit_length())
        g = ((lanes + block - 1) // block) * block
        return g * m, g, block, m
    bucket = next((b for b in RLC_BUCKETS if n <= b), RLC_BUCKETS[-1])
    return bucket, bucket // m, block, m


def _rlc_kernels(m: int, g: int, block: int, interpret: bool, vma,
                 cached: bool) -> tuple:
    """The three pallas_calls (K1, K2, K3) of one RLC pipeline: g lanes
    of m signatures, block lanes per kernel invocation."""
    if m not in WIDTHS:
        raise ValueError(f"lane width {m} not one of {WIDTHS}")
    if g % block:
        raise ValueError(
            f"lane count {g} not a multiple of block {block} (size buckets "
            "via plan_bucket — a truncated grid silently skips lanes)"
        )
    # Mosaic requires the minor block dim divisible by 128 (or the full
    # array dim)
    k2_block = min(block, 128)

    def spec(rows, at=0):
        return pl.BlockSpec((rows, block), lambda i: (at, i),
                            memory_space=pltpu.VMEM)

    def out(rows):
        return jax.ShapeDtypeStruct((rows, g), jnp.int32, vma=vma)

    coords_rows = 2 * m * _POINT_ROWS
    tbl_rows = m * _TABLE_ROWS
    dig_rows = 2 * m * 128
    a_specs = ([spec(m * _POINT_ROWS), spec(m)] if cached
               else [spec(m * 32)])

    k1 = pl.pallas_call(
        _k1_rlc_kernel(m, cached),
        grid=(g // block,),
        in_specs=a_specs + [spec(m * 32), spec(2 * m * 32)],
        out_specs=[spec(coords_rows), spec(2 * m), spec(dig_rows)],
        out_shape=[out(coords_rows), out(2 * m), out(dig_rows)],
        interpret=interpret,
    )
    k2 = pl.pallas_call(
        _k2_rlc_kernel,
        grid=(g // k2_block, m),
        in_specs=[
            pl.BlockSpec((_POINT_ROWS, k2_block),
                         lambda i, t, which=which: (
                             _table_points(m, t)[which], i),
                         memory_space=pltpu.VMEM)
            for which in (0, 1)
        ],
        out_specs=pl.BlockSpec((_TABLE_ROWS, k2_block), lambda i, t: (t, i),
                               memory_space=pltpu.VMEM),
        out_shape=out(tbl_rows),
        interpret=interpret,
    )
    k3 = pl.pallas_call(
        _k3_rlc_kernel(m),
        grid=(g // block,),
        # of the coords only R_0 (slot m) is read, for the final check
        in_specs=[spec(tbl_rows), spec(dig_rows), spec(_POINT_ROWS, at=m),
                  spec(2 * m), spec(m)],
        out_specs=spec(1),
        out_shape=out(1),
        # scoped VMEM: the blocks double-buffered, plus room for the
        # ladder's transients. The default limit (16 MiB) is what the
        # widest lane's table alone takes: 8 MiB a buffer at 128 lanes
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(
                2 * 4 * block * (tbl_rows + dig_rows + _POINT_ROWS)
                + (8 << 20)
            ),
        ),
        interpret=interpret,
    )
    return k1, lambda coords: k2(coords, coords), k3


def _uncached_body(m: int, g: int, block: int, interpret: bool, vma):
    """The uncached pipeline on its four slot-major arrays: a_t, r_t
    (m*32, g) uint8, scal_t (2m*32, g) uint8, sok_t (m, g) int32; g
    lanes of m signatures, block lanes per kernel invocation."""
    k1, k2, k3 = _rlc_kernels(m, g, block, interpret, vma, cached=False)

    def pipeline(a_t, r_t, scal_t, sok_t):
        coords, ok, dig = k1(a_t, r_t, scal_t)
        return k3(k2(coords), dig, coords, ok, sok_t)

    # a stable, shape-bearing name: it is what compile logs, the
    # persistent-cache counters and profiler traces show for this launch
    pipeline.__name__ = f"rlc_verify_g{g}_m{m}_b{block}"
    return pipeline


@functools.lru_cache(maxsize=None)
def _jitted_rlc_verify_slot_major(m: int, g: int, block: int,
                                  interpret: bool,
                                  vma: frozenset | None = None):
    """The uncached pipeline in the mesh's form: the four slot-major
    arrays of slot_major_args, which ops.sharded lane-shards across the
    chips and runs this under shard_map. No single-chip launch takes
    it."""
    return jax.jit(_uncached_body(m, g, block, interpret, vma))


@functools.lru_cache(maxsize=None)
def _jitted_rlc_verify(m: int, g: int, block: int, interpret: bool,
                       donate: bool = False):
    """The uncached single-chip pipeline: splits the launch's ONE packed
    argument buffer (prepare_rlc: pub_rows | r_rows | scal_rows |
    sok_rows) back into its sections, lays them slot-major ON DEVICE, as
    the cached pipeline does, and runs K1/K2/K3 on them. donate=True
    donates the buffer (see ed25519_verify's donation note)."""
    body = _uncached_body(m, g, block, interpret, None)

    def pipeline(packed):
        pub_rows, r_rows, scal_rows, sok_rows = split_packed(
            packed, g * m, m, PUB_WORDS)
        return body(_slot_major(pub_rows, g, m), _slot_major(r_rows, g, m),
                    _slot_major(scal_rows, g, 2 * m), sok_rows.T)

    pipeline.__name__ = body.__name__
    if donate:
        return jax.jit(pipeline, donate_argnums=(0,))
    return jax.jit(pipeline)


# -- a launch's one argument buffer -------------------------------------------
#
# A host-to-device copy is priced per operation on the v5e (~0.25 ms
# whether it carries 600 B or 650 kB), so both RLC launches ship their
# four per-signature arrays as ONE buffer of 32-bit words:
#
#     head | r_rows (bucket, 32) uint8
#         | scal_rows (g, 2m, 32) uint8 | sok_rows (g, m) int32
#
# where the head is the warm-epoch launch's gather indices, idx (bucket,)
# int32 (IDX_WORDS a slot), or the uncached launch's public keys, pub_rows
# (bucket, 32) uint8 (PUB_WORDS a slot). Every offset is a static function
# of bucket, m and the head, every section is a whole number of words, so
# no section needs padding, and the whole is 104 bytes a slot warm and 132
# cold at any width. int32 is the element type because that is what the
# v5e splits cheapest: idx and sok_rows are plain slices, and the byte
# rows come back through one bitcast each (a uint8 buffer with idx/sok
# bitcast up to int32 cost the 10k launch 0.3 ms more on the v5e).

IDX_WORDS = 1
PUB_WORDS = 8


def packed_layout(bucket: int, m: int, head: int = IDX_WORDS) -> tuple:
    """Word offsets (r_rows, scal_rows, sok_rows, end) of the packed
    buffer's sections, with a head of `head` words a slot at 0."""
    g = bucket // m
    o_r = head * bucket
    o_scal = o_r + 8 * bucket
    o_sok = o_scal + 8 * 2 * m * g
    return o_r, o_scal, o_sok, o_sok + m * g


def packed_views(packed: np.ndarray, bucket: int, m: int,
                 head: int = IDX_WORDS) -> tuple:
    """The four host arrays of a packed buffer, as writable VIEWS of it:
    (idx (bucket,) int32 or pub_rows (bucket, 32) uint8, by the head;
    r_rows (bucket, 32) uint8, scal_rows (g, 2m, 32) uint8, sok_rows
    (g, m) int32). The byte rows lie in the words little-endian, as the
    host has them."""
    g = bucket // m
    o_r, o_scal, o_sok, end = packed_layout(bucket, m, head)
    first = packed[:o_r]
    return (
        first if head == IDX_WORDS else first.view(np.uint8).reshape(
            bucket, 32),
        packed[o_r:o_scal].view(np.uint8).reshape(bucket, 32),
        packed[o_scal:o_sok].view(np.uint8).reshape(g, 2 * m, 32),
        packed[o_sok:end].reshape(g, m),
    )


def split_packed(packed, bucket: int, m: int, head: int = IDX_WORDS) -> tuple:
    """packed_views on device, inside the jitted pipeline: static slices,
    and each word of the byte rows bitcast back to its four bytes."""
    g = bucket // m
    o_r, o_scal, o_sok, end = packed_layout(bucket, m, head)

    def rows(words, *shape):
        return lax.bitcast_convert_type(words, jnp.uint8).reshape(*shape)

    return (
        packed[:o_r] if head == IDX_WORDS else rows(packed[:o_r], bucket, 32),
        rows(packed[o_r:o_scal], bucket, 32),
        rows(packed[o_scal:o_sok], g, 2 * m, 32),
        packed[o_sok:end].reshape(g, m),
    )


def _slot_major(rows, g: int, w: int):
    """(g*w, 32) or (g, w, 32) byte rows, lane-major -> (w*32, g), the
    kernels' slot-major layout (numpy on the host, jnp on the device)."""
    return rows.reshape(g, w, 32).transpose(1, 2, 0).reshape(w * 32, g)


def slot_major_args(packed: np.ndarray, bucket: int, m: int) -> tuple:
    """An uncached launch's packed buffer (prepare_rlc) as the four
    slot-major host arrays of _jitted_rlc_verify_slot_major, the mesh's
    form: what the single-chip pipeline lays out on the device."""
    g = bucket // m
    pub_rows, r_rows, scal_rows, sok_rows = packed_views(
        packed, bucket, m, PUB_WORDS)
    return tuple(
        np.ascontiguousarray(a)
        for a in (_slot_major(pub_rows, g, m), _slot_major(r_rows, g, m),
                  _slot_major(scal_rows, g, 2 * m), sok_rows.T)
    )


@functools.lru_cache(maxsize=None)
def _jitted_rlc_verify_cached(m: int, g: int, block: int, vp: int,
                              interpret: bool,
                              vma: frozenset | None = None,
                              donate: bool = False):
    """The epoch-cached RLC pipeline: splits the launch's ONE packed
    argument buffer back into its four arrays (split_packed), gathers
    the committee's decompressed coords from the persistent (4*32, vp)
    device table, rearranges them (and the raw row-major per-sig inputs)
    into the slot-major kernel layout ON DEVICE, and runs
    K1-cached/K2/K3. The host ships only val_idx + raw rows."""
    k1, k2, k3 = _rlc_kernels(m, g, block, interpret, vma, cached=True)

    def pipeline(coords_tbl, ok_tbl, packed):
        idx, r_rows, scal_rows, sok_rows = split_packed(packed, g * m, m)
        # idx is signature-major (i = lane*m + slot); the reshapes below
        # land every array in the kernels' slot-major layout
        ac = (
            coords_tbl[:, idx]
            .reshape(_POINT_ROWS, g, m)
            .transpose(2, 0, 1)
            .reshape(m * _POINT_ROWS, g)
        )
        aok = ok_tbl[:, idx].reshape(g, m).T
        r_t = _slot_major(r_rows, g, m)
        scal_t = _slot_major(scal_rows, g, 2 * m)
        coords, ok, dig = k1(ac, aok, r_t, scal_t)
        return k3(k2(coords), dig, coords, ok, sok_rows.T)

    pipeline.__name__ = f"rlc_verify_cached_g{g}_m{m}_b{block}_vp{vp}"
    if donate:
        # persistent epoch tables (argnums 0-1) are never donated
        return jax.jit(pipeline, donate_argnums=(2,))
    return jax.jit(pipeline)


def rlc_cached_fn(ep, m: int, g: int, block: int, interpret: bool,
                  donate: bool = False):
    """Kernel closure for the warm-epoch RLC pipeline; coords tables
    resolve at CALL time on the dispatch-owner thread."""
    f = _jitted_rlc_verify_cached(m, g, block, ep.vp, interpret,
                                  donate=donate)

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


def _rlc_host_scalars(entries, live: int, g_live: int, m: int):
    """Shared host scalar stage for both RLC preps: packs the live rows,
    draws the z coefficients, and computes the lane scalars. For an
    EntryBlock with the native module built, challenges + scalar mul-adds
    + s<L run as ONE GIL-released call over the block's contiguous
    buffers (tm_native.ed25519_rlc_prep); tuple lists and native-absent
    builds keep the split numpy/Python path with identical outputs.

    Returns (pub (live, 32), r_enc (live, 32), raw, z (live, 32),
    s_ok (live,) bool): raw is the lanes' S rows then their U rows, what
    _scal_rows lays out for the kernel inside the caller's fill span.

    Spans, inside pipeline.prep: ops.rlc_prep.pack, ops.rlc_prep.z, then
    ops.rlc_prep.native / .gil from the fused call's own clock reads
    (native.traced_call), or ops.rlc_prep.native around the split path
    (no .gil: its native pieces do not time themselves)."""
    with _span("ops.rlc_prep.pack"):
        from .backend import _challenges_any, _pack_rows, _s_below_l
        from .entry_block import EntryBlock

        n = len(entries)
        native = _native.load()
        pub, r_enc, s_enc = _pack_rows(entries, live)
        fused = bool(
            n
            and isinstance(entries, EntryBlock)
            and native is not None
            and hasattr(native, "ed25519_rlc_prep")
        )
        if fused:
            buf, offs = entries.msgs_contiguous()
            cols = (
                entries.pub.tobytes(),
                entries.sig.tobytes(),
                buf,
                np.ascontiguousarray(offs).tobytes(),
            )
    with _span("ops.rlc_prep.z"):
        z = _gen_z(live)
        z_b = z.tobytes()
    if fused:
        _k_raw, raw, sok_raw = _native.traced_call(
            native, "ed25519_rlc_prep", "ops.rlc_prep", *cols, z_b, m, live)
        s_ok = np.frombuffer(sok_raw, dtype=np.uint8).astype(bool)
    else:
        with _span("ops.rlc_prep.native"):
            s_ok = _s_below_l(s_enc, n, live)
            k_enc = np.zeros((live, 32), dtype=np.uint8)
            if n:
                ks = _challenges_any(r_enc[:n], pub[:n], entries)
                k_enc[:n] = np.frombuffer(ks, dtype=np.uint8).reshape(n, 32)
            s_b, k_b = s_enc.tobytes(), k_enc.tobytes()
            if native is not None and hasattr(native, "ed25519_rlc_scalars"):
                raw = native.ed25519_rlc_scalars(s_b, k_b, z_b, m)
            else:
                raw = _rlc_scalars_py(s_b, k_b, z_b, m)
    return pub, r_enc, raw, z, s_ok


def _scal_rows(raw: bytes, z: np.ndarray, g_live: int, m: int) -> np.ndarray:
    """(g_live, 2m, 32) uint8 scalar rows of the live lanes: S, the m U
    rows, then the lane's z rows 1..m-1."""
    S = np.frombuffer(raw[: 32 * g_live], dtype=np.uint8).reshape(g_live, 32)
    U = np.frombuffer(raw[32 * g_live :], dtype=np.uint8).reshape(g_live, m, 32)
    scal = np.zeros((g_live, 2 * m, 32), dtype=np.uint8)
    scal[:, 0] = S
    scal[:, 1 : m + 1] = U
    scal[:, m + 1 :] = z.reshape(g_live, m, 32)[:, 1:]
    return scal


def _live_lanes(n: int, bucket: int, m: int) -> tuple:
    """(lanes, live lanes) of n signatures in a bucket of width-m lanes.
    All host work runs over the LIVE lanes only; padding lanes get their
    constant pattern (identity-point A/R encodings, zero scalars, s_ok
    true) via broadcast assigns. A coalesced total just past a quantized
    bucket would otherwise pay the full bucket's packing on the host."""
    if m not in WIDTHS or bucket % m:
        raise ValueError(
            f"bucket {bucket} is not a whole number of lanes of width {m} "
            f"(one of {WIDTHS})"
        )
    g = bucket // m
    return g, min((n + m - 1) // m, g)


def _pack_launch(bucket: int, m: int, head: int, r_enc, raw, z, s_ok):
    """(packed, head view): a launch's ONE int32 buffer (packed_layout)
    with its r, scal and sok sections filled row-major through their
    views, padding lanes given the identity encoding, zero scalars and
    s_ok 1; the head section is the caller's to fill."""
    g_live = len(s_ok) // m
    live = g_live * m
    packed = np.zeros((packed_layout(bucket, m, head)[-1],), dtype=np.int32)
    first, r_rows, scal_rows, sok_rows = packed_views(packed, bucket, m, head)
    r_rows[:live] = r_enc
    r_rows[live:, 0] = 1  # padding lanes: identity encoding
    scal_rows[:g_live] = _scal_rows(raw, z, g_live, m)
    sok_rows[:g_live] = s_ok.reshape(g_live, m)
    sok_rows[g_live:] = 1
    return packed, first


def prepare_rlc(entries, bucket: int, m: int):
    """EntryBlock or (pub32, msg, sig64) triples -> the uncached RLC
    launch's arguments, padded to `bucket` signatures in bucket // m
    lanes of width m. Host work on top of the per-sig prep (pack +
    SHA-512 challenges + s<L): one 128x256-bit mod-L mul-add per
    signature (see _rlc_host_scalars).

    Returns the 1-tuple (packed,): ONE int32 buffer a launch (packed_views
    at PUB_WORDS: pub_rows, r_rows, scal_rows, sok_rows; span
    ops.rlc_prep.fill), filled row-major as prepare_rlc_cached fills its
    own; the slot-major transposes the kernels need run on the device
    (_jitted_rlc_verify), and slot_major_args gives the mesh its four
    arrays."""
    _g, g_live = _live_lanes(len(entries), bucket, m)
    live = g_live * m
    pub, r_enc, raw, z, s_ok = _rlc_host_scalars(entries, live, g_live, m)

    with _span("ops.rlc_prep.fill"):
        packed, pub_rows = _pack_launch(bucket, m, PUB_WORDS, r_enc, raw, z,
                                        s_ok)
        pub_rows[:live] = pub
        pub_rows[live:, 0] = 1  # padding lanes: identity encoding
    return (packed,)


def prepare_rlc_cached(entries, bucket: int, ep, m: int):
    """Warm-epoch RLC prep: same host scalar stage as prepare_rlc, but
    the committee ships as val_idx gather indices (the kernel gathers the
    cached decompressed A coords on device). entries must be an
    EntryBlock with val_idx set.

    Returns the 1-tuple (packed,): ONE int32 buffer per launch, filled
    through its four views (packed_views: idx, r_rows, scal_rows,
    sok_rows; span ops.rlc_prep.fill) — one host-to-device operation
    instead of four."""
    n = len(entries)
    _g, g_live = _live_lanes(n, bucket, m)
    live = g_live * m
    _pub, r_enc, raw, z, s_ok = _rlc_host_scalars(entries, live, g_live, m)

    with _span("ops.rlc_prep.fill"):
        packed, idx = _pack_launch(bucket, m, IDX_WORDS, r_enc, raw, z, s_ok)
        idx[:n] = entries.val_idx
        idx[n:] = ep.vp - 1  # padding: the table's identity row
    return (packed,)


def verify_rlc_compact(packed, m: int, block: int = 0,
                       interpret: bool = False) -> np.ndarray:
    """Run the uncached RLC pipeline on prepare_rlc's packed buffer of
    lanes of width m; returns (g,) bool LANE validity (a lane is valid
    iff the RLC equation holds and every slot's flags pass)."""
    block = block or BLOCK_LANES
    g = packed.size // packed_layout(m, m, PUB_WORDS)[-1]
    # rlc_launch's own call, keyword for keyword: one cache entry, one trace
    out = _jitted_rlc_verify(m, g, block, interpret, donate=False)(packed)
    return np.asarray(out)[0].astype(bool)


def expand_lanes(lane_valid: np.ndarray, entries, m: int) -> np.ndarray:
    """Lane verdicts of a launch that ran at width m -> per-signature
    verdicts (entries: EntryBlock or tuple list). Valid lanes accept all
    m slots; rejected lanes re-verify their live signatures individually
    on the host for blame (types/validation.go:242-248 asymmetry —
    rejects are the rare path, and m host verifies cost ~0.1 ms each).
    The blame path is the ONLY place a per-signature tuple is
    materialized from an EntryBlock — one lane at a time, never the whole
    batch. Every re-verified signature is counted
    in sigs_verified{path="host"}: it was checked on the host, on top of
    the device lane that rejected it."""
    from ..crypto import ed25519 as _ed25519
    from .entry_block import EntryBlock

    n = len(entries)
    per_sig = np.repeat(lane_valid, m)[:n].copy()
    if not lane_valid.all():
        is_block = isinstance(entries, EntryBlock)
        reverified = 0
        rejected = np.nonzero(~lane_valid)[0]
        for lane in rejected:
            for i in range(lane * m, min((lane + 1) * m, n)):
                pk, msg, sig = entries.entry(i) if is_block else entries[i]
                per_sig[i] = _ed25519.verify_zip215_fast(pk, msg, sig)
                reverified += 1
        from ..libs import metrics as _metrics

        om = _metrics.ops_metrics()
        om.sigs_verified.inc(reverified, path="host")
        om.rlc_rejected_lanes.inc(len(rejected), m=str(m))
    return per_sig


def rlc_launch(entries, ep=None, bucket: int = 0, block: int = 0,
               interpret: bool = False, donate: bool = False) -> tuple:
    """One RLC launch for at most MAX_SIGS signatures: (launch_fn, args,
    bucket, m), sized for max(len(entries), bucket) signatures in lanes
    of the width plan_bucket gives that size. With a warm epoch entry the
    committee gathers from the device-resident table (prepare_rlc_cached
    + rlc_cached_fn); without one the batch ships its pubs (prepare_rlc
    + _jitted_rlc_verify). Either way the arguments are one packed
    buffer: one host-to-device operation a launch.
    backend.select_kernel's RLC arm and verify_batch_rlc are both this."""
    from ..libs import metrics as _metrics

    n = len(entries)
    bucket, g, blk, m = plan_bucket(max(n, bucket), block)
    om = _metrics.ops_metrics()
    om.rlc_launches.inc(m=str(m))
    om.rlc_sigs.inc(n, m=str(m))
    if ep is not None:
        return (rlc_cached_fn(ep, m, g, blk, interpret, donate),
                prepare_rlc_cached(entries, bucket, ep, m), bucket, m)
    return (_jitted_rlc_verify(m, g, blk, interpret, donate=donate),
            prepare_rlc(entries, bucket, m), bucket, m)


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
        fn, args, _bucket, m = rlc_launch(chunk, ep, block=block,
                                          interpret=interpret)
        lane_valid = np.asarray(fn(*args))[0].astype(bool)
        out.append(expand_lanes(lane_valid, chunk, m))
    return (
        np.concatenate(out) if out else np.zeros((0,), dtype=bool)
    )
