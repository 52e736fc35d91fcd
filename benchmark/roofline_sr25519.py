"""The work of one launch of the sr25519 ristretto kernel
(tendermint_tpu/ops/pallas_sr25519.py: K1r decode, the K2 table of
ops/pallas_verify.py, K3r ladder), counted from its shapes, for a share of
a peak once one is on record: no int32 VPU peak of the v5e is (PERF.md
§3), so no cell reports a share of it yet.

Counted per lane, every lane of the bucket (padding lanes run the same
code): field multiplications and squarings of 20 limbs of 13 bits, and
the int32 limb products they make (a multiplication 400, a squaring 210:
ops/fe_t.py mul and sq). Additions, carries, selects and the unpacking
of bytes are not counted.
"""

from __future__ import annotations

LIMB_PRODUCTS = {"fe_mul": 400, "fe_sq": 210}

# z^(2^252 - 3): 251 squarings, 11 multiplications (fe_t.pow22523)
_POW = {"fe_sq": 251, "fe_mul": 11}
# sqrt_ratio(u, v): v^3, v^7, the chain, r, check, r * sqrt(-1)
_SQRT_RATIO = {"fe_sq": 3 + _POW["fe_sq"], "fe_mul": 7 + _POW["fe_mul"]}
# one ristretto255 DECODE: s^2, u2^2, u1^2; D*u1^2, v*u2^2, den_x, den_y
# (two), x, y, t; and sqrt_ratio
_DECODE = {"fe_sq": 3 + _SQRT_RATIO["fe_sq"],
           "fe_mul": 8 + _SQRT_RATIO["fe_mul"]}
# extended-coordinate doubling (4 sq, 4 mul; 3 mul without T), addition
# (9 mul), addition of a Niels-form entry (8 mul; 7 without T)
_DBL = {"fe_sq": 4, "fe_mul": 4}
_DBL_NO_T = {"fe_sq": 4, "fe_mul": 3}
_ADD = {"fe_sq": 0, "fe_mul": 9}
_ADD_NIELS_NO_T = {"fe_sq": 0, "fe_mul": 7}
LADDER_STEPS = 127


def _sum(*terms):
    out = {"fe_sq": 0, "fe_mul": 0}
    for k, term in terms:
        for op in out:
            out[op] += k * term[op]
    return out


def per_lane() -> dict:
    """Field operations of one lane of a launch."""
    k1r = _sum((2, _DECODE))                       # A and R
    # B and -A doubled and tripled (two lanes folded), the 9 cross sums,
    # 16 entries to Niels form (one multiplication each)
    k2 = _sum((2, _DBL), (2, _ADD), (9, _ADD), (16, {"fe_sq": 0, "fe_mul": 1}))
    # per step: two doublings (the first without T), one Niels addition
    # without T; then the two cross-multiplied equality tests
    k3r = _sum((LADDER_STEPS, _DBL_NO_T), (LADDER_STEPS, _DBL),
               (LADDER_STEPS, _ADD_NIELS_NO_T), (4, {"fe_sq": 0, "fe_mul": 1}))
    return _sum((1, k1r), (1, k2), (1, k3r))


def sr25519_verify_ops(bucket: int) -> dict:
    """One launch over `bucket` lanes: {"fe_mul", "fe_sq", "limb_products"}."""
    lane = per_lane()
    ops = {op: bucket * n for op, n in lane.items()}
    ops["limb_products"] = sum(ops[op] * LIMB_PRODUCTS[op] for op in lane)
    return ops


def sr25519_verify_bytes(bucket: int) -> int:
    """HBM bytes a launch reads and writes: the seven argument rows (four
    of 32 uint8 bytes a lane, three int32 flags) and the int32 verdict;
    K1r's outputs and K2's table round-trip HBM between the three
    kernels (8*32 + 2 + 2*128 int32 rows, then 16*4*32 written and read)."""
    args = bucket * (4 * 32 + 3 * 4) + bucket * 4
    k1_out = bucket * 4 * (8 * 32 + 2 + 2 * 128)
    table = bucket * 4 * 16 * 4 * 32
    return args + 2 * k1_out + 2 * table
