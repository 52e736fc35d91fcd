"""The plain reference of an sr25519 validator set's commit: schnorrkel
(crypto/sr25519 of Tendermint v0.35, curve25519-voi's schnorrkel v1
signatures) in Python integers, and types/validation.go VerifyCommit's
verifyCommitSingle loop over it. None of the program's code.

  keccak_f1600      the 24-round permutation, 25 lanes of 64 bits
  Strobe            STROBE-128 v1.0.2, the operations merlin uses
  Transcript        merlin v1.0: dom-sep, LE32 length framing
  ristretto255      RFC 9496 decode, encode, equality over edwards25519
  sign / verify     the "substrate" signing context; R == [s]B - [k]A with
                    k = the transcript's "sign:c" challenge mod L, s
                    marked in its top bit (schnorrkel v1)

verify_commit returns what the program must raise, as benchmark/
reference.py does for ed25519: None, or (exception type name, message).
"""

from __future__ import annotations

import hashlib

from . import wire

# -- Keccak-f[1600] -------------------------------------------------------------

_M64 = (1 << 64) - 1
_RC = []
_r = 1
for _i in range(24):
    _c = 0
    for _j in range(7):
        _r = ((_r << 1) ^ ((_r >> 7) * 0x71)) % 256
        if _r & 2:
            _c |= 1 << ((1 << _j) - 1)
    _RC.append(_c)
# lane index x + 5y -> (destination index, rotation) of rho and pi
_RHO_PI = []
_x, _y = 1, 0
_rot = {0: 0}
for _t in range(24):
    _rot[_x + 5 * _y] = ((_t + 1) * (_t + 2) // 2) % 64
    _x, _y = _y, (2 * _x + 3 * _y) % 5
for _idx in range(25):
    _x, _y = _idx % 5, _idx // 5
    _RHO_PI.append((_y + 5 * ((2 * _x + 3 * _y) % 5), _rot[_idx]))


def _rotl(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _M64 if n else v


def keccak_f1600(state: bytearray) -> None:
    a = [int.from_bytes(state[8 * i:8 * i + 8], "little") for i in range(25)]
    for rc in _RC:
        c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
        a = [a[i] ^ d[i % 5] for i in range(25)]
        b = [0] * 25
        for i in range(25):
            dst, n = _RHO_PI[i]
            b[dst] = _rotl(a[i], n)
        a = [b[i] ^ ((~b[(i % 5 + 1) % 5 + 5 * (i // 5)])
                     & b[(i % 5 + 2) % 5 + 5 * (i // 5)]) for i in range(25)]
        a[0] ^= rc
    state[:] = b"".join(v.to_bytes(8, "little") for v in a)


# -- STROBE-128 and merlin --------------------------------------------------------

_RATE = 166
_I, _A, _C, _M, _K = 1, 2, 4, 16, 32


class Strobe:
    def __init__(self, label: bytes):
        self.st = bytearray(200)
        self.st[0:6] = bytes([1, _RATE + 2, 1, 0, 1, 96])
        self.st[6:18] = b"STROBEv1.0.2"
        keccak_f1600(self.st)
        self.pos = self.pos_begin = self.flags = 0
        self.meta_ad(label, False)

    def _run_f(self) -> None:
        self.st[self.pos] ^= self.pos_begin
        self.st[self.pos + 1] ^= 0x04
        self.st[_RATE + 1] ^= 0x80
        keccak_f1600(self.st)
        self.pos = self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for byte in data:
            self.st[self.pos] ^= byte
            self.pos += 1
            if self.pos == _RATE:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.st[self.pos])
            self.st[self.pos] = 0
            self.pos += 1
            if self.pos == _RATE:
                self._run_f()
        return bytes(out)

    def _begin(self, flags: int, more: bool) -> None:
        if more:
            if flags != self.flags:
                raise ValueError("continued STROBE operation changed flags")
            return
        old, self.pos_begin, self.flags = self.pos_begin, self.pos + 1, flags
        self._absorb(bytes([old, flags]))
        if flags & (_C | _K) and self.pos:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool) -> None:
        self._begin(_M | _A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool) -> None:
        self._begin(_A, more)
        self._absorb(data)

    def prf(self, n: int) -> bytes:
        self._begin(_I | _A | _C, False)
        return self._squeeze(n)


class Transcript:
    def __init__(self, label: bytes):
        self.strobe = Strobe(b"Merlin v1.0")
        self.append_message(b"dom-sep", label)

    def append_message(self, label: bytes, message: bytes) -> None:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(len(message).to_bytes(4, "little"), True)
        self.strobe.ad(message, False)

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n.to_bytes(4, "little"), True)
        return self.strobe.prf(n)


# -- edwards25519 and ristretto255 -------------------------------------------------

P = 2 ** 255 - 19
L = 2 ** 252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
IDENTITY = (0, 1, 1, 0)


def _neg(x: int) -> bool:
    return bool(x % P & 1)


def _abs(x: int) -> int:
    x %= P
    return P - x if x & 1 else x


def sqrt_ratio_m1(u: int, v: int):
    """(was_square, r): r = sqrt(u/v) non-negative, or sqrt(i*u/v)."""
    u %= P
    v %= P
    r = u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P) % P
    check = v * r * r % P
    correct, flipped = check == u, check == (-u) % P
    flipped_i = check == (-u * SQRT_M1) % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    return correct or flipped, _abs(r)


INVSQRT_A_MINUS_D = sqrt_ratio_m1(1, (-1 - D) % P)[1]


def add(p1, p2):
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def neg(p1):
    x, y, z, t = p1
    return ((-x) % P, y, z, (-t) % P)


def _base():
    y = 4 * pow(5, P - 2, P) % P
    _ok, x = sqrt_ratio_m1(y * y - 1, D * y * y + 1)
    return (x, y, 1, x * y % P)


BASE = _base()
# 16^i * j * B for the 64 nibbles of a scalar: a base multiple is 64 adds
_TABLE = []
_q = BASE
for _i in range(64):
    row = [IDENTITY]
    for _j in range(15):
        row.append(add(row[-1], _q))
    _TABLE.append(row)
    _q = add(row[-1], _q)


def base_mul(k: int):
    acc = IDENTITY
    for i in range(64):
        nib = (k >> (4 * i)) & 15
        if nib:
            acc = add(acc, _TABLE[i][nib])
    return acc


def mul(k: int, point):
    acc = IDENTITY
    for bit in bin(k)[2:]:
        acc = add(acc, acc)
        if bit == "1":
            acc = add(acc, point)
    return acc


def decode(s_bytes: bytes):
    """RFC 9496 §4.3.1: the point, or None for a non-canonical or invalid
    encoding."""
    s = int.from_bytes(s_bytes, "little")
    if len(s_bytes) != 32 or s >= P or s & 1:
        return None
    ss = s * s % P
    u1, u2 = (1 - ss) % P, (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 * u1) - u2_sqr) % P
    was_square, invsqrt = sqrt_ratio_m1(1, v * u2_sqr)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x * v % P
    x = _abs(2 * s * den_x)
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or _neg(t) or y == 0:
        return None
    return (x, y, 1, t)


def encode(point) -> bytes:
    """RFC 9496 §4.3.2."""
    x0, y0, z0, t0 = point
    u1 = (z0 + y0) * (z0 - y0) % P
    u2 = x0 * y0 % P
    _ok, invsqrt = sqrt_ratio_m1(1, u1 * u2 * u2)
    den1, den2 = invsqrt * u1 % P, invsqrt * u2 % P
    z_inv = den1 * den2 * t0 % P
    if _neg(t0 * z_inv):
        x, y = y0 * SQRT_M1 % P, x0 * SQRT_M1 % P
        den_inv = den1 * INVSQRT_A_MINUS_D % P
    else:
        x, y, den_inv = x0, y0, den2
    if _neg(x * z_inv):
        y = -y
    return _abs(den_inv * (z0 - y)).to_bytes(32, "little")


def equal(p1, p2) -> bool:
    x1, y1, _z1, _t1 = p1
    x2, y2, _z2, _t2 = p2
    return (x1 * y2 - y1 * x2) % P == 0 or (y1 * y2 - x1 * x2) % P == 0


# -- schnorrkel -------------------------------------------------------------------

SIGNING_CONTEXT = b"substrate"


def _transcript(pub: bytes, msg: bytes, r_enc: bytes) -> Transcript:
    t = Transcript(b"SigningContext")
    t.append_message(b"", SIGNING_CONTEXT)
    t.append_message(b"sign-bytes", msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    t.append_message(b"sign:R", r_enc)
    return t


def challenge(pub: bytes, msg: bytes, r_enc: bytes) -> int:
    """k of the signature (R, s) by `pub` over `msg`."""
    return int.from_bytes(
        _transcript(pub, msg, r_enc).challenge_bytes(b"sign:c", 64),
        "little") % L


def keypair(secret: bytes):
    """(scalar, 32-byte public key) from 32 secret bytes."""
    x = int.from_bytes(hashlib.sha512(b"sr25519 key/" + secret).digest(),
                       "little") % L
    return x, encode(base_mul(x))


def sign(x: int, pub: bytes, msg: bytes, nonce: bytes) -> bytes:
    """R || s, s's top bit set; the witness r is drawn from `nonce` (any
    r verifies: it only has to stay secret in a deployment)."""
    r = int.from_bytes(hashlib.sha512(b"sr25519 nonce/" + nonce).digest(),
                       "little") % L
    r_enc = encode(base_mul(r))
    s = (challenge(pub, msg, r_enc) * x + r) % L
    sig = bytearray(r_enc + s.to_bytes(32, "little"))
    sig[63] |= 0x80
    return bytes(sig)


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != 64 or not sig[63] & 0x80:
        return False
    s = int.from_bytes(sig[32:63] + bytes([sig[63] & 0x7F]), "little")
    if s >= L:
        return False
    a_pt, r_pt = decode(pub), decode(sig[:32])
    if a_pt is None or r_pt is None:
        return False
    k = challenge(pub, msg, sig[:32])
    return equal(add(base_mul(s), neg(mul(k, a_pt))), r_pt)


# -- types/validation.go verifyCommitSingle ---------------------------------------


def verify_commit(chain_id: str, pubkeys, powers, height: int, digest: bytes,
                  sigs):
    """sigs: per validator, None (absent) or (timestamp seconds, nanos,
    64-byte signature), in validator-set order — benchmark/reference.py's
    loop, sr25519 signatures."""
    needed = sum(powers) * 2 // 3
    tpl = wire.sign_bytes_template(chain_id, height, digest)
    tallied = 0
    for idx, rec in enumerate(sigs):
        if rec is None:
            continue
        seconds, nanos, sig = rec
        if not verify(bytes(pubkeys[idx]), wire.sign_bytes(tpl, seconds, nanos),
                      sig):
            return ("ValueError",
                    f"wrong signature (#{idx}): {sig.hex().upper()}")
        tallied += powers[idx]
    if tallied <= needed:
        return ("ErrNotEnoughVotingPowerSigned",
                "invalid commit -- insufficient voting power: "
                f"got {tallied}, needed more than {needed}")
    return None
