"""Seeded inputs of a light-client cell: a chain of light blocks whose
validator set changes at every height, held as the wire bytes of
tendermint.types.LightBlock, plus the blocks built to fail and what the
plain reference says of each.

The shape is Tendermint Core v0.35 light/helpers_test.go
genLightBlocksWithKeys(chainID, headers, validators, 1, bTime): at every
height the oldest key leaves the set and one new key joins, every
validator signs, a block a minute. Everything follows from (config,
seed): keys, header fields, per-validator timestamps, proposer
priorities, signatures (OpenSSL ed25519, deterministic), the forged
positions. The Merkle hashes (validator set, header) and every byte of
the wire form are written out here, so that the inputs do not depend on
the program's encoders; tests/benchmark holds them against the program's.
The reference (benchmark/reference_light.py) is run over every pool block
at build, off the clock. A built pool is kept under
<checkout>/.bench_cache/pool/, keyed by config and seed.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import random

import numpy as np
from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from . import reference_light, wire
from .data import _digest, _pack, _unpack, address

T0 = 1_700_000_000        # the chain's bTime, seconds
BLOCK_PROTOCOL = 11       # version.BlockProtocol
FORMAT = 1                # bump when the bytes a (config, seed) gives change
CASES = ("forged_within", "forged_past", "departed_key", "swapped_valset",
         "starved")


# -- Merkle (crypto/merkle/tree.go) and the two hashes the verifier checks -----


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def merkle_root(items) -> bytes:
    """RFC 6962: leaf 0x00, inner 0x01, split at the largest power of two
    strictly below the length."""
    n = len(items)
    if n == 0:
        return _sha(b"")
    if n == 1:
        return _sha(b"\x00" + items[0])
    k = 1 << ((n - 1).bit_length() - 1)
    return _sha(b"\x01" + merkle_root(items[:k]) + merkle_root(items[k:]))


def _pub_proto(pub: bytes) -> bytes:
    return wire._bytes(1, pub, always=True)       # PublicKey{ed25519 = 1}


def valset_hash(vals) -> bytes:
    """ValidatorSet.Hash: the root over SimpleValidator{1 pub_key, 2
    voting_power} of every validator, in set order."""
    return merkle_root([wire._bytes(1, _pub_proto(v.pub), always=True)
                        + wire._varint(2, v.power) for v in vals])


def _wrapped(b: bytes) -> bytes:
    """cdcEncode of a string or bytes value: gogotypes {String,Bytes}Value
    with the value in field 1, nothing for an empty one."""
    return wire._bytes(1, b)


def header_hash(h: "Header") -> bytes:
    """Header.Hash (types/block.go:448-483): the root over the 14 fields."""
    return merkle_root([
        wire._varint(1, h.version_block) + wire._varint(2, h.version_app),
        _wrapped(h.chain_id.encode()),
        wire._varint(1, h.height),
        wire.timestamp(h.seconds, h.nanos),
        wire.block_id(h.last_block_hash, 1 if h.last_block_hash else 0),
        _wrapped(h.last_commit_hash), _wrapped(h.data_hash),
        _wrapped(h.validators_hash), _wrapped(h.next_validators_hash),
        _wrapped(h.consensus_hash), _wrapped(h.app_hash),
        _wrapped(h.last_results_hash), _wrapped(h.evidence_hash),
        _wrapped(h.proposer_address),
    ])


# -- the builder's own records ------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Val:
    address: bytes
    pub: bytes
    power: int
    priority: int
    key: int               # index into the chain's keys


@dataclasses.dataclass(frozen=True)
class Header:
    chain_id: str
    height: int
    seconds: int
    nanos: int
    last_block_hash: bytes
    last_commit_hash: bytes
    data_hash: bytes
    validators_hash: bytes
    next_validators_hash: bytes
    consensus_hash: bytes
    app_hash: bytes
    last_results_hash: bytes
    evidence_hash: bytes
    proposer_address: bytes
    version_block: int = BLOCK_PROTOCOL
    version_app: int = 1


@dataclasses.dataclass(frozen=True)
class Block:
    """One height: the header, the set that signs it, and per validator
    None or (seconds, nanos, signature), in set order."""
    header: Header
    vals: tuple
    block_hash: bytes      # what the commit's BlockID carries
    sigs: tuple

    @property
    def height(self) -> int:
        return self.header.height


@dataclasses.dataclass
class BlameCase:
    what: str
    height: int
    wire: bytes
    expect: object         # None, or (exception type name, message)


@dataclasses.dataclass
class Pool:
    chain_id: str
    n_validators: int
    power: int
    now: tuple             # (seconds, nanos) the verifier is told it is
    trusting_period_s: int
    max_clock_drift_s: int
    blocks: list           # wire bytes of the light block at height i + 1
    blame: list            # [[BlameCase] at one height, the same at the next]
    built: bool = True


# -- wire form (proto/tendermint/types/{types,validator}.proto) -----------------


def _header_wire(h: Header) -> bytes:
    b = wire._bytes
    return (b(1, wire._varint(1, h.version_block)
              + wire._varint(2, h.version_app), always=True)
            + b(2, h.chain_id.encode()) + wire._varint(3, h.height)
            + b(4, wire.timestamp(h.seconds, h.nanos), always=True)
            + b(5, wire.block_id(h.last_block_hash,
                                 1 if h.last_block_hash else 0), always=True)
            + b(6, h.last_commit_hash) + b(7, h.data_hash)
            + b(8, h.validators_hash) + b(9, h.next_validators_hash)
            + b(10, h.consensus_hash) + b(11, h.app_hash)
            + b(12, h.last_results_hash) + b(13, h.evidence_hash)
            + b(14, h.proposer_address))


def _val_wire(v: Val) -> bytes:
    return (wire._bytes(1, v.address)
            + wire._bytes(2, _pub_proto(v.pub), always=True)
            + wire._varint(3, v.power) + wire._varint(4, v.priority))


def valset_wire(vals) -> bytes:
    """ValidatorSet{1* validators, 2 proposer}; the proposer is the
    validator of the highest priority, as ValidatorSet.findProposer."""
    proposer = max(vals, key=lambda v: (v.priority, [-x for x in v.address]))
    return (b"".join(wire._bytes(1, _val_wire(v), always=True) for v in vals)
            + wire._bytes(2, _val_wire(proposer)))


def commit_wire(blk: Block) -> bytes:
    return wire.commit(blk.height, blk.block_hash, [
        wire.ABSENT_SIG if r is None
        else wire.commit_sig(wire.FLAG_COMMIT, blk.vals[i].address, *r)
        for i, r in enumerate(blk.sigs)])


def light_block_wire(blk: Block, vals=None) -> bytes:
    """The block as a light client is handed it; `vals` supplies another
    validator set than the one that signed."""
    signed = (wire._bytes(1, _header_wire(blk.header), always=True)
              + wire._bytes(2, commit_wire(blk), always=True))
    return (wire._bytes(1, signed, always=True)
            + wire._bytes(2, valset_wire(blk.vals if vals is None else vals),
                          always=True))


# -- the chain -----------------------------------------------------------------


class _Keys:
    """The chain's keys by index, made when first asked for."""

    def __init__(self, name: str, seed: int):
        self._name, self._seed, self._made = name, seed, {}

    def __getitem__(self, k: int):
        if k not in self._made:
            sk = Ed25519PrivateKey.from_private_bytes(
                _digest(self._seed, self._name, "key", k))
            pub = sk.public_key().public_bytes(serialization.Encoding.Raw,
                                               serialization.PublicFormat.Raw)
            self._made[k] = (sk, pub, address(pub))
        return self._made[k]


def _set_at(keys: _Keys, height: int, n: int, per_height: int, power: int,
            rng) -> tuple:
    """The set at `height`: keys [(height-1)*per_height, +n), so the oldest
    `per_height` keys left and as many joined since the height before. Equal
    power, so ascending address (ValidatorsByVotingPower)."""
    first = (height - 1) * per_height
    total = n * power
    vals = [Val(keys[k][2], keys[k][1], power,
                rng.randrange(-total, total + 1), k)
            for k in range(first, first + n)]
    vals.sort(key=lambda v: v.address)
    return tuple(vals)


def _sign(keys: _Keys, chain_id: str, blk_vals, height: int, block_hash: bytes,
          base_seconds: int, rng, signer=None) -> tuple:
    """Every validator precommits for the block at its own clock reading;
    `signer` maps a row to the key index that signs there (default: its
    own)."""
    tpl = wire.sign_bytes_template(chain_id, height, block_hash)
    out = []
    for i, v in enumerate(blk_vals):
        seconds, nanos = base_seconds + rng.randrange(2), rng.randrange(10 ** 9)
        sk = keys[(signer or {}).get(i, v.key)][0]
        out.append((seconds, nanos,
                    sk.sign(wire.sign_bytes(tpl, seconds, nanos))))
    return tuple(out)


def chain(cfg: dict, seed: int):
    """(keys, [Block at height 1 .. headers])."""
    name, n = cfg["name"], cfg["validators"]
    per, power = cfg["keys_replaced_per_height"], cfg["voting_power"]
    rng = random.Random(f"{seed}/{name}/light/{FORMAT}")
    keys = _Keys(name, seed)
    heights = range(1, cfg["headers"] + 2)        # one more set: next_validators
    sets = {h: _set_at(keys, h, n, per, power, rng) for h in heights}
    hashes = {h: valset_hash(sets[h]) for h in heights}
    blocks, last = [], b""
    for h in range(1, cfg["headers"] + 1):
        def d(what, h=h):
            return _digest(seed, name, what, h)
        hdr = Header(
            cfg["chain_id"], h, T0 + cfg["block_interval_s"] * h,
            rng.randrange(10 ** 9), last, d("last_commit"), d("data"),
            hashes[h], hashes[h + 1], _digest(seed, name, "consensus"),
            d("app"), d("results"), d("evidence"), sets[h][0].address)
        bh = header_hash(hdr)
        blocks.append(Block(hdr, sets[h], bh, _sign(
            keys, cfg["chain_id"], sets[h], h, bh, hdr.seconds, rng)))
        last = bh
    return keys, blocks


def _enough(cfg: dict) -> int:
    """Signatures of equal power that pass two thirds: where
    VerifyCommitLight stops."""
    n, power = cfg["validators"], cfg["voting_power"]
    return (n * power * 2 // 3) // power + 1


def _joined_row(blocks, h: int) -> int:
    """The row, in the set at height h, of the key that joined there."""
    before = {p.key for p in blocks[h - 2].vals}
    return next(i for i, v in enumerate(blocks[h - 1].vals)
                if v.key not in before)


def _blame_at(cfg, keys, blocks, h: int, rng, ref) -> list:
    """The five blocks built to fail (or, for one, to pass) at height h,
    each a variant of the honest block, with the reference's verdict."""
    blk, prev = blocks[h - 1], blocks[h - 2]
    n, enough = cfg["validators"], _enough(cfg)

    def flipped(idx):
        s = list(blk.sigs)
        sig = bytearray(s[idx][2])
        sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
        s[idx] = (s[idx][0], s[idx][1], bytes(sig))
        return tuple(s)

    joined = _joined_row(blocks, h)
    left = next(p.key for p in prev.vals
                if p.key not in {v.key for v in blk.vals})
    swapped = list(blk.vals)
    j = rng.randrange(n)
    _sk, pub, addr = keys[10 ** 6 + h]                  # a key of no set
    swapped[j] = dataclasses.replace(swapped[j], pub=pub, address=addr)
    variants = {
        "forged_within": dict(sigs=flipped(rng.randrange(enough))),
        "forged_past": dict(sigs=flipped(rng.randrange(enough, n))),
        "departed_key": dict(sigs=_sign(
            keys, cfg["chain_id"], blk.vals, h, blk.block_hash,
            blk.header.seconds, rng, signer={joined: left})),
        "swapped_valset": dict(vals=tuple(swapped)),
        "starved": dict(sigs=tuple(r if i < enough - 1 else None
                                   for i, r in enumerate(blk.sigs))),
    }
    out = []
    for what in CASES:
        v = variants[what]
        forged = dataclasses.replace(blk, sigs=v.get("sigs", blk.sigs))
        expect = ref(prev, forged, v.get("vals", blk.vals))
        if (expect is None) != (what == "forged_past"):
            raise RuntimeError(f"the reference says {expect!r} of {what}@{h}")
        out.append(BlameCase(f"{what}@{h}", h, light_block_wire(
            forged, vals=v.get("vals")), expect))
    return out


def build(cfg: dict, seed: int) -> Pool:
    keys, blocks = chain(cfg, seed)
    rng = random.Random(f"{seed}/{cfg['name']}/blame/{FORMAT}")
    now = (blocks[-1].header.seconds + 1, 0)
    period, drift = cfg["trusting_period_s"], cfg["max_clock_drift_s"]

    def ref(trusted, untrusted, vals):
        return reference_light.verify_adjacent(
            trusted, untrusted, vals, period, now, drift)

    for prev, blk in zip(blocks, blocks[1:]):
        said = ref(prev, blk, blk.vals)
        if said is not None:
            raise RuntimeError(f"the reference rejects honest height "
                               f"{blk.height}: {said!r}")
    # two adjacent heights at both of which the key that joined sits inside
    # the early stop: whichever of them a full table turns into a cold
    # build, the other is served from a patched one
    h = next(h for h in range(len(blocks) - 1, 3, -1)
             if max(_joined_row(blocks, h), _joined_row(blocks, h + 1))
             < _enough(cfg))
    blame = [_blame_at(cfg, keys, blocks, x, rng, ref) for x in (h, h + 1)]
    return Pool(cfg["chain_id"], cfg["validators"], cfg["voting_power"], now,
                period, drift, [light_block_wire(b) for b in blocks], blame)


# -- the pool cache ------------------------------------------------------------


def cache_path(root: str, cfg: dict, seed: int) -> str:
    return os.path.join(root, ".bench_cache", "pool",
                        f"{cfg['name']}-{seed}-light-v{FORMAT}.npz")


def save(pool: Pool, path: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    cases = [c for group in pool.blame for c in group]
    wire_buf, wire_off = _pack(pool.blocks)
    blame_buf, blame_off = _pack([c.wire for c in cases])
    meta = {"chain_id": pool.chain_id, "n_validators": pool.n_validators,
            "power": pool.power, "now": pool.now,
            "trusting_period_s": pool.trusting_period_s,
            "max_clock_drift_s": pool.max_clock_drift_s,
            "groups": [len(g) for g in pool.blame],
            "blame": [[c.what, c.height, c.expect] for c in cases]}
    tmp = path + ".tmp.npz"
    np.savez(tmp, wire=wire_buf, wire_off=wire_off, blame=blame_buf,
             blame_off=blame_off,
             meta=np.frombuffer(json.dumps(meta).encode(), np.uint8))
    os.replace(tmp, path)


def load(path: str) -> Pool:
    with np.load(path) as z:
        meta = json.loads(z["meta"].tobytes())
        cases = [BlameCase(what, height, w, expect and tuple(expect))
                 for (what, height, expect), w in zip(
                     meta["blame"], _unpack(z["blame"], z["blame_off"]))]
        groups, k = [], 0
        for size in meta["groups"]:
            groups.append(cases[k:k + size])
            k += size
        return Pool(meta["chain_id"], meta["n_validators"], meta["power"],
                    tuple(meta["now"]), meta["trusting_period_s"],
                    meta["max_clock_drift_s"],
                    _unpack(z["wire"], z["wire_off"]), groups, built=False)


def pool(root: str, cfg: dict, seed: int) -> Pool:
    """The cell's pool: loaded if this checkout built it before, else
    built and kept."""
    path = cache_path(root, cfg, seed)
    if os.path.exists(path):
        return load(path)
    p = build(cfg, seed)
    save(p, path)
    return p
