"""Seeded inputs of a commit-verification cell: one validator set and a
pool of distinct signed commits over it, held as wire bytes, plus the
commits built to fail and what the plain reference says of each.

Everything follows from (config, seed): keys, block digests, per-
validator timestamps, signatures (OpenSSL ed25519, deterministic), the
forged positions. Building 10 000 keys and 160 000 signatures costs
seconds, so a built pool is kept under <checkout>/.bench_cache/pool/,
keyed by config and seed; a later run of the cell with that seed loads it.
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

from . import reference, wire

T0 = 1_700_000_000        # first block's time, seconds
BLOCK_INTERVAL = 6        # seconds between blocks
FORMAT = 1                # bump when the bytes a (config, seed) gives change
N_FORGED = 2


@dataclasses.dataclass
class BlameCase:
    what: str
    height: int
    digest: bytes
    wire: bytes
    expect: tuple          # (exception type name, message) of the reference


@dataclasses.dataclass
class Pool:
    chain_id: str
    pubkeys: np.ndarray    # (n, 32) uint8, validator-set order
    power: int
    heights: list
    digests: list          # 32-byte block hash per commit
    commits: list          # wire bytes per commit
    blame: list            # [BlameCase]
    built: bool = True     # False when loaded from the cache

    @property
    def n_validators(self) -> int:
        return len(self.pubkeys)


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def address(pub: bytes) -> bytes:
    """crypto/ed25519: the first 20 bytes of SHA-256 of the public key."""
    return hashlib.sha256(pub).digest()[:20]


def _validators(name: str, seed: int, n: int):
    """[(address, public key bytes, private key)] in validator-set order:
    equal power, so ascending address (types/validator_set.go
    ValidatorsByVotingPower)."""
    out = []
    for i in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(_digest(seed, name, "key", i))
        pub = sk.public_key().public_bytes(serialization.Encoding.Raw,
                                           serialization.PublicFormat.Raw)
        out.append((address(pub), pub, sk))
    out.sort(key=lambda v: v[0])
    return out


def _sign_commit(chain_id, vals, height, digest, rng, signers=None):
    """Per validator None or (seconds, nanos, signature): every validator
    (or the first `signers`) precommits for the block at its own clock
    reading, as a live chain's validators do."""
    tpl = wire.sign_bytes_template(chain_id, height, digest)
    base = T0 + BLOCK_INTERVAL * height
    recs = []
    for i, (_addr, _pub, sk) in enumerate(vals):
        if signers is not None and i >= signers:
            recs.append(None)
            continue
        seconds, nanos = base + rng.randrange(2), rng.randrange(10 ** 9)
        recs.append((seconds, nanos,
                     sk.sign(wire.sign_bytes(tpl, seconds, nanos))))
    return recs


def _encode(vals, height, digest, recs) -> bytes:
    return wire.commit(height, digest, [
        wire.ABSENT_SIG if r is None
        else wire.commit_sig(wire.FLAG_COMMIT, vals[i][0], r[0], r[1], r[2])
        for i, r in enumerate(recs)
    ])


def build(cfg: dict, seed: int) -> Pool:
    name, n, power = cfg["name"], cfg["validators"], cfg["voting_power"]
    chain_id = cfg["chain_id"]
    rng = random.Random(f"{seed}/{name}/{FORMAT}")
    vals = _validators(name, seed, n)
    pubkeys = [v[1] for v in vals]
    powers = [power] * n

    def ref(height, digest, recs):
        return reference.verify_commit(chain_id, pubkeys, powers, height,
                                       digest, recs)

    heights = list(range(1, cfg["pool_commits"] + 1))
    digests, commits = [], []
    for h in heights:
        d = _digest(seed, name, "block", h)
        recs = _sign_commit(chain_id, vals, h, d, rng)
        if h == 1 and ref(h, d, recs) is not None:
            raise RuntimeError("the reference rejects an honest commit")
        digests.append(d)
        commits.append(_encode(vals, h, d, recs))

    blame = []
    h = heights[-1]
    for k in range(N_FORGED):
        h += 1
        d = _digest(seed, name, "block", h)
        recs = _sign_commit(chain_id, vals, h, d, rng)
        idx, byte, bit = rng.randrange(n), rng.randrange(64), rng.randrange(8)
        sig = bytearray(recs[idx][2])
        sig[byte] ^= 1 << bit
        recs[idx] = (recs[idx][0], recs[idx][1], bytes(sig))
        blame.append(BlameCase(f"forged#{idx}", h, d, _encode(vals, h, d, recs),
                               ref(h, d, recs)))
    h += 1
    d = _digest(seed, name, "block", h)
    keep = (n * power * 2 // 3) // power      # exactly 2/3: one short of enough
    recs = _sign_commit(chain_id, vals, h, d, rng, signers=keep)
    blame.append(BlameCase(f"starved@{keep}", h, d, _encode(vals, h, d, recs),
                           ref(h, d, recs)))
    if any(c.expect is None for c in blame):
        raise RuntimeError("the reference accepts a commit built to fail")
    return Pool(chain_id, np.frombuffer(b"".join(pubkeys), np.uint8)
                .reshape(n, 32), power, heights, digests, commits, blame)


# -- the pool cache ------------------------------------------------------------


def _pack(blobs):
    off = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=off[1:])
    return np.frombuffer(b"".join(blobs), np.uint8), off


def _unpack(buf, off):
    raw = buf.tobytes()
    return [raw[off[i]:off[i + 1]] for i in range(len(off) - 1)]


def cache_path(root: str, cfg: dict, seed: int) -> str:
    return os.path.join(root, ".bench_cache", "pool",
                        f"{cfg['name']}-{seed}-v{FORMAT}.npz")


def save(pool: Pool, path: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    wire_buf, wire_off = _pack(pool.commits)
    blame_buf, blame_off = _pack([c.wire for c in pool.blame])
    meta = {
        "chain_id": pool.chain_id, "power": pool.power, "heights": pool.heights,
        "blame": [[c.what, c.height, list(c.expect)] for c in pool.blame],
    }
    tmp = path + ".tmp.npz"
    np.savez(tmp, pubkeys=pool.pubkeys, wire=wire_buf, wire_off=wire_off,
             digests=np.frombuffer(b"".join(pool.digests), np.uint8),
             blame=blame_buf, blame_off=blame_off,
             blame_digests=np.frombuffer(
                 b"".join(c.digest for c in pool.blame), np.uint8),
             meta=np.frombuffer(json.dumps(meta).encode(), np.uint8))
    os.replace(tmp, path)


def load(path: str) -> Pool:
    with np.load(path) as z:
        meta = json.loads(z["meta"].tobytes())
        digests = z["digests"].tobytes()
        bdig = z["blame_digests"].tobytes()
        blame = [
            BlameCase(what, height, bdig[32 * i:32 * i + 32], w, tuple(expect))
            for i, ((what, height, expect), w) in enumerate(
                zip(meta["blame"], _unpack(z["blame"], z["blame_off"])))
        ]
        return Pool(meta["chain_id"], z["pubkeys"], meta["power"],
                    meta["heights"],
                    [digests[i:i + 32] for i in range(0, len(digests), 32)],
                    _unpack(z["wire"], z["wire_off"]), blame, built=False)


def pool(root: str, cfg: dict, seed: int) -> Pool:
    """The cell's pool: loaded if this checkout built it before, else
    built and kept."""
    path = cache_path(root, cfg, seed)
    if os.path.exists(path):
        return load(path)
    p = build(cfg, seed)
    save(p, path)
    return p
