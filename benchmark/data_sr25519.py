"""Seeded inputs of an sr25519 commit-verification cell: one validator set
whose keys are sr25519 and a pool of distinct signed commits over it,
held as wire bytes, plus the commits built to fail and what the plain
reference says of each — data.py's pool, signed by benchmark/
reference_sr25519.py in Python integers (no OpenSSL scheme, none of the
program's code).

Everything follows from (config, seed): keys, block digests, per-
validator timestamps, signatures and their witnesses, the forged
positions. Signing 64 commits of 150 validators costs tens of seconds
here, so a built pool is kept under <checkout>/.bench_cache/pool/, keyed
by config and seed, in data.py's file format.
"""

from __future__ import annotations

import os
import random

import numpy as np

from . import data, reference_sr25519 as ref, wire

FORMAT = 1                # bump when the bytes a (config, seed) gives change


def _validators(name: str, seed: int, n: int):
    """[(address, public key bytes, secret scalar)] in validator-set order:
    equal power, so ascending address. An sr25519 address is the first 20
    bytes of SHA-256 of the key, as an ed25519 one is."""
    out = []
    for i in range(n):
        x, pub = ref.keypair(data._digest(seed, name, "sr25519 key", i))
        out.append((data.address(pub), pub, x))
    out.sort(key=lambda v: v[0])
    return out


def _sign_commit(chain_id, vals, height, digest, rng, signers=None):
    """data._sign_commit with schnorrkel signatures: per validator None or
    (seconds, nanos, signature), every validator (or the first
    `signers`) precommitting at its own clock reading."""
    tpl = wire.sign_bytes_template(chain_id, height, digest)
    base = data.T0 + data.BLOCK_INTERVAL * height
    recs = []
    for i, (_addr, pub, x) in enumerate(vals):
        if signers is not None and i >= signers:
            recs.append(None)
            continue
        seconds, nanos = base + rng.randrange(2), rng.randrange(10 ** 9)
        nonce = rng.getrandbits(256).to_bytes(32, "little")
        recs.append((seconds, nanos,
                     ref.sign(x, pub, wire.sign_bytes(tpl, seconds, nanos),
                              nonce)))
    return recs


def build(cfg: dict, seed: int) -> data.Pool:
    name, n, power = cfg["name"], cfg["validators"], cfg["voting_power"]
    chain_id = cfg["chain_id"]
    rng = random.Random(f"{seed}/{name}/sr25519/{FORMAT}")
    vals = _validators(name, seed, n)
    pubkeys = [v[1] for v in vals]
    powers = [power] * n

    def check(height, digest, recs):
        return ref.verify_commit(chain_id, pubkeys, powers, height, digest,
                                 recs)

    heights = list(range(1, cfg["pool_commits"] + 1))
    digests, commits = [], []
    for h in heights:
        d = data._digest(seed, name, "block", h)
        recs = _sign_commit(chain_id, vals, h, d, rng)
        if h == 1 and check(h, d, recs) is not None:
            raise RuntimeError("the reference rejects an honest commit")
        digests.append(d)
        commits.append(data._encode(vals, h, d, recs))

    blame = []
    h = heights[-1]
    for _k in range(data.N_FORGED):
        h += 1
        d = data._digest(seed, name, "block", h)
        recs = _sign_commit(chain_id, vals, h, d, rng)
        idx, byte, bit = rng.randrange(n), rng.randrange(64), rng.randrange(8)
        sig = bytearray(recs[idx][2])
        sig[byte] ^= 1 << bit
        recs[idx] = (recs[idx][0], recs[idx][1], bytes(sig))
        blame.append(data.BlameCase(f"forged#{idx}", h, d,
                                    data._encode(vals, h, d, recs),
                                    check(h, d, recs)))
    h += 1
    d = data._digest(seed, name, "block", h)
    keep = (n * power * 2 // 3) // power      # exactly 2/3: one short of enough
    recs = _sign_commit(chain_id, vals, h, d, rng, signers=keep)
    blame.append(data.BlameCase(f"starved@{keep}", h, d,
                                data._encode(vals, h, d, recs),
                                check(h, d, recs)))
    if any(c.expect is None for c in blame):
        raise RuntimeError("the reference accepts a commit built to fail")
    return data.Pool(chain_id, np.frombuffer(b"".join(pubkeys), np.uint8)
                     .reshape(n, 32), power, heights, digests, commits, blame)


def cache_path(root: str, cfg: dict, seed: int) -> str:
    return os.path.join(root, ".bench_cache", "pool",
                        f"{cfg['name']}-{seed}-sr25519-v{FORMAT}.npz")


def pool(root: str, cfg: dict, seed: int) -> data.Pool:
    """The cell's pool: loaded if this checkout built it before, else
    built and kept."""
    path = cache_path(root, cfg, seed)
    if os.path.exists(path):
        return data.load(path)
    p = build(cfg, seed)
    data.save(p, path)
    return p
