#!/usr/bin/env python3
"""chip_probe.py — do the OTHER lanes compile and run on the chip?

Not part of chip_smoke.py and not gating: a time-boxed look at the four
paths the roadmap's scheme/mesh items are waiting on, each run once
through its normal entry, compared with its host oracle:

  secp    ops.backend.verify_batch_secp at the 128 bucket
  bls     K = 16 aggregated commits of a 128-validator committee through
          types.validation.prepare_aggregated_commit -> the shared
          dispatcher, against verify_aggregated_commit (the sequential
          oracle walk)
  mixed   ops.mixed.verify_mixed with ed25519 + sr25519 (share >= 8) +
          secp256k1 in one batch
  sharded ops.sharded.verify_commit_sharded_rlc over make_mesh(n) for
          every chip the machine shows

One process per chip: this parent never imports jax. Each item runs in
its own child (which owns the device while it lives) under a hard time
box — "did not finish in N s" is a result, not an error. Results print
as a table and land in chiprun_out/chip_probe.json; the exit code is 0
whenever the probe itself ran (read the table for the verdicts).

    python tools/chip_probe.py [--box SECONDS] [--items secp,bls,...]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITEMS = ("secp", "bls", "mixed", "sharded")
SEED = 21


def _seed(tag: str, i: int) -> bytes:
    return hashlib.sha256(f"{SEED}/{tag}/{i}".encode()).digest()


def _twice(fn):
    """(first result, first-use seconds, repeat seconds)"""
    t = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t
    t = time.perf_counter()
    fn()
    return out, first, time.perf_counter() - t


def item_secp() -> dict:
    import numpy as np

    from tendermint_tpu.crypto import secp256k1
    from tendermint_tpu.ops import backend

    n, bad = 100, 37
    lane = []
    for i in range(n):
        sk = secp256k1.PrivKey(_seed("secp", i))
        msg = b"probe-secp-%d" % i
        lane.append((sk.pub_key(), msg, sk.sign(msg)))
    pk, msg, sig = lane[bad]
    lane[bad] = (pk, msg + b"!", sig)
    oracle = [pk.verify_signature(m, s) for pk, m, s in lane]
    entries = [(pk.bytes(), m, s) for pk, m, s in lane]
    got, first, repeat = _twice(lambda: backend.verify_batch_secp(entries))
    return {"n": n, "bucket": backend._secp_bucket_for(n),
            "first_use_s": first, "repeat_s": repeat,
            "matches_oracle": np.asarray(got).tolist() == oracle}


def item_bls() -> dict:
    import numpy as np

    from tendermint_tpu.crypto import bls12381 as bls
    from tendermint_tpu.libs.bits import BitArray
    from tendermint_tpu.ops import pipeline
    from tendermint_tpu.types import validation as V
    from tendermint_tpu.types.block import (
        AggregatedCommit, BlockID, PartSetHeader,
    )
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    n_vals, k, forged = 128, 16, 5
    chain_id = "probe-bls"
    scalars = [int.from_bytes(_seed("bls", i), "big") % bls.R or 1
               for i in range(n_vals)]
    vals = [Validator.new(bls.PrivKey(d.to_bytes(32, "big")).pub_key(), 100)
            for d in scalars]
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    # the set may reorder validators: recover each row's scalar by key
    by_pub = {v.pub_key.bytes(): d for v, d in zip(vals, scalars)}
    row_scalar = [by_pub[v.pub_key.bytes()] for v in vset.validators]
    bid = BlockID(hash=b"\x21" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x21" * 32))
    jobs = []
    for h in range(1, k + 1):
        signers = BitArray(n_vals)
        for i in range(n_vals):
            signers.set_index(i, i % 23 != h % 23)  # ~122 of 128 sign
        unsigned = AggregatedCommit(height=h, round=0, block_id=bid,
                                    signers=signers)
        # every signer signs the SAME bytes, so the aggregate of their
        # signatures is one signature under the sum of their scalars
        d = sum(row_scalar[i] for i in signers.get_true_indices()) % bls.R
        if h == forged:
            d = (d + 1) % bls.R
        sig = bls.PrivKey(d.to_bytes(32, "big")).sign(
            unsigned.sign_bytes(chain_id))
        jobs.append(AggregatedCommit(height=h, round=0, block_id=bid,
                                     signature=sig, signers=signers))

    def outcome(fn):
        try:
            fn()
        except ValueError as e:
            return str(e)
        return None

    t = time.perf_counter()
    oracle = [outcome(lambda a=a: V.verify_aggregated_commit(
        chain_id, vset, bid, a.height, a)) for a in jobs]
    oracle_s = time.perf_counter() - t

    V.prepare_aggregated_commit(chain_id, vset, bid, jobs[0].height,
                                jobs[0], k_hint=k)  # first sight: cold epoch

    def device():
        prepared = [V.prepare_aggregated_commit(chain_id, vset, bid, a.height,
                                                a, k_hint=k) for a in jobs]
        futs = [pipeline.shared_verifier().submit(blk) for blk, _ in prepared]
        return [outcome(lambda f=f, c=c: c(np.asarray(f.result(timeout=900))))
                for f, (_, c) in zip(futs, prepared)]

    got, first, repeat = _twice(device)
    return {"commits": k, "validators": n_vals, "oracle_s": oracle_s,
            "first_use_s": first, "repeat_s": repeat,
            "rejected": [a.height for a, g in zip(jobs, got) if g],
            "matches_oracle": got == oracle}


def item_mixed() -> dict:
    from tendermint_tpu.crypto import ed25519, secp256k1, sr25519
    from tendermint_tpu.ops.mixed import verify_mixed

    entries = []
    for i in range(64):
        sk = ed25519.gen_priv_key(_seed("mx-ed", i))
        entries.append((sk.pub_key(), b"mx-ed-%d" % i, sk.sign(b"mx-ed-%d" % i)))
    for i in range(16):
        sk = sr25519.gen_priv_key(_seed("mx-sr", i))
        entries.append((sk.pub_key(), b"mx-sr-%d" % i, sk.sign(b"mx-sr-%d" % i)))
    for i in range(16):
        sk = secp256k1.PrivKey(_seed("mx-secp", i))
        entries.append((sk.pub_key(), b"mx-secp-%d" % i,
                        sk.sign(b"mx-secp-%d" % i)))
    for bad in (9, 70, 90):  # one per scheme
        pk, msg, sig = entries[bad]
        entries[bad] = (pk, msg + b"!", sig)
    oracle = [pk.verify_signature(m, s) for pk, m, s in entries]
    got, first, repeat = _twice(lambda: verify_mixed(entries))
    return {"ed25519": 64, "sr25519": 16, "secp256k1": 16,
            "first_use_s": first, "repeat_s": repeat,
            "matches_oracle": got == oracle}


def item_sharded() -> dict:
    import jax

    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import sharded

    n, bad = 1000, 613
    entries, powers = [], []
    for i in range(n):
        sk = ed25519.gen_priv_key(_seed("sh", i))
        msg = b"probe-sharded-%d" % i
        entries.append((sk.pub_key().bytes(), msg, sk.sign(msg)))
        powers.append(10 + i % 7)
    pk, msg, sig = entries[bad]
    entries[bad] = (pk, msg + b"!", sig)
    oracle = [ed25519.verify_zip215_fast(p, m, s) for p, m, s in entries]
    want_power = sum(p for p, ok in zip(powers, oracle) if ok)
    n_dev = len(jax.devices())
    mesh = sharded.make_mesh(n_dev)
    (valid, tallied, all_valid), first, repeat = _twice(
        lambda: sharded.verify_commit_sharded_rlc(entries, powers, mesh))
    return {"n": n, "mesh_devices": n_dev,
            "first_use_s": first, "repeat_s": repeat,
            "matches_oracle": (valid.tolist() == oracle
                               and tallied == want_power and not all_valid)}


def child(name: str) -> None:
    sys.path.insert(0, REPO)
    import jax

    from tendermint_tpu.libs import jaxcache
    from tendermint_tpu.ops.engine import engine

    eng = engine().describe()
    out = {"item": name, "engine": eng}
    out.update({"secp": item_secp, "bls": item_bls, "mixed": item_mixed,
                "sharded": item_sharded}[name]())
    cc = jaxcache.counters()
    out["compile_s"] = round(sum(s for _n, s in cc["compiles"]), 1)
    out["compiles"] = [[n, round(s, 1)] for n, s in cc["compiles"] if s >= 1]
    for key in ("first_use_s", "repeat_s", "oracle_s"):
        if key in out:
            out[key] = round(out[key], 3)
    out["jax"] = jax.__version__
    print("RESULT " + json.dumps(out), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--box", type=float, default=600.0,
                    help="seconds each item may take (default 600)")
    ap.add_argument("--items", default=",".join(ITEMS))
    ap.add_argument("--item", help=argparse.SUPPRESS)  # child mode
    args = ap.parse_args()
    if args.item:
        return child(args.item)

    sys.path.insert(0, REPO)
    from tendermint_tpu.libs import jaxcache  # no jax import

    env = jaxcache.set_env(dict(os.environ))
    results = []
    for name in args.items.split(","):
        t = time.perf_counter()
        res = {"item": name}
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--item", name],
                capture_output=True, text=True, env=env, cwd=REPO,
                timeout=args.box,
            )
            line = next((ln for ln in reversed(p.stdout.splitlines())
                         if ln.startswith("RESULT ")), None)
            if line is not None:
                res.update(json.loads(line[len("RESULT "):]))
            else:
                res["error"] = (p.stderr or p.stdout)[-3000:]
            res["rc"] = p.returncode
        except subprocess.TimeoutExpired:
            res["error"] = f"did not finish in {args.box:.0f} s"
        res["wall_s"] = round(time.perf_counter() - t, 1)
        results.append(res)
        print(json.dumps(res), flush=True)

    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "chip_probe.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(f"\n{'item':8s} {'oracle':>7s} {'first use':>10s} {'compile':>8s} "
          f"{'repeat':>8s}  note")
    for r in results:
        note = r.get("error", "").strip().splitlines()[-1:] or [""]
        print(f"{r['item']:8s} {str(r.get('matches_oracle', '-')):>7s} "
              f"{r.get('first_use_s', '-')!s:>10s} "
              f"{r.get('compile_s', '-')!s:>8s} "
              f"{r.get('repeat_s', '-')!s:>8s}  {note[0][:90]}")


if __name__ == "__main__":
    main()
