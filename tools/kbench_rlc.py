"""RLC kernel microbench on the live TPU: device time of one launch of the
per-lane fast-accept pipeline (ops/pallas_rlc.py) at each lane width and
batch size asked for, warm-epoch (cached) pipeline by default. What
plan_bucket's width rule rests on: PERF.md §5 holds the table.

    python tools/kbench_rlc.py --sigs 256,512,1024,10240 --widths 2,4,8

Development tool — not part of the driver protocol. One process, one
chip; every (size, width) is a shape of its own and compiles first."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.libs import jaxcache  # noqa: E402

jaxcache.set_env(os.environ)

import numpy as np  # noqa: E402


def signed_block(n: int):
    """(warm EntryBlock of n valid signatures, its epoch entry)."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import epoch_cache
    from tendermint_tpu.ops.entry_block import EntryBlock

    entries = []
    for i in range(n):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        msg = i.to_bytes(8, "big") + b"\x08\x02\x10\x01" + b"p" * 100
        entries.append((sk.pub_key().bytes(), msg, sk.sign(msg)))
    blk = EntryBlock.from_entries(entries)
    blk.val_idx = np.arange(n, dtype=np.int32)
    ep = epoch_cache.EpochEntry(b"kbench".ljust(32, b"-"), blk.pub)
    blk.epoch_key = ep.key
    return blk, ep


def shape_for(n: int, m: int, block: int) -> tuple:
    """(bucket, lanes, block) of n signatures at a FORCED width m: what
    plan_bucket would give if its rule chose m."""
    lanes = -(-n // m)
    block = min(block, 1 << (lanes - 1).bit_length())
    g = -(-lanes // block) * block
    return g * m, g, block


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sigs", default="10240")
    ap.add_argument("--widths", default="2,4,8")
    ap.add_argument("--cold", action="store_true",
                    help="the uncached pipeline (the batch ships its pubs)")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearsal off the chip: interpret mode, tiny "
                         "sizes only, and its times mean nothing")
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax

    from tendermint_tpu.ops import pallas_rlc as pr

    print(f"backend={jax.default_backend()} devices={jax.devices()}",
          flush=True)
    interp = args.interpret
    if not interp and jax.default_backend() != "tpu":
        raise SystemExit("no TPU found: a CPU run times nothing the chip does")
    rows = []
    for n in (int(x) for x in args.sigs.split(",")):
        blk, ep = signed_block(n)
        for m in (int(x) for x in args.widths.split(",")):
            bucket, g, block = shape_for(n, m, pr.BLOCK_LANES)
            if args.cold:
                f = pr._jitted_rlc_verify(m, g, block, interp)
                host_args = pr.prepare_rlc(blk, bucket, m)
            else:
                f = pr.rlc_cached_fn(ep, m, g, block, interp)
                host_args = pr.prepare_rlc_cached(blk, bucket, ep, m)
            t0 = time.perf_counter()
            out = np.asarray(f(*host_args))
            first = time.perf_counter() - t0
            if not out.all():
                raise SystemExit(f"valid signatures rejected at n={n} m={m}")
            dev_args = [jax.device_put(a) for a in host_args]
            jax.block_until_ready(f(*dev_args))
            t0 = time.perf_counter()
            outs = [f(*dev_args) for _ in range(args.reps)]
            jax.block_until_ready(outs)
            ms = (time.perf_counter() - t0) * 1e3 / args.reps
            row = {
                "sigs": n, "m": m, "bucket": bucket, "lanes": g,
                "block": block, "blocks": g // block,
                "pipeline": "cold" if args.cold else "cached",
                "launch_ms": ms, "block_ms": ms / (g // block),
                "us_per_sig": ms * 1e3 / n, "first_call_s": first,
                "chosen": pr.lane_width(n) == m,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
