"""RLC kernel microbench on the live TPU: time the per-lane fast-accept
pipeline (ops/pallas_rlc.py) at full bucket and compare with the per-sig
kernel's batch time. Development tool — not part of the driver protocol."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.libs import jaxcache  # noqa: E402

jaxcache.set_env(os.environ)

import numpy as np


def main() -> None:
    import jax

    print(f"backend={jax.default_backend()} devices={jax.devices()}", flush=True)
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import pallas_rlc as pr

    n = int(os.environ.get("KB_SIGS", "10240"))
    block = int(os.environ.get("KB_BLOCK", "0")) or pr.BLOCK_LANES
    g = n // pr.M
    entries = []
    for i in range(n):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        msg = i.to_bytes(8, "big") + b"\x08\x02\x10\x01" + b"p" * 100
        entries.append((sk.pub_key().bytes(), msg, sk.sign(msg)))
    t0 = time.perf_counter()
    args = pr.prepare_rlc(entries, n)
    print(f"prep={time.perf_counter()-t0:.3f}s  M={pr.M} lanes={g} block={block}",
          flush=True)

    f = pr._jitted_rlc_verify(g, block, False)
    t0 = time.perf_counter()
    out = np.asarray(f(*args))
    print(f"warm(compile)={time.perf_counter()-t0:.1f}s ok={bool(out.all())}",
          flush=True)
    assert bool(out.all())

    args_dev = [jax.device_put(a) for a in args]
    for reps in (1, 4, 8):
        t0 = time.perf_counter()
        outs = [f(*args_dev) for _ in range(reps)]
        jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        print(f"reps={reps}: {dt*1000/reps:.1f} ms/batch  "
              f"{reps*n/dt:.0f} sigs/s", flush=True)


if __name__ == "__main__":
    main()
