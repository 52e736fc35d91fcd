"""Pipeline microbench: where do the ~35ms/batch of non-kernel overhead go?

Measures, on the live TPU:
  t_prep     host prepare_compact (pack + challenges + transposes)
  t_put      host->device transfer of one batch's args
  t_fetch    device->host fetch of the (1, N) verdict
  pipelined  N batches with prep on a feeder thread, args device_put'd
             ahead, deep in-flight queue — the production shape
"""

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

    print(f"backend={jax.default_backend()}", flush=True)
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import pallas_verify as pv

    n = 10240
    entries = []
    for i in range(n):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        msg = i.to_bytes(8, "big") + b"\x08\x02\x10\x01" + b"p" * 100
        entries.append((sk.pub_key().bytes(), msg, sk.sign(msg)))

    f = pv._jitted_pallas_verify(n, pv.BLOCK, False)
    args = pv.prepare_compact(entries, n)
    out = np.asarray(f(*args))  # warm compile
    assert bool(out.all())

    for _ in range(2):
        t0 = time.perf_counter()
        args = pv.prepare_compact(entries, n)
        t_prep = time.perf_counter() - t0

        t0 = time.perf_counter()
        dev_args = [jax.device_put(a) for a in args]
        jax.block_until_ready(dev_args)
        t_put = time.perf_counter() - t0

        o = f(*dev_args)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        _ = np.asarray(o)
        t_fetch = time.perf_counter() - t0
        print(f"prep={t_prep*1e3:.1f}ms put={t_put*1e3:.1f}ms fetch={t_fetch*1e3:.1f}ms", flush=True)

    # dispatch with numpy args (transfer inside dispatch) back-to-back
    for reps in (8,):
        t0 = time.perf_counter()
        outs = [f(*args) for _ in range(reps)]
        jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        print(f"numpy-arg reps={reps}: {dt*1000/reps:.1f} ms/batch "
              f"{reps*n/dt:.0f} sigs/s", flush=True)

    # production shape: feeder thread preps + device_puts, main dispatches
    from concurrent.futures import ThreadPoolExecutor

    def prep_put():
        a = pv.prepare_compact(entries, n)
        return [jax.device_put(x) for x in a]

    for depth in (2, 4):
        n_batches = 12
        with ThreadPoolExecutor(1) as ex:
            t0 = time.perf_counter()
            nxt = ex.submit(prep_put)
            inflight = []
            for i in range(n_batches):
                dev_args = nxt.result()
                if i + 1 < n_batches:
                    nxt = ex.submit(prep_put)
                inflight.append(f(*dev_args))
                if len(inflight) > depth:
                    np.asarray(inflight.pop(0))
            for o in inflight:
                np.asarray(o)
            dt = time.perf_counter() - t0
        print(f"pipelined depth={depth}: {dt*1000/n_batches:.1f} ms/batch "
              f"{n_batches*n/dt:.0f} sigs/s", flush=True)




def main2() -> None:
    import jax
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import pallas_verify as pv

    n = 10240
    entries = []
    for i in range(n):
        sk = ed25519.gen_priv_key(i.to_bytes(32, "little"))
        msg = i.to_bytes(8, "big") + b"\x08\x02\x10\x01" + b"p" * 100
        entries.append((sk.pub_key().bytes(), msg, sk.sign(msg)))
    f = pv._jitted_pallas_verify(n, pv.BLOCK, False)
    args = pv.prepare_compact(entries, n)
    np.asarray(f(*args))  # warm

    from concurrent.futures import ThreadPoolExecutor

    # production shape + async D2H: feeder preps numpy args, main thread
    # dispatches with numpy args (async H2D), starts async copy-to-host,
    # blocks only on batches `depth` behind.
    for depth in (3, 6):
        n_batches = 16
        with ThreadPoolExecutor(1) as ex:
            t0 = time.perf_counter()
            nxt = ex.submit(pv.prepare_compact, entries, n)
            inflight = []
            for i in range(n_batches):
                a = nxt.result()
                if i + 1 < n_batches:
                    nxt = ex.submit(pv.prepare_compact, entries, n)
                o = f(*a)
                try:
                    o.copy_to_host_async()
                except Exception as e:
                    print(f"copy_to_host_async unavailable: {e}")
                inflight.append(o)
                if len(inflight) > depth:
                    assert np.asarray(inflight.pop(0)).all()
            for o in inflight:
                np.asarray(o)
            dt = time.perf_counter() - t0
        print(f"async-d2h depth={depth}: {dt*1000/n_batches:.1f} ms/batch "
              f"{n_batches*n/dt:.0f} sigs/s", flush=True)


if __name__ == "__main__" and os.environ.get("KB2") == "2":
    main2()
elif __name__ == "__main__":
    main()
