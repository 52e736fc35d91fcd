#!/usr/bin/env python3
"""Host prep microbenchmark: tuple-list vs columnar EntryBlock commit prep.

Measures the `commit_entries -> prepare_batch` path — the GIL-held host
work between types.verify_commit and the device kernel (round 5 found
it the binding constraint at 8 concurrent commits; not re-measured on
this machine) — for both representations:

  baseline   per-signature (pub32, msg, sig64) tuples: vote_sign_bytes_many
             (one PyBytes per lane), a tuple per signature, b"".join
             re-copies inside prepare_batch (the pre-EntryBlock shape)
  columnar   pipeline.commit_entries -> EntryBlock (one contiguous
             sign-bytes buffer + offset table, (n,32)/(n,64) columns) ->
             prepare_batch consuming the block directly

Runs on the CPU backend with NO device work (prep only). By default the
native module is DISABLED (TM_TPU_NO_NATIVE=1) so the numbers isolate the
representation change itself — the pure-Python fallback path. The gate
is argument parity (ISSUE 2's >= 2x floor was the device-hash prep's and
went with it, ISSUE 30). Pass --native to keep the native module and
measure the fused-call path instead.

Usage:
    JAX_PLATFORMS=cpu python tools/prep_bench.py [--sigs 10000] [--reps 5]
                                                 [--native]
"""

import argparse
import os
import statistics
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TM_TPU_PUREPY_CRYPTO", "1")

if "--native" not in sys.argv:
    os.environ["TM_TPU_NO_NATIVE"] = "1"

TRANSFER_RATIO_GATE = 0.5  # --transfer: warm-epoch H2D vs cold-epoch H2D
TRANSFER_SPEEDUP_GATE = 1.3  # --transfer: cached prep vs the PR-4 prep
OVERLAP_POOL_DEPTH = 2  # --overlap: double-buffered input slots

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_synthetic_commit(n_sigs: int):
    """A 10k-scale commit with structurally-valid random signatures.

    Prep cost does not depend on signature VALIDITY (the same hashes,
    packs and transposes run either way), so the bench skips n_sigs
    actual signing ops (~3 ms each under the pure-Python fallback)."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.wire.canonical import Timestamp

    rng = np.random.RandomState(1234)
    vals = []
    sigs = []
    for i in range(n_sigs):
        pk = ed25519.PubKey(rng.randint(0, 256, 32, dtype=np.uint8).tobytes())
        vals.append(Validator.new(pk, 100))
        sigs.append(
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=pk.address(),
                # distinct nanos per lane: a real commit's timestamps
                # differ, so the sign-bytes composer gets no free cache
                # hits here
                timestamp=Timestamp(seconds=1_700_000_000, nanos=int(i) + 1),
                signature=rng.randint(0, 256, 64, dtype=np.uint8).tobytes(),
            )
        )
    # keep commit.signatures index-aligned with the validator list: build
    # the set WITHOUT the power-sort by address (ValidatorSet.new sorts)
    vset = ValidatorSet(validators=vals, proposer=vals[0])
    block_id = BlockID(
        hash=b"\x11" * 32, part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32)
    )
    commit = Commit(height=42, round=0, block_id=block_id, signatures=sigs)
    return vset, commit


def commit_entries_tuples(chain_id, vals, commit, voting_power_needed):
    """The pre-EntryBlock commit_entries, kept verbatim as the baseline:
    per-lane PyBytes sign-bytes + one Python tuple per signature."""
    idxs = []
    tallied = 0
    for idx, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        idxs.append(idx)
        tallied += vals.validators[idx].voting_power
        if tallied > voting_power_needed:
            break
    sign_bytes = commit.vote_sign_bytes_many(chain_id, idxs)
    return [
        (vals.validators[i].pub_key.bytes(), sb, commit.signatures[i].signature)
        for i, sb in zip(idxs, sign_bytes)
    ]


def run_fused(args) -> int:
    """--fused: the round-6 columnar-from-decode gate. Measures the full
    decode-to-kernel-args path — wire-decoded commit (CommitBlock
    columns) -> fused prep (ops/commit_prep.py) -> XLA kernel
    args — against the PR-2 columnar path (commit_entries_legacy object
    walk + generic pad), enforces bit-identical kernel args and reports
    the speedup."""
    import statistics as stats

    from tendermint_tpu.native import load as _load_native
    from tendermint_tpu.ops import backend, pipeline
    from tendermint_tpu.types.block import Commit

    chain_id = "prep-bench"
    vset, commit = build_synthetic_commit(args.sigs)
    needed = vset.total_voting_power() * 2 // 3
    bucket = backend._bucket_for(args.sigs)
    native = _load_native()
    dec = Commit.decode(commit.encode())
    if dec.commit_block() is None:
        print("  FAIL: decode did not produce a CommitBlock", file=sys.stderr)
        return 2
    print(
        f"prep_bench --fused: n={args.sigs} bucket={bucket} reps={args.reps} "
        f"native={'yes' if native is not None else 'no'} "
        f"backend={os.environ.get('JAX_PLATFORMS', '?')}"
    )

    def fused():
        dec._sb_tpl = None
        blk, _ = pipeline.commit_entries(chain_id, vset, dec, needed)
        return backend.prepare_batch(blk, bucket)

    def pr2():
        commit._sb_tpl = None
        blk, _ = pipeline.commit_entries_legacy(
            chain_id, vset, commit, needed
        )
        return backend.prepare_batch(blk, bucket)

    # interleave reps so machine noise hits both paths equally
    fused()
    pr2()
    t_f, t_p = [], []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        fused()
        t_f.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        pr2()
        t_p.append(time.perf_counter() - t0)
    f_ms = stats.median(t_f) * 1e3
    p_ms = stats.median(t_p) * 1e3
    speedup = p_ms / f_ms if f_ms else float("inf")
    a_f = fused()
    a_p = pr2()
    parity = all(np.array_equal(x, y) for x, y in zip(a_f, a_p))
    print(f"  PR-2 columnar (decode->args): {p_ms:9.2f} ms")
    print(f"  fused columnar-from-decode  : {f_ms:9.2f} ms")
    print(f"  speedup                     : {speedup:9.2f}x")
    print(f"  arg parity                  : {'OK' if parity else 'MISMATCH'}")
    # no speed floor: the 1.3x one was the RAM-block prep's, which went
    # with the device-hash kernels (ISSUE 30); arg parity is the gate
    return 0 if parity else 2


def run_transfer(args) -> int:
    """--transfer: the round-7 epoch-cache gate. A validator set seen for
    the SECOND time is device-resident (ops/epoch_cache.py), so a warm
    commit ships only per-signature data — this gate asserts, on the XLA
    preps:

      bytes    steady-state (warm) H2D bytes <= TRANSFER_RATIO_GATE x the
               cold-epoch bytes (the uncached batch args PLUS the one-time
               epoch table upload)
      no pubs  the warm host-hash args carry NO pubkey-derived arrays —
               exactly gather indices + raw r/s/k rows + s<L flags
      speed    warm host prep >= TRANSFER_SPEEDUP_GATE x faster than the
               PR-4 prep of the same batch (interleaved min-of-reps)
    """
    import statistics as stats

    os.environ.setdefault("TM_TPU_EPOCH_CACHE", "8")
    from tendermint_tpu.ops import backend, epoch_cache, pipeline
    from tendermint_tpu.types.block import Commit

    chain_id = "prep-bench"
    vset, commit = build_synthetic_commit(args.sigs)
    needed = vset.total_voting_power() * 2 // 3
    bucket = backend._bucket_for(args.sigs)
    dec = Commit.decode(commit.encode())
    epoch_cache.reset()
    if epoch_cache.cache() is None:
        print("  FAIL: epoch cache disabled (TM_TPU_EPOCH_CACHE=0?)",
              file=sys.stderr)
        return 2
    # first sight: cold epoch — the commit rides the uncached path while
    # the table registers
    blk_cold, _ = pipeline.commit_entries(chain_id, vset, dec, needed)
    if blk_cold.epoch_key is not None:
        print("  FAIL: first-sight commit unexpectedly warm", file=sys.stderr)
        return 2
    blk, _ = pipeline.commit_entries(chain_id, vset, dec, needed)
    ep = epoch_cache.lookup(blk)
    if ep is None:
        print("  FAIL: second-sight commit not warm", file=sys.stderr)
        return 2
    print(
        f"prep_bench --transfer: n={args.sigs} bucket={bucket} "
        f"reps={args.reps} vp={ep.vp} "
        f"backend={os.environ.get('JAX_PLATFORMS', '?')}"
    )

    rc = 0
    table_b = ep.nbytes_host()
    for name, uncached, cached in (
        (
            "host-hash",
            lambda b=blk_cold: backend.prepare_batch(b, bucket),
            lambda: backend.prepare_batch_cached(blk, bucket, ep),
        ),
    ):
        cold_b = backend.h2d_arg_bytes(uncached()) + table_b
        warm_args = cached()
        warm_b = backend.h2d_arg_bytes(warm_args)
        ratio = warm_b / cold_b
        # interleaved min-of-reps (this box's allocator noise drifts
        # medians +-30%)
        uncached(); cached()
        t_u, t_c = [], []
        for _ in range(args.reps):
            t0 = time.perf_counter(); uncached()
            t_u.append(time.perf_counter() - t0)
            t0 = time.perf_counter(); cached()
            t_c.append(time.perf_counter() - t0)
        u_ms, c_ms = min(t_u) * 1e3, min(t_c) * 1e3
        speedup = u_ms / c_ms if c_ms else float("inf")
        print(f"  {name}:")
        print(f"    cold-epoch H2D (args+table): {cold_b:>10} B")
        print(f"    warm-epoch H2D (args only) : {warm_b:>10} B")
        print(f"    warm/cold ratio            : {ratio:10.3f}")
        print(f"    PR-4 prep                  : {u_ms:8.2f} ms")
        print(f"    cached prep                : {c_ms:8.2f} ms")
        print(f"    speedup                    : {speedup:8.2f}x")
        if ratio > TRANSFER_RATIO_GATE:
            print(
                f"  FAIL: warm H2D > {TRANSFER_RATIO_GATE}x cold on {name}",
                file=sys.stderr,
            )
            rc = 1
        if speedup < TRANSFER_SPEEDUP_GATE:
            print(
                f"  FAIL: cached prep < {TRANSFER_SPEEDUP_GATE}x faster "
                f"on {name}",
                file=sys.stderr,
            )
            rc = 1
    # structural "no pubkey bytes": the warm host-hash args are exactly
    # idx(4) + r(32) + s(32) + k(32) bytes per lane + the s<L flags
    idx, r_rows, s_rows, k_rows, s_ok = backend.prepare_batch_cached(
        blk, bucket, ep
    )
    expected = bucket * (4 + 32 + 32 + 32) + s_ok.nbytes
    got = backend.h2d_arg_bytes((idx, r_rows, s_rows, k_rows, s_ok))
    if got != expected:
        print(
            f"  FAIL: warm host-hash args ship {got} B, expected {expected} "
            "(pubkey-derived array leaked into the warm path?)",
            file=sys.stderr,
        )
        rc = 2
    else:
        print(f"  warm host-hash args structurally pub-free: {got} B")
    return rc


def run_overlap(args) -> int:
    """--overlap: the round-8 overlapped-device gate, an on-CPU proxy for
    the transfer/compute pipelining ISSUE 7 adds to the dispatcher.

    The device is mocked SLOW on the readback side only (a proxy result
    whose materialization sleeps ~150 ms — the resolver blocks exactly
    like a TPU's D2H wait), so the dispatcher's loop
    structure is what decides whether batch k+1's H2D transfer is issued
    while batch k computes. Asserts, over a stream of single-job batches
    at depth 1:

      split    every batch's `pipeline.transfer` span closes before its
               `pipeline.dispatch` span opens (transfer split from launch)
      overlap  transfer k+1 is issued BEFORE batch k resolves (span-order
               check transfer[k+1].start < device_wait[k].end, and the
               dispatcher's own hidden=1 marking agrees) — the serial
               prep->transfer->launch->wait loop this PR removed fails
               this deterministically
      pool     steady-state allocations are FLAT: the buffer pool mints
               at most OVERLAP_POOL_DEPTH slots for the whole stream
               (misses == depth, every later acquire is a recycled hit)
               and leaks nothing (in_flight == 0 once drained)
      owner    transfers and launches all ran on ONE thread (the device
               single-owner invariant extends to the transfer stage)
    """
    import numpy as np

    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import backend, pipeline as pl

    n = 96
    n_batches = 6
    resolve_delay = 0.15

    rng = np.random.RandomState(7)

    def batch(tag: int):
        # structurally-valid random entries: the overlap timing being
        # gated does not depend on signature validity
        return [
            (
                rng.randint(0, 256, 32, dtype=np.uint8).tobytes(),
                b"overlap-%d-%d" % (tag, i),
                rng.randint(0, 256, 64, dtype=np.uint8).tobytes(),
            )
            for i in range(n)
        ]

    # one submitted job == one device batch (the coalescer would fuse
    # the whole stream into a single launch otherwise); the slow-readback
    # mock is shared with tests/test_overlap.py (ops/_testing.py)
    from tendermint_tpu.ops._testing import drain_pool, slow_prepare

    backend.max_coalesce = lambda: n
    pl.AsyncBatchVerifier._prepare = staticmethod(
        slow_prepare(pl.AsyncBatchVerifier._prepare, resolve_delay)
    )

    tr.TRACER.clear()
    tr.configure(enabled=True)
    v = pl.AsyncBatchVerifier(depth=1, pool_depth=OVERLAP_POOL_DEPTH)
    try:
        v.submit(batch(99)).result(timeout=600)  # warm: compile the shape
        futs = [v.submit(batch(t)) for t in range(n_batches)]
        for f in futs:
            f.result(timeout=600)
        # the resolver completes futures BEFORE releasing the slot —
        # drain so the leak check does not race the last release
        drain_pool(v._pool)
        pool = v._pool.stats()
    finally:
        tr.configure(enabled=False)
        v.close()

    evs = {"pipeline.transfer": [], "pipeline.dispatch": [],
           "pipeline.device_wait": []}
    tids = set()
    for name, start, end, tid, sargs in tr.TRACER.events():
        if name in evs:
            evs[name].append((start, end, sargs or {}))
        if name in ("pipeline.transfer", "pipeline.dispatch"):
            tids.add(tid)
    for k in evs:
        evs[k].sort()
    xfers = evs["pipeline.transfer"][1:]        # drop the warmup batch
    dispatches = evs["pipeline.dispatch"][1:]
    waits = evs["pipeline.device_wait"][1:]

    print(
        f"prep_bench --overlap: n={n} batches={n_batches} depth=1 "
        f"pool_depth={OVERLAP_POOL_DEPTH} resolve_delay={resolve_delay}s"
    )
    rc = 0
    if not (len(xfers) == len(dispatches) == len(waits) == n_batches):
        print(
            f"  FAIL: expected {n_batches} transfer/dispatch/wait span "
            f"triples, got {len(xfers)}/{len(dispatches)}/{len(waits)}",
            file=sys.stderr,
        )
        return 2
    split_ok = all(x[1] <= d[0] for x, d in zip(xfers, dispatches))
    overlapped = sum(
        1 for i in range(1, n_batches) if xfers[i][0] < waits[i - 1][1]
    )
    hidden = sum(1 for x in xfers if x[2].get("hidden"))
    print(f"  transfer-before-launch split : {'OK' if split_ok else 'BROKEN'}")
    print(f"  transfer k+1 < resolve k     : {overlapped}/{n_batches - 1}")
    print(f"  dispatcher-marked hidden     : {hidden}/{n_batches}")
    print(f"  pool                         : {pool}")
    print(f"  transfer+dispatch threads    : {len(tids)}")
    if not split_ok:
        print("  FAIL: a transfer span closed after its launch span opened",
              file=sys.stderr)
        rc = 1
    if overlapped < n_batches - 2:
        print(
            f"  FAIL: only {overlapped}/{n_batches - 1} transfers were "
            "issued before the previous batch resolved (dispatcher is "
            "serial again?)",
            file=sys.stderr,
        )
        rc = 1
    if hidden < n_batches - 1:
        print(
            f"  FAIL: dispatcher marked only {hidden}/{n_batches} "
            "transfers hidden behind in-flight compute",
            file=sys.stderr,
        )
        rc = 1
    if pool["minted"] > OVERLAP_POOL_DEPTH:
        print(
            f"  FAIL: pool minted {pool['minted']} slots for one layout "
            f"(> depth {OVERLAP_POOL_DEPTH}) — steady-state allocations "
            "are not flat",
            file=sys.stderr,
        )
        rc = 1
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1
    if len(tids) != 1:
        print(
            f"  FAIL: transfers/launches ran on {len(tids)} threads "
            "(single device owner violated)",
            file=sys.stderr,
        )
        rc = 1
    return rc


def run_mesh(args) -> int:
    """--mesh: the round-9 mesh-dispatcher gate, on a mocked 2-lane mesh
    (this box has one device; lane packing + demux is exactly the
    machinery that must be right WITHOUT mesh hardware). The kernel runs
    for real — verdicts are live — behind a slow-readback mock so the
    overlap stages engage like a attached mesh. Asserts:

      pack     deterministic plan shapes: 3 full jobs over a 4-lane plan
               leave one PURE identity-padding lane; per-lane single-
               epoch packing holds; spans tile the live rows exactly
      parity   every job's mesh-packed verdict row is bit-identical to
               the single-device path's (backend.verify_batch), and the
               blame index (first invalid lane) of a tampered job
               survives the demux
      pool     zero slot leak once drained (in_flight == 0)
      owner    transfers and launches all ran on ONE thread — the device
               single-owner invariant extends to the mesh superbatch
      overlap  superbatch k+1's transfer is issued before batch k
               resolves (the ISSUE 7 machinery generalized to lane-
               packed launches)
      gauges   mesh_lane_occupancy + mesh_pad_waste_ratio published and
               complementary
    """
    import numpy as np

    from tendermint_tpu.libs.metrics import ops_stats

    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import backend, mesh as ms, pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, slow_mesh_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock

    os.environ["TM_TPU_MESH_LANE_BUCKET"] = "128"
    resolve_delay = 0.15
    rng = np.random.RandomState(11)

    def rand_batch(n, tag):
        """Structurally-valid random entries — pack/plan checks only."""
        return EntryBlock.from_entries([
            (
                rng.randint(0, 256, 32, dtype=np.uint8).tobytes(),
                b"mesh-%d-%d" % (tag, i),
                rng.randint(0, 256, 64, dtype=np.uint8).tobytes(),
            )
            for i in range(n)
        ])

    from tendermint_tpu.crypto import ed25519

    def signed_batch(n, tag, bad=()):
        """REAL signatures (parity and blame must see live verdicts),
        with `bad` lane indices tampered."""
        out = []
        for i in range(n):
            sk = ed25519.gen_priv_key(
                (tag * 1000 + i + 1).to_bytes(32, "little")
            )
            m = b"mesh-%d-%d" % (tag, i)
            sig = sk.sign(m) if i not in bad else b"\x07" * 64
            out.append((sk.pub_key().bytes(), m, sig))
        return EntryBlock.from_entries(out)

    print("prep_bench --mesh: lanes=2 lane_bucket=128 "
          f"resolve_delay={resolve_delay}s")
    rc = 0

    # -- pack determinism (no kernel): pure-pad lane + span tiling ------
    class _J:
        def __init__(self, blk):
            self.entries = blk

    plan, held = ms.pack_jobs(
        [_J(rand_batch(128, 90)), _J(rand_batch(128, 91)),
         _J(rand_batch(128, 92))], 4, 128
    )
    block, spans = ms.build_superblock(plan)
    pure_pad = plan.n_lanes - len(plan.lanes)
    rows = np.zeros(plan.bucket, dtype=bool)
    for _, off, n in spans:
        if rows[off:off + n].any():
            print("  FAIL: demux spans overlap", file=sys.stderr)
            rc = 1
        rows[off:off + n] = True
    pad_rows = block.pub[plan.live:]
    pad_ok = bool(
        (pad_rows[:, 0] == 1).all() and (pad_rows[:, 1:] == 0).all()
    )
    print(f"  plan: lanes={plan.n_lanes} (pure-pad={pure_pad}) "
          f"live={plan.live} pad={plan.pad} span_rows={int(rows.sum())} "
          f"identity_pad={'OK' if pad_ok else 'BROKEN'}")
    if held or pure_pad != 1 or int(rows.sum()) != plan.live or not pad_ok:
        print("  FAIL: 3 full jobs over 4 lanes must pack 3 live lanes + "
              "1 pure identity-pad lane with exact span tiling",
              file=sys.stderr)
        rc = 1

    # -- live pipeline: parity / blame / pool / owner / overlap ---------
    # job 3 carries one tampered lane (row 17) so the demuxed blame
    # index is checkable against live verdicts
    jobs = [
        signed_batch(n, t, bad=(17,) if t == 3 else ())
        for t, n in enumerate((96, 31, 5, 128, 64, 7))
    ]
    pl.AsyncBatchVerifier._prepare_mesh = staticmethod(
        slow_mesh_prepare(pl.AsyncBatchVerifier._prepare_mesh,
                          resolve_delay)
    )
    tr.TRACER.clear()
    tr.configure(enabled=True)
    v = pl.AsyncBatchVerifier(depth=1, pool_depth=OVERLAP_POOL_DEPTH,
                              mesh_lanes=2)
    try:
        v.submit(jobs[0][0:16]).result(timeout=600)  # warm: compile
        futs = [v.submit(j) for j in jobs]
        res = [np.asarray(f.result(timeout=600)) for f in futs]
        drain_pool(v._pool)
        pool = v._pool.stats()
        stats = ops_stats()
    finally:
        tr.configure(enabled=False)
        v.close()

    mism = None
    for i, (j, r) in enumerate(zip(jobs, res)):
        want = backend.verify_batch(j)
        if not np.array_equal(r, np.asarray(want)):
            mism = i
    # live-verdict blame: ONLY job 3's row 17 fails across the pack
    blame_ok = bool(
        not res[3][17] and res[3].sum() == len(res[3]) - 1
        and all(r.all() for i, r in enumerate(res) if i != 3)
    )
    print(f"  verdict parity vs single-device: "
          f"{'OK' if mism is None else f'MISMATCH job {mism}'}")
    print(f"  tampered-lane blame demux       : "
          f"{'OK' if blame_ok else 'LOST'}")
    if mism is not None or not blame_ok:
        rc = 1

    evs = {"pipeline.transfer": [], "pipeline.dispatch": [],
           "pipeline.device_wait": []}
    tids = set()
    for name, start, end, tid, sargs in tr.TRACER.events():
        if name in evs:
            evs[name].append((start, end, sargs or {}))
        if name in ("pipeline.transfer", "pipeline.dispatch"):
            tids.add(tid)
    for k in evs:
        evs[k].sort()
    xfers = evs["pipeline.transfer"]
    waits = evs["pipeline.device_wait"]
    nb = len(xfers)
    overlapped = sum(
        1 for i in range(1, min(nb, len(waits)))
        if xfers[i][0] < waits[i - 1][1]
    )
    print(f"  superbatches launched           : {nb}")
    print(f"  transfer k+1 < resolve k        : {overlapped}/{max(nb-1, 0)}")
    print(f"  transfer+dispatch threads       : {len(tids)}")
    print(f"  pool                            : {pool}")
    print(f"  mesh_lane_occupancy={stats['mesh_lane_occupancy']:.4f} "
          f"mesh_pad_waste_ratio={stats['mesh_pad_waste_ratio']:.4f}")
    if nb < 2:
        print("  FAIL: expected >= 2 superbatch launches", file=sys.stderr)
        rc = 2
    elif overlapped < 1:
        print("  FAIL: no superbatch transfer overlapped the previous "
              "batch's resolve (mesh dispatcher is serial?)",
              file=sys.stderr)
        rc = 1
    if len(tids) != 1:
        print(f"  FAIL: transfers/launches ran on {len(tids)} threads "
              "(single device owner violated)", file=sys.stderr)
        rc = 1
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1
    occ = stats["mesh_lane_occupancy"]
    padr = stats["mesh_pad_waste_ratio"]
    if not (0.0 < occ <= 1.0) or abs((occ + padr) - 1.0) > 1e-9:
        print(f"  FAIL: occupancy {occ} + pad waste {padr} must be "
              "complementary and published", file=sys.stderr)
        rc = 1
    return rc


def run_schemes(args) -> int:
    """--schemes: the ISSUE 19 scheme-lane gate. A mixed
    ed25519+secp256k1 committee must verify in ONE superbatch launch
    with verdicts AND blame byte-identical to the sequential reference
    walk. Every kernel runs REAL (live verdicts) — correctness is the
    gate here; throughput is `bench.py schemes` (SCHEMES_r*.json).
    Asserts:

      split    prepare_commit_scheme_split partitions a mixed commit
               into per-scheme EntryBlocks (ed25519 first), covering
               every counted signature exactly once
      pack     the mesh packer takes both blocks into ONE plan whose
               superblock is a SchemeSuperBlock with contiguous
               per-scheme segments in plan.schemes() order
      launch   prepare_superbatch hands back ONE launch fn; a single
               call verifies every lane — one device launch for a
               mixed-scheme commit (the mixed-commit acceptance)
      parity   demuxed per-job verdict rows are bit-identical to the
               single-scheme device path (backend.verify_batch), on
               the direct drive AND through the pipeline mesh worker
      blame    a tampered secp256k1 signature raises from conclude()
               with the EXACT error string of the sequential
               _verify_commit_single walk; same for a tampered
               ed25519 signature
      lanes    the secp device verdict row equals the host
               per-signature loop bit-for-bit, including a
               non-lower-S rejection
    """
    os.environ["TM_TPU_MESH_LANE_BUCKET"] = "16"

    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.crypto import secp256k1 as _secp
    from tendermint_tpu.ops import backend, device_pool as dp, mesh as ms
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool
    from tendermint_tpu.types import (
        BlockID,
        PartSetHeader,
        Timestamp,
        Validator,
        ValidatorSet,
        Vote,
        VoteSet,
    )
    from tendermint_tpu.types.block import CommitSig
    from tendermint_tpu.types.vote import PRECOMMIT_TYPE
    from tendermint_tpu.types import validation as V

    chain_id = "schemes-gate"
    n_vals = 12
    print(f"prep_bench --schemes: vals={n_vals} (mixed ed25519+secp256k1) "
          "lane_bucket=16")
    rc = 0

    def build_commit(tag):
        """A mixed committee (every 3rd validator ed25519, the rest
        secp256k1) with REAL signatures — blame must see live verdicts."""
        pairs = []
        for i in range(n_vals):
            seed = (tag * 4096 + i + 1).to_bytes(32, "big")
            sk = (_ed.gen_priv_key(seed) if i % 3 == 0
                  else _secp.PrivKey(seed))
            pairs.append((sk, Validator.new(sk.pub_key(), 100)))
        vset = ValidatorSet.new([v for _, v in pairs])
        by_addr = {v.address: sk for sk, v in pairs}
        sks = [by_addr[v.address] for v in vset.validators]
        bid = BlockID(hash=b"\x05" * 32,
                      part_set_header=PartSetHeader(total=1, hash=b"\x05" * 32))
        vs = VoteSet(chain_id, 7, 0, PRECOMMIT_TYPE, vset)
        for i, sk in enumerate(sks):
            vote = Vote(type=PRECOMMIT_TYPE, height=7, round=0, block_id=bid,
                        timestamp=Timestamp(seconds=1_600_000_000, nanos=0),
                        validator_address=vset.validators[i].address,
                        validator_index=i)
            sig = sk.sign(vote.sign_bytes(chain_id))
            vs.add_vote(Vote(**{**vote.__dict__, "signature": sig}))
        return vset, vs.make_commit()

    def tamper(commit, i):
        cs = commit.signatures[i]
        bad = bytearray(cs.signature)
        bad[9] ^= 0x3C
        commit.signatures[i] = CommitSig(
            block_id_flag=cs.block_id_flag,
            validator_address=cs.validator_address,
            timestamp=cs.timestamp, signature=bytes(bad))

    def seq_error(vset, commit):
        try:
            V._verify_commit_single(
                chain_id, vset, commit, vset.total_voting_power() * 2 // 3,
                V._ignore_not_for_block, V._count_all, False, True)
            return None
        except ValueError as e:
            return str(e)

    class _J:
        def __init__(self, blk):
            self.entries = blk

    def one_launch(blocks):
        """The acceptance drive: both scheme blocks through the
        PRODUCTION pack/build/prep path, verified by a SINGLE call of
        the one launch fn prepare_superbatch returns."""
        jobs = [_J(b) for b in blocks]
        plan, held = ms.pack_jobs(jobs, len(jobs))
        assert not held, "scheme blocks must pack into one plan"
        block, spans = ms.build_superblock(plan)
        res = ms.prepare_superbatch(block, plan)
        f, fargs = res[0], res[1]
        shardings = res[4] if len(res) > 4 else None
        arr = np.asarray(f(*dp.transfer(fargs, shardings=shardings)))
        if arr.ndim == 2:
            arr = arr[0]
        arr = arr.astype(bool)
        by_job = {id(j): (off, n) for j, off, n in spans}
        outs = []
        for j in jobs:
            off, n = by_job[id(j)]
            outs.append(arr[off:off + n])
        return plan, block, outs

    # -- split + pack + ONE launch + verdict parity (good commit) -------
    vset, commit = build_commit(1)
    blocks, conclude = V.prepare_commit_scheme_split(
        chain_id, vset, commit, vset.total_voting_power() * 2 // 3)
    schemes = [b.scheme for b in blocks]
    covered = sum(len(b) for b in blocks)
    # equal powers: the selection walk stops at the first signature that
    # crosses 2/3 of total power, exactly like _verify_commit_single
    want_rows = (vset.total_voting_power() * 2 // 3) // 100 + 1
    print(f"  split: blocks={schemes} rows={[len(b) for b in blocks]} "
          f"(threshold walk selects {want_rows})")
    if schemes != ["ed25519", "secp256k1"] or covered != want_rows:
        print("  FAIL: mixed commit must split into ed25519+secp256k1 "
              "blocks covering every counted signature exactly once",
              file=sys.stderr)
        rc = 1
    plan, sblock, outs = one_launch(blocks)
    is_super = isinstance(sblock, ms.SchemeSuperBlock)
    parts = [s for s, _, _ in sblock.parts] if is_super else []
    print(f"  pack : superblock={'SchemeSuperBlock' if is_super else type(sblock).__name__} "
          f"parts={parts} schemes={plan.schemes()}")
    if not is_super or parts != plan.schemes():
        print("  FAIL: mixed plan must build a SchemeSuperBlock with "
              "per-scheme segments in plan order", file=sys.stderr)
        rc = 1
    print("  launch: 1 (single fn call covered all "
          f"{plan.bucket} rows, {plan.live} live)")
    mism = None
    for i, (b, got) in enumerate(zip(blocks, outs)):
        want = np.asarray(backend.verify_batch(b))
        if not np.array_equal(got, want):
            mism = i
    print(f"  parity vs single-scheme device  : "
          f"{'OK' if mism is None else f'MISMATCH block {mism}'}")
    if mism is not None:
        rc = 1
    try:
        conclude(np.concatenate(outs))
        print("  good commit verdict             : OK (verified)")
    except ValueError as e:
        print(f"  FAIL: good mixed commit rejected: {e}", file=sys.stderr)
        rc = 1

    # -- blame parity: tampered secp sig, then tampered ed sig ----------
    for label, bad_i in (("secp256k1", 1), ("ed25519", 0)):
        vset, commit = build_commit(2)
        # pick a commit index of the wanted scheme
        kinds, _, _ = vset.scheme_rows()
        want_kind = 1 if label == "secp256k1" else 0
        idx = int(np.nonzero(kinds == want_kind)[0][bad_i])
        tamper(commit, idx)
        want_err = seq_error(vset, commit)
        blocks, conclude = V.prepare_commit_scheme_split(
            chain_id, vset, commit, vset.total_voting_power() * 2 // 3)
        _, _, outs = one_launch(blocks)
        try:
            conclude(np.concatenate(outs))
            got_err = None
        except ValueError as e:
            got_err = str(e)
        ok = want_err is not None and got_err == want_err
        print(f"  blame parity ({label:9s})      : "
              f"{'OK' if ok else 'MISMATCH'}")
        if not ok:
            print(f"  FAIL: sequential={want_err!r} batched={got_err!r}",
                  file=sys.stderr)
            rc = 1

    # -- pipeline mesh worker: same verdicts through the async path -----
    vset, commit = build_commit(1)
    blocks, conclude = V.prepare_commit_scheme_split(
        chain_id, vset, commit, vset.total_voting_power() * 2 // 3)
    v = pl.AsyncBatchVerifier(depth=2, mesh_lanes=2)
    try:
        futs = [v.submit(b) for b in blocks]
        res = [np.asarray(f.result(timeout=600)) for f in futs]
        drain_pool(v._pool)
        pool = v._pool.stats()
    finally:
        v.close()
    pipe_ok = all(
        np.array_equal(r, np.asarray(backend.verify_batch(b)))
        for b, r in zip(blocks, res)
    )
    print(f"  pipeline mesh worker parity     : "
          f"{'OK' if pipe_ok else 'MISMATCH'}")
    print(f"  pool                            : {pool}")
    if not pipe_ok:
        rc = 1
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1

    # -- secp lane: device row == host per-signature loop ---------------
    n_lane = 16
    lane = []
    for i in range(n_lane):
        sk = _secp.PrivKey((7000 + i).to_bytes(32, "big"))
        m = b"lane-%d" % i
        lane.append((sk.pub_key(), m, sk.sign(m)))
    # one tampered, one non-lower-S (upper-S re-encoding of a valid sig)
    pk3, m3, s3 = lane[3]
    lane[3] = (pk3, m3, s3[:32] + s3[32:][::-1])
    pk5, m5, s5 = lane[5]
    s_hi = int.from_bytes(s5[32:], "big")
    n_order = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
    lane[5] = (pk5, m5, s5[:32] + (n_order - s_hi).to_bytes(32, "big"))
    host = np.asarray([pk.verify_signature(m, s) for pk, m, s in lane])
    dev = np.asarray(backend.verify_batch_secp(
        [(pk.bytes(), m, s) for pk, m, s in lane]))
    lane_ok = (np.array_equal(host, dev) and not dev[3] and not dev[5]
               and dev.sum() == n_lane - 2)
    print(f"  secp device vs host lane        : "
          f"{'OK' if lane_ok else 'MISMATCH'} "
          f"(rejected {n_lane - int(dev.sum())}/{n_lane}: tampered + "
          "non-lower-S)")
    if not lane_ok:
        rc = 1
    return rc


def run_bls(args) -> int:
    """--bls: the ISSUE 20 aggregation-lane gate. K aggregated commits
    (ONE BLS signature + a signer bitmap each) must verify in a single
    fused multi-pairing launch with verdicts AND blame byte-identical
    to the pure-Python reference walk (crypto/bls12381.py). Every
    kernel runs REAL — correctness is the gate; throughput is
    `bench.py bls` (AGG_r*.json). Asserts:

      wire     AggregatedCommit proto roundtrip, and the commit ships
               96 sig bytes + ceil(V/8) bitmap bytes instead of V
               per-signature rows
      codes    the fused K=4 launch (good / forged / non-subgroup sig /
               non-subgroup pubkey) returns exactly the verdict codes
               the host prep + kernel contract pins — including a
               CRAFTED on-curve-but-out-of-subgroup G2 signature and
               G1 pubkey (rejecting those is what makes apk
               aggregation sound)
      blame    conclude() raises the EXACT string of the sequential
               verify_aggregated_commit walk for every row — pairing
               failure, subgroup sig, subgroup pubkey (validator #i),
               and the pre-crypto wrong-bitmap-size reject
      lanes    an ed25519 + secp256k1 + bls12381 three-lane superbatch
               builds ONE SchemeSuperBlock (BLS segment at its
               quantized width 4, NOT the per-sig lane bucket) and a
               single launch fn call verifies all three lanes; the
               async pipeline fuses the same three submissions into
               ONE dispatch (launch count from the tracer)
      no leak  zero buffer-pool slots in flight once drained
    """
    os.environ["TM_TPU_MESH_LANE_BUCKET"] = "16"

    from tendermint_tpu.crypto import bls12381 as bls
    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.crypto import secp256k1 as _secp
    from tendermint_tpu.libs.bits import BitArray
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import backend, device_pool as dp, mesh as ms
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool
    from tendermint_tpu.ops.entry_block import EntryBlock
    from tendermint_tpu.types import BlockID, PartSetHeader, Validator, ValidatorSet
    from tendermint_tpu.types import validation as V
    from tendermint_tpu.types.block import AggregatedCommit
    from tendermint_tpu.types.validation import ErrInvalidCommitSignatures

    chain_id = "bls-gate"
    rc = 0

    # -- craft on-curve, out-of-subgroup points (the subgroup rows) -----
    def bad_g1():
        x = 1
        while True:
            y2 = (x * x * x + bls.B) % bls.P
            y = bls.fp_sqrt(y2)
            if y is not None and not bls.g1_in_subgroup((x, y)):
                return bls.g1_compress((x, y))
            x += 1

    def bad_g2():
        c = 1
        while True:
            x = (c, 0)
            y2 = bls.f2_add(bls.f2_mul(x, bls.f2_sqr(x)),
                            bls.f2_scalar(bls.XI, bls.B))
            y = bls.f2_sqrt(y2)
            if y is not None and not bls.g2_in_subgroup((x, y)):
                return bls.g2_compress((x, y))
            c += 1

    rogue_pub, rogue_sig = bad_g1(), bad_g2()
    st_pub = bls.pubkey_status(rogue_pub)[1]
    st_sig = bls.signature_status(rogue_sig)[1]
    print(f"prep_bench --bls: crafted subgroup violations "
          f"(pub={st_pub}, sig={st_sig}) lane_bucket=16")
    if st_pub != "subgroup" or st_sig != "subgroup":
        print("  FAIL: crafted points must decompress on-curve but fail "
              "the subgroup check", file=sys.stderr)
        return 1

    # -- committee: 7 real signers + 1 rogue (non-subgroup) pubkey ------
    n_vals = 8
    sks = [bls.PrivKey((i + 1).to_bytes(32, "big")) for i in range(7)]
    vals = [Validator.new(sk.pub_key(), 100) for sk in sks]
    vals.append(Validator.new(bls.PubKey(rogue_pub), 100))
    vset = ValidatorSet.new(vals)
    by_addr = {sk.pub_key().address(): sk for sk in sks}
    order = [by_addr.get(v.address) for v in vset.validators]
    rogue_idx = order.index(None)
    real = [i for i in range(n_vals) if i != rogue_idx]
    bid = BlockID(hash=b"\x14" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x15" * 32))

    def make_agg(signers, forge=False, sig=None):
        ba = BitArray(n_vals)
        for i in signers:
            ba.set_index(i, True)
        agg = AggregatedCommit(height=9, round=0, block_id=bid, signers=ba)
        if sig is not None:
            agg.signature = sig
            return agg
        msg = agg.sign_bytes(chain_id)
        parts = [order[i].sign(msg) for i in signers if order[i] is not None]
        if forge:
            parts[-1] = order[signers[-1]].sign(b"not-the-vote")
        agg.signature = bls.aggregate(parts)
        return agg

    def seq_error(agg):
        try:
            V.verify_aggregated_commit(chain_id, vset, bid, 9, agg)
            return None
        except ValueError as e:
            return str(e)

    # -- wire: proto roundtrip + aggregated footprint -------------------
    good = make_agg(real[:6])
    dec = AggregatedCommit.decode(good.encode())
    wire = len(good.encode())
    print(f"  wire : roundtrip={'OK' if dec == good else 'FAIL'} "
          f"bytes={wire} (96B sig + {(n_vals + 7) // 8}B bitmap, "
          f"not {n_vals} per-sig rows)")
    if dec != good:
        rc = 1

    # -- fused K=4 launch: codes + blame parity -------------------------
    _epoch.reset(8)
    _epoch.note_valset(vset)
    _epoch.note_valset(vset)
    aggs = [
        good,                                       # valid
        make_agg(real[:6], forge=True),             # pairing failure
        make_agg(real[:6], sig=rogue_sig),          # non-subgroup sig
        make_agg(real[:5] + [rogue_idx]),           # non-subgroup pubkey
    ]
    want_errs = [seq_error(a) for a in aggs]
    pairs = [V.prepare_aggregated_commit(chain_id, vset, bid, 9, a, k_hint=4)
             for a in aggs]
    fused = ms.block_concat([blk for blk, _ in pairs])
    t0 = time.perf_counter()
    codes = np.asarray(backend.verify_batch_bls_codes(fused))
    dt = time.perf_counter() - t0
    from tendermint_tpu.ops import bls_verify as bv
    want_codes = [bv.CODE_VALID, bv.CODE_PAIRING, bv.CODE_SIG["subgroup"],
                  bv.CODE_PUB_BASE + rogue_idx]
    print(f"  codes: {codes.tolist()} want={want_codes} "
          f"(K=4 fused, {dt:.1f}s)")
    if codes.tolist() != want_codes:
        print("  FAIL: fused launch verdict codes diverge from the "
              "host-prep/kernel contract", file=sys.stderr)
        rc = 1
    mism = []
    for j, ((_, conc), want) in enumerate(zip(pairs, want_errs)):
        try:
            conc(codes[j:j + 1])
            got = None
        except ValueError as e:
            got = str(e)
        if got != want:
            mism.append((j, want, got))
    print(f"  blame: {'OK (4/4 byte-identical)' if not mism else 'MISMATCH'}")
    for j, want, got in mism:
        print(f"  FAIL row {j}: sequential={want!r} batched={got!r}",
              file=sys.stderr)
        rc = 1

    # -- wrong bitmap size: pre-crypto reject, parity, zero launches ----
    short = make_agg(real[:6])
    short.signers = BitArray(n_vals + 3)
    for i in real[:6]:
        short.signers.set_index(i, True)
    errs = []
    for fn in (lambda: V.verify_aggregated_commit(chain_id, vset, bid, 9, short),
               lambda: V.prepare_aggregated_commit(chain_id, vset, bid, 9,
                                                   short, k_hint=4)):
        try:
            fn()
            errs.append(None)
        except ErrInvalidCommitSignatures as e:
            errs.append(str(e))
    bitmap_ok = errs[0] is not None and errs[0] == errs[1]
    print(f"  bitmap: {'OK' if bitmap_ok else 'FAIL'} "
          f"(both paths: {errs[0]!r})")
    if not bitmap_ok:
        rc = 1

    # -- three-lane superbatch: one plan, one launch fn call ------------
    def ed_block(n, bad=()):
        rows = []
        for i in range(n):
            sk = _ed.gen_priv_key((5000 + i).to_bytes(32, "little"))
            m = b"agg-ed-%d" % i
            rows.append((sk.pub_key().bytes(), m,
                         sk.sign(m) if i not in bad else b"\x07" * 64))
        return EntryBlock.from_entries(rows)

    def secp_block(n, bad=()):
        rows = []
        for i in range(n):
            sk = _secp.PrivKey((6000 + i).to_bytes(32, "big"))
            m = b"agg-secp-%d" % i
            rows.append((sk.pub_key().bytes(), m,
                         sk.sign(m) if i not in bad else b"\x07" * 64))
        return EntryBlock.from_entries(rows, scheme="secp256k1")

    class _J:
        def __init__(self, blk):
            self.entries = blk

    blocks = [ed_block(10, bad=(4,)), secp_block(7, bad=(2,)), fused]
    jobs = [_J(b) for b in blocks]
    plan, held = ms.pack_jobs(jobs, 4)
    sblock, spans = ms.build_superblock(plan)
    is_super = isinstance(sblock, ms.SchemeSuperBlock)
    parts = [(s, len(b)) for s, b, _ in sblock.parts] if is_super else []
    bls_w = dict((s, n) for s, n in parts).get("bls12381")
    print(f"  lanes: schemes={plan.schemes()} parts={parts} "
          f"rows={plan.bucket}")
    if held or not is_super or bls_w != 4:
        print("  FAIL: three-lane plan must build one SchemeSuperBlock "
              "with the BLS segment at quantized width 4", file=sys.stderr)
        rc = 1
    res = ms.prepare_superbatch(sblock, plan)
    f, fargs = res[0], res[1]
    shardings = res[4] if len(res) > 4 else None
    arr = np.asarray(f(*dp.transfer(fargs, shardings=shardings)))
    by_job = {id(j): (off, n) for j, off, n in spans}
    lane_ok = True
    for j, want in ((jobs[0], np.asarray(backend.verify_batch(blocks[0]))),
                    (jobs[1], np.asarray(backend.verify_batch(blocks[1])))):
        off, n = by_job[id(j)]
        lane_ok &= np.array_equal(arr[off:off + n].astype(bool), want)
    off, n = by_job[id(jobs[2])]
    lane_ok &= arr[off:off + n].tolist() == want_codes
    print(f"  launch: 1 fn call, demux parity "
          f"{'OK' if lane_ok else 'MISMATCH'} "
          f"(ed25519 + secp256k1 bool rows, bls12381 code row)")
    if not lane_ok:
        rc = 1

    # -- pipeline: three submissions fuse into ONE dispatch, no leak ----
    tr.TRACER.clear()
    tr.configure(enabled=True)
    v = pl.AsyncBatchVerifier(depth=2, mesh_lanes=4)
    try:
        futs = [v.submit(b) for b in blocks]
        rows = [np.asarray(fu.result(timeout=600)) for fu in futs]
        drain_pool(v._pool)
        pool = v._pool.stats()
    finally:
        tr.configure(enabled=False)
        v.close()
    launches = sum(1 for name, *_ in tr.TRACER.events()
                   if name == "pipeline.dispatch")
    pipe_ok = (np.array_equal(rows[0].astype(bool),
                              np.asarray(backend.verify_batch(blocks[0])))
               and np.array_equal(rows[1].astype(bool),
                                  np.asarray(backend.verify_batch(blocks[1])))
               and rows[2].tolist() == want_codes)
    print(f"  pipeline: launches={launches} parity="
          f"{'OK' if pipe_ok else 'MISMATCH'} pool={pool}")
    if launches != 1:
        print(f"  FAIL: three same-window submissions must fuse into one "
              f"dispatch, saw {launches}", file=sys.stderr)
        rc = 1
    if not pipe_ok:
        rc = 1
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1
    return rc


def run_light(args) -> int:
    """--light: the round-11 light-service gate on a mocked device (slow
    readback over REAL kernels — verdicts are live). Asserts the three
    properties the batched service must hold:

      coalesce  cross-request SAME-EPOCH coalescing proven by launch
                count: R warm requests emit 2R-1 stage blocks but the
                shared pipeline fuses them into far fewer device
                launches (each undersized per-request dispatch would
                otherwise pay a full device RTT — the ~1.2k headers/s
                sequential ceiling)
      parity    verdicts AND blame byte-identical to the sequential
                light/verifier.py path — ok requests, a forged-commit
                request (tampered signature) and an expired-trusted-
                header request all match (type name + error string)
      no leak   zero buffer-pool slots in flight once drained, and a
                memoized resubmission adds ZERO launches
    """
    from dataclasses import replace as dc_replace

    import bench as _bench

    from tendermint_tpu.light import verifier as lv
    from tendermint_tpu.light.batch import HeaderRequest, fingerprint
    from tendermint_tpu.light.service import LightVerifyService
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, slow_prepare
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.wire.canonical import Timestamp

    n_vals, n_headers = 8, 6
    resolve_delay = 0.15
    chain_id = "light-gate"
    print(f"prep_bench --light: vals={n_vals} headers={n_headers} "
          f"resolve_delay={resolve_delay}s")
    rc = 0
    shs = _bench._build_header_chain(chain_id, n_headers, n_vals)
    trusted, vset = shs[0]
    now = Timestamp(seconds=1_600_000_000 + n_headers + 60)
    period = 1e9

    def mkreq(k, untrusted=None, p=period):
        return HeaderRequest(
            trusted_header=trusted, trusted_vals=vset,
            untrusted_header=untrusted or shs[k][0],
            untrusted_vals=vset, trusting_period=p,
        )

    def seq_verdict(req):
        try:
            lv.verify(req.trusted_header, req.trusted_vals,
                      req.untrusted_header, req.untrusted_vals,
                      req.trusting_period, now, req.max_clock_drift,
                      req.trust_level)
            return None
        except Exception as e:  # noqa: BLE001 — the verdict IS the error
            return (type(e).__name__, str(e))

    # warm epoch: one valset across every request, device tables resident
    _epoch.reset(4)
    # adversarial inputs: a forged commit (tampered signature) and an
    # expired trusted header, alongside the clean warm requests
    fcommit = Commit.decode(shs[3][0].commit.encode())
    fcommit.signatures[4] = dc_replace(
        fcommit.signatures[4], signature=b"\x07" * 64
    )
    from tendermint_tpu.types import SignedHeader

    forged = SignedHeader(header=shs[3][0].header, commit=fcommit)
    reqs = [mkreq(k) for k in range(1, n_headers + 1)]
    reqs.append(mkreq(3, untrusted=forged))
    reqs.append(mkreq(5, p=1.0))  # trusted header long expired
    n_stage_blocks = 1 + (n_headers - 1) * 2 + 2 + 0  # adjacent:1, non-adj:2 each, forged:2, expired:0
    assert len({fingerprint(r, now) for r in reqs}) == len(reqs)

    real_prepare = pl.AsyncBatchVerifier._prepare
    pl.AsyncBatchVerifier._prepare = staticmethod(
        slow_prepare(real_prepare, resolve_delay)
    )
    tr.TRACER.clear()
    tr.configure(enabled=True)
    v = pl.AsyncBatchVerifier(depth=1, pool_depth=OVERLAP_POOL_DEPTH)
    svc = LightVerifyService(verifier=v)
    try:
        res = svc.submit_many(reqs, now=now).results(timeout=900)
        launches1 = sum(
            1 for name, *_ in tr.TRACER.events() if name == "pipeline.dispatch"
        )
        # memoized resubmission: byte-identical requests resolve from the
        # verdict memo with ZERO additional device work
        res2 = svc.submit_many(reqs, now=now).results(timeout=120)
        launches2 = sum(
            1 for name, *_ in tr.TRACER.events() if name == "pipeline.dispatch"
        )
        drain_pool(v._pool)
        pool = v._pool.stats()
        stats = svc.stats()
    finally:
        tr.configure(enabled=False)
        svc.close()
        v.close()
        pl.AsyncBatchVerifier._prepare = real_prepare

    # -- parity vs the sequential verifier ------------------------------
    mism = []
    for i, (req, r) in enumerate(zip(reqs, res)):
        want = seq_verdict(req)
        got = None if r["ok"] else (r["error_type"], r["error"])
        if want != got:
            mism.append((i, want, got))
    ok_count = sum(1 for r in res if r["ok"])
    print(f"  requests={len(reqs)} ok={ok_count} "
          f"rejected={len(reqs) - ok_count}")
    print(f"  verdict/blame parity vs sequential : "
          f"{'OK' if not mism else f'MISMATCH {mism[:2]}'}")
    if mism:
        rc = 1
    if [r["ok"] for r in res2] != [r["ok"] for r in res]:
        print("  FAIL: memoized verdicts differ from first pass",
              file=sys.stderr)
        rc = 1

    # -- cross-request coalescing by launch count ------------------------
    print(f"  stage blocks submitted             : {n_stage_blocks}")
    print(f"  device launches (first pass)       : {launches1}")
    print(f"  device launches (memo resubmission): {launches2 - launches1}")
    if launches1 >= n_stage_blocks:
        print(f"  FAIL: {launches1} launches for {n_stage_blocks} stage "
              "blocks — no cross-request coalescing", file=sys.stderr)
        rc = 1
    if launches2 != launches1:
        print("  FAIL: memoized resubmission launched device work",
              file=sys.stderr)
        rc = 1
    if stats["memo_hits"] != len(reqs):
        print(f"  FAIL: expected {len(reqs)} memo hits, got "
              f"{stats['memo_hits']}", file=sys.stderr)
        rc = 1

    # -- epoch grouping + pool hygiene -----------------------------------
    est = _epoch.stats()
    print(f"  epoch cache                        : entries={est['entries']} "
          f"hits={est['hits']} misses={est['misses']}")
    print(f"  pool                               : {pool}")
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1
    if est["hits"] <= 0:
        print("  FAIL: warm-epoch requests never hit the epoch cache",
              file=sys.stderr)
        rc = 1
    return rc


def run_ingress(args) -> int:
    """--ingress: the round-13 mempool-ingress gate on a mocked device
    (slow readback over REAL kernels — verdicts are live). Asserts the
    three properties device-batched CheckTx must hold:

      fuse       N flooded txs reach the device in <= K launches (the
                 accumulator windows them, the coalescer fuses windows) —
                 each per-tx dispatch would otherwise pay a full device
                 RTT, the ~25 tx/s sequential ceiling bench.py measures
      QoS        a consensus-priority batch submitted mid-flood overtakes
                 queued ingress work: preempted_total advances and the
                 commit's verdict lands while ingress futures are still
                 outstanding
      no leak    every tx future resolves (a forged signature resolves
                 FALSE, never silently dropped), and zero buffer-pool
                 slots remain in flight once drained
    """
    import threading

    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.mempool import ingress as ing
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import SlowReadback, drain_pool
    from tendermint_tpu.ops.entry_block import EntryBlock

    n_txs, n_senders, max_batch = 256, 8, 64
    resolve_delay = 0.15
    print(f"prep_bench --ingress: txs={n_txs} senders={n_senders} "
          f"batch={max_batch} resolve_delay={resolve_delay}s")
    rc = 0
    import hashlib

    privs = [ed.gen_priv_key(seed=hashlib.sha256(b"ingress-gate-%d" % s)
                             .digest()) for s in range(n_senders)]
    stxs = []
    for i in range(n_txs):
        raw = ing.make_signed_tx(privs[i % n_senders],
                                 b"gate_k%d=v%d" % (i, i),
                                 nonce=i // n_senders + 1)
        stxs.append(ing.parse_signed_tx(raw))
    # one forged signature mid-flood: its future must resolve FALSE
    forged_i = n_txs // 2
    f = stxs[forged_i]
    bad = bytearray(f.sig)
    bad[0] ^= 0x5A
    stxs[forged_i] = ing.SignedTx(f.scheme, f.pub, f.nonce, bytes(bad),
                                  f.payload, f.raw)
    commit_block = EntryBlock.from_entries(
        [(s.pub, s.signed_bytes(), s.sig) for s in stxs[:32]
         if ing.host_verify(s)]
    )

    _epoch.reset(4)
    real_prepare = pl.AsyncBatchVerifier._prepare
    # The staging below is driven by the pipeline's state, not by sleeps
    # (a loaded host outran "50 ms, then 20 ms" either way): the first
    # readback is held until a preemption has been counted, so the commit
    # always meets an occupied pipeline, however late it arrives.
    preempted = threading.Event()

    class HeldReadback(SlowReadback):
        def __array__(self, dtype=None):
            preempted.wait(30)  # a FAIL below if nothing ever preempts
            return super().__array__(dtype)

    def held_prepare(entries):
        f, args, rlc, bucket = real_prepare(entries)
        return ((lambda *xs: HeldReadback(f(*xs), resolve_delay)),
                args, rlc, bucket)

    pl.AsyncBatchVerifier._prepare = staticmethod(held_prepare)
    tr.TRACER.clear()
    tr.configure(enabled=True)
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    v = pl.AsyncBatchVerifier(depth=1, pool_depth=OVERLAP_POOL_DEPTH)
    v.add_preempt_hook(lambda n: preempted.set())
    acc = ing.IngressAccumulator(verifier=v, max_batch=max_batch,
                                 window_ms=8.0)

    def transferred(n_batches):
        deadline = time.monotonic() + 60
        while (v._pool.stats()["in_flight"] < n_batches
               and time.monotonic() < deadline):
            time.sleep(0.002)

    try:
        # two waves: wave 1 launches and holds the single depth slot;
        # wave 2 transfers and parks on the semaphore. The commit then
        # arrives against a genuinely occupied pipeline — the shape the
        # preemption machinery exists for.
        futs = [acc.submit(s) for s in stxs[:max_batch]]
        acc.flush_now()
        transferred(1)  # wave 1 is on its way to the device
        futs += [acc.submit(s) for s in stxs[max_batch:]]
        acc.flush_now()
        transferred(2)  # wave 2 transferred: parked on the depth sem
        cfut = v.submit(commit_block, priority=pl.PRIORITY_CONSENSUS)
        commit_ok = bool(all(cfut.result(timeout=300)))
        pending_at_commit = sum(1 for x in futs if not x.done())
        verdicts = [x.result(timeout=300) for x in futs]
        launches = sum(
            1 for name, *_ in tr.TRACER.events()
            if name == "pipeline.dispatch"
        )
        drain_pool(v._pool)
        pool = v._pool.stats()
        preempts = v.preempted_total
    finally:
        tr.configure(enabled=False)
        acc.close()
        v.close()
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        pl.AsyncBatchVerifier._prepare = real_prepare

    # -- fuse: N txs in <= K launches ------------------------------------
    k_max = n_txs // max_batch + 2  # windows + the commit + slack
    print(f"  txs flooded                : {n_txs}")
    print(f"  device launches            : {launches} (gate: <= {k_max})")
    if launches > k_max:
        print(f"  FAIL: {launches} launches for {n_txs} txs — "
              "ingress windows are not fusing", file=sys.stderr)
        rc = 1

    # -- QoS: the commit overtook queued ingress work --------------------
    print(f"  commit verdict             : "
          f"{'all-valid' if commit_ok else 'INVALID'}")
    print(f"  ingress futures pending when commit landed: "
          f"{pending_at_commit}")
    print(f"  preempted_total            : {preempts}")
    if not commit_ok:
        print("  FAIL: consensus batch verdict wrong", file=sys.stderr)
        rc = 1
    if preempts <= 0:
        print("  FAIL: consensus batch never preempted queued ingress "
              "work", file=sys.stderr)
        rc = 1
    if pending_at_commit <= 0:
        print("  FAIL: commit landed after the whole flood — no QoS "
              "evidence", file=sys.stderr)
        rc = 1

    # -- verdict integrity + pool hygiene --------------------------------
    bad_verdicts = [i for i, ok in enumerate(verdicts)
                    if ok != (i != forged_i)]
    print(f"  verdicts                   : {sum(verdicts)} valid / "
          f"{len(verdicts) - sum(verdicts)} rejected "
          f"(forged tx at {forged_i})")
    print(f"  pool                       : {pool}")
    if bad_verdicts:
        print(f"  FAIL: wrong verdicts at {bad_verdicts[:4]} — the "
              "forged tx must be the ONLY rejection", file=sys.stderr)
        rc = 1
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1
    return rc


def run_votes(args) -> int:
    """--votes: the round-15 live-vote-ingress gate on a mocked device
    (slow readback over REAL kernels — verdicts are live). Asserts the
    three properties device-batched AddVote must hold:

      fuse       N gossiped votes reach the device in <= K launches (the
                 accumulator windows them by (height, valset epoch), the
                 coalescer fuses windows) — per-vote dispatch would pay a
                 full device RTT each
      parity     a forged signature mid-flood resolves FALSE and is the
                 ONLY rejection — blame lands on exactly the forged vote
      no leak    every vote's verdict arrives (none silently dropped)
                 and zero buffer-pool slots remain in flight once drained
    """
    import threading

    from tendermint_tpu.consensus import vote_ingress as vi
    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import epoch_cache as _epoch
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, slow_prepare
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import PREVOTE_TYPE, Vote
    from tendermint_tpu.wire.canonical import Timestamp

    chain_id = "votes-gate"
    n_vals, n_rounds, max_batch = 32, 8, 64
    n_votes = n_vals * n_rounds
    resolve_delay = 0.15
    print(f"prep_bench --votes: votes={n_votes} vals={n_vals} "
          f"rounds={n_rounds} batch={max_batch} "
          f"resolve_delay={resolve_delay}s")
    rc = 0

    pairs = []
    for i in range(n_vals):
        sk = ed.gen_priv_key(bytes([i + 1]) * 32)
        pairs.append((sk, Validator.new(sk.pub_key(), 100)))
    vset = ValidatorSet.new([v for _, v in pairs])
    by_addr = {v.address: sk for sk, v in pairs}
    sks = [by_addr[v.address] for v in vset.validators]
    bid = BlockID(hash=b"\x07" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x07" * 32))
    height = 10

    pends = []
    for r in range(n_rounds):
        for i, sk in enumerate(sks):
            vote = Vote(
                type=PREVOTE_TYPE, height=height, round=r, block_id=bid,
                timestamp=Timestamp(seconds=1_600_000_000, nanos=0),
                validator_address=vset.validators[i].address,
                validator_index=i,
            )
            msg = vote.sign_bytes(chain_id)
            vote = Vote(**{**vote.__dict__, "signature": sk.sign(msg)})
            pends.append(vi.PendingVote(
                vote, "gate-peer", sk.pub_key().bytes(), msg,
                t_enq=time.perf_counter(),
            ))
    # one forged signature mid-flood: its verdict must be the ONLY False
    forged_i = n_votes // 2
    f = pends[forged_i]
    bad = bytearray(f.vote.signature)
    bad[0] ^= 0x5A
    fv = Vote(**{**f.vote.__dict__, "signature": bytes(bad)})
    pends[forged_i] = vi.PendingVote(fv, f.peer_id, f.pub, f.msg,
                                     t_enq=f.t_enq)

    _epoch.reset(4)
    _epoch.note_valset(vset)  # register
    _epoch.note_valset(vset)  # warm: windows attach val_idx + epoch_key
    verdicts: dict = {}
    done = threading.Event()

    def collect(batch, vds, err):
        for i, p in enumerate(batch):
            key = (p.vote.round, p.vote.validator_index)
            verdicts[key] = None if err is not None else bool(vds[i])
        if len(verdicts) >= n_votes:
            done.set()

    real_prepare = pl.AsyncBatchVerifier._prepare
    pl.AsyncBatchVerifier._prepare = staticmethod(
        slow_prepare(real_prepare, resolve_delay)
    )
    tr.TRACER.clear()
    tr.configure(enabled=True)
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    v = pl.AsyncBatchVerifier(depth=2, pool_depth=OVERLAP_POOL_DEPTH)
    acc = vi.VoteIngress(collect, verifier=v, max_batch=max_batch,
                         window_ms=8.0)
    try:
        for p in pends:
            acc.submit(p, vset)
        acc.flush_now()
        if not done.wait(timeout=300):
            print(f"  FAIL: only {len(verdicts)}/{n_votes} verdicts "
                  "arrived", file=sys.stderr)
            rc = 1
        launches = sum(1 for name, *_ in tr.TRACER.events()
                       if name == "pipeline.dispatch")
        drain_pool(v._pool)
        pool = v._pool.stats()
        stats = acc.stats()
    finally:
        tr.configure(enabled=False)
        acc.close()
        v.close()
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        pl.AsyncBatchVerifier._prepare = real_prepare

    # -- fuse: N votes in <= K launches ----------------------------------
    k_max = n_votes // max_batch + 2  # windows + slack
    print(f"  votes flooded              : {n_votes}")
    print(f"  device launches            : {launches} (gate: <= {k_max}, "
          f"per-vote would be {n_votes})")
    print(f"  ingress stats              : batches={stats['batches']} "
          f"sigs={stats['sigs']} sync_fallbacks={stats['sync_fallbacks']}")
    if launches > k_max:
        print(f"  FAIL: {launches} launches for {n_votes} votes — vote "
              "windows are not fusing", file=sys.stderr)
        rc = 1

    # -- parity: exactly the forged vote rejected ------------------------
    bad_keys = [k for k, ok in verdicts.items()
                if ok != ((k[0], k[1]) != (forged_i // n_vals,
                                           forged_i % n_vals))]
    n_ok = sum(1 for x in verdicts.values() if x)
    print(f"  verdicts                   : {n_ok} valid / "
          f"{len(verdicts) - n_ok} rejected (forged vote at round "
          f"{forged_i // n_vals} idx {forged_i % n_vals})")
    if bad_keys:
        print(f"  FAIL: wrong verdicts at {bad_keys[:4]} — the forged "
              "vote must be the ONLY rejection", file=sys.stderr)
        rc = 1

    # -- pool hygiene ----------------------------------------------------
    print(f"  pool                       : {pool}")
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1
    return rc


def _build_replay_chain(n_blocks: int, n_vals: int, chain_id: str,
                        rotate_at=()):
    """Fully-linked signed chain for the replay gate: block h+1's
    last_commit signs block h's BlockID (hash + part-set header of the
    encoded block), real keys, optional valset rotation."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.block import (
        Block,
        BlockID,
        Data,
        Header,
        Version,
    )
    from tendermint_tpu.types.part_set import BLOCK_PART_SIZE_BYTES, PartSet
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import PRECOMMIT_TYPE, Vote
    from tendermint_tpu.types.vote_set import VoteSet
    from tendermint_tpu.wire.canonical import Timestamp

    def mk_vals(seed):
        pairs = []
        for i in range(n_vals):
            sk = ed25519.gen_priv_key(bytes([seed + i]) * 32)
            pairs.append((sk, Validator.new(sk.pub_key(), 100)))
        vset = ValidatorSet.new([v for _, v in pairs])
        by_addr = {v.address: sk for sk, v in pairs}
        return [by_addr[v.address] for v in vset.validators], vset

    rotate_at = sorted(rotate_at)
    vals_at, keys_at = {}, {}
    seed, cur = 1, mk_vals(1)
    for h in range(1, n_blocks + 2):
        if h in rotate_at:
            seed += n_vals
            cur = mk_vals(seed)
        keys_at[h], vals_at[h] = cur
    blocks, last_commit, prev_bid = [], None, BlockID()
    for h in range(1, n_blocks + 1):
        hdr = Header(
            version=Version(block=11, app=0), chain_id=chain_id, height=h,
            time=Timestamp(seconds=1_600_000_000 + h), last_block_id=prev_bid,
            validators_hash=vals_at[h].hash(),
            next_validators_hash=vals_at[h + 1].hash(),
            consensus_hash=b"\x01" * 32, app_hash=b"",
            proposer_address=vals_at[h].validators[0].address,
        )
        block = Block(header=hdr, data=Data(), last_commit=last_commit)
        block.fill_header()
        parts = PartSet.from_data(block.encode(), BLOCK_PART_SIZE_BYTES)
        bid = BlockID(hash=block.hash(), part_set_header=parts.header())
        vs = VoteSet(chain_id, h, 0, PRECOMMIT_TYPE, vals_at[h])
        for sk in keys_at[h]:
            addr = sk.pub_key().address()
            idx, _ = vals_at[h].get_by_address(addr)
            vote = Vote(
                type=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                timestamp=Timestamp(seconds=1_600_000_000, nanos=0),
                validator_address=addr, validator_index=idx,
            )
            sig = sk.sign(vote.sign_bytes(chain_id))
            vs.add_vote(Vote(**{**vote.__dict__, "signature": sig}))
        last_commit = vs.make_commit()
        prev_bid = bid
        blocks.append(block)
    return blocks, vals_at


def run_replay(args) -> int:
    """--replay: the round-14 chain-replay gate on a mocked device (slow
    readback over REAL kernels — verdicts are live). Asserts the three
    properties range-batched blocksync must hold:

      pack       a window of W same-epoch heights reaches the device in
                 ceil(W*sigs/bucket) launches, NOT W — the whole point
                 of range batching vs the verify-one-ahead path
      parity     a forged commit mid-range falls back to per-height
                 sequential verification whose rejection error is
                 byte-identical to verify_commit_light's, and every
                 height before the forgery still applies
      no leak    zero buffer-pool slots remain in flight once drained
    """
    from tendermint_tpu.blocksync.replay import ReplayEngine
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import backend
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, slow_prepare
    from tendermint_tpu.types.block import BlockID
    from tendermint_tpu.types.part_set import BLOCK_PART_SIZE_BYTES, PartSet
    from tendermint_tpu.types.validation import verify_commit_light

    chain_id = "replay-gate"
    n_blocks, n_vals = 13, 8  # 12 verifiable heights x ~6 light-path sigs
    resolve_delay = 0.05
    print(f"prep_bench --replay: blocks={n_blocks} vals={n_vals} "
          f"resolve_delay={resolve_delay}s")
    rc = 0
    blocks, vals_at = _build_replay_chain(n_blocks, n_vals, chain_id)

    class _St:
        def __init__(self):
            self.chain_id = chain_id
            self.validators = vals_at[1]
            self.last_block_height = 0

    def mk_cbs(st):
        saves = []

        def save(block, parts, seen_commit):
            saves.append(block.header.height)

        def apply(bid, block):
            st.last_block_height = block.header.height
            st.validators = vals_at[block.header.height + 1]
            return st

        return saves, save, apply

    real_prepare = pl.AsyncBatchVerifier._prepare
    pl.AsyncBatchVerifier._prepare = staticmethod(
        slow_prepare(real_prepare, resolve_delay)
    )
    tr.TRACER.clear()
    tr.configure(enabled=True)
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    v = pl.AsyncBatchVerifier(depth=2, pool_depth=OVERLAP_POOL_DEPTH)
    try:
        # -- pack: W same-epoch heights -> ceil(W*sigs/bucket) launches --
        eng = ReplayEngine(synchronous=True, verifier=v)
        st = _St()
        saves, save, apply = mk_cbs(st)
        st, out = eng.replay_blocks(st, blocks, save, apply)
        launches = sum(1 for name, *_ in tr.TRACER.events()
                       if name == "pipeline.dispatch")
        w = n_blocks - 1
        sigs = eng.sigs_submitted
        bucket = backend.quantized_bucket(max(sigs, 1))
        expect = max(1, -(-sigs // bucket))
        print(f"  heights replayed           : {out.applied} "
              f"(range-verified {out.range_heights})")
        print(f"  sigs submitted             : {sigs} (bucket {bucket})")
        print(f"  device launches            : {launches} "
              f"(gate: <= {expect + 1}, sequential would be {w})")
        if out.applied != w or out.range_heights != w:
            print(f"  FAIL: expected {w} range-verified heights, got "
                  f"{out.range_heights}", file=sys.stderr)
            rc = 1
        if saves != list(range(1, w + 1)):
            print("  FAIL: save order broken", file=sys.stderr)
            rc = 1
        if launches > expect + 1:
            print(f"  FAIL: {launches} launches for {w} heights — range "
                  "packing is not fusing", file=sys.stderr)
            rc = 1

        # -- parity: forged commit mid-range falls back byte-identically -
        blocks2, vals2 = _build_replay_chain(n_blocks, n_vals, chain_id)
        bad_h = 6
        commit = blocks2[bad_h].last_commit  # block 7 vouches for h=6
        s0 = commit.signatures[0]
        commit.signatures[0] = s0.__class__(
            block_id_flag=s0.block_id_flag,
            validator_address=s0.validator_address,
            timestamp=s0.timestamp, signature=bytes(64),
        )
        eng2 = ReplayEngine(synchronous=True, verifier=v)
        st2 = _St()
        saves2, save2, apply2 = mk_cbs(st2)
        st2, out2 = eng2.replay_blocks(st2, blocks2, save2, apply2)
        p = PartSet.from_data(blocks2[bad_h - 1].encode(),
                              BLOCK_PART_SIZE_BYTES)
        bid = BlockID(hash=blocks2[bad_h - 1].hash(),
                      part_set_header=p.header())
        seq_err = None
        try:
            verify_commit_light(chain_id, vals2[bad_h], bid, bad_h,
                                blocks2[bad_h].last_commit)
        except (ValueError, RuntimeError) as e:
            seq_err = str(e)
        print(f"  forged commit at height    : {bad_h}")
        print(f"  applied before rejection   : {out2.applied} "
              f"(gate: {bad_h - 1})")
        print(f"  fallback error             : {out2.error!r}")
        if out2.applied != bad_h - 1 or out2.failed_height != bad_h:
            print(f"  FAIL: fallback applied {out2.applied}, failed at "
                  f"{out2.failed_height}; want {bad_h - 1}/{bad_h}",
                  file=sys.stderr)
            rc = 1
        if seq_err is None or out2.error != seq_err:
            print(f"  FAIL: error mismatch vs sequential path:\n"
                  f"    replay    : {out2.error!r}\n"
                  f"    sequential: {seq_err!r}", file=sys.stderr)
            rc = 1

        drain_pool(v._pool)
        pool = v._pool.stats()
    finally:
        tr.configure(enabled=False)
        v.close()
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        pl.AsyncBatchVerifier._prepare = real_prepare

    # -- pool hygiene ----------------------------------------------------
    print(f"  pool                       : {pool}")
    if pool["in_flight"] != 0:
        print(f"  FAIL: {pool['in_flight']} pool slots leaked",
              file=sys.stderr)
        rc = 1
    return rc


def run_fabric(args) -> int:
    """--fabric: the round-17 ingress-fabric gate on a mocked device
    (slow readback over REAL kernels — verdicts are live). Asserts what
    unifying the four windowed accumulators bought:

      one engine  all four lane patterns (mempool / votes / light /
                  replay) register on ONE engine — exactly one
                  flush-scheduler thread and one completer thread serve
                  all of them, where the per-workload era ran four
      adaptive    the consensus-pattern lane's window moves BOTH ways:
                  it deepens under a flood (grows >= 1) and shrinks back
                  on an idle trickle (shrinks >= 1)
      parity      every signature's verdict arrives and the one forged
                  signature is the ONLY rejection, on the right lane
      no leak     zero buffer-pool slots remain in flight once drained
    """
    import threading

    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.ops import ingress as fabric
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, slow_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock

    resolve_delay = 0.05
    n_keys = 8
    keys = [ed.gen_priv_key(bytes([i + 1]) * 32) for i in range(n_keys)]

    def signed(lane: str, i: int):
        sk = keys[i % n_keys]
        msg = f"fabric/{lane}/{i}".encode()
        return (sk.pub_key().bytes(), msg, sk.sign(msg), i)

    rc = 0
    print(f"prep_bench --fabric: lanes=4 resolve_delay={resolve_delay}s")

    real_prepare = pl.AsyncBatchVerifier._prepare
    pl.AsyncBatchVerifier._prepare = staticmethod(
        slow_prepare(real_prepare, resolve_delay))
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    v = pl.AsyncBatchVerifier(depth=2, pool_depth=OVERLAP_POOL_DEPTH)
    eng = fabric.IngressEngine()

    mtx = threading.Lock()
    results = {name: {} for name in ("mempool", "votes", "light")}

    def sink(name):
        def deliver(items, verdicts, err):
            with mtx:
                for i, it in enumerate(items):
                    results[name][it.item[3]] = (
                        None if err is not None else bool(verdicts[i]))
        return deliver

    def host_check(items):
        return [ed.verify_zip215_fast(t[0], t[1], t[2]) for t in items]

    common = dict(verifier=v, entries_fn=lambda t: t[:3],
                  host_fn=host_check)
    mp = eng.register(fabric.LaneSpec(
        name="mempool", priority=fabric.PRIORITY_INGRESS, batch=32,
        window_ms=4.0, use_completer=True, deliver=sink("mempool"),
        **common))
    vo = eng.register(fabric.LaneSpec(
        name="votes", priority=fabric.PRIORITY_CONSENSUS, batch=16,
        window_ms=4.0, adaptive=True, deliver=sink("votes"), **common))
    li = eng.register(fabric.LaneSpec(
        name="light", priority=fabric.PRIORITY_CONSENSUS, stepped=True,
        deliver=sink("light"), **common))
    rp = eng.register(fabric.LaneSpec(
        name="replay", priority=fabric.PRIORITY_REPLAY, stepped=True,
        **common))
    try:
        # -- one engine: four lanes, one scheduler, one completer --------
        names = [t.name for t in threading.enumerate()]
        n_sched = sum(n == "ingress-fabric-flush" for n in names)
        n_comp = sum(n == "ingress-fabric-complete" for n in names)
        print(f"  lanes registered           : {len(eng.lanes())} "
              f"(flush threads={n_sched}, completer threads={n_comp})")
        if len(eng.lanes()) != 4 or n_sched != 1 or n_comp != 1:
            print("  FAIL: expected 4 lanes on exactly one scheduler + "
                  "one completer thread", file=sys.stderr)
            rc = 1

        # -- mempool flood with one forged signature mid-flood -----------
        # (pre-sign everything: purepy signing is slow enough that
        # signing inside the submit loop would turn the flood into a
        # trickle and never fill a window)
        n_mp, forged_i = 96, 48
        mp_items = []
        for i in range(n_mp):
            pub, msg, sig, idx = signed("mempool", i)
            if i == forged_i:
                bad = bytearray(sig)
                bad[0] ^= 0x5A
                sig = bytes(bad)
            mp_items.append((pub, msg, sig, idx))
        n_vo = 128
        vo_items = [signed("votes", i) for i in range(n_vo)]
        trickle_items = [signed("votes", n_vo + i) for i in range(20)]

        for it in mp_items:
            mp.submit(it)
        mp.flush_now()

        # -- votes flood: the window must DEEPEN -------------------------
        # no flush_now() here: a manual flush would race the scheduler
        # and claim the whole flood under CAUSE_MANUAL (which by design
        # never adapts); the full-window force + timer tail drain it
        for it in vo_items:
            vo.submit(it)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            with mtx:
                if (len(results["mempool"]) >= n_mp
                        and len(results["votes"]) >= n_vo):
                    break
            time.sleep(0.01)
        grows = vo.ctrl.grows
        print(f"  votes flood                : {n_vo} sigs -> window "
              f"grows={grows} (target now {vo.ctrl.batch_target()}, "
              f"base 16)")
        if grows < 1:
            print("  FAIL: a flood at the batch target must deepen the "
                  "adaptive window", file=sys.stderr)
            rc = 1

        # -- votes idle trickle: the window must SHRINK back -------------
        trickles = 0
        for it in trickle_items:
            if vo.ctrl.shrinks >= 1:
                break
            vo.submit(it)
            trickles += 1
            time.sleep(0.12)
        shrinks = vo.ctrl.shrinks
        print(f"  votes idle trickle         : {trickles} lone sigs -> "
              f"window shrinks={shrinks} (target now "
              f"{vo.ctrl.batch_target()})")
        if shrinks < 1:
            print("  FAIL: an idle trickle must shrink the adaptive "
                  "window back toward its base", file=sys.stderr)
            rc = 1

        # -- stepped lanes: light host windows, replay block passthrough -
        n_li = 16
        for i in range(n_li):
            li.submit(signed("light", i))
        li.flush_pending()
        blk = EntryBlock.from_entries(
            [signed("replay", i)[:3] for i in range(16)])
        rp_verdicts = list(rp.submit_block(blk).result(timeout=60))

        # -- parity ------------------------------------------------------
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with mtx:
                if len(results["votes"]) >= n_vo + trickles:
                    break
            time.sleep(0.01)
        with mtx:
            snapshot = {k: dict(d) for k, d in results.items()}
        snapshot["replay"] = {i: bool(x) for i, x in enumerate(rp_verdicts)}
        expect = {"mempool": n_mp, "votes": n_vo + trickles,
                  "light": n_li, "replay": 16}
        rejected = [(lane, i) for lane, d in snapshot.items()
                    for i, ok in d.items() if not ok]
        total = sum(len(d) for d in snapshot.values())
        print(f"  verdicts                   : {total} arrived, "
              f"rejected={rejected} (forged: mempool idx {forged_i})")
        for lane, n in expect.items():
            if len(snapshot[lane]) != n:
                print(f"  FAIL: {lane} delivered {len(snapshot[lane])}"
                      f"/{n} verdicts", file=sys.stderr)
                rc = 1
        if rejected != [("mempool", forged_i)]:
            print("  FAIL: the forged signature must be the ONLY "
                  "rejection", file=sys.stderr)
            rc = 1

        # -- pool hygiene ------------------------------------------------
        for lane in (mp, vo, li, rp):
            lane.close(timeout=30)
        drain_pool(v._pool)
        pool = v._pool.stats()
        print(f"  pool                       : {pool}")
        if pool["in_flight"] != 0:
            print(f"  FAIL: {pool['in_flight']} pool slots leaked",
                  file=sys.stderr)
            rc = 1
    finally:
        eng.close(timeout=5)
        v.close()
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        pl.AsyncBatchVerifier._prepare = real_prepare
    return rc


def run_fleet(args) -> int:
    """--fleet: the round-18 verification-fleet gate on a mocked device
    (slow readback over REAL kernels and REAL loopback sockets —
    verdicts are live, frames cross a real TCP stream). Asserts what
    the network-facing verify service must hold:

      coalesce  two client NODES submitting same-epoch blocks through
                ONE fleet server fuse into fewer device launches than
                the same blocks verified solo (sum of the two per-node
                launch counts) — the whole point of sharing the fleet
      blame     the one forged signature (node B, block 3, row 5) is
                the ONLY False verdict across both nodes, demuxed back
                to node B's future at the right row; verdict arrays are
                byte-identical to the solo runs
      failover  killing the fleet server mid-window loses ZERO items —
                every unresolved request fails over to the host path
                with identical verdicts — and a server restarted on the
                same port is rejoined automatically, after which the
                next submit rides the fleet again
      no leak   zero buffer-pool slots remain in flight once drained
    """
    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.fleet.client import FleetClient, FleetUnavailable
    from tendermint_tpu.fleet.server import FleetServer
    from tendermint_tpu.observability import trace as tr
    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, slow_prepare
    from tendermint_tpu.ops.entry_block import EntryBlock

    resolve_delay = 0.15
    n_keys, spb, bpn = 8, 16, 6  # sigs/block, blocks/node
    nodes = ("node-a", "node-b")
    forge_node, forge_block, forge_row = "node-b", 3, 5
    keys = [ed.gen_priv_key(bytes([i + 1]) * 32) for i in range(n_keys)]
    epoch = b"fleet-gate-epoch"  # unregistered: degrades to uncached prep

    print(f"prep_bench --fleet: nodes=2 blocks/node={bpn} sigs/block={spb} "
          f"resolve_delay={resolve_delay}s")
    rc = 0

    def build_block(node: str, b: int) -> EntryBlock:
        pub = np.zeros((spb, 32), dtype=np.uint8)
        sig = np.zeros((spb, 64), dtype=np.uint8)
        offsets = np.zeros(spb + 1, dtype=np.int64)
        msgs = []
        for i in range(spb):
            sk = keys[i % n_keys]
            m = f"fleet/{node}/{b}/{i}".encode()
            s = sk.sign(m)
            if (node, b, i) == (forge_node, forge_block, forge_row):
                bad = bytearray(s)
                bad[0] ^= 0x5A
                s = bytes(bad)
            pub[i] = np.frombuffer(sk.pub_key().bytes(), dtype=np.uint8)
            sig[i] = np.frombuffer(s, dtype=np.uint8)
            msgs.append(m)
            offsets[i + 1] = offsets[i] + len(m)
        return EntryBlock(
            pub, sig, b"".join(msgs), offsets,
            val_idx=np.arange(spb, dtype=np.int32), epoch_key=epoch)

    # pre-sign everything once (purepy signing is slow) and reuse the
    # SAME blocks across the solo and shared phases — parity by identity
    blocks = {node: [build_block(node, b) for b in range(bpn)]
              for node in nodes}

    def launches() -> int:
        return sum(1 for name, *_ in tr.TRACER.events()
                   if name == "pipeline.dispatch")

    real_prepare = pl.AsyncBatchVerifier._prepare
    pl.AsyncBatchVerifier._prepare = staticmethod(
        slow_prepare(real_prepare, resolve_delay))
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    tr.TRACER.clear()
    tr.configure(enabled=True)
    try:
        # -- solo baselines: each node verifies its own blocks ------------
        # Arrivals are PACED (one block per `spacing`, like a live node's
        # request stream) in both phases: a solo node's trickle has no
        # coalescing partner, while the shared fleet sees both nodes'
        # streams and fuses across them — that cross-node fusion is the
        # whole economics of the fleet.
        spacing = 0.10
        solo_verdicts = {}
        solo_launches = {}
        for node in nodes:
            v = pl.AsyncBatchVerifier(depth=2, pool_depth=OVERLAP_POOL_DEPTH)
            try:
                before = launches()
                futs = []
                for i, blk in enumerate(blocks[node]):
                    futs.append(v.submit(blk, flow=1000 + i))
                    time.sleep(spacing)
                solo_verdicts[node] = [
                    np.asarray(f.result(timeout=300), dtype=bool)
                    for f in futs]
                solo_launches[node] = launches() - before
                drain_pool(v._pool)
            finally:
                v.close()
        solo_total = sum(solo_launches.values())
        print(f"  solo launches              : "
              f"{solo_launches['node-a']} + {solo_launches['node-b']} "
              f"= {solo_total} ({bpn} blocks each, one every "
              f"{spacing * 1e3:.0f} ms)")

        # -- shared fleet: both nodes through ONE server ------------------
        v = pl.AsyncBatchVerifier(depth=2, pool_depth=OVERLAP_POOL_DEPTH)
        srv = FleetServer(verifier=v).start()
        port = srv.addr[1]
        clients = {node: FleetClient(srv.addr, name=node, lane=node,
                                     timeout_ms=60_000, rejoin_ms=100)
                   for node in nodes}
        try:
            before = launches()
            futs = []
            for b in range(bpn):  # same per-node pacing as the solo phase
                for ni, node in enumerate(nodes):
                    futs.append((node, b, clients[node].submit(
                        blocks[node][b], flow=2000 + 100 * ni + b)))
                time.sleep(spacing)
            shared_verdicts = {node: [None] * bpn for node in nodes}
            for node, b, f in futs:
                shared_verdicts[node][b] = np.asarray(
                    f.result(timeout=300), dtype=bool)
            shared_launches = launches() - before
            print(f"  shared-fleet launches      : {shared_launches} "
                  f"({2 * bpn} blocks, 2 nodes, one server)")
            if shared_launches >= solo_total:
                print(f"  FAIL: {shared_launches} launches through the "
                      f"shared fleet vs {solo_total} solo — no cross-node "
                      f"coalescing", file=sys.stderr)
                rc = 1

            # -- verdict parity + blame demux ----------------------------
            mism = [
                (node, b)
                for node in nodes for b in range(bpn)
                if not np.array_equal(shared_verdicts[node][b],
                                      solo_verdicts[node][b])
            ]
            rejected = [
                (node, b, i)
                for node in nodes for b in range(bpn)
                for i in np.flatnonzero(~shared_verdicts[node][b])
            ]
            print(f"  verdict parity vs solo     : "
                  f"{'OK' if not mism else f'MISMATCH {mism}'}")
            print(f"  rejections                 : {rejected} "
                  f"(forged: {(forge_node, forge_block, forge_row)})")
            if mism:
                rc = 1
            if rejected != [(forge_node, forge_block, forge_row)]:
                print("  FAIL: the forged signature must be the ONLY "
                      "rejection, demuxed to the right node/row",
                      file=sys.stderr)
                rc = 1

            # -- failover: kill the server mid-window --------------------
            futs = [(node, b, clients[node].submit(blocks[node][b],
                                                   flow=3000 + b))
                    for b in range(bpn) for node in nodes]
            srv.stop()
            lost, fellback = 0, 0
            for node, b, f in futs:
                try:
                    got = np.asarray(f.result(timeout=120), dtype=bool)
                except FleetUnavailable:
                    # graceful degradation: host path, same verdicts
                    fellback += 1
                    blk = blocks[node][b]
                    got = np.asarray(
                        [ed.verify_zip215_fast(*blk.entry(i))
                         for i in range(len(blk))], dtype=bool)
                except Exception:  # noqa: BLE001 — any other loss counts
                    lost += 1
                    continue
                if not np.array_equal(got, solo_verdicts[node][b]):
                    lost += 1
            print(f"  fleet kill mid-window      : {len(futs)} in flight, "
                  f"{fellback} fell back to host, {lost} lost")
            if lost != 0 or fellback == 0:
                print("  FAIL: a fleet kill must lose ZERO items (host "
                      "fallback) and at least one request must have been "
                      "cut over", file=sys.stderr)
                rc = 1

            # -- rejoin: same port, fresh server -------------------------
            srv2 = FleetServer(addr=("127.0.0.1", port), verifier=v).start()
            try:
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    if all(c.connected for c in clients.values()):
                        break
                    time.sleep(0.02)
                rejoined = all(c.connected for c in clients.values())
                rejoins = {n: c.stats()["rejoins"]
                           for n, c in clients.items()}
                post = np.asarray(
                    clients["node-a"].submit(
                        blocks["node-a"][0], flow=4000).result(timeout=120),
                    dtype=bool)
                print(f"  rejoin after restart       : connected="
                      f"{rejoined} rejoins={rejoins}")
                if not rejoined or any(r < 1 for r in rejoins.values()):
                    print("  FAIL: clients must redial a restarted fleet "
                          "host automatically", file=sys.stderr)
                    rc = 1
                if not np.array_equal(post, solo_verdicts["node-a"][0]):
                    print("  FAIL: post-rejoin verdicts diverged",
                          file=sys.stderr)
                    rc = 1
            finally:
                srv2.stop()
        finally:
            for c in clients.values():
                c.close()
            srv.stop()
            drain_pool(v._pool)
            pool = v._pool.stats()
            v.close()

        # -- pool hygiene ------------------------------------------------
        print(f"  pool                       : {pool}")
        if pool["in_flight"] != 0:
            print(f"  FAIL: {pool['in_flight']} pool slots leaked",
                  file=sys.stderr)
            rc = 1
    finally:
        tr.configure(enabled=False)
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        pl.AsyncBatchVerifier._prepare = real_prepare
    return rc


def run_soak(args) -> int:
    """--soak: the round-16 soak-harness gate on a mocked device (verdicts
    come back all-accept with NO kernel — this gate checks the HARNESS,
    not the crypto). Asserts the three properties the soak driver must
    hold before its artifacts are trusted:

      cadence    the telemetry sampler ticks on SimClock cadence — two
                 same-seed mini-soaks produce the SAME tick count, and
                 that count matches duration/cadence (the sampler must
                 never free-run on wall time)
      replay     same-seed runs are replay-exact: identical cluster
                 fingerprint and network schedule digest (the soak loop
                 must not leak wall-clock reads into the trajectory)
      no leak    zero buffer-pool slots in flight once the shared
                 verifier drains, and tmlint's determinism rules stay at
                 0 findings with simnet/soak.py in scope
    """
    import math

    from tendermint_tpu.ops import pipeline as pl
    from tendermint_tpu.ops._testing import drain_pool, mock_mempool_prepare
    from tendermint_tpu.simnet.soak import SoakConfig
    from tendermint_tpu.simnet.soak import run_soak as _run_soak

    duration, cadence, rtt_ms = 6.0, 1.0, 2.0
    print(f"prep_bench --soak: duration={duration}vs cadence={cadence}s "
          f"runs=2 rtt={rtt_ms}ms device=mocked")
    rc = 0

    real_prepare = pl.AsyncBatchVerifier._prepare
    pl.AsyncBatchVerifier._prepare = staticmethod(
        mock_mempool_prepare(real_prepare, rtt_ms / 1e3)
    )
    os.environ["TM_TPU_FORCE_DEVICE"] = "1"
    results, pools = [], []
    try:
        for _ in range(2):
            v = pl.AsyncBatchVerifier(depth=2)
            try:
                cfg = SoakConfig(duration_s=duration, seed=7,
                                 sample_every_s=cadence, max_wall_s=120.0)
                results.append(_run_soak(v, cfg))
                drain_pool(v._pool)
                pools.append(v._pool.stats())
            finally:
                v.close()
    finally:
        os.environ.pop("TM_TPU_FORCE_DEVICE", None)
        pl.AsyncBatchVerifier._prepare = real_prepare

    a, b = results

    # -- sampler cadence determinism -------------------------------------
    expect = math.floor(duration / cadence)
    print(f"  sampler ticks              : {a['sampler_ticks']} / "
          f"{b['sampler_ticks']} (expect ~{expect})")
    if a["sampler_ticks"] != b["sampler_ticks"]:
        print(f"  FAIL: tick count diverged across same-seed runs "
              f"({a['sampler_ticks']} vs {b['sampler_ticks']})",
              file=sys.stderr)
        rc = 1
    if abs(a["sampler_ticks"] - expect) > 1:
        print(f"  FAIL: {a['sampler_ticks']} ticks for {duration}s at "
              f"{cadence}s cadence (expect {expect}±1) — sampler is not "
              f"riding SimClock", file=sys.stderr)
        rc = 1

    # -- replay exactness ------------------------------------------------
    exact = (a["fingerprint"] == b["fingerprint"]
             and a["schedule_digest"] == b["schedule_digest"])
    print(f"  replay exact               : {exact} "
          f"(fp={a['fingerprint'][:16]}… heights={a['heights']})")
    if not exact:
        print("  FAIL: same-seed soak runs diverged — a wall-clock read "
              "leaked into the trajectory", file=sys.stderr)
        rc = 1
    for i, r in enumerate(results):
        if not r["ok"]:
            print(f"  FAIL: run {i} verdict not ok: {r.get('reason')}",
                  file=sys.stderr)
            rc = 1

    # -- pool hygiene ----------------------------------------------------
    for i, pool in enumerate(pools):
        print(f"  pool (run {i})               : {pool}")
        if pool["in_flight"] != 0:
            print(f"  FAIL: {pool['in_flight']} pool slots leaked",
                  file=sys.stderr)
            rc = 1

    # -- tmlint: soak.py is inside the determinism scope -----------------
    from tools.tmlint.__main__ import main as tmlint_main
    lint_rc = tmlint_main([])
    print(f"  tmlint tree gate           : rc={lint_rc} "
          f"(simnet/soak.py in scope)")
    if lint_rc != 0:
        print("  FAIL: tmlint found new findings with soak harness in "
              "scope", file=sys.stderr)
        rc = 1
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sigs", type=int, default=10_000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument(
        "--native",
        action="store_true",
        help="keep the native module (default: TM_TPU_NO_NATIVE=1 to bench "
        "the pure-Python fallback, the acceptance configuration)",
    )
    ap.add_argument(
        "--fused",
        action="store_true",
        help="round-6 gate: fused columnar-from-decode path vs the PR-2 "
        "columnar path (arg parity enforced, speedup gated)",
    )
    ap.add_argument(
        "--transfer",
        action="store_true",
        help="round-7 gate: warm-epoch H2D bytes <= 0.5x cold-epoch and "
        "cached per-signature prep >= 1.3x the PR-4 prep",
    )
    ap.add_argument(
        "--overlap",
        action="store_true",
        help="round-8 gate: dispatcher issues batch k+1's H2D transfer "
        "before blocking on kernel k (span-order proxy with a slow mock "
        "readback) and the buffer pool keeps steady-state allocations flat",
    )
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="round-9 gate: mesh-dispatcher lane packing on a mocked "
        "2-lane mesh — pack/demux parity + blame, pure-pad-lane plan "
        "shape, zero slot leak, single device owner, superbatch overlap",
    )
    ap.add_argument(
        "--light",
        action="store_true",
        help="round-11 gate: light-service batched verification on a "
        "mocked device — cross-request same-epoch coalescing by launch "
        "count, verdict/blame parity vs the sequential verifier, memoized "
        "resubmission launches nothing, zero pool-slot leak",
    )
    ap.add_argument(
        "--ingress",
        action="store_true",
        help="round-13 gate: device-batched mempool CheckTx on a mocked "
        "device — N flooded txs fuse into <= K launches, a mid-flood "
        "consensus batch preempts queued ingress work, a forged tx "
        "resolves FALSE (never dropped), zero pool-slot leak",
    )
    ap.add_argument(
        "--replay",
        action="store_true",
        help="round-14 gate: range-batched blocksync replay on a mocked "
        "device — W same-epoch heights fuse into ceil(W*sigs/bucket) "
        "launches, a forged commit mid-range falls back per-height with "
        "verify_commit_light's exact error, zero pool-slot leak",
    )
    ap.add_argument(
        "--votes",
        action="store_true",
        help="round-15 gate: device-batched live-vote ingress on a mocked "
        "device — N gossiped votes fuse into <= K launches, a forged "
        "signature mid-flood is the ONLY rejection, zero pool-slot leak",
    )
    ap.add_argument(
        "--fabric",
        action="store_true",
        help="round-17 gate: the unified ingress fabric on a mocked device "
        "— four lane patterns on ONE scheduler + completer thread, the "
        "adaptive window deepens under flood AND shrinks back when idle, "
        "a forged signature is the only rejection, zero pool-slot leak",
    )
    ap.add_argument(
        "--fleet",
        action="store_true",
        help="round-18 gate: the network-facing verification fleet on a "
        "mocked device over REAL loopback sockets — two client nodes' "
        "same-epoch blocks coalesce into fewer launches than solo, the "
        "one forged signature demuxes to the right node/row, a mid-window "
        "fleet kill loses zero items (host fallback) and a restarted "
        "server is rejoined, zero pool-slot leak",
    )
    ap.add_argument(
        "--schemes",
        action="store_true",
        help="round-19 gate: scheme-keyed verification lanes — a mixed "
        "ed25519+secp256k1 commit verifies in ONE superbatch launch with "
        "verdicts and blame byte-identical to the sequential walk, and "
        "the secp device lane matches the host per-signature loop "
        "bit-for-bit (incl. non-lower-S rejection)",
    )
    ap.add_argument(
        "--bls",
        action="store_true",
        help="round-20 gate: the BLS12-381 aggregation lane — K "
        "aggregated commits (one signature + signer bitmap each) verify "
        "in ONE fused multi-pairing launch with verdict codes and blame "
        "byte-identical to the pure-Python reference, incl. crafted "
        "non-subgroup G1/G2 points and the pre-crypto bitmap reject; an "
        "ed25519+secp256k1+bls three-lane superbatch is one launch",
    )
    ap.add_argument(
        "--soak",
        action="store_true",
        help="round-16 gate: soak-harness hygiene on a mocked device — "
        "sampler ticks on SimClock cadence, same-seed runs replay-exact, "
        "zero pool-slot leak, tmlint clean with simnet/soak.py in scope",
    )
    args = ap.parse_args()
    if args.fused:
        return run_fused(args)
    if args.transfer:
        return run_transfer(args)
    if args.overlap:
        return run_overlap(args)
    if args.mesh:
        return run_mesh(args)
    if args.schemes:
        return run_schemes(args)
    if args.bls:
        return run_bls(args)
    if args.light:
        return run_light(args)
    if args.ingress:
        return run_ingress(args)
    if args.replay:
        return run_replay(args)
    if args.votes:
        return run_votes(args)
    if args.fabric:
        return run_fabric(args)
    if args.fleet:
        return run_fleet(args)
    if args.soak:
        return run_soak(args)

    from tendermint_tpu.native import load as _load_native
    from tendermint_tpu.ops import backend, pipeline
    from tendermint_tpu.ops.entry_block import EntryBlock

    chain_id = "prep-bench"
    vset, commit = build_synthetic_commit(args.sigs)
    needed = vset.total_voting_power() * 2 // 3
    bucket = backend._bucket_for(args.sigs)
    native = _load_native()
    print(
        f"prep_bench: n={args.sigs} bucket={bucket} reps={args.reps} "
        f"native={'yes' if native is not None else 'no'} "
        f"backend={os.environ.get('JAX_PLATFORMS', '?')}"
    )

    def run(fn):
        times = []
        for _ in range(args.reps):
            # fresh sign-bytes template cache per rep: both paths pay the
            # one-time template build identically
            commit._sb_tpl = None
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    # The pipeline's prep on this (CPU/XLA) config (backend.select_kernel):
    # prepare_batch, challenges hashed on the host. The acceptance gate.
    results = {}
    for name, prep in (
        ("pipeline prep", backend.prepare_batch),
    ):
        t_tuple = run(
            lambda p=prep: p(
                commit_entries_tuples(chain_id, vset, commit, needed), bucket
            )
        )
        t_block = run(
            lambda p=prep: p(
                pipeline.commit_entries(chain_id, vset, commit, needed)[0],
                bucket,
            )
        )
        # parity spot-check while we're here: identical kernel args
        commit._sb_tpl = None
        a_t = prep(commit_entries_tuples(chain_id, vset, commit, needed), bucket)
        commit._sb_tpl = None
        a_b = prep(
            pipeline.commit_entries(chain_id, vset, commit, needed)[0], bucket
        )
        parity = all(np.array_equal(x, y) for x, y in zip(a_t, a_b))
        speedup = t_tuple / t_block if t_block else float("inf")
        results[name] = (t_tuple, t_block, speedup, parity)
        print(f"  {name}:")
        print(f"    tuple-list baseline : {t_tuple * 1e3:9.2f} ms")
        print(f"    EntryBlock columnar : {t_block * 1e3:9.2f} ms")
        print(f"    speedup             : {speedup:9.2f}x")
        print(f"    arg parity          : {'OK' if parity else 'MISMATCH'}")

    # no speed floor: the 2x one was the RAM-block prep's (ISSUE 30)
    return 0 if all(r[3] for r in results.values()) else 2


if __name__ == "__main__":
    sys.exit(main())
