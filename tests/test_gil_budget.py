"""GIL-budget regression gate (VERDICT item 6, tightened for round 6).

Measures the host-side (non-device) prep cost of a 10k-signature
verify_commit on the pure-Python CPU fallback — now the FUSED
columnar-from-decode path (ops/commit_prep.py): the commit decodes
straight into CommitBlock columns and one call does selection + tally +
sign-bytes + pub/sig gather + the device-hash RAM blocks. Gates:

  absolute   the full decode-to-kernel-args path (fused commit_entries ->
             prepare_batch_device_hash) for 10k sigs must stay under
             GIL_BUDGET_MS_10K = 60 ms (PR 3's gate was 150 ms against
             the PR-2 path; measured ~20 ms here on the dev container)

  relative   the stages the fused prep RESTRUCTURED — commit-side prep +
             SHA RAM-block construction — must cost <= 0.5x the PR-2
             implementation of the same stages (commit_entries object
             walk + vote_sign_bytes_block + pad_ram_block's flat scatter
             + shift-or word packing, pinned VERBATIM in the subprocess
             script: the in-tree fallback has since absorbed some of
             round 6's shared optimizations, so gating against it would
             undercount the representation change being guarded).
             Measured ~0.31x on the dev container.

  parity     both paths must produce bit-identical kernel args — the
             verdict/blame equivalence of the fused path rests on it
             (tests/test_commit_block.py covers verdict/blame parity at
             the verify_commit level).

The measurement runs in a subprocess: it needs TM_TPU_PUREPY_CRYPTO=1
(containers without the OpenSSL wheel) + TM_TPU_NO_NATIVE=1 (isolate the
pure-Python path — the gate must hold even where the native module isn't
built), and neither env var may leak into the main pytest process."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

GIL_BUDGET_MS_10K = 60.0
RELATIVE_GATE = 0.5
N_SIGS = 10_000

_SCRIPT = r"""
import importlib.util, json, sys, time

import numpy as np

spec = importlib.util.spec_from_file_location(
    "prep_bench", %(prep_bench)r
)
pb = importlib.util.module_from_spec(spec)
spec.loader.exec_module(pb)

from tendermint_tpu.ops import backend, pipeline
from tendermint_tpu.ops import sha512 as sha
from tendermint_tpu.types.block import Commit

chain_id = "gil-budget"
vset, commit = pb.build_synthetic_commit(%(n_sigs)d)
needed = vset.total_voting_power() * 2 // 3
bucket = backend._bucket_for(%(n_sigs)d)
# columnar-from-decode: the wire round-trip is what fills the CommitBlock
dec = Commit.decode(commit.encode())
assert dec.commit_block() is not None, "decode did not produce columns"

MAX_LEN = 64 + backend.DEVICE_HASH_MAX_MSG


def full_fused():
    dec._sb_tpl = None  # fresh sign-bytes template per rep
    blk, _ = pipeline.commit_entries(chain_id, vset, dec, needed)
    return backend.prepare_batch_device_hash(blk, bucket)


def stage_fused():
    dec._sb_tpl = None
    blk, _ = pipeline.commit_entries(chain_id, vset, dec, needed)
    assert blk.ram_hi is not None, "fused prep did not fill RAM columns"
    return sha.pad_ram_rows(blk, bucket, MAX_LEN)


# ---- the PR-2 implementation of the same stages, pinned verbatim ----

def _buf_to_words_pr2(buf, bsz, nblock):
    words = buf.reshape(bsz, nblock, 16, 8)
    hi = ((words[..., 0].astype(np.uint32) << 24)
          | (words[..., 1].astype(np.uint32) << 16)
          | (words[..., 2].astype(np.uint32) << 8)
          | words[..., 3].astype(np.uint32))
    lo = ((words[..., 4].astype(np.uint32) << 24)
          | (words[..., 5].astype(np.uint32) << 16)
          | (words[..., 6].astype(np.uint32) << 8)
          | words[..., 7].astype(np.uint32))
    return hi, lo


def pad_ram_block_pr2(block, bucket, max_len):
    nblock = (max_len + 17 + 127) // 128
    n = len(block)
    lens = np.full(bucket, 64, dtype=np.int64)
    buf = np.zeros((bucket, nblock * 128), dtype=np.uint8)
    if n:
        mbuf, offs = block.msgs_contiguous()
        offs = np.asarray(offs)
        mlens = np.diff(offs)
        lens[:n] = 64 + mlens
        buf[:n, :32] = block.sig[:, :32]
        buf[:n, 32:64] = block.pub
        total = int(mlens.sum())
        if total:
            flat = np.frombuffer(mbuf, dtype=np.uint8, count=total)
            rows = np.repeat(np.arange(n), mlens)
            cols = 64 + (np.arange(total) - np.repeat(offs[:-1], mlens))
            buf[rows, cols] = flat
    buf[n:, 0] = 1
    buf[n:, 32] = 1
    blocks = (lens + 17 + 127) // 128
    rng = np.arange(bucket)
    buf[rng, lens] = 0x80
    bitlen = lens * 8
    base = blocks * 128 - 8
    for j in range(8):
        buf[rng, base + j] = (bitlen >> (8 * (7 - j))) & 0xFF
    return _buf_to_words_pr2(buf, bucket, nblock) + (blocks.astype(np.int32),)


def stage_pr2():
    commit._sb_tpl = None
    blk, _ = pipeline.commit_entries_legacy(chain_id, vset, commit, needed)
    return pad_ram_block_pr2(blk, bucket, MAX_LEN)


def min_ms(fn, reps=5):
    fn()  # warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times)


# interleave the two stage measurements so machine noise hits both
fused_stage_times, pr2_stage_times = [], []
stage_fused(); stage_pr2()
for _ in range(5):
    t0 = time.perf_counter(); stage_fused()
    fused_stage_times.append((time.perf_counter() - t0) * 1e3)
    t0 = time.perf_counter(); stage_pr2()
    pr2_stage_times.append((time.perf_counter() - t0) * 1e3)

full_ms = min_ms(full_fused)

# arg parity: fused RAM rows (padded) vs the PR-2 pad, and the full
# kernel arg tuple vs the in-tree fallback path
hi_f, lo_f, cnt_f = stage_fused()
hi_p, lo_p, cnt_p = stage_pr2()
ram_parity = (np.array_equal(hi_f, hi_p) and np.array_equal(lo_f, lo_p)
              and np.array_equal(cnt_f, cnt_p))
dec._sb_tpl = None
args_f = backend.prepare_batch_device_hash(
    pipeline.commit_entries(chain_id, vset, dec, needed)[0], bucket)
commit._sb_tpl = None
args_p = backend.prepare_batch_device_hash(
    pipeline.commit_entries_legacy(chain_id, vset, commit, needed)[0],
    bucket)
arg_parity = all(np.array_equal(a, b) for a, b in zip(args_f, args_p))

print(json.dumps({
    "full_fused_ms": full_ms,
    "fused_stage_ms": min(fused_stage_times),
    "pr2_stage_ms": min(pr2_stage_times),
    "ram_parity": ram_parity,
    "arg_parity": arg_parity,
}))
"""


def test_10k_sig_verify_commit_prep_stays_in_budget():
    env = dict(
        os.environ,
        TM_TPU_PUREPY_CRYPTO="1",
        TM_TPU_NO_NATIVE="1",
        JAX_PLATFORMS="cpu",
    )
    script = _SCRIPT % {
        "prep_bench": os.path.join(REPO, "tools", "prep_bench.py"),
        "n_sigs": N_SIGS,
    }
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        env=env,
        cwd=REPO,
        timeout=60,
    )
    assert r.returncode == 0, (r.stderr or b"").decode(errors="replace")[-3000:]
    out = json.loads((r.stdout or b"").decode().strip().splitlines()[-1])
    assert out["ram_parity"], "fused RAM blocks diverge from the PR-2 pad"
    assert out["arg_parity"], "fused kernel args diverge from the fallback path"
    full, fused, pr2 = (
        out["full_fused_ms"], out["fused_stage_ms"], out["pr2_stage_ms"]
    )
    assert full <= GIL_BUDGET_MS_10K, (
        f"decode-to-kernel-args for {N_SIGS} sigs took {full:.1f} ms "
        f"(budget {GIL_BUDGET_MS_10K} ms) — the fused commit prep regressed"
    )
    assert fused <= pr2 * RELATIVE_GATE, (
        f"fused commit prep ({fused:.1f} ms) no longer beats the PR-2 "
        f"implementation of the same stages ({pr2:.1f} ms) by >= "
        f"{1 - RELATIVE_GATE:.0%}"
    )
