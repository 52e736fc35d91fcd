"""Fused commit prep — CommitBlock columns to kernel-ready arrays.

After the EntryBlock representation landed, the remaining
GIL-held host work per 10k-signature verify_commit was the stage BEFORE
the EntryBlock existed — per-signature flag selection and voting-power
tally, per-lane sign-bytes handling, and the entry build — ~26 ms that
serialized concurrent commits. The fix is the round-6 data-structure
change: commits are columnar FROM DECODE (types/block.py fills a
CommitBlock once; CommitSig objects are lazy views), and this module
turns those columns + the validator set's cached pub/power columns into
a dispatch-ready EntryBlock in ONE call:

    selection      flag predicate over the (n,) uint8 flags column
    tally          voting-power sum vs the 2/3 threshold (with the
                   reference's early-stop semantics for the light path)
    sign bytes     canonical vote sign-bytes for every selected lane
                   composed into one contiguous buffer + offset table
    gather         pub (m, 32) / sig (m, 64) rows fancy-indexed from the
                   cached columns

With the native module built the four stages are one C call
(tm_native.commit_prep_fused) in three timed sections — selection +
tally, the sign-bytes sizes, sign bytes + gather — with the outputs
allocated between them. It keeps the GIL through the two scans at every
size and gives it up in the third alone, from 1 024 selected rows (where
that section is spread over threads and runs for milliseconds); a
150-validator commit keeps the GIL from the first stage to the last, so
among many callers it queues for the interpreter once a prep and not
three times (PERF.md §6, PR 38). The numpy fallback below is
differentially tested against it and against the object paths. RLC
scalar prep stays in the per-batch fused native call
(tm_native.ed25519_rlc_prep): the random
z coefficients are drawn per DEVICE batch, and commits coalesce into
batches after this stage, so per-commit RLC scalars would pin the batch
composition before the coalescer has seen the traffic.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .. import native as _native
from ..observability.trace import span as _span
from .entry_block import CommitBlock, EntryBlock

# BlockIDFlag values (types/block.py) — re-declared to keep this module
# importable without the types layer (which imports us for decode)
FLAG_ABSENT = 1
FLAG_COMMIT = 2
FLAG_NIL = 3

# mode bits shared with the native entry point
MODE_SELECT_COMMIT_ONLY = 1
MODE_COUNT_FOR_BLOCK = 2
MODE_EARLY_STOP = 4


def select_and_tally(
    cblock: CommitBlock,
    power_col: np.ndarray,
    threshold: int,
    mode: int,
) -> Tuple[np.ndarray, int]:
    """Selection + voting-power tally over the flags column. Returns
    (sel_idx (m,) int64, tallied). Semantics mirror validation.go:152's
    loop exactly: early-stop keeps the lane that crosses the threshold,
    count-for-block tallies only COMMIT lanes while still selecting NIL
    lanes for verification."""
    flags = cblock.flags
    if mode & MODE_SELECT_COMMIT_ONLY:
        sel = np.flatnonzero(flags == FLAG_COMMIT).astype(np.int64)
    else:
        sel = np.flatnonzero(flags != FLAG_ABSENT).astype(np.int64)
    if sel.size == 0:
        return sel, 0
    if mode & MODE_EARLY_STOP:
        counted = power_col[sel]
        if mode & MODE_COUNT_FOR_BLOCK:
            counted = counted * (flags[sel] == FLAG_COMMIT)
        cum = np.cumsum(counted)
        k = int(np.searchsorted(cum, threshold, side="right"))
        if k < sel.size:
            return sel[: k + 1], int(cum[k])
        return sel, int(cum[-1])
    if mode & MODE_COUNT_FOR_BLOCK:
        tallied = int(power_col[flags == FLAG_COMMIT].sum())
    else:
        tallied = int(power_col[sel].sum())
    return sel, tallied


def _compose_selected(
    cblock: CommitBlock,
    sel: np.ndarray,
    prefix_commit: bytes,
    prefix_nil: bytes,
    suffix: bytes,
) -> Tuple[memoryview, np.ndarray]:
    """Sign bytes for the selected lanes, in selection order, as ONE
    (zero-copy buffer view, (m+1,) int64 offsets) pair. Lanes group by
    flag (at most two groups — COMMIT and NIL — per verify_commit
    selection); a mixed selection composes per group and merges by lane
    order."""
    from ..wire.canonical import compose_vote_sign_bytes_cols

    secs = cblock.ts_seconds[sel]
    nanos = cblock.ts_nanos[sel]
    flags = cblock.flags[sel]
    nil_rows = np.flatnonzero(flags == FLAG_NIL)
    if nil_rows.size == 0:
        buf, offsets, _groups = compose_vote_sign_bytes_cols(
            (prefix_commit, suffix), secs, nanos, with_groups=True
        )
        return memoryview(buf), offsets
    commit_rows = np.flatnonzero(flags != FLAG_NIL)
    composed = []
    lens = np.zeros(sel.size, dtype=np.int64)
    for rows, prefix in ((commit_rows, prefix_commit),
                         (nil_rows, prefix_nil)):
        _buf, offs, rec_groups = compose_vote_sign_bytes_cols(
            (prefix, suffix), secs[rows], nanos[rows], with_groups=True
        )
        composed.append((rows, rec_groups))
        lens[rows] = np.diff(offs)
    # merge the two group buffers back into lane order (grouped 2-D
    # copies by record length — a handful of distinct lengths)
    offsets = np.zeros(sel.size + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    out = np.empty(int(offsets[-1]), dtype=np.uint8)
    for rows, rec_groups in composed:
        for g_rows, arr2d in rec_groups:
            dst = offsets[:-1][rows[g_rows]][:, None] + np.arange(
                arr2d.shape[1]
            )
            out[dst] = arr2d
    return memoryview(out), offsets


def prep_commit_from(
    commit,
    vals,
    chain_id: str,
    threshold: int,
    mode: int,
    scheme: str = "ed25519",
) -> Optional[Tuple[np.ndarray, int, Optional[EntryBlock]]]:
    """The shared fused-path entry for commit-level callers
    (types/validation and ops/pipeline): columnar-eligibility checks
    (CommitBlock present, validator columns of `scheme` — ed25519 or
    sr25519, both 32-byte keys — matching the commit size) + per-flag
    template fetch + prep_commit. Returns None when this commit/valset is
    not columnar-representable — callers fall back to the object path
    and its exact legacy errors. An sr25519 block carries its scheme and
    no epoch table (the tables hold decompressed edwards keys).

    Spans, inside the caller's (verify_commit.prep_fused or
    pipeline.commit_prep_fused): ops.commit_prep.columns up to the fused
    call, ops.commit_prep.native for each of its three sections and
    ops.commit_prep.gil after one that gave the GIL up
    (native.traced_call), ops.commit_prep.block after it."""
    with _span("ops.commit_prep.columns"):
        cblock = commit.commit_block()
        if cblock is None:
            return None
        cols = (vals.ed25519_columns() if scheme == "ed25519"
                else vals.sr25519_columns())
        if cols is None or cols[0].shape[0] != cblock.n:
            return None
        tpl_c = commit.sign_bytes_template(chain_id, FLAG_COMMIT)
        tpl_n = commit.sign_bytes_template(chain_id, FLAG_NIL)
        args = _contiguous(cblock, cols[0], cols[1])
    res = _fused(*args, tpl_c[0], tpl_n[0], tpl_c[1], threshold, mode)
    with _span("ops.commit_prep.block"):
        sel, tallied, block = _entry_block(res)
        if block is not None and scheme != "ed25519":
            block.scheme = scheme
        elif block is not None:
            # epoch-cache metadata: sel IS the valset row of each lane;
            # table_rows names the device table the set gathers from and
            # the lanes' rows there. The key is only attached for WARM sets
            # (ops/epoch_cache.py) — downstream preps then ship gather
            # indices instead of pubkey-derived arrays. A disabled cache
            # returns None and the block is exactly what PR 4 produced.
            from . import epoch_cache as _epoch

            block.epoch_key, block.val_idx = _epoch.table_rows(
                vals, sel.astype(np.int32))
    return sel, tallied, block


def prep_commit(
    cblock: CommitBlock,
    pub_col: np.ndarray,
    power_col: np.ndarray,
    prefix_commit: bytes,
    prefix_nil: bytes,
    suffix: bytes,
    threshold: int,
    mode: int,
) -> Tuple[np.ndarray, int, Optional[EntryBlock]]:
    """The fused commit prep: returns (sel_idx, tallied, EntryBlock or
    None). The block is None exactly when tallied <= threshold — the
    caller raises ErrNotEnoughVotingPowerSigned without any sign-bytes
    work having happened, matching the object path's ordering.

    Native path: ONE call does all four stages, in three timed sections
    of which the last gives the GIL up from 1 024 selected rows
    (tm_native.commit_prep_fused); numpy fallback below is
    differentially tested (tests/test_commit_block.py)."""
    return _entry_block(_fused(
        *_contiguous(cblock, pub_col, power_col),
        prefix_commit, prefix_nil, suffix, threshold, mode))


def _contiguous(cblock: CommitBlock, pub_col, power_col) -> tuple:
    """(cblock, the six columns the native entry reads, as buffers)."""
    return (
        cblock,
        np.ascontiguousarray(cblock.flags),
        np.ascontiguousarray(cblock.sig),
        np.ascontiguousarray(cblock.ts_seconds),
        np.ascontiguousarray(cblock.ts_nanos),
        np.ascontiguousarray(pub_col),
        np.ascontiguousarray(power_col),
    )


def _fused(cblock, flags, sig, ts_seconds, ts_nanos, pub_col, power_col,
           prefix_commit, prefix_nil, suffix, threshold, mode):
    """The four stages over _contiguous's columns: tm_native's result
    tuple, (sel, tallied) or (sel, tallied, pub, sig, msgs, offsets) as
    bytes, or, without the module, _prep_commit_numpy's triple."""
    native = _native.load()
    if native is not None and hasattr(native, "commit_prep_fused"):
        return _native.traced_call(
            native, "commit_prep_fused", "ops.commit_prep",
            flags, sig, ts_seconds, ts_nanos, pub_col, power_col,
            prefix_commit, prefix_nil, suffix, threshold, mode)
    return _prep_commit_numpy(
        cblock, pub_col, power_col, prefix_commit, prefix_nil, suffix,
        threshold, mode)


def _entry_block(res) -> Tuple[np.ndarray, int, Optional[EntryBlock]]:
    """_fused's result as (sel_idx, tallied, EntryBlock or None)."""
    if isinstance(res[0], np.ndarray):  # the numpy fallback's own triple
        return res
    sel = np.frombuffer(res[0], dtype=np.int64)
    tallied = int(res[1])
    if len(res) == 2:
        return sel, tallied, None
    pub_b, sig_b, msgs, offs_b = res[2:]
    m = sel.shape[0]
    return sel, tallied, EntryBlock(
        np.frombuffer(pub_b, dtype=np.uint8).reshape(m, 32),
        np.frombuffer(sig_b, dtype=np.uint8).reshape(m, 64),
        msgs,
        np.frombuffer(offs_b, dtype=np.int64),
    )


def _prep_commit_numpy(
    cblock: CommitBlock,
    pub_col: np.ndarray,
    power_col: np.ndarray,
    prefix_commit: bytes,
    prefix_nil: bytes,
    suffix: bytes,
    threshold: int,
    mode: int,
) -> Tuple[np.ndarray, int, Optional[EntryBlock]]:
    """Vectorized fallback — identical outputs to the native call."""
    sel, tallied = select_and_tally(cblock, power_col, threshold, mode)
    if tallied <= threshold:
        return sel, tallied, None
    msgs_buf, offsets = _compose_selected(
        cblock, sel, prefix_commit, prefix_nil, suffix
    )
    return sel, tallied, EntryBlock(
        pub_col[sel], cblock.sig[sel], msgs_buf, offsets
    )
