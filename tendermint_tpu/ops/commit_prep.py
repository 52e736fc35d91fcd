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
    RAM blocks     the same bytes laid straight into the device-hash
                   kernel's padded SHA-512 R||A||M word layout
                   (EntryBlock ram_* columns), so the downstream batch
                   prep skips its scatter entirely
    gather         pub (m, 32) / sig (m, 64) rows fancy-indexed from the
                   cached columns

With the native module built the whole thing is one GIL-released C call
(tm_native.commit_prep_fused); the numpy fallback below is differentially
tested against it and against the object paths. RLC scalar prep stays in
the per-batch fused native call (tm_native.ed25519_rlc_prep): the random
z coefficients are drawn per DEVICE batch, and commits coalesce into
batches after this stage, so per-commit RLC scalars would pin the batch
composition before the coalescer has seen the traffic.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

from .entry_block import CommitBlock, EntryBlock

# Messages up to this size hash on-device (single source of truth —
# ops.backend re-exports it). Importable without jax: the types layer
# reads RAM_MAX_LEN at verify time to size the fused prep's RAM columns.
DEVICE_HASH_MAX_MSG = int(os.environ.get("TM_TPU_DEVICE_HASH_MAX_MSG", "192"))
RAM_MAX_LEN = 64 + DEVICE_HASH_MAX_MSG

# BlockIDFlag values (types/block.py) — re-declared to keep this module
# importable without the types layer (which imports us for decode)
FLAG_ABSENT = 1
FLAG_COMMIT = 2
FLAG_NIL = 3

# mode bits shared with the native entry point
MODE_SELECT_COMMIT_ONLY = 1
MODE_COUNT_FOR_BLOCK = 2
MODE_EARLY_STOP = 4

# device-hash RAM layout: R(32) || A(32) || M padded into SHA-512 blocks
# (ops/sha512.pad_ram_block). 17 = 0x80 terminator + 16B length field
# floor of what one extra block must fit.
_RAM_HDR = 64


def ram_nblock(max_len: int) -> int:
    return (max_len + 17 + 127) // 128


def scatter_rows_by_length(buf: np.ndarray, col0: int, flat: np.ndarray,
                           offsets: np.ndarray, lens: np.ndarray) -> None:
    """Copy variable-length records flat[offsets[i]:offsets[i]+lens[i]]
    into buf[i, col0:col0+lens[i]] via grouped 2-D gathers by record
    length (a commit's sign bytes have a handful of distinct lengths) —
    ~2.5x cheaper than a flat row/col scatter at 10k messages. Shared by
    _fill_ram's no-groups fallback and sha512.pad_ram_block."""
    base = offsets[: len(lens)]
    for length in np.unique(lens):
        if length == 0:
            continue
        rows = np.flatnonzero(lens == length)
        src = base[rows][:, None] + np.arange(length)
        buf[rows[:, None], col0 + np.arange(length)[None, :]] = flat[src]


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
) -> Tuple[memoryview, np.ndarray, list]:
    """Sign bytes for the selected lanes, in selection order, as ONE
    (zero-copy buffer view, (m+1,) int64 offsets) pair, plus the per-group
    (rows, (g, rec_len) 2-D record array) list so _fill_ram can lay the
    same bytes into SHA blocks without re-gathering from the flat
    buffer. Lanes group by flag (at most two groups — COMMIT and NIL —
    per verify_commit selection); a mixed selection composes per group
    and merges by lane order."""
    from ..wire.canonical import compose_vote_sign_bytes_cols

    secs = cblock.ts_seconds[sel]
    nanos = cblock.ts_nanos[sel]
    flags = cblock.flags[sel]
    nil_rows = np.flatnonzero(flags == FLAG_NIL)
    m = sel.size
    if nil_rows.size == 0:
        flag_groups = [(None, prefix_commit, secs, nanos)]
    else:
        commit_rows = np.flatnonzero(flags != FLAG_NIL)
        flag_groups = [
            (commit_rows, prefix_commit, secs[commit_rows],
             nanos[commit_rows]),
            (nil_rows, prefix_nil, secs[nil_rows], nanos[nil_rows]),
        ]
    lens = np.zeros(m, dtype=np.int64)
    composed = []
    for rows, prefix, s, nn in flag_groups:
        buf, offs, rec_groups = compose_vote_sign_bytes_cols(
            (prefix, suffix), s, nn, with_groups=True
        )
        composed.append((rows, buf, offs, rec_groups))
        if rows is None:
            lens = np.diff(offs)
        else:
            lens[rows] = np.diff(offs)
    groups_out = []
    if len(composed) == 1 and composed[0][0] is None:
        _rows, buf, offsets, rec_groups = composed[0]
        groups_out.extend(rec_groups)
        return memoryview(buf), offsets, groups_out
    # merge the two group buffers back into lane order (grouped 2-D
    # copies by record length — a handful of distinct lengths)
    offsets = np.zeros(m + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    out = np.empty(int(offsets[-1]), dtype=np.uint8)
    for rows, _buf, offs, rec_groups in composed:
        for g_rows, arr2d in rec_groups:
            global_rows = g_rows if rows is None else rows[g_rows]
            length = arr2d.shape[1]
            dst = offsets[:-1][global_rows][:, None] + np.arange(length)
            out[dst] = arr2d
            groups_out.append((global_rows, arr2d))
    return memoryview(out), offsets, groups_out


def _fill_ram(
    msgs_buf,
    offsets: np.ndarray,
    pub_rows: np.ndarray,
    sig_rows: np.ndarray,
    max_len: int,
    groups: Optional[list] = None,
) -> Optional[tuple]:
    """Per-row device-hash SHA blocks: R||A||M padded + length-closed,
    word-packed big-endian (ram_hi/ram_lo (m, nblock*16) uint32-valued +
    counts (m,) int32). `groups` are the composer's (rows, 2-D record
    array) pairs — the message bytes land via direct 2-D assignments
    instead of re-gathering from the flat buffer. The hi/lo outputs are
    big-endian VIEWS over the block buffer (no byteswap copy here); the
    single conversion happens when pad_ram_rows copies rows into the
    padded kernel arrays. Returns None when any message exceeds the
    layout — the generic prep then falls back to host hashing."""
    nblock = ram_nblock(max_len)
    m = pub_rows.shape[0]
    lens = np.diff(offsets)
    tot = lens + _RAM_HDR
    if m and int(tot.max()) > max_len:
        return None
    buf = np.zeros((m, nblock * 128), dtype=np.uint8)
    buf[:, :32] = sig_rows[:, :32]
    buf[:, 32:64] = pub_rows
    if groups is not None:
        for rows, arr2d in groups:
            buf[rows[:, None],
                _RAM_HDR + np.arange(arr2d.shape[1])[None, :]] = arr2d
    else:
        flat = np.frombuffer(msgs_buf, dtype=np.uint8)
        scatter_rows_by_length(buf, _RAM_HDR, flat, offsets, lens)
    rng = np.arange(m)
    buf[rng, tot] = 0x80
    blocks = (tot + 17 + 127) // 128
    bitlen = tot * 8
    base = blocks * 128 - 8
    # messages are < 8191 bytes, so only the low two length bytes are
    # ever nonzero — two scatters instead of eight
    buf[rng, base + 6] = (bitlen >> 8) & 0xFF
    buf[rng, base + 7] = bitlen & 0xFF
    # big-endian word split: each 8-byte group -> (hi, lo) uint32 views
    words = buf.view(">u4").reshape(m, nblock * 16, 2)
    return (
        words[:, :, 0],
        words[:, :, 1],
        blocks.astype(np.int32),
    )


def prep_commit_from(
    commit,
    vals,
    chain_id: str,
    threshold: int,
    mode: int,
    ram_max_len: int = RAM_MAX_LEN,
) -> Optional[Tuple[np.ndarray, int, Optional[EntryBlock]]]:
    """The shared fused-path entry for commit-level callers
    (types/validation and ops/pipeline): columnar-eligibility checks
    (CommitBlock present, all-ed25519 validator columns matching the
    commit size) + per-flag template fetch + prep_commit. Returns None
    when this commit/valset is not columnar-representable — callers fall
    back to the object path and its exact legacy errors."""
    cblock = commit.commit_block()
    if cblock is None:
        return None
    cols = vals.ed25519_columns()
    if cols is None or cols[0].shape[0] != cblock.n:
        return None
    tpl_c = commit.sign_bytes_template(chain_id, FLAG_COMMIT)
    tpl_n = commit.sign_bytes_template(chain_id, FLAG_NIL)
    sel, tallied, block = prep_commit(
        cblock,
        cols[0],
        cols[1],
        tpl_c[0],
        tpl_n[0],
        tpl_c[1],
        threshold,
        mode,
        ram_max_len,
    )
    if block is not None:
        # epoch-cache metadata: sel IS the valset row of each lane, and
        # the key is only attached for WARM epochs (ops/epoch_cache.py) —
        # downstream preps then ship gather indices instead of
        # pubkey-derived arrays. A disabled cache returns None and the
        # block is exactly what PR 4 produced.
        from . import epoch_cache as _epoch

        block.val_idx = sel.astype(np.int32)
        block.epoch_key = _epoch.note_valset(vals)
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
    ram_max_len: int = 0,
) -> Tuple[np.ndarray, int, Optional[EntryBlock]]:
    """The fused commit prep: returns (sel_idx, tallied, EntryBlock or
    None). The block is None exactly when tallied <= threshold — the
    caller raises ErrNotEnoughVotingPowerSigned without any sign-bytes
    work having happened, matching the object path's ordering.

    Native path: ONE GIL-released call does all five stages
    (tm_native.commit_prep_fused); numpy fallback below is differentially
    tested (tests/test_commit_block.py)."""
    from ..native import load as _load_native

    native = _load_native()
    if native is not None and hasattr(native, "commit_prep_fused"):
        res = native.commit_prep_fused(
            np.ascontiguousarray(cblock.flags),
            np.ascontiguousarray(cblock.sig),
            np.ascontiguousarray(cblock.ts_seconds),
            np.ascontiguousarray(cblock.ts_nanos),
            np.ascontiguousarray(pub_col),
            np.ascontiguousarray(power_col),
            prefix_commit,
            prefix_nil,
            suffix,
            threshold,
            mode,
            ram_max_len,
        )
        sel = np.frombuffer(res[0], dtype=np.int64)
        tallied = int(res[1])
        if len(res) == 2:
            return sel, tallied, None
        pub_b, sig_b, msgs, offs_b, ram_hi, ram_lo, counts = res[2:]
        m = sel.shape[0]
        ram = ram_hi is not None
        nblock = ram_nblock(ram_max_len) if ram else 0
        block = EntryBlock(
            np.frombuffer(pub_b, dtype=np.uint8).reshape(m, 32),
            np.frombuffer(sig_b, dtype=np.uint8).reshape(m, 64),
            msgs,
            np.frombuffer(offs_b, dtype=np.int64),
            ram_hi=np.frombuffer(ram_hi, dtype=np.uint32).reshape(
                m, nblock * 16
            )
            if ram
            else None,
            ram_lo=np.frombuffer(ram_lo, dtype=np.uint32).reshape(
                m, nblock * 16
            )
            if ram
            else None,
            ram_counts=np.frombuffer(counts, dtype=np.int32)
            if ram
            else None,
        )
        return sel, tallied, block
    return _prep_commit_numpy(
        cblock,
        pub_col,
        power_col,
        prefix_commit,
        prefix_nil,
        suffix,
        threshold,
        mode,
        ram_max_len,
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
    ram_max_len: int,
) -> Tuple[np.ndarray, int, Optional[EntryBlock]]:
    """Vectorized fallback — identical outputs to the native call."""
    sel, tallied = select_and_tally(cblock, power_col, threshold, mode)
    if tallied <= threshold:
        return sel, tallied, None
    msgs_buf, offsets, groups = _compose_selected(
        cblock, sel, prefix_commit, prefix_nil, suffix
    )
    pub_rows = pub_col[sel]
    sig_rows = cblock.sig[sel]
    ram_hi = ram_lo = ram_counts = None
    if ram_max_len:
        ram = _fill_ram(msgs_buf, offsets, pub_rows, sig_rows,
                        ram_max_len, groups=groups)
        if ram is not None:
            ram_hi, ram_lo, ram_counts = ram
    block = EntryBlock(
        pub_rows,
        sig_rows,
        msgs_buf,
        offsets,
        ram_hi=ram_hi,
        ram_lo=ram_lo,
        ram_counts=ram_counts,
    )
    return sel, tallied, block
