"""Chain-replay catch-up engine (ISSUE 14, ROADMAP item 3).

Turns blocksync from verify-one-ahead into a pipelined range verifier:
up to TM_TPU_REPLAY_WINDOW (default 64) fetched heights are decoded
ahead of apply, grouped by valset epoch — the window is cut at any
height whose header carries a different validators_hash, the range-wide
form of `_take_speculation`'s valhash check — and whole ranges are
packed into mesh superbatches through the shared AsyncBatchVerifier at
PRIORITY_REPLAY (below consensus, above ingress: the PR-12 preemption
points keep a rejoining node's flood from ever delaying live commits).
BlockStore.save_block writes ride a writer thread BEHIND device
verification so storage latency hides under the next range's device.

Failure semantics are byte-identical to the sequential path: a bad
commit anywhere in a range falls back to per-height sequential
`verify_commit_light` for that range, so the rejected height's error
string matches the one-at-a-time path exactly.

The engine is deliberately transport-free: it consumes an ordered run
of consecutive fetched blocks plus save/apply callbacks, so the
BlockSyncReactor (live catch-up), bench.py blocksync (100k-height
replay) and the simnet rejoin scenario all drive the same code.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Callable, List, Optional

from ..observability import trace as _trace
from ..types import BlockID
from ..types.block import Block
from ..types.part_set import BLOCK_PART_SIZE_BYTES, PartSet
from ..types.validation import (
    PrepareUnsupported,
    prepare_commit_range,
    verify_commit_light,
)

_span = _trace.span

DEFAULT_WINDOW = 64


def replay_window() -> int:
    """TM_TPU_INGRESS_REPLAY_WINDOW: max heights decoded ahead of apply
    (legacy TM_TPU_REPLAY_WINDOW honored with a DeprecationWarning)."""
    from ..ops import ingress as _fabric

    v = _fabric.env_setting("TM_TPU_INGRESS_REPLAY_WINDOW",
                            "TM_TPU_REPLAY_WINDOW")
    try:
        return max(int(v), 1)
    except (TypeError, ValueError):
        return DEFAULT_WINDOW


def plan_epoch_range(blocks: List[Block], limit: int) -> int:
    """How many of the verifiable heights at the head of `blocks` share
    the FIRST block's validators_hash — the epoch cut. `blocks` holds
    consecutive fetched blocks [h0 .. h0+k]; height h is verifiable when
    block h+1 (carrying h's commit) is present, so at most len-1 heights
    are plannable. A mismatching hash at block i means applying block
    i-1 changes the validator set: the range ends there and the next
    range starts under the post-apply set.

    A block whose header announces a valset change via
    next_validators_hash also ends the range after its height: applying
    it installs a new set, so later heights cannot share this range's
    verification key material.

    Header hashes are a grouping HEURISTIC only — verification authority
    stays with the applied state's validator set. A chain that lies
    about its hashes can at worst form a range whose commits verify
    under stale keys; the apply step then rejects the block under the
    live valset and the engine falls back to the sequential path (same
    errors, same rejection — see _apply_verified)."""
    n = min(len(blocks) - 1, limit)
    if n <= 0:
        return 0
    first = bytes(blocks[0].header.validators_hash)
    cut = 1
    while cut < n:
        if bytes(blocks[cut].header.validators_hash) != first:
            break
        nxt = bytes(blocks[cut - 1].header.next_validators_hash)
        if nxt and nxt != first:
            break
        cut += 1
    return cut


class ReplayOutcome:
    """Result of one replay_blocks() call."""

    __slots__ = ("applied", "failed_height", "error", "range_heights",
                 "sequential_heights")

    def __init__(self) -> None:
        self.applied = 0                 # heights saved + applied
        self.failed_height: Optional[int] = None
        self.error: Optional[str] = None
        self.range_heights = 0           # verified via a device range
        self.sequential_heights = 0      # verified per-height (fallback,
        #                                  sub-threshold, or tiny range)

    def __repr__(self) -> str:  # debugging aid
        return (
            f"ReplayOutcome(applied={self.applied}, "
            f"failed_height={self.failed_height}, error={self.error!r})"
        )


class _ApplyRejected(Exception):
    """apply() rejected a verified block (InvalidBlockError, a
    ValueError): wrapped so the range/sequential drivers can tell an
    apply rejection (fall back / surface failed_height) apart from a
    save failure (propagate — the store diverged, abort catch-up)."""


class _Writer:
    """Ordered store-write pipeline: save_block (which enforces strictly
    sequential heights itself) runs on this thread while the caller is
    already applying the next height / waiting on the next range's
    device. The first error poisons the writer; drain() re-raises it on
    the replay thread so a failed save aborts catch-up instead of
    silently diverging store from state."""

    def __init__(self, depth: int = 128):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._err: Optional[BaseException] = None
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="replay-writer", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                if self._err is None:
                    save, args = item
                    try:
                        save(*args)
                    except BaseException as e:  # noqa: BLE001 — via drain()
                        self._err = e
            finally:
                self._q.task_done()

    def put(self, save: Callable, block, parts, seen_commit) -> None:
        if self._closed:
            # the sentinel is already queued: a save enqueued behind it
            # would never run (state advanced past the store on disk)
            raise RuntimeError("replay writer closed")
        if self._err is not None:
            raise RuntimeError("replay writer failed") from self._err
        self._q.put((save, (block, parts, seen_commit)))

    def drain(self) -> None:
        """Block until every queued save has run; raise the first error.
        Never hangs on a writer thread that already exited — a dead
        writer with queued saves is an error, not a deadlock."""
        q = self._q
        with q.all_tasks_done:
            while q.unfinished_tasks:
                if not self._thread.is_alive():
                    break
                q.all_tasks_done.wait(0.05)
        if self._err is not None:
            raise RuntimeError("replay writer failed") from self._err
        if q.unfinished_tasks:
            raise RuntimeError("replay writer exited with pending saves")

    def close(self, timeout: float = 10.0) -> None:
        self._closed = True
        self._q.put(None)
        self._thread.join(timeout=timeout)


class ReplayEngine:
    """Range-batched catch-up verifier over the shared verify pipeline.

    replay_blocks(state, blocks, save, apply) verifies and applies as
    many consecutive heights as the window/epoch cuts allow, pipelining
    device verification of later range chunks behind the apply of
    earlier ones and store writes behind both. `synchronous=True` runs
    saves inline (no writer thread) — the simnet rejoin scenario uses it
    so a run stays a pure function of its seed."""

    def __init__(self, window: Optional[int] = None,
                 synchronous: bool = False,
                 verifier=None, result_timeout: float = 600.0):
        from ..ops import ingress as _fabric

        self._window = int(window) if window else replay_window()
        self._synchronous = bool(synchronous)
        self._verifier = verifier  # injected for tests; default shared
        self._timeout = float(result_timeout)
        self._writer: Optional[_Writer] = None
        # the `replay` lane: fused range chunks ride the shared fabric
        # at REPLAY priority (stepped — chunk cuts are data-dependent,
        # the scheduler never flushes for us: replay stays deterministic)
        self._lane = _fabric.shared_engine().register(_fabric.LaneSpec(
            name="replay",
            priority=_fabric.PRIORITY_REPLAY,
            stepped=True,
            closed_msg="replay engine is closed",
            verifier=verifier,
        ))
        # cumulative stats (deterministic: counts derive only from the
        # replayed chain, not from timing)
        self.ranges = 0
        self.range_heights = 0
        self.sequential_heights = 0
        self.fallback_ranges = 0
        self.sigs_submitted = 0
        self.heights_applied = 0

    # -- plumbing --------------------------------------------------------

    @staticmethod
    def _group_cap() -> int:
        from ..ops import backend as _backend

        return _backend.max_coalesce()

    @staticmethod
    def _device_threshold() -> int:
        from ..ops import backend as _backend

        return _backend.DEVICE_THRESHOLD

    @property
    def window(self) -> int:
        return self._window

    def stats(self) -> dict:
        total = self.range_heights + self.sequential_heights
        return {
            "ranges": self.ranges,
            "fallback_ranges": self.fallback_ranges,
            "range_heights": self.range_heights,
            "sequential_heights": self.sequential_heights,
            "heights_applied": self.heights_applied,
            "sigs_submitted": self.sigs_submitted,
            "hit_rate": (self.range_heights / total) if total else 0.0,
            "window": self._window,
        }

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        self._lane.close(timeout=0.0)

    # -- the range verifier ---------------------------------------------

    def replay_blocks(self, state, blocks: List[Block], save: Callable,
                      apply: Callable, applied: Optional[Callable] = None,
                      should_stop: Optional[Callable] = None):
        """Verify + apply consecutive heights from `blocks` (an ordered
        run [h0, h0+1, ...] with h0 == the next height to apply under
        `state`). Returns (new_state, ReplayOutcome).

        save(block, parts, seen_commit)   -> None   (BlockStore.save_block)
        apply(block_id, block)            -> state  (BlockExecutor.apply_block)
        applied(height)                   -> None   (e.g. pool.pop_first)
        should_stop()                     -> bool   (abort between chunks)
        """
        out = ReplayOutcome()
        if len(blocks) < 2:
            return state, out
        h0 = blocks[0].header.height
        for i, b in enumerate(blocks):  # the run must be consecutive
            if b.header.height != h0 + i:
                raise ValueError("replay_blocks requires consecutive heights")
        cut = plan_epoch_range(blocks, self._window)
        if cut <= 0:
            return state, out
        fid = _trace.next_flow() if _trace.TRACER.enabled else None
        if fid is not None:
            _trace.TRACER.flow_point(
                "blocksync.fetch", fid, "s", h0=h0, n=cut
            )
        state = self._replay_range(
            state, blocks[: cut + 1], save, apply, applied, should_stop,
            out, fid,
        )
        if fid is not None:
            _trace.TRACER.flow_point(
                "replay.apply", fid, "f", applied=out.applied
            )
        if self._writer is not None:
            self._writer.drain()
        return state, out

    def _replay_range(self, state, blocks, save, apply, applied,
                      should_stop, out: ReplayOutcome, fid) -> object:
        """One epoch range: blocks[0..n] covering heights h0..h0+n-1."""
        from ..ops.pipeline import DispatchError
        from concurrent.futures import TimeoutError as _FutTimeout

        chain_id = state.chain_id
        vals = state.validators
        n = len(blocks) - 1
        self.ranges += 1
        # decode once per height: part sets + block ids are needed by
        # both verification (block_id binds the commit) and save
        with _span("replay.range_pack", flow=fid, flow_phase="t",
                   h0=blocks[0].header.height, heights=n):
            parts = [
                PartSet.from_data(b.encode(), BLOCK_PART_SIZE_BYTES)
                for b in blocks[:n]
            ]
            ids = [
                BlockID(hash=b.hash(), part_set_header=p.header())
                for b, p in zip(blocks[:n], parts)
            ]
            items = [
                (blocks[i].header.height, ids[i], blocks[i + 1].last_commit)
                for i in range(n)
            ]
            try:
                prepared, synced = prepare_commit_range(
                    chain_id, vals, items
                )
            except (PrepareUnsupported, ValueError, RuntimeError,
                    IndexError):
                prepared, synced = None, None
        if prepared is None:
            # host-side prepare failed somewhere in the range: the
            # sequential path reproduces the exact error for the
            # offending height (and verifies the earlier ones normally)
            self.fallback_ranges += 1
            return self._apply_sequential(
                state, blocks, parts, ids, 0, n, save, apply, applied,
                should_stop, out,
            )
        synced_set = set(synced)
        total_sigs = sum(len(e) for _, e, _ in prepared)
        if total_sigs and total_sigs < self._device_threshold():
            # a tiny range (rare: right before an epoch cut) is cheaper
            # on the host path than a device round trip
            return self._apply_sequential(
                state, blocks, parts, ids, 0, n, save, apply, applied,
                should_stop, out,
            )
        # pack prepared heights into device chunks of up to ~max_coalesce
        # signatures through the fabric's BlockFuser; every chunk is ONE
        # lane submit (the pipeline launches a full bucket per chunk
        # instead of one launch per height)
        from ..ops import ingress as _fabric

        chunks = []  # (future, [((height, conclude), off, len)])

        def _chunk_done(fut, spans) -> None:
            self.sigs_submitted += spans[-1][1] + spans[-1][2]
            chunks.append((fut, spans))

        fuser = _fabric.BlockFuser(self._lane, self._group_cap(),
                                   _chunk_done, flow=fid)
        for height, entries, conclude in prepared:
            fuser.add((height, conclude), entries)
        fuser.flush()

        # resolve chunks in order, applying each chunk's heights while
        # later chunks are still in flight on the device
        verdicts = {}  # height -> conclude() ran clean
        for fut, spans in chunks:
            try:
                valid = fut.result(timeout=self._timeout)
            except (DispatchError, _FutTimeout):
                # device trouble, not a bad chain: everything not yet
                # applied in this range falls back to sequential
                self.fallback_ranges += 1
                return self._apply_sequential(
                    state, blocks, parts, ids,
                    self._range_resume(blocks, state), n,
                    save, apply, applied, should_stop, out,
                )
            for (height, conclude), off, ln in spans:
                try:
                    conclude(valid[off : off + ln])
                except (ValueError, RuntimeError):
                    # bad commit mid-range: per-height sequential
                    # verification for the REST of the range reproduces
                    # the sequential path's exact error string
                    self.fallback_ranges += 1
                    return self._apply_sequential(
                        state, blocks, parts, ids,
                        self._range_resume(blocks, state), n,
                        save, apply, applied, should_stop, out,
                    )
                verdicts[height] = True
            # apply the verified prefix of this chunk
            state, fallback = self._apply_verified(
                state, blocks, parts, ids, verdicts, synced_set, n,
                save, apply, applied, out,
            )
            if fallback:
                # apply rejected a range-verified block: the headers lied
                # about their valset epoch. Re-verify the rest under the
                # LIVE post-apply set — the sequential path's authority —
                # which reproduces its exact rejection for that height.
                self.fallback_ranges += 1
                return self._apply_sequential(
                    state, blocks, parts, ids,
                    self._range_resume(blocks, state), n,
                    save, apply, applied, should_stop, out,
                )
            if should_stop is not None and should_stop():
                return state
        # heights verified sub-threshold (synced) interleave with device
        # heights; a trailing run of them may remain unapplied
        state, fallback = self._apply_verified(
            state, blocks, parts, ids, verdicts, synced_set, n,
            save, apply, applied, out,
        )
        if fallback:
            self.fallback_ranges += 1
            return self._apply_sequential(
                state, blocks, parts, ids,
                self._range_resume(blocks, state), n,
                save, apply, applied, should_stop, out,
            )
        return state

    def _range_resume(self, blocks, state) -> int:
        """Index into the range where sequential fallback resumes: the
        first height not yet applied under `state`."""
        return int(
            state.last_block_height - blocks[0].header.height + 1
        )

    def _apply_verified(self, state, blocks, parts, ids, verdicts,
                        synced_set, n, save, apply, applied,
                        out: ReplayOutcome):
        """Apply the contiguous verified prefix starting at the first
        unapplied height. Returns (state, fallback_needed).

        Commit verification in this range ran under the valset the FIRST
        header claimed; that is a grouping heuristic, not authority. A
        chain forged with stale valset keys passes device verification
        but is rejected here by apply (InvalidBlockError, a ValueError)
        under the live state — in that case nothing is saved (the save
        is only enqueued after apply succeeds) and fallback_needed=True
        sends the caller to _apply_sequential, which re-verifies under
        the live post-apply set and surfaces the sequential path's exact
        failed_height/error for redo_request."""
        i = self._range_resume(blocks, state)
        while i < n:
            h = blocks[i].header.height
            if h in synced_set:
                via_range = False
            elif verdicts.get(h):
                via_range = True
            else:
                break  # later chunk still in flight
            try:
                state = self._save_and_apply(
                    state, blocks[i], parts[i], ids[i],
                    blocks[i + 1].last_commit, save, apply, applied, out,
                )
            except _ApplyRejected:
                return state, True
            if via_range:
                out.range_heights += 1
                self.range_heights += 1
            else:
                out.sequential_heights += 1
                self.sequential_heights += 1
            i += 1
        return state, False

    def _apply_sequential(self, state, blocks, parts, ids, start, n,
                          save, apply, applied, should_stop,
                          out: ReplayOutcome):
        """Per-height sequential verification for heights [start, n) —
        the byte-identical fallback. Stops at the first bad height,
        recording its exact sequential-path error."""
        i = max(self._range_resume(blocks, state), start)
        while i < n:
            if should_stop is not None and should_stop():
                return state
            h = blocks[i].header.height
            try:
                with _span("replay.sequential", height=h):
                    verify_commit_light(
                        state.chain_id, state.validators, ids[i],
                        h, blocks[i + 1].last_commit,
                    )
            except (ValueError, RuntimeError) as e:
                out.failed_height = h
                out.error = str(e)
                return state
            try:
                state = self._save_and_apply(
                    state, blocks[i], parts[i], ids[i],
                    blocks[i + 1].last_commit, save, apply, applied, out,
                )
            except _ApplyRejected as e:
                # commit verified but apply rejected the block body
                # (InvalidBlockError): surface it like a verification
                # failure so the reactor redo_requests instead of the
                # apply thread dying with the block half-persisted
                out.failed_height = h
                out.error = str(e)
                return state
            out.sequential_heights += 1
            self.sequential_heights += 1
            i += 1
        return state

    def _save_and_apply(self, state, block, parts, block_id, seen_commit,
                        save, apply, applied, out: ReplayOutcome):
        """Apply FIRST, save after: apply is the authority (it re-checks
        the block under live state), so a block it rejects must never
        reach the store — a persisted-but-invalid block would wedge the
        node on restart. Saves still pipeline: height h's store write
        runs on the writer thread while h+1 applies."""
        try:
            state = apply(block_id, block)
        except ValueError as e:
            raise _ApplyRejected(str(e)) from e
        if self._synchronous:
            save(block, parts, seen_commit)
        else:
            if self._writer is None:
                self._writer = _Writer()
            self._writer.put(save, block, parts, seen_commit)
        out.applied += 1
        self.heights_applied += 1
        if applied is not None:
            applied(block.header.height)
        return state
