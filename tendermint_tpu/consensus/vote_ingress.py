"""Live-vote ingress — device-batched VoteSet.add_vote (ISSUE 15).

The paper's named hot path is types.VoteSet.AddVote → per-vote signature
verification. Commits, light headers, tx ingress and replay ranges all
ride the device pipeline already; live gossiped prevotes/precommits were
the last one-at-a-time host path. This module micro-windows them:

  ConsensusState host stage (HeightVoteSet.check_vote — index/address/
  step checks, duplicate + non-deterministic-signature detection, the
  ErrVoteConflictingVotes machinery all run BEFORE dispatch)
      │ CheckedVote
      ▼
  VoteIngress.submit(PendingVote)
      ├─ signature-memo consult (PR-6 _SigMemo): a re-gossiped vote
      │  whose (pub, msg, sig) verdict is memoized never re-dispatches
      ├─ in-window duplicate drop: the same signature already queued in
      │  an open window is dropped (sequential processing would apply
      │  the first copy and return False for the second — no observable
      │  difference, one device lane saved)
      └─ window keyed by (height, valset epoch): flushed as ONE
         EntryBlock (val_idx + epoch_key attached when the epoch cache
         is warm) into the SHARED AsyncBatchVerifier at CONSENSUS
         priority — same-round votes from many peers cross-coalesce in
         the pipeline's coalescer (mesh lanes when TM_TPU_MESH)
      ▼
  apply callback (ENQUEUE-ONLY: puts verdict messages on the consensus
  state's own queue) → the consensus pump applies verdicts in
  deterministic submission order via VoteSet.apply_vote_verdict.

Fallbacks — a window verifies on the HOST (via crypto.ed25519.
verify_zip215_fast, which the simnet _SigMemo wraps) when it is smaller
than types.validation.BATCH_VERIFY_THRESHOLD, when no engine is
attached, or in stepped mode. A DispatchError poisons ONLY its own
window: those votes are handed back with the error and the consensus
state re-drives each one through the sequential per-vote path.

Stepped/simnet mode: no threads. Votes accumulate until the consensus
pump drains its queue; ConsensusState.process_pending then calls
flush_pending(), which host-verifies every open window in submission
order and applies inline — flush points are a pure function of message
arrival, so cluster runs stay replay-exact.

Threading (threaded mode): the shared ingress fabric's one scheduler
flushes the lane. Verifier done-callbacks run on the pipeline resolver
thread and ONLY enqueue — the apply callback must never take consensus
locks (ConsensusState's is queue.put + wake, both lock-free from the
resolver's perspective).

Since ISSUE 17 the windowing machinery lives in ops/ingress.py (the
one ingress fabric): this module keeps the vote-shaped host stage —
memo consult, (height, epoch) window keys, val_idx attachment — as a
LaneSpec plus callbacks. Knobs: TM_TPU_INGRESS_VOTES_BATCH (default
128 sigs) and TM_TPU_INGRESS_VOTES_WINDOW_MS (default 2 ms); legacy
TM_TPU_VOTE_BATCH / TM_TPU_VOTE_WINDOW_MS still honored with a
DeprecationWarning.
"""

from __future__ import annotations

import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..crypto import ed25519 as _ed
from ..observability import trace as _trace
from ..types.validation import BATCH_VERIFY_THRESHOLD

DEFAULT_BATCH = 128
DEFAULT_WINDOW_MS = 2.0

# apply callback: (batch, verdicts, error) — verdicts is None iff error
# is set. MUST be enqueue-only (runs on resolver/flusher threads).
ApplyFn = Callable[[List["PendingVote"], Optional[Sequence[bool]],
                    Optional[BaseException]], None]


class PendingVote:
    """One checked, not-yet-verified vote riding a window."""

    __slots__ = ("vote", "peer_id", "flow", "pub", "msg", "t_enq")

    def __init__(self, vote, peer_id: str, pub: bytes, msg: bytes,
                 flow: Optional[int] = None, t_enq: float = 0.0):
        self.vote = vote
        self.peer_id = peer_id
        self.flow = flow
        self.pub = pub        # 32-byte ed25519 key (valset row bytes)
        self.msg = msg        # sign bytes under the chain id
        self.t_enq = t_enq


def memo_verdict(pub: bytes, msg: bytes, sig: bytes) -> Optional[bool]:
    """Consult the PR-6 signature memo WITHOUT computing: when simnet's
    _SigMemo wraps crypto.ed25519.verify_zip215_fast, its cache dict is
    duck-typed here. Read-only — a miss never populates (the device or
    host verify that follows will, through the memo's own __call__)."""
    cache = getattr(_ed.verify_zip215_fast, "cache", None)
    if cache is None:
        return None
    v = cache.get((pub, msg, sig))
    return None if v is None else bool(v)


# live accumulators for /status aggregation (rpc/core.py)
_ACTIVE: "weakref.WeakSet[VoteIngress]" = weakref.WeakSet()


def vote_ingress_stats() -> dict:
    """Aggregate snapshot over every live vote accumulator in the
    process — the /status `vote_ingress` section."""
    accs = list(_ACTIVE)
    if not accs:
        return {"enabled": False}
    out: Dict[str, float] = {
        "enabled": True, "queue_depth": 0, "batches": 0, "sigs": 0,
        "memo_hits": 0, "window_dups": 0, "sync_fallbacks": 0,
        "preemptions": 0, "dispatch_errors": 0,
    }
    waits = []
    for a in accs:
        s = a.stats()
        for k in ("queue_depth", "batches", "sigs", "memo_hits",
                  "window_dups", "sync_fallbacks", "preemptions",
                  "dispatch_errors"):
            out[k] += s[k]
        if s["batch_wait_ms_avg"]:
            waits.append(s["batch_wait_ms_avg"])
    out["batch_wait_ms_avg"] = sum(waits) / len(waits) if waits else 0.0
    return out


class _SetRows:
    """A validator set's rows in its device table, as part of a window
    key: equal and hashed by the set's hash."""

    __slots__ = ("set_hash", "rows")

    def __init__(self, set_hash: bytes, rows: np.ndarray):
        self.set_hash = set_hash
        self.rows = rows

    def __eq__(self, other) -> bool:
        return isinstance(other, _SetRows) and other.set_hash == self.set_hash

    def __hash__(self) -> int:
        return hash(self.set_hash)


class VoteIngress:
    """Window/size-batched live-vote signature verification — a `votes`
    lane on the shared ingress fabric (ops/ingress.py).

    submit(pend, val_set) queues one host-checked vote. Windows are
    keyed by (height, valset epoch) and flush as ONE EntryBlock to the
    shared verifier at PRIORITY_CONSENSUS after the lane's batch target
    or window elapses. Verdicts come back through the apply callback in
    window submission order; the callback only enqueues (see module
    docstring). The lane carries the consensus hot path's 5 ms p99
    budget: when adaptive, the deadline-aware flush fires early enough
    that submit + expected device service still fit it.

    stepped=True builds a threadless lane for simnet: nothing flushes
    until flush_pending() — called by the consensus pump when its queue
    drains — host-verifies and applies inline."""

    def __init__(self, apply_fn: ApplyFn, verifier=None,
                 max_batch: Optional[int] = None,
                 window_ms: Optional[float] = None,
                 stepped: bool = False, metrics=None):
        from ..ops import ingress as _fabric

        cfg = _fabric.resolve_lane_config(
            "votes", batch=max_batch, window_ms=window_ms,
            legacy_batch="TM_TPU_VOTE_BATCH",
            legacy_window="TM_TPU_VOTE_WINDOW_MS",
        )
        self._apply = apply_fn
        self.metrics = metrics
        self.memo_hits = 0
        self.apply_drops = 0    # consensus/state.py bumps this directly
        # (height, id(valset)) -> (table key, the set's rows there) or None
        self._epoch_keys: Dict[Tuple, Optional[Tuple]] = {}
        self._lane = _fabric.shared_engine().register(_fabric.LaneSpec(
            name="votes",
            priority=_fabric.PRIORITY_CONSENSUS,
            batch=cfg.batch,
            window_ms=cfg.window_ms,
            budget_ms=cfg.budget_ms,
            adaptive=cfg.adaptive,
            stepped=bool(stepped),
            full_by_window=True,     # size trigger per (height, epoch)
            device_threshold=BATCH_VERIFY_THRESHOLD,
            submit_error_to_host=True,  # host path is always available
            closed_msg="vote ingress is closed",
            verifier=verifier,
            entries_fn=lambda p: (p.pub, p.msg, p.vote.signature),
            attach_fn=self._attach,
            flow_fn=lambda p: p.flow,
            trace_fn=self._trace,
            host_fn=self._host_check,
            deliver=self._deliver,
            observer=self,
        ))
        _ACTIVE.add(self)

    @property
    def stepped(self) -> bool:
        return self._lane.spec.stepped

    # -- lane callbacks ---------------------------------------------------

    def _deliver(self, items, verdicts, err) -> None:
        """Hand one window's verdicts (or its error) to the apply
        callback — enqueue-only by contract, so the fabric may call this
        straight from the pipeline resolver thread."""
        self._apply([it.item for it in items], verdicts, err)

    def _host_check(self, batch: List[PendingVote]) -> List[bool]:
        """The sync fallback: verify on the host — through
        crypto.ed25519.verify_zip215_fast so simnet's _SigMemo memoizes
        the verdicts."""
        return [
            bool(_ed.verify_zip215_fast(p.pub, p.msg, p.vote.signature))
            for p in batch
        ]

    @staticmethod
    def _attach(block, key: Tuple, batch: List[PendingVote]) -> None:
        """Warm-epoch windows carry val_idx + epoch_key so kernels
        gather A on device: key[1] is the table's key iff warm, key[2]
        the rows of the set's validators in that table."""
        if isinstance(key[1], bytes):
            block.val_idx = key[2].rows[np.array(
                [p.vote.validator_index for p in batch], dtype=np.int32
            )]
            block.epoch_key = key[1]

    @staticmethod
    def _trace(batch: List[PendingVote], flow: int) -> None:
        if _trace.TRACER.enabled:
            _trace.TRACER.flow_point(
                "vote_ingress.flush", flow, "t",
                n=len(batch), height=batch[0].vote.height,
            )

    # -- legacy metric mirror (fabric observer) ---------------------------

    def _metrics(self):
        if self.metrics is None:
            from ..libs import metrics as _m

            self.metrics = _m.vote_ingress_metrics()
        return self.metrics

    def flush(self, n: int, wait_ms: float) -> None:
        try:
            m = self._metrics()
            m.batches.inc()
            m.batch_sigs.inc(n)
            m.batch_wait_ms.observe(wait_ms)
        except Exception:  # noqa: BLE001 — observability never fatal
            pass

    def sync_fallback(self) -> None:
        try:
            self._metrics().sync_fallbacks.inc()
        except Exception:  # noqa: BLE001
            pass

    def dispatch_error(self) -> None:
        try:
            self._metrics().dispatch_errors.inc()
        except Exception:  # noqa: BLE001
            pass

    # -- submission -------------------------------------------------------

    def submit(self, pend: PendingVote, val_set) -> None:
        """Queue one host-checked vote. The verdict reaches the apply
        callback later (possibly immediately, on a memo hit)."""
        if self._lane._closed:
            raise RuntimeError("vote ingress is closed")
        vote = pend.vote
        sig = vote.signature
        hit = memo_verdict(pend.pub, pend.msg, sig)
        if hit is not None:
            self.memo_hits += 1
            try:
                self._metrics().memo_hits.inc()
            except Exception:  # noqa: BLE001 — observability never fatal
                pass
            self._apply([pend], [hit], None)
            return
        dkey = (vote.height, vote.round, vote.type,
                vote.validator_index, sig)
        self._lane.submit(pend, key=self._window_key(vote.height, val_set),
                          dedup_key=dkey, t_enq=pend.t_enq or None)

    def _window_key(self, height: int, val_set) -> Tuple:
        """(height, table key, the set's rows) when the epoch cache knows
        this valset (warm windows attach val_idx so kernels gather A on
        device; the rows tell two sets of one table apart);
        (height, id(valset)) cold — still coalesces same-valset votes,
        never fuses rows from different tables."""
        vkey = (height, id(val_set))
        warm = self._epoch_keys.get(vkey)
        if warm is None and vkey not in self._epoch_keys:
            try:
                from ..ops import epoch_cache as _epoch

                ek, rows = _epoch.table_rows(val_set, np.arange(
                    len(val_set.validators), dtype=np.int32))
                warm = (ek, _SetRows(val_set.hash(), rows)) if ek else None
            except Exception:  # noqa: BLE001 — cache is an optimization
                warm = None
            self._epoch_keys[vkey] = warm
            if len(self._epoch_keys) > 64:
                self._epoch_keys.clear()
                self._epoch_keys[vkey] = warm
        return (height,) + warm if warm is not None else vkey

    def flush_now(self) -> None:
        self._lane.flush_now()

    def flush_pending(self) -> bool:
        """Stepped-mode flush point (ConsensusState.process_pending when
        its queue drains): host-verify every open window in submission
        order and apply inline. Returns True when anything flushed —
        the pump then re-drains its queue for the verdict messages."""
        return self._lane.flush_pending()

    # -- lifecycle / introspection ----------------------------------------

    def stats(self) -> dict:
        s = self._lane.stats()
        return {
            "queue_depth": s["queue_depth"],
            "batches": s["batches"],
            "sigs": s["sigs"],
            "memo_hits": self.memo_hits,
            "window_dups": s["window_dups"],
            "sync_fallbacks": s["sync_fallbacks"],
            "batch_wait_ms_avg": s["batch_wait_ms_avg"],
            "preemptions": s["preemptions"],
            "dispatch_errors": s["dispatch_errors"],
            "apply_drops": self.apply_drops,
            "max_batch": s["max_batch"],
            "window_ms": s["window_ms"],
            "stepped": s["stepped"],
        }

    def close(self, timeout: float = 10.0) -> None:
        self._lane.close(timeout=timeout)
