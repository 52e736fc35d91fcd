"""The Tendermint BFT consensus state machine.

Reference parity: internal/consensus/state.go (2400 LoC). One
receive-routine thread owns all round state (state.go:757 receiveRoutine);
peer messages, internal messages and timeouts arrive on a queue; every
message is WAL-logged before processing; the node's own votes are
WAL-synced before broadcast (the double-sign-safety invariant).

Step flow (types/round_state.go:20-28):
  NewHeight → NewRound → Propose → Prevote → PrevoteWait → Precommit →
  PrecommitWait → Commit → (NewHeight...)

The message handlers mirror state.go's enterX functions with their exact
guard conditions; vote accumulation uses types.VoteSet (per-vote verify)
and the finalize path applies blocks through state.BlockExecutor, whose
LastCommit verification runs on the device batch engine.
"""

from __future__ import annotations

import os
import queue
import threading
import time as _time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Deque, Dict, List, Optional

from ..libs.service import BaseService
from ..observability import trace as _trace
from ..types import (
    BlockID,
    Commit,
    Timestamp,
    ValidatorSet,
    Vote,
    VoteSet,
)
from ..types.block import Block
from ..types.part_set import Part, PartSet
from ..types.proposal import Proposal
from ..types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE
from ..types.vote_set import ErrVoteConflictingVotes, ErrVoteNonDeterministicSignature
from ..state import State
from ..state.execution import BlockExecutor
from .ticker import TimeoutInfo, TimeoutTicker
from .types import (
    STEP_COMMIT,
    STEP_NEW_HEIGHT,
    STEP_NEW_ROUND,
    STEP_PRECOMMIT,
    STEP_PRECOMMIT_WAIT,
    STEP_PREVOTE,
    STEP_PREVOTE_WAIT,
    STEP_PROPOSE,
    HeightVoteSet,
    RoundState,
)
from .wal import WAL, WALMessage


def _ts_from_float(t: float) -> Timestamp:
    sec = int(t)
    return Timestamp(seconds=sec, nanos=int((t - sec) * 1e9))


def _ts_le(a: Timestamp, b: Timestamp) -> bool:
    return (a.seconds, a.nanos) <= (b.seconds, b.nanos)


@dataclass
class ProposalMessage:
    proposal: Proposal


@dataclass
class BlockPartMessage:
    height: int
    round: int
    part: Part


@dataclass
class VoteMessage:
    vote: Vote
    # flow correlation id (ISSUE 10): captured from the tracer's inbound-
    # flow register at enqueue time so the causal chain survives the
    # receive-queue hop (the vote is verified on a later event/thread
    # than the delivery that carried it)
    flow: Optional[int] = None


@dataclass
class VoteVerdictMessage:
    """A vote ingress verdict re-entering the pump (ISSUE 15). The
    original VoteMessage was WAL-logged before dispatch; verdicts are
    NOT (``_wal_write_msg`` skips unknown kinds), so a replayed WAL
    re-verifies the vote through the sequential path instead of trusting
    a stale device verdict. ``valid`` is None iff ``error`` is set — the
    poisoned-window shape, re-driven through the per-vote fallback."""

    pend: object  # vote_ingress.PendingVote
    valid: Optional[bool] = None
    error: Optional[BaseException] = None


@dataclass
class HeightTimeline:
    """Per-height consensus latency attribution (ISSUE 10): the timestamps
    of every phase transition one height passes through, read off the
    state machine's own clock (`self._now` — the simnet virtual clock when
    injected, so simulated timelines are deterministic). The per-phase
    breakdown is the 2302.00418 instrument: where a height's latency
    actually went — waiting for the proposal, gathering 2/3 prevotes,
    gathering 2/3 precommits, fetching/committing the block, or verifying
    and applying it."""

    height: int
    t_new_height: float
    t_proposal: Optional[float] = None       # valid proposal accepted
    t_prevote_23: Optional[float] = None     # 2/3 prevotes observed
    t_precommit_23: Optional[float] = None   # 2/3 precommits observed
    t_commit: Optional[float] = None         # entered STEP_COMMIT
    t_verify_dispatch: Optional[float] = None  # block validate/verify begins
    t_applied: Optional[float] = None        # ABCI apply finished
    rounds: int = 0                          # rounds consumed (>= 1)

    # (phase, start attr, end attr) — consecutive transition deltas
    _PHASES = (
        ("propose", "t_new_height", "t_proposal"),
        ("prevote", "t_proposal", "t_prevote_23"),
        ("precommit", "t_prevote_23", "t_precommit_23"),
        ("commit", "t_precommit_23", "t_commit"),
        ("apply", "t_verify_dispatch", "t_applied"),
    )

    def phases(self) -> Dict[str, float]:
        """Phase durations in seconds, only for transitions that happened
        (a height entered via WAL replay or catch-up can skip phases)."""
        out: Dict[str, float] = {}
        for name, a, b in self._PHASES:
            ta, tb = getattr(self, a), getattr(self, b)
            if ta is not None and tb is not None and tb >= ta:
                out[name] = tb - ta
        return out

    def to_dict(self) -> dict:
        d = {
            "height": self.height,
            "rounds": self.rounds,
            "t_new_height": self.t_new_height,
            "t_proposal": self.t_proposal,
            "t_prevote_23": self.t_prevote_23,
            "t_precommit_23": self.t_precommit_23,
            "t_commit": self.t_commit,
            "t_verify_dispatch": self.t_verify_dispatch,
            "t_applied": self.t_applied,
            "phases": self.phases(),
        }
        if self.t_applied is not None:
            d["total_s"] = self.t_applied - self.t_new_height
        return d


class ConsensusState(BaseService):
    """state.go:81-200 State."""

    def __init__(
        self,
        config,  # ConsensusConfig
        state: State,
        block_exec: BlockExecutor,
        block_store,
        mempool=None,
        evpool=None,
        event_bus=None,
        wal: Optional[WAL] = None,
        priv_validator=None,
        metrics=None,  # libs.metrics.ConsensusMetrics (None = no-op)
        clock=None,  # injectable time source (simnet); None = wall clock
        tracer=None,  # per-node SpanTracer (simnet); None = global TRACER
    ):
        super().__init__("ConsensusState")
        self._cfg = config
        # Flight recorder (ISSUE 10): spans/flows go to the injected
        # per-node tracer under simnet (virtual-clock timebase, one pid
        # per node in the merged trace) and to the process tracer on a
        # real node.
        self._tracer = tracer if tracer is not None else _trace.TRACER
        # last-K completed HeightTimeline records (RPC /height_timeline,
        # SimReport ring, flight-recorder dumps)
        ring = int(os.environ.get("TM_TPU_TIMELINE_RING", "32") or 32)
        self.height_timelines: Deque[HeightTimeline] = deque(
            maxlen=max(ring, 1)
        )
        self._timeline: Optional[HeightTimeline] = None
        # All reads of "now" inside the state machine (round start times,
        # commit times, vote timestamps) go through self._now so a virtual
        # clock can drive the whole machine deterministically.
        self._clock = clock
        self._now: Callable[[], float] = clock.time if clock is not None else _time.time
        self._block_exec = block_exec
        self._block_store = block_store
        self._mempool = mempool
        self._evpool = evpool
        self._event_bus = event_bus
        self._wal = wal
        self._metrics = metrics
        self._priv_validator = priv_validator
        self._priv_validator_pub_key = (
            priv_validator.get_pub_key() if priv_validator else None
        )

        self.rs = RoundState()
        self._state = state  # committed chain state

        self._queue: "queue.Queue" = queue.Queue(maxsize=1000)
        self._internal_queue: "queue.Queue" = queue.Queue(maxsize=1000)
        # Wakes the receive routine when either queue gains a message —
        # a blocking wait instead of a poll (same pattern as the ops
        # pipeline worker). on_enqueue is the external-driver (simnet)
        # hook: called after every enqueue so a scheduler can pump
        # process_pending() instead of running the thread.
        self._msg_ready = threading.Event()
        self.on_enqueue: Optional[Callable[[], None]] = None
        # Committed-height watchers block here rather than sleep-polling
        # (kills ~50 wakeups/s/node that wait_for_height used to cost).
        self._commit_cond = threading.Condition()
        self._ticker = TimeoutTicker(self._tock, clock=clock)
        self._thread: Optional[threading.Thread] = None
        self._done_first_block = threading.Event()
        self._height_events: List[Callable] = []  # hooks per committed height

        # byzantine-test overrides (common_test.go decideProposal/doPrevote)
        self.decide_proposal_override: Optional[Callable] = None
        self.do_prevote_override: Optional[Callable] = None

        # Broadcast seam: the consensus reactor registers here to gossip the
        # node's own proposals/parts/votes (reactor.go's peer routines read
        # these off the internal message flow).
        self.broadcast_hooks: List[Callable] = []
        # Called with every vote successfully added to the height vote sets
        # (any source) — the reactor broadcasts HasVote off this
        # (reactor.go:1031 broadcastHasVoteMessage).
        self.vote_added_hooks: List[Callable] = []

        # Live-vote ingress (ISSUE 15): attach_vote_ingress() wires the
        # device-batched verify lane; None = every vote rides the
        # sequential host path, byte-identically to pre-ISSUE-15.
        self._vote_ingress = None

        self._update_to_state(state)

    # ------------------------------------------------------------------
    # lifecycle

    def on_start(self) -> None:
        self._start_common()
        self._thread = threading.Thread(target=self._receive_routine, daemon=True)
        self._thread.start()
        # start the height's round 0 after commit-timeout from start_time
        self._schedule_round_0()

    def start_stepped(self) -> None:
        """on_start without the receive thread: WAL replay + round-0
        scheduling only. For an external event-driven driver (the simnet
        scheduler) that pumps process_pending() off the on_enqueue hook —
        the whole state machine then runs single-threaded and
        deterministically."""
        self._start_common()
        self._schedule_round_0()

    def _start_common(self) -> None:
        self._reconstruct_last_commit()
        if self._wal is not None:
            self._wal.start()
            self._replay_wal()

    def on_stop(self) -> None:
        self._ticker.stop()
        self._close_vote_ingress()
        self._msg_ready.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._wal is not None:
            self._wal.stop()

    def stop_stepped(self) -> None:
        """Tear down a start_stepped() node (ticker + WAL; no thread)."""
        self._quit.set()
        self._ticker.stop()
        self._close_vote_ingress()
        if self._wal is not None:
            self._wal.stop()

    def _close_vote_ingress(self) -> None:
        ing = self._vote_ingress
        if ing is not None:
            self._vote_ingress = None
            try:
                ing.close(timeout=2.0)
            except Exception:  # noqa: BLE001 — teardown must not raise
                pass

    # ------------------------------------------------------------------
    # external inputs

    def _enqueue(self, q: "queue.Queue", item) -> None:
        """state.go's `select { case cs.peerMsgQueue <- mi: case
        <-cs.Quit(): }`: a full queue holds its producer back while the
        service runs; once _quit is set it drops the message instead."""
        while True:
            try:
                q.put(item, timeout=0.2)
                break
            except queue.Full:
                if self._quit.is_set():
                    return
        self._wake()

    def _wake(self) -> None:
        self._msg_ready.set()
        hook = self.on_enqueue
        if hook is not None:
            try:
                hook()
            except Exception:  # noqa: BLE001 — a driver bug must not break enqueue
                pass

    def set_proposal(self, proposal: Proposal, peer_id: str = "") -> None:
        self._enqueue(self._queue, (ProposalMessage(proposal), peer_id))

    def add_block_part(self, height: int, round_: int, part: Part, peer_id: str = "") -> None:
        self._enqueue(
            self._queue, (BlockPartMessage(height, round_, part), peer_id))

    def add_vote_msg(self, vote: Vote, peer_id: str = "") -> None:
        msg = VoteMessage(vote)
        tr = self._tracer
        if tr.enabled and tr.flow is not None:
            msg.flow = tr.flow  # the delivery's flow rides with the vote
        self._enqueue(self._queue, (msg, peer_id))

    # ------------------------------------------------------------------
    # live-vote ingress (ISSUE 15)

    def attach_vote_ingress(self, verifier=None, stepped: bool = False,
                            max_batch=None, window_ms=None, metrics=None):
        """Wire the device-batched vote-verify lane: peer votes for the
        current height run HeightVoteSet.check_vote on the pump, then
        window through consensus/vote_ingress.py; verdicts re-enter the
        queue and apply in submission order. Attach AFTER start — WAL
        replay must ride the sequential path."""
        from . import vote_ingress as _vi

        ing = _vi.VoteIngress(
            self._on_vote_verdicts, verifier=verifier, stepped=stepped,
            max_batch=max_batch, window_ms=window_ms, metrics=metrics,
        )
        self._vote_ingress = ing
        return ing

    @property
    def vote_ingress(self):
        return self._vote_ingress

    def _on_vote_verdicts(self, batch, verdicts, error) -> None:
        """VoteIngress apply callback — may run on the pipeline resolver
        thread, so it ONLY enqueues (the deadlock rule from
        mempool/ingress.py). A full queue drops the verdict instead of
        blocking the resolver: re-gossip re-delivers the vote, so a drop
        costs latency, never correctness."""
        ing = self._vote_ingress
        for i, pend in enumerate(batch):
            msg = VoteVerdictMessage(
                pend,
                None if error is not None else bool(verdicts[i]),
                error,
            )
            try:
                self._queue.put_nowait((msg, pend.peer_id))
            except queue.Full:
                if ing is not None:
                    ing.apply_drops += 1
        self._wake()

    def _ingress_submit(self, vote: Vote, peer_id: str,
                        flow: Optional[int]) -> bool:
        """Host stage of the batched vote path. Returns True when the
        vote was consumed (queued for device verify, answered from the
        memo, or rejected by a host-stage check with the same outcome
        the sequential path produces); False routes it to the sequential
        path (wrong height shape, non-ed25519 key)."""
        rs = self.rs
        if vote.height != rs.height:
            return False  # catchup / future-height shapes stay sync
        ing = self._vote_ingress
        tr = self._tracer
        fid = None
        if tr.enabled:
            fid = flow if flow is not None else tr.flow
            span = tr.span(
                "consensus.verify_dispatch", flow=fid,
                flow_phase="t" if fid is not None else None,
                height=vote.height, round=vote.round, type=vote.type,
            )
        else:
            span = None
        try:
            if span is not None:
                with span:
                    chk = rs.votes.check_vote(vote, peer_id)
            else:
                chk = rs.votes.check_vote(vote, peer_id)
        except ErrVoteNonDeterministicSignature:
            return True  # sequential outcome: swallowed, returns False
        except ErrVoteConflictingVotes as e:
            self._record_conflicting_votes(vote, e)
            return True
        if chk is None:
            return True  # exact duplicate / invalid type: a no-op add
        pub = chk.pub_key
        if pub.type() != "ed25519":
            return False  # host lane for exotic keys
        from . import vote_ingress as _vi

        pend = _vi.PendingVote(
            vote, peer_id, pub.bytes(),
            vote.sign_bytes(self._state.chain_id),
            flow=fid, t_enq=_time.perf_counter(),
        )
        ing.submit(pend, rs.validators)
        return True

    def _apply_vote_verdict_msg(self, msg: VoteVerdictMessage,
                                peer_id: str) -> None:
        pend = msg.pend
        vote = pend.vote
        if msg.error is not None:
            # poisoned window (DispatchError): exactly these votes
            # re-drive through the full sequential per-vote path
            self._try_add_vote(vote, peer_id, flow=pend.flow)
            return
        tr = self._tracer
        if tr.enabled:
            fid = pend.flow
            with tr.span("consensus.verify_apply", flow=fid,
                         flow_phase="f" if fid is not None else None,
                         height=vote.height, round=vote.round,
                         type=vote.type, valid=bool(msg.valid)):
                self._try_add_vote_impl(vote, peer_id, verdict=msg.valid)
        else:
            self._try_add_vote_impl(vote, peer_id, verdict=msg.valid)

    def _send_internal(self, msg) -> None:
        self._enqueue(self._internal_queue, (msg, ""))
        for hook in self.broadcast_hooks:
            try:
                hook(msg)
            except Exception:  # noqa: BLE001 — gossip must not break consensus
                pass

    def wait_for_height(self, height: int, timeout: float = 30.0) -> None:
        """Block until the committed chain reaches `height` — on a
        condition signalled per commit, not a sleep-poll."""
        # injected-clock reads (not _time.time()): under simnet the
        # deadline must advance with VIRTUAL time or a replay would hang
        # on machine speed (tmlint simnet-determinism). Condition.wait's
        # timeout is REAL time though, so a monotonic deadline backstops
        # the loop — a wedged virtual clock (remaining frozen at
        # `timeout` forever) must still surface as TimeoutError instead
        # of re-waiting indefinitely.
        deadline = self._now() + timeout
        real_deadline = _time.monotonic() + timeout
        with self._commit_cond:
            while self._state.last_block_height < height:
                remaining = deadline - self._now()
                real_remaining = real_deadline - _time.monotonic()
                if remaining <= 0 or real_remaining <= 0:
                    raise TimeoutError(
                        f"height {height} not reached; at {self._state.last_block_height}"
                    )
                self._commit_cond.wait(min(remaining, real_remaining))

    @property
    def committed_state(self) -> State:
        return self._state

    # ------------------------------------------------------------------
    # the receive routine (state.go:757-850)

    def _pop_msg(self):
        """Next queued (msg, peer_id), internal queue first (own
        proposal/votes take priority, state.go:772), or None."""
        try:
            return self._internal_queue.get_nowait()
        except queue.Empty:
            pass
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            return None

    def _dispatch(self, msg, peer_id: str) -> None:
        """WAL-log then handle one message — shared by the receive thread
        and the stepped (simnet) driver."""
        if isinstance(msg, TimeoutInfo):
            self._wal_write(WALMessage(timeout=(
                int(msg.duration * 1000), msg.height, msg.round, msg.step)))
            self._handle_timeout(msg)
        else:
            self._wal_write_msg(msg, peer_id)
            try:
                self._handle_msg(msg, peer_id)
            except Exception:  # noqa: BLE001 — a bad peer message must not kill consensus
                import traceback

                traceback.print_exc()

    def process_pending(self, max_msgs: Optional[int] = None) -> int:
        """Drain queued messages synchronously; returns how many were
        processed. The stepped-mode pump: an external scheduler calls this
        off the on_enqueue hook instead of running _receive_routine."""
        n = 0
        while max_msgs is None or n < max_msgs:
            if self._quit.is_set():
                break
            item = self._pop_msg()
            if item is None:
                # Stepped-mode vote-ingress flush point (ISSUE 15): the
                # queue draining IS the deterministic window boundary —
                # flush_pending() host-verifies every open window in
                # submission order and enqueues the verdicts, which the
                # next loop iterations apply before anything else can
                # arrive. Replay-exact: flush timing is a pure function
                # of message arrival order.
                ing = self._vote_ingress
                if (ing is not None and ing.stepped
                        and ing.flush_pending()):
                    continue
                break
            self._dispatch(*item)
            n += 1
        return n

    def _receive_routine(self) -> None:
        while not self._quit.is_set():
            item = self._pop_msg()
            if item is not None:
                self._dispatch(*item)
            # woken by _wake() on any enqueue; the timeout bounds the _quit re-check
            elif self._msg_ready.wait(timeout=0.2):
                self._msg_ready.clear()

    def _wal_write(self, rec: WALMessage) -> None:
        if self._wal is not None:
            self._wal.write(rec)

    def _wal_write_msg(self, msg, peer_id: str) -> None:
        if self._wal is None:
            return
        if isinstance(msg, ProposalMessage):
            rec = WALMessage(msg_kind="proposal", msg_payload=msg.proposal.encode(), peer_id=peer_id)
        elif isinstance(msg, BlockPartMessage):
            from ..wire.proto import ProtoWriter

            w = ProtoWriter()
            w.write_varint(1, msg.height)
            w.write_varint(2, msg.round)
            w.write_message(3, msg.part.encode(), always=True)
            rec = WALMessage(msg_kind="block_part", msg_payload=w.bytes(), peer_id=peer_id)
        elif isinstance(msg, VoteMessage):
            rec = WALMessage(msg_kind="vote", msg_payload=msg.vote.encode(), peer_id=peer_id)
        else:
            return
        if peer_id == "":
            self._wal.write_sync(rec)  # own messages are synced (state.go:780)
        else:
            self._wal.write(rec)

    def _handle_msg(self, msg, peer_id: str) -> None:
        """state.go:849-920."""
        if isinstance(msg, ProposalMessage):
            self._set_proposal(msg.proposal)
        elif isinstance(msg, BlockPartMessage):
            added = self._add_proposal_block_part(msg, peer_id)
            if added and self.rs.proposal_block_parts is not None and \
                    self.rs.proposal_block_parts.is_complete():
                pass  # handled inside _add_proposal_block_part
        elif isinstance(msg, VoteMessage):
            if (
                self._vote_ingress is not None
                and peer_id != ""  # own votes stay sync (WAL-synced)
                and self._ingress_submit(msg.vote, peer_id, msg.flow)
            ):
                return
            self._try_add_vote(msg.vote, peer_id, flow=msg.flow)
        elif isinstance(msg, VoteVerdictMessage):
            self._apply_vote_verdict_msg(msg, peer_id)
        else:
            raise ValueError(f"unknown msg type {type(msg)}")

    def _tock(self, ti: TimeoutInfo) -> None:
        """Ticker callback → queue (state.go timeoutRoutine → tockChan)."""
        self._enqueue(self._queue, (ti, ""))

    def _handle_timeout(self, ti: TimeoutInfo) -> None:
        """state.go:923-1005."""
        rs = self.rs
        if ti.height != rs.height or ti.round < rs.round or (
            ti.round == rs.round and ti.step < rs.step
        ):
            return  # stale
        if ti.step == STEP_NEW_HEIGHT:
            self._enter_new_round(ti.height, 0)
        elif ti.step == STEP_NEW_ROUND:
            self._enter_propose(ti.height, 0)
        elif ti.step == STEP_PROPOSE:
            if self._event_bus:
                self._event_bus.publish_timeout_propose(rs.round_state_event())
            self._enter_prevote(ti.height, ti.round)
        elif ti.step == STEP_PREVOTE_WAIT:
            if self._event_bus:
                self._event_bus.publish_timeout_wait(rs.round_state_event())
            self._enter_precommit(ti.height, ti.round)
        elif ti.step == STEP_PRECOMMIT_WAIT:
            if self._event_bus:
                self._event_bus.publish_timeout_wait(rs.round_state_event())
            self._enter_precommit(ti.height, ti.round)
            self._enter_new_round(ti.height, ti.round + 1)
        else:
            raise ValueError(f"invalid timeout step {ti.step}")

    # ------------------------------------------------------------------
    # state transitions

    def _update_to_state(self, state: State) -> None:
        """state.go:624-722 updateToState."""
        rs = self.rs
        if rs.commit_round > -1 and 0 < rs.height and rs.height != state.last_block_height:
            raise RuntimeError(
                f"updateToState() expected state height of {rs.height} but found {state.last_block_height}"
            )
        validators = state.validators
        if state.last_block_height == 0:
            last_precommits = None
        else:
            if rs.votes is not None and rs.commit_round > -1:
                precommits = rs.votes.precommits(rs.commit_round)
                if precommits is None or not precommits.has_two_thirds_majority():
                    last_precommits = None
                else:
                    last_precommits = precommits
            else:
                last_precommits = None

        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height

        rs.height = height
        rs.round = 0
        rs.step = STEP_NEW_HEIGHT
        if rs.commit_time:
            rs.start_time = rs.commit_time + self._cfg.commit_timeout()
        else:
            rs.start_time = self._now() + self._cfg.commit_timeout()
        rs.validators = validators
        rs.proposal = None
        rs.proposal_block = None
        rs.proposal_block_parts = None
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        rs.valid_round = -1
        rs.valid_block = None
        rs.valid_block_parts = None
        rs.votes = HeightVoteSet(state.chain_id, height, validators)
        rs.commit_round = -1
        rs.last_commit = last_precommits
        rs.last_validators = state.last_validators
        rs.triggered_timeout_precommit = False
        self._state = state
        # flight recorder: a fresh timeline per height (an unfinished one
        # — catch-up, WAL replay — is simply superseded)
        self._timeline = HeightTimeline(height=height,
                                        t_new_height=self._now())

    # ------------------------------------------------------------------
    # per-height latency attribution (ISSUE 10)

    def _tl_mark(self, attr: str) -> None:
        """Stamp a phase transition once, on the current height's
        timeline; later re-entries (higher rounds re-reaching 2/3) keep
        the FIRST observation — the latency the height actually paid."""
        tl = self._timeline
        if tl is not None and tl.height == self.rs.height and \
                getattr(tl, attr) is None:
            setattr(tl, attr, self._now())

    def _tl_finish(self, tl: HeightTimeline) -> None:
        """Height committed+applied: retire the timeline into the ring and
        feed the phase histograms."""
        self.height_timelines.append(tl)
        self._timeline = None
        m = self._metrics
        if m is not None:
            try:
                for name, dur in tl.phases().items():
                    m.phase_seconds.observe(dur, phase=name)
            except Exception:  # noqa: BLE001 — metrics must never break commit
                pass

    def height_timeline(self, height: Optional[int] = None
                        ) -> Optional[HeightTimeline]:
        """The retained timeline for `height` (latest when None)."""
        ring = list(self.height_timelines)  # snapshot: RPC thread reads
        if not ring:
            return None
        if height is None:
            return ring[-1]
        for tl in ring:
            if tl.height == height:
                return tl
        return None

    def _schedule_round_0(self) -> None:
        sleep = max(self.rs.start_time - self._now(), 0.0)
        self._ticker.schedule_timeout(
            TimeoutInfo(sleep, self.rs.height, 0, STEP_NEW_HEIGHT)
        )

    def _new_step_event(self) -> None:
        if self._event_bus is not None:
            self._event_bus.publish_new_round_step(self.rs.round_state_event())

    def _enter_new_round(self, height: int, round_: int) -> None:
        """state.go:1008-1088."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step != STEP_NEW_HEIGHT
        ):
            return
        validators = rs.validators
        if rs.round < round_:
            validators = validators.copy()
            validators.increment_proposer_priority(round_ - rs.round)
        rs.round = round_
        rs.step = STEP_NEW_ROUND
        rs.validators = validators
        if self._metrics is not None:
            self._metrics.rounds.set(round_)
        if round_ != 0:
            rs.proposal = None
            rs.proposal_block = None
            rs.proposal_block_parts = None
        rs.votes.set_round(round_ + 1)  # track next round's votes
        rs.triggered_timeout_precommit = False
        if self._event_bus is not None:
            self._event_bus.publish_new_round(rs.round_state_event())
        wait_for_txs = (
            self._cfg.create_empty_blocks_interval_ms > 0
            and not self._cfg.create_empty_blocks
            and round_ == 0
        )
        if wait_for_txs:
            self._ticker.schedule_timeout(
                TimeoutInfo(
                    self._cfg.create_empty_blocks_interval_ms / 1000.0,
                    height, round_, STEP_NEW_ROUND,
                )
            )
            return
        self._enter_propose(height, round_)

    def _is_proposer(self) -> bool:
        if self._priv_validator_pub_key is None:
            return False
        proposer = self.rs.validators.get_proposer()
        return proposer is not None and proposer.address == self._priv_validator_pub_key.address()

    def _enter_propose(self, height: int, round_: int) -> None:
        """state.go:1090-1159."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PROPOSE
        ):
            return
        rs.round = round_
        rs.step = STEP_PROPOSE
        self._new_step_event()
        self._ticker.schedule_timeout(
            TimeoutInfo(self._cfg.propose_timeout(round_), height, round_, STEP_PROPOSE)
        )
        if self._priv_validator is not None and self._is_proposer():
            if self.decide_proposal_override is not None:
                self.decide_proposal_override(self, height, round_)
            else:
                self._decide_proposal(height, round_)
        # if the proposal is already complete (e.g. we are the proposer or
        # received parts earlier), advance
        if self._is_proposal_complete():
            self._enter_prevote(height, round_)

    def _decide_proposal(self, height: int, round_: int) -> None:
        """state.go:1161-1226 defaultDecideProposal."""
        rs = self.rs
        if rs.valid_block is not None:
            block, block_parts = rs.valid_block, rs.valid_block_parts
        else:
            commit = None
            if height == self._state.initial_height:
                commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
            elif rs.last_commit is not None and rs.last_commit.has_two_thirds_majority():
                commit = rs.last_commit.make_commit()
            else:
                return  # no commit for the previous block: cannot propose
            proposer_addr = self._priv_validator_pub_key.address()
            block, block_parts = self._block_exec.create_proposal_block(
                height, self._state, commit, proposer_addr
            )
        block_id = BlockID(hash=block.hash(), part_set_header=block_parts.header())
        proposal = Proposal(
            height=height,
            round=round_,
            pol_round=rs.valid_round,
            block_id=block_id,
            timestamp=_ts_from_float(self._now()),
        )
        try:
            proposal = self._priv_validator.sign_proposal(self._state.chain_id, proposal)
        except ValueError:
            return
        self._send_internal(ProposalMessage(proposal))
        for i in range(block_parts.total()):
            self._send_internal(BlockPartMessage(height, round_, block_parts.get_part(i)))

    def _is_proposal_complete(self) -> bool:
        """state.go:1228-1243."""
        rs = self.rs
        if rs.proposal is None or rs.proposal_block is None:
            return False
        if rs.proposal.pol_round < 0:
            return True
        prevotes = rs.votes.prevotes(rs.proposal.pol_round)
        return prevotes is not None and prevotes.has_two_thirds_majority()

    def _enter_prevote(self, height: int, round_: int) -> None:
        """state.go:1268-1296."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PREVOTE
        ):
            return
        rs.round = round_
        rs.step = STEP_PREVOTE
        self._new_step_event()
        if self.do_prevote_override is not None:
            self.do_prevote_override(self, height, round_)
        else:
            self._do_prevote(height, round_)

    def _do_prevote(self, height: int, round_: int) -> None:
        """state.go:1298-1336 defaultDoPrevote."""
        rs = self.rs
        if rs.locked_block is not None:
            self._sign_add_vote(PREVOTE_TYPE, rs.locked_block.hash(),
                                rs.locked_block_parts.header())
            return
        if rs.proposal_block is None:
            self._sign_add_vote(PREVOTE_TYPE, b"", None)
            return
        try:
            self._block_exec.validate_block(self._state, rs.proposal_block)
        except ValueError:
            self._sign_add_vote(PREVOTE_TYPE, b"", None)
            return
        self._sign_add_vote(
            PREVOTE_TYPE, rs.proposal_block.hash(), rs.proposal_block_parts.header()
        )

    def _enter_prevote_wait(self, height: int, round_: int) -> None:
        """state.go:1338-1362."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PREVOTE_WAIT
        ):
            return
        prevotes = rs.votes.prevotes(round_)
        if prevotes is None or not prevotes.has_two_thirds_any():
            raise RuntimeError("enter_prevote_wait without +2/3 prevotes")
        self._tl_mark("t_prevote_23")
        rs.round = round_
        rs.step = STEP_PREVOTE_WAIT
        self._new_step_event()
        self._ticker.schedule_timeout(
            TimeoutInfo(self._cfg.prevote_timeout(round_), height, round_, STEP_PREVOTE_WAIT)
        )

    def _enter_precommit(self, height: int, round_: int) -> None:
        """state.go:1364-1462."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= STEP_PRECOMMIT
        ):
            return
        rs.round = round_
        rs.step = STEP_PRECOMMIT
        self._tl_mark("t_prevote_23")  # entered on polka or prevote-wait
        self._new_step_event()         # timeout — 2/3 prevotes either way
        prevotes = rs.votes.prevotes(round_)
        block_id, ok = (prevotes.two_thirds_majority() if prevotes else (BlockID(), False))
        if not ok:
            # no polka: precommit nil
            self._sign_add_vote(PRECOMMIT_TYPE, b"", None)
            return
        if self._event_bus is not None:
            self._event_bus.publish_polka(rs.round_state_event())
        pol_round, _ = rs.votes.pol_info()
        if pol_round < round_:
            raise RuntimeError(f"POLRound {pol_round} < {round_}")
        if block_id.is_zero():
            # +2/3 prevoted nil: unlock and precommit nil
            if rs.locked_block is not None:
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                if self._event_bus is not None:
                    self._event_bus.publish_relock(rs.round_state_event())
            self._sign_add_vote(PRECOMMIT_TYPE, b"", None)
            return
        if rs.locked_block is not None and rs.locked_block.hash() == block_id.hash:
            # relock
            rs.locked_round = round_
            if self._event_bus is not None:
                self._event_bus.publish_relock(rs.round_state_event())
            self._sign_add_vote(PRECOMMIT_TYPE, block_id.hash, block_id.part_set_header)
            return
        if rs.proposal_block is not None and rs.proposal_block.hash() == block_id.hash:
            try:
                self._block_exec.validate_block(self._state, rs.proposal_block)
            except ValueError as e:
                raise RuntimeError(f"+2/3 prevoted an invalid block: {e}") from e
            rs.locked_round = round_
            rs.locked_block = rs.proposal_block
            rs.locked_block_parts = rs.proposal_block_parts
            if self._event_bus is not None:
                self._event_bus.publish_lock(rs.round_state_event())
            self._sign_add_vote(PRECOMMIT_TYPE, block_id.hash, block_id.part_set_header)
            return
        # +2/3 prevotes for a block we don't have: unlock, fetch, precommit nil
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
            block_id.part_set_header
        ):
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet.new_from_header(block_id.part_set_header)
        self._sign_add_vote(PRECOMMIT_TYPE, b"", None)

    def _enter_precommit_wait(self, height: int, round_: int) -> None:
        """state.go:1464-1491."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.triggered_timeout_precommit
        ):
            return
        precommits = rs.votes.precommits(round_)
        if precommits is None or not precommits.has_two_thirds_any():
            raise RuntimeError("enter_precommit_wait without +2/3 precommits")
        self._tl_mark("t_precommit_23")
        rs.triggered_timeout_precommit = True
        self._new_step_event()
        self._ticker.schedule_timeout(
            TimeoutInfo(self._cfg.precommit_timeout(round_), height, round_, STEP_PRECOMMIT_WAIT)
        )

    def _enter_commit(self, height: int, commit_round: int) -> None:
        """state.go:1518-1579."""
        rs = self.rs
        if rs.height != height or rs.step >= STEP_COMMIT:
            return
        rs.round = rs.round  # unchanged by commit
        rs.step = STEP_COMMIT
        rs.commit_round = commit_round
        rs.commit_time = self._now()
        self._tl_mark("t_precommit_23")  # 2/3 precommits proved just above
        self._tl_mark("t_commit")
        self._new_step_event()
        precommits = rs.votes.precommits(commit_round)
        block_id, ok = precommits.two_thirds_majority()
        if not ok:
            raise RuntimeError("RunActionCommit without +2/3 precommits")
        if rs.locked_block is not None and rs.locked_block_parts.has_header(
            block_id.part_set_header
        ):
            rs.proposal_block = rs.locked_block
            rs.proposal_block_parts = rs.locked_block_parts
        elif rs.proposal_block is None or not rs.proposal_block_parts.has_header(
            block_id.part_set_header
        ):
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet.new_from_header(block_id.part_set_header)
        self._try_finalize_commit(height)

    def _try_finalize_commit(self, height: int) -> None:
        """state.go:1581-1607."""
        rs = self.rs
        if rs.height != height:
            raise RuntimeError("try_finalize_commit at wrong height")
        precommits = rs.votes.precommits(rs.commit_round)
        block_id, ok = precommits.two_thirds_majority()
        if not ok or block_id.is_zero():
            return
        if rs.proposal_block is None or rs.proposal_block.hash() != block_id.hash:
            return  # don't have the block yet
        self._finalize_commit(height)

    def _finalize_commit(self, height: int) -> None:
        """state.go:1609-1700."""
        rs = self.rs
        if rs.height != height or rs.step != STEP_COMMIT:
            return
        precommits = rs.votes.precommits(rs.commit_round)
        block_id, ok = precommits.two_thirds_majority()
        block, block_parts = rs.proposal_block, rs.proposal_block_parts
        if not ok or not block_parts.has_header(block_id.part_set_header):
            raise RuntimeError("finalize_commit preconditions violated")
        if block.hash() != block_id.hash:
            raise RuntimeError("cannot finalize: proposal block does not hash to commit hash")
        # the verify/apply leg begins here: block validation (LastCommit
        # signatures ride the device batch engine) then ABCI apply
        self._tl_mark("t_verify_dispatch")
        self._block_exec.validate_block(self._state, block)

        # Save to block store before applying (state.go:1640-1652)
        if self._block_store.height() < block.header.height:
            seen_commit = precommits.make_commit()
            self._block_store.save_block(block, block_parts, seen_commit)

        if self._wal is not None:
            self._wal.write_sync(WALMessage(end_height=height))

        self._record_metrics(block, block_parts)

        state_copy = self._state.copy()
        new_state = self._block_exec.apply_block(state_copy, block_id, block)

        tl = self._timeline
        if tl is not None and tl.height == height:
            tl.rounds = rs.round + 1
            tl.t_applied = self._now()
            self._tl_finish(tl)

        # NewHeight: updateToState + schedule round 0
        self._update_to_state(new_state)
        self._done_first_block.set()
        with self._commit_cond:
            self._commit_cond.notify_all()
        for hook in self._height_events:
            try:
                hook(height)
            except Exception:  # noqa: BLE001 — observer hooks must not break commit
                pass
        self._schedule_round_0()

    def _record_metrics(self, block, block_parts) -> None:
        """state.go:1702-1757 recordMetrics — called with the pre-apply
        state still current, so last_block_time and last_validators refer
        to the previous height (what the interval and the missing-set
        accounting need)."""
        m = self._metrics
        if m is None:
            return
        try:
            hdr = block.header
            m.height.set(hdr.height)
            n_txs = len(block.data.txs)
            m.num_txs.set(n_txs)
            m.total_txs.inc(n_txs)
            # the part set already carries the wire size — no re-encode
            m.block_size_bytes.set(block_parts.byte_size())
            vals = self._state.validators
            m.validators.set(vals.size())
            m.validators_power.set(vals.total_voting_power())
            m.byzantine_validators.set(len(block.evidence))
            # the block's LastCommit is over the previous height's set
            last_vals = self._state.last_validators
            if block.last_commit is not None and last_vals is not None and \
                    last_vals.size() == len(block.last_commit.signatures):
                missing = 0
                missing_power = 0
                for i, cs in enumerate(block.last_commit.signatures):
                    if cs.is_absent():
                        missing += 1
                        missing_power += last_vals.validators[i].voting_power
                m.missing_validators.set(missing)
                m.missing_validators_power.set(missing_power)
            last_t = self._state.last_block_time
            if self._state.last_block_height > 0 and last_t is not None:
                dt = (hdr.time.seconds - last_t.seconds) + (
                    hdr.time.nanos - last_t.nanos
                ) / 1e9
                if dt >= 0:
                    m.block_interval_seconds.observe(dt)
        except Exception:  # noqa: BLE001 — metrics must never break commit
            pass

    # ------------------------------------------------------------------
    # proposals / parts / votes

    def _set_proposal(self, proposal: Proposal) -> None:
        """state.go:1753-1804 defaultSetProposal."""
        rs = self.rs
        if rs.proposal is not None:
            return
        if proposal.height != rs.height or proposal.round != rs.round:
            return
        if proposal.pol_round < -1 or (
            proposal.pol_round >= 0 and proposal.pol_round >= proposal.round
        ):
            raise ValueError("error invalid proposal POL round")
        proposer = rs.validators.get_proposer()
        if not proposer.pub_key.verify_signature(
            proposal.sign_bytes(self._state.chain_id), proposal.signature
        ):
            raise ValueError("error invalid proposal signature")
        rs.proposal = proposal
        self._tl_mark("t_proposal")
        if rs.proposal_block_parts is None:
            rs.proposal_block_parts = PartSet.new_from_header(
                proposal.block_id.part_set_header
            )

    def _add_proposal_block_part(self, msg: BlockPartMessage, peer_id: str) -> bool:
        """state.go:1806-1895."""
        rs = self.rs
        if msg.height != rs.height:
            return False
        if rs.proposal_block_parts is None:
            return False
        added = rs.proposal_block_parts.add_part(msg.part)
        if added and rs.proposal_block_parts.is_complete():
            data = rs.proposal_block_parts.assemble()
            rs.proposal_block = Block.decode(data)
            if self._event_bus is not None:
                self._event_bus.publish_complete_proposal(rs.round_state_event())
            prevotes = rs.votes.prevotes(rs.round)
            block_id, has_23 = (
                prevotes.two_thirds_majority() if prevotes else (BlockID(), False)
            )
            if has_23 and not block_id.is_zero() and rs.valid_round < rs.round:
                if rs.proposal_block.hash() == block_id.hash:
                    rs.valid_round = rs.round
                    rs.valid_block = rs.proposal_block
                    rs.valid_block_parts = rs.proposal_block_parts
            if rs.step <= STEP_PROPOSE and self._is_proposal_complete():
                self._enter_prevote(rs.height, rs.round)
            elif rs.step == STEP_COMMIT:
                self._try_finalize_commit(rs.height)
        return added

    def _try_add_vote(self, vote: Vote, peer_id: str,
                      flow: Optional[int] = None) -> bool:
        """state.go:1959-2005, span-wrapped: the vote's signature verify +
        set accounting is the consensus-side terminus of a gossiped vote's
        causal chain — the flow id captured at enqueue time (or parked on
        the tracer by a synchronous delivery driver) FINISHES here, so the
        merged trace links gossip send → deliver → verify dispatch."""
        tr = self._tracer
        if tr.enabled:
            fid = flow if flow is not None else tr.flow
            with tr.span("consensus.verify_dispatch", flow=fid,
                         flow_phase="f" if fid is not None else None,
                         height=vote.height, round=vote.round,
                         type=vote.type):
                return self._try_add_vote_impl(vote, peer_id)
        return self._try_add_vote_impl(vote, peer_id)

    def _try_add_vote_impl(self, vote: Vote, peer_id: str,
                           verdict: Optional[bool] = None) -> bool:
        try:
            return self._add_vote(vote, peer_id, verdict=verdict)
        except ErrVoteNonDeterministicSignature:
            return False
        except ErrVoteConflictingVotes as e:
            self._record_conflicting_votes(vote, e)
            return False

    def _record_conflicting_votes(self, vote: Vote,
                                  e: ErrVoteConflictingVotes) -> bool:
        """The ErrVoteConflictingVotes arm of state.go:1959-2005 —
        evidence: our own double-sign would be fatal; peers' recorded.
        Shared by the sequential path and the ingress host/apply stages."""
        if (
            self._priv_validator_pub_key is not None
            and vote.validator_address == self._priv_validator_pub_key.address()
        ):
            return False
        if self._evpool is not None:
            from ..types.evidence import DuplicateVoteEvidence

            try:
                ev = DuplicateVoteEvidence.new(
                    e.vote_a, e.vote_b, self._state.last_block_time,
                    self._state.validators,
                )
                self._evpool.add_evidence(ev)
            except ValueError:
                pass
        return False

    def _add_vote(self, vote: Vote, peer_id: str,
                  verdict: Optional[bool] = None) -> bool:
        """state.go:2007-2180. `verdict` is the device signature verdict
        from the ingress lane (ISSUE 15): None = sequential host verify;
        a bool routes through HeightVoteSet.apply_vote_verdict, which
        re-runs the host checks and applies. A verdict that arrives after
        the height moved on falls into the catchup/stale branches below —
        those always re-verify sequentially, never trusting a verdict
        produced against a different height's vote sets."""
        rs = self.rs
        # A precommit for the previous height (catchup for commit-timeout)
        if vote.height + 1 == rs.height and vote.type == PRECOMMIT_TYPE:
            if rs.step != STEP_NEW_HEIGHT or rs.last_commit is None:
                return False
            added = rs.last_commit.add_vote(vote)
            if added:
                if self._event_bus is not None:
                    self._event_bus.publish_vote(vote)
                for hook in self.vote_added_hooks:
                    try:
                        hook(vote)
                    except Exception:  # noqa: BLE001
                        pass
            return added
        if vote.height != rs.height:
            return False

        if verdict is None:
            added = rs.votes.add_vote(vote, peer_id)
        else:
            added = rs.votes.apply_vote_verdict(vote, peer_id, verdict)
        if not added:
            return False
        if self._event_bus is not None:
            self._event_bus.publish_vote(vote)
        for hook in self.vote_added_hooks:
            try:
                hook(vote)
            except Exception:  # noqa: BLE001 — gossip hooks must not break consensus
                pass

        if vote.type == PREVOTE_TYPE:
            prevotes = rs.votes.prevotes(vote.round)
            # valid-block tracking (state.go:2085-2130)
            block_id, ok = prevotes.two_thirds_majority()
            if ok and not block_id.is_zero() and rs.valid_round < vote.round and vote.round == rs.round:
                if rs.proposal_block is not None and rs.proposal_block.hash() == block_id.hash:
                    rs.valid_round = vote.round
                    rs.valid_block = rs.proposal_block
                    rs.valid_block_parts = rs.proposal_block_parts
                else:
                    rs.proposal_block = None
                    if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                        block_id.part_set_header
                    ):
                        rs.proposal_block_parts = PartSet.new_from_header(
                            block_id.part_set_header
                        )
                if self._event_bus is not None:
                    self._event_bus.publish_valid_block(rs.round_state_event())
            # step transitions (state.go:2132-2160)
            if rs.round < vote.round and prevotes.has_two_thirds_any():
                self._enter_new_round(rs.height, vote.round)
            elif rs.round == vote.round and rs.step >= STEP_PREVOTE:
                block_id2, ok2 = prevotes.two_thirds_majority()
                if ok2 and (self._is_proposal_complete() or block_id2.is_zero()):
                    self._enter_precommit(rs.height, vote.round)
                elif prevotes.has_two_thirds_any():
                    self._enter_prevote_wait(rs.height, vote.round)
            elif rs.proposal is not None and rs.proposal.pol_round >= 0 and rs.proposal.pol_round == vote.round:
                if self._is_proposal_complete():
                    self._enter_prevote(rs.height, rs.round)
        elif vote.type == PRECOMMIT_TYPE:
            precommits = rs.votes.precommits(vote.round)
            block_id, ok = precommits.two_thirds_majority()
            if ok:
                self._enter_new_round(rs.height, vote.round)
                self._enter_precommit(rs.height, vote.round)
                if not block_id.is_zero():
                    self._enter_commit(rs.height, vote.round)
                    if self._cfg.skip_timeout_commit and precommits.has_all():
                        self._enter_new_round(rs.height, 0)
                else:
                    self._enter_precommit_wait(rs.height, vote.round)
            elif rs.round <= vote.round and precommits.has_two_thirds_any():
                self._enter_new_round(rs.height, vote.round)
                self._enter_precommit_wait(rs.height, vote.round)
        return added

    def _sign_vote(self, vote_type: int, hash_: bytes, header) -> Optional[Vote]:
        """state.go:2182-2230 signVote."""
        if self._priv_validator is None or self._priv_validator_pub_key is None:
            return None
        addr = self._priv_validator_pub_key.address()
        idx, val = self.rs.validators.get_by_address(addr)
        if val is None:
            return None  # not a validator
        block_id = BlockID(hash=hash_, part_set_header=header) if hash_ else BlockID()
        vote = Vote(
            type=vote_type,
            height=self.rs.height,
            round=self.rs.round,
            block_id=block_id,
            timestamp=self._vote_time(),
            validator_address=addr,
            validator_index=idx,
        )
        try:
            # The signer returns the signed vote — possibly with the
            # last-signed timestamp restored on a same-HRS re-sign
            # (privval file.go:339-341), so the signature always verifies.
            return self._priv_validator.sign_vote(self._state.chain_id, vote)
        except ValueError:
            return None

    def _vote_time(self) -> Timestamp:
        """state.go voteTime: max(now, lastBlockTime + 1ns-ish)."""
        now = _ts_from_float(self._now())
        lbt = self._state.last_block_time
        min_time = Timestamp(seconds=lbt.seconds, nanos=lbt.nanos + 1)
        if min_time.nanos >= 10**9:
            min_time = Timestamp(seconds=min_time.seconds + 1, nanos=min_time.nanos - 10**9)
        if _ts_le(now, min_time):
            return min_time
        return now

    def _sign_add_vote(self, vote_type: int, hash_: bytes, header) -> Optional[Vote]:
        vote = self._sign_vote(vote_type, hash_, header)
        if vote is not None:
            self._send_internal(VoteMessage(vote))
        return vote

    # ------------------------------------------------------------------
    # WAL replay (replay.go:96-160 catchupReplay)

    def catch_up_to_state(self, state: State) -> None:
        """node.go:323-343 switchToConsensus: adopt a state advanced by
        statesync/blocksync BEFORE the state machine starts (safe while
        commit_round == -1), and rebuild LastCommit from the stored seen
        commit so proposing can resume."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("cannot catch up a running consensus state")
        self._update_to_state(state)
        self._reconstruct_last_commit()

    def _reconstruct_last_commit(self) -> None:
        """state.go:518-543 reconstructLastCommit: after a restart the
        in-memory precommit VoteSet for the last committed height is gone;
        rebuild it from the block store's seen commit so the proposer can
        assemble the next block's LastCommit (without this a restarted
        validator can never propose again)."""
        state = self._state
        if state.last_block_height == 0 or self.rs.last_commit is not None:
            return
        seen = self._block_store.load_seen_commit()
        if seen is None or seen.height != state.last_block_height:
            return
        vs = VoteSet(
            state.chain_id, seen.height, seen.round, PRECOMMIT_TYPE, state.last_validators
        )
        for idx, cs in enumerate(seen.signatures):
            if cs.is_absent():
                continue
            try:
                vs.add_vote(
                    Vote(
                        type=PRECOMMIT_TYPE,
                        height=seen.height,
                        round=seen.round,
                        block_id=cs.block_id(seen.block_id),
                        timestamp=cs.timestamp,
                        validator_address=cs.validator_address,
                        validator_index=idx,
                        signature=cs.signature,
                    )
                )
            except ValueError:
                continue  # e.g. nil-vote sigs; majority check below decides
        if vs.has_two_thirds_majority():
            self.rs.last_commit = vs

    def _replay_wal(self) -> None:
        if self._wal is None:
            return
        tail = self._wal.search_for_end_height(self._state.last_block_height)
        if tail is None:
            return
        for rec in tail:
            if rec.end_height is not None:
                continue
            if rec.timeout is not None:
                continue  # timeouts are rescheduled naturally
            try:
                if rec.msg_kind == "proposal":
                    self._set_proposal(Proposal.decode(rec.msg_payload))
                elif rec.msg_kind == "block_part":
                    from ..wire.proto import decode_message, field_bytes, field_int

                    f = decode_message(rec.msg_payload)
                    self._add_proposal_block_part(
                        BlockPartMessage(
                            height=field_int(f, 1),
                            round=field_int(f, 2),
                            part=Part.decode(field_bytes(f, 3)),
                        ),
                        rec.peer_id,
                    )
                elif rec.msg_kind == "vote":
                    self._try_add_vote(Vote.decode(rec.msg_payload), rec.peer_id)
            except (ValueError, RuntimeError):
                continue
