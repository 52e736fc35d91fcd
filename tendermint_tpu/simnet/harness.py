"""Cluster builder + run driver + invariant checkers.

Drives N REAL consensus nodes — consensus.state.ConsensusState +
consensus.reactor.ConsensusReactor + consensus.wal.WAL + the crypto.batch
verify path — single-threaded over a virtual network and a virtual clock.
Nothing is mocked below the transport: proposals, block parts, votes and
commits flow through the same code a production node runs; only threads,
sockets and the wall clock are replaced by the SimClock event loop.

Determinism contract: a run is a pure function of
(seed, n_nodes, link config, fault schedule, consensus config, txs).
`fingerprint()` digests the committed chain; `SimNetwork.schedule_digest`
digests the delivery order. Same seed ⇒ both identical; different seed ⇒
the schedule digest differs (and usually the fingerprint too, through
vote timestamps).

Crash model: a crashed node loses everything in memory; its WAL file,
block/state/app stores (the "disk") and its privval last-sign-state
survive. Restart rebuilds the node from those — the real WAL-replay
recovery path — and the invariant sweep then requires its chain to
reconverge with the cluster.

Invariants (Tendermint safety, checked live at every commit):
  agreement       every node that commits height h commits the same block
  quorum          every stored commit carries >2/3 of voting power
  monotonicity    a node's committed height never goes backwards
  convergence     after the run, every node's chain is a prefix of the
                  agreed canonical chain (covers WAL-replay recovery)
"""

from __future__ import annotations

import hashlib
import os
import tempfile
import time as _wall
from dataclasses import dataclass, field as _field
from typing import Dict, List, Optional

from ..observability import trace as _trace
from .clock import NodeClock, SimClock
from .faults import Fault, make_double_sign_prevote
from .transport import LinkConfig, SimNetwork, SimRouter

CHAIN_ID = "simnet-chain"
GENESIS_SECONDS = 1_700_000_000


def _default_config():
    from ..config import ConsensusConfig

    return ConsensusConfig(
        timeout_propose_ms=400,
        timeout_propose_delta_ms=100,
        timeout_prevote_ms=200,
        timeout_prevote_delta_ms=100,
        timeout_precommit_ms=200,
        timeout_precommit_delta_ms=100,
        timeout_commit_ms=100,
        skip_timeout_commit=False,
    )


@dataclass
class SimReport:
    ok: bool
    reason: str
    height: int
    heights: List[int]
    fingerprint: str
    schedule_digest: str
    violations: List[str]
    seed: int
    virtual_s: float
    wall_s: float
    events_run: int
    net: dict
    faults_applied: List[str] = _field(default_factory=list)
    n_validators: int = 0
    valset_changes: List[int] = _field(default_factory=list)
    epoch_cache: dict = _field(default_factory=dict)
    # flight recorder (ISSUE 10): the last-K HeightTimeline dicts from the
    # most-advanced live node (virtual-clock timestamps — deterministic),
    # and — ONLY when an invariant broke — a flight_recorder dump carrying
    # every node's recent timelines plus the merged trace tail, so
    # "invariant broke at h=37" arrives with its own evidence attached
    height_timelines: List[dict] = _field(default_factory=list)
    flight_recorder: Optional[dict] = None
    # chain-replay catch-up (ISSUE 14): one summary dict per registered
    # CatchupDriver — replayed-range hit rate, fetch/drop counts and the
    # rejoin point, all virtual-clock-derived (deterministic)
    catchup: Optional[List[dict]] = None
    # the run ended because the REAL-time budget expired, not because the
    # virtual deadline passed or an invariant broke — machine-speed
    # dependent, so schedule search treats such a run as INCONCLUSIVE
    # rather than a bug (a wedge is detected deterministically by the
    # virtual deadline as long as the wall budget exceeds the time needed
    # to burn it)
    wall_budget_hit: bool = False

    def to_dict(self) -> dict:
        return dict(self.__dict__)


class _SigMemo:
    """Process-wide ed25519 verify memo for LARGE clusters: in a
    single-process simulation every node re-verifies the same (pub, msg,
    sig) triples — at 100 nodes that is ~99 redundant pure-Python curve
    evaluations per vote. Verification is a deterministic pure function,
    so memoizing the VERDICT (true and false alike) changes no observable
    behavior, only the wall clock. Installed around crypto.ed25519.
    verify_zip215_fast for the duration of a run; bounded by wholesale
    clear (entries are tiny and a run's unique-signature count is far
    below the cap)."""

    def __init__(self, real, cap: int = 1 << 17):
        self.real = real
        self.cap = cap
        self.cache: Dict[tuple, bool] = {}

    def __call__(self, pub: bytes, msg: bytes, sig: bytes) -> bool:
        key = (pub, msg, sig)
        v = self.cache.get(key)
        if v is None:
            v = self.real(pub, msg, sig)
            if len(self.cache) >= self.cap:
                self.cache.clear()
            self.cache[key] = v
        return v


class SimNode:
    """One simulated validator: persistent 'disk' + rebuildable runtime."""

    def __init__(self, cluster: "Cluster", idx: int):
        from ..crypto import ed25519
        from ..db import MemDB
        from ..privval import FilePV

        self.cluster = cluster
        self.idx = idx
        self.node_id = f"sim{idx}"
        self.sk = ed25519.gen_priv_key(bytes([idx + 1]) * 32)
        # The "disk": survives crashes. The FilePV instance doubles as the
        # persisted last-sign-state file (double-sign protection must hold
        # across a crash/restart, privval file.go).
        self.pv = FilePV(self.sk)
        self.app_db = MemDB()
        self.state_db = MemDB()
        self.block_db = MemDB()
        self.wal_path = os.path.join(cluster.base_dir, f"node{idx}", "cs.wal")
        os.makedirs(os.path.dirname(self.wal_path), exist_ok=True)
        self.node_clock = NodeClock(cluster.clock)
        # per-node tracer on the SHARED virtual clock (ISSUE 10): every
        # node's spans land on one timebase, stamped with the node id, so
        # the cluster exports ONE merged trace with a pid per node.
        # Survives crash/restart (the runtime is rebuilt, the trace isn't)
        self.tracer = _trace.SpanTracer(
            capacity=int(os.environ.get("TM_TPU_SIMNET_TRACE_BUFFER")
                         or "8192"),
            node=self.node_id,
            now=cluster.clock.time,
            epoch=cluster.clock.time(),
        )

        self.crashed = False
        self.byzantine = False
        self.cs = None
        self.reactor = None
        self.router: Optional[SimRouter] = None
        self.bstore = None
        self.sstore = None
        self.mp = None
        self._pump_pending = False
        self._gossip_timer = None
        self._last_maj23 = float("-inf")
        self._last_committed = 0
        self.restarts = 0

    # -- build/teardown --------------------------------------------------

    def build(self, genesis: bool) -> None:
        """Construct the runtime (ConsensusState + reactor) from the
        persistent stores; `genesis=False` is the restart path."""
        from ..abci import LocalClient
        from ..abci.kvstore import PersistentKVStoreApplication
        from ..consensus import ConsensusState, WAL
        from ..consensus.reactor import ConsensusReactor
        from ..eventbus import EventBus
        from ..mempool import TxMempool
        from ..state import make_genesis_state
        from ..state.execution import BlockExecutor
        from ..state.store import StateStore
        from ..store import BlockStore

        c = self.cluster
        # the persistent kvstore variant: "val:<b64 pub>!<power>" txs come
        # back as EndBlock validator updates, so val_join/val_leave/
        # val_power faults rotate the ACTIVE set through the real
        # state.execution update path
        app = PersistentKVStoreApplication(db=self.app_db)
        sstore = StateStore(self.state_db)
        if genesis:
            state = make_genesis_state(c.genesis_doc)
            sstore.save(state)
        else:
            state = sstore.load()
            if state is None:  # crashed before the first state save
                state = make_genesis_state(c.genesis_doc)
        self.sstore = sstore
        self.bstore = BlockStore(self.block_db)
        mp = TxMempool(LocalClient(app))
        self.mp = mp
        if genesis:
            for tx in c.txs_for(self.idx):
                mp.check_tx(tx)
        bus = EventBus()
        ex = BlockExecutor(
            sstore, LocalClient(app), mempool=mp, block_store=self.bstore,
            event_bus=bus,
        )
        self.cs = ConsensusState(
            c.config,
            state,
            ex,
            self.bstore,
            mempool=mp,
            event_bus=bus,
            wal=WAL(self.wal_path),
            priv_validator=self.pv,
            clock=self.node_clock,
            tracer=self.tracer,
        )
        self.cs.on_enqueue = self._on_enqueue
        self.cs._height_events.append(self._on_commit)
        if self.byzantine:
            self.cs.do_prevote_override = make_double_sign_prevote(
                self.sk, c.chain_id
            )
        self.router = SimRouter(c.network, self.node_id)
        self.reactor = ConsensusReactor(
            self.cs, self.router, block_store=self.bstore, rng=c.clock.rng
        )
        c.network.set_receiver(self.node_id, self.reactor.handle_envelope)

    def start(self) -> None:
        self.crashed = False
        self._pump_pending = False
        for peer in self.cluster.nodes:
            if peer is self or peer.crashed:
                continue
            self.reactor.add_peer(peer.node_id)
            peer.reactor.add_peer(self.node_id)
        self.cs.start_stepped()
        if self.cluster.vote_ingress:
            # AFTER start_stepped: WAL replay (inside build) must ride
            # the sequential path; live peer votes window from here on
            self.cs.attach_vote_ingress(stepped=True)
        self._schedule_gossip()

    def crash(self) -> None:
        """SIGKILL-equivalent: drop the runtime, keep the disk."""
        if self.crashed:
            return
        self.crashed = True
        if self._gossip_timer is not None:
            # a tick scheduled before the crash must not survive into a
            # fast restart — it would re-arm and double the gossip chain
            self._gossip_timer.cancel()
            self._gossip_timer = None
        self.cluster.network.set_down(self.node_id, True)
        for peer in self.cluster.nodes:
            if peer is not self and peer.reactor is not None:
                peer.reactor.remove_peer(self.node_id)
        self.cs.stop_stepped()
        self.cs = None
        self.reactor = None

    def restart(self) -> None:
        if not self.crashed:
            return
        self.restarts += 1
        self.cluster.network.set_down(self.node_id, False)
        self.build(genesis=False)
        self.start()

    # -- event-loop plumbing ---------------------------------------------

    def _on_enqueue(self) -> None:
        if self._pump_pending or self.crashed:
            return
        self._pump_pending = True
        self.cluster.clock.call_later(0.0, self._pump)

    def _pump(self) -> None:
        self._pump_pending = False
        if self.crashed or self.cs is None:
            return
        self.cs.process_pending()

    def _schedule_gossip(self) -> None:
        # the reactor's OWN cadence (ConsensusReactor.GOSSIP_INTERVAL) so
        # the sim always validates the production timing regime; small
        # per-node phase offset so sweeps interleave rather than all
        # landing on identical timestamps
        self._gossip_timer = self.cluster.clock.call_later(
            self.reactor.GOSSIP_INTERVAL + self.idx * 0.003, self._gossip_tick
        )

    def _gossip_tick(self) -> None:
        if self.crashed or self.reactor is None:
            return
        now = self.cluster.clock.time()
        query = now - self._last_maj23 >= self.reactor.QUERY_MAJ23_INTERVAL
        if query:
            self._last_maj23 = now
        try:
            self.reactor.gossip_once(query)
        except Exception:  # noqa: BLE001 — gossip must never kill the sim
            pass
        self._gossip_timer = self.cluster.clock.call_later(
            self.reactor.GOSSIP_INTERVAL, self._gossip_tick
        )

    def _on_commit(self, height: int) -> None:
        self.cluster._node_committed(self, height)

    def height(self) -> int:
        return self.bstore.height() if self.bstore is not None else 0


class Cluster:
    """N-node simulated cluster over one SimClock."""

    def __init__(
        self,
        n_nodes: int = 4,
        seed: int = 0,
        link: Optional[LinkConfig] = None,
        faults: Optional[List[Fault]] = None,
        config=None,
        txs_per_node: int = 0,
        base_dir: Optional[str] = None,
        chain_id: str = CHAIN_ID,
        n_validators: Optional[int] = None,
        sig_memo: Optional[bool] = None,
        tracing: Optional[bool] = None,
        vote_ingress: Optional[bool] = None,
    ):
        from ..types import Timestamp
        from ..types.genesis import GenesisDoc, GenesisValidator

        self.seed = seed
        self.chain_id = chain_id
        self.faults = list(faults or [])
        for f in self.faults:  # validate before any filesystem side effects
            f.validate(n_nodes)
        if n_validators is None:
            n_validators = n_nodes
        if not 1 <= n_validators <= n_nodes:
            raise ValueError(f"n_validators must be in 1..{n_nodes}")
        # nodes [0, n_validators) are genesis validators; the rest are
        # standby FULL nodes — they run the complete consensus state
        # machine (track rounds, fetch parts, commit blocks) but hold no
        # voting power until a val_join fault rotates them in
        self.n_validators = n_validators
        self.clock = SimClock(seed=seed)
        self.network = SimNetwork(self.clock, default_link=link)
        self.config = config or _default_config()
        self.txs_per_node = txs_per_node
        self._owns_base_dir = base_dir is None
        self.base_dir = base_dir or tempfile.mkdtemp(prefix="simnet-")
        self._fault_fired = [False] * len(self.faults)
        self.violations: List[str] = []
        self.faults_applied: List[str] = []
        self._canonical: Dict[int, bytes] = {}
        self._started = False
        self._stopped = False
        # memoize ed25519 verification verdicts across nodes — pure
        # wall-clock relief for big clusters (see _SigMemo); default on
        # from 12 nodes up
        self._sig_memo_wanted = n_nodes >= 12 if sig_memo is None else sig_memo
        self._sig_memo: Optional[_SigMemo] = None
        # live-vote ingress (ISSUE 15): stepped accumulators — votes
        # window on each node and flush deterministically when its pump
        # drains, so runs stay replay-exact. Default follows the env knob.
        if vote_ingress is None:
            vote_ingress = bool(os.environ.get("TM_TPU_SIMNET_VOTE_INGRESS"))
        self.vote_ingress = bool(vote_ingress)
        # (height, fault) for fired val_* faults that must change the set
        self._rotations_fired: List[tuple] = []
        self._epoch_stats0 = self._epoch_stats()
        # nodes whose crash fault promises a restart (restart_after or an
        # explicit restart fault) — run_to_height waits for these, while a
        # crash-stop node is simply excluded from the liveness target
        self._pending_restarts: set = set()
        # CatchupDrivers (simnet/catchup.py) register here; run_to_height
        # folds their summaries into SimReport.catchup
        self.catchup_drivers: List = []

        # cluster tracing (ISSUE 10): None follows the process tracer's
        # enabled flag at start() time (tools/simnet_run.py --trace turns
        # that on), True/False forces it. The flow-id counter runs either
        # way, so tracing cannot perturb replay exactness.
        self._tracing = tracing

        self.nodes = [SimNode(self, i) for i in range(n_nodes)]
        self.network.set_tracers({n.node_id: n.tracer for n in self.nodes})
        self.genesis_doc = GenesisDoc(
            chain_id=chain_id,
            genesis_time=Timestamp(seconds=GENESIS_SECONDS),
            validators=[
                GenesisValidator(address=b"", pub_key=n.sk.pub_key(), power=10)
                for n in self.nodes[:n_validators]
            ],
        )
        # trigger-less double_sign faults are byzantine from genesis and
        # must be flagged before build(); triggered ones are installed on
        # the live node when they fire (_apply_fault)
        for f in self.faults:
            if f.kind == "double_sign" and f.at_height is None and f.at_time is None:
                self.nodes[f.node].byzantine = True
        for n in self.nodes:
            n.build(genesis=True)

    def txs_for(self, idx: int) -> List[bytes]:
        return [
            b"k%d_%d=v%d" % (idx, j, j) for j in range(self.txs_per_node)
        ]

    # -- lifecycle -------------------------------------------------------

    @staticmethod
    def _epoch_stats() -> dict:
        from ..ops import epoch_cache as _epoch

        return _epoch.stats()

    def _install_sig_memo(self) -> None:
        from ..crypto import ed25519 as _ed

        if self._sig_memo_wanted and not isinstance(
            _ed.verify_zip215_fast, _SigMemo
        ):
            self._sig_memo = _SigMemo(_ed.verify_zip215_fast)
            _ed.verify_zip215_fast = self._sig_memo

    def _remove_sig_memo(self) -> None:
        from ..crypto import ed25519 as _ed

        if self._sig_memo is not None and _ed.verify_zip215_fast is self._sig_memo:
            _ed.verify_zip215_fast = self._sig_memo.real
        self._sig_memo = None

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._install_sig_memo()
        # start from a COLD epoch cache: the cache is process-wide, so a
        # previous same-process run (e.g. the replay-exactness second
        # pass) would otherwise leave this run's epochs pre-warmed —
        # breaking both the cold-registration invariant and the
        # run-to-run identity of cache behavior
        from ..ops import epoch_cache as _epoch

        c = _epoch.cache()
        if c is not None:
            c.clear()
        self._epoch_stats0 = self._epoch_stats()
        tracing = (
            _trace.TRACER.enabled if self._tracing is None else self._tracing
        )
        for n in self.nodes:
            n.tracer.configure(enabled=tracing)
        for n in self.nodes:
            n.start()
        for i, f in enumerate(self.faults):
            if f.at_time is not None:
                self.clock.call_later(
                    f.at_time, lambda i=i: self._apply_fault(i)
                )
            elif f.at_height is None and f.kind == "double_sign":
                self._apply_fault(i)  # active from genesis; record it

    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        self._remove_sig_memo()
        for n in self.nodes:
            if not n.crashed and n.cs is not None:
                n.cs.stop_stepped()
        if self._owns_base_dir:
            import shutil

            shutil.rmtree(self.base_dir, ignore_errors=True)

    # -- faults ----------------------------------------------------------

    def _node_committed(self, node: SimNode, height: int) -> None:
        """Per-commit hook: live invariants + height-triggered faults."""
        # monotonicity
        if height <= node._last_committed:
            self.violations.append(
                f"monotonicity: node {node.idx} committed h{height} after "
                f"h{node._last_committed}"
            )
        node._last_committed = height
        blk = node.bstore.load_block(height)
        bh = bytes(blk.hash()) if blk is not None else b"?"
        # agreement
        prev = self._canonical.setdefault(height, bh)
        if prev != bh:
            self.violations.append(
                f"agreement: node {node.idx} committed {bh.hex()[:16]} at "
                f"h{height}, cluster committed {prev.hex()[:16]}"
            )
        # quorum (+2/3 voting power on the stored seen commit)
        seen = node.bstore.load_seen_commit()
        if seen is not None and seen.height == height:
            bad = self.commit_quorum_violation(seen, node.idx, node=node)
            if bad is not None:
                self.violations.append(bad)
        # height-triggered faults
        for i, f in enumerate(self.faults):
            if not self._fault_fired[i] and f.at_height is not None and height >= f.at_height:
                self._apply_fault(i)

    def _apply_fault(self, i: int) -> None:
        if self._fault_fired[i]:
            return
        self._fault_fired[i] = True
        f = self.faults[i]
        t = self.clock.time()
        if f.kind == "partition":
            groups = [[self.nodes[j].node_id for j in g] for g in f.groups]
            self.network.set_partition(groups)
            # a real partition eventually severs the TCP links: peers see
            # each other go "down" and forget round state (router would
            # emit PeerUpdate down) — heal redelivers "up" + fresh NRS
            self._for_cross_group_pairs(f.groups, lambda a, b: (
                a.reactor.remove_peer(b.node_id) if a.reactor else None
            ))
            self.faults_applied.append(f"t={t:.2f} partition {f.groups}")
            if f.duration is not None:
                self.clock.call_later(f.duration, self._heal)
        elif f.kind == "heal":
            self._heal()
        elif f.kind == "crash":
            node = self.nodes[f.node]
            node.crash()
            self.faults_applied.append(f"t={t:.2f} crash node {f.node}")
            will_restart = f.restart_after is not None or any(
                g.kind == "restart" and g.node == f.node and not self._fault_fired[j]
                for j, g in enumerate(self.faults)
            )
            if will_restart:
                self._pending_restarts.add(f.node)
            if f.restart_after is not None:
                self.clock.call_later(
                    f.restart_after, lambda n=node: self._restart(n)
                )
        elif f.kind == "restart":
            self._restart(self.nodes[f.node])
        elif f.kind == "clock_skew":
            self.nodes[f.node].node_clock.skew = f.skew
            self.faults_applied.append(
                f"t={t:.2f} clock_skew node {f.node} {f.skew:+.3f}s"
            )
        elif f.kind == "double_sign":
            node = self.nodes[f.node]
            node.byzantine = True  # restarts rebuild with the override
            if node.cs is not None and node.cs.do_prevote_override is None:
                node.cs.do_prevote_override = make_double_sign_prevote(
                    node.sk, self.chain_id
                )
            self.faults_applied.append(f"t={t:.2f} double_sign node {f.node}")
        elif f.kind in ("val_join", "val_leave", "val_power"):
            power = 0 if f.kind == "val_leave" else int(f.power)
            self._inject_validator_update(i, f.node, power)
            self.faults_applied.append(
                f"t={t:.2f} {f.kind} node {f.node} power {power}"
            )

    def _inject_validator_update(self, fault_idx: int, node_idx: int, power: int) -> None:
        """Route a validator-set change through the REAL update path: a
        "val:<b64 pub>!<power>!<nonce>" tx is fed to every live node's
        mempool; whichever proposer wins next reaps it, the kvstore app
        echoes it from EndBlock, and state.execution.update_state rotates
        next_validators via ValidatorSet._update_with_change_set — which
        structurally invalidates the set's hash()/ed25519 columns, keying
        a fresh epoch for the device cache. The nonce keeps a rejoin at a
        previous power distinct for the mempools' seen-tx caches."""
        from ..abci.kvstore import make_validator_tx

        target = self.nodes[node_idx]
        tx = make_validator_tx(
            target.sk.pub_key().bytes(), power, nonce=fault_idx
        )
        injected = 0
        for n in self.nodes:
            if n.crashed or n.mp is None:
                continue
            try:
                n.mp.check_tx(tx)
                injected += 1
            except Exception:  # noqa: BLE001 — dup/full pools must not kill a run
                pass
        # only a rotation that can actually land AND changes the set is
        # held to the churn invariant (check_invariants)
        if injected and self._rotation_changes_set(target, power):
            self._rotations_fired.append(
                (self._max_committed(), self.faults[fault_idx].kind, node_idx)
            )

    def _rotation_changes_set(self, target: "SimNode", power: int) -> bool:
        """Would (target, power) actually alter the CURRENT next-validator
        set? A no-op update (joining at the power it already has) never
        obliges a hash change. Read from the most-advanced live node —
        a lagging node's stale next_validators could misclassify an
        already-applied update as set-changing."""
        pub = target.sk.pub_key().bytes()
        best = None
        for n in self.nodes:
            if n.crashed or n.cs is None:
                continue
            if best is None or n.height() > best.height():
                best = n
        if best is None:
            return False
        vals = best.cs._state.next_validators
        for v in vals.validators:
            if v.pub_key.bytes() == pub:
                return v.voting_power != power
        return power > 0  # not in the set: joins iff power > 0

    def _max_committed(self) -> int:
        return max(self._canonical) if self._canonical else 0

    def _for_cross_group_pairs(self, groups, fn) -> None:
        group_of = {}
        for gi, g in enumerate(groups):
            for j in g:
                group_of[j] = gi
        for a in self.nodes:
            for b in self.nodes:
                if a is b:
                    continue
                if group_of.get(a.idx) != group_of.get(b.idx):
                    fn(a, b)

    def commit_quorum_violation(
        self, commit, node_idx: int = -1, node: Optional[SimNode] = None
    ) -> Optional[str]:
        """None if `commit` carries > 2/3 of the voting power of the set
        that SIGNED it, else the violation record (also the
        _node_committed live check). Under validator-set churn the
        per-height set comes from the node's state store (the same
        checkpoints verify_commit uses); genesis powers are the fallback
        for callers without a node (static-set shortcut)."""
        powers = None
        if node is not None and node.sstore is not None:
            try:
                vals = node.sstore.load_validators(commit.height)
                powers = [v.voting_power for v in vals.validators]
            except KeyError:  # pre-checkpoint heights only — any other
                powers = None  # store fault must surface, not silently
                # fall back to (possibly wrong) genesis powers
        if powers is None:
            powers = [v.power for v in self.genesis_doc.validators]
        total = sum(powers)
        power = sum(
            powers[i]
            for i, cs_ in enumerate(commit.signatures)
            if i < len(powers) and cs_.for_block()
        )
        if 3 * power <= 2 * total:
            return (
                f"quorum: node {node_idx} stored commit at h{commit.height} "
                f"with {power}/{total} voting power"
            )
        return None

    def _heal(self) -> None:
        self.network.heal_partition()
        # "reconnect": every live pair re-exchanges peer-up + NewRoundStep,
        # exactly what the router's dial/accept path would do
        for a in self.nodes:
            for b in self.nodes:
                if a is b or a.crashed or b.crashed or a.reactor is None:
                    continue
                a.reactor.add_peer(b.node_id)
        self.faults_applied.append(f"t={self.clock.time():.2f} heal")

    def _restart(self, node: SimNode) -> None:
        node.restart()
        self._pending_restarts.discard(node.idx)
        self.faults_applied.append(
            f"t={self.clock.time():.2f} restart node {node.idx}"
        )

    # -- observation -----------------------------------------------------

    def heights(self) -> List[int]:
        return [n.height() for n in self.nodes]

    def min_live_height(self) -> int:
        live = [n.height() for n in self.nodes if not n.crashed]
        return min(live) if live else 0

    def export_merged_trace(self, include_process: bool = False) -> dict:
        """ONE Chrome-trace document for the whole cluster (ISSUE 10):
        every node's virtual-clock tracer (pid per node, process_name
        metadata), flow ids preserved so a vote's gossip-send → deliver →
        verify-dispatch chain is clickable in Perfetto across node
        boundaries. All node tracers read the SAME virtual clock, so the
        merged timeline is coherent; the process-wide WALL-clock tracer
        (driver/pipeline spans) uses an incomparable timebase and is only
        appended — as a clearly-labeled separate process — on explicit
        `include_process=True`."""
        docs = []
        labels = []
        if include_process:
            docs.append(_trace.TRACER.export_chrome())
            labels.append("driver (wall-clock)")
        for n in self.nodes:
            docs.append(n.tracer.export_chrome())
            labels.append(n.node_id)
        return _trace.merge_traces(docs, labels)

    def _timeline_ring(self, node: "SimNode", last: Optional[int] = None
                       ) -> List[dict]:
        if node.cs is None:
            return []
        ring = [tl.to_dict() for tl in node.cs.height_timelines]
        return ring[-last:] if last else ring

    def height_timelines(self) -> List[dict]:
        """The last-K HeightTimeline dicts of the most-advanced live node
        — the SimReport ring. Virtual-clock timestamps: deterministic
        under replay."""
        best = None
        for n in self.nodes:
            if n.cs is None:
                continue
            if best is None or n.height() > best.height():
                best = n
        return self._timeline_ring(best) if best is not None else []

    def flight_recorder_dump(self, trace_tail: int = 512,
                             timelines_per_node: int = 8) -> dict:
        """The automatic invariant-failure attachment: every live node's
        recent height timelines plus the merged trace's tail — enough to
        answer "what was each node doing when it broke" without re-running
        the schedule."""
        timelines = {
            n.node_id: self._timeline_ring(n, timelines_per_node)
            for n in self.nodes
            if n.cs is not None
        }
        doc = self.export_merged_trace()
        evs = doc.get("traceEvents", [])
        meta = [e for e in evs if e.get("ph") == "M"]
        rest = [e for e in evs if e.get("ph") != "M"]
        return {
            "height_timelines": timelines,
            "tracing": any(n.tracer.enabled for n in self.nodes),
            "trace_events_total": len(rest),
            "trace_tail": {
                "traceEvents": meta + rest[-trace_tail:],
                "displayTimeUnit": "ms",
            },
        }

    def fingerprint(self) -> str:
        """seed → ordered digest of the committed canonical chain. Two
        same-seed runs must match byte-for-byte (replay exactness)."""
        h = hashlib.sha256()
        h.update(b"seed=%d;" % self.seed)
        for height in sorted(self._canonical):
            h.update(b"%d:" % height)
            h.update(self._canonical[height])
            h.update(b";")
        return h.hexdigest()

    def _valset_hash_walk(self) -> tuple:
        """One pass over the longest live node's committed headers:
        (change_heights, distinct_hash_count). A rotation cycling BACK to
        an earlier membership re-uses its content-derived hash, so the
        distinct count can be smaller than changes+1 — the epoch-cache
        invariant must compare against distinct sets, not change events.
        The FINAL height's valset is excluded from the distinct count:
        height h's commit is only batch-verified when block h+1 carries
        it, so a rotation landing exactly at the last committed height
        can never have cold-registered within the run."""
        best = None
        for n in self.nodes:
            if n.bstore is not None and (best is None or n.height() > best.height()):
                best = n
        if best is None:
            return [], 0
        changes: List[int] = []
        seen: set = set()
        prev = None
        top = best.height()
        for h in range(max(best.bstore.base(), 1), top + 1):
            # meta is enough: the header carries validators_hash and a
            # full load_block would reassemble every part + tx per height
            meta = best.bstore.load_block_meta(h)
            if meta is None:
                continue
            vh = bytes(meta.header.validators_hash)
            if h < top:
                seen.add(vh)
            if prev is not None and vh != prev:
                changes.append(h)
            prev = vh
        return changes, len(seen)

    def valset_change_heights(self) -> List[int]:
        """Heights whose committed header carries a validators_hash
        different from the previous height's — the chain-visible trace of
        every rotation."""
        return self._valset_hash_walk()[0]

    def epoch_cache_delta(self) -> dict:
        """Cache movement attributable to this run (counter deltas since
        Cluster construction) + the live cache state."""
        now = self._epoch_stats()
        d = {
            k: now[k] - self._epoch_stats0.get(k, 0)
            for k in ("hits", "misses", "evictions", "tables_shared",
                      "tables_built")
        }
        d["enabled"] = now["enabled"]
        d["depth"] = now["depth"]
        d["entries"] = now["entries"]
        return d

    def check_invariants(self, _walk=None) -> List[str]:
        """Final sweep: every node's whole chain must be a prefix of the
        canonical chain (convergence after crash/partition recovery);
        under churn, every effective rotation must surface as a
        validators_hash change, and — when the device epoch cache is on —
        the cache counters must actually move through the cold/warm/evict
        cycle the rotations imply. `_walk` is an optional precomputed
        `_valset_hash_walk()` result so run_to_height scans the chain
        once for both the invariants and the report."""
        out = list(self.violations)
        for n in self.nodes:
            if n.bstore is None:
                continue
            for height in range(max(n.bstore.base(), 1), n.height() + 1):
                blk = n.bstore.load_block(height)
                if blk is None:
                    continue
                bh = bytes(blk.hash())
                want = self._canonical.get(height)
                if want is not None and want != bh:
                    out.append(
                        f"convergence: node {n.idx} has {bh.hex()[:16]} at "
                        f"h{height}, canonical {want.hex()[:16]}"
                    )
        # churn: a set-changing rotation injected at height h lands in a
        # block within a couple of heights and takes effect two later
        # (update_state next_validators plumbing) — if the chain ran on
        # long enough, the validators_hash MUST have moved in (h, h+6]
        if self._rotations_fired:
            changes, distinct = (
                _walk if _walk is not None else self._valset_hash_walk()
            )
        else:
            changes, distinct = [], 0
        max_h = self._max_committed()
        for inj_h, kind, node_idx in self._rotations_fired:
            if max_h < inj_h + 6:
                continue  # run ended before the rotation could land
            if not any(inj_h < ch <= inj_h + 6 for ch in changes):
                out.append(
                    f"rotation: {kind} node {node_idx} injected at h{inj_h} "
                    f"never changed validators_hash by h{inj_h + 6} "
                    f"(changes at {changes})"
                )
        if self._rotations_fired and changes:
            ec = self.epoch_cache_delta()
            # counters only move through the batch-verify path (note_valset);
            # commits below BATCH_VERIFY_THRESHOLD sigs (tiny valsets) ride
            # the single-sig path, so "enabled but untouched" proves nothing
            if ec["enabled"] and ec["misses"] + ec["hits"] > 0:
                # every DISTINCT valset must have cold-registered once
                # (a rotation cycling back to an earlier membership
                # re-uses its content hash — counted once); the LRU must
                # have evicted what its depth cannot hold
                if ec["misses"] < distinct:
                    out.append(
                        f"epoch-cache: {distinct} distinct valsets committed "
                        f"but only {ec['misses']} cold registrations"
                    )
                if ec["hits"] == 0:
                    out.append(
                        "epoch-cache: warm re-verifications recorded no hits"
                    )
                # sets that differ by a key map onto one table, so it
                # is the TABLES built that the depth evicts, and a
                # rotation of one key must not have built one each
                expect_evict = ec["tables_built"] - ec["depth"]
                if expect_evict > 0 and ec["evictions"] < expect_evict:
                    out.append(
                        f"epoch-cache: {ec['tables_built']} tables through "
                        f"depth {ec['depth']} implies >= {expect_evict} "
                        f"evictions, saw {ec['evictions']}"
                    )
                if ec["tables_built"] + ec["tables_shared"] < distinct:
                    out.append(
                        f"epoch-cache: {distinct} distinct valsets but "
                        f"{ec['tables_built']} tables built and "
                        f"{ec['tables_shared']} sets mapped"
                    )
        return out

    # -- the driver ------------------------------------------------------

    def run_to_height(
        self, target: int, max_virtual_s: float = 600.0,
        max_wall_s: Optional[float] = None,
    ) -> SimReport:
        """Run the event loop until every live node commits `target` (and
        every crash-faulted node has restarted), then report.
        `max_wall_s` bounds REAL time — the guard rail for 100+-node
        clusters and search sweeps."""
        wall0 = _wall.monotonic()
        t0 = self.clock.time()
        self.start()

        def done() -> bool:
            any_live = False
            for n in self.nodes:
                if n.crashed:
                    if n.idx in self._pending_restarts:
                        return False  # a promised restart hasn't run yet
                    continue  # crash-stop: excluded from the target
                any_live = True
                if n.height() < target:
                    return False
            return any_live

        reached = self.clock.run_until(
            predicate=done, deadline=t0 + max_virtual_s,
            max_wall_s=max_wall_s,
        )
        walk = self._valset_hash_walk() if self._rotations_fired else ([], 0)
        violations = self.check_invariants(_walk=walk)
        # classification comes from the event loop's OWN exit reason — an
        # elapsed-time heuristic would misread a virtual-deadline exit
        # (a real, deterministic wedge) as a wall cutoff whenever the
        # post-run invariant walk pushed total elapsed past the budget
        wall_hit = self.clock.wall_budget_hit
        reason = "ok"
        if not reached:
            budget = f"{max_virtual_s}s virtual"
            if wall_hit:
                budget = f"{max_wall_s}s wall"
            reason = (
                f"height {target} not reached within {budget}"
                f" (heights={self.heights()})"
            )
        elif violations:
            reason = f"{len(violations)} invariant violation(s)"
        return SimReport(
            ok=reached and not violations,
            reason=reason,
            height=self.min_live_height(),
            heights=self.heights(),
            fingerprint=self.fingerprint(),
            schedule_digest=self.network.schedule_digest(),
            violations=violations,
            seed=self.seed,
            virtual_s=self.clock.time() - t0,
            wall_s=_wall.monotonic() - wall0,
            events_run=self.clock.events_run,
            net=self.network.stats(),
            faults_applied=list(self.faults_applied),
            n_validators=self.n_validators,
            valset_changes=walk[0],
            epoch_cache=self.epoch_cache_delta(),
            wall_budget_hit=wall_hit,
            height_timelines=self.height_timelines(),
            catchup=(
                [d.summary() for d in self.catchup_drivers]
                if self.catchup_drivers else None
            ),
            # the flight recorder rides ONLY on invariant failures — a
            # green run keeps the report lean
            flight_recorder=(
                self.flight_recorder_dump() if violations else None
            ),
        )
