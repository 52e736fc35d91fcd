"""State sync — bootstrap a fresh node from an application snapshot.

Reference parity: internal/statesync/ — the discovery/offer/chunk protocol
(syncer.go:178 SyncAny, offerSnapshot:384, applyChunks:420, verifyApp:567),
chunk queue (chunks.go), and the light-client-backed StateProvider
(stateprovider.go:33) that supplies trusted AppHash/Commit/State; the
p2p dispatcher (dispatcher.go) serves light blocks over a dedicated
channel.

Channels (reactor.go): snapshot 0x60, chunk 0x61, light-block 0x62.
Wire oneofs:
  snapshot ch: 1 snapshots_request{} | 2 snapshots_response{1 height,
               2 format, 3 chunks, 4 hash, 5 metadata}
  chunk ch:    1 chunk_request{1 height, 2 format, 3 index}
               | 2 chunk_response{1 height, 2 format, 3 index, 4 chunk, 5 missing}
  light ch:    1 light_block_request{1 height} | 2 light_block_response{1 lb}
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..abci import types as abci
from ..light import verifier as light_verifier
from ..light.provider import LightBlock
from ..p2p.conn.mconnection import ChannelDescriptor
from ..p2p.router import Router
from ..state import State
from ..types import Commit, Header, SignedHeader, ValidatorSet
from ..types.params import ConsensusParams
from ..types.block import BlockID
from ..types.validation import verify_commit_light
from ..version import BLOCK_PROTOCOL
from ..wire.canonical import Timestamp
from ..wire.proto import ProtoWriter, decode_message, field_bytes, field_int, to_signed64

SNAPSHOT_CHANNEL = 0x60
CHUNK_CHANNEL = 0x61
LIGHT_BLOCK_CHANNEL = 0x62
PARAMS_CHANNEL = 0x63  # reactor.go ParamsChannel

# stateprovider.go:21-27: the light client behind the state provider uses
# the node's trusting period; this default mirrors config's 14-day window.
DEFAULT_TRUSTING_PERIOD = 14 * 24 * 3600.0
MAX_CLOCK_DRIFT = 10.0


def _now_ts() -> Timestamp:
    t = time.time()
    return Timestamp(seconds=int(t), nanos=int((t % 1.0) * 1e9))

SNAPSHOT_DESC = ChannelDescriptor(id=SNAPSHOT_CHANNEL, priority=5)
CHUNK_DESC = ChannelDescriptor(
    id=CHUNK_CHANNEL, priority=3, recv_message_capacity=16 * 1024 * 1024
)
LIGHT_BLOCK_DESC = ChannelDescriptor(
    id=LIGHT_BLOCK_CHANNEL, priority=5, recv_message_capacity=8 * 1024 * 1024
)
PARAMS_DESC = ChannelDescriptor(id=PARAMS_CHANNEL, priority=2)

ALL_STATESYNC_DESCS = [SNAPSHOT_DESC, CHUNK_DESC, LIGHT_BLOCK_DESC, PARAMS_DESC]


class SyncError(RuntimeError):
    pass


class RetrySnapshot(Exception):
    """syncer.go errRetrySnapshot: the app asked to restart restoration
    of the SAME snapshot (transient failure) — not a snapshot rejection."""


def _enc(kind: int, fields: Optional[dict] = None) -> bytes:
    inner = ProtoWriter()
    for num, val in sorted((fields or {}).items()):
        if isinstance(val, bytes):
            inner.write_bytes(num, val)
        else:
            inner.write_varint(num, val)
    w = ProtoWriter()
    w.write_message(kind, inner.bytes(), always=True)
    return w.bytes()


@dataclass
class _SnapshotInfo:
    height: int
    format: int
    chunks: int
    hash: bytes
    metadata: bytes
    peers: List[str] = field(default_factory=list)

    def key(self) -> tuple:
        return (self.height, self.format, self.hash)


class StateSyncReactor:
    """internal/statesync/reactor.go + syncer.go (server + client roles)."""

    def __init__(
        self,
        router: Router,
        query_conn,  # ABCI query/snapshot connection
        state_store,
        block_store,
        chain_id: str,
        serving: bool = True,
    ):
        self._router = router
        self._conn = query_conn
        self._state_store = state_store
        self._block_store = block_store
        self._chain_id = chain_id
        self._serving = serving
        self._snap_ch = router.open_channel(SNAPSHOT_DESC)
        self._chunk_ch = router.open_channel(CHUNK_DESC)
        self._lb_ch = router.open_channel(LIGHT_BLOCK_DESC)
        self._params_ch = router.open_channel(PARAMS_DESC)
        self._stopped = threading.Event()
        self._snapshots: Dict[tuple, _SnapshotInfo] = {}
        # (height, format, index) -> (chunk bytes, sender peer id)
        self._chunks: Dict[Tuple[int, int, int], Tuple[bytes, str]] = {}
        self._banned_senders: set = set()
        self._light_blocks: Dict[int, LightBlock] = {}
        self._params: Dict[int, ConsensusParams] = {}
        self._mtx = threading.Lock()

    def start(self) -> None:
        for ch, handler in (
            (self._snap_ch, self._handle_snapshot_msg),
            (self._chunk_ch, self._handle_chunk_msg),
            (self._lb_ch, self._handle_light_block_msg),
            (self._params_ch, self._handle_params_msg),
        ):
            t = threading.Thread(target=self._process, args=(ch, handler), daemon=True)
            t.start()

    def stop(self) -> None:
        self._stopped.set()

    def _process(self, ch, handler) -> None:
        while not self._stopped.is_set():
            try:
                env = ch.receive(timeout=0.5)
            except queue.Empty:
                continue
            try:
                handler(env)
            except (ValueError, KeyError):
                continue

    # -- server side ------------------------------------------------------

    RECENT_SNAPSHOTS = 10  # reactor.go recentSnapshots

    def _handle_snapshot_msg(self, env) -> None:
        f = decode_message(env.message)
        if 1 in f and self._serving:  # snapshots_request
            res = self._conn.list_snapshots()
            # NEWEST first, capped (reactor.go recentSnapshots): apps with
            # bounded retention prune old snapshots, so advertising
            # oldest-first steers the syncer toward soon-to-vanish ones
            advertised = sorted(
                res.snapshots, key=lambda s: (-s.height, s.format)
            )[: self.RECENT_SNAPSHOTS]
            for s in advertised:
                self._snap_ch.send(
                    env.from_id,
                    _enc(2, {1: s.height, 2: s.format, 3: s.chunks, 4: s.hash, 5: s.metadata}),
                )
        elif 2 in f:  # snapshots_response
            r = decode_message(field_bytes(f, 2))
            info = _SnapshotInfo(
                height=field_int(r, 1),
                format=field_int(r, 2),
                chunks=field_int(r, 3),
                hash=field_bytes(r, 4),
                metadata=field_bytes(r, 5),
            )
            with self._mtx:
                existing = self._snapshots.setdefault(info.key(), info)
                if env.from_id not in existing.peers:
                    existing.peers.append(env.from_id)

    def _handle_chunk_msg(self, env) -> None:
        f = decode_message(env.message)
        if 1 in f and self._serving:  # chunk_request
            r = decode_message(field_bytes(f, 1))
            height, fmt = field_int(r, 1), field_int(r, 2)
            res = self._conn.load_snapshot_chunk(
                abci.RequestLoadSnapshotChunk(
                    height=height, format=fmt, chunk=field_int(r, 3)
                )
            )
            # missing means "I no longer have this snapshot" (reactor.go:
            # resp.Chunk == nil), NOT "the chunk is zero-length" — a
            # legitimately empty chunk from a still-advertised snapshot
            # must be served as data or the slot can never be filled
            missing = 1 if not res.chunk else 0
            if missing:
                try:
                    have = self._conn.list_snapshots().snapshots
                    if any(s.height == height and s.format == fmt for s in have):
                        missing = 0
                except Exception:  # noqa: BLE001 — keep the missing verdict
                    pass
            self._chunk_ch.send(
                env.from_id,
                _enc(2, {
                    1: height, 2: fmt, 3: field_int(r, 3),
                    4: res.chunk, 5: missing,
                }),
            )
        elif 2 in f:  # chunk_response
            r = decode_message(field_bytes(f, 2))
            key = (field_int(r, 1), field_int(r, 2), field_int(r, 3))
            with self._mtx:
                # keep the sender: the app can blame it (reject_senders).
                # Banned senders are ignored, and a cached chunk is never
                # overwritten (chunks.go Add: first writer wins) — a
                # malicious re-send must not clobber an honest peer's data
                if env.from_id in self._banned_senders or key in self._chunks:
                    return
                if field_int(r, 5):
                    return  # missing=1: the peer pruned this snapshot
                self._chunks[key] = (field_bytes(r, 4), env.from_id)

    def _handle_light_block_msg(self, env) -> None:
        f = decode_message(env.message)
        if 1 in f and self._serving:  # light_block_request
            r = decode_message(field_bytes(f, 1))
            height = to_signed64(field_int(r, 1))
            lb = self._load_local_light_block(height)
            if lb is not None:
                self._lb_ch.send(env.from_id, _enc(2, {1: lb.encode()}))
        elif 2 in f:  # light_block_response
            r = decode_message(field_bytes(f, 2))
            lb = LightBlock.decode(field_bytes(r, 1))
            with self._mtx:
                self._light_blocks[lb.height] = lb

    def _load_local_light_block(self, height: int) -> Optional[LightBlock]:
        meta = self._block_store.load_block_meta(height)
        commit = self._block_store.load_block_commit(height)
        if meta is None or commit is None:
            return None
        try:
            vals = self._state_store.load_validators(height)
        except KeyError:
            return None
        return LightBlock(
            signed_header=SignedHeader(header=meta.header, commit=commit),
            validators=vals,
        )

    # -- client side: the sync (syncer.go:178 SyncAny) ---------------------

    def _handle_params_msg(self, env) -> None:
        """reactor.go:?? params channel: 1 request{1 height} ->
        2 response{1 height, 2 params}; served from the state store."""
        f = decode_message(env.message)
        if 1 in f and self._serving and self._state_store is not None:
            req = decode_message(field_bytes(f, 1))
            height = to_signed64(field_int(req, 1))
            try:
                params = self._state_store.load_consensus_params(height)
            except KeyError:
                return
            self._params_ch.send(
                env.from_id, _enc(2, {1: height, 2: params.encode()})
            )
        elif 2 in f:
            res = decode_message(field_bytes(f, 2))
            height = to_signed64(field_int(res, 1))
            with self._mtx:
                self._params[height] = ConsensusParams.decode(field_bytes(res, 2))

    def _fetch_params(self, height: int, timeout: float = 10.0) -> Optional[ConsensusParams]:
        """syncer.go params fetch at the snapshot height (replacing the
        round-2 genesis-params shortcut)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._mtx:
                p = self._params.get(height)
            if p is not None:
                return p
            self._params_ch.broadcast(_enc(1, {1: height}))
            time.sleep(0.2)
        return None

    def backfill(self, state: State) -> int:
        """reactor.go:504 backfill: after a snapshot restore, walk the
        chain BACKWARDS from the snapshot height over the evidence window
        (max_age_num_blocks / max_age_duration), hash-link-verifying each
        header, and persist headers+commits+validator sets so historical
        evidence can be verified. Returns the number of blocks stored."""
        ev = state.consensus_params.evidence
        stop_height = max(
            state.initial_height, state.last_block_height - ev.max_age_num_blocks
        )
        stop_time_ns = (
            state.last_block_time.seconds * 10**9
            + state.last_block_time.nanos
            - ev.max_age_duration_ns
        )
        current = self._load_local_light_block(state.last_block_height)
        if current is None:
            return 0
        stored = 0
        for h in range(state.last_block_height - 1, stop_height - 1, -1):
            t_ns = (
                current.signed_header.header.time.seconds * 10**9
                + current.signed_header.header.time.nanos
            )
            if t_ns < stop_time_ns:
                break  # time window exhausted (range() bounds the heights)
            try:
                lb = self._fetch_light_block(h)
            except SyncError:
                break
            # hash-linkage: the verified child must point at this header
            if current.signed_header.header.last_block_id.hash != lb.hash():
                raise SyncError(f"backfill: hash mismatch at height {h}")
            if lb.signed_header.header.validators_hash != lb.validators.hash():
                raise SyncError(f"backfill: validator hash mismatch at {h}")
            # the commit must actually commit THIS header with +2/3 of its
            # validator set (reactor.go backfill verifies light blocks; a
            # byzantine peer could otherwise attach garbage commits to the
            # genuine hash-linked header)
            try:
                lb.signed_header.validate_basic(self._chain_id)
                verify_commit_light(
                    self._chain_id,
                    lb.validators,
                    lb.signed_header.commit.block_id,
                    h,
                    lb.signed_header.commit,
                )
            except ValueError as e:
                raise SyncError(f"backfill: bad commit at height {h}: {e}") from e
            self._block_store.save_signed_header(
                lb.signed_header, current.signed_header.header.last_block_id
            )
            self._state_store.save_validators_at(h, lb.validators)
            stored += 1
            current = lb
        return stored

    def _fetch_light_block(self, height: int, timeout: float = 10.0) -> LightBlock:
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._mtx:
                lb = self._light_blocks.get(height)
            if lb is not None:
                return lb
            self._lb_ch.broadcast(_enc(1, {1: height}))
            time.sleep(0.2)
        raise SyncError(f"no light block at height {height}")

    def sync_any(
        self,
        genesis_state: State,
        trust_height: int,
        trust_hash: bytes,
        discovery_time: float = 5.0,
        chunk_timeout: float = 15.0,
    ) -> Tuple[State, Commit]:
        """Discover a snapshot, restore it, verify the app, and build the
        post-sync State with light-client-verified trust."""
        # 1. verify the root of trust (light/client.go
        # initializeWithTrustOptions: hash match, vals bound to the header,
        # commit verified by those vals over this exact header).
        root = self._fetch_light_block(trust_height)
        if root.hash() != trust_hash:
            raise SyncError(
                f"trust hash mismatch at height {trust_height}: "
                f"got {root.hash().hex()}, want {trust_hash.hex()}"
            )
        root.signed_header.validate_basic(self._chain_id)
        if root.validators.hash() != root.signed_header.header.validators_hash:
            raise SyncError("trusted root validators do not match header")
        verify_commit_light(
            self._chain_id, root.validators, root.signed_header.commit.block_id,
            trust_height, root.signed_header.commit,
        )
        trusted: Dict[int, LightBlock] = {trust_height: root}

        # 2. discover snapshots
        # Multiple discovery rounds with a FRESH snapshot list each time
        # (syncer.go re-discovers as peers advertise): serving apps retain
        # only their newest snapshots, so a fast-moving chain can prune a
        # snapshot between our discovery and the chunk fetch — stale
        # candidates must not doom the whole sync.
        discovered_any = False
        failed: set = set()  # (height, format, hash) keys that already failed
        for _round in range(3):
            with self._mtx:
                self._snapshots.clear()
            # wait the FULL discovery window (syncer.go waits
            # discoveryTime): grabbing the first response would bias
            # toward whatever snapshot message lands first, not the best
            deadline = time.time() + discovery_time
            while time.time() < deadline:
                self._snap_ch.broadcast(_enc(1))
                time.sleep(min(0.2, max(deadline - time.time(), 0.01)))
            with self._mtx:
                candidates = sorted(
                    self._snapshots.values(), key=lambda s: (-s.height, s.format)
                )
            discovered_any = discovered_any or bool(candidates)
            fresh = [c for c in candidates if c.key() not in failed]
            if not fresh:
                break  # only known-bad snapshots left: re-trying won't help
            for snap in fresh:
                for _attempt in range(3):
                    try:
                        return self._sync_one(
                            genesis_state, snap, chunk_timeout, trusted
                        )
                    except RetrySnapshot:
                        # syncer.go errRetrySnapshot: restart restoration
                        # of this same snapshot (not a rejection)
                        continue
                    except SyncError:
                        failed.add(snap.key())
                        break
                    finally:
                        # chunkQueue teardown: drop this snapshot's cached
                        # chunks whether the attempt succeeded or not
                        with self._mtx:
                            for k in [
                                k
                                for k in self._chunks
                                if k[0] == snap.height and k[1] == snap.format
                            ]:
                                del self._chunks[k]
                else:
                    failed.add(snap.key())
        if not discovered_any:
            raise SyncError("no snapshots discovered")
        raise SyncError("all discovered snapshots failed")

    def _verified_light_block(
        self,
        height: int,
        trusted: Dict[int, LightBlock],
        trusting_period: float = DEFAULT_TRUSTING_PERIOD,
    ) -> LightBlock:
        """Fetch a light block and verify it through the light-client chain
        of trust rooted at the operator-provided trust hash — NOT against
        its own peer-supplied validator set (stateprovider.go:33: every
        header the state provider returns flows through light.Client
        verification; skipping verification with bisection is
        light/client.go:639 verifySkipping)."""
        if height in trusted:
            return trusted[height]
        lower = [h for h in trusted if h < height]
        if not lower:
            raise SyncError(
                f"height {height} is below the trusted root "
                f"{min(trusted)} — cannot establish trust"
            )
        cur = trusted[max(lower)]
        now = _now_ts()
        pending = [height]
        fetched: Dict[int, LightBlock] = {}  # unverified fetch cache: each
        # bisection retry would otherwise re-fetch the same block (10s
        # network round-trip each)
        while pending:
            h = pending[-1]
            if h in trusted:
                cur = trusted[h]
                pending.pop()
                continue
            lb = fetched.get(h)
            if lb is None:
                lb = fetched[h] = self._fetch_light_block(h)
            try:
                light_verifier.verify(
                    cur.signed_header, cur.validators,
                    lb.signed_header, lb.validators,
                    trusting_period, now, MAX_CLOCK_DRIFT,
                    light_verifier.DEFAULT_TRUST_LEVEL,
                )
            except light_verifier.ErrNotEnoughTrust:
                # bisect: pivot 9/16 of the way up (client.go:44-45)
                pivot = cur.height + (h - cur.height) * 9 // 16
                if pivot <= cur.height or pivot >= h:
                    raise SyncError(f"cannot bisect between {cur.height} and {h}")
                pending.append(pivot)
                continue
            except ValueError as e:
                raise SyncError(
                    f"light block at height {h} failed verification: {e}"
                ) from e
            trusted[h] = lb
            cur = lb
            pending.pop()
        return trusted[height]

    def _sync_one(
        self,
        genesis_state: State,
        snap: _SnapshotInfo,
        chunk_timeout: float,
        trusted: Dict[int, LightBlock],
    ):
        # Both headers verified through the chain of trust from the root —
        # the trusted app hash comes from the header at snapshot height + 1.
        snap_block = self._verified_light_block(snap.height, trusted)
        header_next = self._verified_light_block(snap.height + 1, trusted)
        trusted_app_hash = header_next.signed_header.header.app_hash
        if header_next.signed_header.header.last_block_id.hash != snap_block.hash():
            raise SyncError("light block chain linkage broken")

        # 3. offer to the app (syncer.go:384)
        res = self._conn.offer_snapshot(
            abci.RequestOfferSnapshot(
                snapshot=abci.Snapshot(
                    height=snap.height, format=snap.format, chunks=snap.chunks,
                    hash=snap.hash, metadata=snap.metadata,
                ),
                app_hash=trusted_app_hash,
            )
        )
        if res.result != abci.OFFER_SNAPSHOT_ACCEPT:
            raise SyncError(f"snapshot rejected by app: {res.result}")

        # 4. fetch + apply chunks (chunks.go + syncer.go:420-470). The app
        # steers recovery: RETRY re-applies the same chunk (refetched),
        # refetch_chunks re-fetches earlier chunks it discarded,
        # reject_senders bans their sources, RETRY_SNAPSHOT/REJECT abort
        # this candidate (sync_any moves to the next snapshot).
        pending = set(range(snap.chunks))  # chunkQueue: lowest unreturned next
        retries = 0
        max_retries = 4 * max(snap.chunks, 1)
        while pending:
            index = min(pending)
            pending.discard(index)
            chunk, sender = self._fetch_chunk(snap, index, chunk_timeout)
            ares = self._conn.apply_snapshot_chunk(
                abci.RequestApplySnapshotChunk(
                    index=index, chunk=chunk, sender=sender
                )
            )
            # chunks.Discard: drop the cached bytes so they are refetched
            for r_idx in ares.refetch_chunks:
                with self._mtx:
                    self._chunks.pop((snap.height, snap.format, r_idx), None)
                pending.add(r_idx)
                retries += 1
                if retries > max_retries:
                    raise SyncError("refetch limit exceeded")
            # snapshots.RejectPeer + chunks.DiscardSender: ban the sender
            # and drop any cached chunks it supplied
            if ares.reject_senders:
                rejected = set(ares.reject_senders)
                with self._mtx:
                    self._banned_senders.update(rejected)
                    for key in [
                        k
                        for k, (_, snd) in self._chunks.items()
                        if snd in rejected
                    ]:
                        del self._chunks[key]
            if ares.result == abci.APPLY_SNAPSHOT_CHUNK_ACCEPT:
                # chunkQueue discards a chunk once applied — a multi-GB
                # snapshot must not pin every chunk in RAM
                with self._mtx:
                    self._chunks.pop((snap.height, snap.format, index), None)
            elif ares.result == abci.APPLY_SNAPSHOT_CHUNK_RETRY:
                # chunks.Retry: re-apply the SAME cached bytes (no refetch)
                retries += 1
                if retries > max_retries:
                    raise SyncError(f"chunk {index}: retry limit exceeded")
                pending.add(index)
            elif ares.result == abci.APPLY_SNAPSHOT_CHUNK_RETRY_SNAPSHOT:
                raise RetrySnapshot(f"app requested retry at chunk {index}")
            else:
                raise SyncError(f"chunk {index} rejected: {ares.result}")

        # 5. verify the app took the snapshot (syncer.go:565 verifyApp)
        info = self._conn.info(abci.RequestInfo())
        if info.last_block_app_hash != trusted_app_hash:
            raise SyncError(
                f"appHash verification failed: expected {trusted_app_hash.hex()}, "
                f"got {info.last_block_app_hash.hex()}"
            )
        if info.last_block_height != snap.height:
            raise SyncError("app reported unexpected last block height")

        # 6. build State (stateprovider.go State()) — validator sets come
        # from chain-of-trust-verified light blocks only.
        next_vals = header_next.validators
        try:
            nn_vals = self._verified_light_block(snap.height + 2, trusted).validators
        except SyncError:
            nn_vals = next_vals
        # consensus params at the snapshot height from the params channel
        # (reactor.go params fetch); genesis params only as a last resort
        params = self._fetch_params(snap.height, timeout=5.0)
        if params is not None:
            params_height = snap.height
        else:
            params = genesis_state.consensus_params
            params_height = genesis_state.initial_height
        state = State(
            version=genesis_state.version,
            chain_id=self._chain_id,
            initial_height=genesis_state.initial_height,
            last_block_height=snap.height,
            last_block_id=header_next.signed_header.header.last_block_id,
            last_block_time=snap_block.signed_header.header.time,
            validators=next_vals.copy(),
            next_validators=nn_vals.copy(),
            last_validators=snap_block.validators.copy(),
            last_height_validators_changed=snap.height + 1,
            consensus_params=params,
            last_height_consensus_params_changed=params_height,
            last_results_hash=header_next.signed_header.header.last_results_hash,
            app_hash=trusted_app_hash,
        )
        # bootstrap the stores (node.go statesync completion)
        self._state_store.bootstrap(state)
        self._block_store.save_signed_header(
            snap_block.signed_header,
            header_next.signed_header.header.last_block_id,
        )
        return state, snap_block.signed_header.commit

    def _fetch_chunk(
        self, snap: _SnapshotInfo, index: int, timeout: float
    ) -> tuple:
        """-> (chunk_bytes, sender_id). Senders the app rejected
        (banned_senders) are never asked again (syncer.go applyChunks
        RejectSenders)."""
        key = (snap.height, snap.format, index)
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._mtx:
                entry = self._chunks.get(key)
                banned = set(self._banned_senders)
                if entry is not None and entry[1] in banned:
                    # poisoned source; drop under the SAME lock so a
                    # fresh chunk landing in between is never discarded
                    del self._chunks[key]
                    entry = None
            if entry is not None:
                return entry
            peers = [p for p in (snap.peers or [""]) if p not in banned]
            if snap.peers and not peers:
                # every known source of this snapshot has been banned via
                # RejectSenders — replies from them are dropped on receipt
                # (_handle_chunk_msg), so waiting out the timeout can never
                # succeed; fail the restore attempt now (syncer.go
                # applyChunks errNoSnapshotSources spirit)
                raise SyncError(
                    f"no usable sources for chunk {index}: all "
                    f"{len(snap.peers)} snapshot peers are banned"
                )
            for peer in peers or [""]:
                msg = _enc(1, {1: snap.height, 2: snap.format, 3: index})
                if peer:
                    self._chunk_ch.send(peer, msg)
                else:
                    self._chunk_ch.broadcast(msg)
            time.sleep(0.2)
        raise SyncError(f"timed out fetching chunk {index}")
