"""Columnar signature-batch representation — the zero-copy commit prep.

Round 5 (a different attachment of the chip; not re-measured on this
machine) found end-to-end types.verify_commit at under a third of the RLC
kernel's rate because the host path between
verify_commit and the kernel was built from per-signature Python objects:
a (pub32, msg, sig64) tuple per lane, PyBytes sign-bytes, and b"".join
re-copies in every prep stage — all GIL-held, so under concurrent commits
the orchestration language (not the device) was the binding constraint.

An EntryBlock carries one commit's (or one coalesced device batch's)
signatures as contiguous columnar buffers built ONCE and handed by
reference:

    pub     (n, 32) uint8   public keys, row per signature
    sig     (n, 64) uint8   signatures (R || s)
    msgs    bytes/memoryview  all sign-bytes concatenated
    offsets (n+1,) int64    msgs[offsets[i]:offsets[i+1]] is message i

Downstream consumers (ops.backend prepare_batch*, ops.pallas_verify
prepare_compact, ops.pallas_rlc prepare_rlc, the async pipeline's
coalescer) slice these arrays directly: no per-signature Python objects
are created between commit selection and the kernel argument arrays, and
batch concatenation is np.concatenate instead of list-extend. The
tuple-list API everywhere remains a thin shim over `as_block`.
"""

from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple, Union

import numpy as np

Entry = Tuple[bytes, bytes, bytes]

_EMPTY_OFFSETS = np.zeros(1, dtype=np.int64)


class EntryBlock:
    """Columnar (pub, msg, sig) batch; see module docstring."""

    __slots__ = ("pub", "sig", "msgs", "offsets",
                 "val_idx", "epoch_key", "scheme", "pub_aux")

    def __init__(self, pub: np.ndarray, sig: np.ndarray,
                 msgs: Union[bytes, memoryview], offsets: np.ndarray,
                 val_idx: "np.ndarray" = None, epoch_key: bytes = None,
                 scheme: str = "ed25519", pub_aux: "np.ndarray" = None):
        n = pub.shape[0]
        if pub.shape != (n, 32) or sig.shape != (n, 64):
            raise ValueError("pub must be (n, 32) and sig (n, 64) uint8")
        if offsets.shape != (n + 1,):
            raise ValueError("offsets must be (n+1,)")
        # monotonicity is load-bearing: downstream native code derives
        # per-message lengths as offsets[i+1]-offsets[i] in GIL-released
        # C, where a negative difference wraps to a huge size_t
        if n and bool((np.diff(offsets) < 0).any()):
            raise ValueError("offsets must be non-decreasing")
        self.pub = pub
        self.sig = sig
        self.msgs = msgs
        self.offsets = offsets
        # Epoch-cache metadata (ops/epoch_cache.py): epoch_key names the
        # device TABLE of public-key rows the lanes gather from (the
        # hash of the set that built it and, for ed25519, the table's
        # own serial: a name never leads to other rows than it did;
        # later sets that share its keys map onto it), val_idx (n,)
        # int32 — each lane's row of THAT table. When set, warm-epoch preps ship val_idx instead of
        # pubkey-derived arrays and the kernels gather A on device;
        # blocks of different sets of one table fuse (concat).
        if val_idx is not None and val_idx.shape != (n,):
            raise ValueError("val_idx must be (n,)")
        self.val_idx = val_idx
        self.epoch_key = epoch_key
        # Scheme tag (ISSUE 19): every row of a block shares ONE signature
        # scheme — the mesh packer keys lanes on it and the kernel prep
        # branches on it. `pub_aux` carries the per-row byte a scheme's
        # wire key needs beyond the (n, 32) column: for secp256k1 the SEC1
        # compression prefix (pub = prefix || X, so pub holds X). ed25519
        # blocks keep pub_aux None.
        self.scheme = scheme
        if pub_aux is not None and pub_aux.shape != (n,):
            raise ValueError("pub_aux must be (n,)")
        self.pub_aux = pub_aux

    # -- construction -------------------------------------------------------

    @classmethod
    def empty(cls, scheme: str = "ed25519") -> "EntryBlock":
        return cls(
            np.zeros((0, 32), dtype=np.uint8),
            np.zeros((0, 64), dtype=np.uint8),
            b"",
            _EMPTY_OFFSETS,
            scheme=scheme,
            pub_aux=(
                np.zeros(0, dtype=np.uint8) if scheme != "ed25519" else None
            ),
        )

    @classmethod
    def from_entries(cls, entries: Sequence[Entry],
                     scheme: str = "ed25519") -> "EntryBlock":
        """Tuple-list shim: one validation pass + two joins, the same cost
        the old per-batch _pack_rows paid — conversion happens once at the
        API boundary instead of in every downstream stage. Non-ed25519
        schemes declare themselves: secp256k1 entries carry 33-byte SEC1
        keys, split here into the prefix column (pub_aux) + X (pub)."""
        n = len(entries)
        if n == 0:
            return cls.empty(scheme)
        klen = 33 if scheme == "secp256k1" else 32
        if any(len(pk) != klen or len(s) != 64 for pk, _, s in entries):
            raise ValueError(
                f"entries must be (pub{klen}, msg, sig64) triples"
            )
        raw = np.frombuffer(
            b"".join(pk for pk, _, _ in entries), dtype=np.uint8
        ).reshape(n, klen)
        pub_aux = None
        if klen == 33:
            pub_aux = np.ascontiguousarray(raw[:, 0])
            pub = np.ascontiguousarray(raw[:, 1:])
        else:
            pub = raw
        sig = np.frombuffer(
            b"".join(s for _, _, s in entries), dtype=np.uint8
        ).reshape(n, 64)
        lens = np.fromiter((len(m) for _, m, _ in entries), dtype=np.int64,
                           count=n)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lens, out=offsets[1:])
        msgs = b"".join(m for _, m, _ in entries)
        return cls(pub, sig, msgs, offsets, scheme=scheme, pub_aux=pub_aux)

    # -- shape --------------------------------------------------------------

    @property
    def n(self) -> int:
        return self.pub.shape[0]

    def __len__(self) -> int:
        return self.pub.shape[0]

    def msg_nbytes(self) -> int:
        return int(self.offsets[-1] - self.offsets[0])

    # -- access -------------------------------------------------------------

    def msg(self, i: int) -> bytes:
        o = self.offsets
        return bytes(memoryview(self.msgs)[int(o[i]) : int(o[i + 1])])

    def pub_bytes(self, i: int) -> bytes:
        """Row i's full wire-format key (prefix byte re-attached for
        schemes that split one into pub_aux)."""
        if self.pub_aux is not None:
            return bytes([int(self.pub_aux[i])]) + self.pub[i].tobytes()
        return self.pub[i].tobytes()

    def entry(self, i: int) -> Entry:
        """Materialize ONE (pub, msg, sig64) tuple — the blame path's
        per-lane re-verify, not a bulk conversion. The pub element is the
        scheme's wire key (32 bytes ed25519, 33 bytes secp256k1)."""
        return self.pub_bytes(i), self.msg(i), self.sig[i].tobytes()

    def iter_entries(self) -> Iterator[Entry]:  # tmlint: fallback — tuple-compat shim, blame/debug path only
        for i in range(self.n):
            yield self.entry(i)

    def to_entries(self) -> List[Entry]:
        return list(self.iter_entries())

    def msg_views(self) -> List[memoryview]:
        """Per-message zero-copy views (hashlib and the native sequence
        APIs both accept memoryview)."""
        mv = memoryview(self.msgs)
        o = self.offsets
        return [mv[int(o[i]) : int(o[i + 1])] for i in range(self.n)]

    def msgs_contiguous(self) -> Tuple[Union[bytes, memoryview], np.ndarray]:
        """(buffer, offsets) with the buffer trimmed to exactly the message
        window and offsets rebased to start at 0 — the form the native
        *_buf calls consume."""
        base = int(self.offsets[0])
        end = int(self.offsets[-1])
        buf = self.msgs
        if base != 0 or end != len(buf):
            buf = memoryview(buf)[base:end]
        if base == 0:
            return buf, self.offsets
        return buf, self.offsets - base

    def __getitem__(self, key: slice) -> "EntryBlock":
        """Zero-copy sub-block (numpy views + a rebased offset window) —
        how a coalesced job straddles two device batches without
        rebuilding per-signature objects."""
        if not isinstance(key, slice):
            raise TypeError("EntryBlock indexing takes a slice")
        start, stop, step = key.indices(self.n)
        if step != 1:
            raise ValueError("EntryBlock slices must be contiguous")
        o = self.offsets
        base = int(o[start])
        mv = memoryview(self.msgs)[base : int(o[stop])]
        return EntryBlock(
            self.pub[start:stop],
            self.sig[start:stop],
            mv,
            o[start : stop + 1] - base,
            val_idx=(
                self.val_idx[start:stop] if self.val_idx is not None else None
            ),
            epoch_key=self.epoch_key,
            scheme=self.scheme,
            pub_aux=(
                self.pub_aux[start:stop] if self.pub_aux is not None else None
            ),
        )

    # -- combination --------------------------------------------------------

    @staticmethod
    def concat(blocks: Sequence["EntryBlock"]) -> "EntryBlock":
        """One np.concatenate per column + one msgs join — the coalescing
        pipeline's replacement for per-signature list.extend. A single
        non-empty block passes through BY IDENTITY (no copies at all —
        the common one-commit dispatch)."""
        blocks = [b for b in blocks if len(b)]
        if not blocks:
            return EntryBlock.empty()
        if len(blocks) == 1:
            return blocks[0]
        # scheme discipline (ISSUE 19): unlike epoch_key (which degrades a
        # mixed merge to the uncached prep), a cross-scheme concat has no
        # meaning — the rows would hit the wrong kernel. The mesh packer
        # keys lanes per scheme precisely so this never fires in the
        # dispatch path; a caller-driven mix is a bug, not a fallback.
        scheme = blocks[0].scheme
        if any(b.scheme != scheme for b in blocks):
            raise ValueError("cannot concat mixed-scheme EntryBlocks")
        pub = np.concatenate([b.pub for b in blocks])
        sig = np.concatenate([b.sig for b in blocks])
        msgs = b"".join(b.msgs_contiguous()[0] for b in blocks)
        offsets = np.zeros(len(pub) + 1, dtype=np.int64)
        pos = 0
        base = 0
        for b in blocks:
            buf, o = b.msgs_contiguous()
            offsets[pos + 1 : pos + len(b) + 1] = o[1:] + base
            pos += len(b)
            base += int(o[-1])
        # epoch metadata survives only a SAME-epoch merge: gather indices
        # are rows of one valset's device table, so a mixed-key concat
        # (the coalescer's mixed-valset fallback) drops to the uncached
        # prep instead of gathering from the wrong table
        val_idx = epoch_key = None
        if (
            blocks[0].epoch_key is not None
            and all(b.epoch_key == blocks[0].epoch_key for b in blocks)
            and all(b.val_idx is not None for b in blocks)
        ):
            epoch_key = blocks[0].epoch_key
            val_idx = np.concatenate([b.val_idx for b in blocks])
        pub_aux = None
        if all(b.pub_aux is not None for b in blocks):
            pub_aux = np.concatenate([b.pub_aux for b in blocks])
        return EntryBlock(pub, sig, msgs, offsets,
                          val_idx=val_idx, epoch_key=epoch_key,
                          scheme=scheme, pub_aux=pub_aux)


class AggBlock:
    """Columnar AGGREGATED-commit batch — the BLS12-381 lane's analogue
    of EntryBlock (ISSUE 20). One row is one whole commit, not one
    signature:

        sig     (k, 96) uint8   aggregated G2 signatures (compressed)
        bits    (k, v)  bool    signer bitmap rows over ONE committee
        msgs    bytes           all sign-bytes concatenated (one per row)
        offsets (k+1,)  int64   msgs[offsets[i]:offsets[i+1]] is row i
        pub48   (v, 48) uint8   the committee's compressed G1 pubkeys —
                                a host snapshot carried so a cold/evicted
                                epoch can still build kernel tables
        is_pad  (k,)    bool    mesh padding rows (verdicts discarded)

    Unlike EntryBlock there is no val_idx column: the bitmap IS the
    committee reference, so `epoch_key` (ValidatorSet.hash()) is ALWAYS
    set — the mesh packer keys lanes on it, which is what guarantees two
    different committees' bitmaps never share a device launch. Pad
    blocks are committee-free (bits width 0) and adopt the committee of
    whatever non-pad block they are concatenated with."""

    __slots__ = ("sig", "bits", "msgs", "offsets", "pub48", "is_pad",
                 "epoch_key", "scheme", "val_idx")

    def __init__(self, sig: np.ndarray, bits: np.ndarray,
                 msgs: Union[bytes, memoryview], offsets: np.ndarray,
                 pub48: np.ndarray, epoch_key: bytes,
                 is_pad: "np.ndarray" = None):
        k = sig.shape[0]
        if sig.shape != (k, 96):
            raise ValueError("sig must be (k, 96) uint8")
        if bits.ndim != 2 or bits.shape[0] != k:
            raise ValueError("bits must be (k, v) bool")
        if offsets.shape != (k + 1,):
            raise ValueError("offsets must be (k+1,)")
        if k and bool((np.diff(offsets) < 0).any()):
            raise ValueError("offsets must be non-decreasing")
        if pub48.shape != (bits.shape[1], 48):
            raise ValueError("pub48 must be (v, 48) matching bits width")
        self.sig = sig
        self.bits = bits
        self.msgs = msgs
        self.offsets = offsets
        self.pub48 = pub48
        self.epoch_key = epoch_key
        if is_pad is None:
            is_pad = np.zeros(k, dtype=bool)
        elif is_pad.shape != (k,):
            raise ValueError("is_pad must be (k,)")
        self.is_pad = is_pad
        self.scheme = "bls12381"
        self.val_idx = None  # epoch_cache.lookup() bypass: bitmap-indexed

    # -- construction -------------------------------------------------------

    @classmethod
    def from_commits(cls, commits, pub48: np.ndarray,
                     epoch_key: bytes) -> "AggBlock":
        """[(bits_bool_row, sign_bytes, sig96), ...] over one committee."""
        k = len(commits)
        v = pub48.shape[0]
        if k == 0:
            return cls(np.zeros((0, 96), dtype=np.uint8),
                       np.zeros((0, v), dtype=bool), b"", _EMPTY_OFFSETS,
                       pub48, epoch_key)
        sig = np.frombuffer(
            b"".join(s for _, _, s in commits), dtype=np.uint8
        ).reshape(k, 96)
        bits = np.stack([np.asarray(b, dtype=bool) for b, _, _ in commits])
        lens = np.fromiter((len(m) for _, m, _ in commits), dtype=np.int64,
                           count=k)
        offsets = np.zeros(k + 1, dtype=np.int64)
        np.cumsum(lens, out=offsets[1:])
        msgs = b"".join(m for _, m, _ in commits)
        return cls(sig, bits, msgs, offsets, pub48, epoch_key)

    @classmethod
    def pad(cls, n: int) -> "AggBlock":
        """Committee-free padding rows (bits width 0; the backend preps
        pads from its fixed self-signed pad commit, not from the bitmap).
        epoch_key None: mesh pad blocks are built per lane AFTER packing,
        so they concat-adopt the lane's key/committee."""
        return cls(
            np.zeros((n, 96), dtype=np.uint8),
            np.zeros((n, 0), dtype=bool),
            b"",
            np.zeros(n + 1, dtype=np.int64),
            np.zeros((0, 48), dtype=np.uint8),
            None,
            is_pad=np.ones(n, dtype=bool),
        )

    # -- shape / access -----------------------------------------------------

    @property
    def n(self) -> int:
        return self.sig.shape[0]

    def __len__(self) -> int:
        return self.sig.shape[0]

    def msg_nbytes(self) -> int:
        return int(self.offsets[-1] - self.offsets[0])

    def msg(self, i: int) -> bytes:
        o = self.offsets
        return bytes(memoryview(self.msgs)[int(o[i]) : int(o[i + 1])])

    def msgs_contiguous(self):
        base = int(self.offsets[0])
        end = int(self.offsets[-1])
        buf = self.msgs
        if base != 0 or end != len(buf):
            buf = memoryview(buf)[base:end]
        if base == 0:
            return buf, self.offsets
        return buf, self.offsets - base

    def __getitem__(self, key: slice) -> "AggBlock":
        if not isinstance(key, slice):
            raise TypeError("AggBlock indexing takes a slice")
        start, stop, step = key.indices(self.n)
        if step != 1:
            raise ValueError("AggBlock slices must be contiguous")
        o = self.offsets
        base = int(o[start])
        mv = memoryview(self.msgs)[base : int(o[stop])]
        return AggBlock(
            self.sig[start:stop],
            self.bits[start:stop],
            mv,
            o[start : stop + 1] - base,
            self.pub48,
            self.epoch_key,
            is_pad=self.is_pad[start:stop],
        )

    # -- combination --------------------------------------------------------

    @staticmethod
    def concat(blocks: Sequence["AggBlock"]) -> "AggBlock":
        """Same one-concatenate-per-column discipline as EntryBlock. The
        committee comes from the non-pad blocks, which must AGREE (the
        mesh keys agg lanes on epoch_key, so a mixed-committee concat is
        a caller bug); width-0 pad blocks adopt it."""
        blocks = [b for b in blocks if len(b)]
        if not blocks:
            raise ValueError("cannot concat zero aggregated rows")
        if len(blocks) == 1:
            return blocks[0]
        live = [b for b in blocks if b.epoch_key is not None]
        if live:
            epoch_key = live[0].epoch_key
            pub48 = live[0].pub48
            if any(b.epoch_key != epoch_key for b in live):
                raise ValueError("cannot concat mixed-committee AggBlocks")
        else:  # all-pad merge keeps the committee-free form
            epoch_key = None
            pub48 = blocks[0].pub48
        v = pub48.shape[0]
        bits = np.zeros((sum(len(b) for b in blocks), v), dtype=bool)
        pos = 0
        for b in blocks:
            if b.bits.shape[1]:
                bits[pos : pos + len(b)] = b.bits
            pos += len(b)
        sig = np.concatenate([b.sig for b in blocks])
        is_pad = np.concatenate([b.is_pad for b in blocks])
        msgs = b"".join(b.msgs_contiguous()[0] for b in blocks)
        offsets = np.zeros(len(sig) + 1, dtype=np.int64)
        pos = 0
        base = 0
        for b in blocks:
            _, o = b.msgs_contiguous()
            offsets[pos + 1 : pos + len(b) + 1] = o[1:] + base
            pos += len(b)
            base += int(o[-1])
        return AggBlock(sig, bits, msgs, offsets, pub48, epoch_key,
                        is_pad=is_pad)


def block_concat(blocks):
    """Type-dispatched concat for the mesh/pipeline coalescers: a lane is
    homogeneous (EntryBlocks or AggBlocks, never both — scheme-keyed
    packing), but the CALLER is generic over lanes."""
    blocks = list(blocks)
    if blocks and isinstance(blocks[0], AggBlock):
        return AggBlock.concat(blocks)
    return EntryBlock.concat(blocks)


class CommitBlock:
    """Columnar commit-signature representation — populated ONCE at wire
    decode (types/block.py Commit.decode) so the verify hot path never
    walks per-signature CommitSig objects. The CommitSig objects the
    `commit.signatures` API exposes are LAZY VIEWS over these columns
    (types/block.py CommitSigs), not the source of truth:

        flags      (n,)    uint8   BlockIDFlag per signature
        val_idx    (n,)    int32   validator index (signature order)
        sig        (n, 64) uint8   signatures; absent lanes all-zero
        ts_seconds (n,)    int64   vote timestamp seconds
        ts_nanos   (n,)    int32   vote timestamp nanos
        addr       (n, 20) uint8   validator addresses; absent lanes zero

    Construction invariant (enforced by the builders in types/block.py):
    every lane matches the canonical CommitSig shape — absent lanes have
    no address/signature and the Go zero timestamp, non-absent lanes
    carry a 20-byte address and exactly 64 signature bytes, and flags are
    one of {ABSENT, COMMIT, NIL}. A commit violating that decodes to
    plain CommitSig objects instead (no CommitBlock), so the object path
    keeps raising exactly the errors it always raised."""

    __slots__ = ("flags", "val_idx", "sig", "ts_seconds", "ts_nanos", "addr")

    def __init__(self, flags: np.ndarray, val_idx: np.ndarray,
                 sig: np.ndarray, ts_seconds: np.ndarray,
                 ts_nanos: np.ndarray, addr: np.ndarray):
        n = flags.shape[0]
        if (
            sig.shape != (n, 64) or addr.shape != (n, 20)
            or val_idx.shape != (n,) or ts_seconds.shape != (n,)
            or ts_nanos.shape != (n,)
        ):
            raise ValueError("CommitBlock column shapes disagree")
        self.flags = flags
        self.val_idx = val_idx
        self.sig = sig
        self.ts_seconds = ts_seconds
        self.ts_nanos = ts_nanos
        self.addr = addr

    @property
    def n(self) -> int:
        return self.flags.shape[0]

    def __len__(self) -> int:
        return self.flags.shape[0]


EntriesLike = Union[EntryBlock, Sequence[Entry]]


def as_block(entries: EntriesLike) -> EntryBlock:
    """Normalize the public tuple-list API onto the columnar form."""
    if isinstance(entries, (EntryBlock, AggBlock)):
        return entries
    return EntryBlock.from_entries(list(entries))
