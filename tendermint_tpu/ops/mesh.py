"""Mesh-aware dispatcher lane packing — multichip scale-out serving.

ISSUE 9 tentpole / ROADMAP §2: after PRs 4-7 the single-device path is
pipelined, epoch-cached and overlapped; the binding constraint is
device-count. `ops/sharded.py` proves the sharded kernels compile, but
nothing *feeds* a mesh with concurrent work — every queued commit still
serializes through one device's lanes. This module turns the pipeline's
coalescer into a **mesh dispatcher**: many commits in flight (many
chains / many heights — the millions-of-users shape) are bin-packed into
per-shard **lanes** of one `(n_lanes, lane_bucket)` superbatch per
launch, so one device launch carries every device's work for the step.

Packing model (committee-scale batching, arXiv 2302.00418):

    lane        one device shard's contiguous `lane_bucket` rows of the
                superbatch. A lane holds whole jobs (EntryBlocks) that
                share ONE epoch key — same-epoch blocks gather from the
                same device-resident table; mixed epochs land in
                DIFFERENT lanes, never mixed within one.
    pad rows    short lanes are completed with identity rows (A = R =
                the identity encoding, s = 0 — verify trivially under
                any challenge, exactly `_pack_rows`' padding lanes), so
                every lane is a full compiled shard.
    superbatch  lanes concatenated on the batch axis: `n_lanes *
                lane_bucket` rows, `n_lanes` rounded up to a power of
                two (compiled-shape discipline — shapes stay in
                {1,2,4,8,...} x BUCKETS). With `jax.shard_map` available
                the batch axis shards lane-per-device over the mesh
                (ops/sharded.mesh_valid_fn); otherwise the SAME
                superbatch launches through the plain jitted kernel —
                bit-identical verdicts, "simulated lanes" (the tier-1 /
                CPU face, and the warn-once fallback of ISSUE 9's first
                satellite).
    demux       per-job verdict spans are global row ranges
                (lane_idx * lane_bucket + offset) into the one verdict
                row — readback stays a single slice per job, blame
                indices unchanged.

The packing itself is pure host bookkeeping (numpy + EntryBlock — no
jax, no crypto), importable standalone the way ops/device_pool.py is;
`prepare_superbatch` is the only device-facing function and defers every
heavy import. Uploads and launches remain the property of the
pipeline's single dispatch-owner thread: this module builds plans and
argument tuples, the dispatcher transfers and launches them (the device
single-owner invariant, tmlint device-ownership + devcheck).

Knobs:
    TM_TPU_MESH              lane count: 0/unset = disabled (classic
                             single-lane dispatch), N = pack up to N
                             lanes per launch, "auto" = one lane per
                             visible jax device.
    TM_TPU_MESH_LANE_BUCKET  per-lane signature capacity cap (default:
                             the largest single-device bucket).
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

import numpy as np

try:
    from .entry_block import AggBlock, EntryBlock, block_concat
except ImportError:  # pragma: no cover — standalone file load (crypto-less
    # containers exec this module by path for the jax-free packing tests;
    # entry_block is numpy-only and loads the same way)
    import importlib.util as _ilu
    import os as _os

    _eb_path = _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)), "entry_block.py"
    )
    _eb_spec = _ilu.spec_from_file_location(
        "_tm_tpu_entry_block_standalone", _eb_path
    )
    _eb = _ilu.module_from_spec(_eb_spec)
    _eb_spec.loader.exec_module(_eb)
    EntryBlock = _eb.EntryBlock
    AggBlock = _eb.AggBlock
    block_concat = _eb.block_concat

# single-device bucket ladder (ops/backend.BUCKETS, duplicated here so the
# packing layer stays importable without the device stack;
# tests/test_ops.py holds the two copies equal)
_BUCKETS = (128, 1024, 10240)
# smallest lane bucket an operator may force via TM_TPU_MESH_LANE_BUCKET:
# the secp256k1 ladder's fine bucket floor (backend.SECP_BUCKETS) — its
# per-row kernel cost makes small lanes worthwhile, and the ed25519
# kernel handles any shape the packer emits
_LANE_BUCKET_FLOOR = 16

# BLS12-381 aggregation lanes (ISSUE 20) quantize to their OWN tiny
# ladder (backend.BLS_BUCKETS, held equal by the same test): one row
# is one whole aggregated commit costing two Miller loops, so padding an
# agg lane out to `lane_bucket` per-signature rows would burn orders of
# magnitude more kernel time than the live work. Superbatch row offsets
# therefore accumulate per-lane widths instead of assuming a uniform
# lane stride.
_BLS_LANE_BUCKETS = (4, 16)


def _lane_width(n: int, scheme: str, lane_bucket: int) -> int:
    """Padded row count of one lane: `lane_bucket` for per-signature
    schemes, the smallest BLS bucket covering `n` commits (exact above
    the ladder top — the kernel jits per shape either way) for the
    aggregation lane."""
    if scheme != "bls12381":
        return lane_bucket
    for b in _BLS_LANE_BUCKETS:
        if n <= b:
            return b
    return n


def lanes_from_env() -> int:
    """TM_TPU_MESH -> lane count (0 = mesh dispatch disabled)."""
    env = os.environ.get("TM_TPU_MESH", "").strip().lower()
    if not env or env == "0":
        return 0
    if env == "auto":
        try:
            import jax

            return max(len(jax.devices()), 1)
        except Exception:  # noqa: BLE001 — no jax: mesh mode off
            return 0
    try:
        return max(int(env), 0)
    except ValueError:
        return 0


def lane_cap() -> int:
    """Max signatures one lane may hold (whole jobs only — submit()
    chunks oversized jobs at this bound in mesh mode). Clamped into the
    bucket ladder: a lane larger than the top bucket would let a lane
    outgrow every compiled shape."""
    env = os.environ.get("TM_TPU_MESH_LANE_BUCKET")
    if env:
        try:
            return min(max(int(env), _LANE_BUCKET_FLOOR), _BUCKETS[-1])
        except ValueError:
            pass
    return _BUCKETS[-1]


def _bucket_for(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return _BUCKETS[-1]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


class Lane:
    """One shard's worth of packed jobs: single epoch key, single
    signature scheme (ISSUE 19 — a mixed-scheme commit's ed25519 and
    secp256k1 halves land in DIFFERENT lanes of the same superbatch),
    whole jobs, live rows <= the plan's lane_bucket."""

    __slots__ = ("key", "scheme", "jobs", "n")

    def __init__(self, key: Optional[bytes], scheme: str = "ed25519"):
        self.key = key
        self.scheme = scheme
        self.jobs: List = []  # objects with an `.entries` EntryBlock
        self.n = 0

    def add(self, job) -> None:
        self.jobs.append(job)
        self.n += len(job.entries)


class MeshPlan:
    """A packed superbatch: `lanes` live lanes (possibly fewer than
    `n_lanes` — the rest are pure identity padding), each padded to
    `lane_bucket` rows; `empty_jobs` resolve as zero-width spans without
    occupying a lane. `bucket` is the launch shape in signatures."""

    __slots__ = ("lanes", "lane_bucket", "n_lanes", "empty_jobs")

    def __init__(self, lanes: List[Lane], max_lanes: int,
                 lane_bucket: Optional[int] = None):
        self.lanes = lanes
        self.empty_jobs: List = []
        self.lane_bucket = lane_bucket or min(
            _bucket_for(max((l.n for l in lanes), default=1)), lane_cap()
        )
        # power-of-two lane count keeps the compiled-shape set small:
        # {1,2,4,...} x the bucket ladder — a non-pow2 TM_TPU_MESH is
        # floored (pack_jobs applies the same floor, so the plan always
        # has room for every lane it packed)
        self.n_lanes = min(
            _next_pow2(max(len(lanes), 1)),
            _pow2_floor(max(max_lanes, 1)),
        )

    @property
    def bucket(self) -> int:
        """Total superbatch rows. With only per-signature lanes this is
        `n_lanes * lane_bucket` (every lane strides uniformly); BLS
        aggregation lanes contribute their own quantized width
        (_lane_width) instead."""
        fill = self.n_lanes - len(self.lanes)
        s0 = self.schemes()[0] if fill else None
        total = fill * _lane_width(0, s0, self.lane_bucket) if fill else 0
        for l in self.lanes:
            total += _lane_width(l.n, l.scheme, self.lane_bucket)
        return total

    @property
    def live(self) -> int:
        return sum(l.n for l in self.lanes)

    @property
    def pad(self) -> int:
        return self.bucket - self.live

    def occupancy(self) -> float:
        """Mean live fraction across the superbatch's lanes (a pure-pad
        lane contributes 0)."""
        return self.live / self.bucket if self.bucket else 0.0

    def pad_ratio(self) -> float:
        return self.pad / self.bucket if self.bucket else 0.0

    def epoch_key(self) -> Optional[bytes]:
        """The superbatch's single epoch key, or None when lanes mix
        epochs (mixed packs ride the uncached prep — pubs ship with the
        batch, exactly EntryBlock.concat's mixed-key fallback)."""
        keys = {l.key for l in self.lanes}
        if len(keys) == 1:
            return next(iter(keys))
        return None

    def schemes(self) -> List[str]:
        """The plan's signature schemes in superblock segment order
        (ed25519 first — its pure-pad filler lanes extend the first
        segment)."""
        found = {l.scheme for l in self.lanes}
        return [s for s in ("ed25519", "secp256k1")
                if s in found or (s == "ed25519" and not found)] + sorted(
                    s for s in found if s not in ("ed25519", "secp256k1"))


def pack_jobs(jobs, max_lanes: int, cap: Optional[int] = None,
              ) -> Tuple[MeshPlan, List]:
    """First-fit bin-pack `jobs` (each with an `.entries` EntryBlock)
    into at most `max_lanes` single-epoch lanes of `cap` signatures.
    Jobs that fit nowhere are returned as held-over for the next
    superbatch (exactly the coalescer's bucket-overflow hold). A job
    larger than `cap` raises — submit() must chunk first.

    QoS ordering (ISSUE 13): jobs pack in (priority, seq) order — a
    CONSENSUS-class job claims its lane before any queued INGRESS
    superjob, so when the pack overflows into the hold list it is the
    lowest-priority latest arrivals that wait for the next superbatch.
    Jobs without the attributes (direct callers, older tests) default to
    the most urgent class in arrival order — the pre-QoS behavior."""
    cap = cap or lane_cap()
    # pow2 lane-count discipline (see MeshPlan): never pack more lanes
    # than the plan will have room for
    max_lanes = _pow2_floor(max(max_lanes, 1))
    lanes: List[Lane] = []
    held: List = []
    empty: List = []
    jobs = sorted(
        jobs,
        key=lambda j: (getattr(j, "priority", 0), getattr(j, "seq", 0)),
    )
    for job in jobs:
        n = len(job.entries)
        if n > cap:
            raise ValueError(
                f"job of {n} sigs exceeds the {cap}-sig lane capacity"
            )
        if n == 0:
            # empty submissions resolve as zero-width spans without
            # pinning a lane (an empty job's key must not demote a
            # same-warm-epoch pack to the uncached prep)
            empty.append(job)
            continue
        key = job.entries.epoch_key
        scheme = getattr(job.entries, "scheme", "ed25519")

        def _fits(l, n=n, key=key, scheme=scheme):
            # bucket-aware fit (the classic coalescer's peel rule, as a
            # pack-time predicate): fusing must not push the lane into a
            # BIGGER ladder bucket unless the fused total nearly fills
            # it — e.g. two 600-sig jobs stay separate 1024-bucket lanes
            # instead of one 1200-live lane quantized to 10240 rows.
            # Scheme-keyed (ISSUE 19): a lane holds ONE scheme — the
            # superblock concatenates per-scheme sub-blocks and the
            # launch runs each scheme's kernel over its own row range.
            if l.key != key or l.scheme != scheme or l.n + n > cap:
                return False
            b = _bucket_for(l.n + n)
            if b == _bucket_for(l.n):
                return True
            return b - (l.n + n) <= max(b // 8, 1024)

        lane = next((l for l in lanes if _fits(l)), None)
        if lane is None:
            if len(lanes) < max_lanes:
                lane = Lane(key, scheme)
                lanes.append(lane)
            else:
                held.append(job)
                continue
        lane.add(job)
    plan = MeshPlan(lanes, max_lanes)
    plan.empty_jobs = empty
    return plan, held


import functools as _functools
import hashlib as _hashlib


@_functools.lru_cache(maxsize=1)
def _secp_pad_row() -> Tuple[bytes, bytes]:
    """The secp256k1 padding lane's (pub33, sig64): a REAL lower-S ECDSA
    signature of the empty message under the generator as pubkey (d = 1,
    nonce k = 1 ⇒ r = Gx mod n, s = ±(e + r) mod n), so pad rows ride
    the normal prep/kernel path and verify deterministically True —
    exactly ed25519's identity-pad convention, no special-casing
    anywhere downstream. Self-contained integer math (standalone file
    loads must not need the crypto package)."""
    n_ord = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
    gx = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
    e = int.from_bytes(_hashlib.sha256(b"").digest(), "big") % n_ord
    r = gx % n_ord
    s = (e + r) % n_ord
    if s > n_ord // 2:
        s = n_ord - s
    pub = bytes([2]) + gx.to_bytes(32, "big")  # compress(G); Gy is even
    sig = r.to_bytes(32, "big") + s.to_bytes(32, "big")
    return pub, sig


def pad_block(n: int, ep=None, scheme: str = "ed25519") -> EntryBlock:
    """`n` padding rows as an EntryBlock. ed25519: A = R = the identity
    encoding (y = 1), s = 0, empty message — verifies trivially under
    any challenge scalar (the `_pack_rows` padding-lane construction).
    secp256k1: the fixed trivially-valid generator signature
    (_secp_pad_row). bls12381: committee-free AggBlock pad rows — the
    backend preps those from its fixed self-signed pad commit
    (bls_verify.PAD_MSG), and AggBlock.concat lets them adopt the lane's
    committee. With a warm epoch entry `ep`, rows carry the table's
    pad-row gather index (vp - 1) and the epoch key, so a cached
    superbatch's padding gathers the table's own pad row."""
    if scheme == "bls12381":
        return AggBlock.pad(n)
    pub = np.zeros((n, 32), dtype=np.uint8)
    sig = np.zeros((n, 64), dtype=np.uint8)
    pub_aux = None
    if scheme == "secp256k1":
        pad_pub, pad_sig = _secp_pad_row()
        pub_aux = np.full((n,), pad_pub[0], dtype=np.uint8)
        if n:
            pub[:] = np.frombuffer(pad_pub[1:], dtype=np.uint8)
            sig[:] = np.frombuffer(pad_sig, dtype=np.uint8)
    elif n:
        pub[:, 0] = 1
        sig[:, 0] = 1  # R = identity encoding; s stays 0
    offsets = np.zeros(n + 1, dtype=np.int64)
    val_idx = epoch_key = None
    if ep is not None:
        val_idx = np.full((n,), ep.vp - 1, dtype=np.int32)
        epoch_key = ep.key
    return EntryBlock(pub, sig, b"", offsets,
                      val_idx=val_idx, epoch_key=epoch_key,
                      scheme=scheme, pub_aux=pub_aux)


def _warm_entry(plan: MeshPlan):
    """The plan's epoch-cache entry iff every lane shares one WARM key
    (lazy import — the cache layer is jax-free but lives behind the ops
    package; standalone loads only exercise the packing half)."""
    key = plan.epoch_key()
    if key is None:
        return None
    try:
        from . import epoch_cache as _epoch
    except ImportError:  # pragma: no cover — standalone file load
        return None
    # lookup keys off the (epoch_key, val_idx) attrs; probe via a stub
    class _Probe:
        epoch_key = key
        val_idx = True

    return _epoch.lookup(_Probe())


class SchemeSuperBlock:
    """A mixed-scheme superbatch (ISSUE 19): EntryBlock.concat refuses
    cross-scheme merges, so the superblock holds one contiguous
    EntryBlock SEGMENT per scheme plus its global row offset. Demux
    spans index the fused verdict row exactly as for a plain superblock;
    prepare_superbatch preps each segment with its scheme's kernel and
    the launch fn concatenates the per-segment verdicts — ONE dispatch
    for the whole mixed commit.

    BLS12-381 aggregation lanes (ISSUE 20) appear as one segment PER
    LANE (an AggBlock is bound to one committee's pubkey table, so two
    agg lanes never merge), and their verdict rows are int32 codes —
    concatenating them with the boolean per-signature verdicts promotes
    the fused row to int32, which demux slicing is agnostic to."""

    __slots__ = ("parts", "_n")

    def __init__(self, parts: List[Tuple], n: int):
        self.parts = parts  # [(scheme, EntryBlock, row_offset), ...]
        self._n = n

    def __len__(self) -> int:
        return self._n

    @property
    def epoch_key(self):  # mixed segments never share one epoch table
        return None


def build_superblock(plan: MeshPlan) -> Tuple[object, List[Tuple]]:
    """Materialize the plan: exactly `plan.bucket` rows (live jobs +
    per-lane padding + pure-pad lanes) and the global demux spans
    [(job, row_offset, n), ...]. Column concat is one np.concatenate per
    column — no per-signature Python. Single-scheme plans return one
    EntryBlock; mixed-scheme plans return a SchemeSuperBlock whose
    segments group each scheme's lanes contiguously (pure-pad filler
    lanes extend the FIRST scheme's segment)."""
    ep = _warm_entry(plan)
    lb = plan.lane_bucket
    order = plan.schemes()
    # emit lanes grouped by scheme; filler pad lanes ride with the first
    # scheme's segment so every segment stays contiguous
    seq: List[Tuple] = []
    for s in order:
        seq.extend((l, s) for l in plan.lanes if l.scheme == s)
        if s == order[0]:
            seq.extend(
                (None, s) for _ in range(plan.n_lanes - len(plan.lanes))
            )
    # segments in seq order: per-signature lanes of one scheme merge into
    # one contiguous EntryBlock segment; every BLS lane stays its OWN
    # segment — agg lanes are keyed on epoch_key and two committees'
    # AggBlocks must never cross-concat (each lane gathers from its own
    # pubkey table)
    segs: List[Tuple] = []  # [(scheme, [blocks])]
    spans: List[Tuple] = []
    base = 0
    for lane, s in seq:
        w = _lane_width(lane.n if lane is not None else 0, s, lb)
        blocks: List = []
        off = 0
        if lane is not None:
            for job in lane.jobs:
                n = len(job.entries)
                spans.append((job, base + off, n))
                if n:
                    blocks.append(job.entries)
                off += n
        if off < w:
            blocks.append(pad_block(w - off, ep, s))
        if s != "bls12381" and segs and segs[-1][0] == s:
            segs[-1][1].extend(blocks)
        else:
            segs.append((s, blocks))
        base += w
    for job in plan.empty_jobs:
        spans.append((job, 0, 0))
    if len(segs) == 1 and segs[0][0] != "bls12381":
        return EntryBlock.concat(segs[0][1]), spans
    parts: List[Tuple] = []
    off = 0
    for s, blocks in segs:
        blk = block_concat(blocks)
        parts.append((s, blk, off))
        off += len(blk)
    return SchemeSuperBlock(parts, off), spans


# ---------------------------------------------------------------------------
# Device-facing half: superbatch prep. Runs on the pipeline's prep pool;
# the returned launch fn runs ONLY on the dispatch-owner thread (which
# also owns the transfer and any lazy epoch-table upload inside the
# cached closures).
# ---------------------------------------------------------------------------


def _prepare_mixed_superbatch(sb: SchemeSuperBlock, bucket: int):
    """Prep a mixed-scheme superbatch: each scheme segment asks
    backend.select_kernel for its kernel + args at its own width, fused
    behind ONE launch fn that slices the flat arg tuple back per segment
    and concatenates the verdict rows in segment order — a single
    dispatch event for the whole commit. A segment that shares one warm
    key still engages the cached gather prep. No shard_map here: every
    segment launches on one device (follow-up, ROADMAP 3a)."""
    from . import backend as _backend

    seg_fns: List[Tuple] = []
    flat_args: List = []
    for _scheme, blk, _off in sb.parts:
        # the segment's exact width: a BLS lane is already quantized (a
        # pad row there costs two Miller loops), the others are whole
        # lane buckets
        fn, args, _rlc, _b = _backend.select_kernel(
            blk, bucket=len(blk), lanes=1
        )
        seg_fns.append((fn, len(flat_args), len(flat_args) + len(args)))
        flat_args.extend(args)

    def _launch(*flat):
        import jax.numpy as jnp

        outs = [fn(*flat[lo:hi]) for fn, lo, hi in seg_fns]
        # a Pallas row is (1, N) int32; the others are flat
        return jnp.concatenate(
            [o[0].astype(bool) if o.ndim == 2 else o for o in outs]
        )

    return _launch, tuple(flat_args), None, bucket, None


def prepare_superbatch(block: EntryBlock, plan: MeshPlan):
    """prep for a mesh superbatch. Same contract as the pipeline's
    `_prepare` plus transfer shardings:

        (launch_fn, args, None, bucket, shardings)

    `shardings` is a per-arg NamedSharding tuple when the superbatch
    launches through a real shard_map mesh (the dispatcher's
    `device_pool.transfer` places each array lane-per-device), or None
    on the single-device / simulated-lanes fallback.

    What is the mesh's own stays here: the plan's width, whether a real
    mesh serves its lanes, the per-segment fusing of a mixed pack. Which
    kernel verifies a segment is backend.select_kernel's answer for a
    lane pack: the platform's per-signature family (verdicts demux by
    row, which RLC lane verdicts cannot), cached when the whole pack
    shares one warm epoch."""
    from . import backend as _backend
    from . import sharded as _sharded

    bucket = plan.bucket
    if len(block) != bucket:
        raise ValueError(
            f"superblock is {len(block)} rows, plan says {bucket}"
        )
    if isinstance(block, SchemeSuperBlock):
        return _prepare_mixed_superbatch(block, bucket)
    m = None
    if plan.n_lanes > 1 and _sharded.mesh_ready(plan.n_lanes):
        m = _sharded.dispatch_mesh(plan.n_lanes)
    fn, args, _rlc, bucket = _backend.select_kernel(
        block, bucket=bucket, lanes=plan.n_lanes, mesh=m
    )
    return fn, args, None, bucket, getattr(fn, "shardings", None)
