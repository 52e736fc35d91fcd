"""Multi-chip sharding of commit verification over a jax device Mesh.

SURVEY.md §7 stage 8 / §2 parallelism table: the reference's only
data-parallel compute — signature batching (types/validation.go:152,
crypto/ed25519/ed25519.go:192) — scales across chips here by sharding the
batch axis over an ICI mesh. The voting-power tally that VerifyCommit
folds over signatures (types/validation.go:152-260) becomes a `psum`
collective, so a 10k-validator commit verifies as: shard signatures,
verify locally (embarrassingly parallel ladder), all-reduce the tallied
power and the all-valid bit over ICI.

This module is the framework's "full training step over a mesh": the
shape the driver's `dryrun_multichip` exercises.
"""

from __future__ import annotations

import logging
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import trace as _trace
from . import backend as _backend
from . import ed25519_verify as _kernel

_span = _trace.span

_log = logging.getLogger("tendermint_tpu.ops.sharded")

AXIS = "dp"


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)"
        )
    return Mesh(np.asarray(devs[:n]), (AXIS,))


def _commit_step(a_y, a_sign, r_y, r_sign, s_bits_t, k_bits_t, s_ok, power, live):
    """Per-shard body: verify local signatures, then all-reduce the tally.

    power: (B, 4) int32 — voting power split into base-2^16 lanes (see
    split_power) so 63-bit totals survive int32-only TPU lanes.
    """
    valid = _kernel.verify_kernel(a_y, a_sign, r_y, r_sign, s_bits_t, k_bits_t, s_ok)
    ok = valid & live
    # Tally voting power of valid signatures in 4 base-2^16 int32 lanes:
    # power < MaxTotalVotingPower = 2^60 (types/validator_set.go:25), so
    # each lane < 2^16 and a 10240-row lane sum < 2^30 — no overflow.
    lanes = jnp.sum(jnp.where(ok[..., None], power, 0), axis=0)
    lanes = jax.lax.psum(lanes, AXIS)
    all_valid = jax.lax.psum(jnp.sum(jnp.where(live & ~valid, 1, 0)), AXIS) == 0
    return valid, lanes, all_valid


def sharded_commit_verifier(mesh: Mesh):
    """Build the jitted, mesh-sharded commit verification step."""
    batch_sharded = NamedSharding(mesh, P(AXIS))
    bits_sharded = NamedSharding(mesh, P(None, AXIS))  # (253, B)
    replicated = NamedSharding(mesh, P())

    from jax import shard_map

    fn = shard_map(
        _commit_step,
        mesh=mesh,
        in_specs=(
            P(AXIS), P(AXIS), P(AXIS), P(AXIS),
            P(None, AXIS), P(None, AXIS), P(AXIS), P(AXIS), P(AXIS),
        ),
        out_specs=(P(AXIS), P(), P()),
    )
    return jax.jit(fn), (batch_sharded, bits_sharded, replicated)


POWER_LANES = 4
POWER_BASE = 1 << 16


def split_power(powers: np.ndarray) -> np.ndarray:
    """(B,) voting powers (< 2^60 = MaxTotalVotingPower cap) -> (B, 4)
    int32 base-2^16 lanes."""
    p = np.asarray(powers, dtype=np.int64)
    if (p < 0).any() or (p >= 1 << 62).any():
        raise ValueError("voting power out of range")
    lanes = [(p >> (16 * i)) & 0xFFFF for i in range(POWER_LANES)]
    return np.stack(lanes, axis=1).astype(np.int32)


def join_power(lanes) -> int:
    return sum(int(v) << (16 * i) for i, v in enumerate(np.asarray(lanes)))


def verify_commit_sharded(
    entries: List[Tuple[bytes, bytes, bytes]],
    powers: List[int],
    mesh: Mesh,
    bucket: int | None = None,
) -> Tuple[np.ndarray, int, bool]:
    """Verify a commit's signatures across the mesh and tally voting power.

    Returns (valid[n], tallied_power_of_valid, all_valid). The device
    equivalent of types/validation.go:152 verifyCommitBatch's accumulation,
    with the per-sig valid[] the blame path (:242-248) needs.

    A warm-epoch EntryBlock (val_idx + epoch_key resident in the cache)
    dispatches to the cached variant: the committee reads from each
    shard's replicated table instead of riding the batch transfer.
    """
    from . import epoch_cache as _epoch

    if _epoch.lookup(entries) is not None:
        return verify_commit_sharded_cached(entries, powers, mesh,
                                            bucket=bucket)
    n = len(entries)
    nd = np.prod(mesh.devices.shape)
    bucket = bucket or _backend._bucket_for(max(n, int(nd)))
    if bucket % nd:
        bucket += int(nd) - bucket % int(nd)
    with _span("sharded.host_prep", n=n, bucket=bucket):
        args = _backend.prepare_batch(entries, bucket)
        live = np.zeros((bucket,), dtype=bool)
        live[:n] = True
        pw = np.zeros((bucket, POWER_LANES), dtype=np.int32)
        pw[:n] = split_power(np.asarray(powers[:n]))
    fn, _ = _jitted_for(mesh)
    with _span("sharded.device", n=n, bucket=bucket):
        valid, lanes, all_valid = fn(*args, pw, live)
        # np.array, not asarray: on the CPU backend the latter is a
        # zero-copy view of the XLA output buffer, and with donation on
        # a later launch can recycle that page under the caller's slice
        valid = np.array(valid)
    return (
        valid[:n],
        join_power(lanes),
        bool(np.asarray(all_valid)),
    )


_mesh_cache: dict = {}


def _jitted_for(mesh: Mesh):
    key = (tuple(d.id for d in mesh.devices.flat),)
    if key not in _mesh_cache:
        _mesh_cache[key] = sharded_commit_verifier(mesh)
    return _mesh_cache[key]


# ---------------------------------------------------------------------------
# Epoch-cached sharding: the valset's device tables (ops/epoch_cache.py)
# REPLICATED across the mesh — every shard gathers its local lanes'
# committee rows from its own resident copy, so a warm epoch ships only
# per-signature data to every chip (the multi-chip face of the PR-7
# epoch cache). Table replication happens once per (epoch, mesh).
# ---------------------------------------------------------------------------


def epoch_tables_sharded(ep, mesh: Mesh):
    """The epoch's XLA limb/sign tables placed with a REPLICATED
    NamedSharding over `mesh` — per-shard residency, uploaded once per
    (epoch key, mesh). Returns (limbs (vp, 20), sign (vp,)) jax Arrays.

    ISSUE 9 (b): mesh-keyed tables live INSIDE the epoch's cache entry
    (EpochEntry._dev, keyed ("xla_sharded", device ids)) instead of a
    module-level side table, so the PR-5 LRU owns their lifetime — an
    evicted epoch drops its mesh replicas with its single-device
    layouts, and the upload runs under the entry lock on the dispatch-
    owner thread (devcheck note_device_touch covers it)."""
    return ep.sharded_xla_tables(mesh)


def _commit_step_cached(tbl_limbs, tbl_sign, idx, r_enc, s_enc, k_enc,
                        s_ok, power, live):
    """Per-shard body of the epoch-cached commit step: gather this
    shard's committee rows from the replicated table, unpack the raw
    per-sig rows on device, verify, then the same psum tally as
    _commit_step."""
    valid = _kernel.verify_kernel_cached(
        tbl_limbs, tbl_sign, idx, r_enc, s_enc, k_enc, s_ok
    )
    ok = valid & live
    lanes = jnp.sum(jnp.where(ok[..., None], power, 0), axis=0)
    lanes = jax.lax.psum(lanes, AXIS)
    all_valid = jax.lax.psum(jnp.sum(jnp.where(live & ~valid, 1, 0)), AXIS) == 0
    return valid, lanes, all_valid


def sharded_commit_verifier_cached(mesh: Mesh, donate: bool = False):
    """Jitted mesh-sharded commit verification over a device-resident
    epoch table: tables replicated (P(None, ...)), per-signature inputs
    sharded on the batch axis.

    donate=True donates ONLY the per-signature batch args (argnums 2+,
    fresh host arrays every call) — the replicated epoch tables (argnums
    0-1) live in the epoch entry's mesh-keyed cache across calls and donating them would
    invalidate every later call's table reference (ISSUE 7: the
    donation-safety rule under the replicated-table path)."""
    from jax import shard_map

    fn = shard_map(
        _commit_step_cached,
        mesh=mesh,
        in_specs=(
            P(None, None), P(None),               # replicated epoch table
            P(AXIS), P(AXIS), P(AXIS), P(AXIS),   # idx, r, s, k
            P(AXIS), P(AXIS), P(AXIS),            # s_ok, power, live
        ),
        out_specs=(P(AXIS), P(), P()),
    )
    if donate:
        return jax.jit(fn, donate_argnums=tuple(range(2, 9)))
    return jax.jit(fn)


def verify_commit_sharded_cached(
    block,
    powers: List[int],
    mesh: Mesh,
    bucket: int | None = None,
) -> Tuple[np.ndarray, int, bool]:
    """verify_commit_sharded for a WARM epoch: `block` is an EntryBlock
    carrying val_idx/epoch_key (ops/entry_block.py) whose valset is in
    the epoch cache. Ships raw per-sig rows + gather indices; each shard
    reads the committee from its replicated table copy. Falls back to
    verify_commit_sharded when the epoch is not resident."""
    from . import epoch_cache as _epoch

    ep = _epoch.lookup(block)
    if ep is None:
        return verify_commit_sharded(block, powers, mesh, bucket=bucket)
    n = len(block)
    nd = int(np.prod(mesh.devices.shape))
    bucket = bucket or _backend._bucket_for(max(n, nd))
    if bucket % nd:
        bucket += nd - bucket % nd
    with _span("sharded.host_prep", n=n, bucket=bucket, cached=1):
        args = _backend.prepare_batch_cached(block, bucket, ep)
        live = np.zeros((bucket,), dtype=bool)
        live[:n] = True
        pw = np.zeros((bucket, POWER_LANES), dtype=np.int32)
        pw[:n] = split_power(np.asarray(powers[:n]))
    donate = _backend.engine().donate
    tbl = epoch_tables_sharded(ep, mesh)
    key = ("cached", tuple(d.id for d in mesh.devices.flat), donate)
    if key not in _mesh_cache:
        _mesh_cache[key] = sharded_commit_verifier_cached(mesh, donate)
    with _span("sharded.device", n=n, bucket=bucket, cached=1):
        valid, lanes, all_valid = _mesh_cache[key](*tbl, *args, pw, live)
        # np.array, not asarray: on the CPU backend the latter is a
        # zero-copy view of the XLA output buffer, and with donation on
        # a later launch can recycle that page under the caller's slice
        valid = np.array(valid)
    return (
        valid[:n],
        join_power(lanes),
        bool(np.asarray(all_valid)),
    )


# ---------------------------------------------------------------------------
# Production-kernel sharding: the compact Pallas pipeline under shard_map
# (shard the kernel VerifyCommit actually runs, not the op-graph
# kernel). Batch-minor compact args shard on their LAST axis;
# the voting-power tally and all-valid bit ride psum collectives over ICI.
# ---------------------------------------------------------------------------


def sharded_pallas_verifier(mesh: Mesh, n_per_shard: int, block: int,
                            interpret: bool):
    from jax import shard_map

    from . import pallas_verify as _pv

    # Compiled path: declare the kernel outputs varying over the dp axis
    # so shard_map's invariant checking (check_vma, the default) stays ON.
    # Interpret path: call positionally without vma — an explicit vma=None
    # kwarg would create a distinct lru_cache entry and re-trace the same
    # pipeline other call sites already compiled.
    if interpret:
        kern = _pv._jitted_pallas_verify(n_per_shard, block, interpret)
    else:
        kern = _pv._jitted_pallas_verify(
            n_per_shard, block, interpret, vma=frozenset({AXIS})
        )

    def _step(a_t, r_t, s_t, k_t, sok_t, power, live):
        valid = kern(a_t, r_t, s_t, k_t, sok_t)[0].astype(bool)
        ok = valid & live
        lanes = jnp.sum(jnp.where(ok[..., None], power, 0), axis=0)
        lanes = jax.lax.psum(lanes, AXIS)
        all_valid = jax.lax.psum(jnp.sum(jnp.where(live & ~valid, 1, 0)), AXIS) == 0
        return valid, lanes, all_valid

    fn = shard_map(
        _step,
        mesh=mesh,
        in_specs=(
            P(None, AXIS), P(None, AXIS), P(None, AXIS), P(None, AXIS),
            P(None, AXIS), P(AXIS), P(AXIS),
        ),
        out_specs=(P(AXIS), P(), P()),
        # The production (Mosaic/TPU) path runs with vma checking ON —
        # the kernel outputs declare vma={dp} above and a 1-device TPU
        # mesh compiles+runs checked (verified on hardware, round 5).
        # interpret mode only: jax's pallas HLO interpreter mixes varying
        # and unvarying operands in its own grid-index lowering and fails
        # with "shift_right_arithmetic requires varying manual axes to
        # match ... pass check_vma=False" — a documented jax workaround,
        # not a property of this kernel.
        check_vma=not interpret,
    )
    return jax.jit(fn)


def verify_commit_sharded_pallas(
    entries: List[Tuple[bytes, bytes, bytes]],
    powers: List[int],
    mesh: Mesh,
    bucket: int | None = None,
) -> Tuple[np.ndarray, int, bool]:
    """verify_commit_sharded on the production Pallas kernel: compact
    wire-format inputs, batch axis sharded across the mesh, psum tally.
    Non-TPU backends run the kernel in interpret mode (the same traced
    program Mosaic compiles on TPU)."""
    from . import pallas_verify as _pv

    n = len(entries)
    nd = int(np.prod(mesh.devices.shape))
    bucket = bucket or max(nd * 8, _bucket_pow2(n, nd))
    if bucket % nd:
        bucket += nd - bucket % nd
    per_shard = bucket // nd
    block = _pv.pick_block(per_shard)
    interpret = _backend.engine().interpret
    with _span("sharded.host_prep", n=n, bucket=bucket):
        a_t, r_t, s_t, k_t, sok_t = _pv.prepare_compact(entries, bucket)
        live = np.zeros((bucket,), dtype=bool)
        live[:n] = True
        pw = np.zeros((bucket, POWER_LANES), dtype=np.int32)
        pw[:n] = split_power(np.asarray(powers[:n]))
    key = ("pallas", tuple(d.id for d in mesh.devices.flat), per_shard, block,
           interpret)
    if key not in _mesh_cache:
        _mesh_cache[key] = sharded_pallas_verifier(mesh, per_shard, block,
                                                   interpret)
    with _span("sharded.device", n=n, bucket=bucket):
        valid, lanes, all_valid = _mesh_cache[key](
            a_t, r_t, s_t, k_t, sok_t, pw, live
        )
        # np.array, not asarray: on the CPU backend the latter is a
        # zero-copy view of the XLA output buffer, and with donation on
        # a later launch can recycle that page under the caller's slice
        valid = np.array(valid)
    return (
        valid[:n],
        join_power(lanes),
        bool(np.asarray(all_valid)),
    )


def _bucket_pow2(n: int, nd: int) -> int:
    b = nd
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Flagship-kernel sharding: the RLC fast-accept pipeline (ops.pallas_rlc —
# the engine VerifyCommit dispatches on TPU since round 5) under shard_map.
# The LANE axis shards over the mesh; the psum tally sums voting power of
# signatures in accepted lanes; rejected lanes re-verify on the host for
# blame exactly like the single-chip path (expand_lanes semantics).
# ---------------------------------------------------------------------------


# Signatures a lane of the sharded RLC program: the mesh plans its own
# shapes (per-shard lane counts from the mesh size), so it names its width
# itself and does not follow pallas_rlc.plan_bucket's single-chip rule.
RLC_M = 4


def sharded_rlc_verifier(mesh: Mesh, g_per_shard: int, block: int,
                         interpret: bool):
    from jax import shard_map

    from . import pallas_rlc as _pr

    m = RLC_M
    # the pipeline's slot-major form: its four arrays shard lane by lane
    # (the single-chip launch ships one packed buffer instead)
    if interpret:
        kern = _pr._jitted_rlc_verify_slot_major(m, g_per_shard, block,
                                                 interpret)
    else:
        kern = _pr._jitted_rlc_verify_slot_major(
            m, g_per_shard, block, interpret, vma=frozenset({AXIS})
        )

    def _step(a_t, r_t, scal_t, sok_t, power, live):
        lane_valid = kern(a_t, r_t, scal_t, sok_t)[0].astype(bool)
        sig_valid = jnp.repeat(lane_valid, m)  # fast-accept: lane -> sigs
        ok = sig_valid & live
        lanes = jnp.sum(jnp.where(ok[..., None], power, 0), axis=0)
        lanes = jax.lax.psum(lanes, AXIS)
        all_valid = (
            jax.lax.psum(jnp.sum(jnp.where(live & ~sig_valid, 1, 0)), AXIS) == 0
        )
        return lane_valid, lanes, all_valid

    fn = shard_map(
        _step,
        mesh=mesh,
        in_specs=(
            P(None, AXIS), P(None, AXIS), P(None, AXIS), P(None, AXIS),
            P(AXIS), P(AXIS),
        ),
        out_specs=(P(AXIS), P(), P()),
        # same rationale as sharded_pallas_verifier above
        check_vma=not interpret,
    )
    return jax.jit(fn)


def verify_commit_sharded_rlc(
    entries: List[Tuple[bytes, bytes, bytes]],
    powers: List[int],
    mesh: Mesh,
) -> Tuple[np.ndarray, int, bool]:
    """verify_commit_sharded on the FLAGSHIP (RLC fast-accept) kernel:
    lanes shard across the mesh, accepted-lane voting power rides a psum,
    rejected lanes fall back to host per-sig verification for blame (and
    their valid signatures' power is added back on the host — identical
    accept/tally semantics to the single-chip RLC path). The batch size
    is derived from the mesh (per-shard lane count is pow2) — unlike the
    siblings there is no bucket parameter to pin. The launch takes the
    pipeline's four slot-major arrays (pallas_rlc.slot_major_args), each
    lane-sharded across the chips, where a single-chip launch ships one
    packed buffer."""
    from . import pallas_rlc as _pr

    n = len(entries)
    nd = int(np.prod(mesh.devices.shape))
    m = RLC_M
    lanes_needed = max((n + m - 1) // m, 1)
    # per-shard lane count: pow2, >= 1, such that total lanes covers n
    g_shard = 1
    while g_shard * nd < lanes_needed:
        g_shard *= 2
    block = min(g_shard, 128)  # pow2 g_shard: block always divides
    g = g_shard * nd
    bucket = g * m

    with _span("sharded.host_prep", n=n, bucket=bucket):
        a_t, r_t, scal_t, sok_t = _pr.slot_major_args(
            *_pr.prepare_rlc(entries, bucket, m), bucket, m)
        live = np.zeros((bucket,), dtype=bool)
        live[:n] = True
        pw = np.zeros((bucket, POWER_LANES), dtype=np.int32)
        pw[:n] = split_power(np.asarray(powers[:n]))
    interpret = _backend.engine().interpret
    key = ("rlc", tuple(d.id for d in mesh.devices.flat), g_shard, block,
           interpret)
    if key not in _mesh_cache:
        _mesh_cache[key] = sharded_rlc_verifier(mesh, g_shard, block,
                                                interpret)
    with _span("sharded.device", n=n, bucket=bucket):
        lane_valid, lanes_pw, all_valid = _mesh_cache[key](
            a_t, r_t, scal_t, sok_t, pw, live
        )
        lane_valid = np.asarray(lane_valid)
    tallied = join_power(lanes_pw)
    # lane verdicts -> per-sig verdicts + host re-verify of rejected
    # lanes (shared with the single-chip path), then add the rescued
    # signatures' power back into the device tally
    per_sig = _pr.expand_lanes(lane_valid, entries, m)
    rescued = per_sig & ~np.repeat(lane_valid, m)[:n]
    tallied += sum(int(powers[i]) for i in np.nonzero(rescued)[0])
    return per_sig, tallied, bool(per_sig.all()) if n else bool(all_valid)


# ---------------------------------------------------------------------------
# Mesh-dispatcher kernels (ISSUE 9 tentpole): valid-bits-only variants of
# the sharded verifiers for the pipeline's lane-packed superbatches. The
# dispatcher needs the per-row verdict vector and nothing else — blame and
# tallies are demuxed per job on the host — so these skip the psum
# collectives entirely: each device verifies its lane(s), the output
# shards back along the batch axis. Built once per (mesh, variant) in
# _mesh_cache; called ONLY from the dispatch-owner thread.
# ---------------------------------------------------------------------------


_few_devices_warned = False


def mesh_ready(n_lanes: int) -> bool:
    """Can a real shard_map mesh serve `n_lanes` lanes? False (fewer
    visible devices than lanes) runs the mesh dispatcher's superbatch as
    simulated lanes on one device — the tier-1/CPU face — and says so
    once."""
    global _few_devices_warned
    if len(jax.devices()) >= n_lanes:
        return True
    if not _few_devices_warned:
        _few_devices_warned = True
        _log.warning(
            "mesh dispatcher asked for %d lanes but only %d devices "
            "are visible — running simulated lanes on one device. "
            "Logged once.", n_lanes, len(jax.devices()),
        )
    return False


_dispatch_meshes: dict = {}


def dispatch_mesh(n_lanes: int) -> Mesh:
    """The dispatcher's mesh over the first `n_lanes` devices (cached —
    Mesh construction is cheap but the _mesh_cache keys off device ids,
    so reusing the object keeps the jit caches warm)."""
    m = _dispatch_meshes.get(n_lanes)
    if m is None:
        m = _dispatch_meshes[n_lanes] = make_mesh(n_lanes)
    return m


# per-batch argument placement of each mesh launch, batch axis sharded
# lane-per-device: the shard_map in_specs and the NamedShardings
# device_pool.transfer places the host arrays by are the same table
_MESH_SPECS = {
    # uncached XLA args: limbs/sign/bits/s_ok (backend.prepare_batch)
    "xla": (P(AXIS), P(AXIS), P(AXIS), P(AXIS),
            P(None, AXIS), P(None, AXIS), P(AXIS)),
    # warm-epoch gather args: idx + raw r/s/k rows + s_ok
    "xla_cached": (P(AXIS),) * 5,
    # compact pallas: batch-minor, shard the last axis
    "pallas": (P(None, AXIS),) * 5,
}


class MeshLaunch:
    """One shard_map launch of the mesh dispatcher: the function, and in
    `shardings` where each per-batch argument goes — batch k+1's H2D
    copies land lane-per-device through device_pool.transfer, overlapping
    mesh kernel k exactly like the single-device overlap path."""

    __slots__ = ("_fn", "shardings")

    def __init__(self, fn, mesh: Mesh, kind: str):
        self._fn = fn
        self.shardings = tuple(
            NamedSharding(mesh, p) for p in _MESH_SPECS[kind]
        )

    def __call__(self, *args):
        return self._fn(*args)


def mesh_valid_fn(mesh: Mesh, donate: bool = False) -> MeshLaunch:
    """Jitted shard_map of the bare per-sig verify kernel: uncached args
    sharded lane-per-device, (B,) bool verdicts out."""
    key = ("mesh_valid", tuple(d.id for d in mesh.devices.flat), donate)
    if key not in _mesh_cache:
        from jax import shard_map

        specs = _MESH_SPECS["xla"]
        fn = shard_map(_kernel.verify_kernel, mesh=mesh, in_specs=specs,
                       out_specs=P(AXIS))
        _mesh_cache[key] = MeshLaunch(
            jax.jit(fn, donate_argnums=tuple(range(len(specs))))
            if donate else jax.jit(fn),
            mesh, "xla",
        )
    return _mesh_cache[key]


def mesh_valid_fn_cached(mesh: Mesh, ep, donate: bool = False) -> MeshLaunch:
    """Cached-epoch mesh kernel closure: each shard gathers committee
    rows from its replicated table copy (epoch_tables_sharded — resident
    per device, owned by the epoch LRU) and unpacks the raw per-sig rows
    on device. The table resolves at CALL time, on the dispatch-owner
    thread, exactly like backend.cached_kernel."""
    key = ("mesh_valid_cached",
           tuple(d.id for d in mesh.devices.flat), donate)
    if key not in _mesh_cache:
        from jax import shard_map

        specs = (P(None, None), P(None)) + _MESH_SPECS["xla_cached"]
        fn = shard_map(_kernel.verify_kernel_cached, mesh=mesh,
                       in_specs=specs, out_specs=P(AXIS))
        # the tables (argnums 0-1) are shared across batches: not donated
        _mesh_cache[key] = (
            jax.jit(fn, donate_argnums=tuple(range(2, len(specs))))
            if donate else jax.jit(fn)
        )
    base = _mesh_cache[key]

    def call(*args):
        tbl_limbs, tbl_sign = epoch_tables_sharded(ep, mesh)
        return base(tbl_limbs, tbl_sign, *args)

    return MeshLaunch(call, mesh, "xla_cached")


def mesh_pallas_valid_fn(mesh: Mesh, n_per_shard: int, block: int,
                         interpret: bool) -> MeshLaunch:
    """Compact-pallas mesh kernel, valid bits only: batch-minor args
    shard on their LAST axis (one lane per device), verdict row out."""
    key = ("mesh_pallas_valid", tuple(d.id for d in mesh.devices.flat),
           n_per_shard, block, interpret)
    if key not in _mesh_cache:
        from jax import shard_map

        from . import pallas_verify as _pv

        if interpret:
            kern = _pv._jitted_pallas_verify(n_per_shard, block, interpret)
        else:
            kern = _pv._jitted_pallas_verify(
                n_per_shard, block, interpret, vma=frozenset({AXIS})
            )

        def _step(a_t, r_t, s_t, k_t, sok_t):
            return kern(a_t, r_t, s_t, k_t, sok_t)[0].astype(bool)

        fn = shard_map(
            _step,
            mesh=mesh,
            in_specs=_MESH_SPECS["pallas"],
            out_specs=P(AXIS),
            # same vma rationale as sharded_pallas_verifier above
            check_vma=not interpret,
        )
        _mesh_cache[key] = MeshLaunch(jax.jit(fn), mesh, "pallas")
    return _mesh_cache[key]
