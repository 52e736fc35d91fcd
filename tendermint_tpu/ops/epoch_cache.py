"""Device-resident validator-set epoch cache.

After PR 4 the per-batch host cost is dominated by data that
never changes between heights — the validator pubkey columns are re-packed
into limbs/bits on the host, re-shipped to the device, and re-decompressed
in kernel K1 for EVERY batch, even though the signer set is stable across
consecutive heights (committee-based consensus amortizes exactly this way;
arxiv 2302.00418) and the light-client loop re-verifies the SAME valset
across a whole trust period (arxiv 2010.07031).

This module keys on `ValidatorSet.hash()` — already cached on the set and
invalidated (with `ed25519_columns`) by `_update_with_change_set`, so a
membership or power change yields a NEW key and the stale entry ages out
of the LRU. On first sight of a valset the cache registers its pubkey
column; from the SECOND commit on, batches carry only per-signature data
(sig rows, sign-bytes, `val_idx` gather indices) and the
kernels gather the committee from persistent device arrays:

    xla_tables()    (vp, 20) int32 limb rows + (vp,) sign bits — the
                    per-sig XLA kernel gathers A rows on device
                    (ops/ed25519_verify.verify_kernel_cached)
    coords_tables() (4*32, vp) int32 decompressed extended coordinates in
                    the pallas 32-row slot layout + (1, vp) ok flags —
                    K1 then decompresses M points (R only) instead of 2M
                    (ops/pallas_verify, ops/pallas_rlc cached kernels)

Table rows are padded to a power of two (identity-point rows) so the
compiled-shape set stays small under arbitrary valset sizes; gather index
`vp - 1` is the padding lane's identity row.

Upload discipline: the device arrays are materialized LAZILY, on first
use by the kernel closure — which runs on the pipeline's single
dispatch-owner thread (exactly one thread may touch the device).
A COLD epoch therefore verifies through the uncached path (no epoch key
attached); only warm epochs ride the cached kernels. That keeps the first
commit's latency unchanged and makes cold-vs-warm H2D accounting exact
(tools/prep_bench.py --transfer).

Enablement: TM_TPU_EPOCH_CACHE=N sets the LRU depth (0 disables). Unset,
the cache is on (depth 8) for the TPU backend and off elsewhere — CPU/XLA
test runs opt in explicitly so they do not compile extra kernel shapes.
Importable without jax (the types layer notes epochs at verify time;
the first note resolves ops/engine.py, which is what loads jax).
"""

from __future__ import annotations

import functools
import os
import threading
from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np

from ..libs import devcheck as _devcheck
from ..observability import trace as _trace

_span = _trace.span

DEFAULT_DEPTH = 8

_IDENT_ENC = np.zeros(32, dtype=np.uint8)
_IDENT_ENC[0] = 1  # y = 1: the identity point's wire encoding


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.lru_cache(maxsize=1)
def _secp_pad_pub() -> np.ndarray:
    """The secp256k1 padding row's pubkey: the compressed generator
    (jax-free — pure-python curve constants only)."""
    from ..crypto import _weierstrass as _wst

    return np.frombuffer(_wst.compress(_wst.G), dtype=np.uint8)


@functools.lru_cache(maxsize=1)
def _bls_pad_pub() -> np.ndarray:
    """The bls12381 padding row's pubkey: the compressed G1 generator —
    the pad lane's self-signed pad commit verifies under sk=1
    (ops/bls_verify.PAD_MSG)."""
    from ..crypto import bls12381 as _bls

    return np.frombuffer(_bls.g1_compress(_bls.G1_GEN), dtype=np.uint8)


def _pad_row_for(scheme: str) -> np.ndarray:
    if scheme == "ed25519":
        return _IDENT_ENC
    if scheme == "bls12381":
        return _bls_pad_pub()
    return _secp_pad_pub()


class EpochEntry:
    """One validator set's device-resident pubkey tables.

    `pub_rows` is the (vp, 32) HOST snapshot — padded with identity rows —
    from which every device layout derives; layouts materialize lazily
    (and upload exactly once) under the entry lock.

    Donation exemption (ISSUE 7): these device arrays persist across
    batches, so every cached kernel's donate_argnums EXCLUDES the table
    arguments — a donated launch consumes only its per-batch buffers.
    Uploads are span-traced (`pipeline.table_upload`) so the overlapped
    dispatcher's transfer accounting can attribute the one-time cold-
    epoch cost separately from steady-state H2D."""

    __slots__ = ("key", "n_vals", "vp", "pub_rows", "scheme", "_mtx",
                 "_dev")

    def __init__(self, key: bytes, pub_col: np.ndarray,
                 scheme: str = "ed25519"):
        v = pub_col.shape[0]
        # pad to a power of two (min 16) so the compiled-shape set stays
        # small: the kernels' shapes are keyed by vp, not the raw size
        vp = max(_next_pow2(v + 1), 16)
        rows = np.empty((vp, pub_col.shape[1]), dtype=np.uint8)
        rows[:v] = pub_col
        # padding rows: the scheme's trivial gather target — ed25519's
        # identity encoding, secp256k1's compressed generator (the secp
        # pad lane verifies a fixed signature under G; ops/mesh.py
        # _secp_pad_row), or bls12381's compressed G1 generator (the agg
        # pad commit is self-signed under sk=1; ops/bls_verify)
        rows[v:] = _pad_row_for(scheme)
        self.key = key
        self.n_vals = v
        self.vp = vp
        self.pub_rows = rows
        self.scheme = scheme
        self._mtx = _devcheck.lock("epoch.entry")
        self._dev: dict = {}

    # -- device layouts (device_put ONCE per layout, lock-protected) -----

    def xla_tables(self) -> Tuple:
        """((vp, 20) int32 limbs, (vp,) int32 sign) on device — gathered
        per batch by verify_kernel_cached. Limbs are packed on the host by
        the SAME _pack_le_limbs the uncached prep uses, so cached vs
        uncached kernel inputs are bit-identical by construction."""
        with self._mtx:
            t = self._dev.get("xla")
            if t is None:
                # device touch: table uploads run on the dispatch-owner
                # thread (lazy, inside the kernel closure) — assert it
                _devcheck.note_device_touch("epoch_cache.xla_tables")
                import jax

                from .backend import _pack_le_limbs

                limbs = _pack_le_limbs(self.pub_rows)
                sign = (self.pub_rows[:, 31] >> 7).astype(np.int32)
                with _span("pipeline.table_upload", layout="xla",
                           vp=self.vp):
                    t = (jax.device_put(limbs), jax.device_put(sign))
                self._dev["xla"] = t
            return t

    def coords_tables(self) -> Tuple:
        """((4*32, vp) int32 decompressed extended coords in the pallas
        32-row slot layout, (1, vp) int32 ok flags) on device. Decompression
        runs ON DEVICE, once per epoch, via the same traced field routines
        the kernels use (ops/pallas_verify._unpack_limbs / decompress) —
        K1's cached variants then skip the committee half of their
        decompression entirely."""
        with self._mtx:
            t = self._dev.get("coords")
            if t is None:
                _devcheck.note_device_touch("epoch_cache.coords_tables")
                import jax

                with _span("pipeline.table_upload", layout="coords",
                           vp=self.vp):
                    coords, ok = _coords_fn()(
                        np.ascontiguousarray(self.pub_rows.T)
                    )
                    # block until materialized so the first cached
                    # dispatch is not racing the table build
                    coords.block_until_ready()
                t = (coords, ok)
                self._dev["coords"] = t
            return t

    def sharded_xla_tables(self, mesh) -> Tuple:
        """The xla_tables layout REPLICATED over a jax device mesh
        (ISSUE 9 (b)): one resident copy per device, keyed inside this
        entry's layout dict by the mesh's device ids — so the epoch LRU
        owns the mesh replicas' lifetime exactly as it owns the
        single-device layouts (eviction drops them all), replacing the
        old module-level side cache in ops/sharded.py. Limbs are packed
        by the SAME _pack_le_limbs as the uncached prep, so mesh-cached
        vs single-device kernel inputs stay bit-identical."""
        key = ("xla_sharded", tuple(d.id for d in mesh.devices.flat))
        with self._mtx:
            t = self._dev.get(key)
            if t is None:
                # device touch: replication is an upload fanned across the
                # mesh — dispatch-owner thread only, like every layout
                _devcheck.note_device_touch("epoch_cache.sharded_tables")
                import jax
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as _P

                from .backend import _pack_le_limbs

                limbs = _pack_le_limbs(self.pub_rows)
                sign = (self.pub_rows[:, 31] >> 7).astype(np.int32)
                repl = NamedSharding(mesh, _P())
                with _span("pipeline.table_upload", layout="xla_sharded",
                           vp=self.vp):
                    t = (jax.device_put(limbs, repl),
                         jax.device_put(sign, repl))
                self._dev[key] = t
            return t

    def secp_tables(self) -> Tuple:
        """((vp, 20) int32 qx limbs, (vp, 20) int32 qy limbs, (vp,) bool
        ok) on device — the committee's DECOMPRESSED affine Q columns for
        the cached secp256k1 kernel (ops/secp_verify.verify_kernel_cached).
        Decompression (the per-key square root) runs once per epoch on
        the host (ops/secp_verify.table_columns, memoized per key); rows
        whose pubkey fails to decompress carry G with ok False, and every
        padding row is (G, True) — the pad lane's trivial-accept base."""
        with self._mtx:
            t = self._dev.get("secp")
            if t is None:
                _devcheck.note_device_touch("epoch_cache.secp_tables")
                import jax

                from . import secp_verify as _sv

                # table_columns appends ONE pad row itself; feed it the
                # first vp-1 rows (live keys + compressed-G padding) so
                # the device shape lands exactly on vp
                qx, qy, ok = _sv.table_columns(
                    [r.tobytes() for r in self.pub_rows[: self.vp - 1]]
                )
                with _span("pipeline.table_upload", layout="secp",
                           vp=self.vp):
                    t = (jax.device_put(qx), jax.device_put(qy),
                         jax.device_put(ok))
                self._dev["secp"] = t
            return t

    def bls_tables(self) -> Tuple:
        """((vp, 36) int32 gx limbs, (vp, 36) int32 gy limbs, (vp,) bool
        ok) on device — the committee's DECOMPRESSED affine G1 columns
        for the aggregation kernel's masked point-sum
        (ops/bls_verify.verify_kernel). Decompression (one Fp square root
        per key) runs once per epoch on the host; rows that fail to
        decompress or sit outside the G1 subgroup carry the generator
        with ok False, and every padding row is (G1, True) — the pad
        commit's sk=1 base."""
        with self._mtx:
            t = self._dev.get("bls")
            if t is None:
                _devcheck.note_device_touch("epoch_cache.bls_tables")
                import jax

                from . import bls_verify as _bv

                # table_columns_g1 appends ONE pad row itself; feed it
                # the first vp-1 rows so the device shape lands on vp
                gx, gy, ok = _bv.table_columns_g1(
                    [r.tobytes() for r in self.pub_rows[: self.vp - 1]]
                )
                with _span("pipeline.table_upload", layout="bls",
                           vp=self.vp):
                    t = (jax.device_put(gx), jax.device_put(gy),
                         jax.device_put(ok))
                self._dev["bls"] = t
            return t

    def nbytes_host(self) -> int:
        """Host bytes a FULL table upload ships (every layout the kernels
        consume) — the cold-epoch H2D cost the --transfer gate accounts."""
        if self.scheme == "secp256k1":
            # qx + qy limb tables + ok flags
            return self.vp * (2 * 20 * 4 + 1)
        if self.scheme == "bls12381":
            # gx + gy 36-limb tables + ok flags
            return self.vp * (2 * 36 * 4 + 1)
        # xla limbs+sign, pallas coords+ok
        return self.vp * (20 * 4 + 4) + self.vp * (4 * 32 * 4 + 4)


@functools.lru_cache(maxsize=1)
def _coords_fn():
    import jax
    import jax.numpy as jnp

    from . import pallas_verify as pv

    def build(a_t):  # (32, vp) uint8
        y, sign = pv._unpack_limbs(a_t.astype(jnp.int32))
        ok, pt = pv.decompress(y, sign)
        vp = a_t.shape[-1]
        pad = jnp.zeros((32 - pv.NL, vp), dtype=jnp.int32)
        coords = jnp.concatenate(
            [jnp.concatenate([pt[c], pad], axis=0) for c in range(4)], axis=0
        )
        return coords, ok.astype(jnp.int32)

    build.__name__ = "epoch_coords_table"
    return jax.jit(build)


class EpochCache:
    """LRU over recent validator-set epochs (thread-safe)."""

    def __init__(self, depth: int):
        self.depth = depth
        self._mtx = _devcheck.lock("epoch.lru")
        self._entries: "OrderedDict[bytes, EpochEntry]" = OrderedDict()

    def __len__(self) -> int:
        with self._mtx:
            return len(self._entries)

    def get(self, key: bytes) -> Optional[EpochEntry]:
        with self._mtx:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
            return e

    def note(self, key: bytes, pub_col: np.ndarray,
             scheme: str = "ed25519") -> Optional[EpochEntry]:
        """Warm lookup-or-register. Returns the entry when the epoch is
        WARM (seen before — counted as a hit); a cold epoch registers and
        returns None so the first commit rides the uncached path and the
        table upload never sits in a cold commit's critical path."""
        m = _ops()
        with self._mtx:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                m.epoch_cache_hits.inc()
                return e
            m.epoch_cache_misses.inc()
            self._entries[key] = EpochEntry(key, pub_col, scheme)
            while len(self._entries) > self.depth:
                self._entries.popitem(last=False)
                m.epoch_cache_evictions.inc()
        return None

    def clear(self) -> None:
        with self._mtx:
            self._entries.clear()


_ops_cached = None


def _ops():
    global _ops_cached
    if _ops_cached is None:
        from ..libs import metrics as _metrics

        _ops_cached = _metrics.ops_metrics()
    return _ops_cached


_cache: Optional[EpochCache] = None
_cache_mtx = _devcheck.lock("epoch.cache")


def _depth_from_env() -> int:
    env = os.environ.get("TM_TPU_EPOCH_CACHE")
    if env is not None:
        try:
            return max(int(env), 0)
        except ValueError:
            return 0
    # default: on for the TPU backend only — CPU/XLA runs opt in so test
    # suites do not compile cached-kernel shapes they never asked for
    from .engine import engine

    return DEFAULT_DEPTH if engine().on_tpu else 0


def cache() -> Optional[EpochCache]:
    """The process-wide cache, or None when disabled. Depth is read once;
    tests use reset(depth=...) to reconfigure."""
    global _cache
    with _cache_mtx:
        if _cache is None:
            _cache = EpochCache(_depth_from_env())
        return _cache if _cache.depth > 0 else None


def reset(depth: Optional[int] = None) -> None:
    """Drop every entry (and optionally reconfigure the depth) — test
    seam; production invalidation is the hash() keying itself."""
    global _cache
    with _cache_mtx:
        _cache = EpochCache(_depth_from_env() if depth is None else depth)


def note_valset(vals) -> Optional[bytes]:
    """Register/refresh `vals` in the cache; returns the epoch key iff the
    epoch is WARM and cacheable (single-scheme columns: all-ed25519 or
    all-secp256k1 — ISSUE 19). The key rides on the EntryBlock
    (`epoch_key`) so the prep stage can find the entry."""
    c = cache()
    if c is None:
        return None
    cols = vals.ed25519_columns()
    scheme = "ed25519"
    if cols is None:
        cols = vals.secp256k1_columns()
        scheme = "secp256k1"
    if cols is None:
        cols = vals.bls12381_columns()
        scheme = "bls12381"
    if cols is None:
        return None
    key = vals.hash()
    return key if c.note(key, cols[0], scheme) is not None else None


def stats() -> dict:
    """Snapshot of the cache state + the process-wide hit/miss/eviction
    counters (cumulative — callers diff two snapshots to attribute
    movement to a workload). Importable and callable without jax; the
    simnet harness embeds the delta in its run report so churn scenarios
    can assert the cache actually cycled cold→warm→evict."""
    m = _ops()
    c = cache()
    return {
        "enabled": c is not None,
        "depth": c.depth if c is not None else 0,
        "entries": len(c) if c is not None else 0,
        "hits": m.epoch_cache_hits.total(),
        "misses": m.epoch_cache_misses.total(),
        "evictions": m.epoch_cache_evictions.total(),
    }


def lookup(entries) -> Optional[EpochEntry]:
    """EntryBlock -> its epoch entry, or None (no key, evicted, or cache
    disabled). Evicted-between-submit-and-prep degrades to the uncached
    path — never an error."""
    key = getattr(entries, "epoch_key", None)
    if key is None or getattr(entries, "val_idx", None) is None:
        return None
    c = cache()
    if c is None:
        return None
    e = c.get(key)
    if e is not None and e.scheme != getattr(entries, "scheme", "ed25519"):
        # hash collision across schemes can't happen for one valset (a
        # set has one scheme), but a stale/mismatched key must degrade to
        # the uncached path, never feed the wrong kernel's tables
        return None
    return e
