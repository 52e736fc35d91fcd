"""Device-resident public-key tables that validator sets share.

The per-batch host cost is dominated by data that rarely changes between
heights: the validators' public keys were re-packed into limbs on the
host, re-shipped to the device and re-decompressed in kernel K1 for every
batch. A committee changes a key or two at a time (arxiv 2302.00418), so
the unit of caching is a TABLE OF PUBLIC-KEY ROWS, and a validator set is
a list of rows of one table:

  * the first set a table sees builds it (`EpochEntry`): its keys are
    rows 0..n-1, the rest is padding up to a power of two. An ed25519
    table is named by that set's `ValidatorSet.hash()` followed by a
    serial number no other table of the process gets — the `epoch_key`
    an EntryBlock carries, beside `val_idx`, the table rows of its lanes;
  * a set never seen before is looked up KEY BY KEY in the resident
    ed25519 tables, most recently used first (`EpochEntry.map_rows`).
    Where at least half its keys are rows of a table and the rest fit
    the table's free padding rows, the set maps onto that table: the
    missing keys are appended to the host snapshot and placed on the
    device, decompressed on the host, the next time a launch asks for
    the table (`pipeline.table_patch`). A change of voting power or of order
    appends nothing. Why half: a patch decompresses the new keys only,
    a build all `vp` rows behind a commit that rode the uncached kernel;
    and a set that shares less than half with a table is the start of
    another committee, whose successors will share with IT (argued, not
    measured: no cell changes more than one key a height, PERF.md §7);
  * a set that shares too little, or a table with too few free rows,
    takes the cold path: the commit rides the uncached kernels (no key
    attached) and a fresh table is registered behind it, built on its
    first use.

Every set the cache has mapped is remembered by its hash (up to
SETS_PER_TABLE a table), so a set seen again costs one dictionary
look-up: `epoch_cache_hits` / `_misses` count set hashes seen / not
seen, `epoch_tables_shared` the misses that mapped onto a resident
table, `epoch_rows_patched` the keys they appended, `epoch_tables_built`
the cold builds. secp256k1 and bls12381 tables stay one per set hash.

Device layouts of a table:

    xla_tables()    (vp, 20) int32 limb rows + (vp,) sign bits — the
                    per-sig XLA kernel gathers A rows on device
                    (ops/ed25519_verify.verify_kernel_cached)
    coords_tables() (4*32, vp) int32 decompressed extended coordinates in
                    the pallas 32-row slot layout + (1, vp) ok flags —
                    K1 then decompresses M points (R only) instead of 2M
                    (ops/pallas_verify, ops/pallas_rlc cached kernels)

Gather index `vp - 1` is the padding lane's identity row and is never
given to a key. A device array is never written: a patch makes a NEW
array value (the old one plus the new rows), so a launch in flight keeps
gathering from the value it was given.

A NAME MEANS ONE CONTENT. Whoever holds (epoch_key, val_idx) — a block
in flight, vote ingress for the length of a height — finds the table by
name alone, so what a name can lead to must never change under it:
within one table a row, once given to a key, keeps it (rows are only
appended), and a table built again after its eviction is ANOTHER table
under another name (the serial), although the same set builds it: its
later rows would follow from the sets mapped since, not from the name.
The stale name finds nothing and the block rides the uncached path with
the public keys it carries. A free row of an ed25519 table holds an
encoding that is no point of the curve (`_FREE_ENC`), not the identity:
the identity verifies R = [s]B for anyone (ZIP-215 accepts it as a key),
so a gather that strayed there must reject its lane. secp256k1 and
bls12381 tables are one a set hash and named by it: there the hash is
the content.

Upload discipline: the device arrays are materialized and patched
LAZILY, on first use by the kernel closure — which runs on the
pipeline's single dispatch-owner thread (exactly one thread may touch
the device). Mapping a set is host work on the caller's thread.

Enablement: TM_TPU_EPOCH_CACHE=N sets the LRU depth in tables (0
disables). Unset, the cache is on (depth 8) for the TPU backend and off
elsewhere — CPU/XLA test runs opt in explicitly so they do not compile
extra kernel shapes. Importable without jax (the types layer notes
epochs at verify time; the first note resolves ops/engine.py, which is
what loads jax).
"""

from __future__ import annotations

import functools
import itertools
import os
import threading
from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np

from ..libs import devcheck as _devcheck
from ..observability import trace as _trace

_span = _trace.span

DEFAULT_DEPTH = 8
SETS_PER_TABLE = 64   # set hashes a table remembers: power-only changes
#                       make new ones without end
MIN_PATCH_ROWS = 8    # a patch places at least this many rows (one shape)

_IDENT_ENC = np.zeros(32, dtype=np.uint8)
_IDENT_ENC[0] = 1  # y = 1: the identity point's wire encoding
_FREE_ENC = np.zeros(32, dtype=np.uint8)
_FREE_ENC[0] = 2   # y = 2 is on no point of the curve: every layout's
#                    decompression gives ok = 0 (tests/test_epoch_tables)

_table_serial = itertools.count()   # one for the process: reset() keeps it


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
    """One device-resident table of public-key rows, and the validator
    sets that are lists of its rows.

    `pub_rows` is the (vp, 32) HOST snapshot — rows [0, n_rows) hold keys
    (the first set's, then every key a later set appended), row vp-1 the
    scheme's padding row, and so do the rows between, except in an
    ed25519 table, whose free rows reject (`_FREE_ENC`) — from which
    every device layout derives.
    Layouts materialize lazily (one upload) and are patched lazily (the
    rows appended since) under the entry lock; a patch binds a new array
    value, so whoever holds the old one keeps a consistent table.

    Donation exemption (ISSUE 7): these device arrays persist across
    batches, so every cached kernel's donate_argnums EXCLUDES the table
    arguments — a donated launch consumes only its per-batch buffers.
    Uploads and patches are span-traced (`pipeline.table_upload`,
    `pipeline.table_patch`) so the overlapped dispatcher's transfer
    accounting can attribute them separately from steady-state H2D."""

    __slots__ = ("key", "n_vals", "vp", "pub_rows", "scheme", "_mtx",
                 "_dev", "n_rows", "_rows_mtx", "_row_of", "set_keys")

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
        if scheme == "ed25519":
            # only ed25519 sets map onto a table: a row that waits for a
            # key must not verify for whoever gathers it before
            rows[v: vp - 1] = _FREE_ENC
        self.key = key
        self.n_vals = v          # the first set's size
        self.vp = vp
        self.pub_rows = rows
        self.scheme = scheme
        self._mtx = _devcheck.lock("epoch.entry")
        self._dev: dict = {}     # layout -> (rows it holds, device arrays)
        self.n_rows = v          # rows that hold keys; row vp-1 never does
        self._rows_mtx = _devcheck.lock("epoch.rows")
        self._row_of: Optional[dict] = None   # key bytes -> row, on demand
        self.set_keys: list = []  # set hashes the cache maps onto this table

    # -- the rows of a set (host, any thread) -----------------------------

    def map_rows(self, pub_col: np.ndarray
                 ) -> Optional[Tuple[Optional[np.ndarray], int]]:
        """The table rows that hold `pub_col`'s keys, in its order, and how
        many of them were appended just now into free rows; rows None when
        they are 0..n-1 (the set gathers as the table's first one does).
        None when fewer than half the keys are rows already or the others
        do not fit: the caller builds a fresh table."""
        n = pub_col.shape[0]
        raw = np.ascontiguousarray(pub_col).tobytes()
        w = self.pub_rows.shape[1]
        with self._rows_mtx:
            row_of = self._row_of
            if row_of is None:
                have = self.pub_rows[: self.n_rows].tobytes()
                row_of = self._row_of = {
                    have[w * r: w * r + w]: r for r in range(self.n_rows)}
            room = min(self.vp - 1 - self.n_rows, n // 2)
            rows = np.empty(n, dtype=np.int32)
            missing = []
            for i in range(n):
                r = row_of.get(raw[w * i: w * i + w])
                if r is None:
                    if len(missing) == room:
                        return None
                    missing.append(i)
                    r = -1
                rows[i] = r
            for i in missing:
                k = raw[w * i: w * i + w]
                r = row_of.get(k)      # the same new key twice in one set
                if r is None:
                    r = row_of[k] = self.n_rows
                    self.pub_rows[r] = pub_col[i]
                    self.n_rows = r + 1   # after the row is written
                rows[i] = r
        if (rows == np.arange(n, dtype=np.int32)).all():
            rows = None
        return rows, len(missing)

    def _snapshot(self):
        """(rows in use, a copy of the host rows) at one instant."""
        with self._rows_mtx:
            return self.n_rows, self.pub_rows.copy()

    def _lacking(self, layout: str):
        """What a device layout lacks (entry lock held): (its arrays,
        None) when they hold every row in use, else (its arrays or None,
        (rows in use now, idx, keys)): with no arrays yet, the whole
        table (idx None, keys (vp, w)); else the rows appended since as a
        block to scatter — idx (k,) int32 table rows and their keys (k,
        w), k a power of two of at least MIN_PATCH_ROWS so that a patch
        has few shapes, filled up with the pad row vp-1 (rewritten with
        what it holds)."""
        have, t = self._dev.get(layout, (0, None))
        if t is not None and have >= self.n_rows:
            return t, None
        if t is None:
            n, rows = self._snapshot()
            return t, (n, None, rows)
        with self._rows_mtx:
            n = self.n_rows
            k = max(_next_pow2(n - have), MIN_PATCH_ROWS)
            idx = np.full(k, self.vp - 1, dtype=np.int32)
            idx[: n - have] = np.arange(have, n, dtype=np.int32)
            return t, (n, idx, self.pub_rows[idx])

    # -- device layouts (one upload a layout, then patches; lock-protected)

    def xla_tables(self) -> Tuple:
        """((vp, 20) int32 limbs, (vp,) int32 sign) on device — gathered
        per batch by verify_kernel_cached. Limbs are packed on the host by
        the SAME _pack_le_limbs the uncached prep uses, so cached vs
        uncached kernel inputs are bit-identical by construction."""
        with self._mtx:
            t, lack = self._lacking("xla")
            if lack is None:
                return t
            n, idx, keys = lack
            # device touch: table uploads run on the dispatch-owner
            # thread (lazy, inside the kernel closure) — assert it
            _devcheck.note_device_touch("epoch_cache.xla_tables")
            import jax

            from .backend import _pack_le_limbs

            limbs = _pack_le_limbs(keys)
            sign = (keys[:, 31] >> 7).astype(np.int32)
            if t is None:
                with _span("pipeline.table_upload", layout="xla",
                           vp=self.vp):
                    t = (jax.device_put(limbs), jax.device_put(sign))
            else:
                with _span("pipeline.table_patch", layout="xla",
                           rows=n - self._dev["xla"][0],
                           bytes=limbs.nbytes + sign.nbytes + idx.nbytes):
                    t = _xla_patch_fn()(t[0], t[1], idx, limbs, sign)
            self._dev["xla"] = (n, t)
            return t

    def coords_tables(self) -> Tuple:
        """((4*32, vp) int32 decompressed extended coords in the pallas
        32-row slot layout, (1, vp) int32 ok flags) on device: K1's cached
        variants skip the committee half of their decompression entirely.
        The whole table is decompressed ON DEVICE, once, via the same
        traced field routines the kernels use (ops/pallas_verify
        ._unpack_limbs / decompress). Keys appended since are decompressed
        on the HOST (crypto/_edwards.py, a modular exponentiation a key)
        and scattered into new array values from ONE packed buffer: as an
        XLA op graph the exponentiation is a chain of small loops that
        cost a launch 0.74 ms of device time and the dispatcher three
        host-to-device operations (PERF.md §6, PR 32)."""
        with self._mtx:
            t, lack = self._lacking("coords")
            if lack is None:
                return t
            n, idx, keys = lack
            _devcheck.note_device_touch("epoch_cache.coords_tables")
            if t is None:
                with _span("pipeline.table_upload", layout="coords",
                           vp=self.vp):
                    coords, ok = _coords_fn()(np.ascontiguousarray(keys.T))
                    # block until materialized so the first cached
                    # dispatch is not racing the table build
                    coords.block_until_ready()
                t = (coords, ok)
            else:
                packed = np.concatenate(
                    [idx, *coords_columns(keys, n - self._dev["coords"][0])])
                with _span("pipeline.table_patch", layout="coords",
                           rows=n - self._dev["coords"][0],
                           bytes=packed.nbytes):
                    t = _coords_patch_fn()(t[0], t[1], packed)
            self._dev["coords"] = (n, t)
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
            have, t = self._dev.get(key, (0, None))
            if t is None or have < self.n_rows:
                # device touch: replication is an upload fanned across the
                # mesh — dispatch-owner thread only, like every layout.
                # Rows appended since are placed by uploading the table
                # again (no cell runs the mesh on a churning chain yet)
                _devcheck.note_device_touch("epoch_cache.sharded_tables")
                import jax
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as _P

                from .backend import _pack_le_limbs

                n, rows = self._snapshot()
                limbs = _pack_le_limbs(rows)
                sign = (rows[:, 31] >> 7).astype(np.int32)
                repl = NamedSharding(mesh, _P())
                with _span("pipeline.table_upload", layout="xla_sharded",
                           vp=self.vp):
                    t = (jax.device_put(limbs, repl),
                         jax.device_put(sign, repl))
                self._dev[key] = (n, t)
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


_POINT_ROWS = 4 * 32   # X, Y, Z, T in 32-row slots (ops/pallas_verify)


def coords_columns(keys: np.ndarray, live: int) -> Tuple[np.ndarray,
                                                         np.ndarray]:
    """Host twin of _coords_fn for a block of k ed25519 keys of which the
    first `live` are real (the rest are identity pad rows): (ok (k,) int32,
    coords (4*32*k,) int32 — the (4*32, k) column block, row-major), by
    crypto/_edwards.decompress in ZIP-215 mode, which is the device
    routine's rule (y taken mod p, "negative zero" accepted). Limbs are
    canonical (13 bits, value < p): inside every bound the kernels'
    field arithmetic asks of its inputs. jax-free."""
    from ..crypto import _edwards

    k = keys.shape[0]
    ok = np.ones(k, dtype=np.int32)
    cols = np.zeros((_POINT_ROWS, k), dtype=np.int32)
    cols[32, :] = cols[64, :] = 1          # the identity: (0, 1, 1, 0)
    for j in range(live):
        pt = _edwards.decompress(keys[j].tobytes())
        if pt is None:
            ok[j] = 0       # K1 rejects the lane; the coords stay a point
            continue
        for c, v in enumerate(pt):
            v %= _edwards.P
            cols[32 * c: 32 * c + 20, j] = [
                (v >> (13 * i)) & 0x1FFF for i in range(20)]
    return ok, cols.reshape(-1)


@functools.lru_cache(maxsize=1)
def _coords_patch_fn():
    """(coords table, ok table, packed int32 [idx (k) | ok (k) | coords
    (4*32*k)]) -> the tables with columns idx set: NEW array values, the
    arguments are left as they were (nothing is donated)."""
    import jax

    def patch(coords_tbl, ok_tbl, packed):
        k = packed.shape[0] // (_POINT_ROWS + 2)
        idx, ok = packed[:k], packed[k:2 * k]
        cols = packed[2 * k:].reshape(_POINT_ROWS, k)
        return (coords_tbl.at[:, idx].set(cols),
                ok_tbl.at[:, idx].set(ok[None, :]))

    patch.__name__ = "epoch_coords_patch"
    return jax.jit(patch)


@functools.lru_cache(maxsize=1)
def _xla_patch_fn():
    """The same for the XLA layout: rows idx of the limb and sign tables
    set to the given rows."""
    import jax

    def patch(limbs_tbl, sign_tbl, idx, limbs, sign):
        return limbs_tbl.at[idx].set(limbs), sign_tbl.at[idx].set(sign)

    patch.__name__ = "epoch_xla_patch"
    return jax.jit(patch)


class EpochCache:
    """LRU over the resident tables, and the set hashes mapped onto each
    (thread-safe). `depth` counts tables."""

    def __init__(self, depth: int):
        self.depth = depth
        self._mtx = _devcheck.lock("epoch.lru")
        self._entries: "OrderedDict[bytes, EpochEntry]" = OrderedDict()
        # set hash -> (its table, its rows there or None for 0..n-1)
        self._by_set: dict = {}

    def __len__(self) -> int:
        with self._mtx:
            return len(self._entries)

    def get(self, key: bytes) -> Optional[EpochEntry]:
        """The table named `key` (an EntryBlock's epoch_key)."""
        with self._mtx:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
            return e

    def _remember(self, e: EpochEntry, key: bytes, rows) -> None:
        if key in self._by_set:     # two callers mapped the same set
            return
        self._by_set[key] = (e, rows)
        e.set_keys.append(key)
        if len(e.set_keys) > SETS_PER_TABLE:
            self._by_set.pop(e.set_keys.pop(0), None)

    def note(self, key: bytes, pub_col: np.ndarray, scheme: str = "ed25519"
             ) -> Optional[Tuple[EpochEntry, Optional[np.ndarray]]]:
        """Look-up, map or register the set hashed `key`. Returns (table,
        rows) when the set gathers from a resident table — seen before (a
        hit), or mapped onto one just now; rows None means 0..n-1. A set
        no table can take registers a fresh one and returns None, so that
        its first commit rides the uncached path and the table's build
        never sits in a cold commit's critical path. The look-up of a set
        never seen, key by key, runs OUTSIDE the cache's lock (a table's
        rows have their own): other callers' hits do not wait for it."""
        m = _ops()
        with self._mtx:
            hit = self._by_set.get(key)
            if hit is not None:
                self._entries.move_to_end(hit[0].key)
                m.epoch_cache_hits.inc()
                return hit
            m.epoch_cache_misses.inc()
            tables = [e for e in reversed(self._entries.values())
                      if e.scheme == scheme] if scheme == "ed25519" else ()
        if tables:
            with _span("epoch.map_set") as sp:
                for e in tables:
                    mapped = e.map_rows(pub_col)
                    if mapped is None:
                        continue
                    rows, new = mapped
                    with self._mtx:
                        if self._entries.get(e.key) is not e:
                            break       # evicted meanwhile: build
                        self._entries.move_to_end(e.key)
                        self._remember(e, key, rows)
                    sp.note(shared=pub_col.shape[0] - new, new=new,
                            table=e.key.hex()[:16])
                    m.epoch_tables_shared.inc()
                    m.epoch_rows_patched.inc(new)
                    return e, rows
        name = key
        if scheme == "ed25519":
            name += next(_table_serial).to_bytes(8, "big")
        e = EpochEntry(name, pub_col, scheme)
        with self._mtx:
            hit = self._by_set.get(key)
            if hit is not None:         # another caller built it meanwhile
                return hit
            self._entries[name] = e
            self._remember(e, key, None)
            m.epoch_tables_built.inc()
            while len(self._entries) > self.depth:
                _k, old = self._entries.popitem(last=False)
                for k in old.set_keys:
                    self._by_set.pop(k, None)
                m.epoch_cache_evictions.inc()
        return None

    def clear(self) -> None:
        with self._mtx:
            self._entries.clear()
            self._by_set.clear()


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
    """Drop every table (and optionally reconfigure the depth) — test
    seam; in production a table ages out of the LRU."""
    global _cache
    with _cache_mtx:
        _cache = EpochCache(_depth_from_env() if depth is None else depth)


def _note(vals):
    """vals -> (table, rows) as EpochCache.note gives them, or None: cold,
    cache off, or a set that is not single-scheme columnar."""
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
    return c.note(vals.hash(), cols[0], scheme)


def table_rows(vals, rows: np.ndarray) -> Tuple[Optional[bytes], np.ndarray]:
    """Register/refresh `vals`; (epoch_key, val_idx) for lanes that are
    rows `rows` of the SET: the table it gathers from and their rows
    THERE, which an EntryBlock carries. (None, rows) when the set is cold
    or not cacheable — the block then ships its public keys."""
    got = _note(vals)
    if got is None:
        return None, rows
    e, at = got
    return e.key, rows if at is None else at[rows]


def note_valset(vals) -> Optional[bytes]:
    """Register/refresh `vals`; returns its table's key iff the set is
    WARM, cacheable (single-scheme columns — ISSUE 19) and gathers by its
    own row numbers, so that a caller who attaches `val_idx` = set rows
    stays right. A set mapped onto another set's table answers None here
    (it would need `table_rows`): such callers ride the uncached path."""
    got = _note(vals)
    return got[0].key if got is not None and got[1] is None else None


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
        "tables_shared": m.epoch_tables_shared.total(),
        "rows_patched": m.epoch_rows_patched.total(),
        "tables_built": m.epoch_tables_built.total(),
    }


def lookup(entries) -> Optional[EpochEntry]:
    """EntryBlock -> its epoch entry, or None (no key, evicted, or cache
    disabled). Evicted-between-submit-and-prep degrades to the uncached
    path — never an error; so does a table built again since, which has
    another name (module docstring)."""
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
