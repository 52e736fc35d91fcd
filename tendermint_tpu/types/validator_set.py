"""Validator and ValidatorSet with exact proposer-priority rotation.

Reference parity: types/validator.go, types/validator_set.go. Every integer
operation mirrors the Go int64 semantics (safeAddClip/safeSubClip clipping,
floor-vs-truncated division differences are respected: Go's `/` truncates
toward zero; Python's `//` floors — use _go_div for signed divisions).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Iterable, List, Optional, Sequence, Tuple

from ..crypto import PubKey, merkle
from ..crypto.encoding import pubkey_from_proto, pubkey_to_proto
from ..wire.proto import ProtoWriter, decode_message, field_bytes, field_int, field_repeated_bytes, to_signed64

INT64_MAX = (1 << 63) - 1
INT64_MIN = -(1 << 63)

MAX_TOTAL_VOTING_POWER = INT64_MAX // 8  # validator_set.go:25
PRIORITY_WINDOW_SIZE_FACTOR = 2  # validator_set.go:30

# ed25519_columns cache sentinel: "computed, not columnar-representable"
_NO_ED_COLS = object()

# secp256k1_columns cache sentinel (same protocol)
_NO_SECP_COLS = object()

# bls12381_columns cache sentinel (same protocol)
_NO_BLS_COLS = object()

# sr25519_columns cache sentinel (same protocol)
_NO_SR_COLS = object()


def _clip64(v: int) -> int:
    return max(INT64_MIN, min(INT64_MAX, v))


def safe_add_clip(a: int, b: int) -> int:
    return _clip64(a + b)


def safe_sub_clip(a: int, b: int) -> int:
    return _clip64(a - b)


def safe_mul(a: int, b: int) -> Tuple[int, bool]:
    v = a * b
    if v > INT64_MAX or v < INT64_MIN:
        return 0, True
    return v, False


def _go_div(a: int, b: int) -> int:
    """Go's truncated integer division (Python // floors)."""
    q = abs(a) // abs(b)
    return -q if (a < 0) != (b < 0) else q


@dataclass
class Validator:
    """types/validator.go:20-33."""

    address: bytes
    pub_key: PubKey
    voting_power: int
    proposer_priority: int = 0

    @classmethod
    def new(cls, pub_key: PubKey, voting_power: int) -> "Validator":
        return cls(
            address=pub_key.address(),
            pub_key=pub_key,
            voting_power=voting_power,
            proposer_priority=0,
        )

    def copy(self) -> "Validator":
        return Validator(self.address, self.pub_key, self.voting_power, self.proposer_priority)

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != 20:
            raise ValueError("validator address is the wrong size")

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """validator.go:63-83: higher priority wins, ties to lower address."""
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare identical validators")

    def bytes(self) -> bytes:
        """SimpleValidator proto (validator.go:116-132) — the ValidatorSet
        hash leaf: 1 pub_key(msg) 2 voting_power(varint)."""
        w = ProtoWriter()
        w.write_message(1, pubkey_to_proto(self.pub_key), always=True)
        w.write_varint(2, self.voting_power)
        return w.bytes()

    def encode(self) -> bytes:
        """Full Validator proto (validator.pb.go:88-91)."""
        w = ProtoWriter()
        w.write_bytes(1, self.address)
        w.write_message(2, pubkey_to_proto(self.pub_key), always=True)
        w.write_varint(3, self.voting_power)
        w.write_varint(4, self.proposer_priority)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "Validator":
        f = decode_message(data)
        return cls(
            address=field_bytes(f, 1),
            pub_key=pubkey_from_proto(field_bytes(f, 2)),
            voting_power=to_signed64(field_int(f, 3)),
            proposer_priority=to_signed64(field_int(f, 4)),
        )


def _sort_by_voting_power(vals: List[Validator]) -> None:
    """ValidatorsByVotingPower: descending power, ties by ascending address."""
    vals.sort(key=lambda v: (-v.voting_power, v.address))


def _sort_by_address(vals: List[Validator]) -> None:
    vals.sort(key=lambda v: v.address)


class ValidatorSet:
    """types/validator_set.go:51-60."""

    def __init__(self, validators: Optional[List[Validator]] = None, proposer: Optional[Validator] = None):
        self.validators: List[Validator] = validators if validators is not None else []
        self.proposer: Optional[Validator] = proposer
        self._total_voting_power: int = 0
        self._hash: Optional[bytes] = None
        self._ed_cols: Optional[tuple] = None
        self._secp_cols: Optional[tuple] = None
        self._bls_cols: Optional[tuple] = None
        self._sr_cols: Optional[tuple] = None

    # ---- construction -------------------------------------------------

    @classmethod
    def new(cls, valz: Sequence[Validator]) -> "ValidatorSet":
        """NewValidatorSet (validator_set.go:70-81). Raises on invalid."""
        vals = cls()
        vals._update_with_change_set([v.copy() for v in valz], allow_deletes=False)
        if valz:
            vals.increment_proposer_priority(1)
        return vals

    @classmethod
    def from_existing(cls, valz: List[Validator]) -> "ValidatorSet":
        """ValidatorSetFromExistingValidators (validator_set.go:858-879):
        rebuild without touching priorities; recover previous proposer."""
        if not valz:
            raise ValueError("validator set is empty")
        for v in valz:
            v.validate_basic()
        vals = cls(validators=valz)
        vals.proposer = vals._find_previous_proposer()
        vals._update_total_voting_power()
        _sort_by_voting_power(vals.validators)
        return vals

    def copy(self) -> "ValidatorSet":
        c = ValidatorSet(
            validators=[v.copy() for v in self.validators],
            proposer=self.proposer,
        )
        c._total_voting_power = self._total_voting_power
        # the hash and ed25519 columns cover (pub_key, power) only, which
        # copy preserves — sharing both caches keeps a copied set on the
        # same device epoch (ops/epoch_cache.py keys on hash())
        c._hash = self._hash
        c._ed_cols = self._ed_cols
        c._secp_cols = self._secp_cols
        c._bls_cols = self._bls_cols
        c._sr_cols = self._sr_cols
        return c

    # ---- queries ------------------------------------------------------

    def is_nil_or_empty(self) -> bool:
        return not self.validators

    def size(self) -> int:
        return len(self.validators)

    def __len__(self) -> int:
        return len(self.validators)

    def has_address(self, address: bytes) -> bool:
        return any(v.address == address for v in self.validators)

    def get_by_address(self, address: bytes) -> Tuple[int, Optional[Validator]]:
        for i, v in enumerate(self.validators):
            if v.address == address:
                return i, v.copy()
        return -1, None

    def get_by_index(self, index: int) -> Tuple[Optional[bytes], Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return None, None
        v = self.validators[index]
        return v.address, v.copy()

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        s = 0
        for v in self.validators:
            s = safe_add_clip(s, v.voting_power)
            if s > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    f"total voting power exceeds max {MAX_TOTAL_VOTING_POWER}: {s}"
                )
        self._total_voting_power = s

    def get_proposer(self) -> Optional[Validator]:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer: Optional[Validator] = None
        for v in self.validators:
            if proposer is None:
                proposer = v
            elif v.address != proposer.address:
                proposer = proposer.compare_proposer_priority(v)
        return proposer

    def _find_previous_proposer(self) -> Optional[Validator]:
        """validator_set.go:680-692: lowest priority = previous proposer."""
        prev: Optional[Validator] = None
        for v in self.validators:
            if prev is None:
                prev = v
                continue
            if prev is prev.compare_proposer_priority(v):
                prev = v
        return prev

    def hash(self) -> bytes:
        # Cached: the hash covers (pub_key, voting_power) only — proposer-
        # priority churn does not touch it — and membership/power changes
        # all flow through _update_with_change_set, which invalidates.
        # (Header sync hashes the same set once per header; the recompute
        # was 76% of the pipelined-header host cost at 128 validators.)
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [v.bytes() for v in self.validators]
            )
        return self._hash

    def _key_columns(self, attr: str, none, pub_cls, width: int):
        """The (pub (n, width) uint8, power (n,) int64) columns of a set
        whose EVERY key is a `pub_cls`, else None; kept in `attr` (`none`
        kept there means "computed, not representable"). Cleared with the
        hash cache by _update_with_change_set, shared by copy()."""
        cols = getattr(self, attr)
        if cols is not None:
            return cols if cols is not none else None
        import numpy as np

        vals = self.validators
        n = len(vals)
        if n and all(isinstance(v.pub_key, pub_cls) for v in vals):
            pub_b = b"".join(v.pub_key.bytes() for v in vals)
            if len(pub_b) == width * n:
                cols = (
                    np.frombuffer(pub_b, dtype=np.uint8).reshape(n, width),
                    np.fromiter(
                        (v.voting_power for v in vals),
                        dtype=np.int64,
                        count=n,
                    ),
                )
        setattr(self, attr, cols if cols is not None else none)
        return cols

    def ed25519_columns(self) -> Optional[tuple]:
        """(pub (n, 32) uint8, power (n,) int64) columns over the set, or
        None unless EVERY validator key is ed25519 — the commit verify
        fast path (types/validation.py fused branch) gathers selected
        lanes from these instead of walking Validator objects per
        signature. A None result also serves as the per-key TYPE check: a
        mixed-key set falls back to the object path, which raises exactly
        as per-entry add() did."""
        from ..crypto import ed25519 as _ed25519

        return self._key_columns("_ed_cols", _NO_ED_COLS, _ed25519.PubKey, 32)

    def sr25519_columns(self) -> Optional[tuple]:
        """ed25519_columns for a set whose every key is sr25519: the same
        32-byte key column, which the fused commit prep gathers from as it
        does an ed25519 set's (types/validation.py)."""
        from ..crypto import sr25519 as _sr25519

        return self._key_columns("_sr_cols", _NO_SR_COLS, _sr25519.PubKey, 32)

    def secp256k1_columns(self) -> Optional[tuple]:
        """(pub (n, 33) uint8, power (n,) int64) columns over the set, or
        None unless EVERY validator key is secp256k1 — the scheme-lane
        analog of ed25519_columns (ISSUE 19): the batched commit prep
        gathers selected 33-byte SEC1 keys from here and the epoch cache
        keys its decompressed affine Q table on the same hash(). A None
        result is the TYPE check: mixed or non-secp sets fall back to the
        object path."""
        from ..crypto import secp256k1 as _secp

        return self._key_columns("_secp_cols", _NO_SECP_COLS, _secp.PubKey, 33)

    def bls12381_columns(self) -> Optional[tuple]:
        """(pub (n, 48) uint8, power (n,) int64) columns over the set, or
        None unless EVERY validator key is bls12381 — the aggregation
        lane's committee snapshot (ISSUE 20): prepare_aggregated_commit
        carries these compressed G1 rows on the AggBlock and the epoch
        cache keys its decompressed G1 limb table on the same hash()."""
        from ..crypto import bls12381 as _bls

        return self._key_columns("_bls_cols", _NO_BLS_COLS, _bls.PubKey, 48)

    def scheme_rows(self) -> Optional[tuple]:
        """Per-validator scheme partition for MIXED device-batchable sets
        (ISSUE 19 tentpole c): (kinds (n,) uint8 — 0 = ed25519, 1 =
        secp256k1, pub (n, 32) uint8, aux (n,) uint8). For ed25519 rows
        `pub` is the key and aux is 0; for secp256k1 rows `pub` is X and
        aux the SEC1 prefix — exactly EntryBlock's (pub, pub_aux) split,
        so the commit prep gathers per-scheme blocks without touching
        Validator objects. None when any key is neither scheme (those
        sets stay on the object path). Not cached separately: derives
        from the per-scheme columns when the set is pure, else builds
        once per call (mixed sets are the rare shape; the gather itself
        is what the hot path repeats)."""
        import numpy as np

        from ..crypto import ed25519 as _ed25519
        from ..crypto import secp256k1 as _secp

        vals = self.validators
        n = len(vals)
        if not n:
            return None
        kinds = np.zeros(n, dtype=np.uint8)
        pub = np.zeros((n, 32), dtype=np.uint8)
        aux = np.zeros(n, dtype=np.uint8)
        for i, v in enumerate(vals):
            k = v.pub_key
            if isinstance(k, _ed25519.PubKey):
                pub[i] = np.frombuffer(k.bytes(), dtype=np.uint8)
            elif isinstance(k, _secp.PubKey):
                kinds[i] = 1
                b = k.bytes()
                aux[i] = b[0]
                pub[i] = np.frombuffer(b, dtype=np.uint8)[1:]
            else:
                return None
        return kinds, pub, aux

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for i, v in enumerate(self.validators):
            try:
                v.validate_basic()
            except ValueError as e:
                raise ValueError(f"invalid validator #{i}: {e}") from e
        if self.proposer is None:
            raise ValueError("proposer failed validate basic: nil")
        self.proposer.validate_basic()

    # ---- proposer rotation (consensus-critical integer math) ----------

    def increment_proposer_priority(self, times: int) -> None:
        """validator_set.go:115-138."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def rescale_priorities(self, diff_max: int) -> None:
        """validator_set.go:143-165: divide priorities by ceil(diff/diffMax)."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        diff = self._compute_max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max  # both nonneg: floor==trunc
        if diff > diff_max:
            for v in self.validators:
                v.proposer_priority = _go_div(v.proposer_priority, ratio)

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = safe_add_clip(v.proposer_priority, v.voting_power)
        mostest = None
        for v in self.validators:
            mostest = v if mostest is None else mostest.compare_proposer_priority(v)
        mostest.proposer_priority = safe_sub_clip(
            mostest.proposer_priority, self.total_voting_power()
        )
        return mostest

    def _compute_avg_proposer_priority(self) -> int:
        # validator_set.go:181-195 uses big.Int.Div — Euclidean division,
        # which floors for a positive divisor: exactly Python's //.
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        return total // n

    def _compute_max_min_priority_diff(self) -> int:
        mx = max(v.proposer_priority for v in self.validators)
        mn = min(v.proposer_priority for v in self.validators)
        d = mx - mn
        return -d if d < 0 else d

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = safe_sub_clip(v.proposer_priority, avg)

    # ---- updates (validator_set.go:365-655) ---------------------------

    def update_with_change_set(self, changes: Sequence[Validator]) -> None:
        self._update_with_change_set([v.copy() for v in changes], allow_deletes=True)

    def _update_with_change_set(self, changes: List[Validator], allow_deletes: bool) -> None:
        self._hash = None  # membership/power may change below
        self._ed_cols = None
        self._secp_cols = None
        self._bls_cols = None
        self._sr_cols = None
        if not changes:
            return
        updates, deletes = _process_changes(changes)
        if not allow_deletes and deletes:
            raise ValueError(f"cannot process validators with voting power 0: {deletes}")
        if _num_new_validators(updates, self) == 0 and len(self.validators) == len(deletes):
            raise ValueError("applying the validator changes would result in empty set")
        removed_power = _verify_removals(deletes, self)
        tvp_after_updates_before_removals = _verify_updates(updates, self, removed_power)
        _compute_new_priorities(updates, self, tvp_after_updates_before_removals)
        self._apply_updates(updates)
        self._apply_removals(deletes)
        self._update_total_voting_power()
        self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        _sort_by_voting_power(self.validators)

    def _apply_updates(self, updates: List[Validator]) -> None:
        existing = list(self.validators)
        _sort_by_address(existing)
        merged: List[Validator] = []
        i = j = 0
        while i < len(existing) and j < len(updates):
            if existing[i].address < updates[j].address:
                merged.append(existing[i])
                i += 1
            else:
                merged.append(updates[j])
                if existing[i].address == updates[j].address:
                    i += 1
                j += 1
        merged.extend(existing[i:])
        merged.extend(updates[j:])
        self.validators = merged

    def _apply_removals(self, deletes: List[Validator]) -> None:
        existing = list(self.validators)
        merged: List[Validator] = []
        di = 0
        for v in existing:
            if di < len(deletes) and v.address == deletes[di].address:
                di += 1
            else:
                merged.append(v)
        self.validators = merged

    # ---- commit verification façade -----------------------------------

    def verify_commit(self, chain_id: str, block_id, height: int, commit) -> None:
        from . import validation

        validation.verify_commit(chain_id, self, block_id, height, commit)

    def verify_commit_light(self, chain_id: str, block_id, height: int, commit) -> None:
        from . import validation

        validation.verify_commit_light(chain_id, self, block_id, height, commit)

    def verify_commit_light_trusting(self, chain_id: str, commit, trust_level) -> None:
        from . import validation

        validation.verify_commit_light_trusting(chain_id, self, commit, trust_level)

    # ---- proto --------------------------------------------------------

    def encode(self) -> bytes:
        w = ProtoWriter()
        for v in self.validators:
            w.write_message(1, v.encode(), always=True)
        if self.proposer is not None:
            w.write_message(2, self.proposer.encode())
        # TotalVotingPower deliberately zeroed (validator_set.go:797-800).
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "ValidatorSet":
        """Two paths, one result. The FAST path is native/tm_native.cpp
        valset_decode_columns: the whole message walked once with the GIL
        released, every validator parsed and shape-checked on every call,
        nothing kept between calls; the Validator objects are then built
        from its columns, which also become the set's ed25519_columns().
        It answers None for any input off the canonical all-ed25519 shape
        (and is absent under TM_TPU_NO_NATIVE or a failed build); then the
        Python walk below runs — the SPECIFICATION, which alone decides
        tolerance and raises every exception of the wire. Either way the
        power total and validate_basic() run here, on the objects.
        ops_stats() counts the sets each path decoded
        (valset_decode_native / valset_decode_python)."""
        cols = _native_valset_columns(data)
        if cols is not None:
            vs = _valset_from_columns(cls, *cols)
        else:
            f = decode_message(data)
            vals = [Validator.decode(raw) for raw in field_repeated_bytes(f, 1)]
            proposer = Validator.decode(field_bytes(f, 2)) if 2 in f else None
            vs = cls(validators=vals, proposer=proposer)
        vs.total_voting_power()  # recompute, never trust the wire
        vs.validate_basic()
        return vs


_OPS = None


def _native_valset_columns(data):
    """The native parse of a ValidatorSet's wire bytes (decode's fast
    path): its column tuple, or None where the module is absent or the
    input is off the canonical shape. Counts the set under the path that
    decodes it."""
    global _OPS
    from .. import native as _native

    cols = _native.columns("valset_decode_columns", data)
    if _OPS is None:
        from ..libs import metrics as _metrics

        _OPS = _metrics.ops_metrics()
    _OPS.valset_decodes.inc(path="python" if cols is None else "native")
    return cols


def _valset_from_columns(
    cls, n, addr, pub, power, priority, p_addr, p_pub, p_power, p_priority
) -> "ValidatorSet":
    """valset_decode_columns' buffers as the set the Python walk builds:
    a Validator and an ed25519 PubKey a row, the proposer an object of its
    own, and (pub, power) kept as the set's ed25519 columns (the arrays
    ed25519_columns() would gather from the objects; read-only views of
    the buffers)."""
    import numpy as np

    from ..crypto.ed25519 import PubKey as _EdPubKey

    power_col = np.frombuffer(power, dtype=np.int64)
    powers = power_col.tolist()
    priorities = np.frombuffer(priority, dtype=np.int64).tolist()
    vs = cls(
        validators=[
            Validator(
                addr[20 * i : 20 * i + 20],
                _EdPubKey(pub[32 * i : 32 * i + 32]),
                powers[i],
                priorities[i],
            )
            for i in range(n)
        ],
        proposer=Validator(p_addr, _EdPubKey(p_pub), p_power, p_priority),
    )
    vs._ed_cols = (np.frombuffer(pub, dtype=np.uint8).reshape(n, 32), power_col)
    return vs


class ErrNotEnoughVotingPowerSigned(ValueError):
    """validator_set.go:703-713."""

    def __init__(self, got: int, needed: int):
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}"
        )
        self.got = got
        self.needed = needed


# ---- free helpers (validator_set.go:365-520) --------------------------


def _process_changes(orig: List[Validator]) -> Tuple[List[Validator], List[Validator]]:
    changes = [v.copy() for v in orig]
    _sort_by_address(changes)
    updates: List[Validator] = []
    removals: List[Validator] = []
    prev_addr: Optional[bytes] = None
    for u in changes:
        if u.address == prev_addr:
            raise ValueError(f"duplicate entry {u} in {changes}")
        if u.voting_power < 0:
            raise ValueError(f"voting power can't be negative: {u.voting_power}")
        if u.voting_power > MAX_TOTAL_VOTING_POWER:
            raise ValueError(
                f"to prevent clipping/overflow, voting power can't be higher than {MAX_TOTAL_VOTING_POWER}: {u.voting_power}"
            )
        if u.voting_power == 0:
            removals.append(u)
        else:
            updates.append(u)
        prev_addr = u.address
    return updates, removals


def _verify_updates(updates: List[Validator], vals: ValidatorSet, removed_power: int) -> int:
    def delta(update: Validator) -> int:
        _, val = vals.get_by_address(update.address)
        if val is not None:
            return update.voting_power - val.voting_power
        return update.voting_power

    updates_copy = sorted(updates, key=delta)
    tvp_after_removals = vals.total_voting_power() - removed_power
    for upd in updates_copy:
        tvp_after_removals += delta(upd)
        if tvp_after_removals > MAX_TOTAL_VOTING_POWER:
            raise OverflowError(
                f"total voting power of resulting valset exceeds max {MAX_TOTAL_VOTING_POWER}"
            )
    return tvp_after_removals + removed_power


def _num_new_validators(updates: List[Validator], vals: ValidatorSet) -> int:
    return sum(1 for u in updates if not vals.has_address(u.address))


def _compute_new_priorities(updates: List[Validator], vals: ValidatorSet, updated_tvp: int) -> None:
    for u in updates:
        _, val = vals.get_by_address(u.address)
        if val is None:
            # -1.125 * updatedTotalVotingPower (validator_set.go:473-489);
            # Go's >> on non-negative int64 == Python's >>.
            u.proposer_priority = -(updated_tvp + (updated_tvp >> 3))
        else:
            u.proposer_priority = val.proposer_priority


def _verify_removals(deletes: List[Validator], vals: ValidatorSet) -> int:
    removed = 0
    for d in deletes:
        _, val = vals.get_by_address(d.address)
        if val is None:
            raise ValueError(f"failed to find validator {d.address.hex().upper()} to remove")
        removed += val.voting_power
    if len(deletes) > len(vals.validators):
        raise ValueError("more deletes than validators")
    return removed
