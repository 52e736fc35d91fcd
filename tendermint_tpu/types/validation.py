"""Commit verification — the north-star hot path.

Reference parity: types/validation.go. VerifyCommit/VerifyCommitLight/
VerifyCommitLightTrusting route through the crypto.batch seam, where the
device (TPU) batch verifier is installed — a commit's signatures become one
fixed-shape device batch (SURVEY.md §3.4). Behavior (error cases, tally
accounting, blame assignment for the first bad signature) is byte-identical
to the single-verify path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from ..crypto import batch as _batch
from ..crypto import tmhash
from ..observability import trace as _trace
from .block import BlockID, Commit, CommitSig
from .validator_set import ErrNotEnoughVotingPowerSigned, ValidatorSet, safe_mul

_span = _trace.span

_OPS = None


def _ops():
    global _OPS
    if _OPS is None:
        from ..libs import metrics as _metrics

        _OPS = _metrics.ops_metrics()
    return _OPS


def _note_host_verified(n: int) -> None:
    """Per-signature host verifications (the sub-threshold single path)
    count toward the ops sigs_verified series like every other path."""
    if n:
        _ops().sigs_verified.inc(n, path="host")


def _note_trusting(n: int, on_device: bool) -> None:
    """Where a by-address check against a trusted set (the light client's
    skipping third) verified its n signatures: the `light_trusting_sigs`
    series, beside sigs_verified, which counts them too."""
    _ops().light_trusting_sigs.inc(n, path="device" if on_device else "host")


BATCH_VERIFY_THRESHOLD = 2  # validation.go:12


@dataclass(frozen=True)
class Fraction:
    """libs/math.Fraction (used for light-client trust level)."""

    numerator: int
    denominator: int

    def validate(self) -> None:
        if self.denominator == 0:
            raise ValueError("fraction has zero denominator")


DEFAULT_TRUST_LEVEL = Fraction(1, 3)


class ErrInvalidCommitHeight(ValueError):
    def __init__(self, expected: int, actual: int):
        super().__init__(f"invalid commit height: expected {expected}, got {actual}")


class ErrInvalidCommitSignatures(ValueError):
    def __init__(self, expected: int, actual: int):
        super().__init__(
            f"invalid commit -- wrong set size: {expected} vs {actual}"
        )


def _should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    proposer = vals.get_proposer()
    return len(commit.signatures) >= BATCH_VERIFY_THRESHOLD and _batch.supports_batch_verifier(
        proposer.pub_key if proposer else None
    )


def _should_batch_prepare(vals: ValidatorSet, commit: Commit) -> bool:
    """The async seam's batch gate (ISSUE 19): the reference's per-key
    batch-verifier gate, OR a scheme column view the device lanes can
    take — an all-secp256k1 committee batches through the secp kernel
    even though crypto/batch.go has no secp verifier (batch.go:26-33
    returns nil; the device lane is a superset, not a parity break,
    because verdicts and blame are bit-identical to the single path)."""
    if _should_batch_verify(vals, commit):
        return True
    return (
        len(commit.signatures) >= BATCH_VERIFY_THRESHOLD
        and vals.secp256k1_columns() is not None
    )


def _ignore_absent(c: CommitSig) -> bool:
    return c.is_absent()


def _ignore_not_for_block(c: CommitSig) -> bool:
    return not c.for_block()


def _count_for_block(c: CommitSig) -> bool:
    return c.for_block()


def _count_all(c: CommitSig) -> bool:
    return True


def verify_commit(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> None:
    """validation.go:25-52: +2/3 signed, ALL signatures checked (the app's
    LastCommitInfo incentive accounting depends on every sig)."""
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    ignore = _ignore_absent
    count = _count_for_block
    with _span("verify_commit", n=len(commit.signatures), height=height):
        if _should_batch_verify(vals, commit):
            _verify_commit_batch(
                chain_id, vals, commit, voting_power_needed, ignore, count, True, True
            )
        else:
            _verify_commit_single(
                chain_id, vals, commit, voting_power_needed, ignore, count, True, True
            )


def verify_commit_light(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> None:
    """validation.go:59-86: +2/3 signed; may exit early."""
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    ignore = _ignore_not_for_block
    count = _count_all
    with _span("verify_commit", n=len(commit.signatures), height=height,
               mode="light"):
        if _should_batch_verify(vals, commit):
            _verify_commit_batch(
                chain_id, vals, commit, voting_power_needed, ignore, count, False, True
            )
        else:
            _verify_commit_single(
                chain_id, vals, commit, voting_power_needed, ignore, count, False, True
            )


def verify_commit_light_trusting(
    chain_id: str, vals: ValidatorSet, commit: Commit, trust_level: Fraction
) -> None:
    """validation.go:94-135: trustLevel of vals signed; vals need not match
    the commit's validator set — look up by address, reject double votes."""
    if vals is None:
        raise ValueError("nil validator set")
    if trust_level.denominator == 0:
        raise ValueError("trustLevel has zero Denominator")
    if commit is None:
        raise ValueError("nil commit")
    total_mul, overflow = safe_mul(vals.total_voting_power(), trust_level.numerator)
    if overflow:
        raise OverflowError(
            "int64 overflow while calculating voting power needed; "
            "please provide smaller trustLevel numerator"
        )
    voting_power_needed = total_mul // trust_level.denominator
    ignore = _ignore_not_for_block
    count = _count_all
    if _should_batch_verify(vals, commit):
        _verify_commit_batch(
            chain_id, vals, commit, voting_power_needed, ignore, count, False, False
        )
    else:
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed, ignore, count, False, False
        )


def validate_hash(h: bytes) -> None:
    """validation.go:138-147."""
    if h and len(h) != tmhash.SIZE:
        raise ValueError(f"expected size to be {tmhash.SIZE} bytes, got {len(h)} bytes")


class PrepareUnsupported(Exception):
    """prepare_commit_batch cannot represent this commit/valset for the
    async seam (e.g. a non-columnar or mixed-key validator set); the
    caller falls back to the synchronous verify path, which handles
    every case the reference handles."""


def prepare_commit_light(chain_id: str, vals: ValidatorSet, block_id: BlockID,
                         height: int, commit: Commit):
    """verify_commit_light's host half (ISSUE 11 seam): the basic
    val/commit binding checks plus prepare_commit_batch with the light
    predicates. Returns (entries, conclude); (None, None) means the
    commit rode the sub-threshold single-signature path synchronously
    and is already fully verified. Raises exactly what
    verify_commit_light raises host-side, or PrepareUnsupported when the
    async seam cannot represent the set."""
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    with _span("verify_commit", n=len(commit.signatures), height=height,
               mode="light"):
        if not _should_batch_prepare(vals, commit):
            _verify_commit_single(
                chain_id, vals, commit, voting_power_needed,
                _ignore_not_for_block, _count_all, False, True,
            )
            return None, None
        return prepare_commit_batch(
            chain_id, vals, commit, voting_power_needed,
            _ignore_not_for_block, _count_all, False, True,
        )


def prepare_commit_range(chain_id: str, vals: ValidatorSet, items):
    """Range form of the prepare seam (ISSUE 14): `items` is an ordered
    iterable of (height, block_id, commit) all claimed to be signed by
    the SAME validator set `vals` (the caller cut the range at every
    valset-changing height). Returns (prepared, synced):

      prepared  [(height, entries, conclude)] — device work per height,
                in range order; each conclude reproduces the sequential
                path's exact blame error for its height
      synced    [height] — heights that rode the sub-threshold
                single-signature path and are ALREADY fully verified

    Host-side failures raise exactly what verify_commit_light raises for
    the offending height (PrepareUnsupported included) — the caller is
    expected to fall back to per-height sequential verification for the
    range, which reproduces the same error byte-for-byte."""
    prepared = []
    synced = []
    for height, block_id, commit in items:
        entries, conclude = prepare_commit_light(
            chain_id, vals, block_id, height, commit
        )
        if entries is None:
            synced.append(height)
        else:
            prepared.append((height, entries, conclude))
    return prepared, synced


def prepare_commit_light_trusting(chain_id: str, vals: ValidatorSet,
                                  commit: Commit, trust_level: Fraction,
                                  epoch_lookup: bool = True):
    """verify_commit_light_trusting's host half (ISSUE 11 seam): nil and
    overflow checks, by-address selection with double-vote detection and
    the trust-level tally — returning the sig work instead of verifying
    in place. Same return/raise contract as prepare_commit_light;
    `epoch_lookup` as prepare_commit_batch has it."""
    if vals is None:
        raise ValueError("nil validator set")
    if trust_level.denominator == 0:
        raise ValueError("trustLevel has zero Denominator")
    if commit is None:
        raise ValueError("nil commit")
    total_mul, overflow = safe_mul(vals.total_voting_power(), trust_level.numerator)
    if overflow:
        raise OverflowError(
            "int64 overflow while calculating voting power needed; "
            "please provide smaller trustLevel numerator"
        )
    voting_power_needed = total_mul // trust_level.denominator
    if not _should_batch_prepare(vals, commit):
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed,
            _ignore_not_for_block, _count_all, False, False,
        )
        return None, None
    return prepare_commit_batch(
        chain_id, vals, commit, voting_power_needed,
        _ignore_not_for_block, _count_all, False, False, epoch_lookup,
    )


def _blame_conclude(sig_idxs, commit, by_address: bool = False):
    """The verdict half of _verify_commit_batch over a validity row:
    all-valid returns, otherwise the FIRST invalid lane maps back through
    the selection to the reference's blame string (validation.go:242-248).
    The caller says where the row was computed, which is known only once
    the block has been submitted: a by-address check counts its
    signatures there (light_trusting_sigs)."""
    import numpy as _np

    def conclude(valid, on_device: bool = True) -> None:
        if by_address:
            _note_trusting(len(sig_idxs), on_device)
        valid_arr = _np.asarray(valid, dtype=bool)
        if valid_arr.size and valid_arr.all():
            return
        if not valid_arr.all() and valid_arr.size:
            idx = int(sig_idxs[int(_np.argmin(valid_arr))])
            sig = commit.signatures[idx]
            raise ValueError(
                f"wrong signature (#{idx}): {sig.signature.hex().upper()}"
            )
        raise RuntimeError(
            "BUG: batch verification failed with no invalid signatures"
        )

    return conclude


def prepare_commit_batch(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
    epoch_lookup: bool = True,
):
    """The host half of _verify_commit_batch with the device verify
    EXTRACTED (ISSUE 11): selection, double-vote detection, length
    checks and the voting-power tally run here, but instead of calling
    bv.verify() the prepared EntryBlock is RETURNED (epoch metadata
    attached, so the shared AsyncBatchVerifier can coalesce it with
    other same-epoch work across requests) together with a
    conclude(valid, on_device=True) callable reproducing the exact blame
    errors. With `epoch_lookup` False a by-address selection does not
    look its set up in the epoch cache: the block ships its keys, and
    carries its lanes' rows of the SET for a caller who will know later
    whether a resident table serves it (on_table_of).
    Host-side failures raise exactly what _verify_commit_batch raises
    before its verify call."""
    proposer = vals.get_proposer()
    cols = vals.ed25519_columns()
    scols = None if cols is not None else vals.secp256k1_columns()
    if (
        proposer is None
        or len(commit.signatures) < BATCH_VERIFY_THRESHOLD
        or (scols is None
            and not _batch.supports_batch_verifier(proposer.pub_key))
    ):
        raise RuntimeError(
            "unsupported signature algorithm or insufficient signatures for batch verification"
        )
    if cols is None and scols is None:
        # mixed/non-columnar set: ONE EntryBlock cannot represent it
        # (per-scheme kernels); mesh-aware callers take
        # prepare_commit_scheme_split, everyone else falls back to the
        # synchronous per-key path, which handles every case
        raise PrepareUnsupported("validator set is not single-scheme columnar")
    if look_up_by_index and cols is not None:
        fused = _fused_commit_prep(
            chain_id, vals, commit, voting_power_needed,
            ignore_sig, count_sig, count_all_signatures,
        )
        if fused is not None:
            sel_idx, tallied, eblk = fused
            if eblk is None:
                raise ErrNotEnoughVotingPowerSigned(
                    got=tallied, needed=voting_power_needed
                )
            return eblk, _blame_conclude(sel_idx, commit)
    selected, tallied = _select_commit_sigs(
        vals, commit, voting_power_needed,
        ignore_sig, count_sig, count_all_signatures, look_up_by_index,
    )
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(got=tallied, needed=voting_power_needed)
    import numpy as _np

    from ..ops import epoch_cache as _epoch
    from ..ops.entry_block import EntryBlock

    batch_sig_idxs = [i for i, _, _ in selected]
    with _span("verify_commit.sign_bytes", n=len(selected)):
        buf, offsets = commit.vote_sign_bytes_block(chain_id, batch_sig_idxs)
    # gather pub rows from the cached columns (key TYPE safety is
    # structural: ed25519_columns is None for any mixed set) and attach
    # the epoch metadata so warm epochs ship only per-sig data —
    # `rows` are VALIDATOR-SET rows (they differ from signature indexes
    # on the by-address path); table_rows turns them into the rows of
    # the device table the set gathers from
    rows = _np.asarray([r for _, r, _ in selected], dtype=_np.int32)
    if cols is not None:
        scheme, pub, pub_aux = "ed25519", cols[0][rows], None
    else:
        # all-secp256k1 committee (ISSUE 19): 33-byte SEC1 rows split
        # into the prefix column so downstream columns stay 32-wide
        raw = scols[0][rows]
        scheme = "secp256k1"
        pub_aux = _np.ascontiguousarray(raw[:, 0])
        pub = _np.ascontiguousarray(raw[:, 1:])
    epoch_key, val_idx = (_epoch.table_rows(vals, rows) if epoch_lookup
                          else (None, rows))
    sigs_list = commit.signatures
    sig = _np.frombuffer(
        b"".join(sigs_list[i].signature for i in batch_sig_idxs),
        dtype=_np.uint8,
    ).reshape(len(selected), 64)
    eblk = EntryBlock(pub, sig, buf, offsets,
                      val_idx=val_idx, epoch_key=epoch_key,
                      scheme=scheme, pub_aux=pub_aux)
    return eblk, _blame_conclude(batch_sig_idxs, commit,
                                 by_address=not look_up_by_index)


def on_table_of(blk, vals: ValidatorSet, other):
    """`blk`, prepared over `vals` without an epoch look-up, on the device
    table `other` gathers from if `vals` maps onto that table (one put
    and the warm kernel for both, once concatenated); else as it is. The
    look-up is made here, where a launch can use what it finds."""
    from ..ops import epoch_cache as _epoch
    from ..ops.entry_block import EntryBlock

    epoch_key, val_idx = _epoch.table_rows(vals, blk.val_idx)
    if epoch_key is None or epoch_key != other.epoch_key:
        return blk
    return EntryBlock(blk.pub, blk.sig, blk.msgs, blk.offsets,
                      val_idx=val_idx, epoch_key=epoch_key,
                      scheme=blk.scheme, pub_aux=blk.pub_aux)


def prepare_commit_scheme_split(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool] = _ignore_not_for_block,
    count_sig: Callable[[CommitSig], bool] = _count_all,
    count_all_signatures: bool = False,
    look_up_by_index: bool = True,
):
    """Mixed-committee prep (ISSUE 19): selection and tally run ONCE
    (same _select_commit_sigs the sequential path shares), then the
    selected lanes split per key scheme into one EntryBlock each —
    submitted together, the mesh packer lands both schemes in different
    lanes of the SAME superbatch, so a mixed commit still costs one
    dispatch. Returns (blocks, conclude): `blocks` is the per-scheme
    EntryBlock list in (ed25519, secp256k1) order and `conclude` takes
    the verdict rows CONCATENATED in that block order, reproducing the
    sequential path's exact blame string (first invalid lane in
    signature order, not concat order). Raises PrepareUnsupported when
    any key is neither scheme."""
    view = vals.scheme_rows()
    if view is None:
        raise PrepareUnsupported("validator set has non-device key schemes")
    kinds, pub32, aux = view
    selected, tallied = _select_commit_sigs(
        vals, commit, voting_power_needed,
        ignore_sig, count_sig, count_all_signatures, look_up_by_index,
    )
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(
            got=tallied, needed=voting_power_needed
        )
    import numpy as _np

    from ..ops.entry_block import EntryBlock

    per: dict = {0: [], 1: []}
    for sig_idx, val_row, _ in selected:
        per[int(kinds[val_row])].append((sig_idx, val_row))
    blocks = []
    parts_sig_idxs = []
    sigs_list = commit.signatures
    for kind, scheme in ((0, "ed25519"), (1, "secp256k1")):
        lanes = per[kind]
        if not lanes:
            continue
        sig_idxs = [i for i, _ in lanes]
        with _span("verify_commit.sign_bytes", n=len(lanes), scheme=scheme):
            buf, offsets = commit.vote_sign_bytes_block(chain_id, sig_idxs)
        rows = _np.asarray([r for _, r in lanes], dtype=_np.int32)
        sig = _np.frombuffer(
            b"".join(sigs_list[i].signature for i in sig_idxs),
            dtype=_np.uint8,
        ).reshape(len(lanes), 64)
        blocks.append(EntryBlock(
            pub32[rows], sig, buf, offsets, val_idx=rows,
            scheme=scheme,
            pub_aux=(_np.ascontiguousarray(aux[rows])
                     if scheme == "secp256k1" else None),
        ))
        parts_sig_idxs.append(sig_idxs)
    all_idx = _np.concatenate(
        [_np.asarray(p, dtype=_np.int64) for p in parts_sig_idxs]
    ) if parts_sig_idxs else _np.zeros(0, dtype=_np.int64)

    def conclude(valid) -> None:
        valid_arr = _np.asarray(valid, dtype=bool)
        if valid_arr.size and valid_arr.all():
            return
        if not valid_arr.all() and valid_arr.size:
            # first invalid lane in SIGNATURE order: the concat order is
            # per-scheme, so min() over the offending sig indexes — not
            # argmin over the row — matches the sequential walk
            idx = int(all_idx[~valid_arr].min())
            sig = commit.signatures[idx]
            raise ValueError(
                f"wrong signature (#{idx}): {sig.signature.hex().upper()}"
            )
        raise RuntimeError(
            "BUG: batch verification failed with no invalid signatures"
        )

    return blocks, conclude


# -- BLS12-381 aggregated commits (ISSUE 20) --------------------------------
#
# Blame strings are built ONCE by the helpers below and shared by the
# sequential reference walk and the batched conclude(), so the
# byte-exactness the acceptance gate pins cannot drift between paths.

_AGG_APK_IDENTITY = "aggregate pubkey is the identity"


def _agg_sig_blame(word: str, sig: bytes) -> str:
    return f"{word} aggregate signature: {sig.hex().upper()}"


def _agg_pub_blame(word: str, idx: int) -> str:
    return f"{word} aggregate pubkey (validator #{idx})"


def _agg_basic_and_tally(vals, block_id, height, agg,
                         voting_power_needed: int):
    """Shared host half of both aggregated-commit paths: shape checks,
    bitmap-size sanity, then the power tally — which runs BEFORE any
    crypto (a commit that cannot reach quorum must not spend pairings).
    Returns the signer validator rows in ascending order."""
    if vals is None:
        raise ValueError("nil validator set")
    if agg is None:
        raise ValueError("nil commit")
    if agg.signers is None or agg.signers.size() != vals.size():
        raise ErrInvalidCommitSignatures(
            vals.size(),
            agg.signers.size() if agg.signers is not None else 0,
        )
    if height != agg.height:
        raise ErrInvalidCommitHeight(height, agg.height)
    if block_id != agg.block_id:
        raise ValueError(
            f"invalid commit -- wrong block ID: want {block_id}, "
            f"got {agg.block_id}"
        )
    idxs = agg.signers.get_true_indices()
    tallied = sum(vals.validators[i].voting_power for i in idxs)
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(
            got=tallied, needed=voting_power_needed
        )
    return idxs


def verify_aggregated_commit(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, agg
) -> None:
    """Sequential reference for an aggregated commit (the pure-Python
    oracle walk the batched path is pinned byte-exact against). Check
    order IS the contract: basic shape -> bitmap size -> power tally
    (before any crypto) -> aggregate signature status -> pubkey statuses
    in ascending validator order -> apk-is-identity -> the one pairing
    check."""
    from ..crypto import bls12381 as _bls

    voting_power_needed = vals.total_voting_power() * 2 // 3
    with _span("verify_agg_commit", n=1, height=height):
        idxs = _agg_basic_and_tally(
            vals, block_id, height, agg, voting_power_needed
        )
        sig = bytes(agg.signature)
        _, reason = _bls.signature_status(sig)
        if reason is not None:
            raise ValueError(_agg_sig_blame(reason, sig))
        pubs = []
        for i in idxs:
            pub = vals.validators[i].pub_key.bytes()
            _, preason = _bls.pubkey_status(pub)
            if preason is not None:
                raise ValueError(_agg_pub_blame(preason, i))
            pubs.append(pub)
        apk, _ = _bls.aggregate_pubkeys(pubs)
        if apk is None:
            raise ValueError(_AGG_APK_IDENTITY)
        if not _bls.fast_aggregate_verify(
            pubs, agg.sign_bytes(chain_id), sig
        ):
            raise ValueError(_agg_sig_blame("wrong", sig))


def prepare_aggregated_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    agg,
    k_hint: int = 1,
):
    """The async-seam half for an aggregated commit: host checks run
    here (raising exactly what the sequential walk raises), then the
    commit is returned as a one-row AggBlock plus a conclude(codes)
    decoding the device lane's int32 verdict code back into the SAME
    pinned blame strings. The shared pipeline coalesces same-committee
    AggBlocks, so K concurrent commits still land in one fused
    multi-pairing launch.

    `k_hint` is the caller's concurrency estimate: below
    backend.BLS_DEVICE_THRESHOLD a fused launch cannot amortize its
    final exponentiation, so the commit verifies synchronously through
    the oracle and (None, None) is returned."""
    from ..ops import backend as _backend

    if k_hint < _backend.BLS_DEVICE_THRESHOLD:
        verify_aggregated_commit(chain_id, vals, block_id, height, agg)
        return None, None
    voting_power_needed = vals.total_voting_power() * 2 // 3
    idxs = _agg_basic_and_tally(
        vals, block_id, height, agg, voting_power_needed
    )
    cols = vals.bls12381_columns()
    if cols is None:
        raise PrepareUnsupported(
            "validator set is not bls12381-columnar"
        )
    pub48 = cols[0]
    import numpy as _np

    from ..ops import epoch_cache as _epoch
    from ..ops.entry_block import AggBlock

    bits = _np.zeros(vals.size(), dtype=bool)
    bits[idxs] = True
    _epoch.note_valset(vals)  # register/refresh the G1 epoch tables
    sig = bytes(agg.signature)
    blk = AggBlock.from_commits(
        [(bits, agg.sign_bytes(chain_id), sig)], pub48, vals.hash()
    )

    def conclude(codes) -> None:
        from ..crypto import bls12381 as _bls
        from ..ops import bls_verify as _bv

        code = int(_np.asarray(codes).reshape(-1)[0])
        if code == _bv.CODE_VALID:
            return
        if code == _bv.CODE_PAIRING:
            raise ValueError(_agg_sig_blame("wrong", sig))
        if code == _bv.CODE_APK_IDENTITY:
            raise ValueError(_AGG_APK_IDENTITY)
        word = _bv.SIG_CODE_WORDS.get(code)
        if word is not None:
            raise ValueError(_agg_sig_blame(word, sig))
        if code >= _bv.CODE_PUB_BASE:
            i = code - _bv.CODE_PUB_BASE
            # the word re-derives from the committee snapshot — the
            # status is memoized per key bytes, so this is a dict hit
            word = _bls.pubkey_status(pub48[i].tobytes())[1]
            raise ValueError(_agg_pub_blame(word or "malformed", i))
        raise RuntimeError(f"BUG: unknown BLS verdict code {code}")

    return blk, conclude


def _select_commit_sigs(
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
):
    """Selection + tally half of the batch path (validation.go:152-240):
    flag filtering, by-index/by-address lookup with double-vote
    detection, signature-length checks, and the voting-power tally with
    the reference's early-stop semantics. Returns (selected, tallied)
    with selected = [(sig_idx, val_row, validator), ...] in signature
    order — val_row is the validator's row in `vals` (== sig_idx when
    looking up by index). Raises exactly the errors the inline selection
    raised. Shared by _verify_commit_batch and prepare_commit_batch so
    the sequential and batched-service paths cannot drift."""
    tallied = 0
    if count_all_signatures and look_up_by_index and ignore_sig is _ignore_absent:
        # verify_commit's exact predicate set on a 10k-validator commit is
        # the benchmark hot path: flag-attribute listcomps cut the
        # 3-calls-per-signature selection ~3x. The whole selection is
        # GIL-held, so this directly bounds how many concurrent commit
        # verifies the async device pipeline can keep fed.
        from .block import BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT

        sigs = commit.signatures
        validators = vals.validators
        flags = [c.block_id_flag for c in sigs]
        selected = [
            (i, i, validators[i])
            for i, f in enumerate(flags)
            if f != BLOCK_ID_FLAG_ABSENT
        ]
        if any(len(sigs[i].signature) != 64 for i, _, _ in selected):
            raise ValueError("invalid signature length")
        if count_sig is _count_for_block:
            tallied = sum(
                validators[i].voting_power
                for i, f in enumerate(flags)
                if f == BLOCK_ID_FLAG_COMMIT
            )
        else:
            tallied = sum(v.voting_power for _, _, v in selected)
        return selected, tallied
    selected = []  # (sig_idx, val_row, val) in signature order
    seen_vals: dict = {}
    for idx, commit_sig in enumerate(commit.signatures):
        if ignore_sig(commit_sig):
            continue
        if look_up_by_index:
            val_row, val = idx, vals.validators[idx]
        else:
            val_row, val = vals.get_by_address(commit_sig.validator_address)
            if val is None:
                continue
            if val_row in seen_vals:
                raise ValueError(
                    f"double vote from {val} ({seen_vals[val_row]} and {idx})"
                )
            seen_vals[val_row] = idx
        # length check here, not at the deferred bv.add below — the
        # error must surface per-lane before the voting-power tally
        # concludes, exactly as when add() ran inside this loop
        # (BatchVerifier.Add order, crypto/ed25519/ed25519.go:203-217)
        if len(commit_sig.signature) != 64:
            raise ValueError("invalid signature length")
        selected.append((idx, val_row, val))
        if count_sig(commit_sig):
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            break
    return selected, tallied


def _fused_commit_prep(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    scheme: str = "ed25519",
):
    """Columnar fast path: CommitBlock + validator columns through ONE
    fused prep call (ops/commit_prep.py — native when built; it gives
    the GIL up for the sign bytes + gather of 1 024 rows or more), over
    the `scheme` columns of the set (ed25519 or sr25519).
    Returns (sel_idx, tallied, EntryBlock-or-None) or None when this
    commit/valset/predicate combination is not columnar-representable
    (the object path below then reproduces the exact legacy behavior and
    errors)."""
    from ..ops import commit_prep as _cp

    if ignore_sig is _ignore_not_for_block:
        mode = _cp.MODE_SELECT_COMMIT_ONLY
    elif ignore_sig is _ignore_absent:
        mode = 0
    else:
        return None
    if count_sig is _count_for_block:
        mode |= _cp.MODE_COUNT_FOR_BLOCK
    elif count_sig is not _count_all:
        return None
    if not count_all_signatures:
        mode |= _cp.MODE_EARLY_STOP
    with _span("verify_commit.prep_fused", n=len(commit.signatures)):
        return _cp.prep_commit_from(
            commit, vals, chain_id, voting_power_needed, mode, scheme
        )


def _verify_commit_batch(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> None:
    """validation.go:152-263."""
    proposer = vals.get_proposer()
    bv = _batch.create_batch_verifier(proposer.pub_key if proposer else None)
    if bv is None or len(commit.signatures) < BATCH_VERIFY_THRESHOLD:
        raise RuntimeError(
            "unsupported signature algorithm or insufficient signatures for batch verification"
        )
    add_block = getattr(bv, "add_block", None)
    # the verifier's key scheme (the proposer's, validation.go:153):
    # the set's columns of that scheme feed the fused prep
    scheme = getattr(bv, "scheme", "ed25519")
    if look_up_by_index and add_block is not None:
        fused = _fused_commit_prep(
            chain_id,
            vals,
            commit,
            voting_power_needed,
            ignore_sig,
            count_sig,
            count_all_signatures,
            scheme,
        )
        if fused is not None:
            import numpy as _np

            sel_idx, tallied, eblk = fused
            if eblk is None:
                raise ErrNotEnoughVotingPowerSigned(
                    got=tallied, needed=voting_power_needed
                )
            # key TYPE safety is proven by the scheme's columns (all of
            # that scheme or the fused path is not taken); signature
            # lengths are structural in the CommitBlock's (n, 64) column
            add_block(eblk)
            with _span("verify_commit.verify", n=len(eblk)):
                ok, valid_sigs = bv.verify()
            if ok:
                return
            # vectorized blame: first invalid lane via argmin over the
            # bool verdict array (no per-entry Python scan)
            valid_arr = _np.asarray(valid_sigs, dtype=bool)
            if not valid_arr.all() and valid_arr.size:
                idx = int(sel_idx[int(_np.argmin(valid_arr))])
                sig = commit.signatures[idx]
                raise ValueError(
                    f"wrong signature (#{idx}): {sig.signature.hex().upper()}"
                )
            raise RuntimeError(
                "BUG: batch verification failed with no invalid signatures"
            )
    sel_rows, tallied = _select_commit_sigs(
        vals, commit, voting_power_needed,
        ignore_sig, count_sig, count_all_signatures, look_up_by_index,
    )
    selected = [(idx, val) for idx, _, val in sel_rows]
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(got=tallied, needed=voting_power_needed)
    batch_sig_idxs = [idx for idx, _ in selected]
    add_block = getattr(bv, "add_block", None)
    if add_block is not None:
        # Columnar zero-copy path: the sign bytes land in ONE contiguous
        # buffer + offset table (no per-lane PyBytes), pub/sig join once
        # into (n, 32)/(n, 64) arrays, and the EntryBlock rides by
        # reference through the pipeline to the kernel prep. The per-key
        # TYPE check rides along (`keys`) — a mixed-key validator set
        # must fail exactly as per-entry add() did.
        import numpy as _np

        from ..ops.entry_block import EntryBlock

        with _span("verify_commit.sign_bytes", n=len(selected)):
            buf, offsets = commit.vote_sign_bytes_block(
                chain_id, batch_sig_idxs
            )
        sigs_list = commit.signatures
        n_sel = len(selected)
        keys = [val.pub_key for _, val in selected]
        pub_b = b"".join(k.bytes() for k in keys)
        if len(pub_b) != 32 * n_sel:
            # a wrong-size key (e.g. secp256k1 in an ed25519 set) must
            # surface as the same error per-entry add() raised, not as a
            # reshape failure
            raise TypeError(f"pubkey is not {scheme}")
        pub = _np.frombuffer(pub_b, dtype=_np.uint8).reshape(n_sel, 32)
        sig = _np.frombuffer(
            b"".join(sigs_list[idx].signature for idx, _ in selected),
            dtype=_np.uint8,
        ).reshape(n_sel, 64)
        add_block(EntryBlock(pub, sig, buf, offsets, scheme=scheme),
                  keys=keys)
    else:
        # one batch sign-bytes composition for all selected lanes (native
        # composer; the per-lane Python encode was the dominant host cost
        # on large commits)
        with _span("verify_commit.sign_bytes", n=len(selected)):
            sign_bytes = commit.vote_sign_bytes_many(
                chain_id, [i for i, _ in selected]
            )
        add_many = getattr(bv, "add_entries", None)
        if add_many is not None:
            # bulk accumulate in ONE pass: lengths were checked during
            # selection and the key type during verifier creation, so the
            # entry build can go straight to wire bytes (every extra
            # 10k-element pass here is GIL-held and serializes concurrent
            # commit verifies)
            sigs_list = commit.signatures
            add_many(
                [
                    (val.pub_key, sb, sigs_list[idx].signature)
                    for (idx, val), sb in zip(selected, sign_bytes, strict=True)
                ],
                lengths_checked=True,
            )
        else:
            for (idx, val), sb in zip(selected, sign_bytes, strict=True):
                bv.add(val.pub_key, sb, commit.signatures[idx].signature)
    with _span("verify_commit.verify", n=len(selected)):
        ok, valid_sigs = bv.verify()
    if not look_up_by_index:
        on_device = getattr(bv, "on_device", None)
        if on_device is not None:   # a verifier that says where it ran
            _note_trusting(len(selected), on_device)
    if ok:
        return
    import numpy as _np

    valid_arr = _np.asarray(valid_sigs, dtype=bool)
    if not valid_arr.all() and valid_arr.size:
        idx = batch_sig_idxs[int(_np.argmin(valid_arr))]
        sig = commit.signatures[idx]
        raise ValueError(
            f"wrong signature (#{idx}): {sig.signature.hex().upper()}"
        )
    raise RuntimeError("BUG: batch verification failed with no invalid signatures")


def _verify_commit_single(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> None:
    """validation.go:265-334."""
    tallied = 0
    checked = 0
    seen_vals: dict = {}
    for idx, commit_sig in enumerate(commit.signatures):
        if ignore_sig(commit_sig):
            continue
        if look_up_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(commit_sig.validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                raise ValueError(
                    f"double vote from {val} ({seen_vals[val_idx]} and {idx})"
                )
            seen_vals[val_idx] = idx
        vote_sign_bytes = commit.vote_sign_bytes(chain_id, idx)
        if not val.pub_key.verify_signature(vote_sign_bytes, commit_sig.signature):
            raise ValueError(
                f"wrong signature (#{idx}): {commit_sig.signature.hex().upper()}"
            )
        checked += 1
        if count_sig(commit_sig):
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            break
    _note_host_verified(checked)
    if checked and not look_up_by_index:
        _note_trusting(checked, False)
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(got=tallied, needed=voting_power_needed)


def _verify_basic_vals_and_commit(
    vals: Optional[ValidatorSet],
    commit: Optional[Commit],
    height: int,
    block_id: BlockID,
) -> None:
    """validation.go:336-358."""
    if vals is None:
        raise ValueError("nil validator set")
    if commit is None:
        raise ValueError("nil commit")
    if vals.size() != len(commit.signatures):
        raise ErrInvalidCommitSignatures(vals.size(), len(commit.signatures))
    if height != commit.height:
        raise ErrInvalidCommitHeight(height, commit.height)
    if block_id != commit.block_id:
        raise ValueError(
            f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
        )
