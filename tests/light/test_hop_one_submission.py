"""A skipping hop is one submission (ISSUE 36): verify_non_adjacent hands
the trusting third and the +2/3 of a hop's commit to ONE batch verifier.

Parity: on a 100-validator chain that replaces a key a height, every case
gives the same exception type and message through the fused path, through
the hop's checks run one after the other (SigCheck.run_sync in order) and
in the plain reference (benchmark/reference_bisect.py). Engagement, by the
program's counters on the CPU's device path: at 100 validators a hop is
one pipeline submission of 34 + 67 signatures; at 4 validators its 2 + 3
stay on the host; a refused attempt submits nothing."""

import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import lightchain, reference_bisect  # noqa: E402

from tendermint_tpu.crypto import batch as crypto_batch  # noqa: E402
from tendermint_tpu.crypto import secp256k1  # noqa: E402
from tendermint_tpu.libs.metrics import ops_stats  # noqa: E402
from tendermint_tpu.light import verifier  # noqa: E402
from tendermint_tpu.light.provider import LightBlock  # noqa: E402
from tendermint_tpu.observability import trace  # noqa: E402
from tendermint_tpu.ops import pipeline  # noqa: E402
from tendermint_tpu.types import Fraction, Validator, ValidatorSet  # noqa: E402
from tendermint_tpu.types import validation, validator_set  # noqa: E402
from tendermint_tpu.wire.canonical import Timestamp  # noqa: E402

SEED = 2 ** 31 + 36
PERIOD, DRIFT, LEVEL = 86400, 10, (1, 3)
CFG = {"name": "hop100", "validators": 100, "voting_power": 100,
       "chain_id": "hop-100", "headers": 70, "keys_replaced_per_height": 1,
       "block_interval_s": 60}
SMALL = dict(CFG, name="hop4", validators=4, chain_id="hop-4", headers=5)
NOW = (lightchain.T0 + 60 * 80, 0)
# 1 -> 61 leaves 40 of the root's keys in the set: the 34th of them signs
# past row 67, so a signature can sit in the trusted third alone
FAR = 61
STARVED = 70            # 31 of the root's keys left: not above a third


@pytest.fixture(scope="module")
def chain100():
    return lightchain.chain(CFG, SEED)[1]


@pytest.fixture(scope="module")
def chain4():
    return lightchain.chain(SMALL, SEED)[1]


def _decoded(blk, vals=None) -> LightBlock:
    return LightBlock.decode(lightchain.light_block_wire(blk, vals=vals))


def _said(fn):
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — the verdict IS the error
        return type(e).__name__, str(e)
    return None


def _args(root: LightBlock, lb: LightBlock, trusted_vals=None):
    return (root.signed_header, trusted_vals or root.validators,
            lb.signed_header, lb.validators, float(PERIOD), Timestamp(*NOW),
            float(DRIFT), Fraction(*LEVEL))


def _fused(root, lb, trusted_vals=None):
    return _said(lambda: verifier.verify_non_adjacent(
        *_args(root, lb, trusted_vals)))


def _one_after_the_other(root, lb, trusted_vals=None):
    def run():
        for chk in verifier.prepare_non_adjacent(*_args(root, lb, trusted_vals)):
            chk.run_sync()
    return _said(run)


def _reference(blocks, blk, vals=None):
    root = blocks[0]
    said, _looked = reference_bisect.verify_non_adjacent(
        (root, root.vals), blk, vals or blk.vals, PERIOD, NOW, DRIFT, LEVEL)
    return said


def _flip(blk, *rows):
    sigs = list(blk.sigs)
    for i in rows:
        seconds, nanos, sig = sigs[i]
        sigs[i] = (seconds, nanos, bytes([sig[0] ^ 1]) + sig[1:])
    return dataclasses.replace(blk, sigs=tuple(sigs))


def _rows(blocks, blk):
    """(rows of the trusted third, rows of the +2/3 alone, rows past both
    stops) of `blk`'s commit seen from the root at height 1."""
    trusted = {v.address for v in blocks[0].vals}
    third = [i for i, v in enumerate(blk.vals) if v.address in trusted][:34]
    return (third, [i for i in range(67) if i not in third],
            [i for i in range(67, 100) if i not in third])


def _absent(blk, keep):
    return dataclasses.replace(blk, sigs=tuple(
        s if i in keep else None for i, s in enumerate(blk.sigs)))


def _cases(blocks):
    """{name: (the block served at the hop's height, the set supplied)}"""
    far = blocks[FAR - 1]
    third, new_only, past = _rows(blocks, far)
    alone = [i for i in third if i >= 67]
    shared = [i for i in third if i < 67]
    assert alone and shared and new_only and past
    short = dataclasses.replace(far, sigs=far.sigs[:-1])
    # the whole third signs and 60 in all: trusted enough, short of +2/3
    sixty = _absent(far, set(third) | set(new_only[:60 - len(third)]))
    a, b = third[:2]
    twice = list(far.vals)
    twice[b] = twice[a]
    return {
        "honest": (far, None),
        "forged_in_trusted_third_alone": (_flip(far, alone[0]), None),
        # the +2/3 check would blame the lower row: the trusting blame wins
        "forged_in_both_selections": (_flip(far, new_only[0], shared[-1]), None),
        "forged_in_new_two_thirds": (_flip(far, new_only[3]), None),
        "forged_past_both_stops": (_flip(far, past[0]), None),
        "too_little_trusted_power": (blocks[STARVED - 1], None),
        "double_vote_by_address": (
            dataclasses.replace(far, vals=tuple(twice)), far.vals),
        "wrong_set_size": (short, None),
        "wrong_set_size_and_forged_third": (_flip(short, alone[0]), None),
        "two_thirds_short": (sixty, None),
        "two_thirds_short_and_forged_third": (_flip(sixty, third[5]), None),
    }


CASES = ["honest", "forged_in_trusted_third_alone", "forged_in_both_selections",
         "forged_in_new_two_thirds", "forged_past_both_stops",
         "too_little_trusted_power", "double_vote_by_address",
         "wrong_set_size", "wrong_set_size_and_forged_third",
         "two_thirds_short", "two_thirds_short_and_forged_third",
         "mixed_key_trusted_set", "dispatch_error"]
SAYS = {
    "honest": None, "forged_past_both_stops": None,
    "mixed_key_trusted_set": None,
    "forged_in_trusted_third_alone": "wrong signature (#",
    "forged_in_both_selections": "wrong signature (#",
    "forged_in_new_two_thirds": "wrong signature (#",
    "too_little_trusted_power": "insufficient voting power: got 3100, "
                                "needed more than 3333",
    "double_vote_by_address": "double vote from Validator(",
    "wrong_set_size": "wrong set size: 100 vs 99",
    "wrong_set_size_and_forged_third": "wrong signature (#",
    "two_thirds_short": "insufficient voting power: got 6000, "
                        "needed more than 6666",
    "two_thirds_short_and_forged_third": "wrong signature (#",
}


def _mixed_key_set(root: LightBlock, lb: LightBlock) -> ValidatorSet:
    """The root's set with a secp256k1 key in place of one that has left
    the chain by the hop's height and does not propose: no single-scheme
    column view, so the seam answers PrepareUnsupported."""
    still = {v.address for v in lb.validators.validators}
    key = secp256k1.gen_priv_key().pub_key()
    for row, v in enumerate(root.validators.validators):
        if v.address in still:
            continue
        vals = list(root.validators.validators)
        vals[row] = Validator.new(key, v.voting_power)
        mixed = ValidatorSet.new(vals)
        if mixed.get_proposer().pub_key is not key:
            break
    assert mixed.ed25519_columns() is None
    assert mixed.secp256k1_columns() is None
    assert crypto_batch.supports_batch_verifier(mixed.get_proposer().pub_key)
    return mixed


class _Poisoned:
    """A batch verifier whose submission fails on the dispatch thread."""

    on_device = True

    def add_block(self, block, keys=None):
        pass

    def verify(self):
        raise pipeline.DispatchError("boom", bucket=128)


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("case", CASES)
def test_fused_hop_gives_the_sequential_and_the_reference_verdict(
        chain100, case):
    blocks = chain100
    root = _decoded(blocks[0])
    if case == "mixed_key_trusted_set":
        lb = _decoded(blocks[FAR - 1])
        mixed = _mixed_key_set(root, lb)
        got = _fused(root, lb, mixed)
        assert got == _one_after_the_other(root, lb, mixed) is None
        # the seam itself, where a caller drives it over such a set (the
        # light service does): the stage is done synchronously in its turn
        checks = verifier.prepare_non_adjacent(*_args(root, lb, mixed))
        stages = verifier.prepare_stages(checks)
        assert [(st.kind, st.entries is None, st.error) for st in stages] == [
            ("trusting", True, None), ("light", False, None)]
        forged = _decoded(_flip(blocks[FAR - 1], _rows(blocks, blocks[FAR - 1])[0][0]))
        assert _fused(root, forged, mixed) == _one_after_the_other(
            root, forged, mixed)
        assert _fused(root, forged, mixed)[1].startswith("wrong signature (#")
        return
    if case == "dispatch_error":
        lb = _decoded(blocks[FAR - 1])
        previous = crypto_batch.use_device_engine(_Poisoned)
        try:
            got, want = _fused(root, lb), _one_after_the_other(root, lb)
        finally:
            crypto_batch.use_device_engine(previous)
        assert got == want and got[0] == "DispatchError" and "boom" in got[1]
        return
    blk, supplied = _cases(blocks)[case]
    lb = _decoded(blk, vals=supplied)
    got = _fused(root, lb)
    assert got == _one_after_the_other(root, lb)
    assert got == _reference(blocks, blk, supplied)
    if SAYS[case] is None:
        assert got is None
    else:
        assert got[0] == ("ErrNotEnoughTrust" if case ==
                          "too_little_trusted_power" else "ErrInvalidHeader")
        assert SAYS[case] in got[1]
    if case == "forged_in_both_selections":
        third, new_only, _past = _rows(blocks, blk)
        shared = [i for i in third if i < 67]
        assert new_only[0] < shared[-1]
        assert got[1].startswith(f"wrong signature (#{shared[-1]}): ")


# -- engagement: where a hop's signatures went, by the program's counters ------

COUNTERS = ("sigs_verified_device", "sigs_verified_host",
            "host_fallback_batches", "light_trusting_sigs_device",
            "light_trusting_sigs_host", "light_hops_fused", "dispatch_errors")


def _rise(fn):
    def read():
        s = ops_stats()
        return dict({k: s[k] for k in COUNTERS},
                    launches=sum(s["batches_by_bucket"].values()))
    c0 = read()
    out = fn()
    c1 = read()
    return out, {k: c1[k] - c0[k] for k in c1 if c1[k] != c0[k]}


def _spy_on_the_light_stage(seen: list):
    real = validation.prepare_commit_light

    def spy(*a, **k):
        seen.append(a)
        return real(*a, **k)
    return spy


# what: (chain, hop 1 -> height, error, counters that rise, +2/3 stages
# prepared through the seam)
ENGAGED = {
    "hop_of_100_is_one_submission": (
        "chain100", FAR, None,
        {"sigs_verified_device": 101, "launches": 1,
         "light_trusting_sigs_device": 34, "light_hops_fused": 1}, 1),
    # under the device threshold each check stays a host batch of its own
    "hop_of_4_stays_on_the_host": (
        "chain4", 3, None,
        {"sigs_verified_host": 5, "host_fallback_batches": 2,
         "light_trusting_sigs_host": 2}, 1),
    # crypto.batch's verifier takes the old set's check alone, wherever its
    # count sends it; the new set's runs after it, as it always did
    "new_set_not_all_ed25519_runs_its_check_after": (
        "chain100", FAR, None,
        {"sigs_verified_host": 34, "host_fallback_batches": 1,
         "light_trusting_sigs_host": 34, "sigs_verified_device": 67,
         "launches": 1}, 0),
    # a refused attempt ends at the trusting tally
    "refused_at_100_moves_nothing": (
        "chain100", STARVED, "ErrNotEnoughTrust", {}, 0),
    "refused_at_4_moves_nothing": ("chain4", 4, "ErrNotEnoughTrust", {}, 0),
}


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("what", list(ENGAGED))
def test_where_a_hops_signatures_go(request, monkeypatch, what):
    fixture, height, error, want, light_prepared = ENGAGED[what]
    blocks = request.getfixturevalue(fixture)
    root, lb = _decoded(blocks[0]), _decoded(blocks[height - 1])
    light_stage = []
    monkeypatch.setattr(validation, "prepare_commit_light",
                        _spy_on_the_light_stage(light_stage))
    if what.startswith("new_set_not_all_ed25519"):
        # as a set with a key of another type answers
        monkeypatch.setattr(lb.validators, "_ed_cols", validator_set._NO_ED_COLS)
    asked, columns = [], ValidatorSet.ed25519_columns
    monkeypatch.setattr(ValidatorSet, "ed25519_columns",
                        lambda vals: (asked.append(vals), columns(vals))[1])
    got, rose = _rise(lambda: _fused(root, lb))
    assert (got and got[0]) == error
    assert rose == want
    assert len(light_stage) == light_prepared
    # a refused attempt asks nothing of the new set, its key columns included
    assert any(v is lb.validators for v in asked) == (not error)


class _Recording:
    """A batch verifier that keeps the blocks it is handed and says the
    device found every signature valid."""

    on_device = True
    blocks: list = []

    def add_block(self, block, keys=None):
        self.blocks.append(block)

    def verify(self):
        return True, [True] * sum(len(b) for b in self.blocks)


TABLES = {
    # 1 -> 3: two new keys, which the root's 128-row table takes, so both
    # checks' lanes gather from it: one look-up each, found
    "the_new_set_maps_onto_the_old_ones_table": (
        3, True, {"hits": 1, "misses": 1, "tables_shared": 1,
                  "rows_patched": 2}),
    # 1 -> 61: sixty new keys, a table of its own and cold at its first
    # commit, so the hop ships its keys and the old set is not looked up
    "the_new_set_is_cold": (
        FAR, False, {"misses": 1, "tables_built": 1, "entries": 1}),
}


@pytest.mark.parametrize("what", list(TABLES))
def test_a_hop_gathers_from_one_table_or_looks_nothing_up(chain100, what):
    from tendermint_tpu.ops import epoch_cache
    from tendermint_tpu.ops.entry_block import EntryBlock

    height, warm, want = TABLES[what]
    root, lb = _decoded(chain100[0]), _decoded(chain100[height - 1])
    epoch_cache.reset(depth=2)
    previous = crypto_batch.use_device_engine(_Recording)
    _Recording.blocks = []
    try:
        assert epoch_cache.note_valset(root.validators) is None   # registers
        table = epoch_cache.note_valset(root.validators)
        assert table is not None
        before = epoch_cache.stats()
        assert _fused(root, lb) is None
        after = epoch_cache.stats()
        sent = EntryBlock.concat(_Recording.blocks)
        entry = epoch_cache.lookup(sent)
    finally:
        crypto_batch.use_device_engine(previous)
        epoch_cache.reset()
    assert {k: after[k] - before[k] for k in after
            if after[k] != before[k]} == want
    assert [len(b) for b in _Recording.blocks] == [34, 67]
    if not warm:
        assert sent.epoch_key is None and sent.val_idx is None
        return
    assert sent.epoch_key == table == entry.key and len(sent.val_idx) == 101
    # each lane's row of the table holds the key the lane was signed with
    assert (sent.pub == entry.pub_rows[sent.val_idx]).all()


def test_a_hop_under_the_tracer(chain100):
    """The spans the cell's metrics read, once each a hop, and the one
    verification around the stages' concludes."""
    blocks = chain100
    root, lb = _decoded(blocks[0]), _decoded(blocks[FAR - 1])
    tr = trace.TRACER
    tr.clear()
    tr.configure(enabled=True)
    try:
        assert _fused(root, lb) is None
    finally:
        tr.configure(enabled=False)
    events = tr.events()
    tr.clear()
    by = {}
    for name, start, end, tid, args in events:
        by.setdefault(name, []).append((start, end, tid, args or {}))
    for name in ("light.header_checks", "light.trusting_check",
                 "light.light_check", "light.hop_verify", "ops.pipeline_wait"):
        assert len(by[name]) == 1, name
    assert "ops.verify_host" not in by
    (h0, h1, tid, args), = by["light.hop_verify"]
    assert args == {"n": 101, "stages": 2, "on_device": True}
    # the +2/3 stage's host half is the entry layer's span, as in
    # verify_commit_light: entry and prep metrics read a hop as they read
    # an adjacent step
    (v0, v1, vtid, vargs), = by["verify_commit"]
    assert vargs["mode"] == "light" and vargs["n"] == 100
    (l0, l1, _t, _a), = by["light.light_check"]
    assert l0 <= v0 <= v1 <= l1 <= h0
    (p0, p1, ptid, _a), = by["ops.pipeline_wait"]
    assert ptid == tid == vtid and h0 <= p0 <= p1 <= h1
    assert any(vtid == t and v0 <= s <= e <= v1
               for s, e, t, _a in by["verify_commit.prep_fused"])
