"""What the RLC kernel's test files share (tests/test_pallas_rlc*.py, one
file per traced shape so that `--dist loadfile` runs them side by side:
each shape costs its process 70-100 s of interpret-mode tracing). A lane
width's kernel tests are the two suites below, subclassed once per width
in a file of that width's own (test_pallas_rlc.py has 4, _m2 and _m8 the
others): at 4-lane blocks plan_bucket gives a batch of N its width M."""

import numpy as np
import pytest

from tendermint_tpu.crypto import _edwards as E
from tendermint_tpu.crypto import ed25519


def _oracle(entries):
    return [E.verify_zip215(p, m, s) for p, m, s in entries]


@pytest.fixture(autouse=True)
def _deterministic_z(monkeypatch):
    monkeypatch.setenv("TM_TPU_RLC_SEED", "1234")


def _sign_batch(n, tamper=()):
    entries = []
    for i in range(n):
        sk = ed25519.gen_priv_key(bytes([i + 1]) * 32)
        m = b"rlc-%d" % i
        sig = sk.sign(m)
        if i in tamper:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        entries.append((sk.pub_key().bytes(), m, sig))
    return entries


def _warm_block(entries):
    """(EntryBlock with gather indices, its epoch entry): the block as
    the pipeline sees it once the validator set's tables are resident."""
    from tendermint_tpu.ops import epoch_cache
    from tendermint_tpu.ops.entry_block import EntryBlock

    blk = EntryBlock.from_entries(entries)
    # a permutation: gather indices are the commit's order, not 0..n-1
    blk.val_idx = np.random.RandomState(len(entries)).permutation(
        len(entries)).astype(np.int32)
    ep = epoch_cache.EpochEntry(b"k" * 32, blk.pub[np.argsort(blk.val_idx)])
    blk.epoch_key = ep.key
    return blk, ep


def _host_reverifies():
    from tendermint_tpu.libs.metrics import ops_stats

    return ops_stats()["sigs_verified_host"]


class KernelSuite:
    """The uncached pipeline at width M: N live signatures in four lanes
    (a 4-lane block), the last lane straddling live and padding."""

    M = 0
    N = 0
    FORGED = 0  # in a full lane: a reject costs exactly M host re-verifies

    def _plan(self):
        from tendermint_tpu.ops import pallas_rlc as pr

        bucket, g, block, m = pr.plan_bucket(self.N, 4)
        assert (bucket, g, block, m) == (4 * self.M, 4, 4, self.M)
        assert (self.N - 1) // m == 3 and self.N % m, "no straddling lane"
        return bucket, g, block

    # the first of a file pays the shape's trace + XLA:CPU compile
    # (136-242 s on a cold cache): the maximum
    @pytest.mark.time_limit(600)
    def test_valid_batch_with_straddling_padding(self):
        from tendermint_tpu.ops import pallas_rlc as pr

        self._plan()
        entries = _sign_batch(self.N)
        res = pr.verify_batch_rlc(entries, block=4, interpret=True)
        assert res.tolist() == _oracle(entries) == [True] * self.N

    @pytest.mark.time_limit(600)
    def test_lane_reject_falls_back_per_sig(self):
        from tendermint_tpu.ops import pallas_rlc as pr

        entries = _sign_batch(self.N, tamper={self.FORGED})
        before = _host_reverifies()
        res = pr.verify_batch_rlc(entries, block=4, interpret=True)
        assert res.tolist() == [i != self.FORGED for i in range(self.N)]
        # blame is per signature: the rejected lane's M, nobody else's
        assert _host_reverifies() - before == self.M

    @pytest.mark.time_limit(600)
    def test_all_valid_small_order_lane_fast_accepts(self):
        """A lane of entirely-valid small-order signatures must accept
        WITHOUT the fallback: [8]e_j = O for each, so the combination
        [8]acc = O identically (torsion cancels under the cofactor)."""
        from tendermint_tpu.ops import pallas_rlc as pr

        bucket, _g, block = self._plan()
        ident_pk = (1).to_bytes(32, "little")
        entries = [(ident_pk, b"m%d" % i, bytes(64)) for i in range(self.M)]
        args = pr.prepare_rlc(entries, bucket, self.M)  # the shape above
        lanes = pr.verify_rlc_compact(*args, block=block, interpret=True)
        assert lanes.tolist() == [True] * 4  # lane 0 small-order, 1-3 padding


class CachedSuite:
    """The warm-epoch pipeline at width M against the uncached one."""

    M = 0
    N = 0
    FORGED = 0

    @pytest.mark.time_limit(600)  # first trace of the cached shape
    @pytest.mark.parametrize("forged", [False, True], ids=["valid", "forged"])
    def test_cached_lanes_equal_uncached(self, forged):
        from tendermint_tpu.ops import pallas_rlc as pr

        tamper = {self.FORGED} if forged else set()
        blk, ep = _warm_block(_sign_batch(self.N, tamper=tamper))
        bucket, g, block, m = pr.plan_bucket(len(blk), 4)
        assert m == self.M
        lanes_u = pr.verify_rlc_compact(
            *pr.prepare_rlc(blk, bucket, m), block=block, interpret=True)
        dev = pr.rlc_cached_fn(ep, m, g, block, True)(
            *pr.prepare_rlc_cached(blk, bucket, ep, m))
        lanes_c = np.asarray(dev)[0].astype(bool)
        assert lanes_c.tolist() == lanes_u.tolist()
        assert lanes_c.tolist() == [
            not (forged and lane == self.FORGED // m) for lane in range(4)]
        assert pr.expand_lanes(lanes_c, blk, m).tolist() == [
            i not in tamper for i in range(self.N)]
