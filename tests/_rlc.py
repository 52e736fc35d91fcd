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


CASES = ["valid", "padding", "forged_first", "forged_last", "s_ge_L",
         "noncanonical_pub"]


def _case_batch(case, n, m):
    """A batch for KernelSuite's shape (four lanes of m), broken as `case`
    says: n valid signatures, the last lane straddling live and padding;
    m + 1 of them (lanes 2 and 3 all padding); a forged signature in slot
    0 or slot m-1 of lane 1; s >= L in lane 2; or, in lane 0, a public
    key that encodes the identity non-canonically (y = 1 + p), which
    ZIP-215 decodes, so with R = [s]B it signs any message."""
    if case == "padding":
        return _sign_batch(m + 1)
    tamper = {"forged_first": {m}, "forged_last": {2 * m - 1}}.get(case, ())
    entries = _sign_batch(n, tamper=tamper)
    if case == "s_ge_L":
        pub, msg, sig = entries[2 * m]
        s = int.from_bytes(sig[32:], "little") + E.L
        entries[2 * m] = (pub, msg, sig[:32] + s.to_bytes(32, "little"))
    elif case == "noncanonical_pub":
        s = 0x1234567
        r = E.compress(E.scalar_mult(s, E.BASE))
        entries[1] = ((1 + E.P).to_bytes(32, "little"), b"nc",
                      r + s.to_bytes(32, "little"))
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
        lanes = pr.verify_rlc_compact(*args, self.M, block=block,
                                      interpret=True)
        assert lanes.tolist() == [True] * 4  # lane 0 small-order, 1-3 padding

    @pytest.mark.time_limit(600)  # the slot-major form's own trace
    @pytest.mark.parametrize("case", CASES)
    def test_packed_launch_equals_slot_major_and_blame(self, case):
        """The single-chip launch (one packed buffer, laid slot-major on
        the device) rejects the lanes the mesh's slot-major form of the
        same arguments rejects, which are the lanes that hold a signature
        ZIP-215 rejects; expand_lanes then blames exactly those."""
        from tendermint_tpu.ops import pallas_rlc as pr

        bucket, g, block = self._plan()
        m = self.M
        entries = _case_batch(case, self.N, m)
        (packed,) = pr.prepare_rlc(entries, bucket, m)
        lanes = pr.verify_rlc_compact(packed, m, block=block, interpret=True)
        slot_major = pr._jitted_rlc_verify_slot_major(m, g, block, True)(
            *pr.slot_major_args(packed, bucket, m))
        assert lanes.tolist() == np.asarray(slot_major)[0].astype(bool).tolist()
        want = _oracle(entries)
        assert lanes.tolist() == [all(want[lane * m:(lane + 1) * m])
                                  for lane in range(g)]
        assert pr.expand_lanes(lanes, entries, m).tolist() == want
        assert all(want) == (case in ("valid", "padding", "noncanonical_pub"))


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
            *pr.prepare_rlc(blk, bucket, m), m, block=block, interpret=True)
        dev = pr.rlc_cached_fn(ep, m, g, block, True)(
            *pr.prepare_rlc_cached(blk, bucket, ep, m))
        lanes_c = np.asarray(dev)[0].astype(bool)
        assert lanes_c.tolist() == lanes_u.tolist()
        assert lanes_c.tolist() == [
            not (forged and lane == self.FORGED // m) for lane in range(4)]
        assert pr.expand_lanes(lanes_c, blk, m).tolist() == [
            i not in tamper for i in range(self.N)]
