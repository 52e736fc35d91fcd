"""Device-batched transaction ingress (ISSUE 13): batched CheckTx must
be field-identical to the sequential host path — accept, bad-signature,
bad-nonce, duplicate, legacy/val: passthrough, malformed envelopes —
while signature windows ride the shared pipeline at PRIORITY_INGRESS and
a consensus commit preempts queued tx superbatches. Plus recheck-after-
commit parity under the held mempool lock, DispatchError poisoned-window
isolation (failed txs stay retryable), and the simnet flood: signed txs
injected mid-run through a partition+heal, consensus stays live, no tx
is lost silently, and the run is replay-exact.

Needs a working ed25519 signer: with the `cryptography` wheel the module
runs directly; without it, tests/test_ingress_isolated.py re-runs it in
a subprocess under TM_TPU_PUREPY_CRYPTO=1.
"""

import hashlib
import importlib.util
import os
import sys
import time

import pytest

if importlib.util.find_spec("cryptography") is None and not os.environ.get(
    "TM_TPU_PUREPY_CRYPTO"
):
    pytest.skip(
        "needs an ed25519 signer (cryptography wheel or the isolated runner)",
        allow_module_level=True,
    )

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tendermint_tpu.abci import LocalClient  # noqa: E402
from tendermint_tpu.abci import types as abci  # noqa: E402
from tendermint_tpu.abci.kvstore import (  # noqa: E402
    KVStoreApplication,
    make_validator_tx,
)
from tendermint_tpu.config import MempoolConfig  # noqa: E402
from tendermint_tpu.crypto import ed25519 as ed  # noqa: E402
from tendermint_tpu.crypto import sr25519 as sr  # noqa: E402
from tendermint_tpu.mempool import (  # noqa: E402
    CODE_BAD_NONCE,
    CODE_BAD_SIGNATURE,
    DuplicateTxError,
    TxMempool,
)
from tendermint_tpu.mempool import ingress as ing  # noqa: E402
from tendermint_tpu.ops import epoch_cache as _epoch  # noqa: E402
from tendermint_tpu.ops import pipeline as pl  # noqa: E402
from tendermint_tpu.ops._testing import (  # noqa: E402
    drain_pool,
    mock_mempool_prepare,
)
from tendermint_tpu.ops.entry_block import EntryBlock  # noqa: E402


def _priv(tag: bytes):
    return ed.gen_priv_key(seed=hashlib.sha256(tag).digest())


def _sr_priv(tag: bytes):
    return sr.gen_priv_key(seed=hashlib.sha256(tag).digest())


def _mk_mp(ingress=None, max_tx_bytes: int = 4096) -> TxMempool:
    cfg = MempoolConfig()
    cfg.max_tx_bytes = max_tx_bytes
    return TxMempool(LocalClient(KVStoreApplication()), config=cfg,
                     ingress=ingress)


@pytest.fixture(scope="module")
def acc():
    """One shared verifier + accumulator for the parity/recheck tests:
    the same topology a node runs — every mempool in the process feeds
    the single device pipeline."""
    _epoch.reset(8)
    v = pl.AsyncBatchVerifier(depth=2)
    a = ing.IngressAccumulator(verifier=v, max_batch=64, window_ms=4.0)
    yield a
    a.close()
    v.close()


# -- envelope ------------------------------------------------------------


class TestEnvelope:
    def test_roundtrip(self):
        priv = _priv(b"env-rt")
        tx = ing.make_signed_tx(priv, b"k=v", nonce=7)
        stx = ing.parse_signed_tx(tx)
        assert stx is not None
        assert stx.scheme == ing.SCHEME_ED25519
        assert stx.pub == priv.pub_key().bytes()
        assert stx.nonce == 7
        assert stx.payload == b"k=v"
        assert stx.raw == tx
        assert ing.host_verify(stx)

    def test_tampered_payload_fails_verify(self):
        tx = bytearray(ing.make_signed_tx(_priv(b"env-tamper"), b"k=v", nonce=1))
        tx[-1] ^= 0x01
        stx = ing.parse_signed_tx(bytes(tx))
        assert not ing.host_verify(stx)

    def test_legacy_tx_has_no_envelope(self):
        assert ing.parse_signed_tx(b"plain_key=plain_value") is None
        assert ing.parse_signed_tx(b"") is None

    def test_truncated_raises(self):
        with pytest.raises(ing.MalformedTxError):
            ing.parse_signed_tx(ing.MAGIC)
        with pytest.raises(ing.MalformedTxError):
            ing.parse_signed_tx(ing.MAGIC + bytes([ing.SCHEME_ED25519]) + b"\x00" * 10)

    def test_unknown_scheme_raises(self):
        with pytest.raises(ing.MalformedTxError):
            ing.parse_signed_tx(ing.MAGIC + bytes([9]) + b"\x00" * 120)

    def test_sr25519_roundtrip(self):
        priv = _sr_priv(b"env-sr")
        tx = ing.make_signed_tx(priv, b"s=1", nonce=3, scheme=ing.SCHEME_SR25519)
        stx = ing.parse_signed_tx(tx)
        assert stx.scheme == ing.SCHEME_SR25519
        assert ing.host_verify(stx)

    def test_signed_bytes_excludes_signature(self):
        pub = bytes(range(32))
        tx = ing.encode_signed_tx(ing.SCHEME_ED25519, pub, 42, bytes(64),
                                  b"k=v")
        stx = ing.parse_signed_tx(tx)
        assert stx.signed_bytes() == (
            ing.MAGIC + bytes([ing.SCHEME_ED25519]) + pub
            + (42).to_bytes(8, "big") + b"k=v"
        )

    def test_dispatch_queue_orders_consensus_first(self):
        q = pl._PriorityQueue()
        q.put("ingress-1", priority=pl.PRIORITY_INGRESS)
        q.put("ingress-2", priority=pl.PRIORITY_INGRESS)
        q.put("commit", priority=pl.PRIORITY_CONSENSUS)
        assert q.best_priority() == pl.PRIORITY_CONSENSUS
        assert q.get_nowait() == "commit"
        # FIFO within a priority class
        assert q.get_nowait() == "ingress-1"
        assert q.get_nowait() == "ingress-2"
        assert q.empty()


# -- batched vs sequential CheckTx parity --------------------------------


def _parity_cases():
    """One ordered script of CheckTx submissions covering every verdict
    class; ed25519 signing is deterministic, so both mempools see
    byte-identical txs."""
    a = _priv(b"parity-a")
    b = _priv(b"parity-b")
    s = _sr_priv(b"parity-sr")
    cases = [
        ("a-n1", ing.make_signed_tx(a, b"pa1=1", nonce=1)),
        ("b-n1", ing.make_signed_tx(b, b"pb1=1", nonce=1)),
        ("a-n2", ing.make_signed_tx(a, b"pa2=2", nonce=2)),
        ("sr-n1", ing.make_signed_tx(s, b"psr=1", nonce=1,
                                     scheme=ing.SCHEME_SR25519)),
    ]
    bad = bytearray(ing.make_signed_tx(a, b"pa3=3", nonce=3))
    bad[-1] ^= 0x5A
    cases += [
        ("a-badsig", bytes(bad)),
        # nonce 1 <= recorded 2: replay rejection, sig itself valid
        ("a-replay", ing.make_signed_tx(a, b"pa1b=9", nonce=1)),
        # byte-identical resubmission of a-n1: seen-cache duplicate
        ("a-dup", ing.make_signed_tx(a, b"pa1=1", nonce=1)),
        ("legacy", b"plain=v"),
        ("valtx", make_validator_tx(b.pub_key().bytes(), 5)),
        ("malformed", ing.MAGIC + bytes([ing.SCHEME_ED25519]) + b"\x00" * 4),
        ("badscheme", ing.MAGIC + bytes([7]) + b"\x00" * 120),
        ("oversized", b"x" * 5000),
    ]
    return cases


def _run_cases(mp: TxMempool, cases):
    out = []
    for label, tx in cases:
        try:
            r = mp.check_tx(tx)
            out.append((label, "res", r.code, r.log, r.codespace,
                        r.gas_wanted, r.sender))
        except Exception as e:  # noqa: BLE001 — parity on exception class too
            out.append((label, "exc", type(e).__name__, str(e)))
    return out


class TestParity:
    def test_batched_matches_sequential(self, acc):
        cases = _parity_cases()
        seq = _run_cases(_mk_mp(ingress=None), cases)
        mp_b = _mk_mp(ingress=acc)
        bat = _run_cases(mp_b, cases)
        assert bat == seq
        # spot-check the interesting verdicts landed as designed
        by = {row[0]: row for row in bat}
        assert by["a-n1"][2] == 0
        assert by["a-badsig"][2:5] == (CODE_BAD_SIGNATURE,
                                       "invalid signature", "ingress")
        assert by["a-replay"][2] == CODE_BAD_NONCE
        assert by["a-dup"][1:3] == ("exc", "DuplicateTxError")
        assert by["legacy"][2] == 0
        assert by["valtx"][2] == 0
        assert by["malformed"][1:3] == ("exc", "MalformedTxError")
        assert by["badscheme"][1:3] == ("exc", "MalformedTxError")
        assert by["oversized"][1:3] == ("exc", "ValueError")

    def test_mempool_contents_identical(self, acc):
        cases = _parity_cases()
        mp_s = _mk_mp(ingress=None)
        mp_b = _mk_mp(ingress=acc)
        _run_cases(mp_s, cases)
        _run_cases(mp_b, cases)
        assert mp_b.txs_fifo() == mp_s.txs_fifo()
        assert mp_b.size() == mp_s.size()
        assert mp_b.size_bytes() == mp_s.size_bytes()
        # only the valid txs made it in: a-n1, b-n1, a-n2, sr-n1,
        # legacy, valtx
        assert mp_b.size() == 6

    def test_rejected_sig_is_retryable_with_fresh_nonce(self, acc):
        """A bad-signature rejection drops the seen-cache entry, so the
        corrected tx (same payload, properly signed) goes through."""
        for mp in (_mk_mp(ingress=None), _mk_mp(ingress=acc)):
            priv = _priv(b"retry-k")
            bad = bytearray(ing.make_signed_tx(priv, b"r=1", nonce=1))
            bad[-1] ^= 0x10
            assert mp.check_tx(bytes(bad)).code == CODE_BAD_SIGNATURE
            assert mp.check_tx(
                ing.make_signed_tx(priv, b"r=1", nonce=1)
            ).code == 0
            assert mp.size() == 1


# -- recheck after commit ------------------------------------------------


class TestRecheck:
    def test_recheck_after_commit_parity(self, acc):
        """update() runs under the caller-held lock and (on the batched
        path) resubmits survivors' signatures as one block-sized window:
        the surviving FIFO must match the sequential mempool exactly, and
        the batched path must not deadlock on its own lock."""
        a, b = _priv(b"rc-a"), _priv(b"rc-b")
        script = [
            ing.make_signed_tx(a, b"ra1=1", nonce=1),
            ing.make_signed_tx(a, b"ra2=2", nonce=2),
            ing.make_signed_tx(b, b"rb1=1", nonce=1),
            ing.make_signed_tx(b, b"rb2=2", nonce=2),
            b"plain1=v",
            make_validator_tx(a.pub_key().bytes(), 3),
        ]
        committed = [script[0], script[2], script[4]]
        deliver = [abci.ResponseDeliverTx(code=0) for _ in committed]
        fifos = []
        for ingress in (None, acc):
            mp = _mk_mp(ingress=ingress)
            for tx in script:
                assert mp.check_tx(tx).code == 0
            mp.lock()
            try:
                mp.update(1, committed, deliver)
            finally:
                mp.unlock()
            fifos.append(mp.txs_fifo())
            # a committed tx stays in the cache: resubmission is a dup
            with pytest.raises(DuplicateTxError):
                mp.check_tx(script[0])
        assert fifos[0] == fifos[1]
        assert set(fifos[0]) == {script[1], script[3], script[5]}


# -- QoS: consensus preempts queued ingress ------------------------------


class TestQoS:
    def test_commit_preempts_queued_ingress_windows(self):
        """Two ingress waves on a depth-1 mocked-device pipeline: wave 1
        is in flight and wave 2 is parked at the depth semaphore when a
        PRIORITY_CONSENSUS block arrives — the commit must jump the
        queue (preemption counted, wave-2 futures still pending when it
        completes) and every tx verdict must still land.
        """
        _epoch.reset(8)
        rtt = 0.12
        real = pl.AsyncBatchVerifier._prepare
        pl.AsyncBatchVerifier._prepare = staticmethod(
            mock_mempool_prepare(real, rtt)
        )
        v = pl.AsyncBatchVerifier(depth=1)
        a = ing.IngressAccumulator(verifier=v, max_batch=32, window_ms=2.0)
        try:
            privs = [_priv(b"qos-%d" % i) for i in range(8)]
            stxs = [
                ing.parse_signed_tx(
                    ing.make_signed_tx(privs[i % 8], b"q%d=v" % i,
                                       nonce=i // 8 + 1)
                )
                for i in range(128)
            ]
            commit_block = EntryBlock.from_entries(
                [(s.pub, s.signed_bytes(), s.sig) for s in stxs[:16]]
            )
            wave1 = [a.submit(s) for s in stxs[:32]]
            a.flush_now()
            time.sleep(rtt / 3)  # wave 1 launched, in flight
            wave2 = [a.submit(s) for s in stxs[32:]]
            a.flush_now()
            time.sleep(rtt / 4)  # wave 2 prepped, parked on the depth sem
            cfut = v.submit(commit_block, priority=pl.PRIORITY_CONSENSUS)
            assert all(cfut.result(timeout=60))
            pending = sum(1 for f in wave2 if not f.done())
            assert pending > 0, "commit should complete before queued ingress"
            assert all(f.result(timeout=60) is True for f in wave1 + wave2)
            assert v.preempted_total >= 1
            assert a.stats()["preemptions"] >= 1
            drain_pool(v._pool)
            assert v._pool.stats()["in_flight"] == 0
        finally:
            a.close()
            v.close()
            pl.AsyncBatchVerifier._prepare = real


# -- DispatchError: a poisoned window fails alone ------------------------


class TestDispatchError:
    def test_poisoned_window_fails_alone_and_is_retryable(self):
        """Prep blows up for exactly one window size: that window's
        check_tx futures raise DispatchError, its txs drop out of the
        seen-cache (retryable), and neighbouring windows are untouched.
        """
        _epoch.reset(8)
        poison_n = 5
        real = pl.AsyncBatchVerifier._prepare

        def poisoned(entries, *args, **kw):
            n = len(entries.entries) if hasattr(entries, "entries") else len(entries)
            if n == poison_n:
                raise RuntimeError("injected poison")
            return real(entries, *args, **kw)

        pl.AsyncBatchVerifier._prepare = staticmethod(poisoned)
        v = pl.AsyncBatchVerifier(depth=2)
        # giant window: only explicit flush_now() submits, so each wave
        # below is exactly one device window
        a = ing.IngressAccumulator(verifier=v, max_batch=256,
                                   window_ms=60_000.0)
        mp = _mk_mp(ingress=a)
        try:
            privs = [_priv(b"poison-%d" % i) for i in range(16)]

            def wave(lo, hi, nonce):
                futs = [
                    mp.check_tx_async(
                        ing.make_signed_tx(privs[i], b"dw%d=%d" % (i, nonce),
                                           nonce=nonce)
                    )
                    for i in range(lo, hi)
                ]
                a.flush_now()
                return futs

            for f in wave(0, 4, 1):  # healthy window before
                assert f.result(timeout=60).code == 0
            poisoned_futs = wave(4, 4 + poison_n, 1)
            for f in poisoned_futs:
                with pytest.raises(pl.DispatchError):
                    f.result(timeout=60)
            for f in wave(12, 16, 1):  # healthy window after
                assert f.result(timeout=60).code == 0
            assert a.stats()["dispatch_errors"] >= 1
            # the poisoned txs were dropped from the seen-cache: each is
            # retryable, and a 1-tx window passes the poison filter
            for i in range(4, 4 + poison_n):
                [f] = wave(i, i + 1, 1)
                assert f.result(timeout=60).code == 0
            assert mp.size() == 4 + poison_n + 4
        finally:
            a.close()
            v.close()
            pl.AsyncBatchVerifier._prepare = real


# -- simnet: signed-tx flood through a partition+heal --------------------


def _flood_run(seed: int):
    """4-node cluster, partition {0,1,2}|{3} at height 3 (quorum stays
    with the majority, so consensus never stalls), heal after 3 virtual
    seconds. Signed txs flood in at commits 2 and 4 — including a forged
    signature and a nonce replay — via node 0's commit hook, a
    deterministic point in the event loop. Returns the report plus the
    per-tx accounting."""
    from tendermint_tpu.simnet import Cluster, Fault

    faults = [Fault(kind="partition", at_height=3,
                    groups=[[0, 1, 2], [3]], duration=3.0)]
    c = Cluster(n_nodes=4, seed=seed, faults=faults)
    privs = [_priv(b"flood-%d" % i) for i in range(4)]
    results = {}  # tx -> ("res", code) | ("exc", type name)
    fired = set()

    def submit(node, tx):
        try:
            results[tx] = ("res", node.mp.check_tx(tx).code)
        except Exception as e:  # noqa: BLE001 — recorded, never dropped
            results[tx] = ("exc", type(e).__name__)

    def inject(wave: int):
        for i, n in enumerate(c.nodes):
            for j in range(2):
                submit(n, ing.make_signed_tx(
                    privs[i], b"f%d_%d_%d=v" % (wave, i, j),
                    nonce=(wave - 1) * 2 + j + 1,
                ))
        # adversarial traffic on node 0: a forged signature and a
        # nonce replay — both must be rejected, not lost
        forged = bytearray(ing.make_signed_tx(privs[0], b"forged%d=1" % wave,
                                              nonce=99 + wave))
        forged[-1] ^= 0x42
        submit(c.nodes[0], bytes(forged))
        submit(c.nodes[0], ing.make_signed_tx(privs[0], b"replay%d=1" % wave,
                                              nonce=1))

    def on_commit(height: int):
        if height == 2 and "w1" not in fired:
            fired.add("w1")
            inject(1)
        elif height == 4 and "w2" not in fired:
            fired.add("w2")
            inject(2)

    c.nodes[0].cs._height_events.append(on_commit)
    report = c.run_to_height(6, max_virtual_s=600.0)
    committed = set()
    for n in c.nodes:
        for h in range(1, n.bstore.height() + 1):
            blk = n.bstore.load_block(h)
            if blk is not None:
                committed.update(blk.data.txs)
    in_mempool = set()
    for n in c.nodes:
        in_mempool.update(n.mp.txs_fifo())
    c.stop()
    return report, results, committed, in_mempool


class TestSimnetFlood:
    def test_flood_through_partition_heal(self):
        report, results, committed, in_mempool = _flood_run(seed=13)
        assert report.ok, report.reason
        assert not report.violations
        assert len(results) == 20, "both waves must have been injected"
        rejected = 0
        for tx, (kind, detail) in results.items():
            if kind == "res" and detail == 0:
                # accepted: either committed into a block or still
                # sitting in some live mempool — never silently lost
                assert tx in committed or tx in in_mempool, (
                    "accepted tx lost: %r" % tx[:20]
                )
            else:
                rejected += 1
                assert tx not in committed
        # the forged-sig and replay txs per wave were rejected loudly
        assert rejected >= 2

    def test_replay_exact(self):
        r1, res1, _, _ = _flood_run(seed=21)
        r2, res2, _, _ = _flood_run(seed=21)
        assert r1.ok and r2.ok, (r1.reason, r2.reason)
        assert r1.fingerprint == r2.fingerprint
        assert res1 == res2
