"""Pallas verification pipeline: differential conformance against the
ZIP-215 oracle (crypto/_edwards) and the backend dispatch wiring.

Runs the real 3-kernel pipeline (ops.pallas_verify) in interpret mode on
the CPU backend — the same traced program Mosaic compiles on TPU — over
the full edge-vector battery (small-order points, non-canonical
encodings, s >= L, corrupted keys/sigs/messages).
"""

import os

import numpy as np
import pytest

pytest.importorskip("jax")

from tendermint_tpu.crypto import _edwards as E  # noqa: E402
from tendermint_tpu.crypto import ed25519  # noqa: E402
from tendermint_tpu.ops import backend, pallas_verify as pv  # noqa: E402
from tests.test_ops import _edge_entries  # noqa: E402


def _oracle(entries):
    return [E.verify_zip215(p, m, s) for p, m, s in entries]


class TestPallasPipeline:
    @pytest.mark.time_limit(390)  # 96-122 s on a cold cache
    def test_edge_vectors_bit_exact(self):
        entries = _edge_entries()
        bucket = ((len(entries) + 7) // 8) * 8
        args = pv.prepare_compact(entries, bucket)
        res = pv.verify_compact(*args, block=8, interpret=True)
        assert res[: len(entries)].tolist() == _oracle(entries)
        # padding lanes (identity A/R, s = k = 0) must verify
        assert res[len(entries) :].all()

    def test_multi_block_grid(self):
        sk = ed25519.gen_priv_key(b"\x09" * 32)
        entries = [
            (sk.pub_key().bytes(), b"g%d" % i, sk.sign(b"g%d" % i))
            for i in range(24)
        ]
        entries[17] = (
            entries[17][0],
            entries[17][1],
            entries[17][2][:-1] + bytes([entries[17][2][-1] ^ 1]),
        )
        args = pv.prepare_compact(entries, 24)
        res = pv.verify_compact(*args, block=8, interpret=True)
        want = [i != 17 for i in range(24)]
        assert res.tolist() == want

    def test_backend_dispatch_uses_pallas(self, monkeypatch):
        """TM_TPU_PALLAS=1 routes verify_batch through the Pallas path
        (interpret mode off-TPU) and results match the oracle."""
        monkeypatch.setenv("TM_TPU_PALLAS", "1")
        backend.engine.cache_clear()
        # tiny pallas block so interpret mode stays fast
        monkeypatch.setattr(pv, "BLOCK", 8)
        try:
            entries = _edge_entries()[:10]
            res = backend.verify_batch(entries)
            assert res.tolist() == _oracle(entries)
        finally:
            backend.engine.cache_clear()

    def test_prepare_compact_matches_prepare_batch_semantics(self):
        """The s<L flag and byte packing agree between the XLA and Pallas
        preps for the same entries."""
        entries = _edge_entries()
        n = len(entries)
        bucket = ((n + 7) // 8) * 8
        a_t, r_t, s_t, k_t, sok_t = pv.prepare_compact(entries, bucket)
        legacy = backend.prepare_batch(entries, backend._bucket_for(n))
        assert (sok_t[0, :n].astype(bool) == legacy[6][:n]).all()
        for i, (pk, _, sig) in enumerate(entries):
            assert bytes(a_t[:, i]) == pk
            assert bytes(r_t[:, i]) == sig[:32]
            assert bytes(s_t[:, i]) == sig[32:]
