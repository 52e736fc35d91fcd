"""RLC fast-accept kernel (ops.pallas_rlc): differential conformance
against the ZIP-215 oracle, lane-reject fallback blame, scalar-prep
parity (native C vs pure Python), and backend dispatch wiring. The edge
battery, the async pipeline and the sharded program trace shapes of their
own and live in test_pallas_rlc_{edge,dispatch,sharded}.py (tests/_rlc.py).

Runs the real 3-kernel RLC pipeline in interpret mode at tiny buckets —
the same traced program Mosaic compiles on TPU (chip_smoke.py runs it
compiled, at the 10240 and 81920 buckets).
"""

import os

import numpy as np
import pytest

pytest.importorskip("jax")

from tendermint_tpu.ops import backend, pallas_rlc as pr  # noqa: E402
from _rlc import _deterministic_z, _sign_batch  # noqa: E402,F401


class TestRlcKernel:
    @pytest.mark.time_limit(600)  # 191-242 s on a cold cache (first trace + XLA:CPU compile of the bucket-16 shape): the maximum
    def test_valid_batch_with_straddling_padding(self):
        # 14 live sigs in a 16-sig bucket: one lane straddles live/padding
        entries = _sign_batch(14)
        res = pr.verify_batch_rlc(entries, block=4, interpret=True)
        assert res.tolist() == [True] * 14

    def test_lane_reject_falls_back_per_sig(self):
        entries = _sign_batch(14, tamper={6})
        res = pr.verify_batch_rlc(entries, block=4, interpret=True)
        assert res.tolist() == [i != 6 for i in range(14)]

    def test_all_valid_small_order_lane_fast_accepts(self):
        """A lane of entirely-valid small-order signatures must accept
        WITHOUT the fallback: [8]e_j = O for each, so the combination
        [8]acc = O identically (torsion cancels under the cofactor)."""
        ident_pk = (1).to_bytes(32, "little")
        entries = [(ident_pk, b"m%d" % i, bytes(64)) for i in range(pr.M)]
        args = pr.prepare_rlc(entries, 4 * pr.M)  # shape shared with above
        lanes = pr.verify_rlc_compact(*args, block=4, interpret=True)
        assert lanes.tolist() == [True] * 4  # lane 0 small-order, 1-3 padding

    def test_scalar_prep_native_matches_python(self):
        entries = _sign_batch(8)
        from tendermint_tpu.ops.backend import _challenges, _pack_rows
        from tendermint_tpu.native import load as _load_native

        native = _load_native()
        if native is None:
            pytest.skip("native module unavailable")
        pub, r_enc, s_enc = _pack_rows(entries, 8)
        ks = _challenges(r_enc, pub, [m for _, m, _ in entries])
        k_enc = np.frombuffer(ks, dtype=np.uint8).reshape(8, 32)
        z = pr._gen_z(8)
        a = native.ed25519_rlc_scalars(
            s_enc.tobytes(), k_enc.tobytes(), z.tobytes(), pr.M
        )
        b = pr._rlc_scalars_py(s_enc.tobytes(), k_enc.tobytes(), z.tobytes(), pr.M)
        assert a == b

    def test_seeded_z_deterministic(self):
        assert (pr._gen_z(8) == pr._gen_z(8)).all()
        # slot-0 coefficients are fixed at 1 (ignored entries stay zero)
        os.environ.pop("TM_TPU_RLC_SEED", None)
        z1, z2 = pr._gen_z(8), pr._gen_z(8)
        assert (z1[:, 16:] == 0).all()
        assert (z1 != z2).any(), "unseeded z must be random per batch"

    def test_backend_dispatch_uses_rlc(self, monkeypatch):
        """TM_TPU_PALLAS=1 + TM_TPU_RLC=1 routes verify_batch through the
        RLC fast-accept path on the CPU interpret backend."""
        monkeypatch.setenv("TM_TPU_PALLAS", "1")
        monkeypatch.setenv("TM_TPU_RLC", "1")
        # tiny lane blocks so interpret mode stays fast (env var is read
        # at module import; patch the module attribute)
        monkeypatch.setattr(pr, "BLOCK_LANES", 4)
        backend.engine.cache_clear()
        try:
            entries = _sign_batch(10, tamper={3})
            res = backend.verify_batch(entries)
            assert res.tolist() == [i != 3 for i in range(10)]
        finally:
            backend.engine.cache_clear()
