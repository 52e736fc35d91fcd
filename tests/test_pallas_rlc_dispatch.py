"""RLC lane verdicts through the shared async pipeline (its own bucket
shape; see tests/_rlc.py)."""

import pytest

pytest.importorskip("jax")

from tendermint_tpu.ops import backend, pallas_rlc as pr  # noqa: E402
from _rlc import _deterministic_z, _sign_batch  # noqa: E402,F401


class TestRlcPipelineDispatch:
    @pytest.mark.time_limit(570)  # 91-185 s on a cold cache
    def test_pipeline_dispatch_rlc_lane_expansion(self, monkeypatch):
        """The shared async pipeline expands RLC lane verdicts back to
        per-signature verdicts (with fallback blame on reject lanes)."""
        monkeypatch.setenv("TM_TPU_PALLAS", "1")
        monkeypatch.setenv("TM_TPU_RLC", "1")
        backend.engine.cache_clear()
        monkeypatch.setattr(pr, "BLOCK_LANES", 4)
        from tendermint_tpu.ops import pallas_verify as pv
        monkeypatch.setattr(pv, "BLOCK", 16)  # _pallas_bucket granularity
        from tendermint_tpu.ops.pipeline import AsyncBatchVerifier

        v = AsyncBatchVerifier()
        try:
            entries = _sign_batch(12, tamper={5})
            res = v.submit(entries).result(timeout=600)
            assert res.tolist() == [i != 5 for i in range(12)]
        finally:
            v.close()
            backend.engine.cache_clear()
