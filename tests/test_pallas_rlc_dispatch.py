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

    def test_each_launch_expands_by_its_own_width(self, monkeypatch):
        """Launches of different sizes run at different lane widths, and
        the resolver expands each one's lane verdicts by the width THAT
        launch ran at: the rejected lane's signatures, and only they, are
        re-verified on the host, and the forged one is blamed. The
        kernels are stood in for by a launch that rejects one lane (the
        kernels themselves: test_pallas_rlc*.py); the spans of each
        launch carry its width."""
        import jax.numpy as jnp

        from tendermint_tpu.libs.metrics import ops_stats
        from tendermint_tpu.observability import trace as _tr
        from tendermint_tpu.ops.pipeline import AsyncBatchVerifier

        reject = {}  # lane width -> the lane the stand-in rejects

        def lanes_but_one(m, g, *_a, **_k):
            def launch(packed):
                # the launch's one buffer, at its own width and lanes
                assert packed.shape == (
                    pr.packed_layout(g * m, m, pr.PUB_WORDS)[-1],)
                return jnp.ones((1, g), jnp.int32).at[0, reject[m]].set(0)

            return launch

        monkeypatch.setenv("TM_TPU_PALLAS", "1")
        monkeypatch.setenv("TM_TPU_RLC", "1")
        monkeypatch.setattr(pr, "BLOCK_LANES", 4)
        monkeypatch.setattr(pr, "_jitted_rlc_verify", lanes_but_one)
        backend.engine.cache_clear()
        v = AsyncBatchVerifier()
        _tr.TRACER.clear()
        _tr.configure(enabled=True)
        try:
            # (signatures, forged one, width at 4-lane blocks, bucket)
            for n, forged, m, bucket in [(6, 3, 2, 8), (12, 5, 4, 16),
                                         (20, 9, 8, 32), (7, 6, 2, 8)]:
                reject[m] = forged // m
                before = ops_stats()
                res = v.submit(_sign_batch(n, tamper={forged})).result(
                    timeout=120)
                after = ops_stats()
                assert res.tolist() == [i != forged for i in range(n)], m
                # the lane's live signatures: m, or fewer in the last lane
                lane_live = min((forged // m + 1) * m, n) - forged // m * m
                assert (after["sigs_verified_host"]
                        - before["sigs_verified_host"]) == lane_live, m
                assert (after["rlc_launches_by_width"][str(m)]
                        - before["rlc_launches_by_width"].get(str(m), 0)) == 1
                assert (after["batches_by_bucket"][str(bucket)]
                        - before["batches_by_bucket"].get(str(bucket), 0)) == 1
        finally:
            _tr.configure(enabled=False)
            v.close()
            backend.engine.cache_clear()
        for name in ("pipeline.prep", "pipeline.dispatch"):
            args = [e[4] for e in _tr.TRACER.events() if e[0] == name]
            assert [(a["bucket"], a["m"]) for a in args] == [
                (8, 2), (16, 4), (32, 8), (8, 2)], name
