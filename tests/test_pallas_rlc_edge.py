"""RLC fast-accept kernel against the ZIP-215 edge battery (its own
bucket shape; see tests/_rlc.py)."""

import pytest

pytest.importorskip("jax")

from tendermint_tpu.ops import pallas_rlc as pr  # noqa: E402
from tests.test_ops import _edge_entries  # noqa: E402
from _rlc import _deterministic_z, _oracle  # noqa: E402,F401


class TestRlcEdgeVectors:
    @pytest.mark.time_limit(510)  # 140-161 s on a cold cache
    def test_edge_vectors_bit_exact(self):
        """The ZIP-215 edge battery (small-order points, non-canonical
        encodings, s >= L, corruptions) through the RLC path must match
        the oracle per signature — valid lanes accept directly, mixed
        lanes reject and the host fallback restores exact per-sig
        semantics."""
        entries = _edge_entries()
        res = pr.verify_batch_rlc(entries, block=4, interpret=True)
        assert res.tolist() == _oracle(entries)
