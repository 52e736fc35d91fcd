"""RLC fast-accept kernel at 2 signatures a lane, uncached and warm-epoch
pipelines (its own traced shapes; the suites are in tests/_rlc.py)."""

import pytest

pytest.importorskip("jax")

from _rlc import CachedSuite, KernelSuite, _deterministic_z  # noqa: E402,F401


class TestRlcKernelM2(KernelSuite):
    M, N, FORGED = 2, 7, 3


class TestRlcCachedM2(CachedSuite):
    M, N, FORGED = 2, 7, 3
