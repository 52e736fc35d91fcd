"""Tier-1 face of the unified ingress fabric (ISSUE 17).

Same pattern as test_vote_ingress_isolated.py: the container lacks the
`cryptography` wheel, so the fabric suite (tests/test_ingress_fabric.py
— adaptive-controller policy [deepen-under-flood / shrink-when-idle /
deadline-aware flush], lane-keyed knob resolution with legacy
deprecation, poisoned-window isolation, stepped semantics, cross-lane
stats parity) and the `tools/prep_bench.py --fabric` gate run in
SUBPROCESSES with TM_TPU_PUREPY_CRYPTO=1, which must never leak into
the main pytest process.
"""

import os
import subprocess
import sys

import pytest


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _purepy_env():
    from tendermint_tpu.libs import jaxcache

    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    env.pop("TM_TPU_DONATE", None)
    env.pop("TM_TPU_MESH", None)
    jaxcache.set_env(env)
    return env


# -- subprocess faces ----------------------------------------------------


def test_ingress_fabric_suite_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_ingress_fabric runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_ingress_fabric.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=60,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, \
        f"isolated test_ingress_fabric run failed:\n{tail}"


def test_prep_bench_fabric_gate():
    """ISSUE 17 satellite: the --fabric gate — all four lane patterns on
    ONE scheduler + completer thread, the adaptive window moving BOTH
    directions under real kernels with a slow readback, exactly the
    forged signature rejected, zero pool-slot leak — wired into tier-1
    through the isolated runner."""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--fabric",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=120,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0, f"--fabric gate failed:\n{out}\n{err[-2000:]}"
