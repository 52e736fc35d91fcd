"""Tier-1 face of the light verification service (ISSUE 11).

Same pattern as test_mesh_isolated.py / test_simnet_isolated.py: the
container lacks the `cryptography` wheel, so the service suite
(tests/test_light_service.py — parity, streaming, RPC endpoint, the
simnet churn e2e with 200+ clients) and the `tools/prep_bench.py
--light` coalescing/parity/leak gate run in SUBPROCESSES with
TM_TPU_PUREPY_CRYPTO=1, which must never leak into the main pytest
process.
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


@pytest.mark.time_limit(360)  # re-runs test_light_service.py: 102 s on a cold cache
def test_light_service_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_light_service runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_light_service.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=330,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_light_service run failed:\n{tail}"


def test_prep_bench_light_gate():
    """ISSUE 11 satellite: the --light gate — cross-request same-epoch
    coalescing proven by launch count, verdict/blame parity vs the
    sequential verifier, memoized resubmission launches nothing, zero
    pool-slot leak — wired into tier-1 through the isolated runner."""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--light",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=90,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0, f"--light gate failed:\n{out}\n{err[-2000:]}"
