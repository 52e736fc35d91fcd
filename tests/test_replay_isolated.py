"""Tier-1 face of chain-replay catch-up (ISSUE 14).

Same pattern as test_ingress_isolated.py: the container lacks the
`cryptography` wheel, so the replay suite (tests/test_blocksync_replay.py
— epoch-cut planning, range verification over a real signed chain,
forged-commit fallback parity, writer-thread ordering, speculation
hit/miss/discard edges, wake-event no-hot-spin) and the
`tools/prep_bench.py --replay` gate run in SUBPROCESSES with
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


def test_replay_suite_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_blocksync_replay runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_blocksync_replay.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=60,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_blocksync_replay run failed:\n{tail}"


def test_simnet_catchup_under_purepy_fallback():
    """ISSUE 14 e2e face: a crashed node rejoins far behind under churn
    + 10% drop links and catches up live through the ReplayEngine
    (tests/test_simnet_catchup.py: range hit-rate > 0.9 in
    SimReport.catchup, replay-exact across seeds)."""
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_simnet_catchup runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_simnet_catchup.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=120,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_simnet_catchup run failed:\n{tail}"


def test_prep_bench_replay_gate():
    """ISSUE 14 satellite: the --replay gate — range packing proven by
    launch count (W same-epoch heights -> ceil(W*sigs/bucket) launches,
    not W), mid-range forged-commit fallback with verify_commit_light's
    exact error string, zero pool-slot leak — wired into tier-1 through
    the isolated runner."""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--replay",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=120,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0, f"--replay gate failed:\n{out}\n{err[-2000:]}"
