"""Tier-1 face of device-batched live-vote ingress (ISSUE 15).

Same pattern as test_ingress_isolated.py: the container lacks the
`cryptography` wheel, so the vote-ingress suite (tests/test_vote_ingress.py
— batched-vs-sequential add_vote error parity, equivocation evidence,
DispatchError poisoned-window isolation, stepped determinism, the
HasVoteBits wire round-trip) and the `tools/prep_bench.py --votes` gate
run in SUBPROCESSES with TM_TPU_PUREPY_CRYPTO=1, which must never leak
into the main pytest process (even envelope parsing pulls the crypto
import chain, so there are no in-process units here).
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


def test_vote_ingress_suite_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_vote_ingress runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_vote_ingress.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=60,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_vote_ingress run failed:\n{tail}"


def test_prep_bench_votes_gate():
    """ISSUE 15 satellite: the --votes gate — vote-window fusing proven
    by launch count (N gossiped votes in <= K device launches), exactly
    the forged signature rejected, zero pool-slot leak — wired into
    tier-1 through the isolated runner."""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--votes",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=90,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0, f"--votes gate failed:\n{out}\n{err[-2000:]}"
