"""Tier-1 face of the verification fleet (ISSUE 18).

Same pattern as test_ingress_fabric_isolated.py: the container lacks
the `cryptography` wheel, so the real-ed25519 fleet suite
(tests/test_fleet.py — local vs through-fleet verdict AND blame parity
per lane over real sockets and real CPU kernels) and the
`tools/prep_bench.py --fleet` gate run in SUBPROCESSES with
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


# -- subprocess faces ----------------------------------------------------


def test_fleet_suite_under_purepy_fallback():
    """Re-runs the whole fleet suite — wire round-trips/adversarial
    frames, socket service behavior, local-vs-fleet verdict+blame
    parity, and the simnet shared-fleet scenario — in one purepy
    subprocess (those modules skip themselves in a crypto-less main
    process because importing the ops package pulls the crypto stack)."""
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; the fleet suite runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_fleet_wire.py"),
            os.path.join(here, "test_fleet_service.py"),
            os.path.join(here, "test_fleet.py"),
            os.path.join(here, "test_simnet_fleet.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=90,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_fleet run failed:\n{tail}"


def test_prep_bench_fleet_gate():
    """ISSUE 18 satellite: the --fleet gate — two client nodes'
    same-epoch blocks coalesce into fewer launches than solo through one
    fleet server over real sockets, the one forged signature demuxes to
    the right node/row, a mid-window fleet kill loses zero items (host
    fallback) with automatic rejoin after restart, zero pool-slot leak —
    wired into tier-1 through the isolated runner."""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--fleet",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=180,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0, f"--fleet gate failed:\n{out}\n{err[-2000:]}"
