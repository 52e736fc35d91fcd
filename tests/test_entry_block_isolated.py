"""Isolated runner for test_entry_block.py on containers without the
`cryptography` wheel.

The EntryBlock tests need a working ed25519 signer for their fixtures.
The pure-Python fallback (TM_TPU_PUREPY_CRYPTO=1) provides one, but the
flag must NOT be set inside the main pytest process: it changes how
`tendermint_tpu.crypto` imports for every module collected afterwards
and unlocks slow OpenSSL-dependent e2e failure paths. So when the wheel
is absent, this wrapper re-runs the whole module in a subprocess where
the flag can't leak."""

import os
import subprocess
import sys

import pytest


def test_entry_block_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_entry_block runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_entry_block.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=env,
        cwd=os.path.dirname(here),
        timeout=180,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_entry_block run failed:\n{tail}"
