"""Isolated runner for test_commit_block.py on containers without the
`cryptography` wheel (same pattern as test_entry_block_isolated.py: the
TM_TPU_PUREPY_CRYPTO flag must not leak into the main pytest process)."""

import os
import subprocess
import sys

import pytest


def test_commit_block_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_commit_block runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_commit_block.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=env,
        cwd=os.path.dirname(here),
        timeout=120,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_commit_block run failed:\n{tail}"
