"""What the RLC kernel's test files share (tests/test_pallas_rlc*.py, one
file per traced shape so that `--dist loadfile` runs them side by side:
each shape costs its process 70-100 s of interpret-mode tracing)."""

import pytest

from tendermint_tpu.crypto import _edwards as E
from tendermint_tpu.crypto import ed25519


def _oracle(entries):
    return [E.verify_zip215(p, m, s) for p, m, s in entries]


@pytest.fixture(autouse=True)
def _deterministic_z(monkeypatch):
    monkeypatch.setenv("TM_TPU_RLC_SEED", "1234")


def _sign_batch(n, tamper=()):
    entries = []
    for i in range(n):
        sk = ed25519.gen_priv_key(bytes([i + 1]) * 32)
        m = b"rlc-%d" % i
        sig = sk.sign(m)
        if i in tamper:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        entries.append((sk.pub_key().bytes(), m, sig))
    return entries
