"""The bring-up contract, CPU side (ISSUE 21): nothing here compiles a
kernel. chip_smoke.py itself only passes on the chip; what tier-1 can
hold is that it REFUSES off the chip, and that the paths that used to
hide the device stay closed:

- the compile cache is placed from outside or at one fixed in-checkout
  path (libs/jaxcache.py);
- the first verifier a library caller gets is the device verifier
  (crypto/batch.py resolves it — no import side effect needed);
- whatever path verifies a signature counts it (ops sigs_verified);
- a failing sr25519 kernel raises out of ops.mixed;
- a failing native build says why.
"""

import json
import logging
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _run(code_or_path, env_extra, cwd=REPO, timeout=120):
    """A fresh CPU-only interpreter; an env value of None unsets it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    for k, v in env_extra.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    argv = (
        [sys.executable, code_or_path]
        if code_or_path.endswith(".py")
        else [sys.executable, "-c", code_or_path]
    )
    return subprocess.run(argv, capture_output=True, text=True, env=env,
                          cwd=cwd, timeout=timeout)


def test_smoke_refuses_without_a_chip():
    t0 = time.monotonic()
    r = _run(os.path.join(REPO, "chip_smoke.py"), {})
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no TPU found" in r.stderr
    # no result line: the refusal comes before any data is built
    assert '"ok"' not in r.stdout and "building data" not in r.stdout


_FRESH_PROCESS = r"""
import json, os, sys
import tendermint_tpu.types.validation  # the ONLY tendermint_tpu import a caller made
assert "jax" not in sys.modules, "importing the types layer loaded jax"
from tendermint_tpu.crypto import batch, ed25519
bv = batch.create_batch_verifier(ed25519.gen_priv_key(b"\x01" * 32).pub_key())
from tendermint_tpu.libs import jaxcache
from tendermint_tpu.ops.engine import engine
eng = engine()  # first use enables the cache
import jax
print(json.dumps({
    "verifier": type(bv).__name__,
    "cache_dir": jaxcache.cache_dir(),
    "jax_cache_dir": jax.config.jax_compilation_cache_dir,
    "child_env": jaxcache.set_env({})["JAX_COMPILATION_CACHE_DIR"],
    "engine": eng.describe(),
}))
"""


def _fresh(env_extra, cwd=REPO):
    r = _run(_FRESH_PROCESS, env_extra, cwd=cwd)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_first_verifier_is_the_device_verifier_and_cache_is_in_checkout():
    # two runs from two working directories: the path is a pure function
    # of checkout + host
    a = _fresh({"JAX_COMPILATION_CACHE_DIR": None}, cwd="/")
    b = _fresh({"JAX_COMPILATION_CACHE_DIR": None})
    assert a["verifier"] == "Ed25519DeviceBatchVerifier"
    assert a["cache_dir"] == b["cache_dir"] == a["jax_cache_dir"]
    assert a["cache_dir"].startswith(os.path.join(REPO, ".jax_cache") + os.sep)
    assert a["child_env"] == a["cache_dir"]
    # on a CPU backend the engine says so: nothing to mistake for a chip
    assert a["engine"]["platform"] == "cpu" and a["engine"]["kernel"] == "xla"


def test_external_cache_dir_is_left_alone(tmp_path):
    outside = str(tmp_path / "placed-from-outside")
    out = _fresh({"JAX_COMPILATION_CACHE_DIR": outside})
    assert out["cache_dir"] == outside
    assert out["jax_cache_dir"] == outside  # JAX's own reading of the env
    assert out["child_env"] == outside      # children inherit it unchanged


def _host_count():
    from tendermint_tpu.libs.metrics import ops_stats

    s = ops_stats()
    return s["sigs_verified_host"], s["sigs_verified_device"]


def _signed(n, tag=b"t"):
    from tendermint_tpu.crypto import ed25519

    out = []
    for i in range(n):
        sk = ed25519.gen_priv_key(bytes([i + 1]) * 32)
        msg = tag + b"-%d" % i
        out.append((sk.pub_key(), msg, sk.sign(msg)))
    return out


def test_host_batch_verifier_counts_its_signatures():
    from tendermint_tpu.crypto import batch

    bv = batch.Ed25519HostBatchVerifier()
    for pk, msg, sig in _signed(20):
        bv.add(pk, msg, sig)
    h0, d0 = _host_count()
    ok, valid = bv.verify()
    assert ok and all(valid)
    h1, d1 = _host_count()
    assert (h1 - h0, d1 - d0) == (20, 0)


def test_first_small_commit_is_counted():
    """A commit under the device threshold is host-verified — and says
    so in ops_stats(), instead of reading 0/0 as it did when the host
    batch verifier sat behind the seam uncounted."""
    import chip_smoke
    from tendermint_tpu.types import validation

    job = chip_smoke.build_commit_jobs(7, "tiny", 5, 1)[0]
    h0, d0 = _host_count()
    validation.verify_commit(*job)
    h1, d1 = _host_count()
    assert (h1 - h0, d1 - d0) == (5, 0)


def test_raising_sr25519_kernel_propagates(monkeypatch):
    """The kernel is launched by the dispatcher: its failure reaches the
    caller as the batch's DispatchError, with the kernel's own exception
    as the cause, and is not retried on the host."""
    from tendermint_tpu.crypto import sr25519
    from tendermint_tpu.ops import backend, mixed
    from tendermint_tpu.ops import pallas_sr25519 as ps
    from tendermint_tpu.ops.pipeline import DispatchError

    class Boom(RuntimeError):
        pass

    def boom(*_a, **_k):
        raise Boom("Mosaic refused the kernel")

    monkeypatch.setenv("TM_TPU_PALLAS", "1")
    monkeypatch.setattr(ps, "prepare_sr25519", lambda chunk, bucket: ())
    monkeypatch.setattr(ps, "verify_sr25519_compact", boom)
    monkeypatch.setattr(mixed, "_host_sr_batch", lambda entries: pytest.fail(
        "a failed kernel must not be retried on the host"))
    backend.engine.cache_clear()
    try:
        key = sr25519.PubKey(b"\x00" * 32)
        for _ in range(2):  # and again: no sticky "use the host" state
            bv = mixed.Sr25519DeviceBatchVerifier()
            for _i in range(mixed.SR_DEVICE_THRESHOLD):
                bv.add(key, b"m", b"\x00" * 64)
            with pytest.raises(DispatchError, match="Mosaic refused") as e:
                bv.verify()
            assert isinstance(e.value.__cause__, Boom)
    finally:
        monkeypatch.undo()
        backend.engine.cache_clear()


def test_failed_native_build_logs_the_compiler(monkeypatch, tmp_path, caplog):
    from tendermint_tpu import native

    (tmp_path / "tm_native.cpp").write_text("this is not C++ {\n")
    monkeypatch.setattr(native, "_ROOT", str(tmp_path))
    monkeypatch.setattr(native, "_BUILD", str(tmp_path / "_build"))
    monkeypatch.setattr(native, "_module", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.delenv("TM_TPU_NO_NATIVE", raising=False)
    with caplog.at_level(logging.ERROR, logger="tendermint_tpu.native"):
        assert native.load() is None
        assert native.load() is None  # cached: the build is not retried
    errs = [r.getMessage() for r in caplog.records]
    assert len(errs) == 1 and "tm_native build failed" in errs[0]
    assert "error" in errs[0]  # g++'s own words
