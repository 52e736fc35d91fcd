"""Test harness: force the CPU backend with an 8-device virtual mesh so
multi-chip sharding (pjit/shard_map over a Mesh) is exercised without TPU
hardware. Mirrors the reference's "multi-node without a cluster" pattern
(in-memory p2p transport, SURVEY.md §4) at the device level.

Backend *initialization* is lazy, so setting the platform here — before
any jax.devices()/jit call — keeps the whole test session (and, through
the inherited environment, every subprocess it spawns) on the in-process
CPU backend: a chip belongs to one process at a time, and the tests must
never be the process that holds it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The verify kernel is a large XLA program (~60s cold compile on one CPU
# core); persist compiled executables across test sessions. The cache is
# keyed per-machine (CPU feature tag) — loading another host's XLA:CPU
# AOT results risks SIGILL (tendermint_tpu.libs.jaxcache). The engine
# enables it on first use; kernel tests that jit without going through
# the engine need it on from the start.
from tendermint_tpu.libs import jaxcache  # noqa: E402

jaxcache.enable()

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """`native_required` tests skip cleanly where tm_native isn't built
    (pure-python containers without a toolchain) — the differential
    suites keep their pure-python halves running everywhere."""
    from tendermint_tpu.native import load as _load_native

    if _load_native() is None:
        skip = pytest.mark.skip(reason="tm_native module not built")
        for item in items:
            if "native_required" in item.keywords:
                item.add_marker(skip)

    # The end-to-end soak smokes are the most expensive subprocess items
    # in the suite; run them after everything else so a wall-clock-capped
    # CI run truncates the soak smokes, not the unit suites.
    items.sort(key=lambda it: it.fspath.basename == "test_soak_isolated.py")
