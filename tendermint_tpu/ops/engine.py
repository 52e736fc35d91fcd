"""The process's verification-engine decision: platform, kernel family
and Pallas `interpret` mode, made ONCE, in one place.

Every module that launches a kernel asks `engine()` instead of reading
`jax.default_backend()` itself, so the whole process agrees on what runs
where and a caller (chip_smoke.py, /status, bench.py) can ask what was
chosen:

- platform   what JAX initialised. A TPU build that finds no chip falls
             to "cpu" without raising — that is logged here, once, and
             is the thing `describe()` exposes so nothing downstream can
             mistake a CPU run for a device run.
- pallas/rlc the kernel family. TPU: the Pallas RLC fast-accept pipeline
             (ops/pallas_rlc). Elsewhere: the XLA op-graph kernels
             (ops/ed25519_verify), which compile natively on CPU where
             Pallas could only interpret. TM_TPU_PALLAS / TM_TPU_RLC
             force either way (tests force the Pallas family onto the
             CPU interpreter at tiny shapes).
- interpret  True exactly when the platform is not a TPU. On a TPU a
             Pallas kernel is always compiled by Mosaic; a kernel that
             fails to compile raises to its caller — there is no retry
             in interpret mode, no swap to the op-graph kernel and no
             host fallback anywhere behind this decision.
- donate     input-buffer donation at launch: on for TPU, off elsewhere
             (XLA:CPU ignores donation and warns per executable).
             TM_TPU_DONATE forces either way.

First use also turns on the persistent compilation cache
(libs/jaxcache), so every caller of the engine — a node, a library
caller of verify_commit, the tests — shares one cache without having to
remember to enable it.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import NamedTuple, Optional

_log = logging.getLogger("tendermint_tpu.ops.engine")


class Engine(NamedTuple):
    platform: str
    device_kind: str
    device_count: int
    pallas: bool
    rlc: bool
    donate: bool

    @property
    def on_tpu(self) -> bool:
        return self.platform == "tpu"

    @property
    def interpret(self) -> bool:
        """Pallas interpret mode: exactly when the platform is not a TPU."""
        return not self.on_tpu

    @property
    def kernel(self) -> str:
        """Name of the ed25519 kernel family this process launches."""
        if not self.pallas:
            return "xla"
        return "pallas_rlc" if self.rlc else "pallas"

    def describe(self) -> dict:
        return {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
            "kernel": self.kernel,
            "interpret": self.interpret if self.pallas else None,
            "donate": self.donate,
        }


def _flag(name: str, default: bool) -> bool:
    env = os.environ.get(name)
    return default if env is None else env != "0"


@functools.lru_cache(maxsize=1)
def engine() -> Engine:
    from ..libs import jaxcache

    jaxcache.enable()
    import jax

    platform = jax.default_backend()
    devices = jax.devices()
    on_tpu = platform == "tpu"
    pallas = _flag("TM_TPU_PALLAS", on_tpu)
    eng = Engine(
        platform=platform,
        device_kind=devices[0].device_kind,
        device_count=len(devices),
        pallas=pallas,
        rlc=pallas and _flag("TM_TPU_RLC", on_tpu),
        donate=_flag("TM_TPU_DONATE", on_tpu),
    )
    if on_tpu or os.environ.get("JAX_PLATFORMS"):
        _log.info("verification engine: %s", eng.describe())
    else:
        # nobody asked for this platform: JAX looked for an accelerator,
        # found none and carried on. Everything still verifies — on the
        # host CPU — so say so where an operator will see it.
        _log.warning(
            "verification engine: no accelerator found, JAX fell back to "
            "%r; signatures verify through the %s kernels on the host CPU "
            "(set JAX_PLATFORMS=cpu to make that explicit)",
            platform, eng.kernel,
        )
    return eng


def resolved() -> Optional[Engine]:
    """The decision if some caller already made it, else None — for
    read-only reporters (/status) that must never be the thing that
    initialises the backend."""
    return engine() if engine.cache_info().currsize else None
