"""The process's persistent JAX compilation cache — the one place that
decides where it lives.

Placement: when `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and this module sets NO directory in code — an operator (or a bench
harness that keeps a warm cache between runs) places the cache from
outside. Unset, the cache goes to `<checkout>/.jax_cache/<machine_tag>`:
a pure function of the checkout and the host, so every process of a run
(node, chip_smoke.py, bench.py, the tests and their subprocesses) and
every later run finds the same entries. The path is part of what a run
can hit, so it is never derived from a pid, a temp name or a clock.

The per-host sub-directory exists for the CPU backend: XLA:CPU AOT
results are compiled for the build machine's exact CPU feature flags,
and loading them on a host with a different CPU risks SIGILL.

`enable()` is called once by the device engine on first use
(ops/engine.py); nothing else needs to call it. `set_env()` hands the
same placement to a child process's environment.
"""

from __future__ import annotations

import hashlib
import os
import threading

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
)


def machine_tag() -> str:
    """Short tag identifying this host's CPU feature set."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 reports "flags", ARM reports "Features"
                if line.startswith(("flags", "Features")):
                    return hashlib.sha256(line.encode()).hexdigest()[:12]
    except OSError:
        pass
    import platform

    # No readable cpuinfo (non-Linux / hardened container): there is no
    # feature list to key on, so fall back to machine|processor|version.
    # processor is often "" there, and version (kernel build) churns on
    # kernel upgrades — accepted: a cold recompile on upgrade beats two
    # different-featured hosts silently sharing AOT executables.
    u = platform.uname()
    return hashlib.sha256(
        f"{u.machine}|{u.processor}|{u.version}".encode()
    ).hexdigest()[:12]


def cache_dir() -> str:
    """The directory compiled executables persist in: the externally set
    one verbatim, else the in-checkout per-host default."""
    return os.environ.get(ENV_DIR) or os.path.join(
        _CHECKOUT, ".jax_cache", machine_tag()
    )


_mtx = threading.Lock()
_enabled = False
_counts = {"requests": 0, "hits": 0, "writes": 0}
_compiles: list = []  # (fun_name, seconds) per backend compile-or-load

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _mtx:
            _counts[key] += 1


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        with _mtx:
            _compiles.append((str(kw.get("fun_name", "?")), float(secs)))


def enable() -> None:
    """Turn the persistent cache on for this process (idempotent) and
    start counting its traffic. With JAX_COMPILATION_CACHE_DIR set the
    directory is JAX's own reading of that variable — untouched here."""
    global _enabled
    with _mtx:
        if _enabled:
            return
        _enabled = True
    import jax

    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def counters() -> dict:
    """Cache traffic since enable(): `requests` compiles that consulted
    the persistent cache, `hits` served from it, `writes` new entries
    stored, and `compiles` — (jitted function name, seconds) for every
    backend compile-or-load, in order. requests - hits is the number of
    executables this process had to build."""
    with _mtx:
        return dict(_counts, compiles=list(_compiles))


def set_env(env: dict) -> dict:
    """Give a child process's environment this process's cache placement.
    An externally set directory passes through unchanged."""
    env.setdefault(ENV_DIR, cache_dir())
    return env
