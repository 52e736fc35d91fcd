"""Loader for the native C++ module (native/tm_native.cpp).

Builds on first use with the in-image toolchain (g++), caches the shared
object under native/_build (git-ignored: a fresh clone builds it), and
returns None when the build or the load fails — every caller keeps a
pure-Python path with identical outputs. That degradation is LOUD: the
failure, with the compiler's own stderr, is logged once; chip_smoke.py
refuses to pass without the module. TM_TPU_NO_NATIVE=1 is the explicit
way to run the pure-Python paths.

The entries on a verified commit's path time their own sections
(tm_native.cpp, namespace gil). `traced_call` is how the program calls
them: with the span tracer on, a section that gave the GIL up becomes two
records on the calling thread, `<prefix>.native` (the work) and
`<prefix>.gil` (the wait to win it back); a section that kept it (too
short to be worth a hand-over: the entry decides by the size of its input)
becomes `<prefix>.native` alone, with `held: True` among its args.
"""

from __future__ import annotations

import importlib.util
import logging
import os
import sys
import sysconfig
import threading
import time

from .observability.trace import TRACER as _TRACER

_log = logging.getLogger("tendermint_tpu.native")

_lock = threading.Lock()
_module = None
_tried = False

_ROOT = os.path.join(os.path.dirname(__file__), "..", "native")
_BUILD = os.path.join(_ROOT, "_build")


def _so_path() -> str:
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_BUILD, f"tm_native{suffix}")


def _build() -> bool:
    src = os.path.join(_ROOT, "tm_native.cpp")
    if not os.path.exists(src):
        return False
    os.makedirs(_BUILD, exist_ok=True)
    import subprocess

    out = _so_path()
    # build beside the target and rename into place: a second process
    # starting on a fresh clone must never dlopen a half-written file
    tmp = f"{out}.{os.getpid()}.tmp"
    include = sysconfig.get_path("include")
    cmd = [
        "g++", "-O3", "-march=x86-64-v3", "-funroll-loops", "-shared", "-fPIC", "-std=c++17",
        f"-I{include}", src, "-o", tmp,
    ]
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        _log.error("tm_native build did not run (%s): %r — continuing on "
                   "the pure-Python paths", " ".join(cmd[:2]), e)
        return False
    if res.returncode != 0 or not os.path.exists(tmp):
        _log.error(
            "tm_native build failed (rc=%d) — continuing on the "
            "pure-Python paths. Compiler output:\n%s",
            res.returncode, res.stderr.decode("utf-8", "replace")[-4000:],
        )
        return False
    os.replace(tmp, out)
    return True


def load():
    """Returns the tm_native module or None."""
    global _module, _tried
    with _lock:
        if _module is not None or _tried:
            return _module
        _tried = True
        if os.environ.get("TM_TPU_NO_NATIVE"):
            return None
        so = _so_path()
        src = os.path.join(_ROOT, "tm_native.cpp")
        if not os.path.exists(so) or (
            os.path.exists(src) and os.path.getmtime(src) > os.path.getmtime(so)
        ):
            if not _build():
                return None
        spec = importlib.util.spec_from_file_location("tm_native", so)
        if spec is None or spec.loader is None:
            return None
        mod = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(mod)
        except ImportError as e:
            _log.error("tm_native built but failed to load from %s: %r — "
                       "continuing on the pure-Python paths", so, e)
            return None
        _module = mod
        return _module


# tm_native reads CLOCK_MONOTONIC; the tracer reads time.perf_counter.
# Where that is another clock, no native span is recorded: never a span
# on a second clock.
_ONE_CLOCK = (time.get_clock_info("perf_counter").implementation
              == "clock_gettime(CLOCK_MONOTONIC)")


def traced_call(mod, entry: str, prefix: str, *args):
    """`mod.entry(*args)`, `mod` the loaded module. With the tracer on,
    the call's timed sections (tm_native.last_sections(), the module's
    own clock reads) are recorded on the calling thread after the fact:
    `<prefix>.native` over [t_released, t_wanted] and `<prefix>.gil` over
    [t_wanted, t_got], one pair a section, args `entry` and `section`. A
    section that held the GIL records `<prefix>.native` alone, with
    `held: True`: it waited for nothing, and a wait of length zero would
    only dilute the percentiles of those that did.
    With the tracer off this is the call and one attribute check."""
    res = getattr(mod, entry)(*args)
    if _TRACER.enabled and _ONE_CLOCK:
        # a shared object built before the sections existed records none
        spans = []
        for i, (released, wanted, got, held) in enumerate(
                getattr(mod, "last_sections", list)()):
            at = {"entry": entry, "section": i}
            if held:
                at["held"] = True
            spans.append((prefix + ".native", released, wanted, at))
            if not held:
                spans.append((prefix + ".gil", wanted, got, at))
        _TRACER.record_all(spans)
    return res


def gil_stats() -> dict:
    """{entry: (sections, free_s, wait_s, held)} since the module was
    loaded: how many sections of its timed entries gave the GIL up, the
    seconds they ran without it, the seconds their threads then waited to
    win it back, and how many sections kept it (commit_prep_fused: all
    three of a commit under 1 024 selected rows, two of three above).
    Empty until the module is loaded (a snapshot builds nothing)."""
    fn = getattr(_module, "gil_stats", None)
    return fn() if fn is not None else {}


def columns(entry: str, data):
    """`entry(data)` of the module — one of the single-pass wire parses
    (commit_decode_columns, valset_decode_columns): its column tuple, or
    None where the parse answers None, the module is absent, or the module
    was built before `entry` existed (native/_build is not tracked: a stale
    shared object reads as absent, never as an error). Spans
    `wire.columns.native` / `wire.columns.gil` (traced_call)."""
    mod = load()
    if not hasattr(mod, entry):
        return None
    return traced_call(mod, entry, "wire.columns", data)
