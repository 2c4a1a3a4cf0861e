"""JAX process set-up shared by every entry point that jits: the broker
(``--router xla``), ``bench.py`` and ``chip_smoke.py``.

Platform choice is JAX's own (``JAX_PLATFORMS``, or the ``jax_platforms``
config as ``tests/conftest.py`` sets it): nothing here probes, retries or
switches platform. What this module adds is the two things a cold process
needs before its first jit — where the persistent compilation cache lives,
and a check that the device the process got is the one that was asked for.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout cache location (listed in .gitignore). The path is
#: part of the cache key, so it must not move between processes — never a
#: tempdir, a pid or a timestamp.
_REPO_CACHE = Path(__file__).resolve().parent.parent.parent / ".jax_cache"


#: process-wide tallies of JAX's own cache events (the cache they describe
#: is process-wide too): compiles that consulted the cache, executables
#: read back from it, entries written, and compile seconds the hits saved
_cache_events = {"requests": 0, "hits": 0, "writes": 0, "saved_s": 0.0}
_listening = False


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; → the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
    set in code. Unset: ``<checkout>/.jax_cache``. Call before the first
    jit of the process. Also starts counting cache hits and writes
    (``compile_cache_stats``)."""
    global _listening
    if not _listening:
        _listening = True
        names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
                 "/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "writes"}

        def on_event(event: str, **_kw) -> None:
            key = names.get(event)
            if key is not None:
                _cache_events[key] += 1

        def on_duration(event: str, secs: float, **_kw) -> None:
            if event == "/jax/compilation_cache/compile_time_saved_sec":
                _cache_events["saved_s"] += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # JAX's default skips compiles under a second — which is every small
        # dispatch shape a restarted broker wants back first
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the named scopes of the match programs (ops/partitioned.py) are
    # metadata, and by default the cache key leaves metadata out: an
    # executable cached before a scope was added comes back WITHOUT it, and
    # a profiler trace then shows the old names ("executables loaded from
    # the cache may have stale metadata", JAX's own note on this option).
    # The trace is how the device's time is read, so the key holds it.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    return str(_REPO_CACHE)


def compile_cache_stats() -> dict:
    """Persistent-cache activity of this process since
    ``setup_compile_cache`` (zeros when it was never called)."""
    out = dict(_cache_events)
    out["saved_s"] = round(out["saved_s"], 3)
    return out


def cpu_requested() -> bool:
    """Was the CPU backend asked for explicitly (``JAX_PLATFORMS=cpu`` in
    the environment — JAX folds it into the config — or the config set in
    code)?"""
    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


def device_identity() -> dict:
    """``{platform, device_kind, device_count}`` of the default backend, as
    JAX reports it. This is the first backend touch of a process: it takes
    the chip, and raises when the requested platform is not there.

    A process that did not ask for the CPU and got it anyway (no
    accelerator found; JAX falls back with a warning) raises too — a
    device router must never serve from a backend nobody chose."""
    devs = jax.devices()
    ident = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }
    if ident["platform"] == "cpu" and not cpu_requested():
        raise RuntimeError(
            "no accelerator found: JAX fell back to the CPU backend. Set "
            "JAX_PLATFORMS=cpu to run the device router on the CPU on "
            "purpose (tests, rehearsals).")
    return ident
