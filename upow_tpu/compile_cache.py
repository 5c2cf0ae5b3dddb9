"""Persistent XLA compile cache, keyed per host fingerprint.

JAX's persistent cache stores XLA:CPU AOT executables whose code is
specialised to the *compiling* machine's CPU features.  When the cache
directory is shared between machines (this repo's ``.jax_cache`` travels
with the checkout), loading an entry produced by a host with a different
feature set logs ``cpu_aot_loader`` feature-mismatch errors and can run
miscompiled code (observed: an execution that never completes).  Keying
the directory by a host fingerprint keeps reruns on the same machine
instant while making foreign entries invisible.

Known cosmetic residue: this XLA build's AOT loader compares the
compile-time LLVM feature string — which includes derived *tuning*
preferences (``+prefer-no-gather``/``+prefer-no-scatter``) — against a
host probe that never reports tuning prefs, so reloading an entry
compiled BY THIS SAME HOST still logs a two-feature mismatch warning
(verified 2026-08-01: cold-compile then warm-reload in one session,
same dir, warnings present, results correct).  Genuine cross-host
divergence is what the fingerprint prevents; the warning text alone is
not evidence of it.
"""

from __future__ import annotations

import hashlib
import logging
import os
import platform


_FP_CACHE = None


def _gcc_native_march() -> str:
    """GCC's CPUID-based microarch detection (``-march=native``
    expansion).  Virtualized /proc/cpuinfo is often generic and
    identical across different physical hosts, while the LLVM tuning
    features XLA:CPU AOT code is specialised to (e.g.
    ``prefer-no-gather``) come from raw CPUID — two hosts with the same
    cpuinfo can still produce incompatible AOT entries (observed: a VM
    migration flagged feature mismatches under an unchanged cpuinfo
    fingerprint).  GCC reads the same CPUID, so its expansion
    distinguishes those hosts."""
    import subprocess

    try:
        out = subprocess.run(
            ["gcc", "-march=native", "-E", "-v", "-"],
            stdin=subprocess.DEVNULL, capture_output=True, text=True,
            timeout=15)
        for line in (out.stderr + out.stdout).splitlines():
            if "-march=" in line:
                return line[line.index("-march="):].strip()
    except Exception as e:
        logging.getLogger("upow_tpu.compile_cache").debug(
            "gcc -march=native probe failed: %s", e)
    return "gcc-unavailable"


def host_fingerprint() -> str:
    """Stable per-machine tag: arch + CPU flags + microarch identity
    (family/model/stepping/microcode) + GCC's CPUID-detected feature
    expansion.  'fpv2' orphans pre-round-4 dirs whose entries may have
    been produced by a cpuinfo-identical but tuning-different host."""
    global _FP_CACHE
    if _FP_CACHE is not None:
        return _FP_CACHE
    bits = ["fpv2", platform.machine()]
    try:
        seen = set()
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                # one of each: the FLAGS are what the AOT cache entries
                # are specialised to; family/model/stepping/microcode
                # pin the microarch even when the model name is generic
                if key in ("flags", "Features", "model name", "vendor_id",
                           "cpu family", "model", "stepping",
                           "microcode") and key not in seen:
                    seen.add(key)
                    bits.append(line.strip())
    except OSError:
        bits.append(platform.processor() or "unknown")
    bits.append(_gcc_native_march())
    _FP_CACHE = hashlib.sha256("|".join(bits).encode()).hexdigest()[:12]
    return _FP_CACHE


#: the one default root: ``.jax_cache`` at the top of the checkout — a
#: fixed function of where the code lives, never of the working
#: directory, a pid, a temporary name or the time (the path is part of
#: what a cache hit depends on: a directory that moves never hits)
DEFAULT_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_enabled_dir = ""  # set by enable(); read by entry_count() for /metrics


def evict_host_dir() -> None:
    """Delete this host's subdir of the default root (the layout twin
    of :func:`enable`) — recovery when a cached XLA:CPU AOT entry
    miscomputes or hangs (CPU features changed under the same
    fingerprint after a VM migration).  A directory placed from outside
    through ``JAX_COMPILATION_CACHE_DIR`` is never touched."""
    import shutil

    shutil.rmtree(os.path.join(DEFAULT_ROOT, host_fingerprint()),
                  ignore_errors=True)


def enable() -> str:
    """Turn on JAX's persistent compile cache; returns the directory.

    One rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and no directory is set in code; otherwise the cache lives in
    ``<checkout>/.jax_cache/<host fingerprint>``.  Called by the device
    runtime at arm (every process: node, miner, benches) and by
    tests/conftest.py.  Never raises ('' on failure)."""
    import jax

    global _enabled_dir
    listen()
    placed = os.environ.get(ENV_DIR)
    path = placed or os.path.join(DEFAULT_ROOT, host_fingerprint())
    try:
        if not placed:
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
        _enabled_dir = path
        return path
    except Exception as e:
        logging.getLogger("upow_tpu.compile_cache").warning(
            "could not enable persistent compile cache at %s: %s", path, e)
        return ""


#: JAX's own monitoring events -> /metrics counters
#: (``compile_cache.persistent_hits`` / ``..._misses``): whether a
#: process found its programs in the persistent cache or compiled them
PERSISTENT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache.persistent_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache.persistent_misses",
}
#: JAX's duration events (the names jax 0.9.0 emits) -> histograms, and
#: the key each takes in a ``compile`` event record.  A backend compile
#: is the last step of one program's trace -> lower -> cache lookup ->
#: compile, so the record made there carries the longest of each seen
#: since the previous one on that thread (a nested jaxpr trace, a Pallas
#: kernel's body, reports inside its parent's duration: the outermost
#: is the program's).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("compile.trace_seconds", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile.lower_seconds", "lower_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("compile.cache_retrieval_seconds", "cache_retrieval_s"),
    BACKEND_COMPILE_EVENT: ("compile.backend_seconds", "backend_s"),
}
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                   120.0, 300.0, 600.0)
_listening = False


def listen() -> None:
    """Count JAX's persistent-cache lookups and time its compiles, once
    a process: the counters above, the ``compile.*_seconds`` histograms,
    ``compile.count`` and one ``compile`` record a backend compile in
    the ``telemetry.events`` ring (function name, the four durations,
    the trace id of the span it ran under).  This is what splits a
    miner's ``mine.first_issue``, the arm and a node's first dispatch
    into trace / lower / cache lookup / compile.  Called by
    :func:`enable` whether or not the persistent cache could be set up;
    on the profiler's clock jax marks the compile itself
    (``backend_compile_and_load``)."""
    global _listening
    if _listening:
        return
    import threading

    import jax.monitoring

    from . import telemetry

    pending = threading.local()   # a program compiles on one thread

    def on_event(event: str, **_kw) -> None:
        name = PERSISTENT_EVENTS.get(event)
        if name is not None:
            telemetry.inc(name)

    def on_duration(event: str, duration_secs: float, **kw) -> None:
        known = DURATION_EVENTS.get(event)
        if known is None:
            return
        hist, key = known
        telemetry.observe(hist, duration_secs, buckets=COMPILE_BUCKETS)
        seen = getattr(pending, "seen", None)
        if seen is None:
            seen = pending.seen = {}
        seen[key] = max(seen.get(key, 0.0), duration_secs)
        if event == BACKEND_COMPILE_EVENT:
            telemetry.inc("compile.count")
            # the ring stamps the record with the current trace id
            telemetry.event(
                "compile", fun_name=str(kw.get("fun_name", "")),
                **{k: round(v, 6) for k, v in seen.items()})
            seen.clear()

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _listening = True


def entry_count() -> int:
    """Entries in the enabled persistent cache dir (-1 when disabled).

    Operational gauge only — complements the in-process jit hit/miss
    counters in telemetry.device, which cover the (far hotter) traced-
    program reuse inside one process lifetime."""
    if not _enabled_dir:
        return -1
    try:
        return len(os.listdir(_enabled_dir))
    except OSError:
        return 0


# --- cpu_aot_loader warning triage ---------------------------------------

# The tuning-pref residue documented at the top of this module: reloads
# of entries compiled BY THIS HOST still mismatch on exactly these two
# derived preferences, because the host probe never reports them.
COSMETIC_TUNING_PREFS = frozenset(
    {"+prefer-no-gather", "+prefer-no-scatter"})

_AOT_MISMATCH = None  # compiled lazily (re import at module top is avoided)


def aot_mismatch_features(stderr_text: str) -> set:
    """Features named by ``cpu_aot_loader`` 'Target machine feature X is
    not supported on the host machine' lines in ``stderr_text``."""
    global _AOT_MISMATCH
    if _AOT_MISMATCH is None:
        import re

        _AOT_MISMATCH = re.compile(
            r"Target machine feature\s+(\S+)\s+is\s+not\s+supported")
    return set(_AOT_MISMATCH.findall(stderr_text))


def foreign_aot_mismatches(stderr_text: str) -> set:
    """Mismatched features BEYOND the documented cosmetic pair — a
    non-empty result means the loaded AOT entry really was compiled for
    a different machine (the thing the host fingerprint exists to
    prevent) and the host cache dir should be evicted, even if the run
    happened to exit 0."""
    return aot_mismatch_features(stderr_text) - COSMETIC_TUNING_PREFS
