"""Persistent XLA compilation cache — compile once, reuse across processes.

The first call of each jitted program costs seconds to minutes of
compilation; a chip session is short and every process starts cold.
JAX's persistent compilation cache makes compiled executables survive
process boundaries: ``bench.py`` runs every phase in its own child, the
supervisor respawns trainers, and a second command of the same session
skips straight to the work.

This is the same economics as the reference's on-disk kernel cache
(ref ``veles/accelerated_units.py`` caches built OpenCL/CUDA program
binaries keyed by source+options so re-runs skip compilation); here the
unit of caching is the whole XLA executable, keyed by JAX on
(HLO, compile options, compiler version, device kind), so a cache
written against one backend can never be served to another.

Where the cache lives is decided OUTSIDE the program when
``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads that variable itself
and this module then sets no directory in code.  Without the variable
the cache is the fixed ``<repo>/.xla_cache`` (the directory is part of
the cache key's environment — a path that moves never hits), except on
the CPU backend, where the automatic default stays off (see
:func:`enable`).

Usage::

    from veles_tpu import compile_cache
    compile_cache.enable()            # env dir, else <repo>/.xla_cache
    compile_cache.enable("/fast/ssd") # explicit location (tests)

Known cosmetic noise: on CPU cache *hits*, XLA's AOT loader logs
E-level "machine type ... doesn't match" lines because the compile-time
feature list includes XLA-internal pseudo-features (prefer-no-scatter/
-gather) that host detection never reports.  Same-host reloads are
safe (verified end-to-end: a cached digits-MLP run reproduces the
fresh-compile results exactly); the TPU executable path does not use
that loader.
"""

import os

#: JAX's own variable for the cache directory; when it is set this
#: module never writes ``jax_compilation_cache_dir``
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

#: min seconds of compile time before an executable is persisted.  0.0
#: persists everything: every process of a chip session starts cold,
#: and the cache directory is scratch, so disk is cheaper than chip time.
_MIN_COMPILE_SECS = 0.0

_enabled_dir = None
_metrics_installed = False


def install_metrics():
    """Subscribe compile count/time to the telemetry registry via
    ``jax.monitoring``: every ``/jax/core/compile/*`` duration event
    feeds ``veles_compile_events_total`` / ``veles_compile_seconds_total``
    (labeled by the event's short name), and the compilation-cache
    events (hits, cache-enabled requests) feed
    ``veles_compile_cache_events_total`` — so a run's metrics JSONL
    carries exactly how much wall time recompilation cost and how often
    the persistent cache saved it.  Idempotent."""
    global _metrics_installed
    if _metrics_installed:
        return True
    import jax.monitoring

    def on_duration(event, duration, **kwargs):
        if "/compile/" not in event and not event.endswith("compile"):
            return
        # listeners fire inside jax's compile path: never raise
        try:
            from veles_tpu import telemetry
            key = event.rsplit("/", 1)[-1]
            reg = telemetry.registry
            reg.counter("veles_compile_events_total",
                        "jax compile-phase events", ("event",)).inc(
                event=key)
            reg.counter("veles_compile_seconds_total",
                        "seconds spent in jax compile phases",
                        ("event",)).inc(duration, event=key)
            # the black box wants compiles too: a post-mortem timeline
            # where the last event is a 40 s backend_compile explains a
            # "hang" that was really a recompile storm — and compiling
            # IS progress, so the hang watchdog must not trip on it
            telemetry.flight.record("compile", event=key,
                                    dur_s=duration)
            telemetry.health.note_progress()
        except Exception:   # noqa: BLE001
            pass

    def on_event(event, **kwargs):
        if "compilation_cache" not in event:
            return
        try:
            from veles_tpu import telemetry
            telemetry.registry.counter(
                "veles_compile_cache_events_total",
                "jax compilation-cache events (hits, cached requests)",
                ("event",)).inc(event=event.rsplit("/", 1)[-1])
        except Exception:   # noqa: BLE001
            pass

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _metrics_installed = True
    return True


def _accelerator_evidence():
    """Cheap accelerator sniff WITHOUT initializing a jax backend:
    TPU device nodes or the libtpu runtime, or NVIDIA device nodes.
    Erring toward True only re-enables the old default (cache on)."""
    import glob
    import importlib.util
    if glob.glob("/dev/accel*") or glob.glob("/dev/nvidia*"):
        return True
    try:
        return importlib.util.find_spec("libtpu") is not None
    except (ImportError, ValueError):
        return False


def _cpu_backend():
    """True when the run will land on the CPU backend: explicitly
    pinned there (config flag or ``JAX_PLATFORMS``), or nothing pinned
    and no accelerator evidence on the machine — jax auto-selects CPU
    there, so an unpinned CPU-only run must decline the cache the same
    way a pinned one does.  Read WITHOUT initializing the backend."""
    import jax
    platforms = str(jax.config.jax_platforms
                    or os.environ.get("JAX_PLATFORMS", ""))
    first = platforms.split(",")[0].strip().lower()
    if first:
        return first == "cpu"
    return not _accelerator_evidence()


def default_dir():
    """The fixed in-checkout ``<repo>/.xla_cache`` (ignored by git):
    survives process restarts, never a temp name, pid or timestamp."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".xla_cache")


def enable(path=None):
    """Turn JAX's persistent compilation cache on and return the
    directory in use, or None when it stays off.  Idempotent; safe to
    call before or after backend init — JAX reads the config at compile
    time, not import time.

    * ``JAX_COMPILATION_CACHE_DIR`` set (and no explicit ``path``): the
      directory is JAX's own reading of that variable; only the
      persistence thresholds are set here.
    * otherwise the directory is ``path`` or :func:`default_dir`, set
      through the ONE ``jax_compilation_cache_dir`` write below.
    * the automatic default stays OFF on the CPU backend: XLA:CPU
      executable DESERIALIZATION is unreliable in sandboxed/old-kernel
      environments (glibc heap corruption — measured ~40% of digits-MLP
      runs die by SIGSEGV/SIGABRT with the cache on, 0% with it off),
      and a CPU compile costs seconds where a TPU recompile costs
      minutes.  The variable or an explicit ``path`` still opts in on
      any backend.
    """
    global _enabled_dir
    # compile telemetry is independent of the on-disk cache: count
    # compiles even when persistence stays off below
    install_metrics()
    import jax
    from_env = path is None and bool(os.environ.get(ENV_DIR))
    if from_env:
        path = jax.config.jax_compilation_cache_dir
    elif path is None:
        if _cpu_backend():
            return None
        path = default_dir()
    if not from_env and jax.config.jax_compilation_cache_dir != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # jax latches its cache singleton (and a cache-unused verdict)
        # at the process's FIRST compile; pointing it at a directory
        # after any compile would otherwise be a silent no-op.
        # ``reset_cache`` is the public un-latch (jax.experimental.
        # compilation_cache.compilation_cache, JAX 0.9.0).
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_SECS)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # also persist XLA-level autotune/kernel caches where the backend
    # supports it (no-op elsewhere)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    _enabled_dir = path
    return path


def enabled_dir():
    """Directory the cache was enabled at this process, or None."""
    return _enabled_dir
