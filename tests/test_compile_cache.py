"""Persistent XLA compilation cache (veles_tpu/compile_cache.py).

The contract that matters on the chip: enabling the cache makes
compiled executables land on disk, so a later process (another bench
phase, the next command of the same chip session) can reuse them
instead of re-paying first-compile — and WHERE they land is decided
from outside when ``JAX_COMPILATION_CACHE_DIR`` is set.  Mirrors the
reference's on-disk kernel-binary cache behavior (build once, hit
thereafter).
"""

import os
import subprocess
import sys

import pytest

import veles_tpu.compile_cache as cc


_CACHE_OPTS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes",
               "jax_persistent_cache_enable_xla_caches")


@pytest.fixture
def restore_cache_config():
    """The cache config is process-global jax state — put every option
    enable() touches back so later suites don't silently serialize
    every executable to a pytest tmp dir that may be garbage-collected
    under JAX."""
    import jax
    missing = object()
    before = {opt: getattr(jax.config, opt, missing) for opt in _CACHE_OPTS}
    saved_dir = cc._enabled_dir
    yield
    for opt, val in before.items():
        if val is not missing:
            jax.config.update(opt, val)
    cc._enabled_dir = saved_dir


def test_enable_writes_entries_and_is_idempotent(tmp_path,
                                                 restore_cache_config):
    cache = tmp_path / "xla"
    got = cc.enable(str(cache))
    assert got == str(cache)
    assert cc.enable(str(cache)) == str(cache)  # idempotent
    assert cc.enabled_dir() == str(cache)

    import jax
    import jax.numpy as jnp
    # a fresh program must produce at least one persisted entry once it
    # compiles
    x = jnp.ones((64, 64), jnp.float32)
    jax.block_until_ready(jax.jit(lambda a: (a @ a).sum())(x))
    entries = [p for p in cache.rglob("*") if p.is_file()]
    assert entries, "no cache entries persisted after a jit compile"


def test_cpu_backend_declines_the_automatic_default(monkeypatch):
    """On the CPU backend (the conftest's JAX_PLATFORMS=cpu) the
    AUTOMATIC default stays off — XLA:CPU executable deserialization
    can corrupt the heap in sandboxed environments (the ROADMAP
    "environment flake", root-caused in PR 9) — while the variable or
    an explicit path still opts in."""
    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    assert cc._cpu_backend()
    assert cc.enable() is None


def test_unpinned_run_resolves_backend_by_accelerator_evidence(
        monkeypatch):
    """Nothing pinned: jax auto-selects CPU on an accelerator-less
    machine, so the decline must cover that case too — an unpinned
    CPU-only run with the cache on is exactly the measured crash
    configuration.  With accelerator evidence the default (cache on)
    stands."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    import jax
    prev = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", None)
        monkeypatch.setattr(cc, "_accelerator_evidence", lambda: False)
        assert cc._cpu_backend()
        monkeypatch.setattr(cc, "_accelerator_evidence", lambda: True)
        assert not cc._cpu_backend()
    finally:
        jax.config.update("jax_platforms", prev)


def test_explicit_path_opts_in_even_on_cpu(tmp_path, monkeypatch,
                                           restore_cache_config):
    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    assert cc.enable(str(tmp_path / "xla")) == str(tmp_path / "xla")


def test_env_dir_means_no_directory_is_set_in_code(tmp_path, monkeypatch,
                                                   restore_cache_config):
    """``JAX_COMPILATION_CACHE_DIR`` set: the cache lives where JAX
    itself read that variable to be — enable() writes no directory
    (only thresholds), on any backend, and reports JAX's own value."""
    import jax
    monkeypatch.setenv(cc.ENV_DIR, str(tmp_path / "outside"))
    writes = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (writes.append(name), real_update(name, val)))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable() == before            # jax's reading, untouched
    assert jax.config.jax_compilation_cache_dir == before
    assert "jax_compilation_cache_dir" not in writes
    assert "jax_persistent_cache_min_compile_time_secs" in writes
    assert not (tmp_path / "outside").exists()   # nor created in code


def test_no_variable_off_cpu_is_the_fixed_in_checkout_dir(
        tmp_path, monkeypatch, restore_cache_config):
    """No variable, accelerator backend: the one fixed
    ``<repo>/.xla_cache`` — never a temp name, pid or timestamp (the
    path is part of what makes a second run hit)."""
    import jax
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.default_dir() == os.path.join(repo, ".xla_cache")
    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    monkeypatch.setattr(cc, "_cpu_backend", lambda: False)
    # keep the repo's real cache out of a CPU test process
    monkeypatch.setattr(cc, "default_dir",
                        lambda: str(tmp_path / ".xla_cache"))
    assert cc.enable() == str(tmp_path / ".xla_cache")
    assert jax.config.jax_compilation_cache_dir == str(
        tmp_path / ".xla_cache")


@pytest.mark.slow
def test_second_process_hits_the_cache(tmp_path):
    """The cross-process contract, asserted end-to-end: process A
    compiles and persists; process B compiles the same program and
    must be served from disk (observed via JAX's cache-hit logger).

    Slow tier: two fresh-jax-init subprocesses (tens of seconds on the
    1-core CI box) — the conftest budget rule for subprocess modules.
    """
    cache = str(tmp_path / "xla")
    prog = (
        "import logging, sys\n"
        "logging.basicConfig(level=logging.INFO)\n"
        "logging.getLogger('jax._src.compilation_cache')"
        ".setLevel(logging.DEBUG)\n"
        "logging.getLogger('jax._src.compiler').setLevel(logging.DEBUG)\n"
        "import veles_tpu.compile_cache as cc\n"
        "cc.enable(%r)\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "x = jnp.full((48, 48), 3.0, jnp.float32)\n"
        "v = jax.jit(lambda a: (a @ a.T).sum())(x)\n"
        "print('VAL', float(v))\n" % cache
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    outs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, timeout=240,
                           env=env, cwd=os.path.dirname(
                               os.path.dirname(os.path.abspath(__file__))))
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(p.stdout + p.stderr)
    assert "VAL" in outs[0] and "VAL" in outs[1]
    # same numeric result either path
    v0 = [l for l in outs[0].splitlines() if l.startswith("VAL")][0]
    v1 = [l for l in outs[1].splitlines() if l.startswith("VAL")][0]
    assert v0 == v1
    second = outs[1].lower()
    assert ("cache hit" in second or "persistent compilation cache hit"
            in second), "second process did not hit the persistent cache"
