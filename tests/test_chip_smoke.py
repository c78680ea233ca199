"""chip_smoke.py — the first command of any chip session — rehearsed
where there is no chip: its whole body at a tiny size with the platform
check off (an argument this test owns, the way conftest.py owns the CPU
pin), and its real entry, which must refuse to run here."""

import dataclasses
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: d=64 / 2 layers / T=64 — every leg, every assertion, seconds on a CPU
TINY = chip_smoke.Sizes(
    vocab=512, d_model=64, n_heads=2, n_layers=2, batch=4, seq=64,
    serve_len=64, slots=4, prompt_lens=(4, 8, 12, 16),
    max_news=(4, 5, 6, 8), flash_shapes=((2, 2, 64, 32), (1, 2, 128, 64)),
    paged_hd=32, dsa_shape=(2, 1, 64, 128, 2048, 48), dsa_live=(1024, 2048),
    window_shape=(3, 4, 2, 128, 4, 6, 10, 90),
    retention_shape=(3, 4, 2, 16))


def test_body_passes_at_tiny_size_with_the_platform_check_off(capsys):
    """Train (two warm-up sweeps + eight steps, no compile inside
    them), the barrier line, flash and paged kernels against their
    references (the prefill pass's masked attention among them, at two
    live widths, and the window layers' decode kernel on wrapped rings, the retention
    decode kernel with an idle row), eight concurrent POSTs + one stream on the bf16 pool,
    one request on the int8 pool — the same code the chip runs, in
    interpret mode."""
    result = chip_smoke.run(TINY, require_tpu=False)
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    legs = [ln.split()[1] for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[smoke]")]
    assert legs == ["device", "setup", "compile", "train", "barrier",
                    "flash", "flash", "paged", "paged", "dsa", "dsa",
                    "window", "retention", "kernels",
                    "serve", "serve", "cache"]


def test_train_leg_under_a_mesh_compiles_once_and_agrees():
    """What tools/mesh_smoke.py asserts on four chips, on four virtual
    CPU devices: under data x model the flash kernel runs per device
    (``shard_map``), fresh stats/health accumulators do not recompile
    the sweep (train_leg's flat compile counters), and the loss agrees
    with the single-device leg to the CPU mesh tests' tolerance."""
    import jax
    from veles_tpu import compile_cache
    from veles_tpu.parallel import MeshConfig, make_mesh
    compile_cache.install_metrics()
    sizes = dataclasses.replace(TINY, batch=8)
    single = chip_smoke.train_leg(chip_smoke.build_flagship(sizes), sizes)
    mc = MeshConfig(make_mesh({"data": 2, "model": 2},
                              devices=jax.devices()[:4]))
    meshed = chip_smoke.train_leg(chip_smoke.build_flagship(sizes, mc),
                                  sizes)
    assert meshed == pytest.approx(single, rel=1e-3)


def _run_entry(cwd, script):
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, script], cwd=cwd,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return r, time.monotonic() - t0


def test_real_entry_exits_nonzero_fast_and_names_the_missing_tpu():
    r, took = _run_entry(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout           # prints no result
    assert took < 60, took


def test_script_alone_without_the_program_fails(tmp_path):
    """The driver also runs the script from a directory that holds
    nothing else of the repo: it must fail there too — here on the
    missing TPU, and with the platform check off on the missing
    program (there is nothing to fall back to)."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), str(tmp_path))
    r, _ = _run_entry(str(tmp_path), "chip_smoke.py")
    assert r.returncode != 0 and '"ok"' not in r.stdout
    r = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.run(require_tpu=False)"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "No module named 'veles_tpu'" in r.stderr


def test_the_window_leg_reads_a_wrapped_ring(capsys):
    """The ``window`` leg alone: rows from inside the window to nine
    windows deep on rings of six pages, a first live position on a
    page's edge and inside one; it prints what it compared and the
    kernel's time."""
    chip_smoke.check_window(TINY)
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[smoke] window")]
    assert "ring=6" in line and "window=10" in line and "kernel_ms=" in line
    # 3 rows at positions 90 x (0, 1/2, 1): min(pos + 1, 10) keys each
    assert "keys=21" in line or "keys=3" in line or "keys=" in line
