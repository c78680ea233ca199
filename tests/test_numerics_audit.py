"""Numerics, determinism & Pallas auditor suite (VN4xx/VR5xx/VP6xx,
docs/static_analysis.md): one seeded hazard per rule caught from a
PURELY ABSTRACT trace (no computation dispatched, no device array
created — asserted), guarded counterparts silent, MNIST- and
CIFAR-shaped sample workflows audit clean end to end, the prng
seed-collision satellite, and the CLI surfaces (``--numerics``,
``--vmem-kib``, unified ``--fail-on`` exit codes)."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.analysis import lint_workflow, threshold_reached
from veles_tpu.analysis.findings import ERROR, INFO, WARNING, Finding
from veles_tpu.analysis.numerics_audit import (DEFAULT_VMEM_KIB,
                                               audit_kernel_launch,
                                               audit_numerics_step,
                                               audit_pallas_kernels,
                                               audit_prng_registry)


def rules(findings):
    return [f.rule for f in findings]


def by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


def S(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def audit(fn, *args, **spec_extra):
    spec = dict({"fn": fn, "args": args, "name": "t"}, **spec_extra)
    return audit_numerics_step(spec)


# --------------------------------------------------------------------------
# VN4xx: seeded hazards fire, guarded counterparts stay silent
# --------------------------------------------------------------------------
class TestVN400:
    def test_unguarded_log_fires(self):
        assert rules(audit(lambda x: jnp.log(x).sum(), S(8))) == ["VN400"]

    def test_clamped_log_silent(self):
        fs = audit(lambda x: jnp.log(jnp.maximum(x, 1e-6)).sum(), S(8))
        assert fs == []

    def test_log_of_exp_silent(self):
        assert audit(lambda x: jnp.log(jnp.exp(x - x.max())).sum(),
                     S(8)) == []

    def test_log_of_eps_plus_erf_fires(self):
        """erf ranges over [-1, 1] — it is NOT nonnegative, so an eps
        does not make log(eps + erf(x)) safe (review finding)."""
        fs = audit(lambda x: jnp.log(1e-6 + jax.lax.erf(x)).sum(),
                   S(8))
        assert rules(fs) == ["VN400"]

    def test_unguarded_div_fires(self):
        assert rules(audit(lambda x, y: (x / y).sum(),
                           S(8), S(8))) == ["VN400"]

    def test_count_guarded_div_silent(self):
        fs = audit(lambda x, n: (x.sum() / jnp.maximum(n, 1.0)),
                   S(8), S())
        assert fs == []

    def test_eps_guarded_rsqrt_silent(self):
        fs = audit(lambda x: jax.lax.rsqrt(x * x + 1e-6).sum(), S(8))
        assert fs == []

    def test_unguarded_rsqrt_fires(self):
        assert rules(audit(lambda x: jax.lax.rsqrt(x).sum(),
                           S(8))) == ["VN400"]

    def test_layer_norm_grad_silent(self):
        """jnp.var's ddof arithmetic and the max-gradient tie count are
        literal-foldable — the classic LN backward must not fire."""
        from veles_tpu.ops import norm

        def step(x, g):
            return jax.grad(lambda x: norm.layer_norm(x, g).sum())(x)
        assert audit(step, S(8, 16, 32), S(32)) == []

    def test_online_softmax_scan_grad_silent(self):
        """The blockwise-attention backward divides by residuals that
        ride a scan — the ``maximum(l, eps)`` guard must survive the
        stacked-ys flag mapping."""
        from veles_tpu.ops import attention

        def step(q, k, v):
            return jax.grad(lambda q: attention.blockwise_attention(
                q, k, v, causal=True).sum())(q)
        assert audit(step, S(2, 2, 16, 8), S(2, 2, 16, 8),
                     S(2, 2, 16, 8)) == []

    def test_adam_bias_correction_needs_vouched_step(self):
        """``1 - beta**t`` is positive only because t >= 1 — which the
        auditor accepts exactly when the caller vouches for the step
        input (the trainer does; an unvouched step still fires)."""
        def adamish(m, step):
            t = step.astype(jnp.float32)
            return m / (1.0 - 0.9 ** t)

        args = (S(4), S(dtype=jnp.int32))
        assert rules(audit(adamish, *args)) == ["VN400"]
        assert audit(adamish, *args,
                     input_flags={1: ("pos", "nonneg")}) == []


class TestVN401:
    def test_unguarded_exp_fires(self):
        assert rules(audit(lambda x: jnp.exp(x).sum(), S(8))) == ["VN401"]

    def test_sub_max_guard_silent(self):
        assert audit(lambda x: jnp.exp(x - x.max()).sum(), S(8)) == []

    def test_clamp_guard_silent(self):
        assert audit(lambda x: jnp.exp(jnp.minimum(x, 30.0)).sum(),
                     S(8)) == []

    def test_literal_minus_unbounded_still_fires(self):
        """exp(c - x) overflows for very negative x — a bounded minuend
        alone must not launder the bound (review finding)."""
        fs = audit(lambda x: jnp.exp(5.0 - x).sum(), S(8))
        assert rules(fs) == ["VN401"]

    def test_literal_minus_nonneg_silent(self):
        assert audit(lambda x: jnp.exp(5.0 - jnp.abs(x)).sum(),
                     S(8)) == []

    def test_log_softmax_loss_silent(self):
        from veles_tpu.ops import losses

        def step(w, x, lbl, valid):
            def loss(w):
                ls, _e, nv = losses.masked_softmax_xent(
                    jnp.tanh(x @ w), lbl, valid)
                return ls / jnp.maximum(nv, 1.0)
            return jax.grad(loss)(w)
        assert audit(step, S(8, 10), S(64, 8),
                     S(64, dtype=jnp.int32), S(64)) == []


class TestVN402:
    def test_raw_softmax_then_log_fires(self):
        fs = audit(lambda x: jnp.log(jax.nn.softmax(x)).sum(), S(4, 8))
        assert rules(fs) == ["VN402"]
        assert "log_softmax" in fs[0].hint

    def test_log_softmax_silent(self):
        assert audit(lambda x: jax.nn.log_softmax(x).sum(), S(4, 8)) == []


class TestVN403:
    B16 = jax.ShapeDtypeStruct((64, 4096), jnp.bfloat16)
    W16 = jax.ShapeDtypeStruct((4096, 64), jnp.bfloat16)

    def test_bf16_dot_accumulation_fires(self):
        fs = audit(lambda x, y: x @ y, self.B16, self.W16)
        assert rules(fs) == ["VN403"]

    def test_f32_preferred_type_silent(self):
        def f(x, y):
            return jax.lax.dot_general(
                x, y, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        assert audit(f, self.B16, self.W16) == []

    def test_small_contraction_silent(self):
        small = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)
        assert audit(lambda x, y: x @ y, small, small) == []

    def test_jnp_sum_upcasts_silent(self):
        """jnp internally upcasts f16/bf16 sums to f32 — no finding."""
        x = jax.ShapeDtypeStruct((4096,), jnp.bfloat16)
        assert audit(lambda x: x.sum(), x) == []


class TestVN404:
    I32 = jax.ShapeDtypeStruct((8,), jnp.int32)

    def test_narrowing_cast_fires(self):
        fs = audit(lambda x: x.astype(jnp.int8).sum(), self.I32)
        assert rules(fs) == ["VN404"]

    def test_clip_guard_silent(self):
        assert audit(lambda x: jnp.clip(x, 0, 127).astype(jnp.int8)
                     .sum(), self.I32) == []

    def test_signed_clip_guard_silent(self):
        """The documented fix — clip to the SIGNED target range — must
        pass (review finding: the lattice has no bounded-below flag,
        so the clamp literals are checked against the dtype range)."""
        assert audit(lambda x: jnp.clip(x, -128, 127).astype(jnp.int8)
                     .sum(), self.I32) == []

    def test_too_wide_clip_still_fires(self):
        fs = audit(lambda x: jnp.clip(x, -1000, 1000).astype(jnp.int8)
                   .sum(), self.I32)
        assert rules(fs) == ["VN404"]

    def test_widening_cast_silent(self):
        i8 = jax.ShapeDtypeStruct((8,), jnp.int8)
        assert audit(lambda x: x.astype(jnp.int32).sum(), i8) == []


# --------------------------------------------------------------------------
# VR5xx: randomness & determinism
# --------------------------------------------------------------------------
KEY = None


def key_spec():
    global KEY
    if KEY is None:
        KEY = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    return KEY


class TestVR500:
    def test_key_reused_by_two_draws_fires(self):
        def f(k):
            return jax.random.normal(k, (4,)) \
                + jax.random.uniform(k, (4,))
        assert rules(audit(f, key_spec())) == ["VR500"]

    def test_split_keys_silent(self):
        def f(k):
            a, b = jax.random.split(k)
            return jax.random.normal(a, (4,)) \
                + jax.random.uniform(b, (4,))
        assert audit(f, key_spec()) == []

    def test_fold_in_same_counter_fires(self):
        def f(k):
            return (jax.random.normal(jax.random.fold_in(k, 7), (4,))
                    + jax.random.uniform(jax.random.fold_in(k, 7),
                                         (4,)))
        assert rules(audit(f, key_spec())) == ["VR500"]

    def test_fold_in_distinct_counters_silent(self):
        def f(k):
            return (jax.random.normal(jax.random.fold_in(k, 1), (4,))
                    + jax.random.uniform(jax.random.fold_in(k, 2),
                                         (4,)))
        assert audit(f, key_spec()) == []

    def test_trainer_per_layer_fold_pattern_silent(self):
        """The StagedTrainer folds the step then each layer index —
        all distinct streams."""
        def f(k, step):
            k = jax.random.fold_in(k, step)
            return sum(jax.random.normal(jax.random.fold_in(k, i),
                                         (4,)).sum()
                       for i in range(3))
        assert audit(f, key_spec(), S(dtype=jnp.int32)) == []


class TestVR501:
    def test_explicit_seed_collision_reported(self):
        from veles_tpu import prng
        prng._streams.clear()
        prng.get("a").seed(123)
        prng.get("b").seed(123)
        try:
            fs = audit_prng_registry()
            assert rules(fs) == ["VR501"]
            assert "a" in fs[0].message and "b" in fs[0].message
        finally:
            prng._streams.clear()

    def test_derived_seeds_never_collide(self):
        from veles_tpu import prng
        prng._streams.clear()
        prng.seed_all(7)
        for i in range(64):
            prng.get("stream-%d" % i)
        try:
            assert prng.seed_collisions() == []
            assert audit_prng_registry() == []
        finally:
            prng._streams.clear()


class TestVR502:
    def test_host_numpy_random_fires(self, tmp_path):
        mod = tmp_path / "staged_host_rand.py"
        mod.write_text(
            "import numpy as np\n"
            "def step(x):\n"
            "    return x * np.random.rand()\n")
        import importlib.util
        spec = importlib.util.spec_from_file_location("staged_host_rand",
                                                      mod)
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        fs = audit(m.step, S(4))
        assert rules(fs) == ["VR502"]
        assert fs[0].severity == ERROR

    def test_jax_random_silent(self):
        def f(k):
            return jax.random.normal(k, (4,))
        assert audit(f, key_spec()) == []

    def test_host_scan_covers_loss_callees(self, tmp_path):
        """The trainer's step fn is framework code — a user loss with
        host randomness is caught via the spec's host_scan list (the
        trainer passes its loss evaluator and non-veles_tpu layers)."""
        mod = tmp_path / "user_loss.py"
        mod.write_text(
            "import numpy as np\n"
            "def noisy_loss(out):\n"
            "    return out.sum() * np.random.rand()\n")
        import importlib.util
        spec = importlib.util.spec_from_file_location("user_loss", mod)
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)

        def clean_step(x):           # framework-style wrapper
            return m.noisy_loss(x)

        assert audit(clean_step, S(4)) == []       # wrapper scan misses
        fs = audit(clean_step, S(4), host_scan=(m.noisy_loss,))
        assert rules(fs) == ["VR502"]
        assert "noisy_loss" in fs[0].message


class TestVR503:
    I32 = jax.ShapeDtypeStruct((4,), jnp.int32)

    def test_float_scatter_add_fires(self):
        fs = audit(lambda x, i, u: x.at[i].add(u), S(8), self.I32, S(4))
        assert rules(fs) == ["VR503"]

    def test_unique_indices_silent(self):
        assert audit(lambda x, i, u: x.at[i].add(u, unique_indices=True),
                     S(8), self.I32, S(4)) == []

    def test_int_scatter_silent(self):
        i8 = jax.ShapeDtypeStruct((8,), jnp.int32)
        u = jax.ShapeDtypeStruct((4,), jnp.int32)
        assert audit(lambda x, i, u: x.at[i].add(u), i8, self.I32,
                     u) == []

    def test_take_along_backward_silent(self):
        """The loss's take_along_axis backward scatters one index per
        batch row (operand batching dims) — exempt."""
        def f(x, lbl):
            return jnp.take_along_axis(x, lbl, axis=1).sum()
        assert audit(lambda x, lbl: jax.grad(f)(x, lbl),
                     S(4, 10), jax.ShapeDtypeStruct((4, 1),
                                                    jnp.int32)) == []

    def test_embedding_backward_silent(self):
        """jnp.take's transpose (the embedding-table gradient) is
        XLA-generated and TPU-deterministic — exempt."""
        def f(table, ids):
            return jnp.take(table, ids, axis=0).sum()
        assert audit(lambda t, i: jax.grad(f)(t, i),
                     S(16, 8), jax.ShapeDtypeStruct((4,),
                                                    jnp.int32)) == []


# --------------------------------------------------------------------------
# VP6xx: Pallas launch geometry
# --------------------------------------------------------------------------
class TestVP600:
    def test_unaligned_sublane_fires(self):
        fs = audit_kernel_launch(
            {"kernel": "t", "masked": True, "scratch": [],
             "blocks": [("q", (1, 100, 256), jnp.bfloat16)],
             "grid_axes": []})
        assert rules(fs) == ["VP600"]
        assert "(16, 128)" in fs[0].message    # bf16 tile

    def test_aligned_silent(self):
        fs = audit_kernel_launch(
            {"kernel": "t", "masked": True, "scratch": [],
             "blocks": [("q", (1, 128, 256), jnp.bfloat16)],
             "grid_axes": []})
        assert fs == []

    def test_full_lane_head_dim_exempt(self):
        """d=64 models exist: a lane dim that IS the head dim is the
        model's geometry, not a tunable tile choice."""
        fs = audit_kernel_launch(
            {"kernel": "t", "masked": True, "scratch": [],
             "blocks": [("q", (1, 128, 64), jnp.bfloat16,
                         {"full_lane": True})],
             "grid_axes": []})
        assert fs == []

    def test_f32_sublane_tile_is_8(self):
        fs = audit_kernel_launch(
            {"kernel": "t", "masked": True, "scratch": [],
             "blocks": [("q", (1, 24, 128), jnp.float32)],
             "grid_axes": []})
        assert fs == []    # 24 % 8 == 0


class TestVP601:
    def test_ragged_unmasked_grid_fires(self):
        fs = audit_kernel_launch(
            {"kernel": "t", "masked": False, "scratch": [],
             "blocks": [], "grid_axes": [("q", 1000, 128)]})
        assert rules(fs) == ["VP601"]

    def test_masked_kernel_exempt(self):
        fs = audit_kernel_launch(
            {"kernel": "t", "masked": True, "scratch": [],
             "blocks": [], "grid_axes": [("q", 1000, 128)]})
        assert fs == []


class TestVP602:
    def test_over_budget_fires_error(self):
        fs = audit_kernel_launch(
            {"kernel": "t", "masked": True,
             "blocks": [("q", (1, 4096, 2048), jnp.float32)],
             "scratch": [], "grid_axes": []})
        assert rules(fs) == ["VP602"]
        assert fs[0].severity == ERROR

    def test_budget_knob(self):
        launch = {"kernel": "t", "masked": True,
                  "blocks": [("q", (1, 128, 128), jnp.float32)],
                  "scratch": [], "grid_axes": []}
        assert audit_kernel_launch(launch) == []
        assert rules(audit_kernel_launch(launch, vmem_kib=64)) \
            == ["VP602"]

    def test_checked_escape_hatch(self):
        launch = {"kernel": "t", "masked": True, "checked": ("VP602",),
                  "blocks": [("q", (1, 4096, 2048), jnp.float32)],
                  "scratch": [], "grid_axes": []}
        assert audit_kernel_launch(launch) == []


class TestConfiguredKernels:
    def test_registered_launches_audit_clean(self):
        """The shipped flash/paged kernels at their site-config block
        sizes pass their own auditor (the analyzer gates the repo that
        ships it)."""
        assert audit_pallas_kernels() == []

    def test_flash_audit_launch_matches_kernel_geometry(self):
        from veles_tpu.ops.pallas import flash
        fwd, dq, dkv = flash.audit_launch(1024, 1024, 128, causal=True,
                                          block_q=512, block_k=512)
        names = [b[0] for b in fwd["blocks"]]
        assert names == ["q", "k", "v", "o", "lse"]
        assert fwd["blocks"][0][1] == (1, 512, 128)
        assert dq["scratch"][0][1] == (512, 128)
        assert {b[0] for b in dkv["blocks"]} >= {"dk", "dv", "delta"}

    def test_flash_oversized_blocks_over_budget(self):
        from veles_tpu.ops.pallas import flash
        launches = flash.audit_launch(8192, 8192, 128, causal=True,
                                      block_q=4096, block_k=4096)
        fs = audit_pallas_kernels(launches=launches,
                                  vmem_kib=DEFAULT_VMEM_KIB)
        assert "VP602" in rules(fs)

    @pytest.mark.parametrize("hd,dtype,where", [
        (128, jnp.bfloat16, "scratch"), (64, jnp.bfloat16, "blocks"),
        (128, jnp.int8, "blocks")])
    def test_paged_audit_launch_describes_the_page_buffers(self, hd,
                                                           dtype, where):
        """The paged launch as it runs: whole-page buffers of
        ``page_schedule``'s chunk, K and V, two slots each — scratch the
        kernel fills by hand (hd 128) or page operands Mosaic
        double-buffers (hd 64, the int8 pool) — inside the buffer
        budget, and the grid axis is the live pages in chunks."""
        from veles_tpu.ops.pallas import paged
        bs, nbm, hkv = 32, 64, 16
        quant = dtype == jnp.int8
        chunk, heads = paged.page_schedule(hkv, bs, hd, dtype, nbm, quant)
        launch, = paged.audit_launch(hd, bs, dtype=dtype, nbm=nbm,
                                     hkv=hkv)
        assert launch["grid_axes"] == [("live-pages", nbm, chunk)]
        by_hand = where == "scratch"
        assert by_hand == paged._sliceable(hd, quant)
        pages = {e[0]: e for e in launch[where]}
        k = pages["k_pages" if by_hand else "k"]
        assert k[1] == ((2,) if by_hand else ()) + (chunk, heads, bs, hd)
        assert np.dtype(k[2]) == np.dtype(dtype) and k[3]["full_lane"]
        assert ("k_scale" in pages) == quant
        slots = 1 if by_hand else 2
        held = sum(slots * int(np.prod(e[1])) * np.dtype(e[2]).itemsize
                   for n, e in pages.items() if n[0] in "kv")
        assert held <= paged._PAGE_BUFFER_BYTES
        assert chunk * bs >= 128
        assert audit_pallas_kernels(launches=[launch]) == []

    def test_paged_misfit_blocks_fail_the_lint(self):
        """A pool block off the dtype's sublane tile is VP600 on the
        page tile in either fetch style, and one whose single page
        overflows the buffers is VP602."""
        from veles_tpu.ops.pallas import paged
        for hd in (64, 128):
            fs = audit_pallas_kernels(launches=paged.audit_launch(
                hd, 24, dtype=jnp.bfloat16, nbm=8))
            assert set(rules(fs)) == {"VP600"}, (hd, fs)
        fat = paged.audit_launch(128, 32768, dtype=jnp.bfloat16, nbm=1)
        assert paged.page_schedule(16, 32768, 128, jnp.bfloat16, 1) == \
            (1, 1)
        assert "VP602" in rules(audit_pallas_kernels(launches=fat))

    def test_unmasked_description_fires_vp601(self):
        from veles_tpu.ops.pallas import flash
        launches = flash.audit_launch(1000, 1000, 128, block_q=128,
                                      block_k=128, masked=False)
        assert "VP601" in rules(audit_pallas_kernels(launches=launches))


# --------------------------------------------------------------------------
# the combined hazard workflow: every rule exactly once through
# lint_workflow (the acceptance fixture)
# --------------------------------------------------------------------------
ALL_RULES = ("VN400", "VN401", "VN402", "VN403", "VN404",
             "VR500", "VR501", "VR502", "VR503",
             "VP600", "VP601", "VP602")


def _hazard_step_module(tmp_path):
    mod = tmp_path / "hazard_step.py"
    mod.write_text(
        "import jax, jax.numpy as jnp, numpy as np\n"
        "def step(x, b16, i32, key, idx, upd):\n"
        "    np.random.rand()                       # VR502\n"
        "    a = jnp.log(x)                         # VN400\n"
        "    b = jnp.exp(x)                         # VN401\n"
        "    c = jnp.log(jax.nn.softmax(x))         # VN402\n"
        "    d = (b16 @ b16.T)                      # VN403\n"
        "    e = i32.astype(jnp.int8)               # VN404\n"
        "    f = jax.random.normal(key, (4,))       # VR500 (reuse)\n"
        "    g = jax.random.uniform(key, (4,))\n"
        "    h = x.at[idx].add(upd)                 # VR503\n"
        "    return (a.sum() + b.sum() + c.sum()\n"
        "            + d.astype(jnp.float32).sum()\n"
        "            + e.sum() + f.sum() + g.sum() + h.sum())\n")
    import importlib.util
    spec = importlib.util.spec_from_file_location("hazard_step", mod)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


class TestHazardWorkflow:
    def test_every_rule_exactly_once(self, tmp_path, monkeypatch):
        from veles_tpu import prng
        from veles_tpu.ops import pallas
        from veles_tpu.units import TrivialUnit
        from veles_tpu.workflow import Workflow

        m = _hazard_step_module(tmp_path)
        args = (S(8), jax.ShapeDtypeStruct((64, 4096), jnp.bfloat16),
                jax.ShapeDtypeStruct((8,), jnp.int32), key_spec(),
                jax.ShapeDtypeStruct((4,), jnp.int32), S(4))

        class Hazard(TrivialUnit):
            def lint_numerics_spec(self):
                return {"fn": m.step, "args": args,
                        "name": "hazard.step"}

        # VP6xx: one bad launch per rule via the kernel-audit registry
        monkeypatch.setattr(pallas, "KERNEL_AUDITS", {"bad": lambda: [
            {"kernel": "bad.tile", "masked": True, "scratch": [],
             "blocks": [("q", (1, 100, 256), jnp.bfloat16)],
             "grid_axes": []},
            {"kernel": "bad.grid", "masked": False, "scratch": [],
             "blocks": [], "grid_axes": [("q", 1000, 128)]},
            {"kernel": "bad.vmem", "masked": True, "scratch": [],
             "blocks": [("q", (1, 4096, 2048), jnp.float32)],
             "grid_axes": []},
        ]})
        # VR501: two explicitly same-seeded streams
        prng._streams.clear()
        prng.get("h1").seed(99)
        prng.get("h2").seed(99)

        wf = Workflow(name="hazards")
        u = Hazard(wf, name="hazard")
        u.link_from(wf.start_point)
        wf.end_point.link_from(u)
        try:
            fs = [f for f in lint_workflow(wf)
                  if f.rule.startswith(("VN", "VR", "VP"))]
        finally:
            prng._streams.clear()
        counts = {}
        for f in fs:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        assert counts == {r: 1 for r in ALL_RULES}

    def test_audit_is_purely_abstract_no_device_arrays(self, tmp_path):
        """The acceptance gate: the VN/VR audit runs off
        ShapeDtypeStructs — no computation dispatched, no device array
        allocated (the VP rules are plain arithmetic)."""
        m = _hazard_step_module(tmp_path)
        args = (S(8), jax.ShapeDtypeStruct((64, 4096), jnp.bfloat16),
                jax.ShapeDtypeStruct((8,), jnp.int32), key_spec(),
                jax.ShapeDtypeStruct((4,), jnp.int32), S(4))
        for leaf in args:
            assert isinstance(leaf, jax.ShapeDtypeStruct)
        gc.collect()
        before = len(jax.live_arrays())
        fs = audit_numerics_step({"fn": m.step, "args": args})
        assert fs    # it did find the seeded hazards
        gc.collect()
        assert len(jax.live_arrays()) <= before


# --------------------------------------------------------------------------
# sample-shaped workflows audit clean (the other half of acceptance)
# --------------------------------------------------------------------------
def build_wf(name, layers, data, labels, loss="softmax", mb=32,
             gd=None):
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    prng.seed_all(7)
    loader = FullBatchLoader(
        None, data=data, labels=labels, minibatch_size=mb,
        class_lengths=[0, len(data) // 4, len(data) - len(data) // 4])
    kwargs = {"gd_defaults": gd} if gd else {}
    wf = StandardWorkflow(layers=layers, loader=loader, loss=loss,
                          decision_config={"max_epochs": 1}, name=name,
                          **kwargs)
    wf.initialize()
    return wf


def numerics_findings(wf):
    return [f for f in lint_workflow(wf)
            if f.rule.startswith(("VN", "VR", "VP"))]


class TestSamplesClean:
    def test_mnist_shaped_mlp_clean(self):
        from veles_tpu.models import zoo
        rng = np.random.default_rng(0)
        wf = build_wf("mnist-numerics", zoo.mnist_mlp(),
                      rng.normal(size=(512, 28, 28)).astype(np.float32),
                      rng.integers(0, 10, 512).astype(np.int32))
        assert numerics_findings(wf) == []

    def test_cifar_shaped_conv_clean(self):
        from veles_tpu.models import zoo
        rng = np.random.default_rng(0)
        wf = build_wf("cifar-numerics", zoo.cifar_conv(),
                      rng.normal(size=(128, 32, 32, 3)).astype(
                          np.float32),
                      rng.integers(0, 10, 128).astype(np.int32), mb=16)
        assert numerics_findings(wf) == []

    @pytest.mark.slow
    def test_transformer_lm_clean(self):
        from veles_tpu.models import zoo
        rng = np.random.default_rng(0)
        tok = rng.integers(0, 64, size=(128, 16)).astype(np.int32)
        wf = build_wf("lm-numerics",
                      zoo.transformer_lm(vocab_size=64, d_model=32,
                                         n_heads=2, n_layers=2,
                                         dropout=0.1),
                      tok, tok, loss="lm", mb=8)
        assert numerics_findings(wf) == []

    def test_grad_accum_adam_clean(self):
        """The cond-wrapped accumulating update keeps its vouched step
        counter through the branch mapping."""
        rng = np.random.default_rng(0)
        wf = build_wf(
            "gacc-numerics",
            [{"type": "all2all_tanh", "output_sample_shape": 16,
              "solver": "adam"},
             {"type": "softmax", "output_sample_shape": 10}],
            rng.normal(size=(128, 24)).astype(np.float32),
            rng.integers(0, 10, 128).astype(np.int32), mb=16,
            gd={"grad_accum_steps": 2, "clip_norm": 1.0})
        assert numerics_findings(wf) == []


# --------------------------------------------------------------------------
# hooks & escape hatches
# --------------------------------------------------------------------------
class TestTrainerHook:
    def test_spec_shape_and_abstract_args(self):
        rng = np.random.default_rng(0)
        wf = build_wf("hook-numerics",
                      [{"type": "all2all_tanh",
                        "output_sample_shape": 16},
                       {"type": "softmax", "output_sample_shape": 10}],
                      rng.normal(size=(128, 24)).astype(np.float32),
                      rng.integers(0, 10, 128).astype(np.int32), mb=16)
        spec = wf.trainer.lint_numerics_spec()
        assert spec is not None
        assert spec["name"].endswith("train_step")
        for leaf in jax.tree_util.tree_leaves(spec["args"]):
            assert isinstance(leaf, jax.ShapeDtypeStruct)
        # the step counter is vouched positive
        assert ("pos", "nonneg") in spec["input_flags"].values()

    def test_none_before_initialize(self):
        from veles_tpu.loader.fullbatch import FullBatchLoader
        from veles_tpu.models.standard_workflow import StandardWorkflow
        rng = np.random.default_rng(0)
        loader = FullBatchLoader(
            None, data=rng.normal(size=(64, 8)).astype(np.float32),
            labels=rng.integers(0, 4, 64).astype(np.int32),
            minibatch_size=16, class_lengths=[0, 16, 48])
        wf = StandardWorkflow(
            layers=[{"type": "softmax", "output_sample_shape": 4}],
            loader=loader, decision_config={"max_epochs": 1},
            name="uninit-numerics")
        assert wf.trainer.lint_numerics_spec() is None

    def test_loss_suppress_escape_hatch(self):
        from veles_tpu.ops.losses import _LOSSES, register_loss

        @register_loss("test_suppressed", kind="class",
                       numerics_suppress=("VN404",))
        def suppressed(out, labels, targets, valid):
            narrowed = labels.astype(jnp.int8).astype(jnp.float32)
            return (narrowed.sum(), jnp.asarray(0.0),
                    jnp.maximum(valid.sum(), 1.0), 1)
        try:
            rng = np.random.default_rng(0)
            wf = build_wf("suppress-numerics",
                          [{"type": "all2all_tanh",
                            "output_sample_shape": 8}],
                          rng.normal(size=(128, 8)).astype(np.float32),
                          rng.integers(0, 4, 128).astype(np.int32),
                          loss="test_suppressed", mb=16)
            spec = wf.trainer.lint_numerics_spec()
            assert "VN404" in spec["suppress"]
            assert by_rule(audit_numerics_step(spec), "VN404") == []
        finally:
            _LOSSES.pop("test_suppressed", None)


# --------------------------------------------------------------------------
# prng satellite: derived-seed collision detection + deterministic rehash
# --------------------------------------------------------------------------
class TestPrngSeedDerivation:
    def test_collision_rehashes_deterministically(self, caplog):
        from veles_tpu import prng
        saved = dict(prng._derived_seeds)
        prng._derived_seeds.clear()
        try:
            s_a = prng._derive_seed("alpha", 1234)
            # force a collision: pretend another stream owns alpha's slot
            prng._derived_seeds.clear()
            prng._derived_seeds[s_a] = "other"
            import logging
            with caplog.at_level(logging.WARNING, logger="prng"):
                s_a2 = prng._derive_seed("alpha", 1234)
            assert s_a2 != s_a
            assert any("collides" in r.message for r in caplog.records)
            # deterministic: same preconditions, same rehash result
            prng._derived_seeds.clear()
            prng._derived_seeds[s_a] = "other"
            assert prng._derive_seed("alpha", 1234) == s_a2
        finally:
            prng._derived_seeds.clear()
            prng._derived_seeds.update(saved)

    def test_same_name_rederives_same_seed(self):
        from veles_tpu import prng
        saved = dict(prng._derived_seeds)
        prng._derived_seeds.clear()
        try:
            assert prng._derive_seed("x", 42) == \
                prng._derive_seed("x", 42)
        finally:
            prng._derived_seeds.clear()
            prng._derived_seeds.update(saved)

    def test_seed_all_replays_fresh_process_derivation(self):
        from veles_tpu import prng
        prng._streams.clear()
        try:
            prng.seed_all(11)
            g1 = prng.get("s1")
            g2 = prng.get("s2")
            seeds_fresh = (g1._seed, g2._seed)
            prng.seed_all(11)     # re-seed in place
            assert (g1._seed, g2._seed) == seeds_fresh
        finally:
            prng._streams.clear()


# --------------------------------------------------------------------------
# exit-code unification satellite + CLI surfaces
# --------------------------------------------------------------------------
class TestThresholdReached:
    FS = [Finding("VN400", WARNING, "u", "m"),
          Finding("VM300", INFO, "u", "m")]

    def test_error_threshold(self):
        assert not threshold_reached(self.FS, "error")
        assert threshold_reached(
            self.FS + [Finding("VR502", ERROR, "u", "m")], "error")

    def test_warning_threshold(self):
        assert threshold_reached(self.FS, "warning")
        assert not threshold_reached([self.FS[1]], "warning")

    def test_bad_threshold_raises(self):
        with pytest.raises(ValueError):
            threshold_reached(self.FS, "nope")


WF_TEMPLATE = """\
import numpy as np
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models.standard_workflow import StandardWorkflow

def run(load, main):
    rng = np.random.default_rng(0)
    loader = FullBatchLoader(
        None, data=rng.normal(size=(128, 16)).astype(np.float32),
        labels=rng.integers(0, 4, 128).astype(np.int32),
        minibatch_size=16, class_lengths=[0, 32, 96])
    load(StandardWorkflow,
         layers=[{"type": "all2all_tanh", "output_sample_shape": 8},
                 {"type": "softmax", "output_sample_shape": 4}],
         loader=loader, decision_config={"max_epochs": 1},
         name="cli-numerics")
    main()
"""


class TestCLI:
    def test_numerics_flag_initializes_and_audits(self, tmp_path,
                                                  capsys):
        from veles_tpu.analysis.cli import main
        wf = tmp_path / "wf.py"
        wf.write_text(WF_TEMPLATE)
        rc = main([str(wf), "--numerics"])
        out = capsys.readouterr().out
        assert rc == 0
        # clean step: only the passive-Forward VG002 infos remain
        assert "VN4" not in out and "VR5" not in out

    def test_vmem_kib_knob_reaches_vp602(self, tmp_path, capsys):
        """A starvation budget turns the shipped flash launches into
        VP602 errors, and --fail-on error exits 1 — the unified gate."""
        from veles_tpu.analysis.cli import main
        wf = tmp_path / "wf.py"
        wf.write_text(WF_TEMPLATE)
        rc = main([str(wf), "--vmem-kib", "16"])
        out = capsys.readouterr().out
        assert "VP602" in out
        assert rc == 1

    def test_fail_on_warning_applies_to_numerics(self, tmp_path,
                                                 capsys, monkeypatch):
        from veles_tpu import prng
        from veles_tpu.analysis.cli import main
        wf = tmp_path / "wf.py"
        wf.write_text(WF_TEMPLATE)
        prng._streams.clear()
        prng.get("c1").seed(5)
        prng.get("c2").seed(5)       # VR501 warning
        try:
            assert main([str(wf)]) == 0
            capsys.readouterr()
            rc = main([str(wf), "--fail-on", "warning"])
            out = capsys.readouterr().out
            assert "VR501" in out
            assert rc == 1
        finally:
            prng._streams.clear()

    def test_help_documents_exit_codes(self, capsys):
        from veles_tpu.analysis.cli import main
        with pytest.raises(SystemExit):
            main(["--help"])
        out = capsys.readouterr().out
        assert "exit codes" in out
        assert "threshold" in out
