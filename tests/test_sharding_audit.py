"""Sharding & memory auditor suite (VS2xx/VM3xx, docs/static_analysis.md):
one seeded defect per rule caught from a PURELY ABSTRACT lowering (no
computation dispatched, no device array created — asserted), the
silent-replication fallback recording in parallel/sharding.py, the VM300
peak-HBM estimate within 2x of XLA's own compiled-buffer accounting on a
real workflow, and the CLI surfaces (`--mesh`, `--fsdp`, `--fail-on`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.analysis import (audit_sharded_step, has_errors,
                                lint_workflow)
from veles_tpu.analysis.sharding_audit import (activation_highwater,
                                               collective_stats,
                                               estimate_peak_hbm)
from veles_tpu.parallel import MeshConfig, make_mesh, sharding


def rules(findings):
    return {f.rule for f in findings}


def by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


def mc22(fsdp=False):
    return MeshConfig(make_mesh({"data": 2, "model": 2}), fsdp=fsdp)


# --------------------------------------------------------------------------
# satellite: the divisibility fallback paths in parallel/sharding.py now
# RECORD (layer, dim, axis, reason) instead of silently returning P()
# --------------------------------------------------------------------------
class TestFallbackRecording:
    def test_model_axis_non_dividing_records(self):
        mc = mc22()
        assert sharding.param_spec((64, 7), mc, ("l00_dense",
                                                 "weights")) == P()
        (fb,) = mc.sharding_fallbacks
        assert fb["layer"] == "l00_dense" and fb["param"] == "weights"
        assert fb["dim"] == 1 and fb["axis"] == "model"
        assert "not divisible" in fb["reason"]
        assert fb["shape"] == (64, 7)

    def test_dividing_dims_record_nothing(self):
        mc = mc22()
        assert sharding.param_spec((64, 32), mc) == P(None, "model")
        assert mc.sharding_fallbacks == []

    def test_fsdp_data_axis_non_dividing_records(self):
        mc = mc22(fsdp=True)
        # last dim shards over model; first dim 7 % data=2 falls back
        assert sharding.param_spec((7, 32), mc, ("l", "w")) == \
            P(None, "model")
        (fb,) = mc.sharding_fallbacks
        assert fb["axis"] == "data" and fb["dim"] == 0
        assert "fsdp" in fb["reason"]

    def test_fsdp_skip_when_model_axis_took_dim0(self):
        """1-D params: the model axis takes dim 0 (bias follows its
        weights), so fsdp cannot also shard it — recorded, not silent."""
        mc = mc22(fsdp=True)
        assert sharding.param_spec((32,), mc, ("l", "bias")) == \
            P("model")
        (fb,) = mc.sharding_fallbacks
        assert "already carries the model axis" in fb["reason"]
        # still sharded on the model axis — informational, NOT a
        # silent replication (VS201 reports it as info severity)
        assert fb["replicated"] is False

    def test_override_longer_than_shape_records(self):
        mc = mc22()
        assert sharding._safe_spec((8,), P(None, "model"), mc,
                                   ("l", "w")) == P()
        (fb,) = mc.sharding_fallbacks
        assert "names 2 dims" in fb["reason"]

    def test_override_non_dividing_axis_records(self):
        mc = mc22()
        assert sharding._safe_spec((8, 9), P(None, "model"), mc,
                                   ("l", "w")) == P()
        (fb,) = mc.sharding_fallbacks
        assert fb["dim"] == 1 and fb["axis"] == "model"

    def test_shard_params_plumbs_layer_and_param_names(self):
        mc = mc22()
        params = {"l03_dense": {"weights": np.zeros((64, 7),
                                                    np.float32)}}
        sharding.shard_params(params, mc)
        (fb,) = mc.sharding_fallbacks
        assert fb["layer"] == "l03_dense" and fb["param"] == "weights"

    def test_optimizer_slots_dedupe_to_one_record(self):
        """slot1/l/w and slot2/l/w are the SAME fallback as l/w — the
        slot prefix is stripped and the entry deduplicated."""
        mc = mc22()
        params = {"l00": {"w": np.zeros((64, 7), np.float32)}}
        sharding.shard_params(params, mc)
        sharding.shard_params({"slot1": params, "slot2": params}, mc)
        assert len(mc.sharding_fallbacks) == 1

    def test_clear_fallbacks(self):
        mc = mc22()
        sharding.param_spec((64, 7), mc)
        mc.clear_fallbacks()
        assert mc.sharding_fallbacks == []


# --------------------------------------------------------------------------
# parsers / estimators
# --------------------------------------------------------------------------
class TestCollectiveStats:
    HLO = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %dot), to_apply=%add
  %ag.1 = bf16[64,64]{1,0} all-gather(bf16[32,64]{1,0} %p0), dimensions={0}
  %rs = f32[16]{0} reduce-scatter(f32[32]{0} %x), dimensions={0}
  %use = f32[128,256]{1,0} fusion(f32[128,256]{1,0} %ar), kind=kLoop
"""

    def test_counts_and_bytes(self):
        stats = collective_stats(self.HLO)
        assert stats["all-reduce"] == {"count": 1,
                                       "bytes": 128 * 256 * 4}
        assert stats["all-gather"] == {"count": 1,
                                       "bytes": 64 * 64 * 2}
        assert stats["reduce-scatter"] == {"count": 1, "bytes": 16 * 4}

    def test_operand_references_not_double_counted(self):
        """A later instruction consuming %ar must not count again."""
        assert collective_stats(self.HLO)["all-reduce"]["count"] == 1

    def test_async_start_counts_result_shape_only(self):
        """Async def lines carry an (operand, result) tuple shape — only
        the result token is traffic; -done carries no new bytes."""
        hlo = """
  %ags = (f32[32,64]{1,0}, f32[64,64]{1,0}) all-gather-start(f32[32,64]{1,0} %p0)
  %agd = f32[64,64]{1,0} all-gather-done((f32[32,64]{1,0}, f32[64,64]{1,0}) %ags)
"""
        stats = collective_stats(hlo)
        assert stats["all-gather"] == {"count": 1,
                                       "bytes": 64 * 64 * 4}


class TestActivationHighwater:
    def test_chain_peaks_at_live_intermediates(self):
        def f(x):
            y = x * 2.0        # intermediate: live until z
            z = y + 1.0        # jaxpr output: excluded
            return z

        closed = jax.make_jaxpr(f)(
            jax.ShapeDtypeStruct((1024,), jnp.float32))
        assert activation_highwater(closed.jaxpr) == 1024 * 4

    def test_recurses_into_pjit(self):
        def f(x):
            y = x * 2.0
            return (y * y).sum()

        closed = jax.make_jaxpr(jax.jit(f))(
            jax.ShapeDtypeStruct((1024,), jnp.float32))
        assert activation_highwater(closed.jaxpr) >= 1024 * 4


# --------------------------------------------------------------------------
# seeded defects: one synthetic broken step per rule, audited from pure
# ShapeDtypeStructs — nothing to execute even by accident
# --------------------------------------------------------------------------
def synth_spec(mc, dtype=jnp.float32, donate=(0,), upcast=False,
               n=256, mb=8):
    """A DP-shaped train step with REPLICATED params (the seeded VS200/
    VS202 defect: gradients psum at full parameter size every step)."""
    repl = NamedSharding(mc.mesh, P())
    batch_sh = NamedSharding(mc.mesh, P("data"))
    params = {"w": jax.ShapeDtypeStruct((n, n), dtype, sharding=repl)}
    x = jax.ShapeDtypeStruct((mb, n), jnp.float32, sharding=batch_sh)

    def step(p, xx):
        w = p["w"]
        if upcast:
            w = w.astype(jnp.float32)
        loss = lambda q: (xx @ (q["w"].astype(jnp.float32)
                                if upcast else q["w"])).sum()  # noqa: E731
        g = jax.grad(loss)(p)
        return {"w": (p["w"] - 0.01 * g["w"].astype(p["w"].dtype))}

    fn = jax.jit(step, donate_argnums=donate,
                 out_shardings={"w": repl})
    return {"fn": fn, "args": (params, x), "mesh_config": mc,
            "donate_argnums": donate, "carry_argnums": (0,),
            "params_argnums": (0,), "opt_argnums": (),
            "minibatch_bytes": mb * n * 4, "name": "synth.step"}


class TestSeededDefects:
    def test_vs200_full_param_psum_exceeds_minibatch(self):
        fs = audit_sharded_step(synth_spec(mc22()))
        hits = by_rule(fs, "VS200")
        assert hits and hits[0].severity == "warning"
        assert "ICI" in hits[0].message

    def test_vs201_reports_recorded_fallback(self):
        mc = mc22()
        sharding.param_spec((64, 7), mc, ("l00_dense", "weights"))
        fs = audit_sharded_step(synth_spec(mc))
        hits = by_rule(fs, "VS201")
        assert hits and "l00_dense.weights" in hits[0].message
        assert "not divisible" in hits[0].message

    def test_vs202_fsdp_psum_instead_of_reduce_scatter(self):
        """Replicated params under fsdp=True: gradients all-reduce at
        full parameter size with no reduce-scatter — ZeRO-3's memory
        win silently lost."""
        fs = audit_sharded_step(synth_spec(mc22(fsdp=True)))
        hits = by_rule(fs, "VS202")
        assert hits and "reduce-scatter" in hits[0].message

    def test_vs202_silent_on_proper_fsdp_trainer(self):
        """The real StagedTrainer under fsdp shards params properly and
        pins the update's out_shardings — GSPMD scatters the gradient
        reduction and VS202 stays silent (the positive case above only
        fires on the seeded replicated-params defect).  The routine
        bias fsdp-skip records surface as info-severity VS201, so a
        clean fsdp config has no VS201 warnings either (the --fail-on
        warning CI gate passes)."""
        pytest.importorskip("sklearn")
        wf = build_digits_wf(mc22(fsdp=True), hidden=64,
                             name="digits-fsdp-clean")
        fs = lint_workflow(wf)
        assert "VS202" not in rules(fs)
        vs201 = by_rule(fs, "VS201")
        assert vs201   # the bias skips ARE reported...
        assert all(f.severity == "info" for f in vs201)  # ...as info

    def test_vs203_bf16_param_upcast_in_step(self):
        fs = audit_sharded_step(synth_spec(mc22(), dtype=jnp.bfloat16,
                                           upcast=True))
        hits = by_rule(fs, "VS203")
        assert hits and "upcast to f32" in hits[0].message

    def test_vs203_silent_without_upcast(self):
        fs = audit_sharded_step(synth_spec(mc22()))
        assert "VS203" not in rules(fs)

    def test_vm301_missing_donation(self):
        fs = audit_sharded_step(synth_spec(mc22(), donate=()))
        hits = by_rule(fs, "VM301")
        assert hits and "not donated" in hits[0].message

    def test_vm301_silent_when_donated(self):
        fs = audit_sharded_step(synth_spec(mc22()))
        assert "VM301" not in rules(fs)

    def test_vm300_predicts_oom_against_tiny_capacity(self):
        fs = audit_sharded_step(synth_spec(mc22()), hbm_gib=1e-5)
        hits = by_rule(fs, "VM300")
        assert hits and hits[0].severity == "error"
        assert "predicted OOM" in hits[0].message

    def test_vm300_info_estimate_always_reported(self):
        fs = audit_sharded_step(synth_spec(mc22()))
        hits = by_rule(fs, "VM300")
        assert hits and hits[0].severity == "info"
        assert "params" in hits[0].message

    def test_audit_is_purely_abstract_no_device_arrays(self):
        """The acceptance gate: the whole audit runs off
        ShapeDtypeStructs — no computation dispatched, no device array
        allocated."""
        import gc
        spec = synth_spec(mc22())
        for leaf in jax.tree_util.tree_leaves(spec["args"]):
            assert isinstance(leaf, jax.ShapeDtypeStruct)
        gc.collect()
        before = len(jax.live_arrays())
        fs = audit_sharded_step(spec)
        assert fs   # it did find things (VS200 + VM300 at least)
        # the audit allocates NOTHING (collection can only shrink it)
        assert len(jax.live_arrays()) <= before

    def test_untraceable_step_reports_vj100(self):
        spec = synth_spec(mc22())

        def bad(p, x):
            if float(x.sum()) > 0:   # concretizes a tracer
                return p
            return p

        spec["fn"] = bad
        fs = audit_sharded_step(spec)
        assert "VJ100" in rules(fs) and has_errors(fs)


# --------------------------------------------------------------------------
# the real StagedTrainer under a mesh: hook + lint_workflow + VM300
# accuracy against XLA's own buffer accounting
# --------------------------------------------------------------------------
def build_digits_wf(mc, hidden=64, name="digits-audit"):
    from sklearn.datasets import load_digits
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    prng.seed_all(7)
    d = load_digits()
    loader = FullBatchLoader(
        None, data=(d.data / 16.0).astype(np.float32),
        labels=d.target.astype(np.int32), minibatch_size=64,
        class_lengths=[0, 297, 1500])
    wf = StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": hidden},
                {"type": "softmax", "output_sample_shape": 10}],
        loader=loader, decision_config={"max_epochs": 1},
        mesh_config=mc, name=name)
    wf.initialize()
    return wf


@pytest.fixture(scope="module")
def digits_wf():
    pytest.importorskip("sklearn")
    return build_digits_wf(mc22())


class TestEstimateAccounting:
    def test_aliased_args_count_once(self):
        """The autoencoder passes its dataset as BOTH data and targets —
        one physical buffer, counted once (review finding: a ~9 GiB
        dataset must not become a spurious 18 GiB predicted OOM)."""
        mc = mc22()
        repl = NamedSharding(mc.mesh, P())
        data = jax.ShapeDtypeStruct((1024, 64), jnp.float32,
                                    sharding=repl)

        def step(d, t):
            return (d - t).sum()

        spec = {"fn": jax.jit(step), "args": (data, data),
                "mesh_config": mc, "donate_argnums": (),
                "carry_argnums": (), "params_argnums": (),
                "opt_argnums": (), "minibatch_bytes": 0,
                "name": "alias.step"}
        est = estimate_peak_hbm(spec)
        one_copy = 1024 * 64 * 4
        assert est["other_args"] == one_copy
        distinct = jax.ShapeDtypeStruct((1024, 64), jnp.float32,
                                        sharding=repl)
        spec["args"] = (data, distinct)
        assert estimate_peak_hbm(spec)["other_args"] == 2 * one_copy

    def test_autoencoder_trainer_spec_shares_target_mirror(self):
        """StagedTrainer's hook preserves the data/targets aliasing in
        its abstract mirrors (same ShapeDtypeStruct object)."""
        pytest.importorskip("sklearn")
        from sklearn.datasets import load_digits
        from veles_tpu import prng
        from veles_tpu.loader.fullbatch import FullBatchLoader
        from veles_tpu.models.standard_workflow import StandardWorkflow
        prng.seed_all(7)
        d = load_digits()
        loader = FullBatchLoader(
            None, data=(d.data / 16.0).astype(np.float32),
            labels=d.target.astype(np.int32), minibatch_size=64,
            class_lengths=[0, 297, 1500])
        wf = StandardWorkflow(
            layers=[{"type": "all2all_tanh", "output_sample_shape": 32},
                    {"type": "all2all", "output_sample_shape": 64}],
            loss="mse", loader=loader,
            decision_config={"max_epochs": 1},
            mesh_config=mc22(), name="digits-ae")
        wf.initialize()
        spec = wf.trainer.lint_sharding_spec()
        assert spec["args"][4] is spec["args"][6]   # data IS targets

    def test_act_bytes_override_wins_over_heuristic(self):
        """The auditor feeds XLA's per-device temp bytes in as the
        activation term (exact, includes replicated DP gradients the
        //data_size heuristic undercounts)."""
        spec = synth_spec(mc22())
        est_h = estimate_peak_hbm(spec)
        est_o = estimate_peak_hbm(spec, act_bytes=12345)
        assert est_o["activations"] == 12345
        assert est_o["peak"] - est_h["peak"] == \
            12345 - est_h["activations"]


class TestStagedTrainerAudit:
    def test_hook_exposes_sharded_spec(self, digits_wf):
        spec = digits_wf.trainer.lint_sharding_spec()
        assert spec is not None
        # params, velocity, class-stat acc, sentinel health
        assert spec["carry_argnums"] == (0, 1, 2, 3)
        assert spec["donate_argnums"] == (0, 1, 2, 3)
        assert spec["minibatch_bytes"] > 0
        for leaf in jax.tree_util.tree_leaves(spec["args"]):
            assert isinstance(leaf, jax.ShapeDtypeStruct)

    def test_staging_hook_defers_to_sharding_hook_under_mesh(self,
                                                            digits_wf):
        assert digits_wf.trainer.lint_staging_spec() is None
        assert digits_wf.trainer.lint_sharding_spec() is not None

    def test_lint_workflow_reports_vm300_no_dispatch(self, digits_wf):
        import gc
        gc.collect()   # flush earlier tests' dead workflows first
        before = len(jax.live_arrays())
        fs = lint_workflow(digits_wf)
        # the audit allocates NOTHING (collection can only shrink it)
        assert len(jax.live_arrays()) <= before
        assert by_rule(fs, "VM300")
        assert not has_errors(fs)

    def test_vm300_estimate_within_2x_of_xla_accounting(self, digits_wf):
        """Acceptance gate: the params+opt+activation estimate lands
        within 2x of XLA's own per-device buffer stats for the compiled
        step (argument + output + temp - aliased)."""
        spec = digits_wf.trainer.lint_sharding_spec()
        est = estimate_peak_hbm(spec)
        ma = spec["fn"].lower(*spec["args"]).compile().memory_analysis()
        measured = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        assert measured > 0
        ratio = est["peak"] / measured
        assert 0.5 <= ratio <= 2.0, (est, measured)

    def test_fallbacks_surface_through_lint_workflow(self):
        """A layer whose output dim doesn't divide the model axis is
        reported by name through the full lint pipeline (the seeded
        VS201 defect on a real workflow)."""
        pytest.importorskip("sklearn")
        # 63 % model=2 != 0 — the hidden layer's sharding falls back
        wf = build_digits_wf(mc22(), hidden=63, name="digits-fallback")
        fs = lint_workflow(wf)
        hits = by_rule(fs, "VS201")
        assert hits and any("l00_all2all_tanh" in f.message
                            for f in hits)


# --------------------------------------------------------------------------
# CLI surfaces
# --------------------------------------------------------------------------
class TestCLI:
    def test_parse_mesh_dxm(self):
        from veles_tpu.analysis.cli import parse_mesh
        assert parse_mesh("2x2") == {"data": 2, "model": 2}
        assert parse_mesh("4X1") == {"data": 4, "model": 1}
        assert parse_mesh("data=4,model=2") == {"data": 4, "model": 2}
        with pytest.raises(SystemExit):
            parse_mesh("2x2x2")
        with pytest.raises(SystemExit):
            parse_mesh("axb")

    def test_fsdp_without_mesh_is_usage_error(self, tmp_path):
        from veles_tpu.analysis.cli import main
        wf = tmp_path / "wf.py"
        wf.write_text("def run(load, main):\n    pass\n")
        with pytest.raises(SystemExit):
            main([str(wf), "--fsdp"])

    def test_mesh_lint_reports_sharding_findings(self, capsys):
        """Acceptance gate: `veles-tpu-lint --mesh 2x2` on a sample
        workflow reports VS2xx/VM3xx findings and exits 0 (warnings
        don't fail by default)."""
        pytest.importorskip("sklearn")
        import os
        from veles_tpu.analysis.cli import main
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        rc = main([os.path.join(repo, "samples", "digits_mlp.py"),
                   os.path.join(repo, "samples", "digits_config.py"),
                   "--mesh", "2x2"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "VM300" in out

    def test_main_cli_lint_composes_with_mesh(self, capsys,
                                              monkeypatch):
        """`python -m veles_tpu WF CFG --lint --mesh data=2,model=2`:
        the lint path initializes under the virtual CPU mesh and the
        sharding findings ride the normal --lint exit semantics."""
        pytest.importorskip("sklearn")
        import os
        from veles_tpu.__main__ import Main
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        m = Main(argv=[os.path.join(repo, "samples", "digits_mlp.py"),
                       os.path.join(repo, "samples",
                                    "digits_config.py"),
                       "--lint", "--mesh", "data=2,model=2"])
        rc = m.run()
        out = capsys.readouterr().out
        assert rc == 0
        assert "VM300" in out
        assert m.workflow._initialized   # mesh lint initializes...
        # ...but the trainer never stepped
        assert m.workflow.trainer._step_counter == 0

    def test_fail_on_warning_gates(self, capsys):
        """--fail-on warning turns the sample's VS200 warning into a
        non-zero exit; the default (error) does not."""
        pytest.importorskip("sklearn")
        import os
        from veles_tpu.analysis.cli import main
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        argv = [os.path.join(repo, "samples", "digits_mlp.py"),
                os.path.join(repo, "samples", "digits_config.py"),
                "--mesh", "2x2", "--fail-on", "warning"]
        rc = main(argv)
        out = capsys.readouterr().out
        assert "warning" in out
        assert rc == 1
