"""Static-analysis suite: one minimal failing workflow per linter rule,
clean passes over real samples, the Bool structural metadata the rules
see through, and the CLI surfaces (`veles-tpu-lint`, `--lint`).

Rule catalog: docs/static_analysis.md."""

import pytest

from veles_tpu.analysis import (ERROR, audit_step, format_findings,
                                has_errors, lint_workflow)
from veles_tpu.mutable import Bool
from veles_tpu.plumbing import Repeater
from veles_tpu.units import TrivialUnit, Unit
from veles_tpu.workflow import Workflow


def rules(findings):
    return {f.rule for f in findings}


def errors(findings):
    return [f for f in findings if f.severity == ERROR]


# classes used by the source-scanning rules MUST be file-backed (defined
# at module level) so inspect.getsource works
class OneWayWriter(Unit):
    def run(self):
        self.v = 9  # linked one-way in the test below: runtime raise


class NeedyUnit(Unit):
    def __init__(self, workflow, **kw):
        super(NeedyUnit, self).__init__(workflow, **kw)
        self.demand("never_set")


class ProvidingProducer(Unit):
    def initialize(self, **kwargs):
        self.made_value = 123


class AnnotatedProducer(Unit):
    def initialize(self, **kwargs):
        self.made_value: int = 123   # AnnAssign form must count too


class NeedsProduced(Unit):
    def __init__(self, workflow, **kw):
        super(NeedsProduced, self).__init__(workflow, **kw)
        self.demand("made_value")


class GateController(Unit):
    """Runtime gate surgery: opens another unit's gate from run()."""

    def run(self):
        self.worker.gate_block <<= False


class ProvidingWorkflow(Workflow):
    """The workflow's own initialize() provides a unit's demand."""

    def initialize(self, **kwargs):
        self["con"].made_value = 7
        super(ProvidingWorkflow, self).initialize(**kwargs)


class TestBoolStructure:
    def test_derived_bool_exposes_operands_and_op(self):
        a, b = Bool(True), Bool(False)
        g = a & ~b
        assert g.derived and g.op == "&"
        assert g.operands[0] is a
        assert g.operands[1].op == "~"
        assert set(map(id, g.leaves())) == {id(a), id(b)}

    def test_expression_and_repr(self):
        a, b = Bool(True), Bool(False)
        g = a & ~b
        assert g.expression() == "(True & ~False)"
        assert repr(g) == "<Bool (True & ~False) = True>"
        assert repr(a) == "<Bool value = True>"
        a <<= False
        assert g.expression() == "(False & ~False)"  # live, not a snapshot

    def test_value_bool_is_its_own_leaf(self):
        a = Bool(True)
        assert a.leaves() == [a]
        assert not a.derived and a.op is None and a.operands == ()

    def test_shared_leaf_counted_once(self):
        a = Bool(False)
        assert (a | ~a).leaves() == [a]

    def test_bare_expr_bool_renders_without_crash(self):
        """A derived Bool built directly with _expr and no operands (the
        pre-metadata form) must still repr, whatever its op tag."""
        assert Bool(_expr=lambda: True, _name="~").expression() == "<~>"
        assert "derived" not in repr(Bool(_expr=lambda: True, _name="&"))

    def test_tautology_over_shared_leaf_is_constant_true(self):
        """a | ~a is true under every assignment of a — the gate-deadlock
        rule must fire even though the leaf itself is flippable."""
        wf = Workflow(name="taut")
        u = TrivialUnit(wf, name="blocked")
        u.link_from(wf.start_point)
        u.flag = Bool(False)              # named attr: flippable leaf
        u.gate_block = u.flag | ~u.flag   # ...but the expression is a
        wf.end_point.link_from(u)         # tautology
        fs = lint_workflow(wf)
        assert any(f.rule == "VG003" and f.unit == "blocked" for f in fs)


class TestCycleRule:
    def build(self, closer):
        wf = Workflow(name="cyc")
        a = TrivialUnit(wf, name="a")
        b = TrivialUnit(wf, name="b")
        a.link_from(wf.start_point)
        b.link_from(a)
        if closer:
            rpt = Repeater(wf)
            rpt.link_from(b)
            a.link_from(rpt)
        else:
            a.link_from(b)
        wf.end_point.link_from(b)
        return wf

    def test_cycle_without_repeater_fires_vg001(self):
        fs = lint_workflow(self.build(closer=False))
        assert "VG001" in rules(errors(fs))

    def test_repeater_closed_cycle_is_clean(self):
        fs = lint_workflow(self.build(closer=True))
        assert "VG001" not in rules(fs)


class TestReachabilityRule:
    def test_unreachable_linked_unit_warns(self):
        wf = Workflow(name="unr")
        a = TrivialUnit(wf, name="a")
        orphan = TrivialUnit(wf, name="orphan")
        sink = TrivialUnit(wf, name="sink")
        a.link_from(wf.start_point)
        sink.link_from(orphan)      # orphan has links but no path from start
        wf.end_point.link_from(a)
        fs = lint_workflow(wf)
        hits = [f for f in fs if f.rule == "VG002" and f.unit == "orphan"]
        assert hits and hits[0].severity == "warning"

    def test_passive_unit_is_info_only(self):
        wf = Workflow(name="pas")
        a = TrivialUnit(wf, name="a")
        TrivialUnit(wf, name="handle")   # no links at all
        a.link_from(wf.start_point)
        wf.end_point.link_from(a)
        fs = lint_workflow(wf)
        hits = [f for f in fs if f.rule == "VG002" and f.unit == "handle"]
        assert hits and hits[0].severity == "info"
        assert not has_errors(fs)


class TestGateDeadlockRule:
    def test_unreachable_predecessor_fires_vg003(self):
        wf = Workflow(name="gd")
        a = TrivialUnit(wf, name="a")
        stranded = TrivialUnit(wf, name="stranded")
        c = TrivialUnit(wf, name="c")
        a.link_from(wf.start_point)
        c.link_from(a, stranded)     # c waits on a unit that never fires
        wf.end_point.link_from(c)
        fs = lint_workflow(wf)
        hits = [f for f in fs if f.rule == "VG003" and f.unit == "c"]
        assert hits and hits[0].severity == ERROR

    def test_constant_true_gate_block_fires_vg003(self):
        wf = Workflow(name="cg")
        u = TrivialUnit(wf, name="blocked")
        u.link_from(wf.start_point)
        u.gate_block = Bool(True)    # anonymous: nothing can ever flip it
        wf.end_point.link_from(u)
        fs = lint_workflow(wf)
        hits = [f for f in fs if f.rule == "VG003" and f.unit == "blocked"]
        assert hits and "constant-true" in hits[0].message

    def test_runtime_gate_write_suppresses_constant_true(self):
        """A unit whose run() writes another unit's gate slot
        (`x.gate_block <<= False`) proves the program manipulates gates
        at runtime — the constant-true rule must stay silent."""
        wf = Workflow(name="rg")
        ctl = GateController(wf, name="ctl")
        worker = TrivialUnit(wf, name="worker")
        ctl.worker = worker
        worker.gate_block = Bool(True)     # opened by ctl at runtime
        ctl.link_from(wf.start_point)
        worker.link_from(ctl)
        wf.end_point.link_from(worker)
        assert "VG003" not in rules(lint_workflow(wf))

    def test_canonical_loop_with_closure_flag_is_clean(self):
        """The test_units_workflow repeater idiom: the completion flag is
        a closure var the Decision flips — the linter must see the flip
        site through the method's closure cells and NOT flag the
        ~complete end_point gate."""
        wf = Workflow(name="loop")
        rpt = Repeater(wf)
        body = TrivialUnit(wf, name="body")
        complete = Bool(False)

        class Decision(Unit):
            def run(self):
                complete.set(True)

        dec = Decision(wf)
        rpt.link_from(wf.start_point)
        body.link_from(rpt)
        dec.link_from(body)
        rpt.link_from(dec)
        rpt.gate_block = complete
        wf.end_point.link_from(dec)
        wf.end_point.gate_block = ~complete
        fs = lint_workflow(wf)
        assert "VG003" not in rules(fs)
        assert "VG001" not in rules(fs)  # repeater closes the cycle
        assert not has_errors(fs)


class TestDanglingLinkRule:
    def build_linked_pair(self):
        wf = Workflow(name="dl")
        src = TrivialUnit(wf, name="src")
        dst = TrivialUnit(wf, name="dst")
        src.out = 1
        dst.link_attrs(src, ("inp", "out"))
        dst.link_from(wf.start_point)
        wf.end_point.link_from(dst)
        return wf, src, dst

    def test_del_refd_source_fires_vg004(self):
        wf, src, dst = self.build_linked_pair()
        src.unlink_all()
        wf.del_ref(src)
        fs = lint_workflow(wf)
        hits = [f for f in fs if f.rule == "VG004"]
        assert hits and hits[0].unit == "dst" and "inp" in hits[0].message

    def test_live_link_is_clean(self):
        wf, _, _ = self.build_linked_pair()
        assert "VG004" not in rules(lint_workflow(wf))

    def test_del_ref_drops_empty_by_name_bucket(self):
        """Linter ground truth (and container hygiene): removing the last
        unit of a name must remove the name itself."""
        wf, src, _ = self.build_linked_pair()
        assert "src" in wf._by_name
        wf.del_ref(src)
        assert "src" not in wf._by_name
        with pytest.raises(KeyError):
            wf["src"]

    def test_unlink_all_clears_one_sided_entries(self):
        wf = Workflow(name="ua")
        a = TrivialUnit(wf, name="a")
        b = TrivialUnit(wf, name="b")
        b.link_from(a)
        b.links_to.add(a)            # simulate sloppy direct graph surgery
        b.unlink_all()
        assert not b.links_from and not b.links_to
        assert b not in a.links_to and b not in a.links_from

    def test_unlink_attrs_inverse_of_link_attrs(self):
        wf = Workflow(name="ul")
        src = TrivialUnit(wf, name="src")
        dst = TrivialUnit(wf, name="dst")
        src.out = 7
        dst.link_attrs(src, ("inp", "out"))
        assert dst.linked_attrs == {"inp": (src, "out", False)}
        dst.unlink_attrs("inp")
        assert dst.linked_attrs == {}


class TestOneWayWriteRule:
    def test_run_method_write_to_one_way_link_fires_vg005(self):
        wf = Workflow(name="ow")
        src = TrivialUnit(wf, name="src")
        src.v = 1
        w = OneWayWriter(wf, name="w")
        w.link_attrs(src, "v")
        w.link_from(wf.start_point)
        wf.end_point.link_from(w)
        fs = lint_workflow(wf)
        hits = [f for f in fs if f.rule == "VG005"]
        assert hits and hits[0].unit == "w"
        assert "ONE-WAY" in hits[0].message

    def test_two_way_link_is_clean(self):
        wf = Workflow(name="ow2")
        src = TrivialUnit(wf, name="src")
        src.v = 1
        w = OneWayWriter(wf, name="w")
        w.link_attrs(src, "v", two_way=True)
        w.link_from(wf.start_point)
        wf.end_point.link_from(w)
        assert "VG005" not in rules(lint_workflow(wf))


class TestDemandRule:
    def test_unsatisfiable_demand_fires_vg006(self):
        wf = Workflow(name="dm")
        n = NeedyUnit(wf, name="needy")
        n.link_from(wf.start_point)
        wf.end_point.link_from(n)
        fs = lint_workflow(wf)
        hits = [f for f in fs if f.rule == "VG006"]
        assert hits and "never_set" in hits[0].message

    def test_demand_satisfied_by_data_link_is_clean(self):
        wf = Workflow(name="dm2")
        src = TrivialUnit(wf, name="src")
        src.out = 5
        n = NeedyUnit(wf, name="needy")
        n.link_attrs(src, ("never_set", "out"))
        n.link_from(wf.start_point)
        wf.end_point.link_from(n)
        assert "VG006" not in rules(lint_workflow(wf))

    def test_demand_satisfied_by_workflow_initialize_is_clean(self):
        """The workflow is a Unit too: its own initialize() assigning the
        demanded attribute must count as a provider."""
        wf = ProvidingWorkflow(name="dm4")
        con = NeedsProduced(wf, name="con")
        con.link_from(wf.start_point)
        wf.end_point.link_from(con)
        assert "VG006" not in rules(lint_workflow(wf))

    def test_demand_satisfied_by_producer_initialize_is_clean(self):
        """The requeue pattern: the producer's initialize() assigns the
        attribute — statically visible, so no finding."""
        wf = Workflow(name="dm3")
        pro = ProvidingProducer(wf, name="pro")
        con = NeedsProduced(wf, name="con")
        con.link_attrs(pro, "made_value")
        pro.link_from(wf.start_point)
        con.link_from(pro)
        wf.end_point.link_from(con)
        assert "VG006" not in rules(lint_workflow(wf))

    def test_annotated_assignment_counts_as_provider(self):
        """`self.x: int = 123` (AnnAssign) must register as an
        assignment — no false-positive VG006."""
        wf = Workflow(name="dm5")
        pro = AnnotatedProducer(wf, name="pro")
        con = NeedsProduced(wf, name="con")
        con.link_attrs(pro, "made_value")
        pro.link_from(wf.start_point)
        con.link_from(pro)
        wf.end_point.link_from(con)
        assert "VG006" not in rules(lint_workflow(wf))


class TestStagingAuditor:
    def test_host_callback_in_step_fires_vj101(self):
        import jax
        import jax.numpy as jnp

        def step(x):
            jax.debug.print("x={}", x)
            return x

        fs = audit_step(step, (jnp.zeros((3,), jnp.float32),))
        assert "VJ101" in rules(errors(fs))

    def test_weak_typed_input_fires_vj102(self):
        import jax.numpy as jnp
        fs = audit_step(lambda x, s: x * s, (jnp.zeros((3,)), 2.0))
        hits = [f for f in fs if f.rule == "VJ102"]
        assert hits and hits[0].severity == "warning"

    def test_carry_dtype_drift_fires_vj103(self):
        import jax
        import jax.numpy as jnp
        fs = audit_step(lambda x: x * 1.0,
                        (jax.ShapeDtypeStruct((3,), jnp.int32),),
                        carry_argnums=(0,))
        hits = [f for f in fs if f.rule == "VJ103"]
        assert hits and "recompiles" in hits[0].message

    def test_clean_step_has_no_findings(self):
        import jax.numpy as jnp

        def step(params, x):
            return params + x.sum()

        fs = audit_step(step, (jnp.zeros(()), jnp.zeros((4,))),
                        carry_argnums=(0,))
        assert fs == []

    def test_untraceable_step_fires_vj100(self):
        import jax.numpy as jnp

        def step(x):
            if float(x.sum()) > 0:   # concretizes a tracer: untraceable
                return x
            return -x

        fs = audit_step(step, (jnp.ones((2,)),))
        assert "VJ100" in rules(errors(fs))

    def test_iter_primitives_recurses_into_dict_params(self):
        """Satellite: a nested jaxpr stashed in a DICT-valued eqn.params
        (keyed branch/function tables) must not hide from VJ101."""
        import jax
        import jax.numpy as jnp
        from types import SimpleNamespace

        from veles_tpu.analysis.staging import iter_primitives

        def leaky(x):
            jax.debug.print("x={}", x)
            return x

        inner = jax.make_jaxpr(leaky)(jnp.zeros(()))
        fake_eqn = SimpleNamespace(
            primitive=SimpleNamespace(name="fake_call"),
            params={"funs": {"branch_a": inner}})
        fake_jaxpr = SimpleNamespace(eqns=[fake_eqn])
        names = {n for n, _ in iter_primitives(fake_jaxpr)}
        assert "debug_callback" in names

    def test_iter_primitives_recurses_into_cond_branch_lists(self):
        """Satellite: jaxprs nested in LIST/TUPLE-valued eqn.params —
        cond/switch carry their branches as a tuple of ClosedJaxprs —
        must not be skipped by any auditor built on iter_primitives."""
        import jax
        import jax.numpy as jnp

        from veles_tpu.analysis.staging import iter_primitives

        def leaky_branch(x):
            jax.debug.print("x={}", x)
            return x * 2.0

        def cond_fn(p, x):
            return jax.lax.cond(p, leaky_branch, lambda x: x, x)

        closed = jax.make_jaxpr(cond_fn)(True, jnp.zeros(()))
        names = {n for n, _ in iter_primitives(closed.jaxpr)}
        assert "cond" in names
        assert "debug_callback" in names     # inside a branch list

        def switch_fn(i, x):
            return jax.lax.switch(
                i, [lambda x: x, leaky_branch, lambda x: -x], x)

        closed = jax.make_jaxpr(switch_fn)(0, jnp.zeros(()))
        names = {n for n, _ in iter_primitives(closed.jaxpr)}
        assert "debug_callback" in names

    def test_nested_containers_in_params_recurse(self):
        """Dicts of lists of jaxprs (and vice versa) all unwrap."""
        import jax
        import jax.numpy as jnp
        from types import SimpleNamespace

        from veles_tpu.analysis.staging import iter_primitives

        def leaky(x):
            jax.debug.print("x={}", x)
            return x

        inner = jax.make_jaxpr(leaky)(jnp.zeros(()))
        fake_eqn = SimpleNamespace(
            primitive=SimpleNamespace(name="fake_call"),
            params={"table": {"a": [inner], "b": ([inner],)}})
        fake_jaxpr = SimpleNamespace(eqns=[fake_eqn])
        names = {n for n, _ in iter_primitives(fake_jaxpr)}
        assert "debug_callback" in names

    def test_lint_workflow_consumes_staging_hook(self):
        """lint_workflow pulls a unit's lint_staging_spec() and audits the
        staged step it describes (StagedTrainer exposes the same hook
        once initialized)."""
        import jax
        import jax.numpy as jnp

        class Staged(TrivialUnit):
            def lint_staging_spec(self):
                def step(acc):
                    jax.debug.print("acc={}", acc)
                    return acc
                return {"fn": step,
                        "args": (jax.ShapeDtypeStruct((), jnp.float32),),
                        "carry_argnums": (0,), "name": "staged.step"}

        wf = Workflow(name="hook")
        s = Staged(wf, name="staged")
        s.link_from(wf.start_point)
        wf.end_point.link_from(s)
        fs = lint_workflow(wf)
        assert any(f.rule == "VJ101" and f.unit == "staged.step"
                   for f in fs)
        assert "VJ101" not in rules(lint_workflow(wf, staging=False))


class TestFindingSurface:
    def test_text_and_json_formats(self):
        wf = Workflow(name="fmt")
        u = TrivialUnit(wf, name="blocked")
        u.link_from(wf.start_point)
        u.gate_block = Bool(True)
        wf.end_point.link_from(u)
        fs = lint_workflow(wf)
        text = format_findings(fs)
        assert "VG003" in text and "hint:" in text
        import json
        data = json.loads(format_findings(fs, "json"))
        assert any(d["rule"] == "VG003" for d in data)
        assert {"rule", "severity", "unit", "message", "hint"} <= set(
            data[0])

    def test_sorted_most_severe_first(self):
        wf = Workflow(name="sort")
        u = TrivialUnit(wf, name="blocked")
        u.link_from(wf.start_point)
        u.gate_block = Bool(True)
        TrivialUnit(wf, name="handle")      # info finding
        wf.end_point.link_from(u)
        fs = lint_workflow(wf)
        sev = [f.severity for f in fs]
        assert sev == sorted(sev, key=("error", "warning", "info").index)


CYCLIC_WF = '''
from veles_tpu.units import TrivialUnit
from veles_tpu.workflow import Workflow

def run(load, main):
    wf = load(Workflow, name="cyclic")
    a = TrivialUnit(wf, name="a")
    b = TrivialUnit(wf, name="b")
    a.link_from(wf.start_point)
    b.link_from(a)
    a.link_from(b)          # control cycle, no Repeater
    wf.end_point.link_from(b)
    main()
'''


class TestCLI:
    def test_lint_flag_exits_nonzero_on_cycle_without_dispatch(self,
                                                               tmp_path,
                                                               capsys,
                                                               monkeypatch):
        """`--lint` on a cyclic workflow: non-zero exit, and the workflow
        is never initialized — so no param init, no XLA dispatch."""
        # (Main.run() enables the persistent compile cache, which a
        # CPU run declines by itself — nothing to switch off here)
        from veles_tpu.__main__ import Main
        wf_file = tmp_path / "cyclic_wf.py"
        wf_file.write_text(CYCLIC_WF)
        m = Main(argv=[str(wf_file), "--lint"])
        rc = m.run()
        assert rc != 0
        assert m.workflow is not None
        assert not m.workflow._initialized   # nothing ran, nothing staged
        assert "VG001" in capsys.readouterr().out

    def test_lint_runs_even_if_workflow_file_skips_main(self, tmp_path,
                                                        capsys,
                                                        monkeypatch):
        """A workflow file that builds via load() but never calls main()
        must still be linted — not silently exit 0."""
        from veles_tpu.__main__ import Main
        wf_file = tmp_path / "no_main_wf.py"
        wf_file.write_text(CYCLIC_WF.replace("    main()\n", ""))
        assert Main(argv=[str(wf_file), "--lint"]).run() != 0
        assert "VG001" in capsys.readouterr().out

    def test_lint_skips_snapshot_import(self, tmp_path, capsys,
                                         monkeypatch):
        """--lint must not unpickle a checkpoint: snapshot restore is
        heavy, side-effectful I/O the lint contract excludes."""
        from veles_tpu.__main__ import Main
        wf_file = tmp_path / "cyclic_wf.py"
        wf_file.write_text(CYCLIC_WF)
        snap = tmp_path / "ckpt.pkl"
        snap.write_bytes(b"not a pickle at all")   # import_ would raise
        m = Main(argv=[str(wf_file), "--snapshot", str(snap), "--lint"])
        assert m.run() != 0                        # lint verdict, no raise
        assert "VG001" in capsys.readouterr().out

    def test_lint_console_script_main(self, tmp_path, capsys):
        from veles_tpu.analysis.cli import main
        wf_file = tmp_path / "cyclic_wf.py"
        wf_file.write_text(CYCLIC_WF)
        assert main([str(wf_file)]) == 1
        assert "VG001" in capsys.readouterr().out

    def test_fail_on_warning_threshold(self, tmp_path, capsys):
        """Satellite: --fail-on warning exits non-zero on warning-only
        findings (the CI gate knob); the default (error) stays 0."""
        from veles_tpu.analysis.cli import main
        wf_file = tmp_path / "warn_wf.py"
        # an unreachable-but-linked unit: VG002 warning, no errors
        wf_file.write_text('''
from veles_tpu.units import TrivialUnit
from veles_tpu.workflow import Workflow

def run(load, main):
    wf = load(Workflow, name="warny")
    a = TrivialUnit(wf, name="a")
    orphan = TrivialUnit(wf, name="orphan")
    sink = TrivialUnit(wf, name="sink")
    a.link_from(wf.start_point)
    sink.link_from(orphan)
    wf.end_point.link_from(a)
    main()
''')
        assert main([str(wf_file)]) == 0
        assert main([str(wf_file), "--fail-on", "warning"]) == 1
        assert main([str(wf_file), "--strict"]) == 1   # legacy alias
        assert "VG002" in capsys.readouterr().out

    def test_lint_clean_sample_digits_mlp(self, capsys):
        """Acceptance gate: `veles-tpu-lint samples/digits_mlp.py` exits 0
        with no error findings."""
        pytest.importorskip("sklearn")
        import os
        from veles_tpu.analysis.cli import main
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        rc = main([os.path.join(repo, "samples", "digits_mlp.py"),
                   os.path.join(repo, "samples", "digits_config.py")])
        assert rc == 0

    def test_initialized_trainer_staging_spec_is_clean(self):
        """StagedTrainer's own hook: after initialize() the real jitted
        eval step traces abstractly with no staging findings."""
        pytest.importorskip("sklearn")
        import os
        from veles_tpu.analysis.cli import build_workflow
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        wf = build_workflow(
            os.path.join(repo, "samples", "digits_mlp.py"),
            os.path.join(repo, "samples", "digits_config.py"))
        wf.initialize()
        spec = wf.trainer.lint_staging_spec()
        assert spec is not None and spec["carry_argnums"] == (1,)
        fs = lint_workflow(wf)
        assert not [f for f in fs if f.rule.startswith("VJ")]
        assert not has_errors(fs)


class TestHotLoopHygiene:
    def test_no_per_iteration_imports_in_run_loop(self):
        """Satellite: the fault-injection imports must live at module
        scope, not inside Workflow.run's per-unit loop."""
        import ast
        import inspect
        import textwrap

        from veles_tpu import workflow as wf_mod
        src = textwrap.dedent(inspect.getsource(wf_mod.Workflow.run))
        assert not [n for n in ast.walk(ast.parse(src))
                    if isinstance(n, (ast.Import, ast.ImportFrom))]
        assert wf_mod.os is not None and wf_mod.random is not None
