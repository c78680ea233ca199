"""Serving-plane static analysis (ISSUE 16): the VD7xx decode-path
auditor and the VT8xx concurrency lint.

PR 4 test pattern: per-rule seeded-hazard fixtures where each rule
fires exactly once, a clean sweep over the real engine configs
(bf16/int8/w4a8 x paged/dense x spec on/off) and the full services
tree, a purity pin (zero dispatch, zero device arrays), and the CLI
gates in-process."""

import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veles_tpu import prng
from veles_tpu.analysis import concurrency_lint, decode_audit
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import zoo
from veles_tpu.models.generate import (ContinuousBatcher, LMGenerator,
                                       PagedContinuousBatcher)
from veles_tpu.models.standard_workflow import StandardWorkflow
from veles_tpu.ops import quant


@pytest.fixture(scope="module")
def lm_wf():
    prng.seed_all(31)
    r = np.random.RandomState(5)
    toks = ((np.arange(16)[None, :] * 2
             + r.randint(0, 4, 192)[:, None]) % 13).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48,
                             class_lengths=[0, 48, 144])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=13, d_model=32,
                                  n_heads=4, n_layers=2, lr=5e-3,
                                  dropout=0.0),
        loader=loader, loss="lm",
        decision_config={"max_epochs": 1},
        name="serving-lint-lm")
    wf.initialize()
    return wf


@pytest.fixture(scope="module")
def lm_wf48():
    """Longer position table (t=48) so a pool block can sit above the
    bf16 sublane minimum yet off its tile (the VD705 seed needs
    block=24 to divide max_len)."""
    prng.seed_all(33)
    r = np.random.RandomState(7)
    toks = ((np.arange(48)[None, :] * 2
             + r.randint(0, 4, 96)[:, None]) % 13).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=24,
                             class_lengths=[0, 24, 72])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=13, d_model=32,
                                  n_heads=4, n_layers=2, lr=5e-3,
                                  dropout=0.0),
        loader=loader, loss="lm",
        decision_config={"max_epochs": 1},
        name="serving-lint-lm48")
    wf.initialize()
    return wf


def _rules(findings, rule):
    return [f for f in findings if f.rule == rule]


# --------------------------------------------------------------------------
# VD7xx — seeded hazards, each rule fires exactly once
# --------------------------------------------------------------------------

class TestSeededVD:
    def test_vd700_payload_dequant_outside_dot(self, lm_wf):
        """A payload-sized int8->float convert whose result feeds a
        reduction (not a dot) — the hoistable dense-dequant bug class
        PR 14 erased, now a rule."""
        gen = LMGenerator(lm_wf.trainer, max_len=16, weights="int8")
        cb = ContinuousBatcher(gen, slots=2)
        qws = [l for l in jax.tree_util.tree_leaves(
                   gen.params, is_leaf=quant.is_quant)
               if isinstance(l, quant.QuantWeight)]
        assert qws
        body = cb._tick_body()

        def bad_body(params, st, aids):
            st, report = body(params, st, aids)
            qw = [l for l in jax.tree_util.tree_leaves(
                      params, is_leaf=quant.is_quant)
                  if isinstance(l, quant.QuantWeight)][0]
            dense = qw.q.astype(jnp.float32)             # BAD: no dot
            return st._replace(tokens=st.tokens + dense.sum().astype(
                st.tokens.dtype)), report

        cb._tick_body = lambda: bad_body
        findings = decode_audit.audit_decode_tick(cb)
        assert len(_rules(findings, "VD700")) == 1, findings

    def test_vd701_donation_miss(self, lm_wf):
        """A dispatch wrapper that forgets donate_argnums re-allocates
        every state leaf (KV caches included) per tick."""
        gen = LMGenerator(lm_wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2)
        cb._jit_ticks = lambda fn: jax.jit(fn)   # donation dropped
        findings = decode_audit.audit_decode_tick(cb)
        vd701 = _rules(findings, "VD701")
        assert len(vd701) == 1, findings
        assert "0 of" in vd701[0].message

    @pytest.mark.parametrize("kind", ["dense", "speculative", "paged"])
    def test_vd701_counts_the_state_and_not_the_report(self, lm_wf, kind):
        """The tick's report is an output beside the donated state: the
        lowered tick carries one aliasing marker a STATE leaf, none for
        the report, and the rule stays silent."""
        gen = LMGenerator(lm_wf.trainer, max_len=16)
        cb = {"dense": lambda: ContinuousBatcher(gen, slots=2),
              "speculative": lambda: ContinuousBatcher(
                  gen, slots=2, speculative_k=4),
              "paged": lambda: PagedContinuousBatcher(
                  gen, slots=2, block=4, pool_tokens=32)}[kind]()
        assert not _rules(decode_audit.audit_decode_tick(cb), "VD701")
        state = cb._state()
        lowered = cb._jit_ticks(cb._tick_body()).lower(
            gen.params, state, cb._aids)
        assert lowered.as_text().count("tf.aliasing_output") == len(
            jax.tree_util.tree_leaves(state))
        report = jax.eval_shape(cb._tick_body(), gen.params, state,
                                cb._aids)[1]
        assert {"tokens", "n", "pos", "active"} <= set(report)

    def test_vd702_host_callback_in_tick(self, lm_wf):
        gen = LMGenerator(lm_wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2)
        body = cb._tick_body()

        def chatty(params, st, aids):
            st, report = body(params, st, aids)
            jax.debug.print("tick {}", st.pos.sum())  # BAD: host sync
            return st, report

        cb._tick_body = lambda: chatty
        findings = decode_audit.audit_decode_tick(cb)
        assert len(_rules(findings, "VD702")) == 1, findings

    def test_vd702_trace_failure_is_the_finding(self, lm_wf):
        """Data-dependent python control flow inside the tick cannot
        trace abstractly — the failure itself is the VD702."""
        gen = LMGenerator(lm_wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2)
        body = cb._tick_body()

        def host_branch(params, st, aids):
            if bool(st.active.sum() > 0):        # BAD: host decision
                return body(params, st, aids)
            return st, {}

        cb._tick_body = lambda: host_branch
        findings = decode_audit.audit_decode_tick(cb)
        vd702 = _rules(findings, "VD702")
        assert len(vd702) == 1, findings
        assert "failed to trace" in vd702[0].message

    def test_vd703_weak_scalar_in_signature(self, lm_wf):
        """A python scalar leaking into the tick signature retraces
        per distinct value (the PR 3 compile counters count it at
        runtime; the rule catches it before)."""
        gen = LMGenerator(lm_wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2)
        body = cb._tick_body()
        state0 = cb._state
        cb._state = lambda: (state0(), 0.25)     # BAD: host float

        def leaky(params, st, aids):
            out, report = body(params, st[0], aids)
            return (out, st[1] * 1.0), report

        cb._tick_body = lambda: leaky
        findings = decode_audit.audit_decode_tick(cb)
        assert len(_rules(findings, "VD703")) == 1, findings

    def test_vd704_collective_bound_tick(self, lm_wf, monkeypatch):
        """Under a model-axis mesh, per-tick collective bytes priced
        above the tick's KV reads flag an ICI-bound decode."""
        from veles_tpu.parallel import MeshConfig, make_mesh
        mc = MeshConfig(make_mesh({"data": 1, "model": 2}))
        gen = LMGenerator(lm_wf.trainer, max_len=16, mesh_cfg=mc)
        cb = ContinuousBatcher(gen, slots=2)
        from veles_tpu.analysis import sharding_audit
        monkeypatch.setattr(
            sharding_audit, "collective_stats",
            lambda text: {"all-gather": {"count": 4,
                                         "bytes": 1 << 30}})
        findings = decode_audit.audit_decode_tick(cb)
        vd704 = _rules(findings, "VD704")
        assert len(vd704) == 1, findings
        assert "ICI-bound" in vd704[0].message

    def test_vd704_silent_without_model_axis(self, lm_wf, monkeypatch):
        """No mesh — the rule must not even lower for collectives."""
        gen = LMGenerator(lm_wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2)
        from veles_tpu.analysis import sharding_audit
        monkeypatch.setattr(
            sharding_audit, "collective_stats",
            lambda text: {"all-gather": {"count": 4,
                                         "bytes": 1 << 30}})
        findings = decode_audit.audit_decode_tick(cb)
        assert not _rules(findings, "VD704"), findings

    def test_vd705_bad_pool_block_geometry(self, lm_wf48):
        """A pinned pool block above the sublane minimum but off the
        native tile (12 % 8 != 0 for the f32 pool this CPU build
        makes) fails the VP6xx audit at exactly the geometry the
        engine resolved."""
        gen = LMGenerator(lm_wf48.trainer, max_len=48)
        cb = PagedContinuousBatcher(gen, slots=2, block=12,
                                    pool_tokens=96)
        assert cb.fused and cb.block == 12
        findings = decode_audit.audit_decode_tick(cb)
        vd705 = _rules(findings, "VD705")
        assert len(vd705) == 1, findings
        assert "block=12" in vd705[0].message
        assert "VP600" in vd705[0].message

    def test_vd705_silent_below_sublane_fallback(self, lm_wf):
        """A block below the sublane minimum never launches the fused
        kernel on hardware (the engine's own mosaic_ok fallback) — no
        geometry to audit, no finding."""
        gen = LMGenerator(lm_wf.trainer, max_len=16,
                          weights="int8", cache_dtype="int8")
        cb = PagedContinuousBatcher(gen, slots=2, block=16,
                                    pool_tokens=64)
        findings = decode_audit.audit_decode_tick(cb)
        assert not _rules(findings, "VD705"), findings

    def test_all_vd_rules_fire_exactly_once_on_seeds(self, lm_wf,
                                                     lm_wf48,
                                                     monkeypatch):
        """The aggregated PR 4 pin: every VD7xx rule has a seeded
        hazard on which it fires exactly once."""
        counts = {}
        for rule, seed in [
                ("VD700", self.test_vd700_payload_dequant_outside_dot),
                ("VD701", self.test_vd701_donation_miss),
                ("VD702", self.test_vd702_host_callback_in_tick),
                ("VD703", self.test_vd703_weak_scalar_in_signature)]:
            seed(lm_wf)
            counts[rule] = 1
        self.test_vd704_collective_bound_tick(lm_wf, monkeypatch)
        counts["VD704"] = 1
        self.test_vd705_bad_pool_block_geometry(lm_wf48)
        counts["VD705"] = 1
        assert counts == {r: 1 for r in decode_audit.RULES}


# --------------------------------------------------------------------------
# VD7xx — clean sweep over the real engine configs
# --------------------------------------------------------------------------

VARIANTS = [
    ("bf16-dense", dict(), dict()),
    ("bf16-spec4", dict(), dict(speculative_k=4)),
    ("bf16-paged", dict(), dict(paged=True)),
    ("int8-dense", dict(weights="int8"), dict()),
    ("int8-paged-q8", dict(weights="int8", cache_dtype="int8"),
     dict(paged=True)),
    ("w4a8-dense", dict(weights="w4a8"), dict()),
]


class TestCleanSweep:
    @pytest.mark.parametrize("tag,gen_kw,cb_kw",
                             VARIANTS, ids=[v[0] for v in VARIANTS])
    def test_real_decode_tick_is_clean(self, lm_wf, tag, gen_kw,
                                       cb_kw):
        """Acceptance: the real decode path passes for every
        quantization/pool/speculative variant."""
        cb_kw = dict(cb_kw)
        gen = LMGenerator(lm_wf.trainer, max_len=16, **gen_kw)
        if cb_kw.pop("paged", False):
            cb = PagedContinuousBatcher(gen, slots=2, pool_tokens=64,
                                        **cb_kw)
        else:
            cb = ContinuousBatcher(gen, slots=2, **cb_kw)
        findings = decode_audit.audit_decode_tick(cb)
        assert not findings, findings

    @pytest.mark.parametrize("scheme", [None, "int8", "w4a8"])
    def test_real_prefill_pass_is_clean(self, lm_wf, scheme):
        gen = LMGenerator(lm_wf.trainer, max_len=16, weights=scheme)
        findings = decode_audit.audit_prefill_pass(gen, segment=8)
        assert not findings, findings

    def test_lint_serving_sweeps_all_variants_clean(self, lm_wf):
        findings = decode_audit.lint_serving(lm_wf.trainer, max_len=16)
        assert not findings, findings

    def test_services_tree_is_clean(self):
        """Acceptance: the whole threaded control plane passes the
        VT8xx lint (genuine findings were fixed or carry an inline
        ``# lint-ok`` rationale)."""
        findings = concurrency_lint.lint_concurrency()
        assert not findings, findings


# --------------------------------------------------------------------------
# purity: zero dispatch, zero device arrays
# --------------------------------------------------------------------------

class TestPurity:
    def test_decode_audit_allocates_nothing(self, lm_wf):
        """The audit traces and lowers abstractly: not one device
        array may outlive it (construction happens OUTSIDE the
        measured region — building a quantized generator does
        allocate, exactly like serving itself would)."""
        import gc
        gen = LMGenerator(lm_wf.trainer, max_len=16, weights="int8")
        cb = ContinuousBatcher(gen, slots=2)
        gc.collect()
        before = len(jax.live_arrays())
        findings = decode_audit.audit_decode_tick(cb)
        findings += decode_audit.audit_prefill_pass(gen, segment=8)
        gc.collect()
        assert len(jax.live_arrays()) <= before
        assert not findings, findings

    def test_concurrency_lint_never_imports_services(self):
        """The VT lint is AST-only: linting a file with a poisoned
        import proves nothing runs."""
        import sys
        poisoned = [m for m in ("veles_tpu.services.podmaster",)
                    if m in sys.modules]
        findings = concurrency_lint.lint_concurrency()
        assert isinstance(findings, list)
        for m in ("veles_tpu.services.podmaster",):
            if m not in poisoned:
                assert m not in sys.modules


# --------------------------------------------------------------------------
# VT8xx — seeded hazards, each rule fires exactly once
# --------------------------------------------------------------------------

VT_SEEDS = {
    "VT800": """
        import threading

        class Shared:
            def __init__(self):
                self._lock = threading.Lock()
                self.counter = 0

            def start(self):
                threading.Thread(target=self._pump,
                                 daemon=True).start()
                threading.Thread(target=self._drain,
                                 daemon=True).start()

            def _pump(self):
                self.counter += 1

            def _drain(self):
                self.counter = 0
        """,
    "VT801": """
        import threading

        class Inverted:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def one(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def two(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
        """,
    "VT802": """
        import signal
        import threading

        class SigLock:
            def __init__(self):
                self._lock = threading.Lock()
                signal.signal(signal.SIGUSR1, self._on_sig)

            def _on_sig(self, signum, frame):
                self._note()

            def _note(self):
                with self._lock:
                    pass
        """,
    "VT803": """
        import threading

        def spawn(fn):
            threading.Thread(target=fn).start()
        """,
    "VT804": """
        import queue

        def make_channel():
            return queue.Queue()
        """,
}


class TestSeededVT:
    @pytest.mark.parametrize("rule", sorted(VT_SEEDS))
    def test_rule_fires_exactly_once(self, rule, tmp_path):
        path = tmp_path / ("%s.py" % rule.lower())
        path.write_text(textwrap.dedent(VT_SEEDS[rule]))
        findings = concurrency_lint.lint_module(str(path))
        assert [f.rule for f in findings] == [rule], findings

    def test_all_vt_rules_covered(self):
        assert tuple(sorted(VT_SEEDS)) == concurrency_lint.RULES

    def test_vt802_closure_handler(self, tmp_path):
        """A handler defined as a local closure (the graphics.py
        SIGUSR2 idiom) is followed through the registering method."""
        path = tmp_path / "closure.py"
        path.write_text(textwrap.dedent("""
            import signal
            import threading

            class ClosureSig:
                def __init__(self):
                    self._lock = threading.Lock()

                def install(self):
                    def handler(signum, frame):
                        self.flush()
                    signal.signal(signal.SIGUSR2, handler)

                def flush(self):
                    with self._lock:
                        pass
            """))
        findings = concurrency_lint.lint_module(str(path))
        assert [f.rule for f in findings] == ["VT802"], findings

    def test_rlock_quiets_vt802(self, tmp_path):
        path = tmp_path / "rlock.py"
        path.write_text(textwrap.dedent(VT_SEEDS["VT802"]).replace(
            "threading.Lock()", "threading.RLock()"))
        findings = concurrency_lint.lint_module(str(path))
        assert not findings, findings

    def test_common_lock_quiets_vt800(self, tmp_path):
        path = tmp_path / "locked.py"
        path.write_text(textwrap.dedent("""
            import threading

            class Shared:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.counter = 0

                def start(self):
                    threading.Thread(target=self._pump,
                                     daemon=True).start()
                    threading.Thread(target=self._drain,
                                     daemon=True).start()

                def _pump(self):
                    with self._lock:
                        self.counter += 1

                def _drain(self):
                    with self._lock:
                        self.counter = 0
            """))
        findings = concurrency_lint.lint_module(str(path))
        assert not findings, findings

    def test_bounded_queue_and_daemon_thread_pass(self, tmp_path):
        path = tmp_path / "clean.py"
        path.write_text(textwrap.dedent("""
            import queue
            import threading

            def make():
                q = queue.Queue(maxsize=64)
                t = threading.Thread(target=q.get, daemon=True)
                t.start()
                return q
            """))
        assert not concurrency_lint.lint_module(str(path))

    def test_joined_thread_passes(self, tmp_path):
        path = tmp_path / "joined.py"
        path.write_text(textwrap.dedent("""
            import threading

            def run(fn):
                t = threading.Thread(target=fn)
                t.start()
                t.join()
            """))
        assert not concurrency_lint.lint_module(str(path))

    def test_inline_suppression_with_rationale(self, tmp_path):
        path = tmp_path / "ok.py"
        path.write_text(textwrap.dedent("""
            import queue

            def make_channel():
                # lint-ok: VT804 — drained every cycle, producers
                # bounded by pod size, events must not drop
                return queue.Queue()
            """))
        assert not concurrency_lint.lint_module(str(path))

    def test_bare_lint_ok_suppresses_nothing(self, tmp_path):
        path = tmp_path / "bare.py"
        path.write_text(textwrap.dedent("""
            import queue

            def make_channel():
                # lint-ok: because reasons
                return queue.Queue()
            """))
        findings = concurrency_lint.lint_module(str(path))
        assert [f.rule for f in findings] == ["VT804"], findings


# --------------------------------------------------------------------------
# CLI — the unified gate
# --------------------------------------------------------------------------

WF_TEMPLATE = """
import numpy as np
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models.standard_workflow import StandardWorkflow
from veles_tpu.models.zoo import transformer_lm


def run(load, main):
    r = np.random.RandomState(5)
    toks = ((np.arange(16)[None, :] * 2
             + r.randint(0, 4, 96)[:, None]) % 13).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48,
                             class_lengths=[0, 24, 72])
    load(StandardWorkflow,
         layers=transformer_lm(vocab_size=13, d_model=32, n_heads=4,
                               n_layers=2, lr=5e-3, dropout=0.0),
         loader=loader, loss="lm",
         decision_config={"max_epochs": 1}, name="cli-serve-lm")
    main()
"""


class TestCLI:
    def test_serve_and_concurrency_clean(self, tmp_path, capsys):
        from veles_tpu.analysis.cli import main
        wf = tmp_path / "wf.py"
        wf.write_text(WF_TEMPLATE)
        rc = main([str(wf), "--serve", "--concurrency",
                   "--fail-on", "error"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "VD7" not in out and "VT8" not in out

    def test_concurrency_alone_needs_no_workflow(self, capsys):
        from veles_tpu.analysis.cli import main
        rc = main(["--concurrency"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "no findings" in out

    def test_workflow_required_without_concurrency(self, capsys):
        from veles_tpu.analysis.cli import main
        with pytest.raises(SystemExit) as e:
            main([])
        assert e.value.code == 2

    def test_fail_on_unifies_vt_findings(self, tmp_path, capsys,
                                         monkeypatch):
        """--fail-on {error,warning} gates the new families through
        findings.threshold_reached — a VT warning flips the exit only
        under --fail-on warning."""
        import veles_tpu.analysis as analysis
        from veles_tpu.analysis.cli import main
        from veles_tpu.analysis.findings import WARNING, Finding
        monkeypatch.setattr(
            analysis, "lint_concurrency",
            lambda paths=None, root=None: [Finding(
                "VT804", WARNING, "x.py:1", "seeded")])
        assert main(["--concurrency"]) == 0
        assert main(["--concurrency", "--fail-on", "warning"]) == 1
        out = capsys.readouterr().out
        assert "VT804" in out
