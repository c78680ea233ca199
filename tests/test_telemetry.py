"""veles_tpu.telemetry: metrics registry (Prometheus rendering, JSONL
sink), span aggregation through the scheduler, step telemetry and the
predicted-vs-measured MFU check from the staged trainer, the Watcher
memory gauges, and the veles-tpu-metrics summarizer."""

import json
import math
import re

import numpy as np
import pytest

from veles_tpu import telemetry
from veles_tpu.telemetry import MetricsRegistry


@pytest.fixture
def reg():
    return MetricsRegistry()


class TestRegistry:
    def test_counter_gauge_histogram_basics(self, reg):
        c = reg.counter("t_total", "a counter", ("kind",))
        c.inc(kind="a")
        c.inc(2.5, kind="a")
        c.inc(kind="b")
        assert c.value(kind="a") == 3.5
        assert c.value(kind="b") == 1.0
        with pytest.raises(ValueError):
            c.inc(-1, kind="a")
        g = reg.gauge("t_gauge")
        g.set(4.0)
        g.inc()
        g.dec(2.0)
        assert g.value() == 3.0
        h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        st = h.state()
        assert st["count"] == 3 and st["sum"] == pytest.approx(5.55)
        assert st["counts"] == [1, 1]     # 5.0 lands only in +Inf

    def test_create_or_return_and_type_mismatch(self, reg):
        c1 = reg.counter("same_name", "x", ("l",))
        assert reg.counter("same_name", "x", ("l",)) is c1
        with pytest.raises(ValueError):
            reg.gauge("same_name")
        with pytest.raises(ValueError):
            reg.counter("same_name", "x", ("other",))
        with pytest.raises(ValueError):
            reg.counter("bad name!")
        with pytest.raises(ValueError):
            c1.inc(wrong_label="x")
        h1 = reg.histogram("same_hist", buckets=(1.0, 2.0))
        assert reg.histogram("same_hist") is h1      # "don't care"
        with pytest.raises(ValueError):
            reg.histogram("same_hist", buckets=(0.5,))
        with pytest.raises(ValueError):
            reg.histogram("le_hist", labelnames=("le",))

    def test_prometheus_escaping_and_label_ordering(self, reg):
        g = reg.gauge("esc_gauge", 'help with \\ and\nnewline',
                      ("zeta", "alpha"))
        g.set(1.5, zeta='va"l\\ue\n2', alpha="plain")
        text = reg.render_prometheus()
        assert '# HELP esc_gauge help with \\\\ and\\nnewline' in text
        # label names sorted alphabetically regardless of declaration
        assert ('esc_gauge{alpha="plain",zeta="va\\"l\\\\ue\\n2"} 1.5'
                in text)
        assert "# TYPE esc_gauge gauge" in text

    def test_prometheus_deterministic_sample_order(self, reg):
        c = reg.counter("order_total", "", ("x",))
        for x in ("b", "a", "c"):
            c.inc(x=x)
        lines = [l for l in reg.render_prometheus().splitlines()
                 if l.startswith("order_total{")]
        assert lines == ['order_total{x="a"} 1', 'order_total{x="b"} 1',
                         'order_total{x="c"} 1']

    def test_prometheus_histogram_cumulative(self, reg):
        h = reg.histogram("lat_seconds", "", ("op",),
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.7, 20.0):
            h.observe(v, op="get")
        text = reg.render_prometheus()
        assert 'lat_seconds_bucket{op="get",le="0.1"} 1' in text
        assert 'lat_seconds_bucket{op="get",le="1"} 3' in text
        assert 'lat_seconds_bucket{op="get",le="10"} 3' in text
        assert 'lat_seconds_bucket{op="get",le="+Inf"} 4' in text
        assert 'lat_seconds_count{op="get"} 4' in text
        assert 'lat_seconds_sum{op="get"} 21.25' in text
        # every exposition line is name{labels} value or a comment
        for line in text.splitlines():
            assert re.match(
                r"(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
                r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+)$", line)

    def test_sink_failure_disables_sink_not_the_run(self, reg,
                                                    tmp_path):
        path = str(tmp_path / "dead.jsonl")
        reg.open_sink(path)
        reg._sink.close()            # simulate ENOSPC/closed-fd
        reg.emit("probe", n=1)       # must not raise
        assert reg.sink_path is None
        reg.emit("probe", n=2)       # sink gone, ring still records
        assert [r["n"] for r in reg.records("probe")] == [1, 2]

    def test_jsonl_sink_emit_and_dump(self, reg, tmp_path):
        path = str(tmp_path / "m.jsonl")
        reg.open_sink(path)
        reg.counter("dump_total").inc(3)
        reg.histogram("dump_seconds", buckets=(1.0,)).observe(0.5)
        reg.emit("custom", answer=42)
        reg.dump_state()
        reg.close_sink()
        recs = [json.loads(l) for l in open(path)]
        kinds = {r["kind"] for r in recs}
        assert {"custom", "counter", "histogram"} <= kinds
        custom = [r for r in recs if r["kind"] == "custom"][0]
        assert custom["answer"] == 42 and "ts" in custom
        hist = [r for r in recs if r["kind"] == "histogram"][0]
        assert hist["count"] == 1 and hist["buckets"] == [[1.0, 1]]


class TestSpans:
    def test_span_context_feeds_aggregate_and_emits(self, reg):
        agg = telemetry.SpanAggregate("unit.run")
        with telemetry.span("unit.run:x", aggregate=agg, emit=True,
                            registry=reg, unit="x"):
            pass
        assert agg.count == 1 and agg.total > 0
        assert agg.min == agg.max == agg.last == agg.total
        recs = reg.records("span")
        assert recs and recs[0]["name"] == "unit.run:x"
        assert recs[0]["dur_s"] >= 0 and recs[0]["unit"] == "x"

    def test_unit_run_compat_properties(self):
        from veles_tpu.units import TrivialUnit
        u = TrivialUnit(None)
        u._run_wrapped()
        u._run_wrapped()
        assert u.run_count == u.span.count == 2
        assert u.run_time == u.span.total > 0
        u.run_count = 7          # legacy writers still work
        u.run_time = 1.25
        assert u.span.count == 7 and u.span.total == 1.25

    def test_workflow_spans_exclude_gated_and_skipped(self, tmp_path):
        from veles_tpu.mutable import Bool
        from veles_tpu.units import TrivialUnit
        from veles_tpu.workflow import Workflow
        wf = Workflow(name="spanwf")
        a = TrivialUnit(wf, name="runner")
        blocked = TrivialUnit(wf, name="blocked")
        skipped = TrivialUnit(wf, name="skipped")
        a.link_from(wf.start_point)
        blocked.link_from(a)
        blocked.gate_block = Bool(True)
        skipped.link_from(a)
        skipped.gate_skip = Bool(True)
        wf.end_point.link_from(a)
        wf.initialize()
        path = str(tmp_path / "spans.jsonl")
        telemetry.registry.open_sink(path)
        try:
            wf.run()
        finally:
            telemetry.registry.close_sink()
        recs = [json.loads(l) for l in open(path)]
        spans = [r for r in recs if r["kind"] == "span"]
        assert any(r["name"] == "workflow.run"
                   and r["workflow"] == "spanwf" for r in spans)
        units = {r["unit"] for r in spans if r["name"] == "unit.run"}
        assert "runner" in units and "EndPoint" in units
        # gated/skipped units never ran: no span record, and the
        # /metrics gauges carry no sample for them either
        assert "blocked" not in units and "skipped" not in units
        g = telemetry.registry.gauge(
            "veles_unit_runs", "unit.run() invocations, per unit "
            "(set at each workflow run end)", ("workflow", "unit"))
        labeled = {l["unit"] for l, _ in g.samples()
                   if l["workflow"] == "spanwf"}
        assert "runner" in labeled and "blocked" not in labeled


def _mnist_shaped_workflow(max_epochs=2):
    """784-100-10 MLP on synthetic data — the MNIST sample's exact
    workflow shape without the dataset mount."""
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import mnist_mlp
    prng.seed_all(11)
    rng = np.random.RandomState(11)
    data = rng.rand(400, 784).astype(np.float32)
    labels = rng.randint(0, 10, 400).astype(np.int32)
    loader = FullBatchLoader(None, data=data, labels=labels,
                             minibatch_size=100,
                             class_lengths=[0, 100, 300])
    return StandardWorkflow(
        layers=mnist_mlp(), loader=loader,
        decision_config={"max_epochs": max_epochs}, name="mnist-shaped")


@pytest.fixture(scope="module")
def mnist_metrics(tmp_path_factory):
    """One trained MNIST-shaped run with the sink open; yields the
    parsed records (the acceptance-criteria artifact, in-process)."""
    from veles_tpu import compile_cache
    compile_cache.install_metrics()
    path = str(tmp_path_factory.mktemp("telemetry") / "mnist.jsonl")
    wf = _mnist_shaped_workflow()
    wf.initialize()
    telemetry.registry.open_sink(path)
    try:
        wf.run()
        telemetry.registry.dump_state()
    finally:
        telemetry.registry.close_sink()
    return [json.loads(l) for l in open(path)]


class TestStagedStepTelemetry:
    def test_jsonl_contains_required_records(self, mnist_metrics):
        """The acceptance-criteria contract: workflow/unit/step spans,
        compile counters, device-memory gauges — and NO MFU record:
        this run is on the CPU, which has no row in the peaks table."""
        kinds = {r["kind"] for r in mnist_metrics}
        assert {"span", "step", "counter", "gauge"} <= kinds
        assert "mfu" not in kinds
        spans = [r for r in mnist_metrics if r["kind"] == "span"]
        assert any(r["name"] == "workflow.run" for r in spans)
        assert any(r["name"] == "unit.run"
                   and r.get("cls") == "StagedTrainer" for r in spans)
        names = {r.get("name") for r in mnist_metrics}
        assert "veles_compile_events_total" in names
        assert "veles_compile_seconds_total" in names
        assert "veles_device_live_bytes" in names
        assert names.isdisjoint({"veles_mfu_measured",
                                 "veles_mfu_predicted", "veles_mfu_ratio"})

    def test_step_records_per_class(self, mnist_metrics):
        steps = [r for r in mnist_metrics if r["kind"] == "step"]
        by_class = {}
        for r in steps:
            by_class.setdefault(r["class"], []).append(r)
        assert set(by_class) == {"train", "validation"}
        train = by_class["train"][-1]
        assert train["steps"] == 3 and train["examples"] == 300
        assert train["wall_s"] > 0
        assert train["examples_per_sec"] == pytest.approx(
            train["examples"] / train["wall_s"])
        assert math.isfinite(train["loss"])

    def test_mfu_predicted_vs_measured_consistent(self, tmp_path,
                                                  monkeypatch):
        """MFU math pinned on the MNIST-shaped step as a v5e would see
        it (the live device forced to report that kind): analytic FLOPs
        for 784-100-10 at batch 100, measured == flops / (step_time *
        peak), ratio == measured/predicted, rows banked under the live
        backend."""
        from veles_tpu.telemetry import ledger
        from veles_tpu.telemetry.registry import MetricsRegistry
        monkeypatch.setenv("VELES_TPU_PERF_LEDGER",
                           str(tmp_path / "led.jsonl"))
        monkeypatch.setattr(telemetry.mfu, "_live_device_kind",
                            lambda: "TPU v5 lite")
        wf = _mnist_shaped_workflow(max_epochs=1)
        wf.initialize()
        reg = MetricsRegistry()
        m = telemetry.mfu.check_step(wf.trainer, steps=3, wall_s=0.015,
                                     registry=reg)
        flops = 3 * (2 * 100 * 784 * 100 + 2 * 100 * 100 * 10)
        assert m["device"] == "TPU v5 lite" and m["peak_flops"] == 197e12
        assert m["flops_per_step"] == pytest.approx(flops)
        assert m["measured_step_ms"] == pytest.approx(5.0)
        assert m["measured"] == pytest.approx(flops / (5e-3 * 197e12))
        assert m["ratio"] == pytest.approx(
            m["measured"] / m["predicted"], rel=1e-6)
        assert 0 < m["predicted"] < 1
        assert m["warned"] == (m["ratio"] < m["warn_fraction"])
        banked = {r["metric"] for r in ledger.default().records()}
        assert banked == {"train_mfu", "train_step_ms"}

    def test_unlisted_device_gets_no_mfu_and_banks_no_row(
            self, tmp_path, monkeypatch):
        """The CPU these tests run on is not in the peaks table: the
        MFU check must emit no value anywhere — no record, no gauge, no
        ``train_mfu`` / ``train_step_ms`` ledger row.  (It used to price
        every device against the v5e's 197 TFLOP/s.)"""
        from veles_tpu.ops import flops
        from veles_tpu.telemetry import ledger
        from veles_tpu.telemetry.registry import MetricsRegistry
        import jax
        assert flops.peak_bf16_tflops(
            jax.devices()[0].device_kind) is None
        assert flops.peak_bf16_tflops("TPU v5 lite") == 197.0
        monkeypatch.setenv("VELES_TPU_PERF_LEDGER",
                           str(tmp_path / "led.jsonl"))
        wf = _mnist_shaped_workflow(max_epochs=1)
        wf.initialize()
        reg = MetricsRegistry()
        assert telemetry.mfu.price_staged_step(wf.trainer) is None
        assert telemetry.mfu.check_step(wf.trainer, steps=3,
                                        wall_s=0.015,
                                        registry=reg) is None
        assert reg.records("mfu") == [] and reg.metrics() == []
        assert ledger.default().records() == []

    def test_stop_clears_open_sweep_accumulators(self):
        """A run stopped mid-sweep must not leak its t0 into the next
        run's first sweep (idle-gap wall time → garbage MFU)."""
        wf = _mnist_shaped_workflow(max_epochs=1)
        wf.initialize()
        wf.trainer._note_step(2)
        assert wf.trainer._sweep_
        wf.trainer.stop()
        assert not wf.trainer._sweep_

    def test_price_staged_step_shape(self):
        wf = _mnist_shaped_workflow(max_epochs=1)
        wf.initialize()
        pricing = telemetry.mfu.price_staged_step(
            wf.trainer, device_kind="TPU v5 lite")
        assert pricing["param_elems"] == 784 * 100 + 100 * 10 + 110
        assert pricing["predicted_step_s"] > 0
        assert pricing["flops_per_step"] == pytest.approx(
            3 * (2 * 100 * 784 * 100 + 2 * 100 * 100 * 10))
        assert pricing["predicted_mfu"] == pytest.approx(
            pricing["flops_per_step"]
            / (pricing["predicted_step_s"] * pricing["peak_flops"]))


class TestWatcher:
    def test_record_sets_gauges_and_survives_cpu_stats(self, reg):
        import jax.numpy as jnp
        from veles_tpu.benchmark import Watcher
        keep = jnp.ones((128, 128))     # something live to census
        w = Watcher()
        per_device = w.record(reg)
        assert per_device and w.peak > 0
        g = reg.gauge("veles_device_live_bytes",
                      "live jax-array bytes per device "
                      "(per-shard census)", ("device",))
        assert any(v > 0 for _, v in g.samples())
        assert reg.gauge("veles_device_peak_bytes",
                         "census high-water mark across snapshots, "
                         "all devices").value() == w.peak
        # CPU memory_stats() is None/partial: the hbm gauges simply
        # carry no samples — no exception, no prints
        text = reg.render_prometheus()
        assert "veles_device_live_bytes" in text
        del keep


class TestTimeit:
    def test_mixed_pytree_blocks_on_array_leaves_only(self):
        import jax.numpy as jnp
        from veles_tpu.timeit2 import timeit

        def fn():
            return {"arrays": [jnp.ones(8), jnp.zeros(3)],
                    "meta": "not-an-array", "n": 3, "none": None}

        result, seconds = timeit(fn)
        assert seconds > 0
        assert result["meta"] == "not-an-array"

    def test_plain_python_result(self):
        from veles_tpu.timeit2 import timeit
        result, seconds = timeit(lambda: sum(range(10)))
        assert result == 45 and seconds >= 0


class TestMetricsCLI:
    def test_summarizer_text_and_json(self, mnist_metrics, tmp_path,
                                      capsys):
        from veles_tpu.telemetry import cli
        path = str(tmp_path / "sum.jsonl")
        with open(path, "w") as f:
            for r in mnist_metrics:
                f.write(json.dumps(r) + "\n")
            # a CPU run has no MFU record; the summarizer must still
            # render one from a run on a listed device
            f.write(json.dumps({
                "kind": "mfu", "predicted": 0.05, "measured": 0.04,
                "ratio": 0.8, "warned": False, "warn_fraction": 0.5,
                "device": "TPU v5 lite", "peak_flops": 197e12,
                "flops_per_step": 1e9, "predicted_step_ms": 1.0,
                "measured_step_ms": 1.25, "steps": 3}) + "\n")
        assert cli.main([path]) == 0
        text = capsys.readouterr().out
        assert "MFU vs" in text and "step telemetry" in text
        assert "unit spans" in text
        assert cli.main([path, "--format", "json"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["mfu"]["predicted"] > 0
        assert summary["steps"]["train"]["steps"] > 0
        assert any(u["unit"] == "StagedTrainer"
                   for u in summary["units"])
        assert summary["compile"]["events"] > 0

    def test_summarizer_missing_file(self, capsys):
        from veles_tpu.telemetry import cli
        assert cli.main(["/nonexistent/m.jsonl"]) == 2


class TestWebStatusTelemetry:
    def test_metrics_endpoint_and_panel_api(self):
        import urllib.request
        from veles_tpu.services.web_status import WebStatusServer
        telemetry.registry.counter(
            "web_probe_total", "endpoint probe").inc()
        server = WebStatusServer(port=0)
        server.start()
        try:
            base = "http://127.0.0.1:%d" % server.port
            with urllib.request.urlopen(base + "/metrics") as r:
                assert r.status == 200
                assert "text/plain" in r.headers["Content-Type"]
                body = r.read().decode()
            assert "# TYPE web_probe_total counter" in body
            assert re.search(r"^web_probe_total 1$", body, re.M)
            with urllib.request.urlopen(base + "/api/telemetry") as r:
                data = json.loads(r.read())
            assert any(s["name"] == "web_probe_total"
                       for s in data["metrics"])
            with urllib.request.urlopen(base + "/") as r:
                page = r.read().decode()
            assert "/metrics" in page and "telemetry" in page
        finally:
            server.stop()
