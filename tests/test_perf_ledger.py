"""Performance ledger + regression sentinel (telemetry.ledger,
telemetry.perfcli, analysis.perf_lint — docs/perf.md "Performance
ledger & regression sentinel").

Pins the PR's acceptance behaviour: a planted 20%-worse run trips the
sentinel (``perf.regression`` flight event, ``veles-tpu-perf gate``
exit 1 naming the drifted component) while the same run inside the MAD
noise band stays quiet (exit 0); appends are atomic under concurrent
writers and fail-soft on an unwritable path; v0 blob rows migrate;
every bench row lands with its pre-registered target attached; the
VL12xx target-contract lint fires exactly once per orphan."""

import json
import threading

import pytest

from veles_tpu.analysis.findings import ERROR, WARNING
from veles_tpu.analysis.perf_lint import lint_perf
from veles_tpu.telemetry import flight
from veles_tpu.telemetry import ledger as led
from veles_tpu.telemetry import perfcli


@pytest.fixture(autouse=True)
def live_backend():
    """The ledger names the LIVE backend or writes nothing: bring the
    (conftest-pinned, virtual 8-device CPU) backend up so appends
    without an explicit ``backend=`` resolve to ``cpu:8``."""
    import jax
    jax.devices()


def _book(tmp_path, name="led.jsonl"):
    return led.PerfLedger(str(tmp_path / name))


def _seed(book, metric="step_ms", values=(100.0, 100.5, 99.5, 100.2),
          components=True, **kw):
    for v in values:
        comps = None
        if components:
            comps = {"compute_ms": v * 0.6, "host_ms": v * 0.1,
                     "dispatch_ms": v * 0.2, "collective_ms": 0.0,
                     "compile_ms": 0.0}
        book.append(metric, v, workload="train", unit="ms",
                    source="test", components=comps, **kw)


# ====================================================== schema / migration
class TestSchema:
    def test_v0_blob_row_migrates(self, tmp_path):
        path = tmp_path / "led.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"metric": "lm_mfu", "value": 0.3,
                                "when": 123.0}) + "\n")
        recs = led.PerfLedger(str(path)).records()
        assert len(recs) == 1
        rec = recs[0]
        assert rec["schema"] == led.SCHEMA
        assert rec["ts"] == 123.0 and "when" not in rec
        # unkeyed axes default so v0 history groups with v1 appends
        for axis in ("workload", "backend", "mesh", "dtype"):
            assert rec[axis] == "-"

    def test_v0_groups_with_fresh_append_on_same_key(self, tmp_path):
        path = tmp_path / "led.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"metric": "m", "value": 1.0,
                                "when": 1.0}) + "\n")
        book = led.PerfLedger(str(path))
        rec = book.append("m", 2.0, workload="-", backend="-",
                          mesh="-", dtype="-")
        assert rec is not None
        key = led.key_of(rec)
        assert [r["value"] for r in book.records(key=key)] == [1.0, 2.0]

    def test_round_trip_preserves_current_schema(self, tmp_path):
        book = _book(tmp_path)
        rec = book.append("m", 3.0, workload="w", unit="ms",
                          dtype="bf16", source="t", extra_field=7)
        got = book.records(metric="m")[0]
        assert got["schema"] == led.SCHEMA
        assert got["value"] == 3.0 and got["extra_field"] == 7
        assert led.key_of(got) == led.key_of(rec)

    def test_future_schema_and_garbage_lines_survive(self, tmp_path):
        path = tmp_path / "led.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"schema": led.SCHEMA + 1,
                                "metric": "m", "value": 9.0}) + "\n")
            f.write("{torn half-line\n")
            f.write("\n")
        recs = led.PerfLedger(str(path)).records()
        assert [r["value"] for r in recs] == [9.0]


# ========================================================= atomic appends
class TestAppend:
    def test_concurrent_writers_interleave_whole_lines(self, tmp_path):
        path = str(tmp_path / "led.jsonl")
        n_per = 100

        def writer(tag):
            book = led.PerfLedger(path)   # one fd-open per append
            for i in range(n_per):
                assert book.append("m", float(i), workload=tag,
                                   assess=False) is not None

        threads = [threading.Thread(target=writer, args=("w%d" % t,))
                   for t in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        with open(path) as f:
            lines = [ln for ln in f if ln.strip()]
        assert len(lines) == 2 * n_per
        for ln in lines:          # every line parses: no torn writes
            assert isinstance(json.loads(ln), dict)
        recs = led.PerfLedger(path).records()
        assert len(recs) == 2 * n_per

    def test_fail_soft_on_unwritable_path(self, tmp_path):
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("plain file")
        book = led.PerfLedger(str(blocker / "led.jsonl"))
        _seed(book, values=(1.0, 1.0, 1.0, 1.0))
        rec = book.append("step_ms", 2.0, workload="train", unit="ms")
        assert rec is not None          # the run is never failed
        assert book._disk_dead
        # history degraded to in-memory, still assessable
        assert len(book.records(metric="step_ms")) == 5
        assert rec["verdict"]["status"] in ("regression", "ok",
                                            "improved")

    def test_backend_is_the_live_one_or_the_row_is_not_written(
            self, tmp_path, monkeypatch):
        """No guess from the environment: a process with no live jax
        backend writes NO row unless the caller names the backend the
        number came from (bench.py's parent passes its probe child's);
        a live process is keyed by what jax reports."""
        book = _book(tmp_path)
        assert book.append("m", 1.0)["backend"] == "cpu:8"
        monkeypatch.setattr(led, "_live_backend", lambda: None)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # must not be read
        assert book.append("m", 2.0) is None
        assert [r["value"] for r in book.records()] == [1.0]
        rec = book.append("m", 3.0, backend="tpu:1")
        assert (rec["backend"], rec["mesh"]) == ("tpu:1", "-")
        assert book.append_bench_line({"value": 9.0},
                                      backend="tpu:1") == 1
        assert book.append_bench_line({"value": 9.0}) == 0

    def test_record_value_respects_enabled_knob(self, tmp_path,
                                                monkeypatch):
        from veles_tpu.config import root
        monkeypatch.setenv("VELES_TPU_PERF_LEDGER",
                           str(tmp_path / "led.jsonl"))
        old = root.common.perf.enabled
        try:
            root.common.perf.enabled = False
            assert led.record_value("m", 1.0) is None
            root.common.perf.enabled = True
            rec = led.record_value("m", 1.0)
            assert rec is not None and rec["value"] == 1.0
        finally:
            root.common.perf.enabled = old


# ============================================================== sentinel
class TestSentinel:
    def test_planted_regression_trips_and_names_component(
            self, tmp_path):
        book = _book(tmp_path)
        _seed(book)
        before = len([e for e in flight.recorder.snapshot()
                      if e.get("kind") == "perf.regression"])
        # 20% worse than the ~100 ms history, compute share inflated
        rec = book.append(
            "step_ms", 120.0, workload="train", unit="ms",
            source="test",
            components={"compute_ms": 80.0, "host_ms": 10.0,
                        "dispatch_ms": 20.0, "collective_ms": 0.0,
                        "compile_ms": 0.0})
        v = rec["verdict"]
        assert v["status"] == "regression"
        assert v["component"] == "compute_ms"
        assert v["drift"] == pytest.approx(0.2, rel=0.05)
        events = [e for e in flight.recorder.snapshot()
                  if e.get("kind") == "perf.regression"]
        assert len(events) == before + 1
        assert events[-1]["component"] == "compute_ms"

    def test_in_band_noise_stays_quiet(self, tmp_path):
        book = _book(tmp_path)
        _seed(book)
        # within the 5% min_rel_band floor of the ~100 ms median
        rec = book.append("step_ms", 102.0, workload="train",
                          unit="ms", source="test")
        assert rec["verdict"]["status"] == "ok"

    def test_improvement_is_not_a_regression(self, tmp_path):
        book = _book(tmp_path)
        _seed(book)
        rec = book.append("step_ms", 80.0, workload="train", unit="ms")
        assert rec["verdict"]["status"] == "improved"

    def test_higher_is_better_polarity(self, tmp_path):
        book = _book(tmp_path)
        for v in (100.0, 101.0, 99.0, 100.0):
            book.append("tok_per_s", v, workload="lm", unit="tok/s",
                        better="higher")
        worse = book.append("tok_per_s", 80.0, workload="lm",
                            unit="tok/s", better="higher")
        assert worse["verdict"]["status"] == "regression"

    def test_no_history_below_min_history(self, tmp_path):
        book = _book(tmp_path)
        book.append("m", 1.0, workload="w", unit="ms")
        rec = book.append("m", 99.0, workload="w", unit="ms")
        assert rec["verdict"]["status"] == "no_history"

    def test_drift_gauge_and_regression_counter(self, tmp_path):
        from veles_tpu.telemetry.registry import MetricsRegistry
        reg = MetricsRegistry()
        book = led.PerfLedger(str(tmp_path / "led.jsonl"),
                              registry=reg)
        _seed(book, components=False)
        book.append("step_ms", 150.0, workload="train", unit="ms")
        names = {s["name"]: s for s in reg.snapshot()}
        assert "veles_perf_drift" in names
        assert names["veles_perf_drift"]["labels"] == {
            "metric": "step_ms"}
        assert names["veles_perf_regressions_total"]["value"] == 1

    def test_target_met_event_and_verdict(self, tmp_path):
        book = _book(tmp_path)
        rec = book.append("lm_large_mfu", 0.47, workload="lm_large",
                          unit="MFU", better="higher")
        # declared target (0.44, higher) auto-attached from TARGETS
        assert rec["target"]["id"] == "lm_large_mfu"
        assert rec["verdict"]["target_met"] is True
        miss = book.append("lm_large_mfu", 0.30, workload="lm_large",
                           unit="MFU", better="higher")
        assert miss["verdict"]["target_met"] is False
        met = [e for e in flight.recorder.snapshot()
               if e.get("kind") == "perf.target_met"]
        assert met and met[-1]["met"] is False


# ============================================================ bench rows
class TestBenchIntegration:
    LINE = {"value": 10611.7, "gemm_bf16_mfu": 0.438,
            "lm_large_mfu": 0.369, "serve_int8_vs_bf16_x": 1.133,
            "flash_bwd_vs_xla_x": 1.743, "serve_seg_stall_x": 2.1,
            "serve_cost_vs_rr_x": 1.05, "mlp_step_ms": 4.463,
            "flash_ok": True, "ring_ok": True, "flash_platform": "cpu",
            "beam_ms_per_pos_t4096": 0.0}    # zero = did not run

    def test_every_row_lands_with_its_registered_target(self,
                                                        tmp_path):
        book = _book(tmp_path)
        n = book.append_bench_line(self.LINE)
        recs = book.records()
        assert n == len(recs) == 8       # bools/zeros/strings stay out
        by_metric = {r["metric"]: r for r in recs}
        assert "beam_ms_per_pos_t4096" not in by_metric
        assert "flash_ok" not in by_metric
        for t in led.TARGETS:
            if t.metric in by_metric:
                tgt = by_metric[t.metric]["target"]
                assert tgt == {"id": t.metric, "goal": t.goal,
                               "better": t.better}
        # untargeted rows carry no target
        assert by_metric["mlp_step_ms"]["target"] is None
        # workload axis is the measuring phase
        assert by_metric["lm_large_mfu"]["workload"] == "lm_large"
        assert by_metric["lm_large_mfu"]["source"] == "bench.lm_large"

    def test_bench_target_keys_read_from_registry(self):
        import bench
        assert bench._target("serve_int8_vs_bf16_x", 0.0) == 1.5
        assert bench._target("serve_seg_stall_x", 0.0) == 4.0
        assert bench._target("serve_cost_vs_rr_x", 0.0) == 1.0
        assert bench._target("no_such_target", 7.0) == 7.0


# ============================================================ VL12xx lint
class TestPerfLint:
    def test_orphan_target_fires_exactly_once(self, tmp_path):
        recs = [{"schema": 1, "metric": "m", "value": 1.0,
                 "target": {"id": "ghost", "goal": 1.0}},
                {"schema": 1, "metric": "m", "value": 2.0,
                 "target": {"id": "ghost", "goal": 1.0}}]
        findings = lint_perf(targets=(), records=recs)
        orphans = [f for f in findings if f.rule == "VL1201"]
        assert len(orphans) == 1
        assert orphans[0].severity == ERROR
        assert "ghost" in orphans[0].message

    def test_target_never_measured_warns(self):
        findings = lint_perf(records=[])
        never = {f.unit for f in findings if f.rule == "VL1200"}
        assert never == {t.metric for t in led.TARGETS}
        assert all(f.severity == WARNING for f in findings
                   if f.rule == "VL1200")

    def test_measured_target_clears_vl1200(self, tmp_path):
        book = _book(tmp_path)
        book.append("lm_large_mfu", 0.4, workload="lm_large",
                    unit="MFU", better="higher")
        findings = lint_perf(records=book.records())
        assert "lm_large_mfu" not in {
            f.unit for f in findings if f.rule == "VL1200"}

    def test_polarity_conflict_warns_once(self):
        recs = [{"schema": 1, "metric": "lm_large_mfu", "value": 0.4,
                 "better": "lower",
                 "target": {"id": "lm_large_mfu", "goal": 0.44}}] * 3
        findings = lint_perf(records=recs)
        pol = [f for f in findings if f.rule == "VL1203"]
        assert len(pol) == 1

    def test_duplicate_conflicting_declaration(self):
        dup = (led.Target("m", 1.0, "lower", "ms", "a"),
               led.Target("m", 2.0, "lower", "ms", "b"))
        findings = lint_perf(targets=dup, records=[])
        assert any(f.rule == "VL1202" and f.severity == ERROR
                   for f in findings)


# ================================================================= CLI
class TestPerfCli:
    def _regressed_ledger(self, tmp_path):
        book = _book(tmp_path)
        _seed(book)
        book.append("step_ms", 120.0, workload="train", unit="ms",
                    components={"compute_ms": 80.0, "host_ms": 10.0,
                                "dispatch_ms": 20.0,
                                "collective_ms": 0.0,
                                "compile_ms": 0.0})
        return book

    def test_gate_exit_1_names_drifted_component(self, tmp_path,
                                                 capsys):
        book = self._regressed_ledger(tmp_path)
        rc = perfcli.main(["gate", "--ledger", book.path])
        out = capsys.readouterr().out
        assert rc == 1
        assert "VL1210" in out and "compute_ms" in out

    def test_gate_exit_0_inside_noise_band(self, tmp_path, capsys):
        book = _book(tmp_path)
        _seed(book)
        book.append("step_ms", 102.0, workload="train", unit="ms")
        rc = perfcli.main(["gate", "--ledger", book.path])
        assert rc == 0
        # VL1200 never-measured warnings ride along but stay below
        # the default --fail-on error threshold
        assert "VL1200" in capsys.readouterr().out

    def test_gate_fail_on_warning_trips_on_missed_target(
            self, tmp_path, capsys):
        book = _book(tmp_path)
        book.append("lm_large_mfu", 0.30, workload="lm_large",
                    unit="MFU", better="higher")
        assert perfcli.main(["gate", "--ledger", book.path]) == 0
        rc = perfcli.main(["gate", "--ledger", book.path,
                           "--fail-on", "warning"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "VL1211" in out

    def test_report_and_targets_exit_0(self, tmp_path, capsys):
        book = self._regressed_ledger(tmp_path)
        assert perfcli.main(["report", "--ledger", book.path]) == 0
        out = capsys.readouterr().out
        assert "regression" in out
        assert perfcli.main(["targets", "--ledger", book.path]) == 0
        out = capsys.readouterr().out
        assert "lm_large_mfu" in out and "NEVER MEASURED" in out

    def test_report_json_is_parseable(self, tmp_path, capsys):
        book = self._regressed_ledger(tmp_path)
        assert perfcli.main(["report", "--ledger", book.path,
                             "--format", "json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload[0]["verdict"]["status"]

    def test_diff_against_baseline_ledger(self, tmp_path, capsys):
        base = _book(tmp_path, "base.jsonl")
        base.append("m", 100.0, workload="w", unit="ms")
        cur = _book(tmp_path, "cur.jsonl")
        cur.append("m", 110.0, workload="w", unit="ms")
        assert perfcli.main(["diff", "--ledger", cur.path,
                             "--baseline", base.path]) == 0
        assert "+10.0%" in capsys.readouterr().out

    def test_usage_error_exits_2(self):
        with pytest.raises(SystemExit) as exc:
            perfcli.main(["no-such-subcommand"])
        assert exc.value.code == 2
        with pytest.raises(SystemExit) as exc:
            perfcli.main([])
        assert exc.value.code == 2

    def test_lint_cli_perf_flag(self, tmp_path, capsys):
        from veles_tpu.analysis import cli as lint_cli
        path = str(tmp_path / "led.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps(
                {"schema": 1, "metric": "m", "value": 1.0,
                 "target": {"id": "ghost", "goal": 1.0}}) + "\n")
        rc = lint_cli.main(["--perf", "--ledger", path])
        out = capsys.readouterr().out
        assert rc == 1                   # VL1201 orphan is an error
        assert "VL1201" in out


# ==================================================== runtime bank hooks
class TestRuntimeHooks:
    def test_web_status_perf_report(self, tmp_path, monkeypatch):
        monkeypatch.setenv("VELES_TPU_PERF_LEDGER",
                           str(tmp_path / "led.jsonl"))
        _seed(led.PerfLedger(str(tmp_path / "led.jsonl")))
        from veles_tpu.services.web_status import WebStatusServer
        report = WebStatusServer(port=0).perf_report()
        assert report["keys"], report.get("error")
        row = report["keys"][0]
        assert row["metric"] == "step_ms"
        assert len(row["trend"]) == 4
        assert row["verdict"]["status"] in ("ok", "no_history",
                                            "improved", "regression")

    def test_anatomy_components_partition_the_step(self):
        from veles_tpu.telemetry import anatomy
        from veles_tpu.telemetry.registry import MetricsRegistry
        reg = MetricsRegistry()
        comps = anatomy.step_components(object(), steps=10,
                                        wall_s=0.5, registry=reg)
        assert comps is not None
        assert set(comps) == set(anatomy.COMPONENTS)
        step_ms = 0.5 / 10 * 1e3
        assert sum(comps.values()) == pytest.approx(step_ms, abs=0.01)
        assert all(v >= 0.0 for v in comps.values())

    def test_anatomy_floors_priced_by_cost_model(self):
        from veles_tpu.telemetry import anatomy
        floors = anatomy.predicted_floors(steps_per_dispatch=100)
        assert floors["host_ms"] > 0.0
        assert floors["dispatch_ms"] < anatomy.predicted_floors(
            steps_per_dispatch=1)["dispatch_ms"]
