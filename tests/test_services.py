"""Service-layer tests (ref SURVEY §4 'Service tests': the reference POSTs
to a live RESTfulAPI unit and spins real servers on localhost — same
approach here with the stdlib client)."""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest
from sklearn.datasets import load_digits

from veles_tpu import prng
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models.standard_workflow import StandardWorkflow
from veles_tpu.services.plotting import (AccumulatingPlotter, MatrixPlotter,
                                         bus)
from veles_tpu.services.restful import RESTfulAPI
from veles_tpu.services.web_status import WebStatusServer


def _post(url, payload):
    req = urllib.request.Request(
        url, json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def _get(url):
    with urllib.request.urlopen(url) as resp:
        return resp.read()


class TestRESTful:
    @pytest.fixture(scope="class")
    def served_model(self):
        prng.seed_all(17)
        d = load_digits()
        x = (d.data / 16.0).astype(np.float32)
        y = d.target.astype(np.int32)
        loader = FullBatchLoader(None, data=x, labels=y, minibatch_size=100,
                                 class_lengths=[0, 297, 1500])
        wf = StandardWorkflow(
            layers=[{"type": "softmax", "output_sample_shape": 10,
                     "learning_rate": 0.2, "gradient_moment": 0.9}],
            loader=loader, decision_config={"max_epochs": 5},
            name="rest-model")
        wf.initialize()
        wf.run()
        fwd = wf.forward_fn()
        params = wf.trainer.params
        api = RESTfulAPI(lambda xx: np.asarray(fwd(params, xx)),
                         (64,), port=0)
        api.start()
        yield api, x, y
        api.stop()

    def test_post_list_codec(self, served_model):
        api, x, y = served_model
        out = _post("http://127.0.0.1:%d/service" % api.port,
                    {"input": x[:3].tolist()})
        probs = np.asarray(out["result"])
        assert probs.shape == (3, 10)
        np.testing.assert_allclose(probs.sum(1), 1.0, rtol=1e-4)
        assert (probs.argmax(1) == y[:3]).mean() >= 2 / 3

    def test_post_base64_codec(self, served_model):
        import base64
        api, x, _ = served_model
        payload = {"codec": "base64",
                   "input": base64.b64encode(x[:2].tobytes()).decode(),
                   "shape": [2, 64]}
        out = _post("http://127.0.0.1:%d/service" % api.port, payload)
        assert np.asarray(out["result"]).shape == (2, 10)

    def test_bad_input_returns_error_json(self, served_model):
        api, _, _ = served_model
        try:
            _post("http://127.0.0.1:%d/service" % api.port,
                  {"input": [[1.0, 2.0]]})
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "error" in json.loads(e.read())

    def test_generate_without_generator_is_an_error(self, served_model):
        api, _, _ = served_model
        try:
            _post("http://127.0.0.1:%d/service" % api.port,
                  {"input": [[1, 2, 3]], "generate": {"max_new": 2}})
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400

    @pytest.mark.slow
    def test_generate_endpoint_serves_int8_weights(self):
        """The REST generate path decodes through int8 W8A8 serving
        weights and returns the same greedy continuation as the float
        generator (trained model, peaked logits)."""
        from veles_tpu.models import zoo
        from veles_tpu.models.generate import LMGenerator

        prng.seed_all(23)
        r = np.random.RandomState(3)
        n, t, vocab = 128, 12, 11
        toks = ((np.arange(t)[None, :] + r.randint(0, 3, n)[:, None])
                % vocab).astype(np.int32)
        loader = FullBatchLoader(None, data=toks, labels=toks,
                                 minibatch_size=32,
                                 class_lengths=[0, 32, 96])
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=vocab, d_model=16,
                                      n_heads=2, n_layers=1, lr=5e-3,
                                      dropout=0.0),
            loader=loader, loss="lm",
            decision_config={"max_epochs": 8}, name="rest-lm-int8")
        wf.initialize()
        wf.run()
        gen_q = LMGenerator(wf.trainer, max_len=t, weights="int8")
        gen_f = LMGenerator(wf.trainer, max_len=t)
        fwd = wf.forward_fn()
        params = wf.trainer.params
        api = RESTfulAPI(lambda xx: np.asarray(fwd(params, xx)), (t,),
                         port=0, generator=gen_q)
        api.start()
        try:
            out = _post("http://127.0.0.1:%d/service" % api.port,
                        {"input": toks[0, :6].tolist(),
                         "generate": {"max_new": 4}})
            res = np.asarray(out["result"])
            np.testing.assert_array_equal(
                res, gen_f.generate(toks[:1, :6], max_new=4))
        finally:
            api.stop()

    @pytest.mark.slow
    def test_generate_endpoint_serves_lm(self):
        from veles_tpu.models import zoo
        from veles_tpu.models.generate import LMGenerator

        prng.seed_all(23)
        r = np.random.RandomState(3)
        n, t, vocab = 128, 12, 11
        toks = ((np.arange(t)[None, :] + r.randint(0, 3, n)[:, None])
                % vocab).astype(np.int32)
        loader = FullBatchLoader(None, data=toks, labels=toks,
                                 minibatch_size=32,
                                 class_lengths=[0, 32, 96])
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=vocab, d_model=16,
                                      n_heads=2, n_layers=1, lr=5e-3,
                                      dropout=0.0),
            loader=loader, loss="lm",
            decision_config={"max_epochs": 8}, name="rest-lm")
        wf.initialize()
        wf.run()
        fwd = wf.forward_fn()
        params = wf.trainer.params
        api = RESTfulAPI(lambda xx: np.asarray(fwd(params, xx)), (t,),
                         port=0,
                         generator=LMGenerator(wf.trainer, max_len=t))
        api.start()
        try:
            out = _post("http://127.0.0.1:%d/service" % api.port,
                        {"input": toks[0, :6].tolist(),
                         "generate": {"max_new": 4}})
            res = np.asarray(out["result"])
            assert res.shape == (1, 10)
            np.testing.assert_array_equal(res[0, :6], toks[0, :6])
            # the natural-but-wrong shorthand gets a descriptive 400,
            # not an opaque AttributeError
            try:
                _post("http://127.0.0.1:%d/service" % api.port,
                      {"input": toks[0, :6].tolist(), "generate": True})
                raise AssertionError("expected HTTP 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert "options object" in json.loads(e.read())["error"]
        finally:
            api.stop()


@pytest.mark.slow
class TestGenerateBatching:
    def test_coalesced_requests_match_solo_and_bound_compiles(self):
        """batch_window > 0: concurrent heterogeneous generate requests
        merge into shared device calls, every client gets exactly the
        tokens a solo call would have produced, and compiles stay
        bounded to power-of-two buckets."""
        import threading as th

        from veles_tpu.models import zoo
        from veles_tpu.models.generate import LMGenerator

        prng.seed_all(29)
        r = np.random.RandomState(3)
        n, t, vocab = 128, 12, 11
        toks = ((np.arange(t)[None, :] + r.randint(0, 3, n)[:, None])
                % vocab).astype(np.int32)
        loader = FullBatchLoader(None, data=toks, labels=toks,
                                 minibatch_size=32,
                                 class_lengths=[0, 32, 96])
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=vocab, d_model=16,
                                      n_heads=2, n_layers=1, lr=5e-3,
                                      dropout=0.0),
            loader=loader, loss="lm",
            decision_config={"max_epochs": 8}, name="rest-batch-lm")
        wf.initialize()
        wf.run()
        gen = LMGenerator(wf.trainer, max_len=t)
        solo = LMGenerator(wf.trainer, max_len=t)     # oracle, unbatched
        fwd = wf.forward_fn()
        params = wf.trainer.params
        api = RESTfulAPI(lambda xx: np.asarray(fwd(params, xx)), (t,),
                         port=0, generator=gen, batch_window=0.15)
        api.start()
        try:
            reqs = [
                {"input": toks[0, :6].tolist(),
                 "generate": {"max_new": 4}},
                {"input": toks[1, :4].tolist(),
                 "generate": {"max_new": 5, "temperature": 0.9,
                              "seed": 7}},
                {"input": toks[2, :8].tolist(),
                 "generate": {"max_new": 2, "temperature": 0.7,
                              "top_k": 3, "seed": 2}},
                {"input": toks[3, :5].tolist(),
                 "generate": {"max_new": 6, "temperature": 1.2,
                              "top_p": 0.9, "seed": 5}},
                {"input": toks[4, :7].tolist(),
                 "generate": {"max_new": 3}},
            ]
            results = [None] * len(reqs)

            def client(i):
                results[i] = _post(
                    "http://127.0.0.1:%d/service" % api.port, reqs[i])

            threads = [th.Thread(target=client, args=(i,))
                       for i in range(len(reqs))]
            for thr in threads:
                thr.start()
            for thr in threads:
                thr.join()
            for req, res in zip(reqs, results):
                opts = req["generate"]
                want = solo.generate(
                    np.asarray(req["input"], np.int32)[None],
                    max_new=opts["max_new"],
                    temperature=opts.get("temperature", 0.0),
                    seed=opts.get("seed", 0),
                    top_k=opts.get("top_k", 0),
                    top_p=opts.get("top_p", 1.0))
                np.testing.assert_array_equal(
                    np.asarray(res["result"]), want)
            # power-of-two buckets only — never one compile per size
            assert set(gen._compiled) <= {1, 2, 4, 8}, list(gen._compiled)
        finally:
            api.stop()


class TestWebStatus:
    def test_dashboard_and_apis(self):
        server = WebStatusServer(port=0)
        server.start()
        try:
            base = "http://127.0.0.1:%d" % server.port
            assert b"veles_tpu status" in _get(base + "/")
            status = json.loads(_get(base + "/api/status"))
            assert "workflows" in status
            out = _post(base + "/update", {"node": "r1", "epoch": 3})
            assert out["ok"]
            status = json.loads(_get(base + "/api/status"))
            assert status["remote"][-1]["update"]["epoch"] == 3
            assert isinstance(json.loads(_get(base + "/api/events")), list)
            # sparkline series: per-epoch metric events from the ring
            assert b"sparkline" in _get(base + "/")
            from veles_tpu.logger import events
            for ep, loss in ((1, 0.8), (2, 0.5), (3, 0.3)):
                events.add({"name": "epoch", "cat": "Decision",
                            "type": "single", "time": 0.0, "epoch": ep,
                            "valid_loss": loss})
            series = json.loads(_get(base + "/api/metrics"))
            assert series["valid_loss"] == [[1, 0.8], [2, 0.5], [3, 0.3]]
            # workflow graph + DOT (ref workflow SVG in status POSTs)
            from veles_tpu.plumbing import Repeater
            from veles_tpu.workflow import Workflow
            wf = Workflow(name="gwf")
            rpt = Repeater(wf)
            rpt.link_from(wf.start_point)
            wf.end_point.link_from(rpt)
            server.register(wf)
            g = json.loads(_get(base + "/api/graph"))["gwf"]
            names = {n["name"] for n in g["nodes"]}
            assert "Repeater" in names and len(g["edges"]) >= 2
            assert all({"cls", "runs", "time", "share"} <= set(n)
                       for n in g["nodes"])
            dot = _get(base + "/api/dot").decode()
            assert dot.startswith("digraph") and "Repeater" in dot
            # chrome-trace export: B/E pairs for begin/end, instants
            # for singles, µs timestamps
            from veles_tpu.logger import events as ev_ring
            ev_ring.add({"name": "unit", "cat": "T", "type": "begin",
                         "time": 10.0})
            ev_ring.add({"name": "unit", "cat": "T", "type": "end",
                         "time": 10.5, "n": 3})
            trace = json.loads(_get(base + "/api/trace"))
            recs = [t for t in trace["traceEvents"]
                    if t["name"] == "unit"]
            assert [t["ph"] for t in recs] == ["B", "E"]
            assert recs[1]["ts"] - recs[0]["ts"] == 5e5
            assert recs[1]["args"]["n"] == 3
            page = _get(base + "/")
            assert b"drawGraph" in page and b"drawTimeline" in page
        finally:
            server.stop()


class TestProfilerEndpoint:
    def test_on_demand_capture_serves_chrome_trace(self, tmp_path,
                                                   monkeypatch):
        """POST /api/profile opens a jax.profiler window over the live
        process; /api/profile/trace then serves the decompressed
        chrome-trace JSON (the on-chip step timeline, VERDICT r3 #10).

        Hermetic over a stubbed ``jax.profiler``: the real profiler's
        ``start_trace`` takes ~8 s to initialize in this sandbox (slow
        enough that a short capture window blows any reasonable poll
        deadline — a pre-existing tier-1 failure), and what this test
        owns is the ENDPOINT state machine — the capture slot's
        exclusivity, running→done lifecycle, and the gz trace being
        found and served decompressed — not jax's tracer."""
        import gzip
        import time as _time

        import jax

        calls = {"started": [], "stopped": 0}

        def fake_start(d):
            calls["started"].append(d)

        def fake_stop():
            calls["stopped"] += 1
            d = os.path.join(calls["started"][-1], "plugins",
                             "profile", "20260803")
            os.makedirs(d, exist_ok=True)
            with gzip.open(os.path.join(d, "host.trace.json.gz"),
                           "wb") as f:
                f.write(json.dumps(
                    {"traceEvents": [{"name": "stub"}]}).encode())

        monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
        monkeypatch.setattr(jax.profiler, "stop_trace", fake_stop)

        from veles_tpu.config import root
        prev = root.common.dirs.get("profiles", None)
        root.common.dirs.profiles = str(tmp_path)
        server = WebStatusServer(port=0)
        server.start()
        try:
            base = "http://127.0.0.1:%d" % server.port
            out = _post(base + "/api/profile", {"seconds": 0.3})
            assert out["ok"] and out["dir"].startswith(str(tmp_path))
            # concurrent capture refused while one is running
            refused = _post(base + "/api/profile", {"seconds": 1})
            assert "error" in refused
            deadline = _time.time() + 15
            while _time.time() < deadline:
                state = json.loads(_get(base + "/api/profile"))
                if not state.get("running"):
                    break
                _time.sleep(0.05)
            assert not state.get("running") and "error" not in state
            assert calls["started"] == [out["dir"]]
            assert calls["stopped"] == 1
            trace = json.loads(_get(base + "/api/profile/trace"))
            assert trace["traceEvents"][0]["name"] == "stub"
        finally:
            server.stop()
            if prev is None:
                if "profiles" in root.common.dirs:
                    del root.common.dirs.profiles
            else:
                root.common.dirs.profiles = prev


class TestCrossRunLogBrowser:
    def test_sqlite_store_and_api(self, tmp_path):
        """Log duplication + cross-run browse (the reference's Mongo
        log store + web browser, ref veles/logger.py:292-331,
        web_status.py:113-200 — redesigned onto sqlite)."""
        import logging

        from veles_tpu.config import root
        from veles_tpu.logger import (duplicate_log_to, log_sessions,
                                      search_logs)
        db = str(tmp_path / "logs.sqlite3")
        prev_level = logging.getLogger().level
        logging.getLogger().setLevel(logging.INFO)
        # two "runs" land in one store
        h1 = duplicate_log_to(db, session="run-A", node="n0")
        logging.getLogger("TestUnit").info("alpha %d", 1)
        logging.getLogger("TestUnit").warning("needle in A")
        logging.getLogger().removeHandler(h1)
        h1.close()
        h2 = duplicate_log_to(db, session="run-B", node="n0")
        logging.getLogger("Other").info("needle in B")
        logging.getLogger().removeHandler(h2)
        h2.close()
        logging.getLogger().setLevel(prev_level)

        runs = log_sessions(db)
        assert [r["session"] for r in runs] == ["run-B", "run-A"]
        assert runs[1]["records"] == 2
        hits = search_logs(db, q="needle")
        assert {h["session"] for h in hits} == {"run-A", "run-B"}
        only_a = search_logs(db, session="run-A", q="needle")
        assert len(only_a) == 1 and only_a[0]["level"] == "WARNING"
        assert search_logs(db, level="warning") and \
            not search_logs(db, q="no-such-text")

        prev = root.common.web.get("log_db", None)
        root.common.web.log_db = db
        server = WebStatusServer(port=0)
        server.start()
        try:
            base = "http://127.0.0.1:%d" % server.port
            runs = json.loads(_get(base + "/api/logruns"))["runs"]
            assert len(runs) == 2
            out = json.loads(_get(base + "/api/logs?q=needle&session=run-B"))
            assert [l["session"] for l in out["logs"]] == ["run-B"]
            assert b"log browser" in _get(base + "/")
        finally:
            server.stop()
            root.common.web.log_db = prev


class TestPlotters:
    def test_accumulating_plotter_writes_png(self, tmp_path):
        from veles_tpu.workflow import Workflow
        wf = Workflow(name="plots")
        values = iter([0.5, 0.4, 0.3])
        p = AccumulatingPlotter(wf, source=lambda: next(values),
                                directory=str(tmp_path), ylabel="err")
        p.run()
        p.run()
        assert p.last_file and p.last_file.endswith(".png")
        import os
        assert os.path.getsize(p.last_file) > 500
        assert bus.snapshot()[-1]["kind"] == "curve"

    def test_matrix_plotter(self, tmp_path):
        from veles_tpu.workflow import Workflow
        wf = Workflow(name="plots2")
        m = np.eye(4) * 5
        p = MatrixPlotter(wf, source=lambda: m, directory=str(tmp_path))
        p.run()
        import os
        assert os.path.exists(p.last_file)


@pytest.mark.slow
class TestCLI:
    def test_sample_workflow_via_cli(self, tmp_path):
        result_file = str(tmp_path / "results.json")
        export_file = str(tmp_path / "model.zip")
        from veles_tpu.services.supervisor import run_with_startup_retry
        proc = run_with_startup_retry(
            [sys.executable, "-m", "veles_tpu", "samples/digits_mlp.py",
             "samples/digits_config.py", "--backend", "cpu",
             "--random-seed", "5",
             "--config-list", "root.digits.max_epochs=2",
             "--result-file", result_file, "--export", export_file],
            timeout=300,
            cwd=str(__import__("pathlib").Path(__file__).parent.parent))
        assert proc.returncode == 0, proc.stderr[-2000:]
        results = json.load(open(result_file))
        assert results["epochs"] == 2
        assert results["best_metric"] < 0.5
        from veles_tpu.services.export import import_workflow
        manifest, _ = import_workflow(export_file)
        assert manifest["name"] == "digits-mlp"

    def test_cli_snapshot_resume(self, tmp_path):
        snap_dir = str(tmp_path / "snaps")
        base = [sys.executable, "-m", "veles_tpu", "samples/digits_mlp.py",
                "--backend", "cpu", "--random-seed", "5"]
        cwd = str(__import__("pathlib").Path(__file__).parent.parent)
        from veles_tpu.services.supervisor import run_with_startup_retry
        p1 = run_with_startup_retry(
            base + ["--config-list", "root.digits.max_epochs=2"],
            timeout=300, cwd=cwd)
        assert p1.returncode == 0, p1.stderr[-2000:]


class TestWebFrontendEndpoint:
    def test_frontend_page_served(self):
        import urllib.request
        from veles_tpu.services.web_status import WebStatusServer
        srv = WebStatusServer(port=0)
        srv.start()
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/frontend" % srv.port) as r:
                html = r.read().decode()
            assert "command composer" in html and "random_seed" in html
        finally:
            srv.stop()


@pytest.mark.slow
class TestProfileFlag:
    def test_cli_profile_writes_trace(self, tmp_path):
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = str(tmp_path / "trace")
        from veles_tpu.services.supervisor import run_with_startup_retry
        r = run_with_startup_retry(
            [sys.executable, "-m", "veles_tpu", "samples/digits_mlp.py",
             "--backend", "cpu", "--random-seed", "3",
             "--config-list", "root.digits.max_epochs=1",
             "--profile", out],
            cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            timeout=420)
        assert r.returncode == 0, r.stderr[-2000:]
        found = [f for _, _, fs in os.walk(out) for f in fs]
        assert any(f.endswith((".pb", ".json.gz", ".xplane.pb"))
                   for f in found), found


class TestNewPlotters:
    """r2 service tails (VERDICT #9): multi-histogram + min-max envelope
    plotters, checked against golden PNGs (ref veles/tests/res/ golden
    plotter images)."""

    GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "res")

    def _check_golden(self, path, name):
        """Pixel comparison against the committed golden render.  A
        missing golden FAILS (a silently self-created golden would bake
        in whatever the current code draws); regenerate deliberately with
        VELES_REGEN_GOLDEN=1 after a reviewed rendering change."""
        from PIL import Image
        golden = os.path.join(self.GOLDEN, name)
        if os.environ.get("VELES_REGEN_GOLDEN") == "1":
            import shutil
            shutil.copy(path, golden)
        assert os.path.exists(golden), (
            "golden image %s missing — run with VELES_REGEN_GOLDEN=1 and "
            "commit it" % golden)
        got = np.asarray(Image.open(path).convert("RGB"), np.float32)
        want = np.asarray(Image.open(golden).convert("RGB"), np.float32)
        assert got.shape == want.shape
        assert np.abs(got - want).mean() < 1.0

    def test_multi_histogram_golden(self, tmp_path):
        from veles_tpu.services.plotting import MultiHistogramPlotter
        from veles_tpu.workflow import Workflow
        rng = np.random.RandomState(0)
        wf = Workflow(name="mh")
        p = MultiHistogramPlotter(
            wf, sources={"l0_weights": rng.normal(size=400),
                         "l1_weights": rng.uniform(size=300),
                         "l2_bias": rng.normal(2.0, 0.5, 200)},
            directory=str(tmp_path), name="multihist")
        p.run()
        assert bus.snapshot()[-1]["kind"] == "multi_histogram"
        assert len(bus.snapshot()[-1]["histograms"]) == 3
        self._check_golden(p.last_file, "golden_multihist.png")

    def test_minmax_golden(self, tmp_path):
        from veles_tpu.services.plotting import MinMaxPlotter
        from veles_tpu.workflow import Workflow
        rng = np.random.RandomState(1)
        wf = Workflow(name="mm")
        feed = iter(rng.normal(0, s, 100) for s in (1.0, 0.8, 0.5, 0.3))
        p = MinMaxPlotter(wf, source=lambda: next(feed), ylabel="weights",
                          directory=str(tmp_path), name="minmax")
        for _ in range(4):
            p.run()
        payload = bus.snapshot()[-1]
        assert payload["kind"] == "minmax"
        assert len(payload["mean"]) == 4
        assert all(a >= b for a, b in zip(payload["max"], payload["min"]))
        self._check_golden(p.last_file, "golden_minmax.png")


class TestNewPublishingBackends:
    def _workflow(self):
        from veles_tpu.units import TrivialUnit
        from veles_tpu.workflow import Workflow
        wf = Workflow(name="pub2")
        u = TrivialUnit(wf, name="trainer")
        u.run_count = 5
        u.run_time = 1.25
        return wf

    def test_pdf_backend(self, tmp_path):
        from veles_tpu.publishing import Publisher
        pub = Publisher(self._workflow(), backends=("pdf",),
                        directory=str(tmp_path), description="pdf test")
        pub.run()
        pdf = open(pub.written[0], "rb").read()
        assert pdf.startswith(b"%PDF")
        assert len(pdf) > 1000

    def test_confluence_backend(self, tmp_path):
        from veles_tpu.publishing import Publisher
        pub = Publisher(self._workflow(), backends=("confluence",),
                        directory=str(tmp_path))
        pub.run()
        text = open(pub.written[0]).read()
        assert "h1. pub2" in text
        assert "||unit||runs||total s||" in text
        assert "|trainer|5|1.250|" in text


class TestUnitStatsPlotter:
    def test_renders_units_and_memory(self, tmp_path):
        import jax.numpy as jnp

        from veles_tpu.services.plotting import UnitStatsPlotter
        from veles_tpu.units import TrivialUnit
        from veles_tpu.workflow import Workflow
        wf = Workflow(name="stats")
        for i, t in enumerate((0.5, 0.2, 0.9)):
            u = TrivialUnit(wf, name="unit%d" % i)
            u.run_count = i + 1
            u.run_time = t
        keep = jnp.ones((64, 64))   # something live on a device
        p = UnitStatsPlotter(wf, directory=str(tmp_path), name="ustats")
        p.run()
        payload = bus.snapshot()[-1]
        assert payload["kind"] == "unit_stats"
        assert payload["units"][0]["name"] == "unit2"   # sorted by time
        assert os.path.getsize(p.last_file) > 1000
        del keep


@pytest.mark.slow
class TestTracingFlags:
    def test_event_log_and_sync_run(self, tmp_path):
        """--event-log writes a JSONL event timeline; --sync-run runs
        the same training with per-step device sync (ref --sync-run +
        the Mongo event timeline)."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        log = str(tmp_path / "events.jsonl")
        from veles_tpu.services.supervisor import run_with_startup_retry
        r = run_with_startup_retry(
            [sys.executable, "-m", "veles_tpu", "samples/digits_mlp.py",
             "--backend", "cpu", "--random-seed", "3",
             "--config-list", "root.digits.max_epochs=1",
             "--event-log", log, "--sync-run"],
            cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            timeout=420)
        assert r.returncode == 0, r.stderr[-2000:]
        lines = [json.loads(ln) for ln in open(log)]
        assert len(lines) > 10
        assert any(e["name"] == "minibatch" for e in lines)
        assert all({"name", "cat", "type", "time"} <= set(e) for e in lines)


@pytest.mark.slow
class TestContinuousServing:
    def test_rest_endpoint_rides_the_continuous_engine(self):
        """continuous_slots>0: concurrent HTTP generate requests join
        the live slot pool and each gets its exact solo continuation
        (the ContinuousEngine REST integration)."""
        import threading as _threading

        from veles_tpu.models import zoo
        from veles_tpu.models.generate import LMGenerator

        prng.seed_all(23)
        r = np.random.RandomState(3)
        n, t, vocab = 128, 12, 11
        toks = ((np.arange(t)[None, :] + r.randint(0, 3, n)[:, None])
                % vocab).astype(np.int32)
        loader = FullBatchLoader(None, data=toks, labels=toks,
                                 minibatch_size=32,
                                 class_lengths=[0, 32, 96])
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=vocab, d_model=16,
                                      n_heads=2, n_layers=1, lr=5e-3,
                                      dropout=0.0),
            loader=loader, loss="lm",
            decision_config={"max_epochs": 8}, name="rest-cont")
        wf.initialize()
        wf.run()
        gen = LMGenerator(wf.trainer, max_len=t)
        fwd = wf.forward_fn()
        params = wf.trainer.params
        api = RESTfulAPI(lambda xx: np.asarray(fwd(params, xx)), (t,),
                         port=0, generator=gen, continuous_slots=3)
        api.start()
        try:
            url = "http://127.0.0.1:%d/service" % api.port
            outs = {}

            def req(i, plen, max_new):
                outs[i] = _post(url, {
                    "input": toks[i, :plen].tolist(),
                    "generate": {"max_new": max_new}})["result"]

            threads = [_threading.Thread(target=req, args=a) for a in
                       ((0, 5, 4), (1, 6, 3), (2, 4, 5), (3, 5, 4))]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
            for i, plen, max_new in ((0, 5, 4), (1, 6, 3), (2, 4, 5),
                                     (3, 5, 4)):
                want = gen.generate(toks[i:i + 1, :plen],
                                    max_new)[0].tolist()
                assert outs[i][0] == want, (i, outs[i][0], want)
        finally:
            api.stop()


    def test_rest_streaming_ndjson(self):
        """{"stream": true}: the response is NDJSON — {"tokens": [...]}
        lines whose concatenation equals the buffered result, then a
        {"done": true, "result": [...]} terminal line matching the
        solo decode.  Ineligible stream requests (beam, two rows, no
        engine) must 400."""
        import urllib.request

        from veles_tpu.models import zoo
        from veles_tpu.models.generate import LMGenerator

        prng.seed_all(23)
        r = np.random.RandomState(3)
        n, t, vocab = 128, 12, 11
        toks = ((np.arange(t)[None, :] + r.randint(0, 3, n)[:, None])
                % vocab).astype(np.int32)
        loader = FullBatchLoader(None, data=toks, labels=toks,
                                 minibatch_size=32,
                                 class_lengths=[0, 32, 96])
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=vocab, d_model=16,
                                      n_heads=2, n_layers=1, lr=5e-3,
                                      dropout=0.0),
            loader=loader, loss="lm",
            decision_config={"max_epochs": 8}, name="rest-stream")
        wf.initialize()
        wf.run()
        gen = LMGenerator(wf.trainer, max_len=t)
        api = RESTfulAPI(lambda xx: xx, (t,), port=0, generator=gen,
                         continuous_slots=2)
        api.start()
        try:
            url = "http://127.0.0.1:%d/service" % api.port
            body = json.dumps({
                "input": toks[0, :5].tolist(),
                "generate": {"max_new": 5, "stream": True}}).encode()
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                assert resp.headers["Content-Type"] == \
                    "application/x-ndjson"
                lines = [json.loads(l)
                         for l in resp.read().decode().splitlines()]
            assert lines[-1]["done"] is True
            streamed = [tok for l in lines[:-1] for tok in l["tokens"]]
            want = gen.generate(toks[:1, :5], 5)[0].tolist()
            assert lines[-1]["result"] == want
            assert toks[0, :5].tolist() + streamed == want
            assert len(lines) >= 3        # genuinely incremental
            # ineligible: beam
            bad = json.dumps({
                "input": toks[0, :5].tolist(),
                "generate": {"max_new": 4, "stream": True,
                             "beam": 2}}).encode()
            req = urllib.request.Request(
                url, data=bad,
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=60)
                assert False, "beam stream must 400"
            except urllib.error.HTTPError as e:
                assert e.code == 400
        finally:
            api.stop()


@pytest.mark.slow
class TestServingSLO:
    """Serving-plane observability + SLO (r4 verdict #4): N concurrent
    clients against a small ContinuousEngine pool — every request
    completes (no starvation), tail latency is bounded, the metrics
    are truthful, and the /metrics endpoint + dashboard panel see it.
    Slow-tier budget (conftest.SLOW_MODULES note): replaces nothing but
    skips training — the untrained model costs compile-only (~30 s)."""

    T, VOCAB = 24, 11

    def _generator(self):
        from veles_tpu.models import zoo
        from veles_tpu.models.generate import LMGenerator

        prng.seed_all(29)
        toks = np.random.RandomState(5).randint(
            0, self.VOCAB, (8, self.T)).astype(np.int32)
        loader = FullBatchLoader(None, data=toks, labels=toks,
                                 minibatch_size=4,
                                 class_lengths=[0, 4, 4])
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=self.VOCAB, d_model=16,
                                      n_heads=2, n_layers=1,
                                      dropout=0.0),
            loader=loader, loss="lm",
            decision_config={"max_epochs": 1}, name="slo-serve")
        wf.initialize()
        return LMGenerator(wf.trainer, max_len=self.T), toks

    def test_load_no_starvation_bounded_tails_truthful_metrics(self):
        import threading as _threading
        import time as _time

        from veles_tpu.services.restful import ContinuousEngine

        gen, toks = self._generator()
        eng = ContinuousEngine(gen, slots=4)
        try:
            n_req, max_new = 16, 8
            # warmup with the burst's EXACT shape: admission prefill
            # and the tick both compile per shape bucket, and a cold
            # compile mid-burst would stall every queued client
            eng.submit(toks[0, :6].tolist(), max_new)
            eng.reset_metrics()     # compile time must not skew SLOs
            done = [None] * n_req

            def client(i):
                done[i] = eng.submit(toks[i % 8, :6].tolist(), max_new)

            t0 = _time.monotonic()
            threads = [_threading.Thread(target=client, args=(i,))
                       for i in range(n_req)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=180)
            wall_ms = (_time.monotonic() - t0) * 1e3
            # no starvation: every client completed with its tokens
            assert all(d is not None and len(d) == 6 + max_new
                       for d in done)

            m = eng.metrics()
            assert m["served"] == n_req
            assert m["queued"] == 0 and m["in_flight"] == 0
            assert m["slots"] == 4
            assert m["p50_ms_per_tok"] > 0
            assert m["agg_tokens_per_sec"] > 0
            # tail bounds: p99 queue-wait can't exceed the burst's own
            # wall time, and must be consistent with FIFO over
            # ceil(16/4) waves of ~max_new-token decodes (generous 6x
            # headroom for the 1-core CI box — catches unbounded waits,
            # not jitter)
            assert m["p99_queue_wait_ms"] < wall_ms
            p99_decode_ms = m["p99_ms_per_tok"] * max_new
            waves = -(-n_req // m["slots"])
            assert m["p99_queue_wait_ms"] < 6 * waves * p99_decode_ms, m
            # no straggler streams: worst decode rate within 25x median
            assert m["p99_ms_per_tok"] < 25 * m["p50_ms_per_tok"], m
        finally:
            eng.stop()

    def test_metrics_endpoint_and_dashboard_panel(self):
        gen, toks = self._generator()
        api = RESTfulAPI(lambda xx: xx, (self.T,), port=0,
                         generator=gen, continuous_slots=2)
        api.start()
        web = WebStatusServer(port=0)
        web.register_serving(api)
        try:
            url = "http://127.0.0.1:%d/service" % api.port
            _post(url, {"input": toks[0, :5].tolist(),
                        "generate": {"max_new": 3}})
            with urllib.request.urlopen(url + "/metrics") as r:
                m = json.loads(r.read())
            assert m["paths"]["continuous"] is True
            assert m["continuous"]["served"] == 1
            assert m["continuous"]["p50_tokens_per_sec"] > 0
            # the dashboard's /api/status carries the same snapshot
            s = web.status()
            assert s["serving"]["continuous"]["served"] == 1
        finally:
            api.stop()


class TestSqliteLogJournalMode:
    def test_local_path_uses_wal(self, tmp_path, monkeypatch):
        """A path the detector classifies local gets WAL.  The
        detector is stubbed: the suite must not depend on what
        filesystem the CI sandbox mounts /tmp on (some containers
        genuinely put it on 9p/overlay-over-network, where the real
        detector CORRECTLY disables WAL — the network-path test
        below covers that branch)."""
        import veles_tpu.logger as vl
        monkeypatch.setattr(vl, "_network_fs_type", lambda p: None)
        h = vl.SqliteLogHandler(str(tmp_path / "logs.db"), session="s1")
        mode = h._conn.execute("PRAGMA journal_mode").fetchone()[0]
        h.close()
        assert mode == "wal"

    def test_network_path_falls_back_to_rollback_journal(
            self, tmp_path, monkeypatch):
        """WAL needs a coherent shared-memory file — unsupported on
        network filesystems; a pod-shared log DB must use the rollback
        journal + busy retry instead (ADVICE r4)."""
        import veles_tpu.logger as vl
        monkeypatch.setattr(vl, "_network_fs_type", lambda p: "nfs4")
        h = vl.SqliteLogHandler(str(tmp_path / "logs.db"), session="s2")
        mode = h._conn.execute("PRAGMA journal_mode").fetchone()[0]
        busy = h._conn.execute("PRAGMA busy_timeout").fetchone()[0]
        h.close()
        assert mode == "delete"
        assert busy == 5000

    def test_network_fs_detector_local_and_boundary(self):
        """Detector semantics over a FAKE mounts table — hermetic, so
        the verdicts hold no matter what the CI sandbox really mounts
        (a 9p-backed /tmp used to fail the old real-path assertion
        while the detector was behaving exactly as designed)."""
        import veles_tpu.logger as vl
        real_open = open

        def fake_mounts(path, *a, **k):
            if path == "/proc/mounts":
                import io
                return io.StringIO(
                    "srv /data nfs4 rw 0 0\n"
                    "tmpfs /scratch tmpfs rw 0 0\n"
                    "overlay / overlay rw 0 0\n")
            return real_open(path, *a, **k)

        import builtins
        orig = builtins.open
        builtins.open = fake_mounts
        try:
            # a local-fs path is classified local (WAL stays on) —
            # if this fails, every pod log DB silently loses WAL
            assert vl._network_fs_type("/scratch/logs.db") is None
            assert vl._network_fs_type("/var/logs.db") is None
            assert vl._network_fs_type("/data/logs.db") == "nfs4"
            # component boundary: /data must not claim /database
            assert vl._network_fs_type("/database/logs.db") is None
        finally:
            builtins.open = orig


class TestBenchPanel:
    def test_api_bench_reports_measured_vs_predicted(self, tmp_path,
                                                     monkeypatch):
        """/api/bench joins the newest bench rows of the process
        performance ledger with the roofline model's predictions — the
        dashboard's measurement-confirms-model view."""
        from veles_tpu.telemetry import ledger

        monkeypatch.setenv("VELES_TPU_PERF_LEDGER",
                           str(tmp_path / "led.jsonl"))
        book = ledger.PerfLedger(str(tmp_path / "led.jsonl"))
        book.append_bench_line({"lm_large_mfu": 0.2, "value": 100.0},
                               backend="tpu:1", ts=1785580254.0)
        book.append_bench_line({"lm_large_mfu": 0.3},
                               backend="tpu:1", ts=1785580354.0)
        server = WebStatusServer(port=0)
        server.start()
        try:
            base = "http://127.0.0.1:%d" % server.port
            rep = json.loads(_get(base + "/api/bench"))
            assert rep["measured"] == {"lm_large_mfu": 0.3,
                                       "value": 100.0}
            assert rep["measured_at"].startswith("20")
            # predictions ride along when the model imports
            assert "lm_large_mfu" in rep.get("predicted", {})
            assert b'id="bench"' in _get(base + "/")
        finally:
            server.stop()
