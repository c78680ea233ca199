"""Launcher lifecycle, LR adjuster schedules, and ZMQ graphics pub/sub."""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from veles_tpu import prng  # noqa: E402
from veles_tpu.launcher import Launcher, filter_argv  # noqa: E402
from veles_tpu.loader.fullbatch import FullBatchLoader  # noqa: E402
from veles_tpu.models.lr_adjuster import LRAdjuster, POLICIES  # noqa: E402
from veles_tpu.models.standard_workflow import StandardWorkflow  # noqa: E402
from veles_tpu.services import plotting  # noqa: E402
from veles_tpu.services.graphics import (GraphicsClient,  # noqa: E402
                                         GraphicsServer)


def _mnistish_workflow(**kw):
    prng.seed_all(21)
    n = 32
    x = np.random.RandomState(0).rand(2 * n, 6, 6, 1).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 3, 2 * n).astype(np.int32)
    loader = FullBatchLoader(None, data=x, labels=y, minibatch_size=16,
                             class_lengths=[0, n, n])
    return StandardWorkflow(
        layers=[{"type": "softmax", "output_sample_shape": 3,
                 "learning_rate": 0.05, "gradient_moment": 0.9}],
        loader=loader, decision_config={"max_epochs": 3}, **kw)


class TestFilterArgv:
    def test_drops_flag_and_value(self):
        argv = ["prog", "-l", "host:1", "--keep", "x", "--drop=5", "tail"]
        assert filter_argv(argv, "-l=", "--drop=") == \
            ["prog", "--keep", "x", "tail"]

    def test_bare_flag_keeps_following_arg(self):
        assert filter_argv(["prog", "-v", "train.py"], "-v") == \
            ["prog", "train.py"]


class TestLauncher:
    def test_standalone_boot(self):
        wf = _mnistish_workflow(name="launch-test")
        launcher = Launcher(workflow=wf)
        assert launcher.is_standalone and launcher.is_master
        launcher.boot()
        assert wf.gather_results()["epochs"] == 3

    def test_named_backend_that_jax_did_not_find_is_an_error(self):
        """``engine.backend`` / ``--backend`` names a device: the run
        starts by logging what jax found, and a name jax did not land
        on is an error — with "auto" jax quietly picks the CPU on a
        chipless machine, which a run that asked for a TPU never
        means."""
        from veles_tpu.config import root
        wf = _mnistish_workflow(name="launch-backend")
        launcher = Launcher(workflow=wf)
        prev = root.common.engine.get("backend", "auto")
        try:
            root.common.engine.backend = "tpu"
            with pytest.raises(RuntimeError, match="found platform 'cpu'"):
                launcher.initialize()
            assert not wf._initialized          # refused before any work
            for ok in ("cpu", "auto"):
                launcher._announce_backend(ok)
        finally:
            root.common.engine.backend = prev

    def test_mode_detection_spmd(self):
        launcher = Launcher(coordinator_address="10.0.0.1:1234",
                            num_processes=4, process_id=2)
        assert launcher.mode == "spmd"
        assert launcher.num_processes == 4

    def test_mesh_axes_build(self):
        wf = _mnistish_workflow(name="launch-mesh")
        launcher = Launcher(workflow=wf, mesh_axes={"data": 1})
        launcher.initialize()
        assert launcher.mesh_config is not None
        launcher.run()

    def test_web_status_service(self):
        import urllib.request
        wf = _mnistish_workflow(name="launch-web")
        launcher = Launcher(workflow=wf, web_status_port=0)
        launcher.initialize()
        port = launcher.web_server.port
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/api/status" % port) as r:
            assert b"launch-web" in r.read()
        launcher.run()   # stops services afterwards (idempotent stop
        assert launcher.web_server is None   # clears the reference)


class TestLRAdjuster:
    def test_policies(self):
        assert POLICIES["exp"](2, base=0.5) == 0.25
        assert POLICIES["step_exp"](25, base=0.1, step=10) == \
            pytest.approx(0.01)
        assert POLICIES["inv"](0) == 1.0
        assert POLICIES["arbitrary_step"](
            7, steps=[(0, 1.0), (5, 0.3), (10, 0.1)]) == 0.3
        # warmup_cosine: linear ramp, peak after warmup, floor at total
        wc = POLICIES["warmup_cosine"]
        assert wc(0, warmup=4, total=20) == pytest.approx(0.25)
        assert wc(3, warmup=4, total=20) == pytest.approx(1.0)
        assert wc(4, warmup=4, total=20) == pytest.approx(1.0)
        assert wc(20, warmup=4, total=20, floor=0.1) == pytest.approx(0.1)
        assert 0.4 < wc(12, warmup=4, total=20) < 0.6
        # the integration path: kwargs must survive the unit's whitelist
        adj = LRAdjuster(None, policy="warmup_cosine", warmup=4,
                         total=20, floor=0.1)
        assert adj.scale_for(0) == pytest.approx(0.25)
        assert adj.scale_for(20) == pytest.approx(0.1)

    def test_adjuster_in_workflow(self):
        wf = _mnistish_workflow(
            name="lr-test",
            lr_adjuster_config={"policy": "exp", "base": 0.5})
        wf.initialize()
        wf.run()
        # after 3 epochs the last applied scale reflects the schedule
        assert wf.trainer.lr_scale == pytest.approx(
            0.5 ** wf.loader.epoch_number)

    def test_training_still_converges_with_schedule(self):
        wf = _mnistish_workflow(
            name="lr-conv",
            lr_adjuster_config={"policy": "inv", "gamma": 0.1,
                                "power": 0.5})
        wf.initialize()
        wf.run()
        res = wf.gather_results()
        assert res["epochs"] == 3 and res["best_metric"] is not None


class TestGraphics:
    def test_pub_sub_roundtrip(self):
        local_bus = plotting.PlotBus()
        srv = GraphicsServer(bus=local_bus).start()
        client = GraphicsClient(srv.endpoint).start()
        time.sleep(0.3)   # SUB connect (slow-joiner)
        for i in range(3):
            local_bus.publish({"name": "loss", "kind": "curve",
                               "values": list(range(i + 1)),
                               "ylabel": "loss"})
            time.sleep(0.05)
        deadline = time.time() + 5
        while client.received < 3 and time.time() < deadline:
            time.sleep(0.05)
        assert client.received >= 1
        assert client.latest["loss"]["kind"] == "curve"
        srv.stop()
        client.stop()

    def test_multicast_binds_degrade_gracefully(self):
        """With a multicast group configured the server attempts an
        epgm:// bind per non-blacklisted interface (ref LAN plot
        broadcast, graphics_server.py:100-133) and the tcp endpoint
        keeps working whether or not libzmq was built with PGM."""
        local_bus = plotting.PlotBus()
        srv = GraphicsServer(bus=local_bus, multicast="239.192.1.1",
                             ifaces=["lo", "fake0"],
                             multicast_port=15555)
        # blacklist filtering happens before any bind attempt
        srv._blacklist = {"fake0"}
        assert srv._multicast_ifaces() == ["lo"]
        srv.start()
        try:
            assert srv.endpoints["tcp"].startswith("tcp://")
            import zmq
            if zmq.has("pgm"):
                assert srv.endpoints["epgm"] == [
                    "epgm://lo;239.192.1.1:15555"]
            else:
                assert srv.endpoints["epgm"] == []   # warned, not raised
            # the tcp path still round-trips
            client = GraphicsClient(srv.endpoint).start()
            time.sleep(0.3)
            local_bus.publish({"name": "mc", "kind": "curve",
                               "values": [1], "ylabel": "x"})
            deadline = time.time() + 5
            while client.received < 1 and time.time() < deadline:
                time.sleep(0.05)
            assert client.received >= 1
            client.stop()
        finally:
            srv.stop()

    def test_client_renders_png(self, tmp_path):
        client = GraphicsClient("tcp://127.0.0.1:1", str(tmp_path))
        client.latest = {"loss": {"name": "loss", "kind": "curve",
                                  "values": [3.0, 2.0, 1.0],
                                  "ylabel": "loss"}}
        written = client.render_all()
        assert len(written) == 1
        assert written[0].endswith("loss.png")
        import os
        assert os.path.getsize(written[0]) > 0

    def test_client_pdf_export_and_signal(self, tmp_path):
        """r2: the reference's SIGUSR2 PDF export
        (veles/graphics_client.py)."""
        import os
        import signal
        client = GraphicsClient("tcp://127.0.0.1:1", str(tmp_path))
        client.latest = {"w": {"name": "w", "kind": "minmax",
                               "min": [0.0, -1.0], "mean": [1.0, 0.5],
                               "max": [2.0, 2.5], "ylabel": "w"}}
        written = client.render_all(fmt="pdf")
        assert written[0].endswith("w.pdf")
        assert open(written[0], "rb").read(4) == b"%PDF"
        os.remove(written[0])
        client.install_pdf_signal()
        os.kill(os.getpid(), signal.SIGUSR2)
        assert open(os.path.join(str(tmp_path), "w.pdf"),
                    "rb").read(4) == b"%PDF"
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)

    def test_plotter_feeds_subscribers(self):
        seen = []
        plotting.bus.subscribe(seen.append)
        try:
            plotting.bus.publish({"name": "x", "kind": "curve"})
            assert seen and seen[0]["name"] == "x"
        finally:
            plotting.bus.unsubscribe(seen.append)
