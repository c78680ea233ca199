"""Launcher — run-mode detection and process lifecycle
(ref veles/launcher.py:100: master/slave/standalone mode selection, reactor
ownership, graphics + web-status startup, `boot()=initialize()+run()`).

The reference's three modes map onto SPMD:

* **standalone** — one process, one device (or a local mesh).
* **spmd** — every host runs the *same* program; ``jax.distributed``
  over DCN replaces the Twisted TCP control plane, and the gradient
  exchange is the ``psum`` XLA inserts over ICI (no master/slave
  asymmetry — "master" duties like snapshotting and dashboards fall to
  process 0).

The reference's SSH slave spawning/YARN discovery are the pod scheduler's
job now (GKE/xmanager); what remains launcher-shaped is: initialize the
distributed runtime, build the mesh, start host-side services on process
0, boot the workflow, and shut everything down."""

import os

from veles_tpu import telemetry
from veles_tpu.logger import Logger
from veles_tpu.parallel import MeshConfig, make_mesh


def filter_argv(argv, *flags):
    """Drop ``flags`` from an argv list — used when respawning/forwarding
    commands (ref launcher.py:75).  A flag spelled with a trailing ``=``
    (e.g. ``"-l="``) also consumes its separate value argument; a bare
    flag name drops only the flag itself (boolean switches)."""
    value_flags = {f[:-1] for f in flags if f.endswith("=")}
    bare_flags = {f for f in flags if not f.endswith("=")}
    out = []
    skip = False
    for arg in argv:
        if skip:
            skip = False
            continue
        key = arg.split("=", 1)[0]
        if key in value_flags:
            skip = "=" not in arg
            continue
        if key in bare_flags:
            continue
        out.append(arg)
    return out


class Launcher(Logger):
    def __init__(self, workflow=None, mode=None, coordinator_address=None,
                 num_processes=None, process_id=None, mesh_axes=None,
                 web_status_port=None, graphics_endpoint=None, fsdp=False,
                 **kwargs):
        super(Launcher, self).__init__(**kwargs)
        self.workflow = workflow
        self.coordinator_address = (coordinator_address or
                                    os.environ.get("VELES_TPU_COORDINATOR"))
        self.num_processes = num_processes or int(
            os.environ.get("VELES_TPU_NUM_PROCESSES", "1"))
        self.process_id = (process_id if process_id is not None else
                           int(os.environ.get("VELES_TPU_PROCESS_ID", "0")))
        if mode is None:
            mode = ("spmd" if (self.coordinator_address or
                               self.num_processes > 1) else "standalone")
        self.mode = mode
        self.mesh_axes = mesh_axes
        self.fsdp = fsdp
        self.mesh_config = None
        self.web_status_port = web_status_port
        self.graphics_endpoint = graphics_endpoint
        self.web_server = None
        self.graphics_server = None
        self._initialized = False

    # ------------------------------------------------------------ identity
    @property
    def is_standalone(self):
        return self.mode == "standalone"

    @property
    def is_master(self):
        """Process 0 owns snapshots/dashboards (ref master duties)."""
        import jax
        return jax.process_index() == 0

    @property
    def is_slave(self):
        return not self.is_master

    # ----------------------------------------------------------- lifecycle
    def _announce_backend(self, wanted):
        """Log the device the run landed on, and refuse to go on when
        ``engine.backend`` (``--backend``) names another: with "auto"
        jax quietly picks the CPU on a machine with no chip, which is
        right for tests and never what a run that asked for a TPU
        means."""
        import jax
        dev = jax.devices()
        self.info("jax %s on platform %s: device_kind %r, %d device(s)",
                  jax.__version__, dev[0].platform, dev[0].device_kind,
                  len(dev))
        if str(wanted) not in ("auto", dev[0].platform):
            raise RuntimeError(
                "engine.backend=%r but jax found platform %r (%s) — "
                "refusing to run on another device"
                % (wanted, dev[0].platform, dev[0].device_kind))

    def initialize(self, **kwargs):
        import jax
        if self.mode == "spmd" and self.num_processes > 1:
            from veles_tpu.compile_cache import _cpu_backend
            if _cpu_backend():
                # multi-process SPMD on the CPU backend (emulated pods,
                # tests, the pod-chaos gate) needs an explicit CPU
                # collectives implementation — without it every
                # cross-process collective dies with "Multiprocess
                # computations aren't implemented on the CPU backend".
                # Must land before the backend initializes; harmless to
                # set again on re-entry, no-op for TPU/GPU platforms.
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
            self.info("jax.distributed.initialize(%s, %d, %d)",
                      self.coordinator_address, self.num_processes,
                      self.process_id)
            jax.distributed.initialize(
                coordinator_address=self.coordinator_address,
                num_processes=self.num_processes,
                process_id=self.process_id)
        from veles_tpu.config import root
        self._announce_backend(root.common.engine.get("backend", "auto"))
        if self.mesh_axes:
            axes = self.mesh_axes
            if root.common.pod.get("elastic_mesh", False) or \
                    os.environ.get("VELES_TPU_ELASTIC_MESH") == "1":
                # elastic pods (services.podmaster) respawn workers on
                # whatever hosts survive: the mesh must be built from
                # the LIVE device set, not the configured topology — a
                # fixed data axis rescales, model/seq/... axes must fit
                from veles_tpu.parallel.mesh import fit_axes_to_devices
                import jax as _jax
                fitted = fit_axes_to_devices(axes,
                                             _jax.device_count())
                if fitted != dict(axes):
                    self.info("elastic mesh: %s -> %s (%d live "
                              "devices)", dict(axes), fitted,
                              _jax.device_count())
                    telemetry.flight.record(
                        "mesh.refit", configured=dict(axes),
                        live=fitted, devices=_jax.device_count())
                    # kernel-autotuner winners are keyed by mesh
                    # topology: the configured (full-size) entries are
                    # invalidated so the degraded pod RE-TUNES for its
                    # survivor mesh instead of inheriting block sizes
                    # measured at full size (docs/perf.md "Autotuning")
                    try:
                        from veles_tpu import tuner as _tuner
                        _tuner.on_mesh_refit(dict(axes), fitted)
                    except Exception:  # noqa: BLE001 — advisory
                        pass
                axes = fitted
            try:
                from veles_tpu import tuner as _tuner
                _tuner.set_ambient_mesh(axes)
            except Exception:  # noqa: BLE001 — advisory
                pass
            self.mesh_config = MeshConfig(make_mesh(axes),
                                          fsdp=self.fsdp)
            if self.fsdp and self.mesh_config.data_size <= 1:
                self.warning("--fsdp has no effect: the mesh has no "
                             "data axis larger than 1 (got %s)",
                             dict(self.mesh_config.mesh.shape))
        elif self.fsdp:
            self.warning("--fsdp ignored: no --mesh given (parameters "
                         "shard over the mesh's data axis)")
        if jax.process_count() > 1 and self.workflow is not None:
            self._verify_checksum()
        if self.is_master:
            self._launch_services()
        # services are live from here on: a failure anywhere below
        # (crash-handler install, workflow.initialize raising, even the
        # telemetry gauge) must tear them down before re-raising, or the
        # web-status/graphics daemon threads leak — boot() and the CLI
        # rely on this rather than wrapping initialize() themselves
        try:
            self._install_blackbox()
            if self.workflow is not None:
                trainer = getattr(self.workflow, "trainer", None)
                # only trainers that understand meshes (StagedTrainer) —
                # Kohonen/RBM trainers have no mesh_config attribute
                if self.mesh_config is not None and trainer is not None:
                    if not hasattr(trainer, "mesh_config"):
                        self.warning("--mesh ignored: %s does not support "
                                     "SPMD meshes", type(trainer).__name__)
                    elif trainer.mesh_config is None:
                        trainer.mesh_config = self.mesh_config
                # the trainer will row-shard the dataset: the loader must
                # not materialize a single-device replica first (the
                # workflow constructor handles this when it got
                # mesh_config directly; this covers the --mesh CLI path
                # where the mesh is assigned here, before any unit
                # initializes)
                mc = getattr(trainer, "mesh_config", None)
                loader = getattr(self.workflow, "loader", None)
                if (mc is not None and loader is not None
                        and getattr(trainer, "dataset_placement", None)
                        == "shard" and mc.data_size > 1
                        and getattr(loader, "on_device", None) is True):
                    loader.on_device = "defer"
                # initialization is where first-compiles land: span it so
                # the metrics JSONL attributes that wall time correctly
                # (and the TraceAnnotation names it in a device capture)
                with telemetry.span("workflow.initialize", emit=True,
                                    workflow=self.workflow.name):
                    self.workflow.initialize(**kwargs)
            telemetry.registry.gauge(
                "veles_launcher_info",
                "constant 1; run topology rides the labels",
                ("mode", "processes")).set(
                1, mode=self.mode, processes=self.num_processes)
            # the watchdog arms only AFTER initialize returns: its
            # progress clock must not start against first compiles,
            # which routinely exceed any sane hang window (runtime
            # recompiles keep feeding it via the compile listeners)
            self._arm_health()
        except BaseException as e:
            telemetry.flight.record(
                "launcher.initialize_failed",
                error=type(e).__name__, message=str(e))
            self.stop()
            raise
        self._initialized = True

    def _install_blackbox(self):
        """Install the crash-forensics hooks (telemetry.health) — live
        BEFORE workflow.initialize so a crash during initialization
        still leaves a black box.  The watchdog/heartbeat arm later
        (`_arm_health`), once initialization's first compiles are paid;
        tests and plain standalone runs pay nothing (docs/services.md
        "Black box")."""
        from veles_tpu.telemetry import health
        health.install(mode=self.mode, workflow=self.workflow)

    def _arm_health(self):
        """Arm the hang watchdog and the multi-host heartbeat/desync
        check: spmd runs by default, standalone only when the config
        asks explicitly."""
        from veles_tpu.config import root
        from veles_tpu.telemetry import health
        # None = unset; an EXPLICIT watchdog_seconds=0 (--watchdog 0)
        # disarms even spmd, where unset defaults to the spmd window
        window = root.common.blackbox.get("watchdog_seconds", None)
        if window is None and self.mode == "spmd":
            window = root.common.blackbox.get("spmd_watchdog_seconds",
                                              300)
        if window:
            health.arm_watchdog(window)
        if self.mode == "spmd" and self.num_processes > 1:
            health.enable_multihost()

    def _verify_checksum(self):
        """Every process must run the same workflow code (ref the per-file
        SHA1 handshake check, veles/workflow.py:847 + server.py:478) —
        a silently divergent binary would produce corrupt collectives."""
        import numpy as np
        from jax.experimental import multihost_utils
        digest = np.frombuffer(
            bytes.fromhex(self.workflow.checksum()), np.uint8)
        gathered = np.asarray(multihost_utils.process_allgather(digest))
        if not (gathered == digest[None, :]).all():
            raise RuntimeError(
                "workflow checksum mismatch across processes — every "
                "host must run identical workflow code")
        self.debug("workflow checksum verified across %d processes",
                   gathered.shape[0] if gathered.ndim > 1 else 1)

    def _launch_services(self):
        if self.web_status_port is not None:
            from veles_tpu.services.web_status import WebStatusServer
            self.web_server = WebStatusServer(port=self.web_status_port)
            if self.workflow is not None:
                self.web_server.register(self.workflow)
            self.web_server.start()
        if self.graphics_endpoint is not None:
            from veles_tpu.services.graphics import GraphicsServer
            self.graphics_server = GraphicsServer(
                endpoint=self.graphics_endpoint).start()

    def run(self):
        if not self._initialized:
            raise RuntimeError("Launcher.run() before initialize()")
        try:
            self.workflow.run()
        finally:
            self.stop()

    def boot(self, **kwargs):
        """initialize() + run() (ref launcher.py:573)."""
        self.initialize(**kwargs)
        self.run()

    def stop(self):
        """Idempotent — run() calls it in its finally and the CLI calls it
        again on the way out."""
        from veles_tpu.telemetry import health
        health.disarm_watchdog()
        if self.graphics_server is not None:
            self.graphics_server.stop()
            self.graphics_server = None
        if self.web_server is not None:
            self.web_server.stop()
            self.web_server = None
