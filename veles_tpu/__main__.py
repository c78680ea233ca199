"""CLI entry point (ref: veles/__main__.py — `python -m veles_tpu
workflow.py [config.py]`).

Keeps the reference's module contract (__main__.py:799-818): the workflow
file defines ``run(load, main)`` and calls
``load(WorkflowClass, **kwargs)`` to construct (or snapshot-resume) the
workflow, then ``main(**kwargs)`` to initialize + run it.  Config files
are Python executed with ``root`` in scope, mutating the global config
tree (ref _apply_config, __main__.py:426-472); ``--config-list`` inline
statements layer on top."""

import argparse
import json
import runpy
import sys

from veles_tpu import prng
from veles_tpu.config import root
from veles_tpu.logger import setup_logging


def _death_probability(value):
    """argparse type for --death-probability: [0, 1).  P >= 1 would
    crash before the first unit ever runs — the supervisor drill would
    spin forever with zero progress; negative P silently disables it."""
    p = float(value)
    if not 0.0 <= p < 1.0:
        raise argparse.ArgumentTypeError(
            "death probability must be in [0, 1) — P >= 1 dies before "
            "any unit runs, so a restarting supervisor never progresses")
    return p


class Main(object):
    def __init__(self, argv=None):
        self.argv = argv if argv is not None else sys.argv[1:]
        self.workflow = None
        self._interactive_session = None

    def parse(self):
        return self._build_parser().parse_args(self.argv)

    def _build_parser(self):
        """The full CLI parser (also consumed by
        scripts.generate_frontend to emit the HTML command composer)."""
        p = argparse.ArgumentParser(
            prog="veles_tpu",
            description="TPU-native deep-learning platform")
        p.add_argument("workflow", help="workflow .py file defining "
                       "run(load, main)")
        p.add_argument("config", nargs="?", help="config .py file "
                       "executed with `root` in scope")
        p.add_argument("--config-list", nargs="*", default=[],
                       help="inline config statements, e.g. "
                       "'root.mnist.lr=0.1'")
        p.add_argument("--random-seed", type=int, default=None)
        p.add_argument("--snapshot", default=None,
                       help="resume from a snapshot file, or 'auto' to "
                       "resolve <workflow>_current in the snapshot dir "
                       "(fresh start when absent — the restart-on-failure "
                       "idiom; ref _current symlink snapshotter.py:397-409)")
        p.add_argument("--warm-start", default=None, metavar="SNAPSHOT",
                       help="fine-tuning initializer: copy params whose "
                       "layer/param names and shapes match this snapshot "
                       "(architecture changes tolerated — mismatches stay "
                       "freshly initialized; optimizer/loader/PRNG state "
                       "is NOT restored). Composes with --snapshot auto: "
                       "a found checkpoint wins, so preemption restarts "
                       "keep fine-tuning progress")
        p.add_argument("--snapshot-every", type=int, default=None,
                       metavar="N", help="checkpoint every N epochs "
                       "(injects a snapshotter into StandardWorkflow runs; "
                       "pairs with --snapshot auto for preemption-safe "
                       "training)")
        p.add_argument("--supervise", action="store_true",
                       help="run the training command under the respawn "
                       "supervisor (services.supervisor): the run "
                       "executes as a child process that is respawned "
                       "after SIGKILL/SIGTERM/crashes with exponential "
                       "backoff and crash-loop detection, resuming via "
                       "--snapshot auto (added if absent) — the paper's "
                       "Launcher role for single-host training "
                       "(docs/distributed_training.md)")
        p.add_argument("--allow-remote-snapshot", action="store_true",
                       help="opt in to importing --snapshot from an "
                       "http(s) URL (pickle import runs code)")
        p.add_argument("--snapshot-sha256", default=None,
                       help="expected sha256 of a remote --snapshot")
        p.add_argument("--test", action="store_true",
                       help="skip training; run forward on the loader's "
                       "test/validation set")
        p.add_argument("--lint", action="store_true",
                       help="build the workflow, run the static "
                       "analyzers (veles_tpu.analysis: graph linter + "
                       "jit-staging auditor; with --mesh also the "
                       "VS2xx/VM3xx sharding & memory audit, which "
                       "initializes the workflow on a virtual CPU "
                       "mesh) and exit non-zero on error findings — "
                       "no training, no compute dispatch")
        p.add_argument("--numerics", action="store_true",
                       help="with --lint: initialize the workflow "
                       "(params allocate, no step dispatches) so the "
                       "VN4xx/VR5xx numerics & determinism audit can "
                       "trace the real staged train step; composes "
                       "with --mesh")
        p.add_argument("--serve-max-len", type=int, default=16,
                       metavar="T",
                       help="with --lint --serve: sequence budget the "
                       "audited generators are built with (default "
                       "16)")
        p.add_argument("--concurrency", action="store_true",
                       help="with --lint: add the VT8xx concurrency "
                       "lint (pure AST scan of veles_tpu/services)")
        p.add_argument("--all", action="store_true", dest="lint_all",
                       help="with --lint: add every registered AST "
                       "family (VT8xx concurrency, VW9xx protocol, "
                       "VC95x config/telemetry, VK10xx serialized "
                       "state, VB11xx host determinism) to the "
                       "workflow families — one merged report, one "
                       "exit gate (identical to veles-tpu-lint --all)")
        p.add_argument("--vmem-kib", type=float, default=None,
                       metavar="KiB",
                       help="with --lint: per-core VMEM budget for the "
                       "VP602 Pallas kernel-footprint rule (default "
                       "16384, ~16 MiB)")
        p.add_argument("--fail-on", choices=("error", "warning"),
                       default="error", metavar="{error,warning}",
                       help="with --lint: severity threshold for the "
                       "non-zero exit (exit 0 = below threshold, 1 = "
                       "reached — identical semantics to "
                       "veles-tpu-lint)")
        p.add_argument("--result-file", default=None,
                       help="write gather_results() JSON here")
        p.add_argument("--export-dtype", default="float32",
                       choices=("float32", "float16", "int8"),
                       help="weight storage dtype for --export "
                       "(float16 halves the package, int8 quarters it "
                       "with per-channel scales; the native runtime "
                       "widens to f32 on load)")
        p.add_argument("--export", default=None,
                       help="export trained model package to this path")
        p.add_argument("--export-stablehlo", default=None, metavar="PATH",
                       help="export the jitted forward as a portable "
                       "StableHLO artifact (+params) runnable on any "
                       "XLA backend without the model code")
        p.add_argument("--export-lora", default=None, metavar="PATH",
                       help="export ONLY the LoRA adapters as a small "
                       "package (adapters.npz + base-model sha256 "
                       "lineage) — a rank-8 fine-tune of a 124M base "
                       "ships ~MBs instead of the full model")
        p.add_argument("--serve", type=int, nargs="?", const=-1,
                       default=None, metavar="PORT",
                       help="after training, serve the model over REST;"
                       " with --lint (port optional): run the VD7xx "
                       "decode-path audit over the serving engine's "
                       "tick + prefill pass instead — abstract traces "
                       "only, nothing serves")
        p.add_argument("--generate", default=None,
                       metavar="PROMPT[:MAX_NEW]",
                       help="after training a causal LM, greedily decode "
                       "MAX_NEW (default 32) byte tokens from PROMPT and "
                       "print the result")
        p.add_argument("--web-status", type=int, default=None,
                       metavar="PORT", help="launch the status dashboard")
        p.add_argument("--backend", default=None,
                       help="cpu|tpu|<platform> override")
        p.add_argument("--mesh", default=None, metavar="AXES",
                       help="device mesh for SPMD training, e.g. "
                       "'data=4,model=2' (-1 = all remaining devices); "
                       "ref launcher node specs -n host/0:0x3")
        p.add_argument("--fsdp", action="store_true",
                       help="fully shard parameters and optimizer state "
                       "over the data axis (ZeRO-3 style; composes with "
                       "--mesh model= tensor parallelism)")
        p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                       help="jax.distributed coordinator address "
                       "(multi-host SPMD; ref master -l flag)")
        p.add_argument("--num-processes", type=int, default=None,
                       help="total processes in the multi-host job")
        p.add_argument("--process-id", type=int, default=None,
                       help="this process's index (ref slave -m identity)")
        p.add_argument("--optimize", default=None, metavar="SIZE[:GENS]",
                       help="genetic hyperparameter search over Range() "
                       "config leaves: population SIZE, GENS generations "
                       "(ref veles --optimize, __main__.py:334-345)")
        p.add_argument("--optimize-workers", default="1",
                       help="concurrent fitness evaluations (each is its "
                       "own training subprocess; >1 pins children to "
                       "cpu).  'N@HOST:PORT' additionally serves the "
                       "chromosome queue over HTTP so remote "
                       "--optimize-worker processes on other hosts pull "
                       "evaluations (N local evaluators share the queue; "
                       "N=0 = remote-only — the run then waits for "
                       "workers to connect).  Ref: distributed GA "
                       "fitness, genetics/optimization_workflow.py:"
                       "181-216")
        p.add_argument("--optimize-worker", default=None,
                       metavar="HOST:PORT",
                       help="run as a fitness worker: pull chromosome "
                       "jobs from a coordinator's --optimize-workers "
                       "queue, train this workflow locally per job, "
                       "post the fitness back; exits when the "
                       "coordinator finishes (ref: the slave side of "
                       "the GA job protocol)")
        p.add_argument("--optimize-encoding", default="float",
                       choices=("float", "gray"),
                       help="chromosome encoding: float vector or the "
                       "reference's gray-code binary genome")
        p.add_argument("--ensemble-train", default=None, metavar="N:RATIO",
                       help="train N instances on random train subsets of "
                       "RATIO (ref ensemble/model_workflow.py:137)")
        p.add_argument("--ensemble-workers", type=int, default=1,
                       help="concurrent member trainings (>1 pins "
                       "children to cpu)")
        p.add_argument("--ensemble-test", default=None,
                       metavar="RESULTS.json",
                       help="aggregate the members from an "
                       "--ensemble-train results file: mean-probability "
                       "vote on the eval set (ref --ensemble-test)")
        p.add_argument("--interactive", action="store_true",
                       help="drop into an IPython REPL (fallback: "
                       "code.interact) after constructing the workflow; "
                       "training runs in a background scheduler thread "
                       "so the live workflow stays inspectable — "
                       "wf.stop() / status() / weights(layer) from the "
                       "prompt (ref Main(interactive=True), "
                       "veles/__main__.py:380-394 + the reactor thread, "
                       "launcher.py:556-562)")
        p.add_argument("--manhole", default=None, metavar="SOCKET",
                       help="attachable debug REPL on a unix socket "
                       "(`socat - UNIX-CONNECT:SOCKET`; ref the bundled "
                       "manhole, veles/external/)")
        p.add_argument("--log-db", default=None, metavar="SQLITE",
                       help="duplicate every log record into this "
                       "sqlite file keyed by a per-run session id — "
                       "the cross-run log store behind the dashboard's "
                       "/api/logs browser (ref the Mongo log "
                       "duplication, veles/logger.py:292-331)")
        p.add_argument("--event-log", default=None, metavar="PATH",
                       help="append structured trace events as JSONL "
                       "(ref the Mongo event timeline, logger.py:264-289)")
        p.add_argument("--metrics-out", default=None,
                       metavar="FILE.jsonl",
                       help="stream telemetry records (workflow/unit/"
                       "step spans, compile counters, device-memory "
                       "gauges, predicted-vs-measured MFU) to this "
                       "JSON-lines file, with the final metric state "
                       "dumped at exit — summarize with "
                       "veles-tpu-metrics (docs/services.md)")
        p.add_argument("--steps-per-dispatch", type=int, default=None,
                       metavar="K",
                       help="fuse K minibatch steps into one device "
                       "dispatch (lax.scan inside the jitted sweep) — "
                       "amortizes host-to-device dispatch latency for "
                       "small models and remote TPUs; numerically "
                       "identical to per-step execution")
        p.add_argument("--death-probability", type=_death_probability,
                       default=0.0, metavar="P",
                       help="fault injection: per-unit-run probability "
                       "of a sudden checkpoint-less process crash "
                       "(exit 1) — drills the checkpoint-restart "
                       "elasticity path under a restarting supervisor "
                       "(ref --slave-death-probability)")
        p.add_argument("--watchdog", type=float, default=None,
                       metavar="SECONDS",
                       help="arm the hang watchdog: when no unit/step "
                       "progress is observed for this many seconds, "
                       "the flight record + all-thread stacks are "
                       "dumped to an artifacts/crashdump-* directory "
                       "(the run is NOT killed; read dumps with "
                       "veles-tpu-blackbox).  Default: off standalone, "
                       "300 s in spmd mode")
        p.add_argument("--sentinel", choices=("on", "off"), default=None,
                       help="the numeric-fault sentinel "
                       "(services.sentinel; default on): in-jit health "
                       "probes on every staged train step — "
                       "loss/grad-norm finiteness, EWMA loss-spike "
                       "z-score, update-norm explosion — with "
                       "skip-update, automatic rollback to the last "
                       "healthy commit + exact replay, and a "
                       "numerics:<kind> give-up class; 'off' sets "
                       "root.common.sentinel.enabled=False "
                       "(docs/distributed_training.md \"Numeric-fault "
                       "survival\")")
        p.add_argument("--sync-run", action="store_true",
                       help="block on the device after every trainer step "
                       "for honest per-unit timing (ref --sync-run, "
                       "accelerated_units.py:186-193)")
        p.add_argument("--profile", default=None, metavar="DIR",
                       help="capture a jax/xplane profiler trace of the "
                       "run into DIR (view with tensorboard or xprof; "
                       "the TPU equivalent of the reference's per-unit "
                       "timing + event timeline)")
        p.add_argument("--verbose", "-v", action="count", default=0)
        return p

    def run(self):
        args = self.parse()
        if args.warm_start and args.snapshot and args.snapshot != "auto":
            # fail before any side effect (config exec, model build).
            # --snapshot auto DOES compose: a found checkpoint wins so
            # preemption restarts keep fine-tuning progress
            raise SystemExit(
                "--warm-start with an explicit --snapshot path is "
                "ambiguous: exact resume restores everything, "
                "warm-start only matching params (use --snapshot auto "
                "to combine with restart-on-failure)")
        import logging
        setup_logging(logging.DEBUG if args.verbose else logging.INFO)
        if args.supervise:
            # the parent never touches jax/XLA: it only spawns, watches
            # and respawns the real training command
            return self._run_supervised(args)
        # --backend wins; root.common.engine.backend (seeded from
        # VELES_TPU_BACKEND) is the config-side fallback, "auto"
        # meaning "leave platform selection to jax".  The knob carries
        # the choice on: Launcher.initialize logs what jax found and
        # REFUSES to run when a named backend is not what it got — a
        # `--backend tpu` run never silently becomes a CPU run.
        if args.backend:
            root.common.engine.backend = args.backend
        backend = str(root.common.engine.get("backend", "auto"))
        if backend != "auto":
            # BEFORE compile_cache.enable(): its CPU-backend gate reads
            # jax_platforms, and `--backend cpu` without JAX_PLATFORMS
            # in the env would otherwise slip past it
            import jax
            jax.config.update("jax_platforms", backend)
        # persistent XLA compilation cache: re-runs of the same workflow
        # (and supervisor restarts after preemption) skip recompilation
        # — the TPU-era analogue of the reference's on-disk kernel cache
        from veles_tpu import compile_cache
        compile_cache.enable()
        if args.metrics_out:
            # before any jit: the compile listeners installed by
            # enable() must see the first compiles of the run
            from veles_tpu import telemetry
            telemetry.registry.open_sink(args.metrics_out,
                                         dump_at_exit=True)
        if not args.backend and args.lint:
            # linting never needs an accelerator (same guard as the
            # standalone veles-tpu-lint): module-level jax use in the
            # workflow file must not lock chips on a shared host.  A
            # --mesh lint additionally needs enough VIRTUAL cpu devices
            # to build the mesh for the sharding/memory audit
            from veles_tpu.analysis.cli import _force_cpu_devices
            _force_cpu_devices(self._parse_mesh(args.mesh)
                               if args.mesh else None)
        if args.random_seed is not None:
            prng.seed_all(args.random_seed)
        self._apply_config(args)
        if args.event_log:
            from veles_tpu.logger import events
            events.open_sink(args.event_log)
        if args.log_db:
            from veles_tpu.logger import duplicate_log_to
            duplicate_log_to(args.log_db)
            root.common.web.log_db = args.log_db
        if args.sync_run:
            root.common.engine.sync_run = True
        if args.watchdog is not None:
            root.common.blackbox.watchdog_seconds = args.watchdog
        if args.sentinel is not None:
            root.common.sentinel.enabled = args.sentinel == "on"
        if args.steps_per_dispatch is not None:
            root.common.engine.steps_per_dispatch = args.steps_per_dispatch

        if args.optimize_worker:
            return self._run_optimize_worker(args)
        if args.optimize:
            return self._run_optimize(args)
        if args.ensemble_train:
            return self._run_ensemble_train(args)

        web = None
        if args.web_status is not None:
            from veles_tpu.services.web_status import WebStatusServer
            web = WebStatusServer(port=args.web_status)
            web.start()
        self._web = web

        wf_globals = runpy.run_path(args.workflow, run_name="__veles__")
        if "run" not in wf_globals:
            raise SystemExit("%s does not define run(load, main)"
                             % args.workflow)

        def load(cls, **kwargs):
            if args.interactive:
                # ref Main(interactive=True): names defined in an
                # enclosing IPython session fill missing constructor
                # kwargs (explicit kwargs win; only names the workflow
                # class actually accepts are considered)
                import inspect
                try:
                    accepted = {k for k in inspect.signature(
                        cls.__init__).parameters if k != "self"}
                except (TypeError, ValueError):
                    accepted = set()
                for k, v in self._get_interactive_locals().items():
                    if k in accepted and k not in kwargs:
                        kwargs[k] = v
            if args.snapshot_every is not None:
                from veles_tpu.models.standard_workflow import \
                    StandardWorkflow
                if isinstance(cls, type) and \
                        issubclass(cls, StandardWorkflow):
                    kwargs.setdefault("snapshotter_config",
                                      {"interval": args.snapshot_every})
            self.workflow = cls(**kwargs)
            if args.lint:
                # static analysis never restores state: a snapshot
                # import is heavy, side-effectful I/O (pickle executes
                # code) that the --lint contract promises not to do
                self._pending_snapshot = None
                self._pending_warm_start = None
                if web is not None:
                    web.register(self.workflow)
                return self.workflow
            snapshot = args.snapshot
            auto = snapshot == "auto"
            if auto:
                snapshot = self._resolve_auto_snapshot(self.workflow)
            self._pending_warm_start = None
            self._pending_snapshot = None
            resume_src, resume_reason = None, "fresh"
            if snapshot:
                from veles_tpu.services.snapshotter import SnapshotterBase
                # initialize first so staged steps exist, then restore.
                # With --warm-start + --snapshot auto, a found
                # checkpoint WINS: the preemption-restart idiom (exit
                # 75 → same command) must keep fine-tuning progress,
                # not re-warm-start from the base snapshot.
                try:
                    self._pending_snapshot = SnapshotterBase.import_(
                        snapshot,
                        allow_remote=args.allow_remote_snapshot,
                        expected_sha256=args.snapshot_sha256)
                    import os as _os
                    resume_src = _os.path.realpath(snapshot)
                    resume_reason = "current" if auto else "explicit"
                except Exception as e:  # noqa: BLE001 — see below
                    if not auto or args.snapshot_sha256:
                        # an explicit path must fail loudly; and a
                        # sha256 pin names ONE exact artifact — falling
                        # back to a different (unpinned) file would
                        # defeat the integrity gate
                        raise
                    # restart-on-failure must never crash-loop on a
                    # torn checkpoint (a kill can land inside a
                    # checkpoint commit): step back to the next-newest
                    # complete one, else start fresh
                    self._pending_snapshot, resume_src = \
                        self._auto_snapshot_fallback(snapshot, e)
                    resume_reason = (
                        "fallback: %s failed to load (%s: %s)"
                        % (snapshot, type(e).__name__, e))
            if args.snapshot:
                # the resume decision joins the flight record: a
                # post-mortem must show WHAT was restored and WHY (the
                # crashdump distance to this event is the work lost)
                from veles_tpu.telemetry import flight
                flight.record(
                    "train.resume", snapshot=resume_src,
                    reason=resume_reason,
                    epoch=None if self._pending_snapshot is None
                    else self._pending_snapshot.get("epoch"))
            if self._pending_snapshot is None and args.warm_start:
                # no (loadable) checkpoint anywhere — the fine-tuning
                # initializer applies exactly as on a fresh start
                from veles_tpu.services.snapshotter import SnapshotterBase
                self._pending_warm_start = SnapshotterBase.import_(
                    args.warm_start,
                    allow_remote=args.allow_remote_snapshot,
                    expected_sha256=args.snapshot_sha256)
            if web is not None:
                web.register(self.workflow)
            return self.workflow

        def main(**kwargs):
            wf = self.workflow
            if args.lint:
                # static analysis only: skip initialize/run entirely (no
                # XLA dispatch) — the lint itself happens after run()
                # returns, so a workflow file that never calls main()
                # still gets analyzed
                return wf
            if args.death_probability:
                wf.death_probability = args.death_probability
            launcher = self._make_launcher(args, wf)
            launcher.initialize(**kwargs)
            # graceful preemption: TPU schedulers deliver SIGTERM with a
            # grace window before the pod goes away — checkpoint at the
            # next cycle boundary and exit 75 (EX_TEMPFAIL) so the
            # deploy units' auto-restart resumes via --snapshot auto
            import signal
            import threading
            prev_term = None
            if threading.current_thread() is threading.main_thread():
                def _on_sigterm(signum, frame):
                    # flag FIRST: stderr may be mid-write when the signal
                    # lands, and a reentrant-IO RuntimeError in print()
                    # must not lose the preemption request
                    wf.request_preempt()
                    # this handler REPLACES the launcher-installed
                    # health one — keep its black box: record + dump so
                    # a pod eviction leaves the same forensics as a
                    # crash (the run itself continues to the graceful
                    # preemption checkpoint)
                    from veles_tpu.telemetry import health as _health
                    _health.note_signal("SIGTERM")
                    try:
                        print("SIGTERM: graceful preemption — "
                              "checkpointing at the next cycle, then "
                              "exit 75", file=sys.stderr, flush=True)
                    except RuntimeError:
                        pass
                prev_term = signal.signal(signal.SIGTERM, _on_sigterm)
            manhole = None
            profiling = False
            # the try opens HERE, directly after the handler install, so
            # a failure anywhere below (manhole bind, snapshot restore,
            # warm start, profiler) still restores the previous SIGTERM
            # disposition in the finally
            try:
                if args.manhole:
                    from veles_tpu.interaction import Manhole
                    manhole = Manhole(args.manhole,
                                      scope={"wf": wf, "root": root,
                                             "launcher": launcher}).start()
                if self._pending_snapshot is not None:
                    wf.restore(self._pending_snapshot)
                elif getattr(self, "_pending_warm_start", None) is not None:
                    # polymorphic like wf.restore — custom workflows can
                    # override their warm-start semantics
                    wf.warm_start(self._pending_warm_start)
                if args.profile:
                    import jax
                    jax.profiler.start_trace(args.profile)
                    profiling = True
                if args.interactive:
                    # REPL mode: the scheduler runs in a background
                    # thread so the prompt stays live with the workflow
                    # mid-training (ref the reactor thread,
                    # launcher.py:556-562).  Cleanup is DEFERRED to the
                    # REPL exit in run() — the finally below must not
                    # tear the session down under the user.
                    thread = threading.Thread(
                        name="VelesScheduler", target=launcher.run,
                        daemon=True)
                    self._interactive_session = {
                        "launcher": launcher, "thread": thread,
                        "manhole": manhole, "prev_term": prev_term,
                        "profiling": profiling, "args": args}
                    thread.start()
                    return wf
                if args.test:
                    if root.common.serve.get("use_ema", False):
                        stats = wf.evaluate(use_ema=True)
                    else:
                        # keep the zero-argument signature working for
                        # custom workflow classes that predate use_ema
                        stats = wf.evaluate()
                    print(json.dumps({"test": stats}, indent=2))
                elif args.ensemble_test:
                    stats = self._ensemble_test(wf, args)
                    print(json.dumps({"ensemble_test": stats}))
                else:
                    launcher.run()
            finally:
                if getattr(self, "_interactive_session", None) is not None:
                    pass  # deferred: _finish_interactive at REPL exit
                else:
                    if prev_term is not None:
                        import signal
                        signal.signal(signal.SIGTERM, prev_term)
                    if profiling:
                        import jax
                        jax.profiler.stop_trace()
                        print("profiler trace -> %s" % args.profile)
                    if manhole is not None:
                        manhole.stop()
                    launcher.stop()
            if args.result_file:
                wf.write_results(args.result_file)
            wf.print_stats()
            return wf

        wf_globals["run"](load, main)
        wf = self.workflow

        if args.lint:
            if wf is None:
                raise SystemExit("%s never called load(WorkflowClass, "
                                 "...) — nothing to lint" % args.workflow)
            from veles_tpu.analysis import (format_findings,
                                            lint_workflow,
                                            threshold_reached)
            if args.mesh:
                # --lint --mesh: initialize under the virtual CPU mesh
                # so the VS2xx/VM3xx sharding/memory audit can lower the
                # real staged step (params allocate; no training step
                # ever dispatches — same contract as veles-tpu-lint)
                from veles_tpu.analysis.cli import _attach_mesh
                _attach_mesh(wf, self._parse_mesh(args.mesh), args.fsdp)
            elif args.numerics or args.serve is not None:
                # --lint --numerics / --serve: same contract, no mesh —
                # both auditors need real (constructed) staged state
                from veles_tpu.analysis.cli import _initialize_plain
                _initialize_plain(wf)
            findings = lint_workflow(wf, vmem_kib=args.vmem_kib)
            if args.serve is not None:
                # VD7xx: audit the serving engine this workflow would
                # serve — abstract traces of the decode tick, no
                # decode ever dispatches
                from veles_tpu.analysis import lint_serving
                trainer = getattr(wf, "trainer", None)
                if trainer is None:
                    raise SystemExit("--lint --serve: workflow has no "
                                     ".trainer unit to build a "
                                     "serving engine from")
                findings = findings + lint_serving(
                    trainer, args.serve_max_len,
                    vmem_kib=args.vmem_kib)
            if args.concurrency or args.lint_all:
                from veles_tpu.analysis import lint_concurrency
                findings = findings + lint_concurrency()
            if args.lint_all:
                # --lint --all: every registered AST family joins the
                # workflow families in one merged report/exit gate
                # (veles-tpu-lint --all parity)
                from veles_tpu.analysis import (lint_config,
                                                lint_determinism,
                                                lint_protocol,
                                                lint_state)
                findings = (findings + lint_protocol() + lint_config()
                            + lint_state() + lint_determinism())
            print(format_findings(findings))
            return 1 if threshold_reached(findings,
                                          args.fail_on) else 0

        if self._interactive_session is not None:
            try:
                self._repl(load, main)
            finally:
                self._finish_interactive()

        if wf is not None and getattr(wf, "preempted_", False):
            # 75 = EX_TEMPFAIL: "try again" — the deploy systemd/k8s
            # units restart the identical command line and
            # --snapshot auto picks up the preemption checkpoint
            print("preempted — exiting 75 for supervisor restart",
                  file=sys.stderr, flush=True)
            return 75

        if args.export and wf is not None:
            from veles_tpu.services.export import export_workflow
            export_workflow(wf, args.export, dtype=args.export_dtype)
            print("exported -> %s" % args.export)
        if args.export_stablehlo and wf is not None:
            from veles_tpu.services.export import export_stablehlo
            meta = export_stablehlo(wf, args.export_stablehlo)
            print("stablehlo (%s) -> %s"
                  % (",".join(meta["platforms"]), args.export_stablehlo))
        if args.export_lora and wf is not None:
            from veles_tpu.services.export import export_lora_adapters
            meta = export_lora_adapters(wf, args.export_lora)
            print("lora adapters (%s) -> %s"
                  % (",".join(meta["layers"]), args.export_lora))
        if args.generate is not None and wf is not None:
            self._generate(wf, args.generate)
        if args.serve is not None and wf is not None:
            self._serve(wf, args.serve)
        return 0

    @staticmethod
    def _get_interactive_locals():
        """Workflow-construction kwargs harvested from an enclosing
        IPython session, if any (ref veles/__main__.py:380-394: the
        interactive Main feeds notebook locals into load()).  Empty dict
        outside IPython."""
        try:
            from IPython.core.getipython import get_ipython
        except ImportError:
            return {}
        shell = get_ipython()
        if shell is None:
            return {}
        return {k: v for k, v in shell.user_ns.items()
                if k[:1] != "_" and k not in
                ("In", "Out", "exit", "quit", "get_ipython", "open")}

    def _repl(self, load, main):
        """The --interactive prompt: the scheduler thread is already
        running; expose the live workflow and lifecycle helpers (ref
        Main(interactive=True) + the background reactor thread,
        veles/__main__.py:380-394, launcher.py:556-562)."""
        session = self._interactive_session
        wf = self.workflow

        def stop():
            """Stop the running workflow and join the scheduler."""
            wf.stop()
            session["thread"].join(timeout=60)
            print("scheduler %s" % ("stopped" if not
                  session["thread"].is_alive() else "STILL RUNNING"))

        def status():
            """One-line liveness + progress summary."""
            alive = session["thread"].is_alive()
            parts = ["scheduler=%s" % ("running" if alive else "done")]
            loader = getattr(wf, "loader", None)
            if loader is not None:
                parts.append("epoch=%s" % getattr(
                    loader, "epoch_number", "?"))
            dec = getattr(wf, "decision", None)
            if dec is not None and getattr(dec, "min_validation_error",
                                           None) is not None:
                parts.append("best_err=%s" % dec.min_validation_error)
            print("  ".join(parts))
            return alive

        def weights(layer=None):
            """Live parameter tree: whole dict, or one layer's params
            as numpy (safe to call mid-training — jax arrays are
            immutable snapshots)."""
            import numpy as np
            params = getattr(getattr(wf, "trainer", None), "params", {})
            if layer is None:
                return params
            return {k: np.asarray(v) for k, v in params[layer].items()}

        ns = {"wf": wf, "root": root, "load": load, "main": main,
              "launcher": session["launcher"], "stop": stop,
              "status": status, "weights": weights, "veles_main": self}
        banner = ("veles_tpu interactive — training runs in a "
                  "background thread.\n  wf        live workflow\n"
                  "  status()  scheduler/epoch/best-error\n"
                  "  weights('layer')  live params as numpy\n"
                  "  stop()    stop training and join the scheduler\n"
                  "  root      config tree     exit to leave")
        import os
        if os.environ.get("VELES_PLAIN_REPL"):
            # deterministic prompt for drivers/tests and dumb terminals
            import code
            code.interact(banner=banner, local=ns)
            return
        try:
            from IPython.terminal.embed import InteractiveShellEmbed
            InteractiveShellEmbed(banner1=banner)(local_ns=ns)
        except ImportError:
            import code
            code.interact(banner=banner, local=ns)

    def _finish_interactive(self):
        """Deferred cleanup from main()'s skipped finally: stop the
        workflow, join the scheduler thread, restore the SIGTERM
        disposition, close the profiler/manhole, stop services."""
        session, self._interactive_session = self._interactive_session, None
        wf = self.workflow
        if wf is not None:
            wf.stop()
        session["thread"].join(timeout=60)
        if session["prev_term"] is not None:
            import signal
            signal.signal(signal.SIGTERM, session["prev_term"])
        if session["profiling"]:
            import jax
            jax.profiler.stop_trace()
            print("profiler trace -> %s" % session["args"].profile)
        if session["manhole"] is not None:
            session["manhole"].stop()
        session["launcher"].stop()
        if wf is not None:
            if session["args"].result_file:
                wf.write_results(session["args"].result_file)
            wf.print_stats()

    @staticmethod
    def _make_generator(wf, min_len=0):
        """Guarded LMGenerator construction shared by --serve and
        --generate: None unless the workflow is a causal-LM stack."""
        if not any(layer.type == "transformer_block" and
                   layer.cfg.get("causal") for layer in wf.trainer.layers):
            return None
        from veles_tpu.models.generate import LMGenerator
        t0 = (wf.trainer.layers[0].input_shape[0]
              if wf.trainer.layers[0].input_shape else 0)
        if any(l.cfg.get("rope") for l in wf.trainer.layers):
            t0 = max(t0, min_len)    # rope has no position-table bound
        # root.common.serve.lora_adapters=PATH grafts a --export-lora
        # package onto the (warm-started base) workflow BEFORE the
        # generator snapshots its serving params: serve a base
        # checkpoint + a tiny adapters file instead of a full adapted
        # model (sha256 lineage enforced; ...strict=False downgrades
        # a cross-base mismatch to a warning)
        adapters = root.common.serve.get("lora_adapters", None)
        if adapters:
            from veles_tpu.services.export import apply_lora_adapters
            meta = apply_lora_adapters(
                wf, adapters,
                strict=root.common.serve.get("lora_strict", True))
            import logging
            logging.getLogger("Main").info(
                "serving with LoRA adapters %s (layers: %s)",
                adapters, ",".join(meta["layers"]))
        cd = root.common.serve.get("cache_dtype", None)
        import numpy as np
        kwargs = dict(max_len=t0, cache_dtype=None if cd is None
                      else np.dtype(cd))
        try:
            gen = LMGenerator(wf.trainer, **kwargs)
        except ValueError:
            return None              # not a generate-shaped stack
        w = root.common.serve.get("weights", None)
        use_ema = root.common.serve.get("use_ema", False)
        if w is not None or use_ema:
            # the stack IS generate-shaped (probed above) — a failure
            # here is a configuration error (bad weights value, EMA not
            # tracked, TP×int8) and must surface, not silently disable
            # generation
            gen = LMGenerator(wf.trainer, weights=w, use_ema=use_ema,
                              **kwargs)
        return gen

    def _generate(self, wf, spec):
        """--generate 'PROMPT[:MAX_NEW]' — byte-level decode from the
        trained causal LM, printed to stdout."""
        prompt, _, n = spec.rpartition(":")
        if n.strip().isdigit() and prompt:
            max_new = int(n)
        else:                        # no numeric suffix: all is prompt
            prompt, max_new = spec, 32
        toks = list(prompt.encode("utf-8"))       # true byte-level
        gen = self._make_generator(wf, min_len=len(toks) + max_new)
        if gen is None:
            raise SystemExit("--generate needs a causal transformer LM "
                             "workflow (embedding ... transformer_block "
                             "... timestep_dense)")
        if len(toks) + max_new > gen.max_len:
            raise SystemExit(
                "--generate: prompt (%d bytes) + MAX_NEW (%d) exceeds "
                "the model's position limit %d — shorten one, or train "
                "with pos='rope' (no table bound)"
                % (len(toks), max_new, gen.max_len))
        out = gen.generate([toks], max_new=max_new)
        print("generated: %r" % bytes(
            t if 0 <= t < 256 else 63 for t in out[0].tolist()
        ).decode("utf-8", errors="replace"))

    def _apply_config(self, args):
        from veles_tpu.genetics.core import Range
        if args.config:
            scope = {"root": root, "Range": Range}
            with open(args.config) as f:
                exec(compile(f.read(), args.config, "exec"), scope)
        for stmt in args.config_list:
            exec(stmt, {"root": root, "Range": Range})

    def _run_supervised(self, args):
        """``--supervise``: respawn the identical command (minus the
        flag itself, plus ``--snapshot auto``) under the supervisor's
        backoff/crash-loop policy — the Veles Launcher role collapsed
        onto one host (docs/distributed_training.md "Preemption-safe
        training")."""
        if args.snapshot not in (None, "auto"):
            raise SystemExit(
                "--supervise drives restart-on-failure through "
                "--snapshot auto; an explicit --snapshot path would "
                "re-resume the SAME file after every respawn, losing "
                "all progress between restarts")
        # config must apply in the parent too: the supervisor reads its
        # own knobs plus the snapshot/blackbox dirs from the tree
        self._apply_config(args)
        from veles_tpu.services.supervisor import Supervisor
        child = [a for a in self.argv if a != "--supervise"]
        if args.snapshot is None:
            child += ["--snapshot", "auto"]
        if args.snapshot_every is None:
            print("[supervise] note: no --snapshot-every — unless the "
                  "workflow wires its own snapshotter, respawns will "
                  "restart from scratch", file=sys.stderr)
        # progress is watched on the CONFIG-TREE snapshot dir: a
        # workflow whose snapshotter_config names a different explicit
        # 'directory' still restarts correctly, but checkpoint commits
        # there won't reset the backoff/deterministic-bug counters —
        # point root.common.dirs.snapshots at it to get both
        sup = Supervisor(
            [sys.executable, "-m", "veles_tpu"] + child,
            blackbox_dir=root.common.blackbox.get("dir", "artifacts"),
            progress_paths=[root.common.dirs.get("snapshots",
                                                 "snapshots")])
        return sup.run()

    @staticmethod
    def _auto_snapshot_fallback(current, error):
        """--snapshot auto hit a torn/unloadable checkpoint: quarantine
        it (rename to ``*.corrupt`` so restarts stop re-trying it),
        then try the other snapshots of the same prefix, newest first;
        ``(None, None)`` (fresh start) when none load.  A supervisor
        restart loop must converge to training, never to a crash loop.

        :returns: ``(snapshot_dict_or_None, loaded_path_or_None)``."""
        import os

        from veles_tpu.services.snapshotter import (MANIFEST_SUFFIX,
                                                    SnapshotterBase)
        real = os.path.realpath(current)
        directory = os.path.dirname(real)
        prefix = os.path.basename(current).replace("_current", "")
        print("[auto-resume] %s failed to load (%s) — trying older "
              "checkpoints" % (real, error), file=sys.stderr)
        # quarantine only CORRUPTION-class failures: a transient
        # OSError (shared-storage hiccup) must not permanently demote
        # the newest good checkpoint — the next restart retries it
        if not isinstance(error, OSError) and os.path.exists(real):
            q = SnapshotterBase.quarantine(real)
            if q:
                print("[auto-resume] quarantined torn checkpoint -> %s"
                      % q, file=sys.stderr)
        candidates = sorted(
            (os.path.join(directory, n) for n in os.listdir(directory)
             # prefix + "_": the filename format is "<prefix>_<suffix>"
             # — a bare startswith would also match a DIFFERENT
             # workflow ("digits-mlp-big") sharing the snapshot dir
             if n.startswith(prefix + "_")
             and not n.endswith("_current")
             and not n.endswith(MANIFEST_SUFFIX)
             and not n.endswith(".corrupt")
             # .tmp leftovers are UNCOMMITTED writes (a kill between
             # dump and rename): a complete-looking one would resume
             # manifest-less past the integrity gate
             and ".tmp" not in n
             and os.path.join(directory, n) != real),
            key=os.path.getmtime, reverse=True)
        for cand in candidates:
            try:
                snap = SnapshotterBase.import_(cand)
            except Exception as e:  # noqa: BLE001 — keep stepping back
                print("[auto-resume] %s also failed (%s)" % (cand, e),
                      file=sys.stderr)
                if not isinstance(e, OSError):
                    q = SnapshotterBase.quarantine(cand)
                    if q:
                        print("[auto-resume] quarantined -> %s" % q,
                              file=sys.stderr)
                continue
            print("[auto-resume] recovered from %s" % cand,
                  file=sys.stderr)
            return snap, cand
        print("[auto-resume] no loadable checkpoint — fresh start",
              file=sys.stderr)
        return None, None

    @staticmethod
    def _resolve_auto_snapshot(wf):
        """--snapshot auto: follow <prefix>_current in the snapshot dir;
        absent → fresh start (so the same command line both starts and
        resumes a run — ref respawn semantics, veles/server.py:637-655
        mapped to checkpoint-restart)."""
        import os
        snap = getattr(wf, "snapshotter", None)
        directory = (snap.directory if snap is not None
                     else root.common.dirs.get("snapshots", "snapshots"))
        prefix = snap.prefix if snap is not None else wf.name
        current = os.path.join(directory, "%s_current" % prefix)
        if os.path.islink(current) and not os.path.exists(current):
            # dangling symlink (target deleted/renamed/never finalized):
            # used to silently read as "no checkpoint" and fresh-start
            # over real progress — surface it and let the fallback scan
            # pick the newest valid checkpoint instead
            try:
                target = os.readlink(current)
            except OSError:
                target = "?"
            print("[auto-resume] %s dangles (target %s is missing) — "
                  "falling back to the newest valid checkpoint"
                  % (current, target), file=sys.stderr)
            return current   # import_ fails -> _auto_snapshot_fallback
        if os.path.exists(current):
            print("[auto-resume] %s" % os.path.realpath(current),
                  file=sys.stderr)
            return current
        print("[auto-resume] no %s — fresh start" % current,
              file=sys.stderr)
        return None

    # ------------------------------------------------------------- launcher
    @staticmethod
    def _parse_mesh(spec):
        """'data=4,model=2' -> {'data': 4, 'model': 2} (ref device-spec
        grammar backends.py:299-308 / launcher -n node specs; 'DxM'
        shorthand also accepted — one parser, analysis.cli.parse_mesh)."""
        if not spec:
            return None
        from veles_tpu.analysis.cli import parse_mesh
        return parse_mesh(spec)

    def _make_launcher(self, args, wf):
        from veles_tpu.launcher import Launcher
        self.launcher = Launcher(
            workflow=wf, mesh_axes=self._parse_mesh(args.mesh),
            coordinator_address=args.coordinator,
            num_processes=args.num_processes, process_id=args.process_id,
            fsdp=args.fsdp)
        return self.launcher

    # -------------------------------------------------- meta: genetics / GA
    @staticmethod
    def _child_argv(args, extra_config, extra_flags, workers=1):
        """argv for a child training run: rebuilt from the parsed parent
        args (workflow/config/config-list/backend carry over; meta flags
        do not — ref Launcher.filter_argv forwarding, launcher.py:75).
        With concurrent workers the children are pinned to cpu HERE too:
        a forwarded --backend would override the JAX_PLATFORMS env pin
        inside the child and put N children on one accelerator."""
        argv = [sys.executable, "-m", "veles_tpu", args.workflow]
        if args.config:
            argv.append(args.config)
        config_list = list(args.config_list) + list(extra_config)
        if config_list:
            argv += ["--config-list"] + config_list
        if workers > 1:
            argv += ["--backend", "cpu"]
        elif args.backend:
            argv += ["--backend", args.backend]
        return argv + list(extra_flags)

    @staticmethod
    def _child_env(workers):
        import os
        env = dict(os.environ)
        if workers > 1:
            # concurrent children must not fight over one accelerator
            env["JAX_PLATFORMS"] = "cpu"
        return env

    #: watchdog for each child training run — a wedged backend must fail
    #: the evaluation, not hang the whole GA/ensemble (same reasoning as
    #: bench.py's per-phase watchdogs)
    @staticmethod
    def _child_timeout():
        import os
        return float(os.environ.get("VELES_TPU_CHILD_TIMEOUT", 1800))

    @staticmethod
    def _executor_map(workers):
        """Parallel map over training *subprocesses* (one eval per process
        — ref distributed GA fitness, genetics/optimization_workflow.py:
        181-216; threads only marshal argv/JSON, the work is in the
        children)."""
        if workers <= 1:
            return lambda f, xs: list(map(f, xs))

        def pmap(f, xs):
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(max_workers=workers) as pool:
                return list(pool.map(f, xs))
        return pmap

    def _evaluate_leaves(self, args, leaves, workers):
        """ONE fitness evaluation: full training subprocess with the
        chromosome's {dotted-path: value} overrides; --result-file
        best_metric (lower is better) becomes -fitness.  Shared by the
        local GA executor and the --optimize-worker loop."""
        import subprocess
        import tempfile

        overrides = ["root.%s=%r" % (p, v) for p, v in leaves.items()]
        seed_flags = ([] if args.random_seed is None
                      else ["--random-seed", str(args.random_seed)])
        with tempfile.NamedTemporaryFile("r", suffix=".json") as tmp:
            argv = self._child_argv(
                args, overrides,
                ["--result-file", tmp.name] + seed_flags,
                workers=workers)
            try:
                r = subprocess.run(
                    argv, capture_output=True, text=True,
                    timeout=self._child_timeout(),
                    env=self._child_env(workers))
            except subprocess.TimeoutExpired:
                print("[optimize] evaluation timed out", file=sys.stderr)
                return float("-inf")
            if r.returncode != 0:
                print("[optimize] evaluation failed: %s"
                      % r.stderr[-500:], file=sys.stderr)
                return float("-inf")
            metric = json.load(open(tmp.name)).get("best_metric")
        return float("-inf") if metric is None else -float(metric)

    @staticmethod
    def _parse_optimize_workers(spec):
        """'N' -> (N, None); 'N@HOST:PORT' -> (N, 'HOST:PORT')."""
        head, _, addr = str(spec).partition("@")
        try:
            n = int(head)
            if addr:
                int(addr.rpartition(":")[2])   # PORT must be numeric
        except ValueError:
            raise SystemExit("--optimize-workers: expected N or "
                             "N@HOST:PORT, got %r" % (spec,))
        return n, (addr or None)

    def _run_optimize_worker(self, args):
        """The slave side of the GA job protocol: pull chromosome jobs
        from the coordinator's queue, train locally, post fitness."""
        from veles_tpu.genetics.distributed import run_worker

        count = run_worker(
            args.optimize_worker,
            lambda leaves: self._evaluate_leaves(args, leaves, workers=1))
        print(json.dumps({"optimize_worker": {"evaluated": count}}))
        return 0

    def _run_optimize(self, args):
        """--optimize SIZE[:GENS] (ref veles/__main__.py:334-345): GA over
        every Range() leaf in the config tree; each fitness evaluation is
        a full training subprocess whose --result-file best_metric (lower
        is better) becomes -fitness.  With --optimize-workers N@HOST:PORT
        the evaluations additionally spread over remote --optimize-worker
        processes (ref distributed fitness,
        genetics/optimization_workflow.py:181-216)."""
        from veles_tpu.genetics.core import extract_ranges
        from veles_tpu.genetics.optimizer import GeneticsOptimizer

        n_workers, queue_addr = self._parse_optimize_workers(
            args.optimize_workers)
        head, _, tail = args.optimize.partition(":")
        size, generations = int(head), int(tail) if tail else 10
        cfg = root.as_dict()
        paths = extract_ranges(cfg)
        if not paths:
            raise SystemExit("--optimize: no Range() leaves in the config "
                             "tree — tag tunables like "
                             "root.x.lr = Range(0.01, 1.0)")

        def leaf(tree, path):
            for k in path:
                tree = tree[k]
            return tree

        def evaluate(config):
            return self._evaluate_leaves(
                args, {".".join(p): leaf(config, p) for p, _ in paths},
                workers=n_workers)

        queue = None
        executor_map = self._executor_map(n_workers)
        if queue_addr:
            from veles_tpu.genetics.distributed import FitnessQueue
            host, _, port = queue_addr.rpartition(":")
            # lease > child watchdog + margin: an evaluation that itself
            # times out must post its -inf BEFORE the lease expires, or
            # a second worker redundantly re-runs the doomed config
            queue = FitnessQueue(host or "0.0.0.0", int(port or 0),
                                 job_timeout=self._child_timeout() + 120)
            queue.start()
            print("[optimize] serving chromosome queue on %s:%d — "
                  "workers join with --optimize-worker HOST:%d"
                  % (queue.host, queue.port, queue.port),
                  file=sys.stderr)

            def executor_map(f, configs):  # noqa: F811 — queue mode
                return queue.map(
                    lambda leaves: self._evaluate_leaves(
                        args, leaves, workers=max(n_workers, 1)),
                    [{".".join(p): leaf(c, p) for p, _ in paths}
                     for c in configs],
                    local_workers=n_workers)

        try:
            opt = GeneticsOptimizer(
                cfg, evaluate, size=size, generations=generations,
                encoding=args.optimize_encoding,
                executor_map=executor_map)
            best = opt.run()
        finally:
            if queue is not None:
                queue.shutdown()
                # give polling workers a beat to read the done signal
                import time as _time
                _time.sleep(1.5)
                queue.stop()
        if opt.population.best.fitness == float("-inf"):
            print("--optimize: every fitness evaluation failed — no "
                  "usable result", file=sys.stderr)
            return 1
        result = {
            "optimize": {
                "best_config": {"root." + ".".join(p): leaf(best, p)
                                for p, _ in paths},
                "best_fitness": opt.population.best.fitness,
                "history": opt.history,
            }
        }
        print(json.dumps(result, indent=2))
        if args.result_file:
            with open(args.result_file, "w") as f:
                json.dump(result, f, indent=2)
        return 0

    # ------------------------------------------------------------ ensembles
    def _run_ensemble_train(self, args):
        """--ensemble-train N:RATIO (ref ensemble/model_workflow.py:137):
        N training subprocesses, each on a random train subset of RATIO
        and its own seed, each exporting a model package; aggregated
        results JSON feeds --ensemble-test."""
        import os
        import subprocess

        head, _, tail = args.ensemble_train.partition(":")
        n_models, ratio = int(head), float(tail) if tail else 0.8
        out_file = args.result_file or "ensemble_results.json"
        out_dir = os.path.abspath(
            os.path.splitext(out_file)[0] + "_members")
        os.makedirs(out_dir, exist_ok=True)

        def train_member(i):
            res = os.path.join(out_dir, "member_%02d.json" % i)
            pkg = os.path.join(out_dir, "member_%02d.zip" % i)
            seed = 1000 + i
            argv = self._child_argv(
                args,
                ["root.common.ensemble.instance=%d" % i,
                 "root.common.ensemble.train_ratio=%r" % ratio],
                ["--random-seed", str(seed),
                 "--result-file", res, "--export", pkg],
                workers=args.ensemble_workers)
            try:
                r = subprocess.run(
                    argv, capture_output=True, text=True,
                    timeout=self._child_timeout(),
                    env=self._child_env(args.ensemble_workers))
            except subprocess.TimeoutExpired:
                return {"instance": i, "seed": seed,
                        "error": "training timed out"}
            if r.returncode != 0:
                return {"instance": i, "seed": seed,
                        "error": r.stderr[-500:]}
            member = {"instance": i, "seed": seed, "package": pkg,
                      "train_ratio": ratio}
            member["result"] = json.load(open(res))
            return member

        members = self._executor_map(args.ensemble_workers)(
            train_member, range(n_models))
        failed = [m for m in members if "error" in m]
        result = {"members": members, "n_models": n_models,
                  "train_ratio": ratio}
        with open(out_file, "w") as f:
            json.dump(result, f, indent=2)
        print(json.dumps({"ensemble_train": {
            "n_models": n_models, "failed": len(failed),
            "results_file": out_file}}, indent=2))
        return 1 if failed else 0

    def _ensemble_test(self, wf, args):
        """Mean-probability vote over the members' exported packages on
        the workflow's eval samples (ref EnsembleTestWorkflow averaging,
        ensemble/test_workflow.py:102)."""
        import numpy as np

        from veles_tpu.loader.base import TEST, VALID
        from veles_tpu.services.export import import_workflow

        loader = wf.loader
        if loader.carries_data:
            raise SystemExit("--ensemble-test needs an index loader with "
                             "an HBM/host-resident eval set")
        from veles_tpu.ops.losses import get_loss
        if get_loss(wf.trainer.loss)[1] != "class" or loader.labels is None:
            raise SystemExit("--ensemble-test is a mean-probability vote — "
                             "it needs a classification workflow with "
                             "labels")
        members = json.load(open(args.ensemble_test))["members"]
        members = [m for m in members if "package" in m]
        if not members:
            raise SystemExit("no successfully trained members in %s"
                             % args.ensemble_test)
        # eval span: validation if present, else test
        cls = VALID if loader.class_lengths[VALID] else TEST
        start = 0 if cls == TEST else loader.class_offsets[TEST]
        end = loader.class_offsets[cls]
        if end == start:
            raise SystemExit("--ensemble-test: the loader has no "
                             "test/validation samples to vote on")
        x = np.asarray(loader.data)[start:end]
        labels = np.asarray(loader.labels)[start:end]
        fwd = wf.forward_fn()
        probs = None
        for m in members:
            manifest, arrays = import_workflow(m["package"])
            from veles_tpu.services.export import unflatten_params
            params = {
                u["name"]: unflatten_params(
                    {p: arrays[f] for p, f in u["arrays"].items()})
                for u in manifest["units"] if u["arrays"]}
            p = np.asarray(fwd(params, x))
            probs = p if probs is None else probs + p
        pred = (probs / len(members)).argmax(axis=1)
        error = float((pred != labels).mean())
        return {"n_members": len(members), "n_samples": int(end - start),
                "error": error}

    def _serve(self, wf, port):
        import numpy as np

        from veles_tpu.services.restful import RESTfulAPI
        fwd = wf.forward_fn()
        # root.common.serve.use_ema=True serves the Polyak/EMA-averaged
        # weights (train with gd_defaults={'ema_decay': ...})
        params = wf.trainer.serve_params(
            root.common.serve.get("use_ema", False))
        # root.common.serve.cache_dtype='bfloat16' halves the serve-time
        # KV-cache memory ('int8' quarters it — and feeds the fused
        # paged decode kernel's quantized-pool variant);
        # root.common.serve.weights='int8' quantizes the serving weights
        # (W8A8-dynamic, ops.quant) for ~half the decode HBM traffic,
        # 'w4a8' nibble-packs them to int4 payloads (quarter bytes);
        # root.common.serve.batch_window_ms>0 coalesces concurrent
        # generate requests into shared device calls;
        # root.common.serve.continuous_slots>0 runs the in-flight
        # continuous-batching engine instead (docs/services.md)
        api = RESTfulAPI(lambda x: np.asarray(fwd(params, x)),
                         wf.trainer.layers[0].input_shape, port=port,
                         generator=self._make_generator(wf),
                         batch_window=float(
                             root.common.serve.get("batch_window_ms", 0))
                         / 1e3,
                         max_batch=int(
                             root.common.serve.get("max_batch", 8)),
                         continuous_slots=int(
                             root.common.serve.get("continuous_slots",
                                                   0)),
                         # root.common.serve.paged_block>0: block-table
                         # KV pool of root.common.serve.pool_tokens —
                         # memory scales with active tokens, admission
                         # backpressures on pool exhaustion; "auto"/-1
                         # = paged with the pool block resolved through
                         # config > the kernel autotuner > default
                         # (generate.parse_paged_block grammar)
                         paged_block=root.common.serve.get(
                             "paged_block", 0),
                         pool_tokens=root.common.serve.get(
                             "pool_tokens", None),
                         # prefix_cache: concurrent requests sharing a
                         # prompt prefix share its KV blocks (the
                         # system-prompt case pays for it once)
                         prefix_cache=bool(root.common.serve.get(
                             "prefix_cache", False)),
                         # speculative_k>0: n-gram speculative ticks in
                         # the dense slot pool (exact decode semantics)
                         speculative_k=int(root.common.serve.get(
                             "speculative_k", 0)),
                         # K>1 fuses K engine ticks per device dispatch
                         # (for when a dispatch costs more than a tick)
                         ticks_per_dispatch=int(root.common.serve.get(
                             "ticks_per_dispatch", 1)))
        # root.common.serve.prefill_segment>0: segmented prefill
        # admission — long prompts prefill in bounded chunk passes
        # interleaved with decode ticks, so one admission can't stall
        # every in-flight stream (the engine reads the knob itself;
        # docs/services.md "Disaggregated prefill")
        api.start()
        if getattr(self, "_web", None) is not None:
            # the dashboard's serving panel shows the slot pool's SLO
            # surface (queue depth, p50/p99 latency) live
            self._web.register_serving(api)
        # SIGTERM (preemption, `kill`, a fleet operator) drains
        # gracefully: stop admission (503 + Retry-After), finish every
        # in-flight request, exit 0 — the same lifecycle a fleet
        # replica walks (docs/services.md "Fleet serving"), instead of
        # the training path's crashdump-and-die
        from veles_tpu.services.restful import (announce_ready,
                                                install_sigterm_drain)
        install_sigterm_drain(api)
        # under a pod agent (VELES_TPU_REPLICA_ANNOUNCE set) this
        # prints the fleet READY handshake so the agent can register
        # the bound port with the router — any --serve command is a
        # fleet replica (docs/services.md "Autoscaling fleet")
        announce_ready(api)
        print("REST serving on port %d; Ctrl-C to stop, SIGTERM to "
              "drain" % api.port)
        try:
            import time
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            api.stop()


def __run__():
    argv = sys.argv[1:]
    if argv and argv[0] == "--tune":
        # kernel-autotuner surface (no workflow involved):
        # `python -m veles_tpu --tune sweep ...` == `veles-tpu-tune
        # sweep ...` — sweep/list/clear the winner cache (docs/cli.md)
        from veles_tpu.tuner.cli import main as tune_main
        sys.exit(tune_main(argv[1:]))
    sys.exit(Main().run())


if __name__ == "__main__":
    __run__()
