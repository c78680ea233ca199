"""veles-tpu-lint — build a workflow file's graph and statically lint it.

Honors the module contract (``run(load, main)``, ref __main__.py): the
workflow file constructs its Workflow through ``load(...)``; ``main()``
here is a no-op, so nothing is initialized, no XLA computation is
dispatched, and no data is loaded beyond what construction itself does.
With ``--mesh`` the workflow IS additionally initialized (on a virtual
CPU device mesh — parameters are allocated, but no training step ever
runs) so the sharding/memory auditor can lower the real staged step
under the mesh (VS2xx/VM3xx, docs/static_analysis.md).

Exit status: 0 = no findings at or above the ``--fail-on`` severity
threshold (default ``error``), 1 = threshold reached (``--fail-on
warning`` lets CI gate on warnings too), 2 = usage."""

import argparse
import os
import re
import runpy
import sys


def build_workflow(workflow_path, config_path=None, config_list=()):
    """Construct (but never initialize or run) the workflow a file
    defines, applying config layering exactly like the training CLI."""
    from veles_tpu.config import root
    from veles_tpu.genetics.core import Range
    if config_path:
        scope = {"root": root, "Range": Range}
        with open(config_path) as f:
            exec(compile(f.read(), config_path, "exec"), scope)
    for stmt in config_list:
        exec(stmt, {"root": root, "Range": Range})

    wf_globals = runpy.run_path(workflow_path, run_name="__veles__")
    if "run" not in wf_globals:
        raise SystemExit("%s does not define run(load, main)"
                         % workflow_path)
    built = {}

    def load(cls, **kwargs):
        built["wf"] = cls(**kwargs)
        return built["wf"]

    def main(**kwargs):
        return built.get("wf")  # lint never initializes or runs

    wf_globals["run"](load, main)
    if "wf" not in built:
        raise SystemExit("%s never called load(WorkflowClass, ...)"
                         % workflow_path)
    return built["wf"]


def parse_mesh(spec):
    """``'2x2'`` (data x model) or the training CLI's ``'data=2,model=2'``
    axis grammar → ``{axis: size}`` — the ONE mesh-spec parser
    (``__main__.Main._parse_mesh`` delegates here)."""
    if "=" not in spec:
        parts = spec.lower().replace("*", "x").split("x")
        if len(parts) != 2:
            raise SystemExit("--mesh wants DxM (e.g. 2x2) or "
                             "axis=size[,axis=size...], got %r" % spec)
        try:
            return {"data": int(parts[0]), "model": int(parts[1])}
        except ValueError:
            raise SystemExit("--mesh: %r is not DxM" % spec)
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        if not size:
            raise SystemExit("--mesh wants axis=size, got %r" % part)
        try:
            axes[name.strip()] = int(size)
        except ValueError:
            raise SystemExit("--mesh: size in %r is not an integer"
                             % part)
    return axes


_DEVCOUNT_RE = re.compile(
    r"--xla_force_host_platform_device_count=(\d+)")


def _force_cpu_devices(axes):
    """Linting must never grab an accelerator, and a mesh lint needs
    enough virtual CPU devices to build the mesh — both are env knobs
    that only work before the jax backend initializes (the
    tests/conftest.py pattern).  An XLA_FLAGS pin SMALLER than the mesh
    is raised to fit; a larger one is left alone."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    n = 1
    for size in (axes or {}).values():
        if size > 0:
            n *= size
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1:
        m = _DEVCOUNT_RE.search(flags)
        if m is None:
            flags = (flags + " --xla_force_host_platform_device_count"
                     "=%d" % n).strip()
        elif int(m.group(1)) < n:
            flags = _DEVCOUNT_RE.sub(
                "--xla_force_host_platform_device_count=%d" % n, flags)
        os.environ["XLA_FLAGS"] = flags
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — backend already initialized: too
        pass           # late to repoint, construction won't dispatch


def _initialize_plain(wf):
    """Initialize the workflow on the (forced-CPU) default device so
    the staged steps exist for the numerics auditor — parameters are
    allocated, no training step ever dispatches (the ``--mesh``
    contract, minus the mesh)."""
    if not getattr(wf, "_initialized", False):
        wf.initialize()


def _attach_mesh(wf, axes, fsdp):
    """Build the MeshConfig and initialize the workflow under it (the
    Launcher's --mesh wiring, minus services/distributed): params are
    allocated on the virtual CPU mesh so the staged steps and their
    shardings exist for the auditor — still no training dispatch."""
    from veles_tpu.parallel import MeshConfig, make_mesh
    mc = MeshConfig(make_mesh(axes), fsdp=fsdp)
    for unit in [wf] + list(wf.units):
        if hasattr(unit, "mesh_config") and \
                getattr(unit, "mesh_config") is None:
            unit.mesh_config = mc
    trainer = getattr(wf, "trainer", None)
    loader = getattr(wf, "loader", None)
    if (trainer is not None and loader is not None
            and getattr(trainer, "dataset_placement", None) == "shard"
            and mc.data_size > 1
            and getattr(loader, "on_device", None) is True):
        loader.on_device = "defer"   # never materialize a full replica
    wf.initialize()
    return mc


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="veles-tpu-lint",
        description="static workflow-graph linter + jit-staging auditor "
                    "+ sharding/memory auditor + numerics/determinism "
                    "auditor + serving decode-path auditor + "
                    "control-plane concurrency lint + wire-protocol "
                    "contract lint + config/telemetry contract audit "
                    "+ serialized-state contract audit + "
                    "host-determinism lint "
                    "(rule catalog: docs/static_analysis.md)",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="exit codes (identical across every family, VG...VB — "
               "analysis.findings\n.threshold_reached is the one "
               "gate):\n"
               "  0  no findings at or above the --fail-on severity\n"
               "  1  threshold reached (default --fail-on error: any "
               "error finding)\n"
               "  2  usage error (bad arguments, workflow file without "
               "run(load, main))")
    p.add_argument("workflow", nargs="?", default=None,
                   help="workflow .py file defining run(load, main) "
                   "(optional only for a pure --concurrency / "
                   "--protocol / --config-audit / --state / "
                   "--determinism / --all run — the AST lints need no "
                   "workflow)")
    p.add_argument("config", nargs="?", help="config .py file executed "
                   "with `root` in scope")
    p.add_argument("--config-list", nargs="*", default=[],
                   help="inline config statements, e.g. "
                   "'root.mnist.lr=0.1'")
    p.add_argument("--format", choices=("text", "json", "markdown"),
                   default="text",
                   help="'text'/'json' render findings; 'markdown' "
                   "(only with --config-audit alone or --state alone) "
                   "prints the generated contract reference "
                   "(docs/config_reference.md or docs/"
                   "state_reference.md) instead and always exits 0")
    p.add_argument("--no-staging", action="store_true",
                   help="graph rules only; skip the jit-staging audit "
                   "hooks")
    p.add_argument("--mesh", default=None, metavar="DxM",
                   help="initialize the workflow under a DATAxMODEL "
                   "device mesh (virtual CPU devices) and run the "
                   "VS2xx/VM3xx sharding & memory audit of the staged "
                   "step; also accepts the training CLI's "
                   "'data=2,model=2' axis grammar")
    p.add_argument("--fsdp", action="store_true",
                   help="audit with ZeRO-3 fully-sharded parameters "
                   "over the data axis (pairs with --mesh)")
    p.add_argument("--numerics", action="store_true",
                   help="initialize the workflow (params allocate, no "
                   "step dispatches — composes with --mesh) so the "
                   "VN4xx/VR5xx numerics & determinism audit can trace "
                   "the real staged train step; the prng-registry "
                   "(VR501) and Pallas kernel-geometry (VP6xx) rules "
                   "run even without this flag")
    p.add_argument("--vmem-kib", type=float, default=None, metavar="KiB",
                   help="per-core VMEM budget the VP602 Pallas kernel "
                   "footprint is judged against (default: "
                   "numerics_audit.DEFAULT_VMEM_KIB = 16384, ~16 MiB)")
    p.add_argument("--hbm-gib", type=float, default=None, metavar="GiB",
                   help="per-device HBM capacity the VM300 peak "
                   "estimate is judged against (default: "
                   "sharding_audit.DEFAULT_HBM_GIB = 16, v5e)")
    p.add_argument("--serve", action="store_true",
                   help="initialize the workflow and run the VD7xx "
                   "decode-path audit over the serving engine's decode "
                   "tick + segmented-prefill pass for every standard "
                   "variant (bf16/int8/w4a8 x dense/paged x spec "
                   "on/off) — abstract traces only, no decode step "
                   "ever dispatches")
    p.add_argument("--serve-max-len", type=int, default=16,
                   metavar="T", help="sequence budget the --serve "
                   "audit builds its generators with (default 16 — "
                   "geometry-relevant rules scale with it)")
    p.add_argument("--concurrency", action="store_true",
                   help="run the VT8xx concurrency lint (pure AST "
                   "scan) over the threaded control plane in "
                   "veles_tpu/services — needs no workflow file")
    p.add_argument("--protocol", action="store_true",
                   help="run the VW9xx wire-protocol contract lint "
                   "(pure AST scan) over the control-plane line-JSON "
                   "protocol in veles_tpu/services — every message "
                   "kind needs a sender AND a handler, state-mutating "
                   "handlers must consult the incarnation fence, "
                   "socket reads need timeout bounds; needs no "
                   "workflow file")
    p.add_argument("--config-audit", action="store_true",
                   dest="config_audit",
                   help="run the VC95x config/telemetry contract audit "
                   "(pure AST scan) over the whole tree — root.common "
                   "knob reads vs the config.py declarations (typos, "
                   "dead knobs, conflicting defaults) and flight-event"
                   "/metric emits vs the test/tool/docs surface; "
                   "needs no workflow file")
    p.add_argument("--state", action="store_true",
                   help="run the VK10xx serialized-state contract "
                   "audit (pure AST scan) over the snapshot/manifest/"
                   "winners/crashdump/fleet-spec/NDJSON state plane — "
                   "every serialized key needs a reader, every read "
                   "key a writer, optional keys a .get default or "
                   "version guard, digests canonical serialization, "
                   "pickled payloads picklable leaves; needs no "
                   "workflow file")
    p.add_argument("--determinism", action="store_true",
                   help="run the VB11xx host-determinism lint (pure "
                   "AST scan) over the modules the chaos gates "
                   "bit-compare (snapshotter/sentinel/podmaster/prng/"
                   "generate/loaders) — wall-clock into payloads or "
                   "digests, unsorted filesystem enumeration, "
                   "set-order iteration, host random/uuid, unordered "
                   "threaded accumulation; needs no workflow file")
    p.add_argument("--perf", action="store_true",
                   help="run the VL12xx performance target-contract "
                   "lint over the performance ledger (telemetry."
                   "ledger): targets declared but never measured, "
                   "measurements referencing unknown targets, "
                   "duplicate/conflicting declarations — a data "
                   "audit of the ledger file, not an AST scan; "
                   "needs no workflow file (--ledger picks the "
                   "file; sentinel verdicts live in veles-tpu-perf "
                   "gate)")
    p.add_argument("--ledger", default=None, metavar="PATH",
                   help="ledger JSONL the --perf lint reads "
                   "(default: the process ledger — "
                   "root.common.perf.ledger > "
                   "VELES_TPU_PERF_LEDGER > <dirs.cache>/"
                   "perf_ledger.jsonl)")
    p.add_argument("--all", action="store_true",
                   help="run every registered AST family in one pass "
                   "(--concurrency --protocol --config-audit --state "
                   "--determinism) with one merged findings report "
                   "and one exit gate; with a workflow file the "
                   "graph/staging families run too")
    p.add_argument("--fail-on", choices=("error", "warning"),
                   default="error", metavar="{error,warning}",
                   help="severity threshold for the non-zero exit: "
                   "'error' (default) fails only on error findings, "
                   "'warning' fails on warnings too — the CI gate "
                   "knob, shared by every family (VG/VJ/VS/VM/VN/VR/"
                   "VP/VD/VT/VW/VC) through findings.threshold_reached")
    p.add_argument("--strict", action="store_true",
                   help="deprecated alias for --fail-on warning")
    args = p.parse_args(argv)

    if args.all:
        args.concurrency = args.protocol = args.config_audit = True
        args.state = args.determinism = True
    ast_only = (args.concurrency or args.protocol or args.config_audit
                or args.state or args.determinism or args.perf)
    if args.workflow is None and not ast_only:
        p.error("a workflow file is required (only pure --concurrency/"
                "--protocol/--config-audit/--state/--determinism/--all "
                "runs work without one)")
    if args.serve and args.workflow is None:
        p.error("--serve audits a workflow's serving engine — give "
                "it the workflow file")
    if args.format == "markdown":
        only_config = (args.config_audit and not args.state)
        only_state = (args.state and not args.config_audit)
        if args.workflow is not None or args.concurrency \
                or args.protocol or args.determinism \
                or not (only_config or only_state):
            p.error("--format markdown prints a generated contract "
                    "reference — it pairs with --config-audit alone "
                    "(docs/config_reference.md) or --state alone "
                    "(docs/state_reference.md)")
        if only_state:
            from veles_tpu.analysis.state_audit import build_reference
        else:
            from veles_tpu.analysis.config_audit import build_reference
        sys.stdout.write(build_reference())
        return 0

    findings = []
    if args.workflow is not None:
        axes = parse_mesh(args.mesh) if args.mesh else None
        if args.fsdp and not axes:
            raise SystemExit("--fsdp needs --mesh (parameters shard "
                             "over the mesh's data axis)")
        # env knobs must land before anything touches a jax backend
        _force_cpu_devices(axes)

        from veles_tpu.analysis import lint_serving, lint_workflow
        wf = build_workflow(args.workflow, args.config,
                            args.config_list)
        if axes:
            _attach_mesh(wf, axes, args.fsdp)
        elif args.numerics or args.serve:
            _initialize_plain(wf)
        findings.extend(lint_workflow(wf, staging=not args.no_staging,
                                      hbm_gib=args.hbm_gib,
                                      vmem_kib=args.vmem_kib))
        if args.serve:
            trainer = getattr(wf, "trainer", None)
            if trainer is None:
                raise SystemExit("--serve: workflow has no .trainer "
                                 "unit to build a serving engine from")
            findings.extend(lint_serving(trainer, args.serve_max_len,
                                         vmem_kib=args.vmem_kib))
    if args.concurrency:
        from veles_tpu.analysis import lint_concurrency
        findings.extend(lint_concurrency())
    if args.protocol:
        from veles_tpu.analysis import lint_protocol
        findings.extend(lint_protocol())
    if args.config_audit:
        from veles_tpu.analysis import lint_config
        findings.extend(lint_config())
    if args.state:
        from veles_tpu.analysis import lint_state
        findings.extend(lint_state())
    if args.determinism:
        from veles_tpu.analysis import lint_determinism
        findings.extend(lint_determinism())
    if args.perf:
        from veles_tpu.analysis import lint_perf
        findings.extend(lint_perf(ledger_path=args.ledger))

    from veles_tpu.analysis import (format_findings, sort_findings,
                                    threshold_reached)
    findings = sort_findings(findings)
    print(format_findings(findings, args.format))
    fail_on = ("warning" if args.strict else args.fail_on)
    return 1 if threshold_reached(findings, fail_on) else 0


if __name__ == "__main__":
    sys.exit(main())
