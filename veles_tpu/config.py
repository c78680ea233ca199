"""Auto-vivifying configuration tree (ref: veles/config.py:60-308).

``root`` is a process-global :class:`Config` tree.  Reading a missing attribute
vivifies a child node, so workflows can write ``root.mnist.learning_rate = 0.1``
without declaring the path first.  Layered overrides mirror the reference:
defaults (this module) < site config < per-run config file < CLI ``--config-list``
statements — later layers win via :meth:`Config.update`.

Differences from the reference, by design:
  * precision is expressed as a dtype *policy* (compute/accum/param dtypes) —
    the reference's Kahan/multipartial ``precision_level`` (veles/config.py:246)
    maps onto "accumulate in f32 over bf16 inputs" on TPU;
  * engine.backend defaults to whatever ``jax.devices()`` provides.
"""

import os
import pprint


class Config(object):
    """One node of the configuration tree."""

    def __init__(self, path):
        self.__dict__["_path_"] = path

    def __getattr__(self, name):
        if name.startswith("__") and name.endswith("__"):
            raise AttributeError(name)
        child = Config("%s.%s" % (self._path_, name))
        self.__dict__[name] = child
        return child

    def __setattr__(self, name, value):
        self.__dict__[name] = value

    def __delattr__(self, name):
        del self.__dict__[name]

    def __contains__(self, name):
        return name in self.__dict__

    def __iter__(self):
        for k, v in sorted(self.__dict__.items()):
            if k != "_path_":
                yield k, v

    def update(self, value):
        """Deep-merge a dict (or another Config) into this node.

        Mirrors ref veles/config.py:90-116: nested dicts recurse, everything
        else overwrites the leaf.
        """
        if isinstance(value, Config):
            value = value.as_dict()
        if not isinstance(value, dict):
            raise TypeError(
                "Config.update() takes a dict, got %s" % type(value))
        for k, v in value.items():
            if isinstance(v, dict):
                node = self.__dict__.get(k)
                if not isinstance(node, Config):
                    # widening a scalar leaf into a subtree: vivify fresh node
                    node = Config("%s.%s" % (self._path_, k))
                    self.__dict__[k] = node
                node.update(v)
            else:
                setattr(self, k, v)
        return self

    def get(self, name, default=None):
        """Return the attribute if it was explicitly set, else ``default``.

        Unlike plain attribute access this never vivifies a node.
        """
        v = self.__dict__.get(name, default)
        return default if isinstance(v, Config) and not v.as_dict() else v

    def as_dict(self):
        out = {}
        for k, v in self:
            out[k] = v.as_dict() if isinstance(v, Config) else v
        return out

    def print_(self, indent=0, stream=None):
        """Pretty-print the subtree (ref veles/config.py:128-149)."""
        import sys
        stream = stream or sys.stdout
        stream.write("%s:\n" % self._path_)
        pprint.pprint(self.as_dict(), stream=stream)

    def __repr__(self):
        return "<Config %s: %s>" % (self._path_, self.as_dict())


#: The global configuration tree (ref veles/config.py:152).
root = Config("root")


def get(cfg, default=None):
    """Resolve a config leaf: unset Config nodes collapse to ``default``."""
    if isinstance(cfg, Config):
        return default
    return cfg


def _default_dirs():
    base = os.environ.get("VELES_TPU_HOME",
                          os.path.join(os.path.expanduser("~"), ".veles_tpu"))
    return {
        "base": base,
        "cache": os.path.join(base, "cache"),
        "snapshots": os.path.join(base, "snapshots"),
        "datasets": os.environ.get("VELES_TPU_DATA",
                                   os.path.join(base, "datasets")),
    }


# Defaults (ref veles/config.py:178-291).
root.common.update({
    "dirs": _default_dirs(),
    "engine": {
        # "tpu" | "cpu" | "auto": mesh construction consults this
        "backend": os.environ.get("VELES_TPU_BACKEND", "auto"),
        # dtype policy replacing the reference's precision_type/precision_level
        "precision": {
            "compute": "bfloat16",   # MXU-native multiplies
            "accum": "float32",      # accumulation / loss / optimizer math
            "param": "float32",      # master weights
        },
        # precision_level parity knob: 0 => compute dtype as-is,
        # 1/2 => force float32 compute (Kahan/multipartial equivalent on TPU)
        "precision_level": 0,
    },
    "random_seed": 1234,
    "timings": False,
    # crash-consistent checkpointing (services.snapshotter,
    # docs/distributed_training.md "Preemption-safe training"):
    # keep_last bounds the on-disk checkpoint ring per prefix (0 =
    # unlimited); manifest=True writes a per-leaf checksum sidecar
    # validated on restore so torn commits are detected and skipped;
    # commit_retries/retry_backoff_ms retry transient filesystem
    # errors during the commit write before surfacing.
    # per_host=True (the pod tier): EVERY process writes its own full
    # checkpoint copy into its own host-local snapshot directory
    # instead of only process 0 — the substrate the pod master's
    # cross-host checkpoint agreement runs over.
    # reject_nonfinite: commit-time poison valve — a checkpoint whose
    # params/velocity contain NaN/inf is REFUSED (loud death of this
    # life) so the restart loops can never faithfully resume a
    # poisoned state; disable for workloads that legitimately
    # checkpoint non-finite leaves.
    "snapshot": {"interval": 1, "min_interval_seconds": 0, "codec": "gz",
                 "keep_last": 5, "manifest": True, "per_host": False,
                 "reject_nonfinite": True,
                 "commit_retries": 3, "retry_backoff_ms": 100},
    # the training supervisor (services.supervisor, `--supervise`):
    # respawn-on-failure with exponential backoff.  Graceful
    # preemptions (exit 75) respawn immediately and unbounded;
    # kills/fault-injections/crashes respawn with backoff and count
    # against max_restarts per window_seconds (crash-loop valve);
    # deterministic_limit consecutive IDENTICAL crashes with zero
    # checkpoint progress give up early — restarting a deterministic
    # bug only burns the restart budget.
    "supervise": {"max_restarts": 8, "window_seconds": 600,
                  "backoff_base_ms": 200, "backoff_max_ms": 30000,
                  "deterministic_limit": 3},
    # chaos/fault-drill knobs (tools/train_chaos.py, tools/pod_chaos.py,
    # tools/numerics_chaos.py):
    # unit_delay_ms sleeps per scheduler unit-run so external kills land
    # mid-sweep; with unit_delay_file set the sleep additionally
    # requires that file to EXIST, letting a harness switch a long
    # stall on mid-run (the pod chaos gate's forged collective hang).
    # nan_grads_step poisons the gradient tree with NaN at exactly that
    # staged train step (transient numeric fault); nan_grads_from
    # poisons every step >= that counter (persistent divergence) — both
    # are build-time gates inside the jitted step, zero cost when unset
    # (the numerics-chaos gate's injection hooks).
    "chaos": {"unit_delay_ms": 0, "unit_delay_file": None,
              "nan_grads_step": None, "nan_grads_from": None},
    # the numeric-fault survival tier (services.sentinel,
    # docs/distributed_training.md "Numeric-fault survival"): cheap
    # in-jit health probes fused into the staged train step —
    # loss/grad-norm finiteness, EWMA loss-spike z-score, update-norm
    # explosion — read back at the existing read_class_stats sync
    # point (no extra device sync per step), driving a three-rung
    # response ladder: (1) in-jit skip-update of a poisoned step via
    # select (bit-deterministic), (2) after strikes_to_rollback
    # anomalous sweeps, automatic rollback to the last HEALTHY commit
    # plus deterministic replay that skips the poisoned global
    # minibatch (the skip list rides max_skip_steps traced slots, so
    # growing it never recompiles), (3) after rollbacks_to_escalate
    # rollbacks with an identical anomaly signature, escalate with a
    # numerics:<kind> crash class the supervisor/pod master classify
    # under the deterministic-bug valve instead of crash-looping.
    # spike_zscore/spike_warmup tune the EWMA loss-spike probe (the
    # z threshold only fires after warmup observations);
    # update_norm_limit bounds the global update L2 norm (explosion);
    # force_skip_steps pre-loads the skip list (the numerics-chaos
    # golden-skip leg); rollback=False degrades rung 2 to escalation
    # (pods always escalate: pod-scope rollback rides the coordinated
    # restart, whose checkpoint agreement prefers healthy commits).
    "sentinel": {"enabled": True, "strikes_to_rollback": 1,
                 "rollbacks_to_escalate": 3, "spike_zscore": 12.0,
                 "spike_warmup": 64, "update_norm_limit": 1e6,
                 "ewma_decay": 0.99, "max_skip_steps": 8,
                 "force_skip_steps": (), "rollback": True},
    # the pod survival tier (services.podmaster, `veles-tpu-pod`):
    # a pod master coordinates one per-host supervisor agent per host.
    # Agents heartbeat every heartbeat_ms; an agent silent for
    # stale_after_ms, a worker death on ANY host, or no step/commit
    # progress pod-wide for hang_seconds (the collective-hang latch —
    # survivors of a dead/stalled host don't crash, they hang in the
    # next collective) all trigger ONE coordinated pod restart:
    # every agent escalates SIGTERM -> (kill_grace_ms) -> SIGKILL on
    # its worker, the restart checkpoint is computed by cross-host
    # agreement over the per-host integrity manifests
    # (snapshot.per_host), and workers respawn under a new fenced
    # incarnation id (stale registrations are refused).  PR 8's valves
    # lifted to pod scope: max_restarts bounded restarts per
    # window_seconds, deterministic_limit identical pod-wide crash
    # signatures with zero agreed-checkpoint progress give up early.
    # The ELASTIC tier: with elastic=True a host whose agent misses
    # loss_strikes consecutive agreement windows (loss_window_s each)
    # is classified permanently lost and the pod DEGRADES to the
    # survivors (resized mesh, resharded checkpoint) instead of
    # retrying the dead topology; reexpand=True folds the host back in
    # with one re-expand restart when its agent re-registers, shipping
    # the agreed commit to its frozen ring over the control plane
    # (capped at replicate_max_mb — shared-storage pods never need the
    # transfer).  Degrade/re-expand restarts count in their own valve
    # bucket, never the crash-loop or deterministic budget.
    # elastic_mesh is threaded into WORKERS by the master: the
    # launcher then rebuilds a fixed --mesh from the live device set
    # (parallel.mesh.fit_axes_to_devices).
    "pod": {"heartbeat_ms": 500, "stale_after_ms": 10000,
            "hang_seconds": 300, "kill_grace_ms": 5000,
            "max_restarts": 8, "window_seconds": 600,
            "deterministic_limit": 3,
            "backoff_base_ms": 200, "backoff_max_ms": 10000,
            "elastic": True, "loss_strikes": 2, "loss_window_s": 60,
            "reexpand": True, "replicate_max_mb": 64,
            "elastic_mesh": False},
    # status/benchmark web UI (services.web_status): host/port are the
    # WebStatusServer defaults (--web-status PORT overrides the port)
    "web": {"host": "127.0.0.1", "port": 8090},
    # telemetry thresholds (telemetry.mfu): warn when measured MFU
    # falls below this fraction of the roofline prediction
    "telemetry": {"mfu_warn_fraction": 0.5},
    # the persistent performance ledger + regression sentinel
    # (telemetry.ledger, docs/perf.md "Performance ledger & regression
    # sentinel").  ledger: explicit JSONL path (None = the
    # VELES_TPU_PERF_LEDGER env var, else <dirs.cache>/
    # perf_ledger.jsonl); enabled gates the automatic trainer/MFU/
    # harness appends; min_history is the fewest prior records before
    # the sentinel bands a key; the band is
    # band_mads x 1.4826 x MAD, floored at min_rel_band of the
    # median; history caps the records read back per key.
    "perf": {"ledger": None, "enabled": True, "min_history": 3,
             "band_mads": 4.0, "min_rel_band": 0.05, "history": 64},
    # the flight recorder / crash forensics / watchdog layer
    # (veles_tpu.telemetry.flight + .health, docs/services.md "Black
    # box").  watchdog_seconds: None = unset (standalone stays
    # disarmed, spmd arms at spmd_watchdog_seconds); an EXPLICIT 0
    # disarms even spmd runs.
    "blackbox": {"capacity": 4096, "dir": "artifacts",
                 "watchdog_seconds": None,
                 "spmd_watchdog_seconds": 300},
    # request tracing (veles_tpu.telemetry.tracing, docs/services.md
    # "Request tracing"): the per-process bounded span store behind
    # /api/trace/<id> and the veles-tpu-trace CLI.  capacity bounds
    # distinct traces held (oldest trace evicted past it), max_spans
    # bounds spans per trace; both evictions are counted
    # (veles_trace_dropped_total).  enabled=False stops span recording
    # entirely (trace ids still propagate on headers/flight events, so
    # post-mortem reconstruction keeps working).
    "trace": {"enabled": True, "capacity": 1024, "max_spans": 128},
    # serving survival layer (services.lifecycle + ContinuousEngine,
    # docs/services.md "Serving robustness").  slo_queue_wait_ms > 0
    # turns breaches from recorded (flight serve.slo_breach) into
    # enforced: the closed-loop shedder rejects new work with 503 +
    # Retry-After past the SLO and reopens below shed_close_fraction
    # of it.  default_deadline_ms > 0 gives every request a deadline
    # (per-request "deadline_ms" overrides); expired requests are
    # cancelled — mid-decode if needed — instead of decoded uselessly.
    # stream_queue_chunks bounds each streaming request's token
    # channel; stream_overflow picks what happens when the consumer
    # falls behind: 'drop_oldest' (default — the terminal line still
    # carries the full result) or 'block' (per-request backpressure:
    # chunks are held back until the consumer drains; a request that
    # makes no progress for stream_stall_timeout_ms is cancelled as a
    # slowloris).
    "serve": {
        "slo_queue_wait_ms": 0,
        "default_deadline_ms": 0,
        # segmented prefill admission (docs/services.md "Disaggregated
        # prefill"): prefill_segment > 0 splits a long prompt's
        # admission prefill into bounded chunk passes of at most this
        # many tokens, interleaved with decode ticks, so one long
        # admission can no longer stall every in-flight decode stream
        # for its whole prompt.  Outputs are byte-identical to the
        # unsegmented path (the chunk resume math is the prefix-cache
        # resume's).  0 = off (whole-prompt prefill at admission).
        # prefill_tick_budget caps the prefill tokens advanced per
        # engine tick across ALL staging admissions (0 = one segment).
        "prefill_segment": 0,
        "prefill_tick_budget": 0,
        "stream_queue_chunks": 64,
        "stream_overflow": "drop_oldest",
        "stream_stall_timeout_ms": 10000,
        "shed_close_fraction": 0.5,
        # retry_after_overshoot_cap bounds how far the 503 Retry-After
        # hint scales with the measured queue-wait overshoot: a replica
        # whose queue wait sits at 4x the SLO tells clients to back off
        # 4 SLO windows (capped here) instead of the flat minimum.
        "retry_after_overshoot_cap": 8.0,
        # graceful drain (services.lifecycle.DrainState): a draining
        # endpoint stops admitting (503 + Retry-After), finishes every
        # in-flight request, then reports "drained" on {path}/health —
        # standalone serve processes drain on SIGTERM and exit 0, fleet
        # replicas drain and get deregistered by the router.
        # drain_timeout_ms caps how long in-flight work may take before
        # the drain is forced through anyway.
        "drain_timeout_ms": 30000,
        # replica fleet tier (services.router.FleetRouter,
        # docs/services.md "Fleet serving"): a front-end router owns N
        # engine replicas, health-checks them every health_interval_ms
        # off each replica's {path}/health surface, and routes with
        # session affinity ("session": same session key sticks to one
        # replica so its prefix cache keeps hitting; "none": round-
        # robin).  A dead replica is retried onto a survivor up to
        # retry_max times with exponential backoff (backoff_base_ms
        # doubling per attempt, capped at backoff_max_ms, jittered);
        # stream_read_timeout_ms bounds one upstream read before the
        # router treats the replica as stalled and fails over; a
        # BUFFERED request produces no bytes until its whole decode
        # finishes, so it gets its own request_timeout_ms budget
        # (default 5 min) instead of the per-chunk one.
        "fleet": {
            "health_interval_ms": 100,
            "retry_max": 3,
            "backoff_base_ms": 20,
            "backoff_max_ms": 2000,
            "affinity": "session",
            "stream_read_timeout_ms": 30000,
            "request_timeout_ms": 300000,
            # --- the autoscaling fleet spec (services.podmaster
            # ServeFleetMaster, `veles-tpu-pod --serve`, docs/
            # services.md "Autoscaling fleet"): the pod master owns
            # the serving replicas declaratively — min..max engine
            # replicas fleet-wide, at most per_host on any one host;
            # agents spawn/drain them and the master auto-registers/
            # deregisters each with its FleetRouter.
            "min": 1,
            "max": 8,
            "per_host": 2,
            # --- prefill/decode fleet roles (docs/services.md
            # "Disaggregated prefill"): prefill_replicas > 0 reserves
            # that many of the desired replicas as PREFILL-role —
            # requests whose prompt length >= prefill_prompt_min are
            # routed there first for the heavy admission prefill plus
            # the first prefill_handoff_new tokens, then continue on a
            # decode-role replica via the same prefix-resume splice
            # the failover path uses (the client sees ONE
            # byte-identical stream).  0 = no role split.
            "prefill_replicas": 0,
            "prefill_prompt_min": 64,
            "prefill_handoff_new": 4,
            # --- placement: "cost" prices every request as predicted
            # prefill work (prompt_len x per-token prefill cost, from
            # tools/cost_model device constants calibrated against the
            # fleet's measured ms/tok) plus predicted decode residency
            # (max_new x measured ms/tok) and routes to the replica
            # with the least outstanding predicted work;
            # "round_robin" keeps the PR 7 rotation.  Session
            # affinity still wins over either.
            "placement": "cost",
            # --- the autoscaler loop: scale UP when any replica's
            # measured queue-wait overshoot (SloShedder.overshoot,
            # read off /health) reaches scale_up_overshoot or fresh
            # serve.shed rejections arrive; scale DOWN after
            # scale_idle_s of fleet-wide idle (always through the
            # SIGTERM drain, so scale-down is lossless by
            # construction).  scale_cooldown_s spaces consecutive
            # decisions; on top of that every decision is budgeted in
            # its own PodValves bucket (scale_max_per_window per
            # scale_window_s — flap damping: a scale oscillation can
            # never consume the crash-loop budget).
            "scale_up_overshoot": 1.0,
            # scale UP early when the fleet-wide queued-but-unprefilled
            # prompt backlog (replica queued_prefill_tokens, summed by
            # FleetRouter.fleet_signals) reaches this many tokens —
            # prefill backlog predicts the queue-wait breach before
            # the shedder can measure it.  0 disables the signal.
            "scale_up_prefill_backlog": 4096,
            "scale_idle_s": 30.0,
            "scale_cooldown_s": 10.0,
            "scale_window_s": 120.0,
            "scale_max_per_window": 4,
            # a spawned replica must announce READY (bound port)
            # within this budget or the spawn is classified a crash
            # and replaced — a wedged replica must not hold a fleet
            # slot forever
            "ready_timeout_ms": 180000,
            # a replica must stay up this long (or serve a request)
            # before its next crash counts as "progressed" for the
            # deterministic-bug valve — mirrors the training
            # supervisor's checkpoint-progress reset
            "min_uptime_s": 30.0,
        },
    },
})


def apply_site_config():
    """Site override chain (ref veles/config.py:294-308): import
    ``veles_tpu_site_config`` if present and call its ``update(root)``."""
    try:
        import veles_tpu_site_config  # noqa: F401
    except ImportError:
        return
    if hasattr(veles_tpu_site_config, "update"):
        veles_tpu_site_config.update(root)


apply_site_config()
