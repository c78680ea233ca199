"""Persistent performance ledger + noise-aware regression sentinel.

TVM's central discipline (PAPERS.md) — every measurement lands in a
persistent store the optimizer can train against — applied to *all* of
this repo's performance numbers, not just the tuner's kernel winners.
Before this module, bench numbers lived in an ad-hoc blob per run,
chaos-harness gate numbers (storm_ms_per_tok, failover detect latency,
stall p99s) evaporated after each run, and a silent perf regression
would only be caught by a human re-reading JSON.

The ledger is an append-only JSONL file; every record is one line:

    {"schema": 1, "ts": ..., "metric": ..., "value": ..., "unit": ...,
     "workload": ..., "backend": "tpu:4", "mesh": "-", "dtype": "bf16",
     "better": "lower"|"higher", "source": "bench.serve",
     "target": {"id": ..., "goal": ..., "better": ...}|null,
     "components": {"compute_ms": ..., ...}|null, ...}

Records are keyed the tuner's way (``veles_tpu.tuner.make_key``):
``metric | workload | backend:devcount | mesh-topology | dtype`` — the
same five axes that decide whether two kernel timings are comparable
decide whether two ledger rows are.  Appends are atomic (one
``os.write`` on an ``O_APPEND`` fd — concurrent writers interleave
whole lines, never bytes) and **fail-soft** like the PR 3 metrics
sink: ledger I/O can never fail the run it observes; an unwritable
directory degrades to in-memory history.  The ``backend`` axis is never
guessed: it is the live jax backend of the appending process, or what
the caller passes (``bench.py``'s parent passes what its probe child
reported), or the row is not written at all.

**Targets** are pre-registered here, not in bench-phase code: the
:data:`TARGETS` registry is THE declaration (``bench.py`` reads its
goal constants from it, each appended row carries the target it
answers, and the VL12xx lint — :mod:`veles_tpu.analysis.perf_lint` —
cross-checks declared-vs-measured both ways).

**Sentinel**: every fresh append is compared against the key's history
using a median/MAD band — ``median ± band_mads · 1.4826 · MAD``,
floored at ``min_rel_band`` of the median so a freakishly quiet
history cannot turn run-to-run noise into alarms.  A value outside the
band on the worse side emits a ``perf.regression`` flight event and
bumps ``veles_perf_regressions_total``; meeting/missing the declared
target emits ``perf.target_met``; signed drift vs the median lands on
the ``veles_perf_drift{metric}`` gauge.  When records carry a
``components`` decomposition (the step-anatomy layer,
:mod:`veles_tpu.telemetry.anatomy`), the verdict names the component
whose share grew the most — "step got slower" becomes "dispatch-queue
share doubled".

Knobs: ``root.common.perf.*`` (docs/config_reference.md).  Surfaces:
``veles-tpu-perf`` (report/diff/gate/targets), the web-status
``/api/perf`` panel, docs/perf.md "Performance ledger & regression
sentinel".  Import cost is stdlib-only, and the ledger never
initializes a jax backend: it asks one only when one is already up."""

import dataclasses
import json
import os
import sys
import threading
import time

#: current record schema; readers migrate older shapes forward (a
#: record with no "schema" field is v0: pre-ledger blob rows whose
#: timestamp key was "when" and which carried no keying axes)
SCHEMA = 1

_MAD_SCALE = 1.4826   # MAD -> sigma-equivalent for normal noise


# --------------------------------------------------------------- targets
@dataclasses.dataclass(frozen=True)
class Target:
    """One pre-registered performance target: the number a future TPU
    window must answer, declared HERE (not inline in a bench phase) so
    the declared-vs-measured contract is lintable."""
    metric: str     #: ledger metric the target gates
    goal: float     #: the pre-registered bar
    better: str     #: "lower" | "higher" — which side of goal wins
    unit: str       #: unit of goal (display only)
    source: str     #: who measures it, e.g. "bench.serve"
    note: str = ""  #: provenance — where the bar was argued for

    def met(self, value):
        return (value <= self.goal if self.better == "lower"
                else value >= self.goal)


#: THE target registry (ROADMAP item 1's pre-registered bars moved out
#: of bench-phase code).  bench.py emits the legacy ``target_*`` phase
#: keys FROM these values, so the driver contract is unchanged.
TARGETS = (
    Target("serve_int8_vs_bf16_x", 1.5, "higher", "x", "bench.serve",
           "int8 >= 1.5x bf16 ms/tok on the memory-bound flagship "
           "width"),
    Target("serve_seg_stall_x", 4.0, "lower", "x", "bench.serve",
           "segmented-prefill p99 decode stall <= 4x the base cadence "
           "while a long prompt admits mid-stream"),
    Target("serve_cost_vs_rr_x", 1.0, "higher", "x", "bench.serve",
           "cost-weighted routing must not lose to round-robin under "
           "the skewed-length storm (rr/cost ms-per-tok ratio)"),
    Target("flash_bwd_vs_xla_x", 1.0, "lower", "x", "bench.flash",
           "tuned flash bwd <= XLA (the flashtune sweep's job)"),
    Target("lm_large_mfu", 0.44, "higher", "MFU", "bench.lm_large",
           "bar fitted 2026-08-01, not re-measured on this machine; "
           "ROADMAP S2 moves it with the re-measured gemm row"),
)

TARGETS_BY_METRIC = {t.metric: t for t in TARGETS}


def target_goal(metric, default=None):
    """The declared goal for ``metric`` — bench phases emit their
    legacy ``target_*`` keys through this, so the registry is the one
    source of truth."""
    t = TARGETS_BY_METRIC.get(metric)
    return default if t is None else t.goal


#: bench.py ``line`` keys that are ledger rows: key -> (unit, better,
#: phase).  Keys absent here (flags, metadata, nested blobs) stay out
#: of the ledger.  The serve/flash ``*_x`` ratios are derived in
#: bench.main() from the raw ms keys so their targets are judgeable.
BENCH_ROWS = {
    "value": ("GFLOP/s", "higher", "gemm"),
    "vs_baseline": ("x", "higher", "gemm"),
    "gemm_bf16_gflops": ("GFLOP/s", "higher", "gemm"),
    "gemm_bf16_mfu": ("MFU", "higher", "gemm"),
    "gemm_precision_overhead_pct": ("%", "lower", "gemm"),
    "mlp_step_ms": ("ms", "lower", "mlp"),
    "mlp_step_fused_ms": ("ms", "lower", "mlp"),
    "alexnet_samples_per_sec": ("samples/s", "higher", "alexnet"),
    "lm_tokens_per_sec": ("tok/s", "higher", "lm"),
    "lm_mfu": ("MFU", "higher", "lm"),
    "lm_large_tokens_per_sec": ("tok/s", "higher", "lm_large"),
    "lm_large_mfu": ("MFU", "higher", "lm_large"),
    "kohonen_ms_per_step": ("ms", "lower", "kohonen"),
    "kohonen_sweep_speedup": ("x", "higher", "kohonen"),
    "flash_ms_bf16": ("ms", "lower", "flash"),
    "flash_ms_bf16_xla": ("ms", "lower", "flash"),
    "flash_ms_bwd": ("ms", "lower", "flash"),
    "flash_ms_bwd_xla": ("ms", "lower", "flash"),
    "flash_bwd_vs_xla_x": ("x", "lower", "flash"),
    "flash_ms_long_t8192": ("ms", "lower", "flash"),
    "flash_ms_long_t8192_xla": ("ms", "lower", "flash"),
    "beam_ms_per_pos_t4096": ("ms", "lower", "beam"),
    "serve_ms_per_tok_bf16": ("ms", "lower", "serve"),
    "serve_ms_per_tok_int8": ("ms", "lower", "serve"),
    "serve_int8_vs_bf16_x": ("x", "higher", "serve"),
    "serve_seg_stall_x": ("x", "lower", "serve"),
    "serve_cost_vs_rr_x": ("x", "higher", "serve"),
}


# ---------------------------------------------------------------- keying
def _live_backend():
    """The tuner's ``backend:devcount`` for the jax backend this
    process has ALREADY initialized, else None.  The ledger must never
    be the one to initialize a backend — a process that only reports
    (``bench.py``'s parent, a chaos harness's parent) would take the
    chip from the child that needs it — and must never guess one from
    the environment: a row under a backend nobody ran on is a lie.
    ``jax._src.xla_bridge.backends_are_initialized`` (JAX 0.9.0) is
    the one way to ask without initializing."""
    if "jax" not in sys.modules:
        return None
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    from veles_tpu.tuner import mesh_descriptor
    return mesh_descriptor()


def key_of(record):
    """``metric | workload | backend:devcount | mesh-topology | dtype``
    — the tuner's keying discipline (tuner.make_key) over the ledger's
    five comparability axes."""
    return "|".join((str(record.get("metric", "?")),
                     str(record.get("workload", "-")),
                     str(record.get("backend", "-")),
                     str(record.get("mesh", "-")),
                     str(record.get("dtype", "-"))))


def _migrate(record):
    """Upgrade one parsed record to the current schema, in place-ish.
    v0 (no "schema"): pre-ledger rows used "when" for the timestamp
    and carried no keying axes — fill the axes with the unkeyed
    defaults so v0 history still groups with v1 appends of the same
    metric."""
    if not isinstance(record, dict) or "metric" not in record:
        return None
    ver = record.get("schema", 0)
    if ver > SCHEMA:            # from the future: keep what we parse
        return record
    if ver < 1:
        record = dict(record)
        if "when" in record and "ts" not in record:
            record["ts"] = record.pop("when")
        for axis in ("workload", "backend", "mesh", "dtype"):
            record.setdefault(axis, "-")
        record["schema"] = SCHEMA
    return record


def _infer_better(unit, better=None):
    if better in ("lower", "higher"):
        return better
    u = (unit or "").lower()
    if u in ("ms", "s", "us", "ms/tok", "%") or u.startswith("ms"):
        return "lower"
    return "higher"


def _median(vals):
    s = sorted(vals)
    n = len(s)
    if not n:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


# ---------------------------------------------------------------- ledger
class PerfLedger(object):
    """One JSONL performance ledger: atomic fail-soft appends, per-key
    history, and the median/MAD regression sentinel."""

    def __init__(self, path=None, registry=None):
        self.path = path or default_path()
        self._registry = registry
        self._lock = threading.Lock()
        self._mem = []           # appended this process (disk or not)
        self._disk_dead = False  # first write failure silences retries

    # -- knobs (root.common.perf.*, declared in config.py) -------------
    @staticmethod
    def _knob(name, default):
        try:
            from veles_tpu.config import root
            return root.common.perf.get(name, default)
        except Exception:   # noqa: BLE001 — knobs are advisory here
            return default

    def _reg(self):
        if self._registry is None:
            from veles_tpu import telemetry
            self._registry = telemetry.registry
        return self._registry

    # -- reading --------------------------------------------------------
    def records(self, metric=None, key=None):
        """All parseable records, disk first then this process's
        unpersisted in-memory tail, migrated to the current schema and
        optionally filtered by metric or full key."""
        out = []
        try:
            with open(self.path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = _migrate(json.loads(line))
                    except ValueError:
                        continue   # torn/garbage line: skip, not fatal
                    if rec is not None:
                        out.append(rec)
        except OSError:
            pass
        with self._lock:
            if self._disk_dead:
                out.extend(self._mem)
        if metric is not None:
            out = [r for r in out if r.get("metric") == metric]
        if key is not None:
            out = [r for r in out if key_of(r) == key]
        return out

    def by_key(self):
        """{key: [records, oldest first]} over the whole ledger."""
        groups = {}
        for rec in self.records():
            groups.setdefault(key_of(rec), []).append(rec)
        return groups

    def history(self, key, limit=None):
        recs = self.records(key=key)
        limit = limit or int(self._knob("history", 64))
        return recs[-limit:]

    # -- sentinel -------------------------------------------------------
    def assess(self, record, prior=None):
        """Noise-aware verdict of ``record`` against its key's prior
        history and its declared target.  Pure function of its inputs
        (no I/O when ``prior`` is given) so tests and the CLI gate can
        replay it.  Returns::

            {"status": "regression"|"improved"|"ok"|"no_history",
             "n": len(prior), "median": ..., "mad": ..., "band": ...,
             "drift": signed fraction vs median, "better": ...,
             "target": goal|None, "target_met": bool|None,
             "component": worst-drifting component name|None}
        """
        if prior is None:
            prior = self.history(key_of(record))
            if prior and prior[-1] == record:   # already appended
                prior = prior[:-1]
        vals = [r.get("value") for r in prior
                if isinstance(r.get("value"), (int, float))]
        value = record.get("value")
        better = _infer_better(record.get("unit"),
                               record.get("better"))
        tgt = record.get("target") or None
        decl = TARGETS_BY_METRIC.get(record.get("metric"))
        goal = (tgt or {}).get("goal",
                               decl.goal if decl else None)
        verdict = {"status": "no_history", "n": len(vals),
                   "median": None, "mad": None, "band": None,
                   "drift": None, "better": better, "target": goal,
                   "target_met": None, "component": None}
        if isinstance(value, (int, float)) and goal is not None:
            verdict["target_met"] = (value <= goal if better == "lower"
                                     else value >= goal)
        min_hist = int(self._knob("min_history", 3))
        if len(vals) < min_hist or not isinstance(value, (int, float)):
            return verdict
        med = _median(vals)
        mad = _median([abs(v - med) for v in vals])
        band = max(float(self._knob("band_mads", 4.0)) * _MAD_SCALE
                   * mad,
                   float(self._knob("min_rel_band", 0.05)) * abs(med))
        drift = (value - med) / med if med else 0.0
        verdict.update(median=med, mad=mad, band=band,
                       drift=round(drift, 6))
        worse = (value > med + band if better == "lower"
                 else value < med - band)
        improved = (value < med - band if better == "lower"
                    else value > med + band)
        verdict["status"] = ("regression" if worse
                             else "improved" if improved else "ok")
        if worse:
            verdict["component"] = self._drifted_component(record,
                                                           prior)
        return verdict

    @staticmethod
    def _drifted_component(record, prior):
        """Name the component whose time grew the most vs its own
        history median — the step-anatomy attribution that turns "step
        got slower" into "dispatch-queue share doubled"."""
        comps = record.get("components")
        if not isinstance(comps, dict):
            return None
        hist = {}
        for rec in prior:
            pc = rec.get("components")
            if isinstance(pc, dict):
                for name, v in pc.items():
                    if isinstance(v, (int, float)):
                        hist.setdefault(name, []).append(v)
        worst, excess = None, 0.0
        for name, v in comps.items():
            if not isinstance(v, (int, float)) or name not in hist:
                continue
            delta = v - _median(hist[name])
            if delta > excess:
                worst, excess = name, delta
        return worst

    def _emit_verdict(self, record, verdict):
        """Flight events + gauges for one fresh verdict — the PR 3
        fail-soft emit path (observe, never abort)."""
        try:
            from veles_tpu.telemetry import flight
            reg = self._reg()
            metric = str(record.get("metric", "?"))
            if verdict.get("drift") is not None:
                reg.gauge(
                    "veles_perf_drift",
                    "signed drift of the freshest ledger append vs "
                    "its key's history median", ("metric",)).set(
                    verdict["drift"], metric=metric)
            if verdict["status"] == "regression":
                reg.counter(
                    "veles_perf_regressions_total",
                    "ledger appends outside their key's MAD noise "
                    "band on the worse side").inc()
                flight.record(
                    "perf.regression", metric=metric,
                    key=key_of(record), value=record.get("value"),
                    median=verdict["median"], band=verdict["band"],
                    drift=verdict["drift"],
                    component=verdict["component"],
                    source=record.get("source"))
            if verdict.get("target_met") is not None:
                flight.record(
                    "perf.target_met", metric=metric,
                    value=record.get("value"),
                    target=verdict["target"],
                    met=verdict["target_met"],
                    source=record.get("source"))
        except Exception:   # noqa: BLE001 — emit is observational
            pass

    # -- writing --------------------------------------------------------
    def append(self, metric, value, workload="-", dtype="-", mesh=None,
               backend=None, unit="", better=None, target=None,
               source="", components=None, ts=None, assess=True,
               **extra):
        """Append one measurement; returns the record with its
        sentinel ``verdict`` attached (the verdict is derived state —
        it never lands on disk), or None when the row was not written:
        building the record failed, or no ``backend`` was given and
        this process has no live jax backend to name (see
        :func:`_live_backend` — the axis is never guessed).
        NEVER raises: ledger I/O cannot fail the run it observes
        (fail-soft like the PR 3 sink)."""
        try:
            if backend is None:
                backend = _live_backend()
                if backend is None:
                    return None
            decl = TARGETS_BY_METRIC.get(metric)
            if target is None and decl is not None:
                target = {"id": decl.metric, "goal": decl.goal,
                          "better": decl.better}
            rec = {"schema": SCHEMA,
                   "ts": time.time() if ts is None else ts,
                   "metric": str(metric), "value": value,
                   "unit": unit, "workload": str(workload),
                   "backend": str(backend),
                   "mesh": "-" if mesh is None else str(mesh),
                   "dtype": str(dtype),
                   "better": _infer_better(unit, better),
                   "source": str(source), "target": target}
            if components:
                rec["components"] = components
            for k, v in extra.items():
                rec.setdefault(k, v)
            prior = self.history(key_of(rec)) if assess else None
            self._write(rec)
            with self._lock:
                self._mem.append(rec)
            if assess:
                verdict = self.assess(rec, prior)
                self._emit_verdict(rec, verdict)
                rec = dict(rec, verdict=verdict)
            return rec
        except Exception:   # noqa: BLE001 — fail-soft by contract
            return None

    def _write(self, rec):
        """One atomic line: a single O_APPEND write interleaves whole
        records under concurrent writers (POSIX append semantics), and
        the first OSError retires the disk path for the process —
        history keeps accumulating in memory."""
        if self._disk_dead:
            return
        line = (json.dumps(rec, sort_keys=True,
                           default=str) + "\n").encode("utf-8")
        try:
            d = os.path.dirname(self.path)
            if d and not os.path.isdir(d):
                os.makedirs(d, exist_ok=True)
            fd = os.open(self.path,
                         os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                         0o644)
            try:
                os.write(fd, line)
            finally:
                os.close(fd)
        except OSError:
            self._disk_dead = True

    # -- bench integration ---------------------------------------------
    def append_bench_line(self, line, source="bench", ts=None,
                          backend=None):
        """Every measured ``bench.py`` phase row -> one ledger record
        carrying its pre-registered target (BENCH_ROWS is the row
        spec; zeros are "phase did not run", not measurements).
        ``backend``: what the phases ran on, as bench's probe child
        reported it (bench's parent has no backend of its own).
        Returns the number of rows appended."""
        n = 0
        for bench_key, (unit, better, phase) in BENCH_ROWS.items():
            v = line.get(bench_key)
            if not isinstance(v, (int, float)) \
                    or isinstance(v, bool) or not v:
                continue
            if self.append(bench_key, v, workload=phase, unit=unit,
                           better=better, dtype="-",
                           source="%s.%s" % (source, phase),
                           ts=ts, backend=backend) is not None:
                n += 1
        return n


# --------------------------------------------------- module-level surface
def default_path():
    """Ledger path resolution: ``root.common.perf.ledger`` knob >
    ``VELES_TPU_PERF_LEDGER`` env > ``<dirs.cache>/perf_ledger.jsonl``
    (next to the tuner's winners — the other persistent measurement
    store)."""
    try:
        from veles_tpu.config import root
        knob = root.common.perf.get("ledger", None)
        if knob:
            return str(knob)
        cache = root.common.dirs.get("cache", None)
    except Exception:   # noqa: BLE001
        cache = None
    env = os.environ.get("VELES_TPU_PERF_LEDGER")
    if env:
        return env
    if not cache:
        cache = os.path.join(os.path.expanduser("~"), ".veles_tpu",
                             "cache")
    return os.path.join(cache, "perf_ledger.jsonl")


_default = None
_default_lock = threading.Lock()


def default():
    """The process ledger (resolved once; pass an explicit
    :class:`PerfLedger` to target another file)."""
    global _default
    with _default_lock:
        if _default is None or _default.path != default_path():
            _default = PerfLedger()
        return _default


def record_value(metric, value, **kwargs):
    """Fail-soft convenience append to the process ledger — the hook
    trainers/harnesses call inline (``root.common.perf.enabled``
    gates it; returns the record+verdict or None)."""
    try:
        from veles_tpu.config import root
        if not root.common.perf.get("enabled", True):
            return None
        return default().append(metric, value, **kwargs)
    except Exception:   # noqa: BLE001 — never fail the caller
        return None
