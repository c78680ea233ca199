"""Step-anatomy attribution: decompose the training step the way PR 18
decomposed serving requests.

PR 18 taught the serving plane to partition every request's
admitted→finished span into prefill/decode phases that must sum
exactly; this module gives the *training* step the same treatment, so
a ledger regression on ``train_step_ms`` names a component instead of
"step got slower".  Components, and where each number comes from:

* ``compile_ms``   — measured: the compile listener's
  ``veles_compile_seconds_total`` counter delta since the previous
  sweep (compile_cache.py), amortized per step.  Nonzero means the
  sweep paid a recompile — the classic silent step-time cliff.
* ``host_ms``      — priced: the calibrated per-step host floor
  (``h_step``) from tools/cost_model.py's device constants.
* ``dispatch_ms``  — priced: the dispatch-queue floor
  (``t_dispatch / steps_per_dispatch``) — the number the
  steps-per-dispatch knob exists to amortize.
* ``collective_ms`` — measured: the multi-host heartbeat's
  sync-point cost when a pod is up (0 on one host).
* ``compute_ms``   — residual: measured step time minus everything
  above, floored at 0 — device compute plus anything the model
  doesn't price (the honest "unexplained" bucket rides here, exactly
  like cost_model's postdiction residuals).

Each measured component is priced against the cost model's floors
(:func:`tools.cost_model.anatomy_floors` when the repo's tools/ is
importable, else the same baked-in v5e constants mfu.py carries), so
``attribute()`` can say WHICH share outgrew its floor.  Stdlib-only,
fail-soft: attribution rides the telemetry path and must never kill
the loop it observes."""

import threading

from veles_tpu.telemetry import mfu

#: component order is the display/report order (docs/perf.md)
COMPONENTS = ("compile_ms", "host_ms", "dispatch_ms",
              "collective_ms", "compute_ms")

_state_lock = threading.Lock()
_last_compile_s = {}   # id(registry) -> cumulative compile seconds


def predicted_floors(steps_per_dispatch=1, kernels=8,
                     compute_ms=None):
    """Per-component predicted floors in ms, from the calibrated
    device constants (tools/cost_model.anatomy_floors preferred — the
    single calibration source — else mfu's baked-in mirror)."""
    try:
        from tools.cost_model import anatomy_floors
        floors = anatomy_floors(steps_per_dispatch=steps_per_dispatch,
                                kernels=kernels)
    except Exception:   # noqa: BLE001 — installed without tools/
        dm = mfu._FALLBACK
        spd = max(int(steps_per_dispatch), 1)
        floors = {"compile_ms": 0.0,
                  "host_ms": dm["h_step"] * 1e3,
                  "dispatch_ms": dm["t_dispatch"] / spd * 1e3,
                  "collective_ms": 0.0,
                  "compute_ms": kernels * dm["t_kernel"] * 1e3}
    if compute_ms is not None:
        floors["compute_ms"] = compute_ms
    return floors


def _compile_delta_s(registry, steps):
    """Compile seconds this registry accumulated since the previous
    sweep, amortized per step (the compile listener's counter is
    cumulative; the anatomy wants per-sweep)."""
    total = 0.0
    try:
        for sample in registry.snapshot():
            if sample.get("name") == "veles_compile_seconds_total":
                total += float(sample.get("value", 0.0))
    except Exception:   # noqa: BLE001 — observational
        return 0.0
    with _state_lock:
        prev = _last_compile_s.get(id(registry), 0.0)
        _last_compile_s[id(registry)] = total
    return max(total - prev, 0.0) / max(steps, 1)


def _collective_ms(registry, steps):
    """Per-step collective-wait proxy: the multi-host heartbeat's
    straggler spread (``veles_step_wall_skew_seconds``,
    telemetry.health) amortized over the sweep — the time the
    allgather spent waiting for the slowest host; 0 on one host."""
    try:
        for sample in registry.snapshot():
            if sample.get("name") == "veles_step_wall_skew_seconds":
                return (float(sample.get("value", 0.0))
                        / max(steps, 1) * 1e3)
    except Exception:   # noqa: BLE001
        pass
    return 0.0


def step_components(trainer, steps, wall_s, registry):
    """Measured per-step component decomposition (ms) of one finished
    class sweep, ready to ride a ledger record's ``components``
    field.  Fail-soft: returns None rather than raising."""
    try:
        if not steps or wall_s <= 0.0:
            return None
        step_ms = wall_s / steps * 1e3
        spd = max(int(getattr(trainer, "steps_per_dispatch", 1)), 1)
        floors = predicted_floors(steps_per_dispatch=spd)
        compile_ms = _compile_delta_s(registry, steps) * 1e3
        host_ms = min(floors["host_ms"], step_ms)
        dispatch_ms = min(floors["dispatch_ms"],
                          max(step_ms - host_ms - compile_ms, 0.0))
        collective_ms = min(_collective_ms(registry, steps),
                            max(step_ms - host_ms - dispatch_ms
                                - compile_ms, 0.0))
        compute_ms = max(step_ms - compile_ms - host_ms - dispatch_ms
                         - collective_ms, 0.0)
        return {"compile_ms": round(compile_ms, 6),
                "host_ms": round(host_ms, 6),
                "dispatch_ms": round(dispatch_ms, 6),
                "collective_ms": round(collective_ms, 6),
                "compute_ms": round(compute_ms, 6)}
    except Exception:   # noqa: BLE001 — observe, never abort
        return None


def attribute(measured, predicted=None):
    """(component, excess_ms) whose measured time exceeds its priced
    floor the most — the drift-attribution verdict.  None when
    nothing exceeds its floor (the step is AT the model)."""
    if not isinstance(measured, dict):
        return None
    if predicted is None:
        predicted = predicted_floors()
    worst, excess = None, 0.0
    for name in COMPONENTS:
        m = measured.get(name)
        if not isinstance(m, (int, float)):
            continue
        delta = m - float(predicted.get(name, 0.0))
        if delta > excess:
            worst, excess = name, delta
    return (worst, excess) if worst else None
