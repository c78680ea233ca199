"""Predicted-vs-measured MFU / roofline check for the staged step.

TVM's central lesson (PAPERS.md) applied to the training loop: a cost
model is only trustworthy when fed measured runtimes.  PR 2's static
model (``tools/cost_model.py``) prices bench phases offline; this module
prices the *actual configured* staged step — the layers the trainer
built, the minibatch the loader feeds — and, at every train-class sweep,
compares the utilization the chip actually delivered against that
prediction.  A measured/predicted ratio below a configurable fraction
(``root.common.telemetry.mfu_warn_fraction``, default 0.5) raises a
warning metric: the "your step is leaving the roofline" tripwire a
production fleet scrapes.

FLOP counting follows the repo's analytic conventions
(:mod:`veles_tpu.ops.flops`: fwd+bwd = 3x fwd matmul FLOPs, no padding
in the numerator); the *time* prediction pads to the MXU grid and uses
the fitted device constants from ``tools/cost_model.py`` when that
module is importable (repo checkouts), else the baked-in mirror — same
numbers, so predictions agree either way.

A utilization needs a peak, and the peak is the LIVE device's row in
``ops.flops.PEAK_BF16_TFLOPS``.  A device without a row — the CPU the
tests run on included — gets no MFU at all: no gauge, no record, no
ledger row.  A CPU step time over a TPU's peak is not a small MFU, it
is a wrong one."""

import math

#: v5e cost-model constants — MUST mirror tools/cost_model.py (which is
#: preferred at runtime when importable; this copy only covers installed
#: packages without the repo's tools/ directory).  Fitted 2026-08-01,
#: not re-measured on this machine (``t_dispatch`` above all: it priced
#: that date's remote chip); ROADMAP D4 removes them.  The PEAK is not
#: here: it comes from the peaks table, per device.
_FALLBACK = {
    "eff_mxu": 0.440, "hbm_bw": 819e9, "eff_bw": 0.8,
    "t_kernel": 4.3e-6, "h_step": 67e-6, "t_dispatch": 4.09e-3,
}


def _live_device_kind():
    import jax
    return jax.devices()[0].device_kind


def device_model(device_kind=None):
    """Cost-model constants with the peak of ``device_kind`` (default:
    the live device), or None when the peaks table has no row for it.
    The fitted constants come from ``tools.cost_model`` when the repo's
    tools/ is importable, else the baked-in mirror."""
    from veles_tpu.ops.flops import peak_bf16_tflops
    if device_kind is None:
        device_kind = _live_device_kind()
    peak = peak_bf16_tflops(device_kind)
    if peak is None:
        return None
    try:
        from tools.cost_model import device_constants
        fitted = device_constants()
    except Exception:   # noqa: BLE001 — installed without tools/
        fitted = _FALLBACK
    return dict(fitted, name=str(device_kind), peak_flops=peak * 1e12)


def _pad(x, m=128):
    return int(math.ceil(x / m)) * m


def _tree_elems(tree):
    n = 0
    stack = [tree]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            stack.extend(node.values())
        elif node is not None:
            size = getattr(node, "size", None)
            if size is not None:
                n += int(size)
    return n


def _layer_matmuls(layer, batch):
    """[(m, k, n)] for the forward matmuls of one layer, or None when
    the layer has no recognized matmul shape."""
    if hasattr(layer, "n_in") and layer.output_shape:   # dense family
        n_out = 1
        for d in layer.output_shape:
            n_out *= int(d)
        return [(batch, int(layer.n_in), n_out)]
    if hasattr(layer, "kx") and hasattr(layer, "n_kernels") \
            and layer.output_shape and len(layer.output_shape) == 3:
        ho, wo, _ = layer.output_shape        # conv via im2col mapping
        k = int(layer.n_channels) * int(layer.kx) * int(layer.ky)
        return [(batch * int(ho) * int(wo), k, int(layer.n_kernels))]
    return None


def price_staged_step(trainer, device_kind=None):
    """Roofline pricing of ONE train step of ``trainer``'s staged chain
    on ``device_kind`` (default: the live device): analytic FLOPs
    (numerator), padded-MXU compute time, optimizer HBM traffic,
    kernel/dispatch/host floors — the per-workflow analogue of
    ``tools/cost_model.predict_mlp``.  None for a device the peaks
    table does not list."""
    dm = device_model(device_kind)
    if dm is None:
        return None
    batch = int(trainer.loader.minibatch_size)
    flops_fwd = 0.0          # analytic (MFU numerator convention)
    padded_fwd = 0.0         # what the MXU actually grinds through
    param_elems = 0
    n_param_layers = 0
    for layer in trainer.layers:
        if getattr(layer, "has_params", False):
            n_param_layers += 1
            param_elems += _tree_elems(trainer.params.get(layer.name))
        mms = _layer_matmuls(layer, batch)
        if mms is None:
            if getattr(layer, "has_params", False):
                # unrecognized parameterized layer: dense-equivalent
                # floor — every param participates in one MAC per sample
                n = _tree_elems(trainer.params.get(layer.name))
                flops_fwd += 2.0 * batch * n
                padded_fwd += 2.0 * batch * n
            continue
        for m, k, n in mms:
            flops_fwd += 2.0 * m * k * n
            padded_fwd += 2.0 * _pad(m) * _pad(k) * _pad(n)
    flops_step = 3.0 * flops_fwd            # fwd + bwd = 3x fwd
    # optimizer traffic, f32 sgd-momentum floor: w rd/wr, m rd/wr,
    # grad rd = 5 passes (adam adds 2 more; a floor, not a ceiling)
    hbm_bytes = param_elems * 4 * 5
    t_compute = 3.0 * padded_fwd / (dm["peak_flops"] * dm["eff_mxu"])
    t_hbm = hbm_bytes / (dm["hbm_bw"] * dm["eff_bw"])
    # fused-kernel floor: ~7 kernels per parameterized layer (fwd 2 +
    # bwd 3 + update 2) + ~8 for loss/stats (cost_model.predict_mlp)
    kernels = 7 * n_param_layers + 8
    spd = max(int(getattr(trainer, "steps_per_dispatch", 1)), 1)
    predicted = (max(t_compute, t_hbm) + kernels * dm["t_kernel"]
                 + dm["h_step"] + dm["t_dispatch"] / spd)
    return {
        "device": dm["name"],
        "peak_flops": dm["peak_flops"],
        "flops_per_step": flops_step,
        "hbm_bytes_per_step": hbm_bytes,
        "param_elems": param_elems,
        "predicted_step_s": predicted,
        "predicted_mfu": flops_step / (predicted * dm["peak_flops"]),
    }


def check_step(trainer, steps, wall_s, registry=None):
    """Compare a finished train-class sweep (``steps`` staged steps in
    ``wall_s`` wall seconds) against :func:`price_staged_step`.  Updates
    the ``veles_mfu_*`` gauges, emits a ``kind="mfu"`` record carrying
    BOTH ``predicted`` and ``measured``, banks predicted & measured in
    the performance ledger (telemetry.ledger) with the step-anatomy
    decomposition attached, and fires the shortfall warning.  The
    one-shot warning routes through the sentinel's drift band: with
    ledger history, "shortfall" means the measured MFU fell outside
    its own MAD noise band on the worse side (noise-aware); only a
    history-less first run falls back to the bare
    ``mfu_warn_fraction`` compare.

    On a device the peaks table does not list (the CPU included) this
    does nothing and returns None: no MFU value exists there."""
    if registry is None:
        from veles_tpu.telemetry import registry
    if not steps or wall_s <= 0.0:
        return None
    if "_mfu_pricing_" not in trainer.__dict__:
        trainer.__dict__["_mfu_pricing_"] = price_staged_step(trainer)
    pricing = trainer.__dict__["_mfu_pricing_"]
    if pricing is None:
        return None
    measured_step_s = wall_s / steps
    measured_mfu = (pricing["flops_per_step"]
                    / (measured_step_s * pricing["peak_flops"]))
    predicted_mfu = pricing["predicted_mfu"]
    ratio = measured_mfu / predicted_mfu if predicted_mfu else 0.0
    from veles_tpu.config import root
    frac = float(root.common.telemetry.get("mfu_warn_fraction", 0.5))
    # bank the sweep: measured MFU (with the anatomy components) and
    # the step time, each assessed against their ledger history — the
    # drift band below reads the returned verdict
    from veles_tpu.telemetry import anatomy, ledger
    comps = anatomy.step_components(trainer, steps, wall_s, registry)
    wl = str(getattr(trainer, "name", "trainer"))
    banked = ledger.record_value(
        "train_mfu", measured_mfu, workload=wl, unit="MFU",
        better="higher", source="mfu.check_step",
        predicted=predicted_mfu, ratio=ratio)
    ledger.record_value(
        "train_step_ms", measured_step_s * 1e3, workload=wl,
        unit="ms", source="mfu.check_step", components=comps,
        predicted=pricing["predicted_step_s"] * 1e3)
    verdict = (banked or {}).get("verdict") or {}
    if verdict.get("status") in ("regression", "improved", "ok"):
        # history exists: the band verdict IS the shortfall call
        warned = verdict["status"] == "regression"
    else:
        warned = ratio < frac
    registry.gauge("veles_mfu_predicted",
                   "roofline-predicted MFU of the staged step").set(
        predicted_mfu)
    registry.gauge("veles_mfu_measured",
                   "measured MFU of the staged step").set(measured_mfu)
    registry.gauge("veles_mfu_ratio",
                   "measured/predicted MFU").set(ratio)
    if warned:
        registry.counter(
            "veles_mfu_shortfall_total",
            "train sweeps whose measured MFU fell below "
            "mfu_warn_fraction of the prediction").inc()
        if not trainer.__dict__.get("_mfu_warned_"):
            trainer.__dict__["_mfu_warned_"] = True
            if verdict.get("status") == "regression":
                trainer.warning(
                    "measured MFU %.3g fell %.1f%% below its ledger "
                    "history median %.3g — outside the MAD noise band "
                    "(%s roofline predicted %.3g; "
                    "root.common.perf.band_mads tunes the band)",
                    measured_mfu,
                    -100.0 * (verdict.get("drift") or 0.0),
                    verdict.get("median") or 0.0, pricing["device"],
                    predicted_mfu)
            else:
                trainer.warning(
                    "measured MFU %.3g is %.2fx the %s roofline "
                    "prediction %.3g (threshold %.2f) — the step is "
                    "off the modeled roofline "
                    "(root.common.telemetry.mfu_warn_fraction tunes "
                    "this tripwire)",
                    measured_mfu, ratio, pricing["device"],
                    predicted_mfu, frac)
    return registry.emit(
        "mfu", predicted=predicted_mfu, measured=measured_mfu,
        ratio=ratio, warned=warned, warn_fraction=frac,
        device=pricing["device"], peak_flops=pricing["peak_flops"],
        flops_per_step=pricing["flops_per_step"],
        predicted_step_ms=pricing["predicted_step_s"] * 1e3,
        measured_step_ms=measured_step_s * 1e3, steps=steps)
