"""Benchmark — prints exactly ONE JSON line on stdout, and exits
non-zero when the probe or any phase failed.

Headline metric: the reference's own DeviceBenchmark methodology
(square 3001x3001 f32 gemm, chained repeats — ref
veles/accelerated_units.py:706-824, veles/backends.py:672-731), which the
reference ships a measured number for: 0.1642 s/multiply ~= 329 GFLOP/s on
a GeForce GTX TITAN (devices/device_infos.json, BASELINE.md).
``vs_baseline`` is our f32 GFLOP/s over that 329.

Every phase runs in its OWN subprocess with a watchdog timeout and
backend-init failures are retried with backoff.  The final JSON line is
emitted no matter what, with an ``error`` field naming what failed —
and a failed run is a failed run: no number is carried over from an
earlier one, the exit code is non-zero, and a probe that finds anything
but a TPU is a failure.  The orchestrating parent never calls into jax
(one process owns the chip at a time: the phase child); the probe child
reports the device, and the ledger rows are keyed by what it said.
Secondary numbers (MLP step time, AlexNet samples/sec, bf16 gemm, Pallas
flash + ring-attention on-chip smokes) ride along in the same JSON.

Usage:  python bench.py            # orchestrator (the driver runs this)
        python bench.py --phase X  # internal: one phase, child process
"""

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_GEMM_GFLOPS = 329.0   # GTX TITAN, f32, ref devices/device_infos.json

#: (name, watchdog seconds).  Order matters: the headline gemm goes first
#: so a later hang can never cost us the one number BASELINE demands; the
#: LM flagships and flash head-to-head come next (round-3 priority:
#: MFU-credible numbers on record) — they are also the most hang-prone,
#: so the default budget covers a full worst-case LM+flash stall while
#: still reaching the cheap phases behind them.
#: gemm runs first: its success gates banking the run in the ledger.
PHASES = [
    ("gemm", 420),
    ("lm_large", 900),
    ("lm", 600),
    ("flash", 600),
    ("serve", 600),
    ("mlp", 420),
    ("alexnet", 600),
    ("beam", 420),
    ("ring", 420),
    ("kohonen", 300),
]


def _causal_attn_flops(b, h, t, d):
    """Shared convention — see veles_tpu/ops/flops.py."""
    from veles_tpu.ops.flops import causal_attn_flops
    return causal_attn_flops(b, h, t, d)


def _target(metric, default):
    """Pre-registered goal from the declared target registry
    (telemetry.ledger.TARGETS) — the registry is the one source of
    truth, phases only *report* the bar they are judged against.
    Fail-soft: a broken install must not cost the measurement."""
    try:
        from veles_tpu.telemetry import ledger as _ledgermod
        return _ledgermod.target_goal(metric, default)
    except Exception:  # noqa: BLE001 — fail-soft by contract
        return default


def _peak_bf16():
    """bf16 peak TFLOP/s of device 0 — the MFU denominator — from the
    one peaks table (veles_tpu.ops.flops.PEAK_BF16_TFLOPS).  A device
    that is not in the table is an error, not a default: a utilization
    against a made-up peak is worse than none."""
    import jax
    from veles_tpu.ops.flops import peak_bf16_tflops
    kind = jax.devices()[0].device_kind
    peak = peak_bf16_tflops(kind)
    if peak is None:
        raise RuntimeError(
            "device_kind %r is not in ops.flops.PEAK_BF16_TFLOPS — no "
            "peak to price utilization against" % kind)
    return peak

#: stderr substrings that mean "backend init flake — worth retrying"
RETRYABLE = (
    "Unable to initialize backend",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "backend setup/compile error",
    "Socket closed",
    "failed to connect",
)

_BACKOFF = (5, 25, 60)          # seconds between attempts (>=3 over ~2 min)
_RESULT_TAG = "PHASE_RESULT "


def _log(msg):
    print("[bench] %s" % msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Phase implementations — each runs inside a child process.
# --------------------------------------------------------------------------

def _block(x):
    import jax
    return jax.block_until_ready(x)


def _fetch_sync(wf, cls=2):
    """Device barrier by FETCHING the loss scalar: the VALUE of the
    final step's loss cannot exist before every queued predecessor
    executed, so a device_get is transitively honest.  Adopted
    2026-08-01 because ``block_until_ready`` returned early on the
    remote backend of that date; on the current machine the two agree
    (chip_smoke.py's barrier line) — ROADMAP S0/D3 decide its fate."""
    import jax
    return float(jax.device_get(wf.trainer.class_stats[cls]["loss"]))


def _timed_steps(wf, steps, cls=2):
    """Wall seconds for ``steps`` loader+trainer steps, fetch-synced.

    The async enqueues inside the loop are free; the closing fetch
    forces the whole dependency chain.  The returned time includes one
    fetch round trip — callers timing sub-100ms regions should
    difference two calls (slope) so the constant cancels."""
    tr = wf.trainer
    _fetch_sync(wf, cls)                  # drain anything outstanding
    t0 = time.perf_counter()
    for _ in range(steps):
        wf.loader.run()
        tr.run()
    tr.flush()
    _fetch_sync(wf, cls)
    return time.perf_counter() - t0


def _per_step_ms_slope(wf, steps, cls=2, reps=3):
    """Per-step ms via two-point slope — T(2k) - T(k) over k steps —
    so the constant fetch round trip and enqueue overheads cancel.
    Median of ``reps`` slope samples; callers pick ``steps`` so the
    differenced region is well above timing jitter (>= ~200 ms).
    A non-positive median slope means the region was jitter-dominated:
    fail LOUDLY (the fail-soft runner reports the phase error) rather
    than publish another physically-impossible throughput."""
    slopes = []
    for _ in range(reps):
        t1 = _timed_steps(wf, steps, cls)
        t2 = _timed_steps(wf, 2 * steps, cls)
        slopes.append((t2 - t1) / steps * 1e3)
    med = sorted(slopes)[len(slopes) // 2]
    if med <= 0.0:
        raise RuntimeError(
            "slope timing jitter-dominated (samples %s ms/step over "
            "%d steps) — raise `steps`" % (slopes, steps))
    return med


def _norm_operand(n):
    """n x n operand pre-normalized by its dominant singular value
    (host-side power iteration) so a y <- y @ a chain needs NO per-iter
    rescale op: the timed loop is pure MXU matmuls."""
    import numpy as np

    a = np.random.RandomState(0).rand(n, n).astype(np.float32)
    v = np.random.RandomState(1).rand(n).astype(np.float32)
    for _ in range(8):
        v = a.T @ (a @ v)
        v /= np.linalg.norm(v)
    return a / float(np.linalg.norm(a @ v))


def phase_gemm():
    """Chained-matmul loop *inside one jit dispatch* (lax.scan): measures
    device compute the way the reference's kernel timer did, immune to
    per-dispatch overhead and to result caching (each multiply consumes
    the previous one's output).

    f32 path uses precision="highest" (true f32 accumulation, matching the
    reference's PRECISION_LEVEL 0 float math).  The bf16 path is the TPU's
    native MXU number — reported alongside, since bf16 is what real
    training on this hardware uses."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(n, dtype, precision, iters=20):
        a = jnp.asarray(_norm_operand(n)).astype(dtype)

        def body(y, _):
            return jnp.dot(y, a, precision=precision), None

        f = jax.jit(lambda y: lax.scan(body, y, None, length=iters)[0],
                    donate_argnums=(0,))
        # the seed must not alias the captured multiplicand: f donates it
        y = _block(f(jnp.copy(a)))         # compile + warmup
        dt = float("inf")
        for _ in range(3):                  # best of 3 (shared-chip noise)
            t0 = time.perf_counter()
            y = _block(f(y))
            dt = min(dt, (time.perf_counter() - t0) / iters)
        return dt, 2.0 * n * n * n / dt / 1e9

    # baseline-comparable: the reference's exact 3001^2 f32 methodology
    dt32, gf32 = run(3001, jnp.float32, "highest")
    _log("gemm 3001^2 f32(highest): %.4f s/multiply, %.1f GFLOP/s"
         % (dt32, gf32))
    # MXU-native: large bf16 gemm, what real TPU training runs on
    dt16, gf16 = run(8192, jnp.bfloat16, "default", iters=10)
    peak = _peak_bf16()
    mfu = gf16 / 1e3 / peak
    _log("gemm 8192^2 bf16: %.4f s/multiply, %.1f GFLOP/s (MFU %.1f%% of "
         "%s TF/s peak)" % (dt16, gf16, mfu * 100, peak))
    # precision-level overhead at the reference's own 3001^2 shape
    # (BASELINE rows: Kahan level 1 = +9%, multipartial level 2 = +90%
    # on the GTX TITAN).  On TPU, level 0 (bf16 compute) already
    # accumulates in f32 ON THE MXU — the exactness Kahan bought in
    # software is hardware-native and costs nothing; the only "more
    # precision, slower" step left is f32 COMPUTE (level >= 1), whose
    # measured overhead vs bf16 is reported here against those rows.
    dt16s, gf16s = run(3001, jnp.bfloat16, "default")
    overhead = (dt32 / dt16s - 1.0) * 100.0 if dt16s else 0.0
    _log("gemm 3001^2 bf16: %.4f s/multiply, %.1f GFLOP/s -> f32 "
         "precision-level overhead +%.0f%% (ref Kahan +9%%, "
         "multipartial +90%% — both obsolete: f32 accumulation is "
         "MXU-native at level 0)" % (dt16s, gf16s, overhead))
    return {"s_per_multiply": dt32, "gflops": gf32, "bf16_gflops": gf16,
            "bf16_mfu": mfu, "peak_bf16_tflops": peak,
            "bf16_3001_gflops": gf16s,
            "precision_overhead_pct": overhead,
            "device": str(jax.devices()[0])}


def phase_gemmtune():
    """Manual diagnostic (not in PHASES): where do the missing bf16 MFU
    points go?  Sweeps size x iters x chain shape — serial dependence
    (y@a), independent pairs (two live chains interleaved), and an
    f32-output variant — so dispatch amortization, scheduling stalls and
    output-write bandwidth can be told apart."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    peak = _peak_bf16()
    out = {}

    def measure(f, seed, iters, flops_per_iter):
        y = _block(f(seed))
        dt = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            y = _block(f(y))
            dt = min(dt, (time.perf_counter() - t0) / iters)
        return flops_per_iter / dt / 1e12

    for n in (4096, 8192, 16384):
        a = jnp.asarray(_norm_operand(n)).astype(jnp.bfloat16)
        iters = max(10, int(3e12 / (2 * n ** 3)))   # ~3 TFLOP per dispatch
        flops = 2.0 * n ** 3

        f_ser = jax.jit(lambda y, a=a, it=iters: lax.scan(
            lambda y, _: (jnp.dot(y, a), None), y, None, length=it)[0],
            donate_argnums=(0,))
        tf_ser = measure(f_ser, jnp.copy(a), iters, flops)

        # two independent chains per scan step: exposes cross-matmul
        # overlap if the serial chain is scheduling-stalled
        f_par = jax.jit(lambda c, a=a, it=iters: lax.scan(
            lambda c, _: ((jnp.dot(c[0], a), jnp.dot(c[1], a)), None),
            c, None, length=it)[0], donate_argnums=(0,))
        tf_par = measure(f_par, (jnp.copy(a), jnp.copy(a.T)), iters,
                         2 * flops)

        # f32 accumulator output (halved output-write count vs two bf16
        # stores is NOT the point — the doubled store width is: if the
        # serial chain is output-write bound this variant drops hardest)
        f_f32 = jax.jit(lambda y, a=a, it=iters: lax.scan(
            lambda y, _: (jnp.dot(y.astype(jnp.bfloat16), a,
                                  preferred_element_type=jnp.float32),
                          None), y, None, length=it)[0],
            donate_argnums=(0,))
        tf_f32 = measure(f_f32, jnp.copy(a).astype(jnp.float32), iters,
                         flops)

        out[n] = {"serial_tf": round(tf_ser, 1), "pair_tf": round(tf_par, 1),
                  "f32out_tf": round(tf_f32, 1), "iters": iters}
        _log("gemmtune n=%d iters=%d: serial %.1f TF/s (%.1f%%), "
             "pairs %.1f TF/s (%.1f%%), f32-out %.1f TF/s"
             % (n, iters, tf_ser, 100 * tf_ser / peak,
                tf_par, 100 * tf_par / peak, tf_f32))
    return {"peak": peak, "sweep": {str(k): v for k, v in out.items()}}


def phase_mlp():
    """MNIST 784-100-10 step time (BASELINE 'MNIST MLP step time'), plus
    the fused steps_per_dispatch=20 sweep (k minibatches per host→device
    round trip — the dispatch-amortized number real training runs at)."""
    import numpy as np
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import mnist_mlp

    def build(k):
        prng.seed_all(3)
        x = np.random.RandomState(0).rand(2000, 784).astype(np.float32)
        y = np.random.RandomState(1).randint(0, 10, 2000).astype(np.int32)
        loader = FullBatchLoader(None, data=x, labels=y, minibatch_size=100,
                                 class_lengths=[0, 0, 2000])
        wf = StandardWorkflow(layers=mnist_mlp(), loader=loader,
                              decision_config={"max_epochs": 1},
                              steps_per_dispatch=k, name="bench-mlp")
        wf.initialize()
        return wf

    def measure(wf, steps):
        for _ in range(60):             # compile + warmup (covers sweep)
            wf.loader.run()
            wf.trainer.run()
        wf.trainer.flush()
        _block(wf.trainer.class_stats[2]["loss"])
        # sub-ms steps: slope timing, the fetch RTT constant cancels;
        # step counts sized so the differenced region clears jitter
        return _per_step_ms_slope(wf, steps)

    step_ms = measure(build(1), steps=200)
    fused_ms = measure(build(20), steps=2000)
    _log("mnist mlp 784-100-10 step: %.3f ms per-step, %.3f ms fused k=20"
         % (step_ms, fused_ms))
    return {"step_ms": step_ms, "step_fused_ms": fused_ms}


def phase_alexnet():
    """AlexNet train samples/sec/chip on synthetic 227x227x3 data."""
    import numpy as np
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import alexnet

    prng.seed_all(4)
    batch, steps = 256, 10   # 256 keeps the MXU fed (~1.8x batch 64)
    n = batch * 2
    x = np.random.RandomState(0).rand(n, 227, 227, 3).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 1000, n).astype(np.int32)
    loader = FullBatchLoader(None, data=x, labels=y, minibatch_size=batch,
                             class_lengths=[0, 0, n])
    wf = StandardWorkflow(layers=alexnet(), loader=loader,
                          decision_config={"max_epochs": 1000},
                          name="bench-alexnet")
    wf.initialize()
    wf.loader.run()
    wf.trainer.run()          # compile
    _block(wf.trainer.class_stats[2]["loss"])
    # three back-to-back repeats: the r2→r3 "regression" (8,617 → 7,430)
    # was a cross-session comparison with no variance band — same-session
    # repeats make every future number interpretable (median headline,
    # min/max band published alongside)
    reps = []
    for _ in range(3):
        # a step is comparable to one fetch round trip: slope timing
        reps.append(batch / _per_step_ms_slope(wf, steps) * 1e3)
    sps = sorted(reps)[1]
    _log("alexnet synthetic: %.1f samples/sec/chip "
         "(median of 3; band %.1f-%.1f, spread %.1f%%)"
         % (sps, min(reps), max(reps),
            (max(reps) - min(reps)) / sps * 100))
    return {"samples_per_sec": sps, "band_low": min(reps),
            "band_high": max(reps)}


def _lm_train_flops_per_token(d_model, n_layers, seq, vocab, d_ff=None,
                              n_heads=None, n_kv_heads=None):
    """Shared convention — see veles_tpu/ops/flops.py."""
    from veles_tpu.ops.flops import lm_train_flops_per_token
    return lm_train_flops_per_token(d_model, n_layers, seq, vocab,
                                    d_ff=d_ff, n_heads=n_heads,
                                    n_kv_heads=n_kv_heads)


def _run_lm(tag, zoo_kwargs, batch, seq, steps, steps_per_dispatch,
            vocab):
    """Shared LM-throughput harness: train ``steps`` minibatches through
    the StandardWorkflow hot loop, report tokens/sec and model FLOPs
    utilization against the detected chip peak."""
    import jax
    import numpy as np
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import transformer_lm

    prng.seed_all(5)
    n = batch * 4
    toks = np.random.RandomState(0).randint(
        0, vocab, (n, seq)).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=batch,
                             class_lengths=[0, 0, n])
    wf = StandardWorkflow(
        layers=transformer_lm(vocab_size=vocab, **zoo_kwargs),
        loader=loader, loss="lm",
        gd_defaults={"clip_norm": 1.0},
        decision_config={"max_epochs": 1000},
        steps_per_dispatch=steps_per_dispatch, name="bench-" + tag)
    wf.initialize()
    n_params = sum(int(np.prod(p.shape))
                   for lp in wf.trainer.params.values()
                   for p in jax.tree_util.tree_leaves(lp))
    for _ in range(2 * steps_per_dispatch):  # compile + warmup (2 sweeps)
        wf.loader.run()
        wf.trainer.run()
    wf.trainer.flush()
    _block(wf.trainer.class_stats[2]["loss"])
    ms_step = _per_step_ms_slope(wf, steps)
    tps = batch * seq / ms_step * 1e3
    fpt = _lm_train_flops_per_token(
        zoo_kwargs["d_model"], zoo_kwargs["n_layers"], seq, vocab,
        n_heads=zoo_kwargs.get("n_heads"),
        n_kv_heads=zoo_kwargs.get("n_kv_heads"))
    peak = _peak_bf16()
    mfu = tps * fpt / (peak * 1e12)
    _log("%s (%.1fM params, T=%d): %.0f tokens/sec/chip, "
         "%.1f ms/step, MFU %.1f%%"
         % (tag, n_params / 1e6, seq, tps, ms_step, mfu * 100))
    return {"tokens_per_sec": tps, "ms_per_step": ms_step,
            "mfu": mfu, "n_params": n_params,
            "peak_bf16_tflops": peak}


def phase_lm():
    """Causal transformer LM training throughput (tokens/sec/chip):
    GPT-style decoder (~25M params, T=1024, Pallas flash attention +
    fused FA2 backward, RoPE, GQA, AdamW with global-norm clipping, bf16
    MXU compute) through the SAME StandardWorkflow hot loop as every
    other model, with the fused k-step dispatch."""
    return _run_lm(
        "lm-25M",
        dict(d_model=512, n_heads=8, n_kv_heads=2, n_layers=8,
             dropout=0.0, impl="flash", pos="rope", solver="adamw",
             lr=1e-3),
        batch=8, seq=1024, steps=20, steps_per_dispatch=5, vocab=8192)


def phase_lm_large():
    """The MFU-credible flagship (round-3 verdict item #4): GPT-2-small
    class — 124M params, d=768, 12 heads, 12 layers, T=1024, vocab
    50304 (MXU-friendly multiple of 128), tied embeddings, flash
    attention + fused backward, RoPE, AdamW + global-norm clip, bf16
    compute, fused 4-step dispatch.  Target: >= 40% MFU single-chip.

    Walks a three-rung memory ladder, stepping down only on OOM:
    (remat="dots", batch 16) — selective dots_saveable checkpointing,
    no recompute FLOPs burned, the MFU-preserving first choice —
    then (full remat, batch 16), then (full remat, batch 8).  The
    result records which rung produced the headline number
    (``remat``/``batch`` keys)."""
    import gc

    base = dict(d_model=768, n_heads=12, n_layers=12, dropout=0.0,
                impl="flash", pos="rope", solver="adamw", lr=6e-4,
                tie_embeddings=True)
    # MFU ladder: selective remat first — "dots" keeps matmul outputs,
    # so the backward skips the recompute FLOPs that full remat burns
    # (recompute never counts toward MFU).  Full remat at b16, then b8,
    # are the progressively-smaller-memory fallbacks.
    from veles_tpu.ops.flops import LM_LARGE_LADDER
    ladder = [(remat, batch, steps)
              for remat, batch, steps, _ in LM_LARGE_LADDER]
    try:  # the rung order is model-ranked; log the predicted MFUs
        from tools.cost_model import predict_lm_large_ladder
        _log("lm_large ladder predicted MFU: %s"
             % ["%s/b%d: %.1f%%" % (r["remat"], r["batch"],
                                    100 * r["mfu"])
                for r in predict_lm_large_ladder()])
    except Exception:  # noqa: BLE001 — advisory only
        pass
    for i, (remat, batch, steps) in enumerate(ladder):
        try:
            return dict(_run_lm("lm-124M[remat=%s,b%d]" % (remat, batch),
                                dict(base, remat=remat), batch=batch,
                                seq=1024, steps=steps,
                                steps_per_dispatch=4, vocab=50304),
                        batch=batch, remat=str(remat))
        except Exception as e:  # noqa: BLE001 — RESOURCE_EXHAUSTED
            if i == len(ladder) - 1 or (
                    "RESOURCE_EXHAUSTED" not in str(e)
                    and "Out of memory" not in str(e)):
                raise
            _log("lm_large remat=%s b%d OOM — next rung" % (remat, batch))
        # retry OUTSIDE the except block: an in-flight exception's
        # traceback would pin the failed attempt's device buffers
        gc.collect()


def _chain_attn(attn_fn, q, k, v, iters, grad=False):
    """True kernel-time harness: ``iters`` attention calls chained INSIDE
    one jit dispatch (each call consumes the previous output as q — same
    shape), so per-dispatch latency amortizes away (a kernel that runs
    for microseconds cannot be timed per dispatch).  With
    ``grad`` the chain feeds dQ back as the next q (fused backward
    timing).  Returns ms per single attention call (fwd or fwd+bwd)."""
    import jax
    from jax import lax

    import jax.numpy as jnp

    if grad:
        # FULL backward on both contenders — dQ and dK/dV (argnums=0
        # alone would let XLA dead-code the dK/dV matmuls and bias the
        # head-to-head).  dQ feeds back as the next chain link; dK/dV
        # stay live through cheap elementwise accumulators.
        g = jax.grad(
            lambda q_, k_, v_: attn_fn(q_, k_, v_).sum(),
            argnums=(0, 1, 2))

        def body(carry, _):
            y, ak, av = carry
            dq, dk, dv = g(y, k, v)
            return (dq.astype(y.dtype), ak + dk, av + dv), None

        def chain(y):
            (y, ak, av), _ = lax.scan(
                body, (y, jnp.zeros_like(k), jnp.zeros_like(v)), None,
                length=iters)
            return y, ak, av
    else:
        def body(y, _):
            return attn_fn(y, k, v).astype(y.dtype), None

        def chain(y):
            return lax.scan(body, y, None, length=iters)[0]

    f = jax.jit(chain, donate_argnums=(0,))
    out = _block(f(jnp.copy(q)))           # compile + warmup
    y = out[0] if grad else out
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = _block(f(y))
        y = out[0] if grad else out
        dt = min(dt, (time.perf_counter() - t0) / iters)
    return dt * 1e3


def phase_flash():
    """Pallas flash-attention kernel ON HARDWARE: correctness vs the
    naive reference, then chained in-jit timing (fwd f32/bf16, fused
    bwd, T=8192 long context) HEAD-TO-HEAD against XLA's O(T²) native
    attention — the number that decides whether the kernel earns its
    keep (round-2 verdict item #2)."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.attention import attention
    from veles_tpu.ops.pallas.flash import flash_attention

    platform = jax.default_backend()
    key = jax.random.key(0)
    b, h, t, d = 4, 8, 1024, 128
    q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.float32) * 0.1
               for kk in jax.random.split(key, 3))
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa
    naive = lambda q, k, v: attention(q, k, v, causal=True)        # noqa
    ref = naive(q, k, v)
    err = float(jnp.max(jnp.abs(jax.jit(flash)(q, k, v) - ref)))
    if err > 5e-3:
        raise AssertionError("flash kernel mismatch: max_err=%g" % err)

    # causal attention matmul flops for one call (qk + pv, T²/2 each)
    flops = _causal_attn_flops(b, h, t, d)

    def tf(ms):
        return flops / (ms / 1e3) / 1e12 if ms else 0.0

    ms = _chain_attn(flash, q, k, v, iters=20)
    q16, k16, v16 = (x.astype(jnp.bfloat16) for x in (q, k, v))
    err16 = float(jnp.max(jnp.abs(
        jax.jit(flash)(q16, k16, v16).astype(jnp.float32) - ref)))
    if err16 > 0.05:
        raise AssertionError("bf16 flash mismatch: max_err=%g" % err16)
    ms16 = _chain_attn(flash, q16, k16, v16, iters=20)
    ms16_xla = _chain_attn(naive, q16, k16, v16, iters=20)

    # fused Pallas backward: correctness vs the naive gradient, then
    # chained fwd+bwd timing vs XLA differentiating its own attention
    gf = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash(q, k, v) ** 2), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        naive(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
    bwd_err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(gf, gr))
    if bwd_err > 5e-2:
        raise AssertionError("fused backward mismatch: %g" % bwd_err)
    ms_bwd = _chain_attn(flash, q16, k16, v16, iters=10, grad=True)
    ms_bwd_xla = _chain_attn(naive, q16, k16, v16, iters=10, grad=True)

    # long-context headline: one chip, T=8192 causal bf16 —
    # the O(T·block) VMEM tiling is what makes this shape possible.
    # Real-kernel only (interpret mode would outlive the watchdog).
    ms_long = ms_long_xla = ms_win = 0.0
    if platform == "tpu":
        bl, hl, tl, dl = 1, 8, 8192, 128
        ql, kl, vl = (jax.random.normal(kk, (bl, hl, tl, dl),
                                        jnp.bfloat16) * 0.1
                      for kk in jax.random.split(jax.random.key(2), 3))
        ms_long = _chain_attn(flash, ql, kl, vl, iters=10)
        fl = _causal_attn_flops(bl, hl, tl, dl)
        try:
            ms_long_xla = _chain_attn(naive, ql, kl, vl, iters=5)
        except Exception as e:  # noqa: BLE001 — XLA may OOM the T² matrix
            _log("naive XLA at T=8192 failed (%s) — flash-only number"
                 % type(e).__name__)
        _log("flash long-context T=8192 bf16: %.2f ms (%.1f TF/s "
             "causal-effective) vs XLA naive %.2f ms"
             % (ms_long, fl / (ms_long / 1e3) / 1e12, ms_long_xla))
        # sliding window at long context: the shrunken k-grid should
        # make this ~T/window times cheaper than full causal
        wfn = lambda q_, k_, v_: flash_attention(  # noqa: E731
            q_, k_, v_, causal=True, window=1024)
        ms_win = _chain_attn(wfn, ql, kl, vl, iters=10)
        _log("flash T=8192 window=1024 bf16: %.2f ms (%.1fx vs full "
             "causal)" % (ms_win, ms_long / ms_win if ms_win else 0.0))

    _log("pallas flash (4,8,1024,128) causal on %s, chained in-jit: "
         "fwd %.2f ms f32 | %.2f ms bf16 (%.1f TF/s) vs XLA %.2f ms | "
         "fwd+bwd %.2f ms vs XLA %.2f ms | errs fwd %.2e bwd %.2e"
         % (platform, ms, ms16, tf(ms16), ms16_xla, ms_bwd, ms_bwd_xla,
            err, bwd_err))
    return {"ms": ms, "ms_bf16": ms16, "ms_bf16_xla": ms16_xla,
            "tf_bf16": tf(ms16), "ms_bwd": ms_bwd,
            "ms_bwd_xla": ms_bwd_xla, "bwd_max_err": bwd_err,
            "max_err": err, "ms_long_t8192": ms_long,
            "ms_long_t8192_xla": ms_long_xla,
            "ms_long_t8192_w1024": ms_win, "platform": platform}


def phase_beam():
    """Long-context beam-search decode rate (T=4096, beam=8) vs greedy —
    the number that prices the per-step full-cache reorder documented at
    models/generate.py (O(T²·beam) HBM traffic per decode)."""
    import numpy as np
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import transformer_lm
    import jax.numpy as jnp

    prng.seed_all(9)
    # BENCH_BEAM_T: CPU smoke tests shrink the context (4095 scan
    # positions are a TPU-scale workload)
    t_max = int(os.environ.get("BENCH_BEAM_T", 4096))
    beam = 8
    toks = np.random.RandomState(0).randint(
        0, 512, (8, 32)).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=4, class_lengths=[0, 0, 8])
    wf = StandardWorkflow(
        layers=transformer_lm(vocab_size=512, d_model=256, n_heads=8,
                              n_kv_heads=2, n_layers=2, dropout=0.0,
                              pos="rope", impl="flash"),
        loader=loader, loss="lm",
        decision_config={"max_epochs": 1}, name="bench-beam")
    wf.initialize()
    gen = LMGenerator(wf.trainer, max_len=t_max,
                      cache_dtype=jnp.bfloat16)
    prompt = toks[:1, :16]

    def timed(fn):
        fn()                              # compile + warmup
        reps = []
        for _ in range(3):                # median-of-3 (run variance)
            t0 = time.perf_counter()
            fn()
            reps.append(time.perf_counter() - t0)
        # the scan always runs all t_max - 1 positions (traced lengths)
        return sorted(reps)[1] / (t_max - 1) * 1e3

    ms_beam = timed(lambda: gen.beam_search(prompt, max_new=64,
                                            beam=beam))
    ms_greedy = timed(lambda: gen.generate(prompt, max_new=64))

    # speculative decode on a self-similar prompt (the regime n-gram
    # drafting exists for): wall-clock per generated token vs the plain
    # greedy scan — both prefill the long prompt
    rep = np.tile(np.arange(64, dtype=np.int32),
                  t_max // 64 + 1)[None, :t_max // 2]
    max_new = max(16, t_max // 8)

    def timed_gen(fn):
        fn()                              # compile + warmup
        reps = []
        for _ in range(3):                # median-of-3 (run variance)
            t0 = time.perf_counter()
            fn()
            reps.append(time.perf_counter() - t0)
        return sorted(reps)[1] / max_new * 1e3

    ms_spec = timed_gen(lambda: gen.generate_speculative(
        rep, max_new=max_new, draft_k=8))
    ms_plain = timed_gen(lambda: gen.generate(rep, max_new=max_new))
    # both paths prefill the prompt and decode ~max_new positions
    # (generate()'s post-prefill scan buckets on max_new), so ms/token
    # over max_new compares like for like
    _log("beam decode T=%d beam=%d (2L d=256 lm): %.3f ms/pos beam, "
         "%.3f ms/pos greedy (reorder cost x%.1f); speculative "
         "%.3f ms/tok vs plain %.3f ms/tok (x%.1f)"
         % (t_max, beam, ms_beam, ms_greedy,
            ms_beam / ms_greedy if ms_greedy else 0.0,
            ms_spec, ms_plain,
            ms_plain / ms_spec if ms_spec else 0.0))
    return {"ms_per_pos_beam8": ms_beam, "ms_per_pos_greedy": ms_greedy,
            "ms_per_tok_spec": ms_spec, "ms_per_tok_greedy": ms_plain,
            "t": t_max}


def phase_serve():
    """Weight-bound decode throughput: greedy ms/token on a
    GPT-2-small-class stack (untrained — timing only), f32 weights
    (as-trained) vs bf16 vs int8 W8A8 (root.common.serve.weights).
    Expected shape on TPU: f32 ≈ bf16 (XLA hoists the policy's bf16
    cast out of the decode scan, so the f32 baseline already streams
    bf16 per step — bf16 weights save resident memory, not bandwidth);
    int8 is the one that cuts per-step weight traffic, because the
    int8 payload enters the dot itself."""
    import numpy as np
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import transformer_lm

    prng.seed_all(17)
    d = int(os.environ.get("BENCH_SERVE_D", 768))        # CPU smoke: 64
    n_layers = int(os.environ.get("BENCH_SERVE_L", 12))
    vocab = 50304 if d >= 768 else 512
    t_max = 512 if d >= 768 else 48
    toks = np.random.RandomState(0).randint(
        0, vocab, (4, 32)).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=4, class_lengths=[0, 0, 4])
    wf = StandardWorkflow(
        layers=transformer_lm(vocab_size=vocab, d_model=d,
                              n_heads=max(1, d // 64), n_layers=n_layers,
                              dropout=0.0, pos="rope",
                              tie_embeddings=True),
        loader=loader, loss="lm", decision_config={"max_epochs": 1},
        name="bench-serve")
    wf.initialize()
    prompt = toks[:1, :16]

    def timed(gen):
        gen.generate(prompt, max_new=32)           # compile + warmup
        reps = []
        for _ in range(3):       # median-of-3: the 2026-08-01 window
            t0 = time.perf_counter()   # showed ~15% run-to-run spread
            gen.generate(prompt, max_new=32)
            reps.append(time.perf_counter() - t0)
        # the decode scan always runs all t_max - 1 traced positions
        return sorted(reps)[1] / (t_max - 1) * 1e3

    out = {"d_model": d, "n_layers": n_layers, "t": t_max}
    for name, w in (("f32", None), ("bf16", "bf16"), ("int8", "int8"),
                    ("w4a8", "w4a8")):
        gen = LMGenerator(wf.trainer, max_len=t_max,
                          cache_dtype=jnp.bfloat16, weights=w)
        out["ms_per_tok_" + name] = round(timed(gen), 4)
        del gen
    base = out["ms_per_tok_f32"]
    _log("serve decode %dM-class (d=%d L=%d T=%d): f32 %.3f ms/tok, "
         "bf16 %.3f (x%.2f), int8 %.3f (x%.2f), w4a8 %.3f (x%.2f)"
         % (12 * d * d * n_layers // 1_000_000 if d >= 768 else 0,
            d, n_layers, t_max, base, out["ms_per_tok_bf16"],
            base / out["ms_per_tok_bf16"] if out["ms_per_tok_bf16"]
            else 0.0, out["ms_per_tok_int8"],
            base / out["ms_per_tok_int8"] if out["ms_per_tok_int8"]
            else 0.0, out["ms_per_tok_w4a8"],
            base / out["ms_per_tok_w4a8"] if out["ms_per_tok_w4a8"]
            else 0.0))
    # PRE-REGISTERED target: int8 >= 1.5x bf16 ms/tok on this
    # memory-bound workload.  The goal
    # itself lives in telemetry.ledger.TARGETS — one registry, so the
    # VL12xx contract lint can cross-check declared vs measured.
    out["target_int8_vs_bf16"] = _target("serve_int8_vs_bf16_x", 1.5)
    out["int8_vs_bf16"] = round(
        out["ms_per_tok_bf16"] / out["ms_per_tok_int8"], 3) \
        if out["ms_per_tok_int8"] else None

    # ---- paged continuous decode: bf16 pool vs int8 (QuantCache)
    # pool through the SAME fused kernel — prices the quantized-pool
    # variant's in-kernel dequant against its halved/quartered KV
    # stream (the serving-shaped number, 4 concurrent streams)
    from veles_tpu.models.generate import PagedContinuousBatcher
    slots, prompt_len = 4, 16
    max_new = max(16, t_max // 8)

    def timed_pool(cb):
        def run_pool():
            for i in range(slots):
                cb.submit(toks[i % toks.shape[0],
                               :prompt_len].tolist(), max_new)
            cb.run_all()
        run_pool()                       # compile + warmup
        t0 = time.perf_counter()
        run_pool()
        return (time.perf_counter() - t0) / (slots * max_new) * 1e3

    for name, cd in (("paged_bf16", jnp.bfloat16), ("paged_int8",
                                                    "int8")):
        # int8 tiles need 32 sublanes on silicon — a 16-block int8
        # pool is refused there (the CPU smoke's t_max isn't
        # 32-divisible; interpret mode takes any block)
        block = 32 if (cd == "int8" and t_max % 32 == 0) else 16
        need = slots * -(-(prompt_len + max_new + 1) // block) * block
        genp = LMGenerator(wf.trainer, max_len=t_max, cache_dtype=cd,
                           weights="int8")
        cb = PagedContinuousBatcher(genp, slots=slots, block=block,
                                    pool_tokens=need)
        out["ms_per_tok_" + name] = round(timed_pool(cb), 4)
        out[name + "_fused"] = bool(cb.fused)
        out[name + "_block"] = cb.block
        del cb, genp
    _log("paged serve decode (int8 weights, %d streams): bf16 pool "
         "%.3f ms/tok (fused=%s), int8 pool %.3f ms/tok (fused=%s)"
         % (slots, out["ms_per_tok_paged_bf16"],
            out["paged_bf16_fused"], out["ms_per_tok_paged_int8"],
            out["paged_int8_fused"]))

    # ---- the speculation cliff, before/after: an all-greedy spec
    # pool vs the same pool with ONE sampled row.  Per-row routing
    # means the greedy rows keep speculating either way — the ratio
    # is the cliff's depth (was: whole-pool sampled step)
    from veles_tpu.models.generate import ContinuousBatcher
    rep_row = np.tile(np.arange(8, dtype=np.int32),
                      t_max)[: t_max // 2].tolist()
    spec_new = max(8, t_max // 8)

    def timed_spec(mixed):
        cb = ContinuousBatcher(LMGenerator(wf.trainer, max_len=t_max),
                               slots=slots, speculative_k=8)

        def run_pool():
            for i in range(slots):
                cb.submit(rep_row, spec_new,
                          temperature=(0.7 if mixed and i == 0
                                       else 0.0), seed=i)
            cb.run_all()
        run_pool()                       # compile + warmup
        t0 = time.perf_counter()
        run_pool()
        return (time.perf_counter() - t0) / (slots * spec_new) * 1e3

    out["ms_per_tok_spec_all_greedy"] = round(timed_spec(False), 4)
    out["ms_per_tok_spec_mixed"] = round(timed_spec(True), 4)
    cliff = (out["ms_per_tok_spec_mixed"]
             / out["ms_per_tok_spec_all_greedy"]
             if out["ms_per_tok_spec_all_greedy"] else 0.0)
    _log("speculation pool (k=8, %d streams): all-greedy %.3f ms/tok, "
         "one-sampled %.3f ms/tok (cliff x%.2f — per-row routing "
         "keeps greedy rows speculating)"
         % (slots, out["ms_per_tok_spec_all_greedy"],
            out["ms_per_tok_spec_mixed"], cliff))

    # ---- decode-tick stall under long-prompt admission: segmented
    # vs whole-prompt prefill at 3 prompt lengths.  One in-flight
    # decode stream; a long prompt admits mid-stream; the inter-tick
    # gap p50/p99 is what the stream's client feels.  PRE-REGISTERED
    # target: segmented p99 stays within 4x the no-admission cadence
    # while unsegmented scales with the whole prompt.
    from veles_tpu.models.generate import ContinuousBatcher as _CB
    gen_st = LMGenerator(wf.trainer, max_len=t_max)
    seg = max(8, t_max // 32)

    def stall_row(plen, segment):
        cb = _CB(gen_st, slots=2, prefill_segment=segment)
        long_prompt = toks[1 % toks.shape[0], :16].tolist() \
            * (plen // 16 + 1)
        long_prompt = [int(t) for t in long_prompt[:plen]]
        short = [int(t) for t in toks[0, :8]]
        # warm every shape (short decode, prefill buckets)
        cb.submit(short, 4)
        cb.submit(long_prompt, 2)
        cb.run_all()
        cb.submit(short, max(16, t_max // 8))
        cb.tick()
        gaps = []
        cb.submit(long_prompt, 2)
        last = time.perf_counter()
        while not cb.idle():
            cb.tick()
            now = time.perf_counter()
            gaps.append((now - last) * 1e3)
            last = now
        gaps.sort()
        return (gaps[len(gaps) // 2],
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))])

    out["prefill_stall"] = {}
    for plen in (t_max // 4, t_max // 2, 3 * t_max // 4):
        p50_u, p99_u = stall_row(plen, 0)
        p50_s, p99_s = stall_row(plen, seg)
        out["prefill_stall"][str(plen)] = {
            "segment": seg,
            "unseg_p50_ms": round(p50_u, 4),
            "unseg_p99_ms": round(p99_u, 4),
            "seg_p50_ms": round(p50_s, 4),
            "seg_p99_ms": round(p99_s, 4)}
        _log("decode stall @ prompt %d: unsegmented p99 %.3f ms vs "
             "segmented(%d) p99 %.3f ms (p50 %.3f/%.3f)"
             % (plen, p99_u, seg, p99_s, p50_u, p50_s))
    # seg p99 <= 4x base cadence (goal declared in ledger.TARGETS)
    out["target_seg_stall_x"] = _target("serve_seg_stall_x", 4.0)

    # ---- cost-weighted vs least-loaded routing under a skewed-
    # length storm: 2 in-process replicas behind a FleetRouter,
    # 75/25 short/long buffered clients; completed wall per token.
    # PRE-REGISTERED: cost-weighted <= round-robin (pricing keeps
    # long prompts off the replica already holding one).
    import json as _json
    import http.client as _http
    import threading as _threading
    from veles_tpu.services.router import FleetRouter as _FR

    def routing_storm(placement):
        router = _FR(port=0, placement=placement,
                     prefill_prompt_min=0, rng_seed=3,
                     health_interval_ms=200)
        router.start()
        router.spawn_local(gen_st, 2, continuous_slots=4)
        short = [int(t) for t in toks[0, :8]]
        longp = [int(t) for t in toks[0, :8]] * (t_max // 16)
        longp = longp[:t_max // 2]
        n_short, n_long = 18, 6
        new_s, new_l = max(8, t_max // 16), 2

        def client(prompt, max_new):
            try:
                conn = _http.HTTPConnection(router.host, router.port,
                                            timeout=600)
                conn.request("POST", router.path, _json.dumps(
                    {"input": prompt,
                     "generate": {"max_new": max_new}}),
                    {"Content-Type": "application/json"})
                conn.getresponse().read()
                conn.close()
            except Exception:  # noqa: BLE001 — bench storm
                pass

        try:
            # warmup both replicas and shapes
            for api in router._local_apis:
                api.engine.wait(api.engine.submit_async(short, new_s))
                api.engine.wait(api.engine.submit_async(longp, new_l))
            jobs = ([(short, new_s)] * n_short
                    + [(longp, new_l)] * n_long)
            threads = [_threading.Thread(target=client, args=(p, n),
                                         daemon=True)
                       for p, n in jobs]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=600)
            wall = time.perf_counter() - t0
            toks_done = n_short * new_s + n_long * new_l
            return wall * 1e3 / toks_done
        finally:
            router.stop()

    out["routing_rr_ms_per_tok"] = round(
        routing_storm("round_robin"), 4)
    out["routing_cost_ms_per_tok"] = round(routing_storm("cost"), 4)
    # cost-weighted must not lose (goal declared in ledger.TARGETS)
    out["target_cost_vs_rr"] = _target("serve_cost_vs_rr_x", 1.0)
    _log("skewed-length routing storm (2 replicas): round-robin "
         "%.3f ms/tok vs cost-weighted %.3f ms/tok (x%.2f)"
         % (out["routing_rr_ms_per_tok"],
            out["routing_cost_ms_per_tok"],
            out["routing_rr_ms_per_tok"]
            / out["routing_cost_ms_per_tok"]
            if out["routing_cost_ms_per_tok"] else 0.0))
    return out


def phase_servecont():
    """Continuous-batching serving throughput — NOT in the default
    phase list; run manually on hardware (``python bench.py --phase
    servecont``).  N concurrent greedy streams through one
    ContinuousBatcher slot pool vs the same N requests decoded solo,
    aggregate tokens/sec each way: the multi-stream utilization number
    a serving deployment actually sees (each tick advances every slot
    for ~one slot's weight-streaming cost)."""
    import numpy as np
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.generate import ContinuousBatcher, LMGenerator
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.models.zoo import transformer_lm

    prng.seed_all(17)
    d = int(os.environ.get("BENCH_SERVE_D", 768))        # CPU smoke: 64
    n_layers = int(os.environ.get("BENCH_SERVE_L", 12))
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    vocab = 50304 if d >= 768 else 512
    t_max = 512 if d >= 768 else 48
    max_new = t_max // 4
    toks = np.random.RandomState(0).randint(
        0, vocab, (slots, 32)).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=4,
                             class_lengths=[0, 0, slots])
    wf = StandardWorkflow(
        layers=transformer_lm(vocab_size=vocab, d_model=d,
                              n_heads=max(1, d // 64),
                              n_layers=n_layers, dropout=0.0,
                              pos="rope", tie_embeddings=True),
        loader=loader, loss="lm", decision_config={"max_epochs": 1},
        name="bench-servecont")
    wf.initialize()
    gen = LMGenerator(wf.trainer, max_len=t_max)

    prompt_len = 16     # shared by pool sizing AND the submit slices
    tpd = int(os.environ.get("BENCH_SERVE_TPD", 16))
    # ONE batcher reused across warmup + timed runs (a fresh instance
    # would recompile its fused tick); fuse K engine ticks per dispatch
    # so the dispatch cost amortizes exactly like the trainer's fused
    # sweep.  BENCH_SERVE_PAGED=<block> swaps in the
    # block-table pool (budget = exactly the workload's tokens) so the
    # window prices the paged pool vs dense.
    paged = int(os.environ.get("BENCH_SERVE_PAGED", 0))
    if paged:
        from veles_tpu.models.generate import PagedContinuousBatcher
        need = slots * -(-(prompt_len + max_new) // paged) * paged
        cb = PagedContinuousBatcher(gen, slots=slots,
                                    ticks_per_dispatch=tpd,
                                    block=paged, pool_tokens=need)
    else:
        cb = ContinuousBatcher(gen, slots=slots, ticks_per_dispatch=tpd)

    def run_pool():
        for i in range(slots):
            cb.submit(toks[i, :prompt_len].tolist(), max_new)
        cb.run_all()

    run_pool()                           # compile + warmup
    t0 = time.perf_counter()
    run_pool()
    pool_s = time.perf_counter() - t0
    pool_tps = slots * max_new / pool_s

    gen.generate(toks[:1, :prompt_len], max_new)  # compile + warmup
    t0 = time.perf_counter()
    for i in range(slots):
        gen.generate(toks[i:i + 1, :prompt_len], max_new)
    solo_s = time.perf_counter() - t0
    solo_tps = slots * max_new / solo_s
    _log("continuous serving (%dM-class d=%d L=%d, %d streams x %d "
         "new): pool %.0f tok/s vs solo-sequential %.0f tok/s "
         "(x%.1f)"
         % (12 * d * d * n_layers // 1_000_000 if d >= 768 else 0,
            d, n_layers, slots, max_new, pool_tps, solo_tps,
            pool_tps / solo_tps if solo_tps else 0.0))
    return {"pool_tokens_per_sec": pool_tps,
            "solo_tokens_per_sec": solo_tps,
            "slots": slots, "max_new": max_new, "d_model": d,
            "paged_block": paged,
            "paged_fused": bool(paged) and getattr(cb, "fused", False)}


def phase_flashtune():
    """Block-size sweep for the flash kernels — DELEGATED to the kernel
    autotuner (veles_tpu.tuner): the forward and the SPLIT dq/dkv
    backward grids are swept independently (the backward used to be
    yoked to the forward's geometry), every candidate passes the VP6xx
    tile/VMEM audit before it may win, and winners persist in the tuner
    cache — later launches pick them up at ``tuner.lookup`` time with
    no bake step.  NOT in the default phase
    list; run manually on hardware (``python bench.py --phase
    flashtune``).  The legacy ``t{T}_q{bq}_k{bk}`` grid keys are still
    emitted (now with per-config dq/dkv backward timings alongside the
    forward) for tools/bake_flashtune.py."""
    from veles_tpu import tuner as tn
    from veles_tpu.tuner import sweeps

    tuner = tn.get_tuner()
    results = sweeps.sweep_flash(
        tuner, ts=(1024, 8192), d=128, kinds=sweeps.FLASH_KINDS,
        iters=8, repeats=3, warmup=1, log=_log,
        source="bench-flashtune")

    # flatten the per-kernel sweeps back into the legacy grid: one
    # entry per (T, bq, bk) carrying fwd ms + the isolated dq/dkv
    # kernel timings.  Each backward measurement runs its forward at
    # the PINNED geometry (flash_measure passes only the candidate's
    # bwd blocks — constant across candidates, that is the isolation),
    # so the reconstructed fwd+bwd at this row is
    #   ms + (ms_dq - F_pin) + (ms_dkv - F_pin)
    # with F_pin = the measured forward at the pinned geometry;
    # ms_bwd is omitted when that row failed (no honest number exists)
    from veles_tpu.ops.pallas.flash import _resolve_blocks
    per = {}
    for (kind, t), res in results.items():
        for row in res.candidates:
            if row.get("ms") is None:
                continue
            cfg = row["config"]
            per.setdefault((t, cfg["block_q"], cfg["block_k"]),
                           {})[kind] = row["ms"]
    grid = {}
    for (t, bq, bk), kinds in sorted(per.items()):
        if "fwd" not in kinds:
            continue
        b, h, d = (4, 8, 128) if t == 1024 else (1, 8, 128)
        flops = _causal_attn_flops(b, h, t, d)
        ms = kinds["fwd"]
        entry = {"ms": round(ms, 3),
                 "tf": round(flops / (ms / 1e3) / 1e12, 1)}
        pin_q, pin_k = _resolve_blocks(t, t, d, "bfloat16")[:2]
        f_pin = per.get((t, min(pin_q, -(-t // 128) * 128),
                         min(pin_k, -(-t // 128) * 128)),
                        {}).get("fwd")
        if "bwd_dq" in kinds and "bwd_dkv" in kinds:
            entry["ms_dq"] = round(kinds["bwd_dq"], 3)
            entry["ms_dkv"] = round(kinds["bwd_dkv"], 3)
            if f_pin is not None:
                entry["ms_bwd"] = round(
                    max(ms, ms + (kinds["bwd_dq"] - f_pin)
                        + (kinds["bwd_dkv"] - f_pin)), 3)
        grid["t%d_q%d_k%d" % (t, bq, bk)] = entry
    for (kind, t), res in sorted(results.items()):
        if res.winner:
            grid["winner_%s_t%d" % (kind, t)] = {
                "config": res.winner["config"],
                "ms": round(res.winner["ms"], 3),
                "audit_rejected": len(res.audit_rejected)}
    return grid


def phase_ring():
    """Ring attention through shard_map ON HARDWARE (1-chip mesh here;
    the same code path the 8-device CPU tests exercise for correctness)."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.attention import attention
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.parallel.ring import ring_attention_sharded

    platform = jax.default_backend()
    mesh = make_mesh({"seq": len(jax.devices())})
    key = jax.random.key(1)
    b, h, t, d = 2, 4, 512, 64
    q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.float32) * 0.1
               for kk in jax.random.split(key, 3))
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    ref = attention(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out - ref)))
    if err > 5e-3:
        raise AssertionError("ring attention mismatch: max_err=%g" % err)
    _log("ring attention on %s (%d-dev mesh): max_err %.2e"
         % (platform, len(jax.devices()), err))
    return {"max_err": err, "platform": platform,
            "n_devices": len(jax.devices())}


def phase_kohonen():
    """Kohonen SOM training throughput (BASELINE config 4): batched
    (MXU matmul) step vs the per-sample online scan."""
    from veles_tpu.models.kohonen import benchmark_som

    res = benchmark_som(n_samples=2048, n_features=784, sx=16, sy=16,
                        minibatch_size=512, steps=20)
    _log("kohonen 16x16 som, batch 512, 784 feats: %.3f ms/step batched, "
         "%.3f fused-sweep vs %.2f scan (%.1fx / %.1fx), qe %.4f/%.4f"
         % (res["ms_per_step"], res["sweep_ms_per_step"],
            res["scan_ms_per_step"], res["speedup"], res["sweep_speedup"],
            res["quantization_error"], res["sweep_quantization_error"]))
    return res


# --------------------------------------------------------------------------
# Orchestrator
# --------------------------------------------------------------------------

def _probe(deadline):
    """Cheap device probe with retries — decides whether to run phases at
    all, and is the parent's only knowledge of the device (the parent
    itself never calls into jax).  Runs in a watchdogged child like
    everything else.  Returns (device dict, None) or (None, error);
    anything but a TPU is an error."""
    code = ("import json, jax; d = jax.devices(); "
            "print('PROBE_OK ' + json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))")
    for i, backoff in enumerate((0,) + _BACKOFF):
        if backoff:
            _log("probe retry in %ds ..." % backoff)
            time.sleep(backoff)
        if time.monotonic() > deadline:
            return None, "probe: global deadline exceeded"
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True, timeout=150)
        except subprocess.TimeoutExpired:
            _log("probe attempt %d: timeout (150s)" % (i + 1))
            continue
        ok = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("PROBE_OK ")]
        if proc.returncode == 0 and ok:
            device = json.loads(ok[-1][len("PROBE_OK "):])
            _log("probe: %s" % device)
            if device["platform"] != "tpu":
                return None, ("probe: jax found platform %r (%s), not a "
                              "TPU — nothing to benchmark"
                              % (device["platform"], device["kind"]))
            return device, None
        _log("probe attempt %d failed: %s"
             % (i + 1, (proc.stderr or "")[-300:].replace("\n", " ")))
    return None, ("device probe failed after %d attempts"
                  % (1 + len(_BACKOFF)))


def _run_phase(name, timeout, deadline):
    """One phase in a watchdogged subprocess; retry on backend flakes."""
    for i, backoff in enumerate((0,) + _BACKOFF):
        if backoff:
            _log("%s: retry in %ds ..." % (name, backoff))
            time.sleep(backoff)
        remaining = deadline - time.monotonic()
        if remaining < 30:
            return {"ok": False, "error": "skipped: global deadline"}
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--phase", name],
                capture_output=True, text=True,
                timeout=min(timeout, remaining))
        except subprocess.TimeoutExpired:
            _log("%s: WATCHDOG timeout after %ds" % (name, timeout))
            # a hang is rarely cured by retrying — one attempt only
            return {"ok": False, "error": "watchdog timeout (%ds)" % timeout}
        sys.stderr.write(proc.stderr or "")
        sys.stderr.flush()
        for line in (proc.stdout or "").splitlines():
            if line.startswith(_RESULT_TAG):
                out = json.loads(line[len(_RESULT_TAG):])
                out["ok"] = True
                _log("%s: done in %.1fs" % (name, time.time() - t0))
                return out
        err_blob = (proc.stderr or "") + (proc.stdout or "")
        if any(pat in err_blob for pat in RETRYABLE):
            _log("%s: attempt %d hit retryable backend error" % (name, i + 1))
            continue
        tail = err_blob.strip().splitlines()[-3:]
        return {"ok": False, "error": "rc=%d: %s"
                % (proc.returncode, " | ".join(tail)[-400:])}
    return {"ok": False, "error": "retries exhausted (backend unavailable)"}


def _bank_line(line, device):
    """Append every measured row to the process performance ledger
    (``telemetry.ledger.default_path()``), each with its pre-registered
    target attached (telemetry.ledger.BENCH_ROWS maps line key ->
    unit/polarity/phase) and keyed by the device the probe child
    reported — the parent never asks jax.  Fail-soft by contract:
    ledger I/O must never fail a bench run."""
    try:
        from veles_tpu.telemetry import ledger as _ledgermod
        book = _ledgermod.default()
        n = book.append_bench_line(
            line, backend="%s:%d" % (device["platform"], device["count"]))
        _log("banked %d rows into %s" % (n, book.path))
    except Exception as e:  # noqa: BLE001 — fail-soft by contract
        _log("perf ledger unavailable: %s" % e)


def main():
    """Returns the process exit code: 0 only when the probe found a TPU
    and every phase produced its result."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--phase", help="internal: run one phase")
    parser.add_argument("--budget", type=float,
                        default=float(os.environ.get("BENCH_BUDGET", 2400)),
                        help="global wall-clock budget, seconds")
    args = parser.parse_args()

    if args.phase:
        # persistent XLA cache: phases run in fresh subprocesses, so
        # without this every phase re-pays first-compile; with it a
        # session's second run skips straight to measurement
        from veles_tpu import compile_cache
        compile_cache.enable()
        result = globals()["phase_" + args.phase]()
        print(_RESULT_TAG + json.dumps(result), flush=True)
        return 0

    deadline = time.monotonic() + args.budget
    results = {}
    device, probe_err = _probe(deadline)
    if device is not None:
        for name, timeout in PHASES:
            results[name] = _run_phase(name, timeout, deadline)
    else:
        _log("probe failed — skipping all phases: %s" % probe_err)

    gemm = results.get("gemm", {})
    errors = {n: r["error"] for n, r in results.items() if not r.get("ok")}
    if probe_err:
        errors["probe"] = probe_err
    gflops = gemm.get("gflops", 0.0)
    flash = results.get("flash", {})
    line = {
        "metric": "gemm_3001x3001_f32_gflops",
        "value": round(gflops, 1),
        "unit": "GFLOP/s",
        "vs_baseline": round(gflops / BASELINE_GEMM_GFLOPS, 2),
        "gemm_bf16_gflops": round(gemm.get("bf16_gflops", 0.0), 1),
        "gemm_bf16_mfu": round(gemm.get("bf16_mfu", 0.0), 3),
        "gemm_precision_overhead_pct": round(
            gemm.get("precision_overhead_pct", 0.0), 1),
        "peak_bf16_tflops": gemm.get("peak_bf16_tflops", 0.0),
        "mlp_step_ms": round(results.get("mlp", {}).get("step_ms", 0.0), 3),
        "mlp_step_fused_ms": round(
            results.get("mlp", {}).get("step_fused_ms", 0.0), 3),
        "alexnet_samples_per_sec": round(
            results.get("alexnet", {}).get("samples_per_sec", 0.0), 1),
        "alexnet_band_low": round(
            results.get("alexnet", {}).get("band_low", 0.0), 1),
        "alexnet_band_high": round(
            results.get("alexnet", {}).get("band_high", 0.0), 1),
        "lm_tokens_per_sec": round(
            results.get("lm", {}).get("tokens_per_sec", 0.0), 1),
        "lm_mfu": round(results.get("lm", {}).get("mfu", 0.0), 3),
        "lm_large_tokens_per_sec": round(
            results.get("lm_large", {}).get("tokens_per_sec", 0.0), 1),
        "lm_large_mfu": round(
            results.get("lm_large", {}).get("mfu", 0.0), 3),
        "kohonen_ms_per_step": round(
            results.get("kohonen", {}).get("ms_per_step", 0.0), 2),
        "kohonen_sweep_speedup": round(
            results.get("kohonen", {}).get("sweep_speedup", 0.0), 1),
        "flash_ok": bool(flash.get("ok")),
        "flash_platform": flash.get("platform"),
        "flash_ms_bf16": round(flash.get("ms_bf16", 0.0), 3),
        "flash_ms_bf16_xla": round(flash.get("ms_bf16_xla", 0.0), 3),
        "flash_ms_bwd": round(flash.get("ms_bwd", 0.0), 3),
        "flash_ms_bwd_xla": round(flash.get("ms_bwd_xla", 0.0), 3),
        "flash_bwd_max_err": flash.get("bwd_max_err", 0.0),
        "flash_ms_long_t8192": round(flash.get("ms_long_t8192", 0.0), 2),
        "flash_ms_long_t8192_xla": round(
            flash.get("ms_long_t8192_xla", 0.0), 2),
        # only a genuine T=4096 run may claim the headline key (a
        # BENCH_BEAM_T-shrunken smoke must not masquerade as it)
        "beam_ms_per_pos_t4096": round(
            results.get("beam", {}).get("ms_per_pos_beam8", 0.0)
            if results.get("beam", {}).get("t") == 4096 else 0.0, 3),
        "serve_ms_per_tok_bf16": round(
            results.get("serve", {}).get("ms_per_tok_bf16", 0.0), 3),
        "serve_ms_per_tok_int8": round(
            results.get("serve", {}).get("ms_per_tok_int8", 0.0), 3),
        "ring_ok": bool(results.get("ring", {}).get("ok")),
        "error": ("; ".join("%s: %s" % kv for kv in sorted(errors.items()))
                  or None),
    }
    # derived ratio headlines — the keys the pre-registered targets
    # (telemetry.ledger.TARGETS) actually judge; computed here so the
    # ledger's target-bearing rows exist whenever their inputs do
    serve = results.get("serve", {})
    if line["serve_ms_per_tok_int8"]:
        line["serve_int8_vs_bf16_x"] = round(
            line["serve_ms_per_tok_bf16"]
            / line["serve_ms_per_tok_int8"], 3)
    stalls = [v for v in (serve.get("prefill_stall") or {}).values()
              if isinstance(v, dict) and v.get("seg_p50_ms")]
    if stalls:
        line["serve_seg_stall_x"] = round(
            max(v["seg_p99_ms"] / v["seg_p50_ms"] for v in stalls), 2)
    if serve.get("routing_cost_ms_per_tok"):
        line["serve_cost_vs_rr_x"] = round(
            serve.get("routing_rr_ms_per_tok", 0.0)
            / serve["routing_cost_ms_per_tok"], 3)
    if line["flash_ms_bwd_xla"]:
        line["flash_bwd_vs_xla_x"] = round(
            line["flash_ms_bwd"] / line["flash_ms_bwd_xla"], 3)
    # predicted-vs-measured record (tools/cost_model.py): every number
    # above has an offline roofline prediction riding alongside.  The
    # import pulls in jax (never a backend) — after the last child
    # exited, so even that cannot cross a phase
    try:
        from tools.cost_model import predictions_for_bench
        line["predicted"] = predictions_for_bench()
    except Exception as e:  # noqa: BLE001 — predictions are advisory
        _log("cost model unavailable: %s" % e)
    line["device"] = device
    if gemm.get("ok"):
        _bank_line(line, device)
    print(json.dumps(line), flush=True)
    return 1 if errors else 0


def _guarded_main():
    """The one-JSON-line-on-stdout contract must survive even a bug in
    the orchestrator itself: any uncaught exception still emits a
    minimal, parseable line — and exits non-zero.  Phase children
    (``--phase``) are exempt: their parent wants the raw rc + traceback
    to drive retry/error classification."""
    if "--phase" in sys.argv:
        return main()
    try:
        return main()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 — keep the line contract
        line = {"metric": "gemm_3001x3001_f32_gflops", "value": 0.0,
                "unit": "GFLOP/s", "vs_baseline": 0.0,
                "error": "orchestrator: %s: %s" % (type(e).__name__, e)}
        print(json.dumps(line), flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(_guarded_main())
