#!/usr/bin/env python3
"""Offline roofline cost model for every ``bench.py`` phase.

Chip uptime is the scarcest resource this repo has (round 3: one
16-minute window in ~12 h; round 4: zero).  This module converts the
numbers already captured on silicon into an *analytical* per-phase model
— MXU FLOPs, HBM bytes, kernel-launch floors, dispatch overhead — so
that the next uptime window CONFIRMS predictions instead of exploring:
the lm_large remat ladder and the flashtune block grid are pre-ranked by
predicted payoff, and ``bench.py`` emits predicted-vs-measured for every
phase it runs.  This is the reference's autotune-DB idea (measurement
turned into a reusable model, ref ``veles/backends.py:672-731``) applied
at the roofline level.

Method
------
Every phase workload is decomposed into
  t_step = max(t_compute, t_hbm) + n_kernels * T_KERNEL      (device)
         + H_STEP                  (host python loop work, if any)
         + T_DISPATCH / steps_per_dispatch                  (dispatch)
with
  t_compute = padded_matmul_flops / (PEAK * eff)
  t_hbm     = bytes / (HBM_BW * EFF_BW)
Matmul dims are padded to the (8, 128) tile / 128x128 MXU grid before
counting FLOPs, which is what prices the reference workloads' unfriendly
shapes (3001^2 gemm -> 3072, AlexNet conv1 k=363 -> 384).

Calibration vs postdiction
--------------------------
The device constants below are calibrated ONCE, each against a single
named on-chip anchor from the 2026-08-01 window — the first with
fetch-synced honest timing (bench.py `_fetch_sync`; the round-2/3
lm/mlp/alexnet numbers were enqueue-biased and are not comparable).
Each constant's own comment names its anchor.  The honest validation
is the held-out rows no constant was fit to:

  lm-25M ms/step       pred 26.0  meas 26.4   (-1.5%)
  lm-124M T=2048       pred 220   meas 215.5  (+2.2%)
  beam ms/pos          pred 0.115 meas 0.111  (+3.3%)
  serve bf16 d=1536    pred 1.48  meas 1.553  (-4.7%)
(flash T=8192 moved to an ANCHOR: its B*H=8 grid-underfill regime has
its own calibrated efficiency, FLASH_LONG_EFF.)
(the serve int8 rows are ANCHORS — the width-dependent effective
B/param curve was fit to those measurements, so they cannot count as
holdouts.)

Run ``python tools/cost_model.py`` for the postdiction table; the
assertions in ``tests/test_cost_model.py`` pin the tolerances
(anchors 5%, postdicts 20%).

v5e single-chip roofline: 197 TF/s bf16 (PEAK_BF16_TFLOPS table in
bench.py), 819 GB/s HBM.
"""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the MFU / attention-FLOP conventions and the lm_large ladder are the
# SAME objects bench.py uses — predicted-vs-measured stays comparable
from veles_tpu.ops.flops import (  # noqa: E402
    LM_LARGE_LADDER as _BENCH_LADDER, causal_attn_flops as
    _causal_attn_flops, dtype_nbytes as _dtype_nbytes,
    lm_train_flops_per_token as _lm_train_flops_per_token)

# byte-per-element pricing rides the same table the sharding/memory
# auditor (analysis/sharding_audit) uses — the two accountings cannot
# silently diverge
_BF16 = _dtype_nbytes("bfloat16")
_F32 = _dtype_nbytes("float32")

# ---------------------------------------------------------------------------
# Device model (v5e unless overridden)
# ---------------------------------------------------------------------------

PEAK_BF16 = 197e12          # FLOP/s, v5e MXU
HBM_BW = 819e9              # B/s spec
#: SERIAL-dependency MXU efficiency — what a train step's chained
#: matmuls actually achieve (2026-08-01 gemmtune: serial 44.0%,
#: independent pairs 58.5%; the gap is dependency stalls, so a step,
#: which IS a serial chain, inherits the serial number).  Round 3's
#: 0.606 was a different, faster chip-day — anchors must follow the
#: window they were measured in.
EFF_MXU = 0.440
#: chained 3001^2 (pad 3072) matmuls are LATENCY-bound, not
#: throughput-bound — bf16 runs 15.5 TF/s there vs 86.7 at 8192^2
#: (each ~0.6 ms multiply leaves the serial chain mostly stalled).
#: Shape-specific anchors; the f32-highest slowdown at that shape is
#: their measured ratio, NOT a pass count.
EFF_MXU_3001_BF16 = 0.0844  # calibrated: gemm 3001^2 bf16 anchor
                            # (padded-3072 flops; unpadded rate 15.5 TF/s)
F32_OVER_BF16_3001 = 1.452  # calibrated: f32-highest / bf16 at 3001^2
EFF_BW = 0.8                # a-priori achieved-bandwidth fraction
#: conv-vs-gemm efficiency: 2026-08-01 honest alexnet (9,584 samples/s,
#: slope-timed) shows XLA's implicit-gemm convs run near the serial
#: gemm rate — the old 0.6 guess was fit to enqueue-biased numbers
CONV_DERATE = 0.975
#: flash-kernel MXU efficiency, fit on the lm-124M step anchor and
#: VALIDATED on three holdouts it was not fit to (2026-08-01 window):
#: lm-25M 27.6 vs 28.0 ms (-1.4%), lm-124M@T2048 241.0 vs 215.5
#: (+11.8%), lm-124M spd1..16 flat (measured flat).  The a-priori
#: 0.45 guess overpredicted MFU 55.8% vs the measured 35.0%; the
#: kernel's measured causal-effective rate is 3.1 TF/s at T=1024 and
#: 33 TF/s at T=8192 (flashtune), i.e. eff 0.016-0.17.  0.13 is the
#: flagship-regime fit AFTER the d<=64 (1024,1024) block default
#: landed (0.10 fit the pre-tune 189.8 ms step).
FLASH_EFF = 0.13
FLASH_BWD_EFF = 0.13
#: the T=8192 d=128 long-context shape runs the (512,512)-block kernel
#: at a LOWER effective rate than the flagship regime (16.8 TF/s
#: measured = eff 0.085 — B*H=8 underfills the grid vs the flagship's
#: 192); calibrated on the flash T=8192 anchor
FLASH_LONG_EFF = 0.085
#: XLA-naive attention's long-context fusion cliff (see predict_flash.
#: naive_ms): calibrated on the measured T=8192 XLA anchor, 237.49 ms
XLA_NAIVE_LONG_FACTOR = 36.0
T_KERNEL = 4.3e-6           # calibrated: kohonen step anchor (2026-08-01 final run: 0.050 ms)
#: per-kernel floor INSIDE a lax.scan body (decode loops): XLA fuses
#: scan-body kernels far tighter than dispatch-level ones — fit on the
#: serve bf16 anchor (0.558 ms/tok = weight+KV stream at EFF_BW plus
#: ~154 in-scan kernels; 3.5 us/kernel would alone exceed the total)
T_KERNEL_SCAN = 1.0e-6
H_STEP = 67e-6              # calibrated: mlp fused-step anchor
#: per-dispatch cost: fitted 2026-08-01, not re-measured on this
#: machine (slope-timed mlp on the remote chip of that date: per-step
#: 4.255 ms minus fused 0.356 ms).  ROADMAP D4 removes it.
T_DISPATCH = 4.09e-3

#: on-chip anchors fitted 2026-08-01 (fetch-synced slope timing), not
#: re-measured on this machine; ROADMAP D4 replaces them with trace
#: measurements
ANCHORS = {
    "gemm_f32_gflops": 10667.7,
    "gemm_bf16_tf": 86.7,
    "gemm_bf16_3001_gflops": 15493.9,
    "gemm_bf16_pairs_tf": 115.2,
    "mlp_step_ms": 4.463,
    "mlp_step_fused_ms": 0.378,
    "alexnet_samples_per_sec": 9608.3,
    "lm_large_ms_per_step": 180.0,   # with the d64 (1024,1024) flash blocks
    "lm_ms_per_step": 26.4,          # d_head=64: same block win applies
    "lm_large_t2048_ms_per_step": 215.5,  # measured pre-d64-blocks
    "beam_ms_per_pos_t4096": 0.111,
    "kohonen_ms_per_step": 0.050,
    "flash_t8192_ms": 8.18,
    "flash_t8192_xla_ms": 237.49,
    # run-to-run serve spread this window: bf16 0.526-0.637,
    # int8 0.541-0.562 — anchored at the mid-window pair
    "serve_ms_per_tok_int8": 0.541,
    "serve_ms_per_tok_bf16": 0.558,
    # d=1536 scaling check (2026-08-01): int8 wins x1.80
    # once weights dominate — see _int8_eff_bytes for the fitted
    # width-dependent effective-B/param curve
    "serve_d1536_ms_per_tok_bf16": 1.553,
    "serve_d1536_ms_per_tok_int8": 0.862,
}


def device_constants():
    """The fitted v5e device model as one dict — the contract
    ``veles_tpu.telemetry.mfu`` consumes to price a live workflow's
    staged step with the SAME constants this module's phase predictions
    use (its baked-in fallback mirrors these values for installs
    without tools/).  No peak here: ``mfu`` takes the live device's
    from ``ops.flops.PEAK_BF16_TFLOPS``."""
    return {"eff_mxu": EFF_MXU, "hbm_bw": HBM_BW, "eff_bw": EFF_BW,
            "t_kernel": T_KERNEL, "h_step": H_STEP,
            "t_dispatch": T_DISPATCH}


def anatomy_floors(steps_per_dispatch=1, kernels=8):
    """Per-component predicted floors (ms) of one staged step — the
    pricing side of the step-anatomy attribution
    (``veles_tpu.telemetry.anatomy``): each measured component of a
    regressed step is judged against ITS floor here, so ledger drift
    is attributed to a component instead of "step got slower".
    ``compile``/``collective`` floor at 0 (steady-state single host
    pays neither); ``compute`` here is only the kernel-launch floor —
    workload compute rides on top and is priced per-phase by the
    ``predict_*`` family."""
    spd = max(int(steps_per_dispatch), 1)
    return {"compile_ms": 0.0,
            "host_ms": H_STEP * 1e3,
            "dispatch_ms": T_DISPATCH / spd * 1e3,
            "collective_ms": 0.0,
            "compute_ms": kernels * T_KERNEL * 1e3}


def _pad(x, m=128):
    return int(math.ceil(x / m)) * m


def t_matmul(m, k, n, eff=None, passes=1):
    """Seconds for one (m,k)@(k,n) on the MXU, dims padded to 128."""
    eff = EFF_MXU if eff is None else eff
    flops = 2.0 * _pad(m) * _pad(k) * _pad(n) * passes
    return flops / (PEAK_BF16 * eff)


def t_hbm(nbytes):
    return nbytes / (HBM_BW * EFF_BW)


def conv_mk(h, w, cin, cout, kh, kw, stride=1, pad=0):
    """im2col mapping of a conv: returns (out_h, out_w, m_per_sample,
    k, n) for the equivalent matmul."""
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (w + 2 * pad - kw) // stride + 1
    return ho, wo, ho * wo, cin * kh * kw, cout


# ---------------------------------------------------------------------------
# Phase models.  Each returns a dict whose keys mirror bench.py's JSON.
# ---------------------------------------------------------------------------

def predict_gemm():
    """Calibration anchors re-emitted (self-consistency, not evidence).
    The precision-level overhead at the reference's 3001^2 shape is the
    ratio of two shape-specific anchors — the old flat-efficiency model
    predicted ~+657% against a measured +45% because it priced f32 as
    extra MXU passes at a throughput the latency-bound 3001^2 chain
    never reaches."""
    n = 3001
    t16 = t_matmul(n, n, n, eff=EFF_MXU_3001_BF16)
    t32 = t16 * F32_OVER_BF16_3001
    t8192 = t_matmul(8192, 8192, 8192)
    return {
        "gflops": 2.0 * n ** 3 / t32 / 1e9,
        "bf16_gflops": 2.0 * 8192 ** 3 / t8192 / 1e9,
        "bf16_mfu": (2.0 * 8192 ** 3 / t8192) / PEAK_BF16,
        "precision_overhead_pct": (F32_OVER_BF16_3001 - 1.0) * 100.0,
    }


def predict_mlp():
    """784-100-10, batch 100.  Device time is kernel-floor dominated
    (~22 fused kernels: 2 dense layers x (fwd 2 + bwd 3 + update 2) +
    loss/stats ~8); compute and optimizer bytes are sub-microsecond."""
    b, i, h, o = 100, 784, 100, 10
    compute = 3 * (t_matmul(b, i, h) + t_matmul(b, h, o))
    params = i * h + h * o + h + o
    opt_bytes = params * _F32 * 5     # w rd/wr, m rd/wr, grad rd (f32)
    dev = max(compute, t_hbm(opt_bytes)) + 22 * T_KERNEL
    step = dev + H_STEP
    return {"step_ms": (step + T_DISPATCH) * 1e3,
            "step_fused_ms": (step + T_DISPATCH / 20) * 1e3}


#: AlexNet conv/fc walk for 227x227x3 (zoo.alexnet, single tower):
#: (h, w, cin, cout, k, stride, pad) per conv; pools shrink the grid.
_ALEXNET_CONVS = [
    (227, 227, 3, 96, 11, 4, 0),     # conv1 -> 55x55
    (27, 27, 96, 256, 5, 1, 2),      # conv2 (after pool1 3x3/2)
    (13, 13, 256, 384, 3, 1, 1),     # conv3 (after pool2)
    (13, 13, 384, 384, 3, 1, 1),     # conv4
    (13, 13, 384, 256, 3, 1, 1),     # conv5
]
_ALEXNET_FCS = [(9216, 4096), (4096, 4096), (4096, 1000)]


def predict_alexnet(batch=256):
    """Per-layer roofline walk.  fwd+bwd = 3x matmul FLOPs at
    CONV_DERATE x gemm efficiency; plus AdamW-free SGD-momentum
    optimizer traffic (62M params x 20 B) and the LRN/pool/activation
    elementwise streams."""
    t = 0.0
    act_elts = 0
    for h, w, cin, cout, k, s, p in _ALEXNET_CONVS:
        ho, wo, m, kk, n = conv_mk(h, w, cin, cout, k, k, s, p)
        t += 3 * t_matmul(batch * m, kk, n, eff=EFF_MXU * CONV_DERATE)
        act_elts += ho * wo * cout
    for fi, fo in _ALEXNET_FCS:
        t += 3 * t_matmul(batch, fi, fo)
    params = sum(cin * cout * k * k for _, _, cin, cout, k, _, _
                 in _ALEXNET_CONVS) + sum(a * b for a, b in _ALEXNET_FCS)
    t += t_hbm(params * _F32 * 5)                  # sgd-momentum f32
    # LRN (2 sites, window-5 cross-channel) + pools + relu grads: ~6
    # passes over the big early activations, bf16
    t += t_hbm(batch * act_elts * _BF16 * 6)
    t += 80 * T_KERNEL + H_STEP + T_DISPATCH / 10  # ~80 kernels/step
    return {"samples_per_sec": batch / t}


def _lm_predict(d_model, n_layers, seq, vocab, batch, n_heads,
                n_kv_heads=None, d_ff=None, steps_per_dispatch=4,
                recompute_frac=0.0, solver_bytes=28, tied=True):
    """Transformer-LM training step roofline.  ``recompute_frac`` is the
    extra forward recomputed in the backward (full remat = 1.0, dots
    remat = 0.0 for matmul-FLOP purposes); recompute time counts toward
    the step but NOT toward MFU (bench.py's MFU uses analytic 3x-fwd
    FLOPs only).  ``solver_bytes``: AdamW f32 = w rd/wr + m rd/wr +
    v rd/wr + grad rd = 28 B/param/step."""
    d_ff = d_ff or 4 * d_model
    kv = (n_kv_heads or n_heads) / n_heads
    toks = batch * seq
    # per-layer matmul time (fwd), padded shapes, m = batch*seq
    proj = (t_matmul(toks, d_model, d_model) * 2            # q, o
            + t_matmul(toks, d_model, int(d_model * kv)) * 2  # k, v
            + t_matmul(toks, d_model, d_ff) + t_matmul(toks, d_ff, d_model))
    attn_flops = _causal_attn_flops(batch, n_heads, seq,
                                    d_model // n_heads)
    attn = attn_flops / (PEAK_BF16 * FLASH_EFF)
    fwd = n_layers * (proj + attn) + t_matmul(toks, d_model, vocab)
    bwd = 2 * fwd + recompute_frac * fwd
    params = n_layers * ((2 + 2 * kv) * d_model ** 2 + 2 * d_ff * d_model) \
        + vocab * d_model * (1 if tied else 2)
    opt = t_hbm(params * solver_bytes)
    kernels = n_layers * 25 + 15                   # fused region count
    step = fwd + bwd + opt + kernels * T_KERNEL + H_STEP \
        + T_DISPATCH / steps_per_dispatch
    tps = toks / step
    # MFU numerator is bench.py's own convention, imported not copied
    fpt = _lm_train_flops_per_token(d_model, n_layers, seq, vocab,
                                    d_ff=d_ff, n_heads=n_heads,
                                    n_kv_heads=n_kv_heads or n_heads)
    return {"tokens_per_sec": tps, "ms_per_step": step * 1e3,
            "mfu": tps * fpt / PEAK_BF16, "n_params": params,
            # components for composed models (pipeline prediction):
            # pure fwd+bwd compute vs the once-per-step constants
            "compute_ms": (fwd + bwd) * 1e3, "opt_ms": opt * 1e3,
            "overhead_ms": (kernels * T_KERNEL + H_STEP
                            + T_DISPATCH / steps_per_dispatch) * 1e3}


def predict_lm():
    return _lm_predict(512, 8, 1024, 8192, batch=8, n_heads=8,
                       n_kv_heads=2, steps_per_dispatch=5, tied=False)


def predict_lm_large_ladder():
    """Predicted MFU per ladder rung — the rungs ARE bench.py's
    (veles_tpu/ops/flops.py:LM_LARGE_LADDER, single source of truth).
    The ranking is the pre-decided uptime-window order: confirm the top
    rung, only descend on OOM."""
    out = []
    for remat, batch, _steps, rec in _BENCH_LADDER:
        p = _lm_predict(768, 12, 1024, 50304, batch=batch, n_heads=12,
                        recompute_frac=rec, steps_per_dispatch=4)
        p.update(remat=str(remat), batch=batch)
        out.append(p)
    return sorted(out, key=lambda r: -r["mfu"])


def predict_flash():
    """Flash vs XLA-naive head-to-head, (4,8,1024,128) bf16 and the
    T=8192 long-context shape.  XLA naive materializes the T^2 score /
    prob tensors: ~4 full passes of b*h*T^2 bf16 traffic on top of the
    same matmul FLOPs."""
    def flash_ms(b, h, t, d, window=None, eff=FLASH_EFF, x=1.0):
        fl = _causal_attn_flops(b, h, t, d) * x
        if window and window < t:
            fl *= (window * t - window ** 2 / 2) / (t ** 2 / 2)
        return fl / (PEAK_BF16 * eff) * 1e3

    def naive_ms(b, h, t, d):
        fl = _causal_attn_flops(b, h, t, d)
        mm = fl / (PEAK_BF16 * EFF_MXU)
        hbm = t_hbm(b * h * t * t * _BF16 * 4)
        if t >= 4096:
            # fusion cliff: XLA's materialized-T^2 path measured
            # 237.49 ms at T=8192 vs the 8.1 ms a linear bytes model
            # gives — multiple T^2 temporaries with transposes/reduces
            # defeat streaming.  One calibrated factor on that anchor.
            hbm *= XLA_NAIVE_LONG_FACTOR
        return (mm + hbm) * 1e3

    # fwd+bwd: dq/dk/dv + in-kernel recompute ~= 2.5x fwd FLOPs on top
    return {
        "ms_bf16": flash_ms(4, 8, 1024, 128),
        "ms_bf16_xla": naive_ms(4, 8, 1024, 128),
        "ms_bwd": flash_ms(4, 8, 1024, 128, eff=FLASH_BWD_EFF, x=3.5),
        "ms_bwd_xla": naive_ms(4, 8, 1024, 128) * 3.5,
        "ms_long_t8192": flash_ms(1, 8, 8192, 128,
                                  eff=FLASH_LONG_EFF),
        "ms_long_t8192_xla": naive_ms(1, 8, 8192, 128),
        "ms_long_t8192_w1024": flash_ms(1, 8, 8192, 128, window=1024,
                                        eff=FLASH_LONG_EFF),
    }


def predict_flashtune_order():
    """Ranked (block_q, block_k) candidates for phase_flashtune, best
    predicted first.  Model: larger blocks amortize the softmax/rescale
    bookkeeping between inner matmuls (fewer k-steps) and keep the MXU
    on longer accumulate runs; all 9 grid points fit VMEM at d=128
    (q/k/v slabs <= 512*128*2 B = 128 KB each, f32 scores <= 1 MB,
    double-buffered well under the ~16 MB budget), so the ordering is
    bookkeeping-overhead-per-FLOP, ascending.  Causal block skipping
    makes bq=bk preferable at equal area (cleaner diagonal masks)."""
    cands = []
    for bq in (512, 256, 128):
        for bk in (512, 256, 128):
            # per-(bq,bk)-tile bookkeeping ~ O(bq) rescale + O(1)
            # launch, amortized over 2*bq*bk*d MACs
            overhead = (bq * 4 + 200) / (2.0 * bq * bk * 128)
            cands.append(((bq, bk), overhead + (0 if bq == bk else 1e-9)))
    return [c for c, _ in sorted(cands, key=lambda t: t[1])]


def predict_beam(t_max=4096, beam=8, d_model=256, n_layers=2,
                 n_heads=8, n_kv_heads=2, vocab=512):
    """Per-position beam-8 decode: ~3.5 HBM passes over the KV pool —
    the reorder's gather read + write (2) plus the attention's own
    K/V streams (~1.5 with causal masking) — plus weight streaming
    and ~20 in-scan kernels."""
    d_kv = d_model // n_heads * n_kv_heads
    cache = n_layers * 2 * beam * t_max * d_kv * _BF16  # bf16 bytes
    params = n_layers * ((2 + 2 * n_kv_heads / n_heads) * d_model ** 2
                         + 8 * d_model ** 2) + 2 * vocab * d_model
    # ~3.5 cache passes/position: reorder gather read + write (2) plus
    # the attention's own K and V streams (~1.5 with causal masking)
    step = t_hbm(cache * 3.5) + t_hbm(params * _BF16) \
        + 20 * T_KERNEL_SCAN
    return {"ms_per_pos_beam8": step * 1e3}


def _int8_eff_bytes(d):
    """Measured effective B/param of the int8 matmul path vs model
    width, with the embedding modeled separately at its real int8 size
    (1.25 B/row-element incl. scales): 2.19 at d=768 — yes, WORSE than
    bf16's 2.0, the per-matmul quant bookkeeping costs more than the
    streaming saves on small weights (int8 only won 3% there because
    the embedding shrank) — down to 0.97 =~ true-1B streaming at
    d>=1536 (the measured x1.80 over bf16).  Two-anchor linear
    interpolation (2026-08-01, not re-measured); a mid-size
    measurement would refine the crossover."""
    if d <= 768:
        return 2.19
    if d >= 1536:
        return 0.97
    return 2.19 + (d - 768) * (0.97 - 2.19) / (1536 - 768)


def predict_serve(d=768, n_layers=12, vocab=50304, t_max=512):
    """Weight-bound greedy decode, batch 1: ms/token = streamed weight
    bytes / BW + KV traffic + per-layer kernel floors.  f32 and bf16
    tie (the policy cast is hoisted; both stream 2 B/param); int8
    streams ``_int8_eff_bytes(d)`` per matmul param and 1.25 B per
    embedding element (int8 rows + per-row scales)."""
    mm_params = n_layers * 12 * d * d
    emb = vocab * d                                  # tied head table
    cache = n_layers * 2 * t_max * d * _BF16
    floors = (n_layers * 12 + 10) * T_KERNEL_SCAN
    out = {}
    for name, wbytes, ebytes in (("f32", 2, 2), ("bf16", 2, 2),
                                 ("int8", _int8_eff_bytes(d), 1.25)):
        step = t_hbm(mm_params * wbytes + emb * ebytes + cache) + floors
        out["ms_per_tok_" + name] = step * 1e3
    return out


def serve_request_costs(d=768, n_layers=12, vocab=50304, t_max=512):
    """Per-token request pricing for the fleet router's cost-weighted
    placement (``services.costing``): a serving request is predicted
    as  prompt_len x prefill_ms_per_tok + max_new x decode_ms_per_tok.

    * prefill is COMPUTE-bound — the prompt chunk rides one MXU-fed
      parallel pass, so a token costs its matmul flops at the
      calibrated efficiency (plus a share of the per-pass kernel
      floors);
    * decode is WEIGHT-STREAMING-bound — per-token cost is
      ``predict_serve``'s bf16 ms/tok, anchored by the measured
      ``serve_ms_per_tok_bf16`` last-known-good.

    The router CALIBRATES both against the fleet's live measured
    decode ms/tok (the same ratio rescales prefill — the two share
    the device).  The absolute numbers only matter relative to each
    other: placement ranks replicas by predicted outstanding work."""
    mm_params = n_layers * 12 * d * d
    prefill_ms = (2.0 * mm_params / (PEAK_BF16 * EFF_MXU)
                  + (n_layers * 12 + 10) * T_KERNEL_SCAN / 128.0) * 1e3
    decode_ms = predict_serve(d, n_layers, vocab, t_max)[
        "ms_per_tok_bf16"]
    return {"prefill_ms_per_tok": prefill_ms,
            "decode_ms_per_tok": decode_ms,
            "measured_decode_ms_per_tok":
                ANCHORS["serve_ms_per_tok_bf16"]}


def predict_kohonen():
    """512x784 @ 784x256 distance matmul + argmax + weight update."""
    comp = t_matmul(512, 784, 256)
    upd = t_hbm(784 * 256 * 4 * 3)
    return {"ms_per_step": (comp + upd + 10 * T_KERNEL) * 1e3}


#: ContinuousEngine anchors, 2026-08-01 on-chip servecont (84M-class,
#: 8 streams x 128 new tokens, chunked prefill interleaved): solo
#: 328 tok/s, dense pool 521 tok/s (x1.59), paged(16) pool 420 tok/s.
#: The a-priori "weights shared -> 3-8x" model was WRONG on silicon:
#: the engine tick is per-slot-cost dominated (prefill chunks ride the
#: same ticks as decode, and each slot pays its own attention/gather),
#: so the tick decomposes as  tick(slots) = a + slots*b  with
#: a =~ the solo per-token cost (engine + dispatch + weight stream,
#: identical solo vs pooled) and b fit at the measured 8-slot tick.
SERVECONT_SOLO_MS = 3.05          # anchor: 1e3/328
SERVECONT_TICK8_MS = 15.35        # anchor: 8e3/521 (dense)
SERVECONT_TICK8_PAGED_MS = 19.05  # anchor: 8e3/420 (paged GATHER tick)


def predict_servecont(slots=8, paged=False, fused=True):
    """Pool-vs-solo throughput ratio at ``slots`` concurrent streams,
    from the measured tick decomposition above.  At the measured
    8-slot point this reproduces the anchors by construction; other
    slot counts are the prediction.

    ``paged + fused`` is a PRE-REGISTERED prediction (no on-chip
    anchor yet): the fused tick deletes the gather/scatter
    re-materialization — the entire measured paged-vs-dense tick gap
    (19.05 - 15.35 ms at 8 slots) is that copy traffic, and the fused
    kernel's extra cost vs the dense einsum is only the table-indexed
    DMA pattern over the SAME bytes, so the prediction is the dense
    tick.  A three-way servecont A/B (dense / paged-fused /
    paged-gather, ROADMAP S0) confirms or refutes exactly this
    number."""
    a = SERVECONT_SOLO_MS
    tick8 = (SERVECONT_TICK8_MS if (not paged or fused)
             else SERVECONT_TICK8_PAGED_MS)
    b = (tick8 - a) / 8.0
    tick = a + slots * b
    pool_tps = slots / tick * 1e3
    solo_tps = 1e3 / a
    return {"pool_tokens_per_sec": pool_tps,
            "solo_tokens_per_sec": solo_tps,
            "pool_vs_solo": pool_tps / solo_tps}


def predict_pipeline_lm_large(s=4, m=16, v=2):
    """Multi-chip pipeline prediction for the 124M flagship: step time
    under plain vs interleaved 1F1B from the verified schedule tables
    (parallel.interleave) x the roofline per-chunk compute time, plus
    the once-per-step constants (optimizer sweep over this chip's 1/s
    of the params, dispatch/host overhead).  No chip pod exists to
    measure against yet — this is the pre-registered prediction the
    first multi-chip window confirms."""
    from veles_tpu.parallel.interleave import build_schedule

    base = _lm_predict(768, 12, 1024, 50304, batch=m, n_heads=12,
                       steps_per_dispatch=4)
    # one microbatch through one chunk (1/(s*v) of the blocks), fwd
    # only — compute time only; bwd sub-ticks cost ~2x fwd
    t_chunk_fwd = base["compute_ms"] / 1e3 / (3 * m * v * s)
    const = (base["opt_ms"] / s + base["overhead_ms"]) / 1e3
    ticks_plain = (m + 2 * (s - 1)) * v      # superstage = v chunks
    ticks_inter = build_schedule(s, v, m)["n_ticks"]
    step_plain = ticks_plain * 3 * t_chunk_fwd + const
    step_inter = ticks_inter * 3 * t_chunk_fwd + const
    ideal = m * v * 3 * t_chunk_fwd + const  # zero-bubble bound
    return {
        "s": s, "m": m, "v": v,
        "step_ms_plain_1f1b": round(step_plain * 1e3, 1),
        "step_ms_interleaved": round(step_inter * 1e3, 1),
        "step_ms_zero_bubble_bound": round(ideal * 1e3, 1),
        "interleaved_speedup": round(step_plain / step_inter, 3),
        "bubble_plain": round(1 - ideal / step_plain, 3),
        "bubble_interleaved": round(1 - ideal / step_inter, 3),
    }


# ---------------------------------------------------------------------------
# Postdiction + bench integration
# ---------------------------------------------------------------------------

def postdiction_table():
    """(name, predicted, measured, ratio, kind) rows.  kind='anchor'
    rows calibrated a constant (self-consistency only); kind='postdict'
    rows are the honest validation."""
    g = predict_gemm()
    mlp = predict_mlp()
    alex = predict_alexnet()
    beam = predict_beam()
    koh = predict_kohonen()
    sv = predict_serve()
    fl = predict_flash()
    lm_big = _lm_predict(768, 12, 1024, 50304, batch=16, n_heads=12,
                         steps_per_dispatch=4)
    lm_small = _lm_predict(512, 8, 1024, 8192, batch=8, n_heads=8,
                           n_kv_heads=2, steps_per_dispatch=5,
                           tied=False)
    lm_t2048 = _lm_predict(768, 12, 2048, 50304, batch=8, n_heads=12,
                           steps_per_dispatch=4)
    rows = [
        # anchors: each calibrated one constant on the 2026-08-01
        # window (EFF_MXU, the 3001^2 pair, H_STEP/T_DISPATCH, T_KERNEL,
        # CONV_DERATE, FLASH_EFF, T_KERNEL_SCAN respectively)
        ("gemm f32 GFLOP/s", g["gflops"], ANCHORS["gemm_f32_gflops"],
         "anchor"),
        ("gemm bf16 TF/s", g["bf16_gflops"] / 1e3, ANCHORS["gemm_bf16_tf"],
         "anchor"),
        ("gemm bf16 3001^2 GFLOP/s",
         2.0 * 3001 ** 3 / t_matmul(3001, 3001, 3001,
                                    eff=EFF_MXU_3001_BF16) / 1e9,
         ANCHORS["gemm_bf16_3001_gflops"], "anchor"),
        ("mlp step ms", mlp["step_ms"], ANCHORS["mlp_step_ms"], "anchor"),
        ("mlp fused ms", mlp["step_fused_ms"], ANCHORS["mlp_step_fused_ms"],
         "anchor"),
        ("kohonen ms/step", koh["ms_per_step"],
         ANCHORS["kohonen_ms_per_step"], "anchor"),
        ("alexnet samples/s", alex["samples_per_sec"],
         ANCHORS["alexnet_samples_per_sec"], "anchor"),
        ("lm-124M ms/step", lm_big["ms_per_step"],
         ANCHORS["lm_large_ms_per_step"], "anchor"),
        ("serve bf16 ms/tok", sv["ms_per_tok_bf16"],
         ANCHORS["serve_ms_per_tok_bf16"], "anchor"),
        # postdicts: holdouts no constant was fit to — the honest
        # validation rows
        ("lm-25M ms/step", lm_small["ms_per_step"],
         ANCHORS["lm_ms_per_step"], "postdict"),
        ("lm-124M T=2048 ms/step", lm_t2048["ms_per_step"],
         ANCHORS["lm_large_t2048_ms_per_step"], "postdict"),
        ("beam ms/pos", beam["ms_per_pos_beam8"],
         ANCHORS["beam_ms_per_pos_t4096"], "postdict"),
        ("serve int8 ms/tok", sv["ms_per_tok_int8"],
         ANCHORS["serve_ms_per_tok_int8"], "anchor"),
        ("flash T=8192 ms", fl["ms_long_t8192"],
         ANCHORS["flash_t8192_ms"], "anchor"),
        ("flash T=8192 XLA ms", fl["ms_long_t8192_xla"],
         ANCHORS["flash_t8192_xla_ms"], "anchor"),
        ("serve bf16 d=1536 ms/tok",
         predict_serve(d=1536)["ms_per_tok_bf16"],
         ANCHORS["serve_d1536_ms_per_tok_bf16"], "postdict"),
        ("serve int8 d=1536 ms/tok",
         predict_serve(d=1536)["ms_per_tok_int8"],
         ANCHORS["serve_d1536_ms_per_tok_int8"], "anchor"),
    ]
    return [(n, p, m, p / m if m else 0.0, k) for n, p, m, k in rows]


def predictions_for_bench():
    """Flat predicted-value dict keyed like bench.py's JSON line — the
    orchestrator attaches this under ``"predicted"`` so every uptime
    window ships its own predicted-vs-measured record."""
    g = predict_gemm()
    mlp = predict_mlp()
    lm = predict_lm()
    ladder = predict_lm_large_ladder()
    fl = predict_flash()
    sv = predict_serve()
    return {
        "value": round(g["gflops"], 1),
        "gemm_bf16_gflops": round(g["bf16_gflops"], 1),
        "gemm_bf16_mfu": round(g["bf16_mfu"], 3),
        "gemm_precision_overhead_pct": round(
            g["precision_overhead_pct"], 1),
        "mlp_step_ms": round(mlp["step_ms"], 3),
        "mlp_step_fused_ms": round(mlp["step_fused_ms"], 3),
        "alexnet_samples_per_sec": round(
            predict_alexnet()["samples_per_sec"], 1),
        "lm_tokens_per_sec": round(lm["tokens_per_sec"], 1),
        "lm_mfu": round(lm["mfu"], 3),
        "lm_large_tokens_per_sec": round(ladder[0]["tokens_per_sec"], 1),
        "lm_large_mfu": round(ladder[0]["mfu"], 3),
        "lm_large_ladder": [
            {"remat": r["remat"], "batch": r["batch"],
             "mfu": round(r["mfu"], 3)} for r in ladder],
        "flash_ms_bf16": round(fl["ms_bf16"], 3),
        "flash_ms_bf16_xla": round(fl["ms_bf16_xla"], 3),
        "flash_ms_bwd": round(fl["ms_bwd"], 3),
        "flash_ms_bwd_xla": round(fl["ms_bwd_xla"], 3),
        "flash_ms_long_t8192": round(fl["ms_long_t8192"], 2),
        "flash_ms_long_t8192_xla": round(fl["ms_long_t8192_xla"], 2),
        "beam_ms_per_pos_t4096": round(
            predict_beam()["ms_per_pos_beam8"], 3),
        "serve_ms_per_tok_bf16": round(sv["ms_per_tok_bf16"], 3),
        "serve_ms_per_tok_int8": round(sv["ms_per_tok_int8"], 3),
        "kohonen_ms_per_step": round(
            predict_kohonen()["ms_per_step"], 3),
        "flashtune_order": [list(c) for c in predict_flashtune_order()],
    }


def main():
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--json", action="store_true",
                   help="dump predictions_for_bench() as JSON")
    args = p.parse_args()
    if args.json:
        print(json.dumps(predictions_for_bench(), indent=1))
        return
    print("Roofline postdiction vs the 2026-08-01 on-chip anchors")
    print("%-22s %10s %10s %7s  %s" % ("phase", "predicted", "measured",
                                       "ratio", "kind"))
    for name, pred, meas, ratio, kind in postdiction_table():
        print("%-22s %10.3f %10.3f %6.2fx  %s"
              % (name, pred, meas, ratio, kind))
    print("\nPredictions for never-measured phases "
          "(the uptime window confirms these):")
    for k, v in sorted(predictions_for_bench().items()):
        print("  %-28s %s" % (k, v))


if __name__ == "__main__":
    main()

