"""Shared analytic FLOP conventions + the lm_large bench ladder.

Single source of truth for the MFU numerator and the attention FLOP
count, imported by BOTH ``bench.py`` (measurement) and
``tools/cost_model.py`` (prediction) so the two can never silently
diverge — predicted-vs-measured is only meaningful when both sides
count the same FLOPs.  (The reference's device DB had the same
property: one methodology produced both the stored numbers and the
runtime estimates, ref veles/backends.py:672-731.)"""


#: bytes per element by dtype name — the ONE byte-pricing table shared by
#: ``tools/cost_model.py`` (HBM-traffic terms) and the VS2xx/VM3xx
#: sharding/memory auditor (``veles_tpu.analysis.sharding_audit``), keyed
#: by both numpy/jax dtype names and the short HLO/StableHLO tokens that
#: appear in compiled-module text.
DTYPE_BYTES = {
    "pred": 1, "bool": 1,
    "s8": 1, "u8": 1, "int8": 1, "uint8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "int16": 2, "uint16": 2,
    "f16": 2, "bf16": 2, "float16": 2, "bfloat16": 2,
    "s32": 4, "u32": 4, "int32": 4, "uint32": 4, "f32": 4, "float32": 4,
    "s64": 8, "u64": 8, "int64": 8, "uint64": 8, "f64": 8, "float64": 8,
    "c64": 8, "complex64": 8, "c128": 16, "complex128": 16,
}


def dtype_nbytes(dtype):
    """Bytes per element of ``dtype`` — accepts a numpy/jax dtype, a dtype
    name, or an HLO shape token ("f32", "bf16", ...)."""
    name = getattr(dtype, "name", None) or str(dtype)
    try:
        return DTYPE_BYTES[name]
    except KeyError:
        import numpy as np
        return int(np.dtype(name).itemsize)


def shape_nbytes(shape, dtype):
    """Bytes of one dense tensor, priced with :data:`DTYPE_BYTES`."""
    n = dtype_nbytes(dtype)
    for d in shape:
        n *= int(d)
    return n


#: bf16 peak of ONE chip by ``device_kind`` substring (TFLOP/s) — the
#: denominator of every utilization this repo reports, in this one
#: table (``bench.py`` and ``telemetry.mfu`` both read it).  Order
#: matters ("v5 lite" before "v5").  The v5e row is the chip this round
#: measures on: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
#: bf16 (393 TOP/s int8, 819 GB/s HBM); it reports itself to JAX as
#: ``TPU v5 lite``.  A device with no row has NO peak: a benchmark
#: fails on it and telemetry emits no utilization at all, the CPU
#: included — there is no default.
PEAK_BF16_TFLOPS = (
    ("v5 lite", 197.0), ("v5e", 197.0), ("v5p", 459.0), ("v5", 459.0),
    ("v6 lite", 918.0), ("v6e", 918.0), ("v6", 918.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
)


def peak_bf16_tflops(device_kind):
    """bf16 peak TFLOP/s for a ``jax.Device.device_kind`` string, or
    None when :data:`PEAK_BF16_TFLOPS` does not list the device."""
    kind = str(device_kind).lower()
    for sub, peak in PEAK_BF16_TFLOPS:
        if sub in kind:
            return peak
    return None


def causal_attn_flops(b, h, t, d):
    """Matmul FLOPs of ONE causal attention call (qk + pv, each 2·b·h·
    t·(t/2)·d with the triangular mask halving effective keys)."""
    return 4 * b * h * t * t * d / 2


def lm_train_flops_per_token(d_model, n_layers, seq, vocab, d_ff=None,
                             n_heads=None, n_kv_heads=None):
    """Analytic matmul FLOPs per trained token (fwd+bwd = 3x fwd): per
    layer q/o project 2·d² each, k/v project 2·d·d_kv each (GQA shrinks
    d_kv = d·n_kv/n_heads), MLP 2·(2·d_ff·d), causal attention 2·T·d
    (T/2 effective keys, qk + pv), plus the 2·d·V LM head.  Embedding
    lookup is a gather — no FLOPs."""
    d_ff = d_ff or 4 * d_model
    kv_frac = ((n_kv_heads / n_heads)
               if n_heads and n_kv_heads else 1.0)
    per_layer = ((4 + 4 * kv_frac) * d_model ** 2
                 + 4 * d_ff * d_model + 2 * seq * d_model)
    return 3 * (n_layers * per_layer + 2 * d_model * vocab)


#: lm_large memory ladder, best rung first: (remat, batch, bench_steps,
#: recompute_frac).  ``recompute_frac`` is the extra forward recomputed
#: in the backward (full remat = 1.0; "dots" keeps matmul outputs so no
#: matmul recompute) — bench walks the rungs on OOM, the cost model
#: predicts each rung's MFU from the same tuple.
LM_LARGE_LADDER = (("dots", 16, 8, 0.0), (True, 16, 8, 1.0),
                   (True, 8, 12, 1.0))
