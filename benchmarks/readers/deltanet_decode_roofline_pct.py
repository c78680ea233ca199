"""The gated-delta-rule decode kernel's share of its HBM roofline: the
least bytes of its launches while the profiler ran (each token
delivered in that interval an active row of each linear layer's launch:
S of 32 value heads of [128, 128] float32 read and written, q, k, v,
beta and g read; ``kernel_work_qwen3next``) over
``veles_deltanet_decode``'s device seconds in the same interval,
against the HBM peak.  None where the trace names no such kernel."""
from benchmarks import kernel_work_qwen3next as kw


def read(c):
    return kw.roofline_pct(c)
