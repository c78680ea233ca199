"""The paged decode kernel's share of its HBM roofline in a model whose
one attention layer in four keeps keys (the other three a fixed-size
state): the K and V bytes the tokens delivered while the profiler ran
needed (p + 1 keys of that one layer for a token at position p;
``kernel_work_qwen3next.paged_roofline_pct``) over
``veles_paged_decode``'s device seconds in the same interval, against
the HBM peak.  None where the trace names no such kernel or the
configuration is not that model's."""
from benchmarks import kernel_work_qwen3next as kw


def read(c):
    return kw.paged_roofline_pct(c)
