"""The full-attention layers' paged decode kernel's share of its HBM
roofline in a model that also has window layers: needed K and V bytes
of the decoded rows (``pos + 1`` keys a full layer;
``kernel_work_cmda.full_decode_bytes``) / the window / the HBM peak,
over ``veles_paged_decode``'s share of the traced window (the window
layers' calls carry another name).  None where the trace names no such
kernel or the configuration has no window layers."""
from benchmarks import kernel_work_cmda as kw


def read(c):
    return kw.roofline_pct(c, kw.FULL, kw.full_decode_bytes)
