"""The window layers' paged decode kernel's share of its HBM roofline:
needed K and V bytes of the decoded rows (``min(pos + 1, window)`` keys
a sliding layer; ``kernel_work_cmda.window_decode_bytes``) / the window
/ the HBM peak, over ``veles_paged_decode_window``'s share of the traced
window.  None where the trace names no such kernel."""
from benchmarks import kernel_work_cmda as kw


def read(c):
    return kw.roofline_pct(c, kw.WINDOW, kw.window_decode_bytes)
