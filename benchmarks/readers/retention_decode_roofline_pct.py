"""The retention decode kernel's share of its HBM roofline: the least
bytes the tokens delivered while the profiler ran need (S and z of 8 KV
heads x 8 layers, once read and once written, at 8,256 features;
``kernel_work_brumby``) over ``veles_retention_decode``'s device
seconds in the same interval, against the HBM peak.  None where the
trace names no such kernel."""
from benchmarks import kernel_work_brumby as kw


def read(c):
    return kw.roofline_pct(c)
