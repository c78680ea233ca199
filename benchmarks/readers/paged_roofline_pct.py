"""The paged decode kernel's share of its HBM roofline: the share of
the HBM peak the needed reads amount to, over the share of the traced
window the kernel ran.  Needed: the keys and values of every token
decoded in the window (each attends its own context once;
``kernel_work.decode_kv_bytes`` over the decode entries of
``token_ranges``, at the pool's ``cache_dtype``) / the window.  Kernel:
device seconds of ``veles_paged_decode*`` / the traced window.  The
bytes bound it: a decode step does one FLOP a byte of bf16 cache.  None where
the trace names no such kernel."""
from benchmarks import kernel_work


def read(c):
    tr = c.get("trace")
    if not tr or not c.get("token_ranges") or tr["window_s"] <= 0 \
            or c.get("window_s", 0) <= 0:
        return None
    busy = kernel_work.kernel_seconds(tr["op_seconds"], kernel_work.PAGED)
    if not busy:
        return None
    import jax.numpy as jnp
    itemsize = jnp.dtype(c["traffic"]["cache_dtype"]).itemsize
    need = kernel_work.decode_kv_bytes(c["cfg"], c["token_ranges"], itemsize)
    needed_share = need / c["window_s"] / c["peaks"]["hbm_bytes_per_s"]
    return 100.0 * needed_share / (busy / tr["window_s"])
