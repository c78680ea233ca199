"""The sparse decode path's share of its HBM roofline: the share of the
HBM peak the needed reads amount to, over the share of the traced
window the path ran.  Needed: for every token decoded in the window at
or past ``topk`` positions, the index keys of its whole context and K
and V of the ``topk`` keys selected (``kernel_work_keye.
sparse_decode_bytes`` over ``token_ranges``, at the pool's
``cache_dtype``) / the window.  Path: device seconds inside the
conditional the program runs it under, nested operations included
(``kernel_work_keye.sparse_decode_seconds``, taken by the kind) / the
traced window.  The bytes bound it: one FLOP a byte.  None where the
trace holds no such conditional (a program without the path; the CPU
rehearsal)."""
from benchmarks import kernel_work_keye


def read(c):
    tr, ran = c.get("trace"), c.get("sparse_decode")
    if not tr or not ran or not c.get("token_ranges") \
            or tr["window_s"] <= 0 or c.get("window_s", 0) <= 0:
        return None
    import jax.numpy as jnp
    itemsize = jnp.dtype(c["traffic"]["cache_dtype"]).itemsize
    need = kernel_work_keye.sparse_decode_bytes(
        c["cfg"], c["token_ranges"], itemsize)
    needed_share = need / c["window_s"] / c["peaks"]["hbm_bytes_per_s"]
    return 100.0 * needed_share / (ran["seconds"] / tr["window_s"])
