"""The two paged decode kernels of a model whose layers keep two kinds
of state, and the least each could read.

A sliding-window layer's decode step runs ``veles_paged_decode_window``
(``veles_tpu/ops/pallas/paged.py`` ``KERNEL_NAMES_WINDOW``), a
full-attention layer's ``veles_paged_decode``: two names in the device
trace, so each kernel's device time is its own.  The least bytes: a
token decoded at position p reads K and V of ``min(p + 1, window)``
keys in every sliding layer and of ``p + 1`` keys in every full one,
whatever the kernels' grids and pages do."""

from benchmarks import flops_cmda

WINDOW = ("veles_paged_decode_window",)
FULL = ("veles_paged_decode",)


def _decoded(ranges):
    """The ranges that are decode steps: one that starts at 0 is a
    prefill and is left out."""
    return [(a, b) for a, b in ranges if a != 0]


def window_decode_bytes(cfg, ranges, itemsize):
    window = cfg["sliding_window"]

    def band_upto(n):                       # sum of min(p + 1, window)
        m = min(n, window)
        return m * (m + 1) // 2 + (n - m) * window

    keys = sum(band_upto(b) - band_upto(a) for a, b in _decoded(ranges))
    return keys * flops_cmda.layer_kinds(cfg)[0] \
        * flops_cmda.kv_bytes_per_token_layer(cfg, itemsize)


def full_decode_bytes(cfg, ranges, itemsize):
    keys = sum((b * (b + 1) - a * (a + 1)) // 2
               for a, b in _decoded(ranges))
    return keys * flops_cmda.layer_kinds(cfg)[1] \
        * flops_cmda.kv_bytes_per_token_layer(cfg, itemsize)


def roofline_pct(c, names, need_bytes):
    """The share of the HBM peak the needed reads amount to, over the
    share of the traced window the kernel ``names`` ran (as
    ``readers/paged_roofline_pct.py`` reads the one-kind model's);
    None where the trace names no such kernel or the configuration is
    not this model's."""
    from benchmarks import kernel_work
    tr = c.get("trace")
    if not tr or not c.get("token_ranges") or tr["window_s"] <= 0 \
            or c.get("window_s", 0) <= 0 \
            or "sliding_window" not in c.get("cfg", {}):
        return None
    busy = kernel_work.kernel_seconds(tr["op_seconds"], names)
    if not busy:
        return None
    import jax.numpy as jnp
    itemsize = jnp.dtype(c["traffic"]["cache_dtype"]).itemsize
    need = need_bytes(c["cfg"], c["token_ranges"], itemsize)
    needed_share = need / c["window_s"] / c["peaks"]["hbm_bytes_per_s"]
    return 100.0 * needed_share / (busy / tr["window_s"])
