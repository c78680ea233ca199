"""The decode kernels of a model of DeltaNet and attention layers, and
the least each could move.

``veles_deltanet_decode`` (``veles_tpu/ops/pallas/deltanet.py``
``KERNEL_NAMES``) is one name in the device trace, one launch a linear
layer and tick.  The least bytes a launch needs for each ACTIVE row (a
row whose token it decodes): S of every value head read once and
written once in float32, and the token's q, k, v (a key head's q and k
for each of its value heads, as the kernel is handed them), beta and g
read once — the conv's tail, the output and the rows the kernel skips
are not its work.  A token delivered is one active row in each of the
linear layers' launches of its tick, so the launches of an interval
need ``tokens x linear layers x bytes_per_row``."""

from benchmarks import flops_qwen3next

DECODE = ("veles_deltanet_decode",)


def bytes_per_row(cfg):
    """A launch's least bytes for one active row."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 4 * hv * (2 * dk * dv + 2 * dk + dv + 2)


def roofline_pct(c):
    """The least bytes of the launches in the traced interval — each
    token DELIVERED while the profiler ran (``traced_decode_tokens``)
    an active row of every linear layer's launch — over the kernel's
    device seconds in the same interval, against the HBM peak.  None
    where the trace names no such kernel, the kind counted no such
    tokens, or the configuration is not this model's."""
    from benchmarks import kernel_work
    tr = c.get("trace")
    cfg = c.get("cfg", {})
    if not tr or not c.get("traced_decode_tokens") \
            or cfg.get("model_type") != "qwen3_next":
        return None
    busy = kernel_work.kernel_seconds(tr["op_seconds"], DECODE)
    if not busy:
        return None
    need = c["traced_decode_tokens"] * flops_qwen3next.layer_kinds(cfg)[0] \
        * bytes_per_row(cfg)
    return 100.0 * need / busy / c["peaks"]["hbm_bytes_per_s"]


def paged_roofline_pct(c):
    """The paged decode kernel's share of its HBM roofline, counted in
    the traced interval alone: each token DELIVERED while the profiler
    ran (``traced_token_ranges``) at position p was decoded against p +
    1 keys of the ONE attention layer (``flops_qwen3next.
    kv_bytes_per_token``: the DeltaNet layers keep no keys), over
    ``veles_paged_decode``'s device seconds in the same interval,
    against the HBM peak.  None where the trace names no such kernel,
    the kind kept no such ranges, or the configuration is not this
    model's."""
    from benchmarks import kernel_work
    tr = c.get("trace")
    cfg = c.get("cfg", {})
    if not tr or not c.get("traced_token_ranges") \
            or cfg.get("model_type") != "qwen3_next":
        return None
    busy = kernel_work.kernel_seconds(tr["op_seconds"], kernel_work.PAGED)
    if not busy:
        return None
    import jax.numpy as jnp
    keys = sum((b * (b + 1) - a * (a + 1)) // 2
               for a, b in c["traced_token_ranges"] if a != 0)
    need = keys * flops_qwen3next.kv_bytes_per_token(
        cfg, jnp.dtype(c["traffic"]["cache_dtype"]).itemsize)
    return 100.0 * need / busy / c["peaks"]["hbm_bytes_per_s"]
