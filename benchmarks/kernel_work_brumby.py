"""The retention decode kernel and the least it could move.

``veles_retention_decode`` (``veles_tpu/ops/pallas/retention.py``
``KERNEL_NAMES``) is one name in the device trace.  The least bytes a
decoded token needs: S and z of every KV head of every layer, read once
and written once, at the 8,256 features a head NEEDS
(``flops_brumby.features``) — the lanes the layout pads to (8,320), the
features and the v / gate columns the kernel is handed are overhead."""

from benchmarks import flops_brumby

DECODE = ("veles_retention_decode",)


def decode_bytes_per_token(cfg):
    return 2 * flops_brumby.state_bytes_per_row(cfg)


def roofline_pct(c):
    """Needed bytes of the tokens DELIVERED while the profiler ran
    (``traced_decode_tokens``: counted over the interval the kernel's
    seconds come from, so the share does not move with the slice) over
    the kernel's device seconds, against the HBM peak.  None where the
    trace names no such kernel, the kind counted no such tokens, or the
    configuration is not this model's."""
    from benchmarks import kernel_work
    tr = c.get("trace")
    if not tr or not c.get("traced_decode_tokens") \
            or c.get("cfg", {}).get("model_type") != "brumby":
        return None
    busy = kernel_work.kernel_seconds(tr["op_seconds"], DECODE)
    if not busy:
        return None
    need = c["traced_decode_tokens"] * decode_bytes_per_token(c["cfg"])
    return 100.0 * need / busy / c["peaks"]["hbm_bytes_per_s"]
